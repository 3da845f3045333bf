"""Median time to first token, from when it was due, over the requests
of the window due after the traced slice was written out (that export
stalls the loop for seconds, which no untraced run has); host clock.
At the engine's capacity it holds the wait behind other sessions'
steps, so it swings with small changes of load."""
from bench.readers import percentile, ttft_s


def read(rec):
    v = ttft_s(rec, untraced=True)
    return 1e3 * percentile(v, 0.50) if v else None
