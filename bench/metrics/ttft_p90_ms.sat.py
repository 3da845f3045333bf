"""90th percentile of time to first token, from when it was due, over
the requests of the window due after the traced slice was written out
(host clock): the highest percentile the ten to twenty requests
support."""
from bench.readers import percentile, ttft_s


def read(rec):
    v = ttft_s(rec, untraced=True)
    return 1e3 * percentile(v, 0.90) if v else None
