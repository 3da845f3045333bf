"""Median time a request waited for the scheduler: from its submit to
the serving loop to its first dispatch (the loop's ``dispatch_time``),
over the requests of the window due after the traced slice was written
out, the set the ``ttft_*.sat`` readers take; one never dispatched
counts with the time it waited until the run ended (host clock).

In chat-mt-sat a turn waits at the client for one of the sessions the
deployment serves, not in the scheduler's queue, which never fills:
there the median is the gap to the loop's next tick, about 1 ms, and
guards only against a regression at that grain.  A cell whose arrivals
outrun the engine (bursts) is where it reads the queue itself."""
from bench.readers import percentile


def read(rec):
    t0 = rec["untraced_from"]
    v = [(r["dispatch"] if r["dispatch"] is not None else r["end"])
         - r["submit"] for r in rec["requests"]
         if r["due"] >= t0 and r["submit"] is not None]
    return 1e3 * percentile(v, 0.50) if v else None
