"""The program's tracer: host spans and counters.

Every layer records through this module; nothing else under ``src/``
traces.  Off, the default, ``span`` returns one shared no-op context
and ``count`` returns at once.  ``enable()`` turns it on
for the whole process.  Each span then enters
``jax.profiler.TraceAnnotation``, so while the JAX profiler runs it
lands in the trace's host plane on the device operations' clock, and
is kept in memory, timed on ``time.monotonic`` (the serving loop's
clock), with its parent, its request id and its args.  ``drain()``
returns what was kept and clears it.

Names follow ``laps.<layer>.<step>``; a child step is named under its
parent (``laps.engine.decode.fetch``).  A span inherits its parent's
request id and ``engine`` arg when it is given none, so the spans of
one request share its id and those of a cluster replica its engine.

One serving loop drives one thread; the tracer keeps one stack of open
spans and is not meant for spans opened from several threads at once.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Dict, List, Optional

from jax.profiler import TraceAnnotation

CLOCK = time.monotonic


@dataclasses.dataclass
class Span:
    name: str
    start: float
    end: Optional[float]          # None while open
    parent: Optional[int]         # ``id`` of the enclosing span
    rid: Optional[int]
    args: Dict[str, Any]
    id: int

    @property
    def seconds(self) -> float:
        return self.end - self.start


class _Noop:
    """What ``span`` returns while the tracer is off."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False


NOOP = _Noop()


class _Open:
    """One span while it runs: its record and its profiler annotation."""
    __slots__ = ("rec", "ann")

    def __init__(self, rec: Span, ann):
        self.rec, self.ann = rec, ann

    def __enter__(self) -> Span:
        _stack.append(self.rec)
        self.ann.__enter__()
        self.rec.start = CLOCK()
        return self.rec

    def __exit__(self, *exc) -> bool:
        self.rec.end = CLOCK()
        _stack.pop()
        self.ann.__exit__(*exc)
        return False


_on = False
_spans: List[Span] = []
_stack: List[Span] = []
_counters: Dict[str, float] = {}
_next_id = 0


def enable() -> None:
    global _on
    _on = True


def disable() -> None:
    global _on
    _on = False


def enabled() -> bool:
    return _on


def span(name: str, rid: Optional[int] = None, **args):
    """Context manager around one step of the host's work."""
    if not _on:
        return NOOP
    global _next_id
    parent = _stack[-1] if _stack else None
    if parent is not None:
        if rid is None:
            rid = parent.rid
        if "engine" in parent.args and "engine" not in args:
            args["engine"] = parent.args["engine"]
    rec = Span(name, 0.0, None, parent.id if parent else None, rid, args,
               _next_id)
    _next_id += 1
    _spans.append(rec)
    return _Open(rec, TraceAnnotation(name, **args))


def traced(name: str):
    """Decorator: run the function inside ``span(name)``."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*a, **k):
            if not _on:
                return fn(*a, **k)
            with span(name):
                return fn(*a, **k)
        return inner
    return wrap


def count(name: str, n: float) -> None:
    """Add ``n`` to counter ``name``."""
    if _on:
        _counters[name] = _counters.get(name, 0) + n


def drain() -> Dict[str, Any]:
    """Everything recorded since the last drain: ``spans`` in start
    order (open ones have ``end`` None) and ``counters``; the records
    are cleared."""
    global _spans, _counters
    out = {"spans": _spans, "counters": _counters}
    _spans, _counters = [], {}
    return out
