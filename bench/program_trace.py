"""Readings from the program's own tracer (``repro.serving.trace``).

The program records spans named ``laps.<layer>.<step>`` and counters
while its tracer is on (``trace.enable()``), and hands them over with
``trace.drain()``.  A traced run that turns the tracer on for its
window keeps the drained records under ``rec["program"]``; the readers
below take them from there, and return None where a run has none (a
program without the tracer, or an untraced run).

``reduce`` is ``bench.trace.reduce`` with the idle time named anew:
each idle gap of the device is cut at the edges of the ``bench.*`` and
``laps.*`` host spans open during it, and each piece is named after the
innermost span open over it.  ``bench.trace`` names a whole gap after
the span open at its middle, which misreads gaps that outlast the
program's short spans (a 3-ms gap that ends in ``sample`` is all
``sample`` there).  The program's spans sit inside the harness's
wrappers of the engine calls, so a gap the harness would put under
``bench.decode_batch`` is named for the program's step, such as
``laps.engine.decode.fetch``.  Busy time and the operations are the
same as ``bench.trace.reduce`` gives.
"""
from __future__ import annotations

import bisect
import dataclasses
from typing import Dict, List, Optional, Tuple

from bench import trace as bench_trace
from bench.readers import percentile

SPAN_PREFIXES = ("bench.", "laps.")
PROGRAM_PREFIX = "laps."
D2H_COUNTER = "laps.engine.d2h_bytes"

Interval = Tuple[float, float]
HostSpan = Tuple[float, float, str]


def split_gaps(idle: List[Interval], spans: List[HostSpan]
               ) -> List[Tuple[str, float]]:
    """Each idle interval cut at the edges of the host spans inside it,
    each piece named after the innermost span open over it.  ``spans``
    is sorted by start and nests as one thread's spans do."""
    starts = [a for a, _, _ in spans]
    edges = sorted({x for a, b, _ in spans for x in (a, b)})
    out: List[Tuple[str, float]] = []
    for a, b in idle:
        inner = edges[bisect.bisect_right(edges, a):
                      bisect.bisect_left(edges, b)]
        cuts = [a] + inner + [b]
        for c0, c1 in zip(cuts, cuts[1:]):
            out.append((bench_trace._innermost(spans, starts,
                                               (c0 + c1) / 2), c1 - c0))
    return out


def _host_spans(path: str) -> List[HostSpan]:
    """The ``bench.*`` and ``laps.*`` host spans other than the window,
    in seconds from the window's start, sorted by start."""
    from jax.profiler import ProfileData
    raw, w0 = [], None
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == bench_trace.WINDOW_SPAN:
                    w0 = ev.start_ns
                elif ev.name.startswith(SPAN_PREFIXES):
                    raw.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                ev.name))
    return sorted(((a - w0) * 1e-9, (b - w0) * 1e-9, n) for a, b, n in raw)


def reduce(path: str) -> bench_trace.Reduction:
    red = bench_trace.reduce(path)
    spans = _host_spans(path)
    by_dev: Dict[int, List[Interval]] = {}
    for op in red.ops:
        by_dev.setdefault(op.device, []).append((op.start,
                                                 op.start + op.dur))
    # a device with no operation in the window was idle all through it
    idle_all = [[(0.0, red.window_s)]] * (red.devices - len(by_dev))
    gaps: List[Tuple[str, float]] = []
    for busy in [bench_trace._union(v) for v in by_dev.values()]:
        edges = [0.0] + [x for iv in busy for x in iv] + [red.window_s]
        idle_all.append([(a, b) for a, b in zip(edges[::2], edges[1::2])
                         if b > a])
    for idle in idle_all:
        gaps += split_gaps(idle, spans)
    return dataclasses.replace(red, gaps=gaps)


def host_ms_p50(rec: Dict, step: str) -> Optional[float]:
    """Median over the ``laps.engine.<step>`` spans of their duration
    less their ``wait`` child: the host's own time in one dispatch, in
    ms."""
    prog = rec.get("program")
    if not prog:
        return None
    name = f"laps.engine.{step}"
    spans = [s for s in prog["spans"] if s.end is not None]
    wait = {s.parent: s.seconds for s in spans if s.name == name + ".wait"}
    v = [s.seconds - wait.get(s.id, 0.0) for s in spans if s.name == name]
    return 1e3 * percentile(v, 0.50) if v else None


def host_idle_share(rec: Dict) -> Optional[float]:
    """Share of the traced slice in which the device was idle while the
    innermost open host span was the program's own (``laps.*``), in %:
    the device waiting on the program's host work, as against a
    sleeping loop or the client.  Needs a reduction from ``reduce``."""
    red = rec.get("trace")
    if red is None:
        return None
    s = sum(t for name, t in red.gaps if name.startswith(PROGRAM_PREFIX))
    return 100.0 * s / (red.window_s * red.devices)


def d2h_kb_per_token(rec: Dict) -> Optional[float]:
    """Bytes the engine brought to the host for sampling over the
    records' span, per output token of that span, in KB."""
    prog = rec.get("program")
    n = rec.get("output_tokens")
    if not prog or D2H_COUNTER not in prog["counters"] or not n:
        return None
    return prog["counters"][D2H_COUNTER] / 1e3 / n
