"""Reduce a profiler trace (``.xplane.pb``) to what the readers need.

Device planes are those named ``/device:...`` other than the host's;
on each, the ``XLA Ops`` line holds one event per operation that ran
and ``XLA Modules`` one per compiled program.  Busy time is the union
of the operation intervals inside the traced window, averaged over the
devices; idle gaps are the rest of the window, each named after the
innermost ``bench.*`` host span open at its middle (the harness's own
spans around the serving loop, the scheduler and the engine calls).
The window is the host span named ``bench.window``.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
SPAN_LOOKBACK = 256


def op_kind(text: str) -> str:
    """``%decode_attn_paged.9 = bf16[...] custom-call(...)`` ->
    ``decode_attn_paged``: the HLO name without its number.  Pallas
    kernels keep the name of the function that launched them."""
    name = text.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", name)


# ops that contain other ops: counted in busy time through their
# children, never on their own
CONTAINERS = ("while", "conditional", "call")


@dataclasses.dataclass
class Op:
    name: str
    module: str
    start: float      # seconds from the window's start
    dur: float        # seconds, clipped to the window
    device: int


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float                      # averaged over devices
    devices: int
    ops: List[Op]
    gaps: List[Tuple[str, float]]      # every idle gap: (host span, s)

    def op_seconds(self) -> Dict[str, float]:
        out: Dict[str, float] = defaultdict(float)
        for op in self.ops:
            out[op.name] += op.dur
        return dict(out)

    def top_ops(self, n: int = 10) -> List[List]:
        ranked = sorted(self.op_seconds().items(), key=lambda kv: -kv[1])
        return [[k, v / self.devices] for k, v in ranked[:n]]

    def idle_by_span(self, n: int = 10) -> List[List]:
        tot: Dict[str, float] = defaultdict(float)
        for name, s in self.gaps:
            tot[name] += s
        ranked = sorted(tot.items(), key=lambda kv: -kv[1])
        return [[k, v / self.devices] for k, v in ranked[:n]]


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _innermost(spans: List[Tuple[float, float, str]], starts: List[float],
               t: float) -> str:
    """The latest-starting span that contains ``t``: the innermost, as
    one thread's spans nest.  ``spans`` is sorted by start."""
    i = bisect.bisect_right(starts, t) - 1
    for j in range(i, max(-1, i - SPAN_LOOKBACK), -1):
        a, b, name = spans[j]
        if b >= t:
            return name
    return "outside bench spans"


def _stat(ev, key: str) -> Optional[str]:
    for k, v in ev.stats:
        if k == key:
            return str(v)
    return None


def reduce(path: str) -> Reduction:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    host_spans: List[Tuple[float, float, str]] = []
    window: Optional[Tuple[float, float]] = None
    device_planes = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if not ev.name.startswith(SPAN_PREFIX):
                        continue
                    a = ev.start_ns
                    b = a + ev.duration_ns
                    if ev.name == WINDOW_SPAN:
                        window = (a, b)
                    else:
                        host_spans.append((a, b, ev.name))
        elif plane.name.startswith("/device:"):
            device_planes.append(plane)
    if window is None:
        raise ValueError(f"no {WINDOW_SPAN!r} span in {path}")
    host_spans.sort()
    starts = [a for a, _, _ in host_spans]
    w0, w1 = window
    ops: List[Op] = []
    gaps: List[Tuple[str, float]] = []
    busy_total = 0.0
    n_dev = 0
    for dev, plane in enumerate(device_planes):
        lines = {line.name: line for line in plane.lines}
        if OP_LINE not in lines:
            continue
        n_dev += 1
        modules = sorted((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                         for ev in lines[MODULE_LINE].events) \
            if MODULE_LINE in lines else []
        mi = 0
        intervals = []
        for ev in sorted(lines[OP_LINE].events, key=lambda e: e.start_ns):
            a = max(ev.start_ns, w0)
            b = min(ev.start_ns + ev.duration_ns, w1)
            kind = op_kind(ev.name)
            if b <= a or kind in CONTAINERS:
                continue
            while mi < len(modules) and modules[mi][1] < ev.start_ns:
                mi += 1
            module = _stat(ev, "hlo_module") or (
                modules[mi][2] if mi < len(modules)
                and modules[mi][0] <= ev.start_ns else "")
            intervals.append((a, b))
            ops.append(Op(kind, module.split("(")[0], (a - w0) * 1e-9,
                          (b - a) * 1e-9, dev))
        busy = _union(intervals)
        busy_total += sum(b - a for a, b in busy) * 1e-9
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((_innermost(host_spans, starts, (a + b) / 2),
                             (b - a) * 1e-9))
    if n_dev == 0:
        raise ValueError(f"no device operations in {path}")
    return Reduction(window_s=(w1 - w0) * 1e-9, busy_s=busy_total / n_dev,
                     devices=n_dev, ops=ops, gaps=gaps)
