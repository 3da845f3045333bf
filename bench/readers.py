"""Shared arithmetic of the metric readers in ``bench/metrics/``.

Each reader gets the run record the harness builds (``harness.serve``)
and returns a number, or None where it finds nothing to read (the
metric is then left out of the result line).  A share of a roofline or
of a peak is never returned as 0 for want of data.

Operations and bytes come from the module of the architecture the run
served (``rec["arch"]``, ``bench/arch/<name>.py``), never from counts
written here for one architecture.
"""
from __future__ import annotations

import math
from types import ModuleType
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from bench import flops, manifest


def percentile(vals: Sequence[float], q: float) -> float:
    """Nearest rank: the ceil(q * n)-th smallest value (copied from the
    program's ``core/slo.py``, whose arithmetic is sound)."""
    s = sorted(vals)
    idx = min(max(math.ceil(q * len(s)), 1), len(s)) - 1
    return s[idx]


def ttft_s(rec: Dict, untraced: bool = False) -> List[float]:
    """Due to first token, every request that arrived in the window; an
    unanswered one counts with the time it waited until the run ended.
    ``untraced`` keeps those due after the profiler's trace was written
    out, a stall of seconds that no untraced run has."""
    t0 = rec["untraced_from"] if untraced else -math.inf
    return [(r["first"] if r["first"] is not None else r["end"]) - r["due"]
            for r in rec["requests"] if r["due"] >= t0]


def tpot_s(rec: Dict) -> List[float]:
    """Every gap between successive tokens of those requests; a tick
    that hands back n tokens at once counts n gaps of 1/n its length."""
    out: List[float] = []
    for r in rec["requests"]:
        st = r["stamps"]
        for (t0, _), (t1, n) in zip(st, st[1:]):
            out.extend([(t1 - t0) / n] * n)
    return out


def arch(rec: Dict) -> ModuleType:
    """The module of the architecture the run served."""
    return manifest.arch_by_name(rec["bench_dir"], rec["arch"])


def step_mfu(rec: Dict) -> Optional[float]:
    """Model FLOPs of the work the engine was handed in the traced
    window over (window x the chip's peak), in %."""
    red, peak = rec.get("trace"), rec.get("peak")
    if red is None or peak is None or not rec["steps"]:
        return None
    mod = arch(rec)
    fl = sum(mod.step_flops(rec["hf"], s["segs"]) for s in rec["steps"])
    return 100.0 * fl / (red.window_s * red.devices * peak["bf16_flops"])


def idle_share(rec: Dict) -> Optional[float]:
    red = rec.get("trace")
    if red is None:
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)


# Each attention kernel: its device operation (the Pallas kernel keeps
# the name of the function that launched it) and the kind of step the
# harness recorded its launches under.
KERNELS = {
    "decode_attn": {"op": "decode_attn_paged", "step": "decode"},
    "prefill_attn": {"op": "ragged_prefill_paged", "step": "packed"},
}


Counts = Callable[[ModuleType, Dict, List[Tuple[int, int]]],
                  List[Tuple[float, float, int]]]


def kernel_roofline(rec: Dict, op: str, step: str,
                    counts: Counts) -> Optional[float]:
    """Least time of the launches of device operation ``op`` in the
    traced window over its device time, in %.  Each step of kind
    ``step`` the engine was handed launched it once per layer;
    ``counts(arch, hf, segments)`` gives one launch's ``(flops, bytes,
    layers)`` for each group of layers that does the same work, and
    each launch takes its least time at the chip's peaks."""
    red, peak = rec.get("trace"), rec.get("peak")
    if red is None or peak is None:
        return None
    launches = [s["segs"] for s in rec["steps"] if s["kind"] == step]
    dev = sum(o.dur for o in red.ops if o.name == op)
    if not launches or dev <= 0:
        return None
    mod = arch(rec)
    least = sum(sum(flops.least_time(fl, by, peak)[0] * n
                    for fl, by, n in counts(mod, rec["hf"], segs))
                for segs in launches)
    return 100.0 * least / dev


def roofline(rec: Dict, kernel: str) -> Optional[float]:
    """Roofline share of one of ``KERNELS``, counted by the
    architecture's ``kernel_counts``."""
    k = KERNELS[kernel]
    return kernel_roofline(
        rec, k["op"], k["step"],
        lambda mod, hf, segs: mod.kernel_counts(hf, kernel, segs))
