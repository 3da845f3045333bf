"""The chip's peaks and the arithmetic every architecture's counts share.

Operations and bytes of the served work are counted by each
architecture's module (``bench/arch/<name>.py``: ``step_flops``,
``kernel_counts``), from the algorithm's shapes.  Every count is of the
real work: segment lengths as the engine was handed them, never the
token bucket or decode rung they were padded to, so the same request
costs the same here whatever implements it.  Widths come from the
configuration file's ``hf_config`` (published key names).
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Tuple

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> Dict[str, float]:
    """The chip's published peaks; a kind not in the table is an error."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def causal_keys(q_len: int, history: int) -> int:
    """Query-key pairs of ``q_len`` new tokens after ``history`` cached
    ones under a causal mask: token i sees history + i + 1 keys."""
    return q_len * history + q_len * (q_len + 1) // 2


def least_time(flops: float, nbytes: float, peak: Dict[str, float]
               ) -> Tuple[float, str]:
    """The larger of compute time and memory time at the chip's peaks,
    and which of the two bounds it."""
    tc = flops / peak["bf16_flops"]
    tm = nbytes / peak["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
