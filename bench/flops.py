"""Operations and bytes of the served work, from the algorithm's shapes.

Every count is of the real work: segment lengths as the engine was
handed them, never the token bucket or decode rung they were padded
to, so the same request costs the same here whatever implements it.
Widths come from the configuration file's ``hf_config`` (published
key names).  Bytes assume the served dtype (``dtype_bytes``) and count
what the kernel must move at least once.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Iterable, Sequence, Tuple

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> Dict[str, float]:
    """The chip's published peaks; a kind not in the table is an error."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def widths(hf: Dict) -> Dict[str, int]:
    d = int(hf["hidden_size"])
    hq = int(hf["num_attention_heads"])
    hd = int(hf.get("head_dim") or d // hq)
    return dict(d=d, hq=hq, hkv=int(hf["num_key_value_heads"]), hd=hd,
                ff=int(hf["intermediate_size"]), v=int(hf["vocab_size"]),
                layers=int(hf["num_hidden_layers"]))


def causal_keys(q_len: int, history: int) -> int:
    """Query-key pairs of ``q_len`` new tokens after ``history`` cached
    ones under a causal mask: token i sees history + i + 1 keys."""
    return q_len * history + q_len * (q_len + 1) // 2


def attn_flops(hf: Dict, q_len: int, history: int) -> float:
    """One layer's attention core (QK^T and PV) for one segment."""
    w = widths(hf)
    return 4.0 * w["hq"] * w["hd"] * causal_keys(q_len, history)


def attn_bytes(hf: Dict, q_len: int, history: int,
               dtype_bytes: int = 2) -> float:
    """One layer's attention core for one segment: K and V of every
    visible position read once, Q read and O written once."""
    w = widths(hf)
    kv = 2 * (history + q_len) * w["hkv"] * w["hd"]
    qo = 2 * q_len * w["hq"] * w["hd"]
    return float((kv + qo) * dtype_bytes)


def layer_matmul_params(hf: Dict) -> int:
    w = widths(hf)
    qd, kvd = w["hq"] * w["hd"], w["hkv"] * w["hd"]
    return w["d"] * (qd + 2 * kvd) + qd * w["d"] + 3 * w["d"] * w["ff"]


def step_flops(hf: Dict, segments: Iterable[Tuple[int, int]]) -> float:
    """Model FLOPs of one step over ``(q_len, history)`` segments: every
    layer's projections and MLP for each new token, the attention core
    for each segment, and one vocabulary row per segment (the step
    emits one next-token row per segment)."""
    w = widths(hf)
    segs = list(segments)
    tokens = sum(q for q, _ in segs)
    linear = 2.0 * tokens * layer_matmul_params(hf) * w["layers"]
    attn = sum(attn_flops(hf, q, h) for q, h in segs) * w["layers"]
    head = 2.0 * len(segs) * w["d"] * w["v"]
    return linear + attn + head


def least_time(flops: float, nbytes: float, peak: Dict[str, float]
               ) -> Tuple[float, str]:
    """The larger of compute time and memory time at the chip's peaks,
    and which of the two bounds it."""
    tc = flops / peak["bf16_flops"]
    tm = nbytes / peak["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")


def kernel_least_time(hf: Dict, calls: Sequence[Tuple[int, int]],
                      peak: Dict[str, float]) -> Tuple[float, str]:
    """Least time of one attention kernel launch per layer over the
    ``(q_len, history)`` segments it serves, summed over the layers;
    the bound is that of the launch (one launch covers every segment
    of a step)."""
    w = widths(hf)
    fl = sum(attn_flops(hf, q, h) for q, h in calls)
    by = sum(attn_bytes(hf, q, h) for q, h in calls)
    t, bound = least_time(fl, by, peak)
    return t * w["layers"], bound
