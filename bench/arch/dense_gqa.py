"""A dense GQA decoder (Qwen2 / Qwen3 family): everything the benchmark
knows of the architecture, found by the name a configuration gives
under ``reference``.

Every layer is the same: grouped-query attention over the whole causal
history (QKV biases and per-head q/k RMSNorm where ``arch`` says so),
then one SwiGLU MLP of ``intermediate_size``.

Weights: the program's parameter tree (pattern-stacked blocks,
vocabulary padded to 256 rows) drawn in the served dtype, a leaf at a
time and a block of rows at a time on the device (``bench/weights.py``).
Scales: embedding N(0, 1); every projection N(0, fan_in^-1/2); norm
weights 1 + N(0, 0.1); QKV biases N(0, 0.1).  Norm weights and biases
are not left at 1 and 0, so that a path that skipped them would show.

Counts: the real work of the segments the engine was handed
(``bench/flops.py``); bytes assume the served dtype (``dtype_bytes``)
and count what a kernel must move at least once.
"""
from __future__ import annotations

import functools
from typing import Dict, Iterable, List, Tuple

import jax
import jax.numpy as jnp

from bench.flops import causal_keys
from bench.weights import _normal, _row_blocks, padded_vocab, seed_key

KERNELS = ("decode_attn", "prefill_attn")


def widths(hf: Dict) -> Dict[str, int]:
    d = int(hf["hidden_size"])
    hq = int(hf["num_attention_heads"])
    hd = int(hf.get("head_dim") or d // hq)
    return dict(d=d, hq=hq, hkv=int(hf["num_key_value_heads"]), hd=hd,
                ff=int(hf["intermediate_size"]), v=int(hf["vocab_size"]),
                layers=int(hf["num_hidden_layers"]))


# --------------------------------------------------------------- weights


def _build(hf: Dict, arch: Dict, dtype, key):
    w = widths(hf)
    d, hd, ff, L = w["d"], w["hd"], w["ff"], w["layers"]
    pv = padded_vocab(w["v"])
    qd, kvd = w["hq"] * hd, w["hkv"] * hd

    ks = iter(jax.random.split(key, 16))

    def mat(shape, fan_in):
        return _normal(next(ks), (L,) + shape, fan_in ** -0.5, dtype, L)

    mixer = {"wq": mat((d, qd), d), "wk": mat((d, kvd), d),
             "wv": mat((d, kvd), d), "wo": mat((qd, d), qd)}
    if arch.get("qkv_bias"):
        for name, n in (("bq", qd), ("bk", kvd), ("bv", kvd)):
            mixer[name] = _normal(next(ks), (L, n), 0.1, dtype, 1)
    if arch.get("qk_norm"):
        for name in ("q_norm", "k_norm"):
            mixer[name] = _normal(next(ks), (L, hd), 0.1, dtype, 1, 1.0)
    block = {
        "ln1": _normal(next(ks), (L, d), 0.1, dtype, 1, 1.0),
        "mixer": mixer,
        "ln2": _normal(next(ks), (L, d), 0.1, dtype, 1, 1.0),
        "ffn": {"wg": mat((d, ff), d), "wi": mat((d, ff), d),
                "wo": mat((ff, d), ff)},
    }
    return {
        "embed": _normal(next(ks), (pv, d), 1.0, dtype,
                         _row_blocks(pv)),
        "blocks": [block],
        "final_norm": _normal(next(ks), (d,), 0.1, dtype, 1, 1.0),
        "lm_head": _normal(next(ks), (d, pv), d ** -0.5, dtype,
                           _row_blocks(d)),
    }


def make(hf: Dict, arch: Dict, seed: int, dtype=jnp.bfloat16):
    """The parameter tree (``hf``: published config keys; ``arch``:
    {qk_norm, qkv_bias}), drawn from ``seed`` in one jitted call."""
    return jax.jit(functools.partial(_build, hf, arch, dtype))(
        seed_key(seed))


def shapes(hf: Dict, arch: Dict, dtype=jnp.bfloat16):
    """The tree's shapes, drawing nothing."""
    return jax.eval_shape(functools.partial(_build, hf, arch, dtype),
                          jax.random.key(0))


# ---------------------------------------------------------- config mirror


def program_fields(hf: Dict, arch: Dict) -> Dict:
    """The program's ``ModelConfig`` attributes the published keys fix."""
    w = widths(hf)
    return {"num_layers": w["layers"], "d_model": w["d"],
            "num_heads": w["hq"], "num_kv_heads": w["hkv"],
            "d_ff": w["ff"], "vocab_size": w["v"], "hdim": w["hd"],
            "norm_eps": hf["rms_norm_eps"], "rope_theta": hf["rope_theta"],
            "qk_norm": arch["qk_norm"], "qkv_bias": arch["qkv_bias"],
            "dtype": arch["dtype"]}


# ---------------------------------------------------------------- counts


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


def kernel_counts(hf: Dict, kernel: str,
                  segments: Iterable[Tuple[int, int]]
                  ) -> List[Tuple[float, float, int]]:
    """FLOPs and bytes of one launch of an attention ``kernel`` over the
    ``(q_len, history)`` segments it serves, as ``(flops, bytes,
    layers)``: every layer launches it once over the whole history, so
    one entry covers them all."""
    if kernel not in KERNELS:
        raise KeyError(f"no counts for kernel {kernel!r}; known: "
                       f"{KERNELS}")
    segs = list(segments)
    fl = sum(attn_flops(hf, q, h) for q, h in segs)
    by = sum(attn_bytes(hf, q, h) for q, h in segs)
    return [(fl, by, widths(hf)["layers"])]
