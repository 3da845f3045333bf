"""A throwaway mixture-of-experts architecture, for the test that adds a
configuration of a new architecture as new files only: ``tiny.add_moe``
copies this file into a copy of the benchmark as ``arch/moe_gqa.py``.

Every layer: grouped-query attention over the whole causal history
(per-head q/k RMSNorm where ``arch`` says so), then a router over
``num_experts`` SwiGLU experts of ``moe_intermediate_size``, of which
each token runs its ``num_experts_per_tok`` best (weights renormalised,
``norm_topk_prob``).  No shared expert; ``intermediate_size`` is unused.
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
    return dict(d=d, hq=hq, hkv=int(hf["num_key_value_heads"]),
                hd=int(hf.get("head_dim") or d // hq),
                f=int(hf["moe_intermediate_size"]),
                e=int(hf["num_experts"]), k=int(hf["num_experts_per_tok"]),
                v=int(hf["vocab_size"]), layers=int(hf["num_hidden_layers"]))


def _build(hf: Dict, arch: Dict, dtype, key):
    w = widths(hf)
    d, hd, f, e, L = w["d"], w["hd"], w["f"], w["e"], w["layers"]
    qd, kvd = w["hq"] * hd, w["hkv"] * hd
    pv = padded_vocab(w["v"])
    ks = iter(jax.random.split(key, 16))

    def mat(shape, fan_in):
        return _normal(next(ks), (L,) + shape, fan_in ** -0.5, dtype, L)

    mixer = {"wq": mat((d, qd), d), "wk": mat((d, kvd), d),
             "wv": mat((d, kvd), d), "wo": mat((qd, d), qd)}
    if arch.get("qk_norm"):
        for name in ("q_norm", "k_norm"):
            mixer[name] = _normal(next(ks), (L, hd), 0.1, dtype, 1, 1.0)
    block = {
        "ln1": _normal(next(ks), (L, d), 0.1, dtype, 1, 1.0),
        "mixer": mixer,
        "ln2": _normal(next(ks), (L, d), 0.1, dtype, 1, 1.0),
        "ffn": {"router": mat((d, e), d), "wg": mat((e, d, f), d),
                "wi": mat((e, d, f), d), "wo": mat((e, f, d), f)},
    }
    return {"embed": _normal(next(ks), (pv, d), 1.0, dtype, _row_blocks(pv)),
            "blocks": [block],
            "final_norm": _normal(next(ks), (d,), 0.1, dtype, 1, 1.0),
            "lm_head": _normal(next(ks), (d, pv), d ** -0.5, dtype,
                               _row_blocks(d))}


def make(hf: Dict, arch: Dict, seed: int, dtype=jnp.bfloat16):
    return jax.jit(functools.partial(_build, hf, arch, dtype))(
        seed_key(seed))


def shapes(hf: Dict, arch: Dict, dtype=jnp.bfloat16):
    return jax.eval_shape(functools.partial(_build, hf, arch, dtype),
                          jax.random.key(0))


def program_fields(hf: Dict, arch: Dict) -> Dict:
    if not hf.get("norm_topk_prob"):
        raise ValueError("the program always renormalises the top-k "
                         "router weights")
    w = widths(hf)
    return {"family": "moe", "num_layers": w["layers"], "d_model": w["d"],
            "num_heads": w["hq"], "num_kv_heads": w["hkv"], "hdim": w["hd"],
            "num_experts": w["e"], "num_experts_per_tok": w["k"],
            "moe_d_ff": w["f"], "vocab_size": w["v"],
            "norm_eps": hf["rms_norm_eps"], "rope_theta": hf["rope_theta"],
            "qk_norm": arch["qk_norm"], "qkv_bias": arch["qkv_bias"],
            "dtype": arch["dtype"]}


def layer_matmul_params(hf: Dict) -> int:
    """Weights one token multiplies by in one layer: the attention
    projections, the router and k experts."""
    w = widths(hf)
    qd, kvd = w["hq"] * w["hd"], w["hkv"] * w["hd"]
    return (w["d"] * (qd + 2 * kvd) + qd * w["d"] + w["d"] * w["e"]
            + w["k"] * 3 * w["d"] * w["f"])


def step_flops(hf: Dict, segments: Iterable[Tuple[int, int]]) -> float:
    w = widths(hf)
    segs = list(segments)
    tokens = sum(q for q, _ in segs)
    keys = sum(causal_keys(q, h) for q, h in segs)
    return (2.0 * tokens * layer_matmul_params(hf) * w["layers"]
            + 4.0 * w["hq"] * w["hd"] * keys * w["layers"]
            + 2.0 * len(segs) * w["d"] * w["v"])


def kernel_counts(hf: Dict, kernel: str,
                  segments: Iterable[Tuple[int, int]]
                  ) -> List[Tuple[float, float, int]]:
    if kernel not in KERNELS:
        raise KeyError(kernel)
    w = widths(hf)
    segs = list(segments)
    fl = 4.0 * w["hq"] * w["hd"] * sum(causal_keys(q, h) for q, h in segs)
    by = 2.0 * sum(2 * (h + q) * w["hkv"] * w["hd"] + 2 * q * w["hq"] * w["hd"]
                   for q, h in segs)
    return [(fl, by, w["layers"])]
