"""Plain float32 reference of ``moe_arch.py``'s architecture, copied into
a copy of the benchmark as ``reference/moe_gqa.py``.

Written from the description: per layer RMSNorm, Q/K/V projections,
per-head RMSNorm of q and k, rotary embedding (rotate-half) with
``rope_theta``, causal softmax attention with grouped KV heads, output
projection and residual; then RMSNorm, a softmax router, each token's
``num_experts_per_tok`` most probable experts with their probabilities
renormalised to sum to 1, the weighted sum of those experts' SwiGLU
outputs, and residual; a final RMSNorm and the LM head.  One sequence,
no cache, every product at ``Precision.HIGHEST``.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    n, d = x.shape[0], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(n, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(hf: Dict, arch: Dict, x, lw):
    n = x.shape[0]
    eps, theta = float(hf["rms_norm_eps"]), float(hf["rope_theta"])
    hq, hkv = int(hf["num_attention_heads"]), int(hf["num_key_value_heads"])
    hd = int(hf["head_dim"])
    k_top = int(hf["num_experts_per_tok"])
    mx, ffn = lw["mixer"], lw["ffn"]
    mm = lambda a, b: jnp.matmul(a, b, precision=HI)      # noqa: E731

    h = _rms(x, lw["ln1"], eps)
    q = mm(h, mx["wq"]).reshape(n, hq, hd)
    k = mm(h, mx["wk"]).reshape(n, hkv, hd)
    v = mm(h, mx["wv"]).reshape(n, hkv, hd)
    if arch.get("qk_norm"):
        q, k = _rms(q, mx["q_norm"], eps), _rms(k, mx["k_norm"], eps)
    q, k = _rope(q, theta), _rope(k, theta)
    g = hq // hkv
    s = jnp.einsum("qkgd,tkd->kgqt", q.reshape(n, hkv, g, hd), k,
                   precision=HI) / np.sqrt(hd)
    causal = jnp.arange(n)[None, :] <= jnp.arange(n)[:, None]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("kgqt,tkd->qkgd", p, v, precision=HI).reshape(n, hq * hd)
    x = x + mm(o, mx["wo"])

    h = _rms(x, lw["ln2"], eps)
    probs = jax.nn.softmax(mm(h, ffn["router"]), axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k_top)              # (n, k)
    top_p = top_p / top_p.sum(-1, keepdims=True)
    gate = jnp.einsum("nd,nkdf->nkf", h, ffn["wg"][top_e], precision=HI)
    up = jnp.einsum("nd,nkdf->nkf", h, ffn["wi"][top_e], precision=HI)
    out = jnp.einsum("nkf,nkfd->nkd", jax.nn.silu(gate) * up,
                     ffn["wo"][top_e], precision=HI)
    return x + jnp.einsum("nk,nkd->nd", top_p, out, precision=HI), None


def logits(hf: Dict, arch: Dict, weights, tokens: np.ndarray,
           rows: np.ndarray) -> np.ndarray:
    """float32 logits of ``tokens`` (one sequence) at positions ``rows``."""
    w = jax.tree.map(lambda a: a.astype(jnp.float32), weights)
    x = jnp.take(w["embed"], jnp.asarray(tokens, jnp.int32), axis=0)
    x, _ = jax.lax.scan(lambda c, lw: _layer(hf, arch, c, lw), x,
                        w["blocks"][0])
    h = _rms(jnp.take(x, jnp.asarray(rows, jnp.int32), axis=0),
             w["final_norm"], float(hf["rms_norm_eps"]))
    out = jnp.matmul(h, w["lm_head"], precision=HI)
    return np.asarray(out[:, :int(hf["vocab_size"])])
