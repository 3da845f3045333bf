"""Plain reference for a dense GQA decoder (Qwen2 / Qwen3 family).

Written from the published architecture, not from the program: token
embedding; per layer RMSNorm, Q/K/V projections (with biases where the
config has them), per-head RMSNorm of q and k (Qwen3), rotary position
embedding in the rotate-half form with ``rope_theta``, causal softmax
attention with grouped KV heads, output projection and residual, then
RMSNorm, a SwiGLU MLP and residual; a final RMSNorm and the LM head.
No cache, no batching, no kernel: one sequence at a time in float32
with every matrix product at ``Precision.HIGHEST``.

It reads the benchmark's weights in their served dtype (bfloat16) and
widens each layer to float32 as it goes, so the whole model is never
held twice.  Attention runs a block of queries at a time and the MLP a
block of rows at a time, so an 8k-token context fits beside the weights.

``quantize="fp8"`` is the control, the step below the stated bfloat16
that would tempt a later change: every projection computes in fp8
(e4m3) — its weight rounded with one scale per output channel, its
input with one scale per row — and accumulates in float32, as an fp8
matrix unit does.  The embedding rows are rounded alike.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512
ROW_BLOCK = 1024
VOCAB_BLOCKS = 16


def _mm(a, b, quantize=None):
    if quantize == "fp8":
        a = _fp8(a, -1)
    return jnp.matmul(a, b, precision=HI)


FP8_MAX = 448.0          # largest finite float8_e4m3fn


def _fp8(a, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    scale = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / FP8_MAX
    scale = jnp.where(scale == 0, 1.0, scale)
    return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _rms(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _rope(x, pos, theta):
    """x: (L, H, D) rotated by absolute positions pos: (L,)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _blocks(n: int, size: int) -> int:
    return max(1, n // size) if n % size == 0 else 1


def _layer(hf: Dict, arch: Dict, quantize: Optional[str], x, lw):
    n = x.shape[0]
    eps = float(hf["rms_norm_eps"])
    d = int(hf["hidden_size"])
    hq, hkv = int(hf["num_attention_heads"]), int(hf["num_key_value_heads"])
    hd = int(hf.get("head_dim") or d // hq)
    f32 = lambda a: a.astype(jnp.float32)          # noqa: E731
    mat = (lambda a: _fp8(f32(a), -2)) if quantize == "fp8" else f32
    mm = functools.partial(_mm, quantize=quantize)
    mx, ffn = lw["mixer"], lw["ffn"]
    pos = jnp.arange(n)

    h = _rms(x, f32(lw["ln1"]), eps)
    q = mm(h, mat(mx["wq"]))
    k = mm(h, mat(mx["wk"]))
    v = mm(h, mat(mx["wv"]))
    if arch.get("qkv_bias"):
        q, k, v = q + f32(mx["bq"]), k + f32(mx["bk"]), v + f32(mx["bv"])
    q = q.reshape(n, hq, hd)
    k = k.reshape(n, hkv, hd)
    v = v.reshape(n, hkv, hd)
    if arch.get("qk_norm"):
        q = _rms(q, f32(mx["q_norm"]), eps)
        k = _rms(k, f32(mx["k_norm"]), eps)
    q = _rope(q, pos, float(hf["rope_theta"]))
    k = _rope(k, pos, float(hf["rope_theta"]))
    g = hq // hkv
    nb = _blocks(n, Q_BLOCK)
    qb = q.reshape(nb, n // nb, hkv, g, hd)

    def attend(args):
        qi, i = args
        qpos = i * (n // nb) + jnp.arange(n // nb)
        s = jnp.einsum("qkgd,tkd->kgqt", qi, k, precision=HI) / np.sqrt(hd)
        s = jnp.where(pos[None, None, None, :] <= qpos[None, None, :, None],
                      s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", p, v, precision=HI)

    o = jax.lax.map(attend, (qb, jnp.arange(nb))).reshape(n, hq * hd)
    x = x + mm(o, mat(mx["wo"]))

    h = _rms(x, f32(lw["ln2"]), eps)
    wg, wi, wo = mat(ffn["wg"]), mat(ffn["wi"]), mat(ffn["wo"])
    rb = _blocks(n, ROW_BLOCK)
    y = jax.lax.map(lambda hb: mm(jax.nn.silu(mm(hb, wg)) * mm(hb, wi), wo),
                    h.reshape(rb, n // rb, d))
    return x + y.reshape(n, d), None


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _logits(hf_items, arch_items, quantize, weights, tokens, rows):
    hf, arch = dict(hf_items), dict(arch_items)
    f32 = lambda a: a.astype(jnp.float32)          # noqa: E731
    mat = (lambda a: _fp8(f32(a), -2)) if quantize == "fp8" else f32
    eps = float(hf["rms_norm_eps"])
    vocab = int(hf["vocab_size"])
    x = f32(jnp.take(weights["embed"], tokens, axis=0))
    if quantize == "fp8":
        x = _fp8(x, -1)             # a table: each row on its own scale
    x, _ = jax.lax.scan(functools.partial(_layer, hf, arch, quantize), x,
                        weights["blocks"][0])
    h = _rms(jnp.take(x, rows, axis=0), f32(weights["final_norm"]), eps)
    head = weights["lm_head"]
    nv = VOCAB_BLOCKS
    cw = head.shape[1] // nv

    def cols(i):
        w = jax.lax.dynamic_slice_in_dim(head, i * cw, cw, axis=1)
        return _mm(h, mat(w), quantize)

    out = jax.lax.map(cols, jnp.arange(nv))          # (nv, R, cw)
    out = jnp.moveaxis(out, 0, 1).reshape(h.shape[0], nv * cw)
    return out[:, :vocab]


def logits(hf: Dict, arch: Dict, weights, tokens: np.ndarray,
           rows: np.ndarray, quantize: Optional[str] = None) -> np.ndarray:
    """float32 logits of ``tokens`` (one sequence) at positions ``rows``:
    row r predicts the token after position r.  Pad ``tokens`` at the
    end and ``rows`` with copies to reuse one compiled shape: causal
    attention keeps pad tokens from reaching earlier positions."""
    key = lambda d: tuple(sorted(          # noqa: E731
        (k, v) for k, v in d.items() if isinstance(v, (int, float, str))))
    out = _logits(key(hf), key(arch), quantize, weights,
                  jnp.asarray(tokens, jnp.int32), jnp.asarray(rows, jnp.int32))
    return np.asarray(out)
