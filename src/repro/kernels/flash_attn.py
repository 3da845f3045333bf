"""Flash attention Pallas kernel for prefill AND re-prefill.

TPU-native design (HBM→VMEM→MXU):
  * grid = (B, Hq, n_q_blocks, n_kv_blocks); the kv axis is sequential
    ("arbitrary") so the online-softmax accumulator lives in VMEM scratch.
  * blocks are MXU-aligned: block_q × head_dim and block_k × head_dim
    tiles, fp32 accumulation via ``preferred_element_type``.
  * re-prefill = same kernel with per-request ``q_offsets`` (history
    length): query absolute positions are offset + arange, so causal
    masking over a KV cache longer than the query block is exact.
  * GQA without KV duplication: the kv-head index is derived from the
    q-head grid index (h // rep) in the BlockSpec index maps.
  * causal / sliding-window block skipping: fully-masked kv blocks are
    skipped via ``pl.when`` (no MXU work, no VMEM traffic beyond the
    prefetch the pipeline already issued).

Scratch m/l are kept as (block_q, 128) lane-replicated tiles — the TPU
layout for per-row scalars.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128


def _kernel(off_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref, *, scale: float, causal: bool,
            window: Optional[int], block_q: int, block_k: int,
            n_kv_blocks: int):
    ki = pl.program_id(3)
    qi = pl.program_id(2)
    offset = off_ref[pl.program_id(0)]
    kv_len = len_ref[pl.program_id(0)]

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = offset + qi * block_q
    k_start = ki * block_k

    # block-level skip: entire kv block after the last query position,
    # or entirely before the sliding window of the first query position
    run = k_start <= q_start + block_q - 1 if causal else True
    if window is not None:
        run = jnp.logical_and(run, k_start + block_k - 1 > q_start - window)
    run = jnp.logical_and(run, k_start < kv_len)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0]                                        # (bq, D)
        k = k_ref[0, 0]                                        # (bk, D)
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale        # (bq, bk)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = kpos < kv_len
        if causal:
            mask = jnp.logical_and(mask, kpos <= qpos)
        if window is not None:
            mask = jnp.logical_and(mask, kpos > qpos - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, :1]                                  # (bq, 1)
        l_prev = l_ref[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)                        # (bq, 1)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                # (bq, D)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == n_kv_blocks - 1)
    def _finish():
        l = l_ref[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)                        # fully-masked rows
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "interpret"))
def flash_attn(q: jax.Array, k: jax.Array, v: jax.Array,
               q_offsets: Optional[jax.Array] = None,
               kv_lengths: Optional[jax.Array] = None, *,
               causal: bool = True, window: Optional[int] = None,
               block_q: int = 128, block_k: int = 128,
               interpret: bool = True) -> jax.Array:
    """q: (B, Lq, Hq, D); k, v: (B, S, Hkv, D).  Returns (B, Lq, Hq, D).

    q_offsets: (B,) int32 history length per request (re-prefill);
    kv_lengths: (B,) valid KV entries (defaults to S).
    """
    b, lq, hq, d = q.shape
    s, hkv = k.shape[1], k.shape[2]
    rep = hq // hkv
    if q_offsets is None:
        q_offsets = jnp.zeros((b,), jnp.int32)
    if kv_lengths is None:
        kv_lengths = jnp.full((b,), s, jnp.int32)

    block_q = min(block_q, max(lq, 1))
    block_k = min(block_k, s)
    lq_pad = -(-lq // block_q) * block_q
    s_pad = -(-s // block_k) * block_k
    qt = jnp.moveaxis(q, 2, 1)                                 # (B, Hq, Lq, D)
    kt = jnp.moveaxis(k, 2, 1)
    vt = jnp.moveaxis(v, 2, 1)
    if lq_pad != lq:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, lq_pad - lq), (0, 0)))
    if s_pad != s:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, s_pad - s), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, s_pad - s), (0, 0)))
    nq, nk = lq_pad // block_q, s_pad // block_k

    grid = (b, hq, nq, nk)
    kern = functools.partial(
        _kernel, scale=d ** -0.5, causal=causal, window=window,
        block_q=block_q, block_k=block_k, n_kv_blocks=nk)
    # offsets and lengths ride in SMEM as scalar prefetch: a (1, 1)
    # block over a (B, 1) array does not meet the TPU tiling rule
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bb, h, qi, ki, *_: (bb, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bb, h, qi, ki, *_: (bb, h // rep, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bb, h, qi, ki, *_: (bb, h // rep, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda bb, h, qi, ki, *_: (bb, h, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hq, lq_pad, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q_offsets.astype(jnp.int32), kv_lengths.astype(jnp.int32), qt, kt, vt)
    return jnp.moveaxis(out[:, :, :lq], 1, 2)
