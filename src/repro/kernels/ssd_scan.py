"""Chunked SSD (Mamba2) scan as a Pallas kernel.

The SSD hot loop is the compute core of the mamba2/jamba architectures:
per (batch, head) it alternates a quadratic intra-chunk block (two
(Q×Q)·(Q×HD) matmuls on the MXU) with an O(HD×DS) state update.  Grid =
(B, NH, n_chunks); the chunk axis is sequential and the recurrent state
(HD × DS fp32, e.g. 64×128 = 32 KiB) lives in VMEM scratch — the whole
recurrence never leaves VMEM.

Per-row scalars (dt, cumulative decay) are handled as (Q, 1)-shaped
columns, lane-broadcast where needed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, x_ref, dt_ref, b_ref, c_ref, h0_ref, y_ref, hout_ref,
            state_ref, *, chunk: int, n_chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = h0_ref[0, 0].astype(jnp.float32)

    a = a_ref[0, 0]                                            # scalar (<0)
    x = x_ref[0, 0].astype(jnp.float32)                        # (Q, HD)
    dt = dt_ref[0, 0].astype(jnp.float32)                      # (Q, 1)... stored (1,Q)
    dt = dt.reshape(chunk, 1)
    bmat = b_ref[0, 0].astype(jnp.float32)                     # (Q, DS)
    cmat = c_ref[0, 0].astype(jnp.float32)                     # (Q, DS)

    la = dt * a                                                # (Q, 1) log-decay
    cum = jnp.cumsum(la, axis=0)                               # (Q, 1)
    # intra-chunk: M[t,s] = exp(cum_t - cum_s) * (C_t·B_s) * dt_s for s<=t
    decay = jnp.exp(cum - cum.reshape(1, chunk))               # (Q, Q)
    tri = (jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
           >= jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1))
    cb = jax.lax.dot_general(cmat, bmat, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (Q, Q)
    m = jnp.where(tri, decay * cb * dt.reshape(1, chunk), 0.0)
    y = jax.lax.dot_general(m, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)   # (Q, HD)
    # inter-chunk: y += (C_t * exp(cum_t)) @ state^T
    cdecay = cmat * jnp.exp(cum)                               # (Q, DS)
    y = y + jax.lax.dot_general(cdecay, state_ref[...],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
    y_ref[0, 0] = y.astype(y_ref.dtype)
    # state update: h' = exp(cum_Q) h + X^T (w ⊙ B),  w_s = exp(cum_Q-cum_s)·dt_s
    w = jnp.exp(cum[chunk - 1] - cum) * dt                     # (Q, 1)
    dstate = jax.lax.dot_general(x, w * bmat, (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # (HD, DS)
    state_ref[...] = jnp.exp(cum[chunk - 1, 0]) * state_ref[...] + dstate

    @pl.when(ci == n_chunks - 1)
    def _finish():
        hout_ref[0, 0] = state_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x: jax.Array, dt: jax.Array, a: jax.Array, bmat: jax.Array,
             cmat: jax.Array, init_state: jax.Array, *, chunk: int = 128,
             interpret: bool = True):
    """Chunked SSD scan.

    x: (B, L, NH, HD); dt: (B, L, NH) post-softplus; a: (NH,) negative;
    bmat, cmat: (B, L, NH, DS); init_state: (B, NH, HD, DS) fp32.
    Returns (y (B, L, NH, HD), final_state (B, NH, HD, DS)).
    """
    b, l, nh, hd = x.shape
    ds = bmat.shape[-1]
    chunk = min(chunk, l)
    l_pad = -(-l // chunk) * chunk
    xt = jnp.moveaxis(x, 2, 1)                                 # (B, NH, L, HD)
    dtt = jnp.moveaxis(dt, 2, 1)                               # (B, NH, L)
    bt = jnp.moveaxis(bmat, 2, 1)
    ct = jnp.moveaxis(cmat, 2, 1)
    if l_pad != l:  # dt=0 padding is an exact identity for the state
        xt = jnp.pad(xt, ((0, 0), (0, 0), (0, l_pad - l), (0, 0)))
        dtt = jnp.pad(dtt, ((0, 0), (0, 0), (0, l_pad - l)))
        bt = jnp.pad(bt, ((0, 0), (0, 0), (0, l_pad - l), (0, 0)))
        ct = jnp.pad(ct, ((0, 0), (0, 0), (0, l_pad - l), (0, 0)))
    nc = l_pad // chunk

    kern = functools.partial(_kernel, chunk=chunk, n_chunks=nc)
    y, hout = pl.pallas_call(
        kern,
        grid=(b, nh, nc),
        in_specs=[
            pl.BlockSpec((1, 1), lambda bb, h, ci: (h, 0)),
            pl.BlockSpec((1, 1, chunk, hd), lambda bb, h, ci: (bb, h, ci, 0)),
            pl.BlockSpec((1, 1, chunk), lambda bb, h, ci: (bb, h, ci)),
            pl.BlockSpec((1, 1, chunk, ds), lambda bb, h, ci: (bb, h, ci, 0)),
            pl.BlockSpec((1, 1, chunk, ds), lambda bb, h, ci: (bb, h, ci, 0)),
            pl.BlockSpec((1, 1, hd, ds), lambda bb, h, ci: (bb, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, hd), lambda bb, h, ci: (bb, h, ci, 0)),
            pl.BlockSpec((1, 1, hd, ds), lambda bb, h, ci: (bb, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, nh, l_pad, hd), x.dtype),
            jax.ShapeDtypeStruct((b, nh, hd, ds), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, ds), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a.reshape(nh, 1).astype(jnp.float32), xt, dtt, bt, ct, init_state)
    return jnp.moveaxis(y[:, :, :l], 1, 2), hout
