"""Fused on-device sampling kernel (DESIGN.md §10).

One grid row per logits row: apply additive logit bias, temperature,
EXACT top-k (kth-value threshold, ties kept) and tie-inclusive top-p
truncation, then draw the token by inverse CDF from ONE uniform — plus
the speculative-decoding outputs: the filtered probability of a draft
token (the accept test ``u_acc < p_draft``) and the residual resample
token with the draft zeroed out (the reject commit).  Greedy rows
(``temp <= 0``) short-circuit to the biased argmax.

Only the (R,) token ids leave the device — never the (R, V) logits —
which closes the last host round-trip the fused greedy slice (PR 5)
left open for non-greedy sessions.

Key derivation: uniforms are drawn HOST-side from each session's
replayable ``np.random.Generator`` (seeded from ``SamplingParams.seed``
or the session id) and shipped as (R,) scalars.  Host and device
sampling therefore consume the SAME uniform stream in the same order —
``serving/sampling.py`` is the bit-level oracle, and a session can hop
between fused and host paths mid-stream without forking its rng.

Exactness over a sort-free kernel: both truncations reduce to a value
threshold, and float32 ordering equals int32 ordering of the monotone
key ``bits >= 0 ? bits : bits ^ 0x7fffffff``, so the kth largest value
(top-k) and the minimal kept probability (top-p) are found by a 31-step
binary descent over key bits — O(V log) elementwise work, no sort, no
scatter, and bit-identical thresholds to ``np.partition`` on host.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = np.float32(-1e30)
LANES = 128       # output lane width: scalars broadcast across one tile
MAX_BIAS = 8      # logit-bias entries per row (engine falls back past it)
_SIGN_LOW = np.int32(0x7FFFFFFF)


def _float_key(x: jax.Array) -> jax.Array:
    """Monotone int32 key: x < y  ⟺  key(x) < key(y) (float32, no NaN).
    Positives keep their bits; negatives flip the low 31 so larger
    magnitude sorts lower.  Lets value thresholds be searched bitwise."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(bits >= 0, bits, bits ^ _SIGN_LOW)


def _kth_key(keys: jax.Array, valid: jax.Array, k: jax.Array) -> jax.Array:
    """Max nonnegative T with ``count(valid & keys >= T) >= k`` — the
    kth largest key among ``valid`` when that key is >= 0.  Greedy MSB
    descent: claim each bit iff enough keys still clear the raised bar."""

    def body(i, t):
        cand = t | (np.int32(1) << (30 - i))
        cnt = jnp.sum(jnp.where(valid & (keys >= cand), 1, 0))
        return jnp.where(cnt >= k, cand, t)

    return jax.lax.fori_loop(0, 31, body, np.int32(0))


def _topk_keep(scaled: jax.Array, k: jax.Array) -> jax.Array:
    """Boolean keep-mask of the k largest entries of ``scaled``, TIES
    INCLUDED — exactly ``scaled >= np.partition(scaled, -k)[-k]``.  The
    kth value may be negative, where int32 keys are negative too, so the
    descent runs on whichever side of zero holds the kth key: all of
    ``key & 0x7fffffff`` preserves order WITHIN the negatives."""
    key = _float_key(scaled)
    nonneg = key >= 0
    cnt_nn = jnp.sum(nonneg.astype(jnp.int32))
    t_nn = _kth_key(key, nonneg, k)
    low = key & _SIGN_LOW
    t_ng = _kth_key(low, ~nonneg, k - cnt_nn)
    keep_nn = nonneg & (key >= t_nn)
    keep_ng = nonneg | ((low >= t_ng) & ~nonneg)
    return jnp.where(cnt_nn >= k, keep_nn, keep_ng)


def _topp_theta(probs: jax.Array, top_p: jax.Array) -> jax.Array:
    """Minimal probability theta with strictly-greater mass
    ``G(theta) = sum(probs > theta) < top_p``; keeping ``probs >=
    theta`` is then the tie-inclusive nucleus (equal-prob tokens live or
    die together), matching ``serving.sampling.filtered_probs``.  Probs
    are nonnegative so their bitcasts ARE their keys; descend from the
    MSB, leaving a bit clear iff the predicate already holds with every
    lower bit filled (the minimal-K invariant)."""
    keys = jax.lax.bitcast_convert_type(probs, jnp.int32)

    def body(i, kacc):
        bit = np.int32(1) << (30 - i)
        trial = kacc | (bit - 1)
        mass = jnp.sum(jnp.where(keys > trial, probs, np.float32(0.0)))
        return jnp.where(mass < top_p, kacc, kacc | bit)

    kmin = jax.lax.fori_loop(0, 31, body, np.int32(0))
    return jax.lax.bitcast_convert_type(kmin, jnp.float32)


def _inv_cdf(probs: jax.Array, u: jax.Array) -> jax.Array:
    """Inverse-CDF draw: count of cumulative masses <= u (== host
    ``searchsorted(cumsum, u, side='right')``), clamped into range."""
    v = probs.shape[-1]
    cdf = jnp.cumsum(probs, axis=-1)
    idx = jnp.sum((cdf <= u).astype(jnp.int32))
    return jnp.minimum(idx, v - 1).astype(jnp.int32)


def _sample_core(biased, iota, temp, top_k, top_p, u, draft):
    """Shared math for the kernel body and the jnp oracle.

    biased: (1, V) float32 logits with bias applied; iota: (1, V) int32
    column ids; scalars: temp/top_p/u float32, top_k/draft int32
    (top_k == 0 → off, top_p >= 1 → off).  Returns scalar
    (token int32, p_draft float32, alt int32).  Mirrors
    ``serving.sampling.filtered_probs`` op for op so thresholds agree
    bit-for-bit; only reduction summation order may differ.
    """
    v = biased.shape[-1]
    gtok = jnp.argmax(biased).astype(jnp.int32)

    scaled = biased / jnp.maximum(temp, np.float32(1e-6))
    do_k = (top_k > 0) & (top_k < v)
    keep = _topk_keep(scaled, top_k) | ~do_k
    scaled = jnp.where(keep, scaled, NEG)

    probs = jnp.exp(scaled - jnp.max(scaled))
    probs = probs / jnp.sum(probs)
    do_p = (top_p > np.float32(0.0)) & (top_p < np.float32(1.0))
    keep = (probs >= _topp_theta(probs, top_p)) | ~do_p
    scaled = jnp.where(keep, scaled, NEG)

    probs = jnp.exp(scaled - jnp.max(scaled))
    probs = probs / jnp.sum(probs)
    stok = _inv_cdf(probs, u)

    dcol = iota == jnp.clip(draft, 0, v - 1)
    p_d = jnp.sum(jnp.where(dcol, probs, np.float32(0.0)))
    # residual distribution for a deterministic (point-mass) draft:
    # p with the draft zeroed, renormalized — the exact reject commit
    resid = jnp.where(dcol, np.float32(0.0), probs)
    mass = jnp.sum(resid)
    salt = _inv_cdf(resid / jnp.maximum(mass, np.float32(1e-30)), u)
    salt = jnp.where(mass > 0, salt, stok)

    greedy = temp <= np.float32(0.0)
    token = jnp.where(greedy, gtok, stok)
    p_draft = jnp.where(greedy, (gtok == draft).astype(jnp.float32), p_d)
    alt = jnp.where(greedy, gtok, salt)
    return token, p_draft, alt


def _bias_row(row, iota, bias_ids, bias_vals):
    """Additive logit bias from up to MAX_BIAS (id, val) pairs; id < 0
    is an empty entry.  Out-of-range ids match no column — the host
    path ignores them the same way."""
    for j in range(MAX_BIAS):
        row = jnp.where(iota == bias_ids[j], row + bias_vals[j], row)
    return row


def _fused_sample_kernel(temp_ref, topk_ref, topp_ref, u_ref, draft_ref,
                         bids_ref, bvals_ref, logits_ref,
                         tok_ref, pd_ref, alt_ref):
    r = pl.program_id(0)
    row = logits_ref[...].astype(jnp.float32)               # (1, V)
    v = row.shape[-1]
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, v), 1)
    for j in range(MAX_BIAS):
        row = jnp.where(iota == bids_ref[r, j], row + bvals_ref[r, j], row)
    token, p_draft, alt = _sample_core(
        row, iota, temp_ref[r], topk_ref[r], topp_ref[r],
        u_ref[r], draft_ref[r])
    tok_ref[...] = jnp.full((1, LANES), token, jnp.int32)
    pd_ref[...] = jnp.full((1, LANES), p_draft, jnp.float32)
    alt_ref[...] = jnp.full((1, LANES), alt, jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_sample(logits, temp, top_k, top_p, bias_ids, bias_vals, u,
                 draft, *, interpret: bool = False):
    """Sample R rows on device.  logits: (R, V); temp/top_p/u: (R,)
    float32; top_k/draft: (R,) int32; bias_ids/bias_vals: (R, MAX_BIAS).
    Returns (token (R,) int32, p_draft (R,) float32, alt (R,) int32);
    only these (R,)-sized results ever cross to host."""
    r, v = logits.shape
    # one (1, V) row per program: blocks squeeze a leading row axis so
    # their two trailing dims equal the array's own (TPU tiling rule)
    outs = [jax.ShapeDtypeStruct((r, 1, LANES), jnp.int32),
            jax.ShapeDtypeStruct((r, 1, LANES), jnp.float32),
            jax.ShapeDtypeStruct((r, 1, LANES), jnp.int32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(r,),
        in_specs=[pl.BlockSpec((None, 1, v), lambda i, *_: (i, 0, 0))],
        out_specs=[pl.BlockSpec((None, 1, LANES),
                                lambda i, *_: (i, 0, 0))] * 3,
    )
    tok, p_draft, alt = pl.pallas_call(
        _fused_sample_kernel,
        grid_spec=grid_spec,
        out_shape=outs,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(jnp.asarray(temp, jnp.float32), jnp.asarray(top_k, jnp.int32),
      jnp.asarray(top_p, jnp.float32), jnp.asarray(u, jnp.float32),
      jnp.asarray(draft, jnp.int32), jnp.asarray(bias_ids, jnp.int32),
      jnp.asarray(bias_vals, jnp.float32),
      jnp.asarray(logits, jnp.float32).reshape(r, 1, v))
    return tok[:, 0, 0], p_draft[:, 0, 0], alt[:, 0, 0]


@jax.jit
def fused_sample_reference(logits, temp, top_k, top_p, bias_ids,
                           bias_vals, u, draft):
    """jnp oracle: the same shared core vmapped over rows (the XLA
    fallback path off-TPU; also what `kernels.ref.ref_fused_sample`
    re-exports)."""
    v = logits.shape[-1]
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, v), 1)

    def row_fn(row, t, k, p, uu, d, bi, bv):
        biased = _bias_row(row[None, :].astype(jnp.float32), iota, bi, bv)
        return _sample_core(biased, iota, t, k, p, uu, d)

    return jax.vmap(row_fn)(
        jnp.asarray(logits, jnp.float32), jnp.asarray(temp, jnp.float32),
        jnp.asarray(top_k, jnp.int32), jnp.asarray(top_p, jnp.float32),
        jnp.asarray(u, jnp.float32), jnp.asarray(draft, jnp.int32),
        jnp.asarray(bias_ids, jnp.int32), jnp.asarray(bias_vals, jnp.float32))
