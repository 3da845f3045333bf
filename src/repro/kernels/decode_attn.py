"""Flash-decode Pallas kernels: one new token attending to a KV cache.

The decode step is memory-bound (the paper's short-request regime): the
valid KV prefix is streamed HBM→VMEM once; arithmetic is a (rep × D) ·
(D × block_k) GEMV-like matmul per block.  Grid = (B, Hkv, n_kv_blocks)
with the kv axis sequential; the online-softmax state for the ``rep``
query heads of one KV group sits in VMEM scratch.

Two entry points share the kernel math:

  * :func:`decode_attn` — the batch-cache form: k/v are (B, S, Hkv, D)
    rows already gathered out of the arena (the legacy dense path);
  * :func:`decode_attn_arena` — the arena-resident form: k/v are the
    WHOLE KV arena (N_slots, S, Hkv, D) and a scalar-prefetched
    ``slot_map`` selects each batch row's slot inside the BlockSpec
    index maps, so a decode tick streams only the valid cache prefixes
    of its live sessions — no whole-slot gather/scatter round-trip, no
    O(S_max) HBM copies per generated token.  KV blocks past a row's
    valid length are clamped to the last valid block in the index map
    (a repeated block index skips the DMA) and their compute is skipped.

Layout note: the arena and paged forms read (1, block_k, Hkv, D)
blocks straight from the pool's native (slots|pages, S, Hkv, D) layout
— a transpose would copy the whole pool and defeat the in-place point.
A block's two trailing dims must be multiples of the TPU tile or equal
the array's own, so one block carries EVERY KV head (a size-1 head block
does not compile for the chip) and one program serves all query heads of
its row, selecting each head's rows inside the kernel.
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


def _largest_divisor(n: int, cap: int) -> int:
    """Largest block size ≤ cap dividing n (arena S is never padded —
    padding would copy the whole arena)."""
    for b in range(min(cap, n), 0, -1):
        if n % b == 0:
            return b
    return 1


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, block_k: int, n_kv_blocks: int):
    ki = pl.program_id(2)
    kv_len = len_ref[pl.program_id(0)]

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    k_start = ki * block_k

    @pl.when(k_start < kv_len)
    def _compute():
        q = q_ref[0, 0]                                        # (rep, D)
        k = k_ref[0, 0]                                        # (bk, D)
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale        # (rep, bk)
        kpos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        mask = kpos < kv_len
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == n_kv_blocks - 1)
    def _finish():
        l = l_ref[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode_attn(q: jax.Array, k: jax.Array, v: jax.Array,
                lengths: jax.Array, *, block_k: int = 512,
                interpret: bool = True) -> jax.Array:
    """q: (B, Hq, D); k, v: (B, S, Hkv, D); lengths: (B,).

    Returns (B, Hq, D).
    """
    b, hq, d = q.shape
    s, hkv = k.shape[1], k.shape[2]
    rep = hq // hkv
    block_k = min(block_k, s)
    s_pad = -(-s // block_k) * block_k
    kt = jnp.moveaxis(k, 2, 1)                                 # (B, Hkv, S, D)
    vt = jnp.moveaxis(v, 2, 1)
    if s_pad != s:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, s_pad - s), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, s_pad - s), (0, 0)))
    qg = q.reshape(b, hkv, rep, d)
    nk = s_pad // block_k

    kern = functools.partial(_kernel, scale=d ** -0.5, block_k=block_k,
                             n_kv_blocks=nk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, hkv, nk),
        in_specs=[
            pl.BlockSpec((1, 1, rep, d), lambda bb, g, ki, *_: (bb, g, 0, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bb, g, ki, *_: (bb, g, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bb, g, ki, *_: (bb, g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, rep, d),
                               lambda bb, g, ki, *_: (bb, g, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rep, LANES), jnp.float32),
            pltpu.VMEM((rep, LANES), jnp.float32),
            pltpu.VMEM((rep, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, rep, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lengths.astype(jnp.int32), qg, kt, vt)
    return out.reshape(b, hq, d)


def _arena_kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, scale: float,
                  window: Optional[int], depth: int, block_k: int,
                  n_kv_blocks: int, n_phys_blocks: int):
    """One (row, kv block) program over ALL heads: the kv block carries
    every KV head, so each fetched block serves every query head of the
    row (static loop over KV heads, each a (rep, D) × (D, block_k)
    product)."""
    del tbl_ref                      # consumed by the BlockSpec index maps
    b = pl.program_id(0)
    ki = pl.program_id(1)
    kv_len = len_ref[b]
    if window is None:
        n_valid = kv_len
        k_start = ki * block_k
    else:
        # rolling arena: only the last min(kv_len, depth) slots are
        # valid, and the in-window ones form a CYCLIC contiguous range
        # starting at the oldest in-window position's slot — iteration
        # index ki walks that range's blocks (mirroring the index map),
        # so only ceil(window/block_k)+1 blocks stream per row
        n_valid = jnp.minimum(kv_len, depth)
        w_eff = jnp.minimum(window, kv_len)
        s0 = (kv_len - w_eff) % depth
        phys = (s0 // block_k + ki) % n_phys_blocks
        k_start = phys * block_k

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(k_start < n_valid)
    def _compute():
        rep = q_ref.shape[2]
        slot = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (rep, block_k), 1)
        mask = slot < n_valid
        if window is not None:
            # rolling slot s holds the newest position < kv_len congruent
            # to s mod depth; the query sits at kv_len − 1, so keep only
            # keys inside its window (qpos − window, qpos]
            wraps = jnp.maximum(kv_len - 1 - slot, 0) // depth
            kpos = slot + wraps * depth
            mask = jnp.logical_and(mask, kpos > kv_len - 1 - window)
        for g in range(k_ref.shape[2]):
            q = q_ref[0, g]                                    # (rep, D)
            k = k_ref[0, :, g, :]                              # (bk, D)
            v = v_ref[0, :, g, :]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale    # (rep, bk)
            s = jnp.where(mask, s, NEG_INF)
            m_prev = m_ref[g][:, :1]
            l_prev = l_ref[g][:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
            pv = jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            acc_ref[g] = acc_ref[g] * alpha + pv
            m_ref[g] = jnp.broadcast_to(m_new, m_ref.shape[1:])
            l_ref[g] = jnp.broadcast_to(l_new, l_ref.shape[1:])

    @pl.when(ki == n_kv_blocks - 1)
    def _finish():
        l = l_ref[:, :, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _arena_call(kv_map, table, lengths, q, k, v, *, window: Optional[int],
                depth: int, block_k: int, n_kv_blocks: int,
                n_phys_blocks: int, interpret: bool) -> jax.Array:
    """Shared pallas_call of the arena and paged forms.  Grid = (row, kv
    block); each program holds every query head of its row and reads
    (1, block_k, Hkv, D) kv blocks, whose two trailing dims are the
    pool's own — the TPU tiling rule for a block's last two dims — so
    one DMA per block serves all heads."""
    b, hq, d = q.shape
    hkv = k.shape[2]
    rep = hq // hkv
    qg = q.reshape(b, hkv, rep, d)
    kern = functools.partial(_arena_kernel, scale=d ** -0.5, window=window,
                             depth=depth, block_k=block_k,
                             n_kv_blocks=n_kv_blocks,
                             n_phys_blocks=n_phys_blocks)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_kv_blocks),
        in_specs=[
            pl.BlockSpec((1, hkv, rep, d), lambda bb, ki, *_: (bb, 0, 0, 0)),
            pl.BlockSpec((1, block_k, hkv, d), kv_map),
            pl.BlockSpec((1, block_k, hkv, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, hkv, rep, d),
                               lambda bb, ki, *_: (bb, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((hkv, rep, LANES), jnp.float32),
            pltpu.VMEM((hkv, rep, LANES), jnp.float32),
            pltpu.VMEM((hkv, rep, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, rep, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(table.astype(jnp.int32), lengths.astype(jnp.int32), qg, k, v)
    return out.reshape(b, hq, d)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def decode_attn_paged(q: jax.Array, k: jax.Array, v: jax.Array,
                      page_table: jax.Array, lengths: jax.Array, *,
                      window: Optional[int] = None,
                      interpret: bool = True) -> jax.Array:
    """Paged flash decode.

    The paged generalization of :func:`decode_attn_arena`: each row's KV
    lives on fixed-size pages scattered in a shared pool and a per-row
    page table maps logical kv block → physical page, so pages can be
    SHARED between rows (prefix reuse, COW forks).

    q: (B, Hq, D); k, v: (N_pages, page_size, Hkv, D) — the FULL page
    pools, untouched; page_table: (B, P_max) physical page of each row's
    logical page i; lengths: (B,) valid cache entries (history + the new
    row, which the caller scatter-wrote before this call).

    Returns (B, Hq, D).  One kv grid block == one page: logical page ki
    holds absolute positions [ki·ps, (ki+1)·ps), so the shared
    ``_arena_kernel`` math is reused verbatim with the page-id lookup
    replacing the slot-id lookup.  Logical pages past
    ``ceil(lengths/ps)`` clamp to the last valid page (a repeated page
    index skips the DMA), so a tick streams only ``lengths[b]`` cache
    rows per sequence.

    ``window``: sliding-window width.  The page table is then a RING
    over its P_max entries (§7's rolling arena at page granularity):
    position p lives on ring page (p // ps) % P_max at offset p % ps.
    The kv grid axis shrinks to the pages the window can touch — the
    walk starts at the oldest in-window position's page and wraps
    modularly, exactly :func:`decode_attn_arena`'s windowed form with
    the page-id lookup replacing the slot-id lookup.
    """
    ps = k.shape[1]
    p_max = page_table.shape[1]
    nk = p_max                     # the page IS the kv block
    nk_iter = nk if window is None else min(nk, (window - 1) // ps + 2)
    depth = ps * p_max

    def kv_map(bb, ki, pt_ref, len_ref):
        if window is None:
            last = jnp.maximum(len_ref[bb] - 1, 0) // ps
            return (pt_ref[bb, jnp.minimum(ki, last)], 0, 0, 0)
        kvl = len_ref[bb]
        n_valid = jnp.minimum(kvl, depth)
        w_eff = jnp.minimum(window, kvl)
        s0 = (kvl - w_eff) % depth      # oldest in-window ring slot
        phys = (s0 // ps + ki) % nk
        # pre-wraparound (kvl < depth) the walk cannot wrap, so clamping
        # to the last valid page only retargets pages the kernel skips
        last = jnp.maximum(n_valid - 1, 0) // ps
        return (pt_ref[bb, jnp.minimum(phys, last)], 0, 0, 0)

    return _arena_call(kv_map, page_table, lengths, q, k, v, window=window,
                       depth=depth, block_k=ps, n_kv_blocks=nk_iter,
                       n_phys_blocks=nk, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("window", "block_k",
                                             "interpret"))
def decode_attn_arena(q: jax.Array, k: jax.Array, v: jax.Array,
                      slot_map: jax.Array, lengths: jax.Array, *,
                      window: Optional[int] = None, block_k: int = 512,
                      interpret: bool = True) -> jax.Array:
    """Arena-resident flash decode.

    q: (B, Hq, D); k, v: (N_slots, S, Hkv, D) — the FULL per-layer KV
    arena, untouched; slot_map: (B,) arena slot of each batch row;
    lengths: (B,) valid cache entries (history + the new row, which the
    caller scatter-wrote before this call).

    Returns (B, Hq, D).  The arena slot axis is indexed inside the
    BlockSpec index maps via scalar prefetch, so only ``lengths[b]``
    cache rows per sequence move HBM→VMEM — never whole slots and never
    slots the batch doesn't own.

    ``window``: sliding-window width.  The arena is then a ROLLING cache
    (slot depth S = window + margin, written modularly at position % S):
    block iteration clamps to the last ceil(min(lengths, S)/block_k)
    valid blocks, slot positions are reconstructed modularly, and only
    keys inside the query's window survive the mask — O(min(cached,
    window)) HBM rows per generated token instead of O(cached).
    """
    s = k.shape[1]
    block_k = _largest_divisor(s, block_k)
    nk = s // block_k
    # windowed form: the in-window slots are a cyclic contiguous range
    # of ≤ window rows, so the kv grid axis shrinks to the blocks that
    # range can touch — the walk starts at the oldest in-window slot's
    # block and wraps modularly (see kv_map/_arena_kernel)
    nk_iter = nk if window is None else min(nk, (window - 1) // block_k + 2)

    def kv_map(bb, ki, slot_ref, len_ref):
        # clamp past-the-length blocks to the last valid one: a repeated
        # block index is not re-fetched, so invalid blocks cost no DMA.
        if window is None:
            last = jnp.maximum(len_ref[bb] - 1, 0) // block_k
            return (slot_ref[bb], jnp.minimum(ki, last), 0, 0)
        kvl = len_ref[bb]
        n_valid = jnp.minimum(kvl, s)
        w_eff = jnp.minimum(window, kvl)
        s0 = (kvl - w_eff) % s          # oldest in-window slot
        phys = (s0 // block_k + ki) % nk
        # pre-wraparound (kvl < s) the walk cannot wrap, so clamping to
        # the last valid block only retargets blocks the kernel skips
        last = jnp.maximum(n_valid - 1, 0) // block_k
        return (slot_ref[bb], jnp.minimum(phys, last), 0, 0)

    return _arena_call(kv_map, slot_map, lengths, q, k, v, window=window,
                       depth=s, block_k=block_k, n_kv_blocks=nk_iter,
                       n_phys_blocks=nk, interpret=interpret)
