"""Ragged (padding-free) flash attention for packed prefill batches.

The packed short-prefill path concatenates every request's new tokens
into ONE flat token stream of a bucketed total length T — no per-request
length padding, no (L, B) shape cross-product.  This kernel is the
attention core of that path:

  * queries arrive flat: ``q (T, Hq, D)``; sequence i owns the rows
    ``[cu_seqlens[i], cu_seqlens[i+1])`` of the stream;
  * KV stays per-sequence: ``k/v (B, S, Hkv, D)`` — the gathered arena
    rows with this step's new KV already written at positions
    ``[q_offsets[i], q_offsets[i] + len_i)``;
  * ``q_offsets (B,)`` is the re-prefill history length (absolute
    position of each sequence's first new token), ``kv_lengths (B,)``
    the total valid cache entries (history + new);
  * grid = (Hq, n_q_blocks, B, n_kv_blocks) with the (B, kv) axes
    sequential so the online-softmax accumulator for one q block scans
    every sequence's cache in VMEM scratch;
  * cu_seqlens / q_offsets / kv_lengths ride scalar prefetch (SMEM), so
    block skipping is decided before any VMEM traffic: a (q_block, seq)
    pair is skipped unless the q block intersects the sequence's row
    range, and kv blocks past the causal frontier or the valid cache
    length are skipped like the dense kernel's.

Rows of the flat stream beyond ``cu_seqlens[-1]`` (bucket tail padding)
belong to no sequence: they accumulate nothing and produce zeros.
Masking at sequence boundaries is exact — a q block straddling two
sequences contributes each row only to its own sequence's softmax.

Two entry points share the kernel math:

  * :func:`ragged_prefill_attn` — the batch-cache form: k/v are
    (B, S, Hkv, D) rows already gathered out of the arena;
  * :func:`ragged_prefill_arena` — the arena-resident form: k/v are the
    WHOLE KV arena (N_slots, S_max, Hkv, D) and a scalar-prefetched
    ``slot_map (B,)`` routes each segment's KV blocks through its arena
    slot inside the BlockSpec index maps.  KV blocks past a segment's
    valid length clamp to the last valid block (a repeated block index
    skips the DMA), so a packed prefill / mixed / chunk tick streams
    only the valid cache prefixes of its live sessions — no whole-slot
    gather before the step and no scatter after it, killing the
    O(b_max · S_max) HBM round-trip of the gathered path.  Blocks read
    (1, block_k, Hkv, D) straight from the arena's native layout — a
    transpose would copy the arena and defeat the in-place point — and
    carry every KV head, since a block's two trailing dims must match
    the TPU tile or the array's own; one program serves all query heads.

Decode segments (continuous batching) need no special path: a length-1
segment with ``q_offsets[i] = H`` and ``kv_lengths[i] = H + 1`` attends
over exactly ``H + 1`` keys — the causal frontier check caps the kv
scan at ``offset + 1`` blocks for that row, and kv blocks past the
valid cache length are skipped before any VMEM traffic, so a decode
row costs O(H) kv reads, not O(S_max).

The gathered form reads the kv head as h // rep in the index maps; the
arena and paged forms loop over heads inside the program.  Accumulation
is fp32 via ``preferred_element_type``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attn import _largest_divisor

NEG_INF = -1e30
LANES = 128


def _kernel(cu_ref, off_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref, *, scale: float, causal: bool,
            block_q: int, block_k: int, n_seqs: int, n_kv_blocks: int):
    qi = pl.program_id(1)
    b = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(jnp.logical_and(b == 0, ki == 0))
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    seg_start = cu_ref[b]
    seg_end = cu_ref[b + 1]
    offset = off_ref[b]
    kv_len = len_ref[b]

    q_start = qi * block_q                 # flat row of this q block
    k_start = ki * block_k

    # block-level skip: q block must own rows of sequence b, the kv
    # block must hold valid cache entries, and (causal) must not lie
    # entirely after the block's last query position
    run = jnp.logical_and(q_start < seg_end, q_start + block_q > seg_start)
    run = jnp.logical_and(run, k_start < kv_len)
    if causal:
        last_row = jnp.minimum(seg_end, q_start + block_q) - 1
        max_qpos = offset + last_row - seg_start
        run = jnp.logical_and(run, k_start <= max_qpos)

    @pl.when(run)
    def _compute():
        q = q_ref[0]                                           # (bq, D)
        k = k_ref[0, 0]                                        # (bk, D)
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale        # (bq, bk)
        rows = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)                  # flat row ids
        kpos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mine = jnp.logical_and(rows >= seg_start, rows < seg_end)
        qpos = offset + rows - seg_start
        mask = jnp.logical_and(mine, kpos < kv_len)
        if causal:
            mask = jnp.logical_and(mask, kpos <= qpos)
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

    @pl.when(jnp.logical_and(b == n_seqs - 1, ki == n_kv_blocks - 1))
    def _finish():
        l = l_ref[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)     # rows owned by no sequence
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "interpret"))
def ragged_prefill_attn(q: jax.Array, k: jax.Array, v: jax.Array,
                        cu_seqlens: jax.Array,
                        q_offsets: Optional[jax.Array] = None,
                        kv_lengths: Optional[jax.Array] = None, *,
                        causal: bool = True,
                        block_q: int = 128, block_k: int = 128,
                        interpret: bool = True) -> jax.Array:
    """q: (T, Hq, D) packed stream; k, v: (B, S, Hkv, D).  Returns
    (T, Hq, D) with zeros on rows past ``cu_seqlens[-1]``.

    cu_seqlens: (B+1,) int32 row offsets of each sequence in the stream;
    q_offsets: (B,) history length per sequence (re-prefill);
    kv_lengths: (B,) valid KV entries per sequence (defaults to S).
    """
    t, hq, d = q.shape
    b, s, hkv = k.shape[0], k.shape[1], k.shape[2]
    rep = hq // hkv
    if q_offsets is None:
        q_offsets = jnp.zeros((b,), jnp.int32)
    if kv_lengths is None:
        kv_lengths = jnp.full((b,), s, jnp.int32)

    block_q = min(block_q, max(t, 1))
    block_k = min(block_k, s)
    t_pad = -(-t // block_q) * block_q
    s_pad = -(-s // block_k) * block_k
    qt = jnp.moveaxis(q, 1, 0)                                 # (Hq, T, D)
    kt = jnp.moveaxis(k, 2, 1)                                 # (B, Hkv, S, D)
    vt = jnp.moveaxis(v, 2, 1)
    if t_pad != t:
        qt = jnp.pad(qt, ((0, 0), (0, t_pad - t), (0, 0)))
    if s_pad != s:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, s_pad - s), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, s_pad - s), (0, 0)))
    nq, nk = t_pad // block_q, s_pad // block_k

    kern = functools.partial(
        _kernel, scale=d ** -0.5, causal=causal,
        block_q=block_q, block_k=block_k, n_seqs=b, n_kv_blocks=nk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(hq, nq, b, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda h, qi, bb, ki, *_: (h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda h, qi, bb, ki, *_: (bb, h // rep, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda h, qi, bb, ki, *_: (bb, h // rep, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d),
                               lambda h, qi, bb, ki, *_: (h, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((hq, t_pad, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel",
                                 "arbitrary", "arbitrary")),
        interpret=interpret,
    )(cu_seqlens.astype(jnp.int32), q_offsets.astype(jnp.int32),
      kv_lengths.astype(jnp.int32), qt, kt, vt)
    return jnp.moveaxis(out[:, :t], 0, 1)


def _arena_kernel(tbl_ref, cu_ref, off_ref, len_ref, q_ref, k_ref, v_ref,
                  o_ref, m_ref, l_ref, acc_ref, *, scale: float, causal: bool,
                  window: Optional[int], depth: int, rep: int,
                  block_q: int, block_k: int, n_seqs: int, n_kv_blocks: int):
    """One (q block, segment, kv block) program over ALL heads: the kv
    block carries every KV head, so each fetched block serves its whole
    GQA group of query heads (static loop over KV heads, ``fori_loop``
    over the group's query heads)."""
    del tbl_ref                      # consumed by the BlockSpec index maps
    qi = pl.program_id(0)
    b = pl.program_id(1)
    ki = pl.program_id(2)
    hkv = k_ref.shape[2]

    @pl.when(jnp.logical_and(b == 0, ki == 0))
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    seg_start = cu_ref[b]
    seg_end = cu_ref[b + 1]
    offset = off_ref[b]
    kv_len = len_ref[b]
    # rolling arenas hold the last min(kv_len, depth) positions; the
    # full-depth form has depth == S_max so n_valid == kv_len always
    n_valid = jnp.minimum(kv_len, depth) if window is not None else kv_len

    q_start = qi * block_q                 # flat row of this q block
    k_start = ki * block_k

    # block-level skip, identical to the gathered kernel's: the q block
    # must own rows of segment b, the kv block must hold valid cache
    # entries (clamped blocks re-read the last valid one and are skipped
    # here), and causally it must not lie past the block's last query.
    # The causal refinement assumes slot index == absolute position, so
    # it only applies to the non-rolling form.
    run = jnp.logical_and(q_start < seg_end, q_start + block_q > seg_start)
    run = jnp.logical_and(run, k_start < n_valid)
    if causal and window is None:
        last_row = jnp.minimum(seg_end, q_start + block_q) - 1
        max_qpos = offset + last_row - seg_start
        run = jnp.logical_and(run, k_start <= max_qpos)

    @pl.when(run)
    def _compute():
        rows = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)                  # flat row ids
        slot = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mine = jnp.logical_and(rows >= seg_start, rows < seg_end)
        qpos = offset + rows - seg_start
        if window is None:
            kpos = slot                    # full-depth: slot == position
        else:
            # rolling slot s holds the newest position < kv_len congruent
            # to s mod depth: kpos = s + depth·⌊(kv_len−1−s)/depth⌋
            wraps = jnp.maximum(kv_len - 1 - slot, 0) // depth
            kpos = slot + wraps * depth
        mask = jnp.logical_and(mine, slot < n_valid)
        if causal:
            mask = jnp.logical_and(mask, kpos <= qpos)
        if window is not None:
            mask = jnp.logical_and(mask, kpos > qpos - window)

        for g in range(hkv):
            k = k_ref[0, :, g, :]                              # (bk, D)
            v = v_ref[0, :, g, :]

            def head(r, carry, k=k, v=v, g=g):
                h = g * rep + r
                s = jax.lax.dot_general(
                    q_ref[h], k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale  # (bq, bk)
                s = jnp.where(mask, s, NEG_INF)
                m_prev = m_ref[h][:, :1]                       # (bq, 1)
                l_prev = l_ref[h][:, :1]
                m_new = jnp.maximum(m_prev,
                                    jnp.max(s, axis=-1, keepdims=True))
                p = jnp.exp(s - m_new)
                p = jnp.where(mask, p, 0.0)
                alpha = jnp.exp(m_prev - m_new)                # (bq, 1)
                l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
                pv = jax.lax.dot_general(
                    p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)        # (bq, D)
                acc_ref[h] = acc_ref[h] * alpha + pv
                m_ref[h] = jnp.broadcast_to(m_new, m_ref.shape[1:])
                l_ref[h] = jnp.broadcast_to(l_new, l_ref.shape[1:])
                return carry

            jax.lax.fori_loop(0, rep, head, 0)

    @pl.when(jnp.logical_and(b == n_seqs - 1, ki == n_kv_blocks - 1))
    def _finish():
        l = l_ref[:, :, :1]
        l = jnp.where(l == 0.0, 1.0, l)     # rows owned by no segment
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)


def _arena_call(kv_map, table, cu_seqlens, q_offsets, kv_lengths, q, k, v,
                *, causal: bool, window: Optional[int], depth: int,
                block_q: int, block_k: int, n_kv_blocks: int,
                interpret: bool) -> jax.Array:
    """Shared pallas_call of the arena and paged forms.  Grid = (q
    block, segment, kv block); each program holds every query head of
    its q block and reads (1, block_k, Hkv, D) kv blocks, whose two
    trailing dims are the pool's own — the TPU tiling rule for a block's
    last two dims — so one DMA per block serves all heads."""
    t, hq, d = q.shape
    hkv = k.shape[2]
    b = table.shape[0]
    block_q = min(block_q, max(t, 1))
    t_pad = -(-t // block_q) * block_q
    qt = jnp.moveaxis(q, 1, 0)                                 # (Hq, T, D)
    if t_pad != t:
        qt = jnp.pad(qt, ((0, 0), (0, t_pad - t), (0, 0)))
    nq = t_pad // block_q

    kern = functools.partial(
        _arena_kernel, scale=d ** -0.5, causal=causal, window=window,
        depth=depth, rep=hq // hkv, block_q=block_q, block_k=block_k,
        n_seqs=b, n_kv_blocks=n_kv_blocks)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(nq, b, n_kv_blocks),
        in_specs=[
            pl.BlockSpec((hq, block_q, d), lambda qi, bb, ki, *_: (0, qi, 0)),
            pl.BlockSpec((1, block_k, hkv, d), kv_map),
            pl.BlockSpec((1, block_k, hkv, d), kv_map),
        ],
        out_specs=pl.BlockSpec((hq, block_q, d),
                               lambda qi, bb, ki, *_: (0, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((hq, block_q, LANES), jnp.float32),
            pltpu.VMEM((hq, block_q, LANES), jnp.float32),
            pltpu.VMEM((hq, block_q, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((hq, t_pad, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(table.astype(jnp.int32), cu_seqlens.astype(jnp.int32),
      q_offsets.astype(jnp.int32), kv_lengths.astype(jnp.int32), qt, k, v)
    return jnp.moveaxis(out[:, :t], 0, 1)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "interpret"))
def ragged_prefill_paged(q: jax.Array, k: jax.Array, v: jax.Array,
                         page_table: jax.Array, cu_seqlens: jax.Array,
                         q_offsets: Optional[jax.Array] = None,
                         kv_lengths: Optional[jax.Array] = None, *,
                         causal: bool = True, window: Optional[int] = None,
                         block_q: int = 128,
                         interpret: bool = True) -> jax.Array:
    """Paged ragged prefill flash attention.

    The paged generalization of :func:`ragged_prefill_arena`: instead of
    one contiguous arena slot per segment, each segment's KV lives on a
    list of fixed-size PAGES scattered anywhere in a shared pool, and a
    per-segment page table maps logical kv block → physical page.  Pages
    can therefore be SHARED between segments (radix-tree prefix reuse,
    COW forks) — the kernel neither knows nor cares: it reads whatever
    page the table names.

    q: (T, Hq, D) packed flat stream; k, v: (N_pages, page_size, Hkv, D)
    — the FULL page pools with this step's new KV already scatter-written
    at each token's (page, offset); page_table: (B, P_max) int32 physical
    page of each segment's logical page i (entries past the valid length
    may point anywhere live — they are clamped in the index map and never
    computed on); cu_seqlens: (B+1,) flat row offsets; q_offsets: (B,)
    history length per segment; kv_lengths: (B,) valid cache entries
    (history + new).

    Returns (T, Hq, D) with zeros on rows past ``cu_seqlens[-1]``.  One
    kv grid block == one page (block_k = page_size): logical page ki of
    segment b holds absolute positions [ki·ps, (ki+1)·ps), so the shared
    ``_arena_kernel`` math is reused verbatim with the page-id lookup
    replacing the slot-id lookup in the BlockSpec index map.  Pages past
    ``ceil(kv_lengths[b]/ps)`` clamp to the last valid page (a repeated
    block index skips the DMA), so a step streams only the valid pages
    of the segments it serves.

    ``window``: sliding-window width.  The page table is then a RING
    over its P_max entries (§7's rolling arena at page granularity):
    position p lives on logical ring page (p // ps) % P_max at offset
    p % ps, so the ring holds the last min(kv_lengths, ps·P_max)
    positions.  The shared ``_arena_kernel`` rolling math reconstructs
    each slot's absolute position modularly with depth = ps·P_max and
    masks to (qpos − window, qpos] — identical to
    :func:`ragged_prefill_arena`'s windowed form with the page-id
    lookup replacing the slot-id lookup.
    """
    ps = k.shape[1]
    b, p_max = page_table.shape
    if q_offsets is None:
        q_offsets = jnp.zeros((b,), jnp.int32)
    if kv_lengths is None:
        kv_lengths = jnp.full((b,), ps * p_max, jnp.int32)

    def kv_map(qi, bb, ki, pt_ref, cu_ref, off_ref, len_ref):
        # clamp past-the-length logical pages to the last valid one: a
        # repeated physical page is not re-fetched, so invalid pages
        # cost no DMA.  Ring tables have every page valid once
        # kv_len ≥ ps·P_max.
        n_valid = jnp.minimum(len_ref[bb], ps * p_max) \
            if window is not None else len_ref[bb]
        last = jnp.maximum(n_valid - 1, 0) // ps
        return (pt_ref[bb, jnp.minimum(ki, last)], 0, 0, 0)

    # the page IS the kv block
    return _arena_call(kv_map, page_table, cu_seqlens, q_offsets,
                       kv_lengths, q, k, v, causal=causal, window=window,
                       depth=ps * p_max, block_q=block_q, block_k=ps,
                       n_kv_blocks=p_max, interpret=interpret)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "interpret"))
def ragged_prefill_arena(q: jax.Array, k: jax.Array, v: jax.Array,
                         slot_map: jax.Array, cu_seqlens: jax.Array,
                         q_offsets: Optional[jax.Array] = None,
                         kv_lengths: Optional[jax.Array] = None, *,
                         causal: bool = True, window: Optional[int] = None,
                         block_q: int = 128, block_k: int = 128,
                         interpret: bool = True) -> jax.Array:
    """Arena-resident ragged prefill flash attention.

    q: (T, Hq, D) packed flat stream; k, v: (N_slots, S_max, Hkv, D) —
    the FULL per-layer KV arenas with this step's new KV already
    scatter-written at each token's (slot, position); slot_map: (B,)
    arena slot of each segment (pad segments point at any live slot —
    they own no stream rows, so the block is fetched at most once and
    never computed on); cu_seqlens: (B+1,) flat row offsets;
    q_offsets: (B,) history length per segment; kv_lengths: (B,) valid
    cache entries (history + new).

    Returns (T, Hq, D) with zeros on rows past ``cu_seqlens[-1]``.  The
    arena slot axis is indexed inside the BlockSpec index maps via
    scalar prefetch and kv blocks past ``kv_lengths[b]`` clamp to the
    last valid block, so one packed step streams only the valid cache
    prefixes of the segments it serves — never whole slots and never
    slots the step doesn't own.

    ``window``: sliding-window width.  The arena is then a ROLLING
    cache: its slot depth D (= k.shape[1]) is window + margin deep and
    holds the last min(kv_lengths, D) positions, written modularly at
    position % D by the layer.  KV block iteration clamps to the last
    ceil(min(kv_len, D)/block_k) valid blocks of the slot, the kernel
    reconstructs each slot's absolute position modularly, and the mask
    keeps only keys inside (qpos − window, qpos] — so a step streams
    O(min(cached, window) + margin) cache rows per segment, not
    O(S_max).  (The decode kernel tightens its grid to the window's
    own blocks; here a segment's queries span up to the whole valid
    range, so every valid block stays on the grid.)
    """
    s = k.shape[1]
    b = slot_map.shape[0]
    if q_offsets is None:
        q_offsets = jnp.zeros((b,), jnp.int32)
    if kv_lengths is None:
        kv_lengths = jnp.full((b,), s, jnp.int32)
    # the arena's S axis is never padded (padding would copy the arena)
    block_k = _largest_divisor(s, block_k)

    def kv_map(qi, bb, ki, slot_ref, cu_ref, off_ref, len_ref):
        # clamp past-the-length blocks to the last valid one: a repeated
        # block index is not re-fetched, so invalid blocks cost no DMA.
        # Rolling arenas have every slot row valid once kv_len ≥ depth.
        n_valid = jnp.minimum(len_ref[bb], s) if window is not None \
            else len_ref[bb]
        last = jnp.maximum(n_valid - 1, 0) // block_k
        return (slot_ref[bb], jnp.minimum(ki, last), 0, 0)

    return _arena_call(kv_map, slot_map, cu_seqlens, q_offsets, kv_lengths,
                       q, k, v, causal=causal, window=window, depth=s,
                       block_q=block_q, block_k=block_k,
                       n_kv_blocks=s // block_k, interpret=interpret)
