"""Core transformer layers: RMSNorm, RoPE, GQA attention, SwiGLU MLP.

Pure-functional JAX; parameters are plain nested dicts built through
:class:`ParamBuilder`, which records a parallel tree of logical sharding
axes consumed by ``distributed.sharding``.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain

# ----------------------------------------------------------------- params


class ParamBuilder:
    """Builds a param dict + a parallel logical-axes dict.

    ``abstract=True`` records ShapeDtypeStructs instead of materializing
    arrays — used by the dry-run to get (shapes, axes) with zero
    allocation and zero tracing.
    """

    def __init__(self, key: Optional[jax.Array], dtype=jnp.float32,
                 abstract: bool = False):
        self._key = key
        self.dtype = dtype
        self.abstract = abstract
        self.params: Dict = {}
        self.axes: Dict = {}

    def _split(self) -> Optional[jax.Array]:
        if self.abstract:
            return None
        self._key, k = jax.random.split(self._key)
        return k

    def sub(self, name: str) -> "ParamBuilder":
        child = ParamBuilder(self._split(), self.dtype, self.abstract)
        self.params[name] = child.params
        self.axes[name] = child.axes
        return child

    def _store(self, name, shape, axes, make):
        if self.abstract:
            self.params[name] = jax.ShapeDtypeStruct(tuple(shape), self.dtype)
        else:
            self.params[name] = make()
        self.axes[name] = axes

    def dense(self, name: str, shape: Tuple[int, ...], axes: Tuple[Optional[str], ...],
              scale: Optional[float] = None):
        fan_in = shape[0] if len(shape) > 1 else shape[-1]
        scale = scale if scale is not None else fan_in ** -0.5
        self._store(name, shape, axes, lambda: (
            scale * jax.random.normal(self._split(), shape)).astype(self.dtype))

    def zeros(self, name: str, shape, axes):
        self._store(name, shape, axes, lambda: jnp.zeros(shape, self.dtype))

    def ones(self, name: str, shape, axes):
        self._store(name, shape, axes, lambda: jnp.ones(shape, self.dtype))

    def const(self, name: str, value, axes):
        self._store(name, jnp.shape(value), axes,
                    lambda: value.astype(self.dtype))


# ------------------------------------------------------------------ norms


def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * weight.astype(jnp.float32)).astype(dt)


# ------------------------------------------------------------------- rope


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, L, H, D); positions: (B, L) absolute token positions."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                              # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B, L, D/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# -------------------------------------------------------------- attention


def attention_mask(q_positions: jax.Array, kv_len: int, *, causal: bool,
                   window: Optional[int], kv_valid_len: Optional[jax.Array]) -> jax.Array:
    """Boolean mask (B, Lq, S): True = attend.

    q_positions: (B, Lq) absolute positions of query tokens; cache slot s
    holds absolute position s, so the causal condition ``s <= qp`` also
    excludes unwritten (junk) slots for ragged cached batches.
    kv_valid_len: (B,) valid-entry count — only needed for non-causal
    (encoder) padded batches.
    """
    kv_pos = jnp.arange(kv_len)[None, None, :]                # (1,1,S)
    qp = q_positions[:, :, None]                              # (B,Lq,1)
    mask = jnp.ones(qp.shape[:2] + (kv_len,), dtype=bool)
    if causal:
        mask = mask & (kv_pos <= qp)
    if window is not None:
        mask = mask & (kv_pos > qp - window)
    if kv_valid_len is not None:
        mask = mask & (kv_pos < kv_valid_len[:, None, None])
    return mask


def rolling_mask(q_positions: jax.Array, window: int) -> jax.Array:
    """Mask for a rolling (modular) SWA cache: slot s valid iff
    s < min(pos+1, window).  Decode-oriented (every valid slot is past)."""
    slots = jnp.arange(window)[None, None, :]
    limit = jnp.minimum(q_positions[:, :, None] + 1, window)
    return slots < limit


def gqa_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                  mask: jax.Array) -> jax.Array:
    """Grouped-query attention without materializing repeated KV.

    q: (B, Lq, Hq, D); k,v: (B, S, Hkv, D); mask: (B, Lq, S) bool.
    Returns (B, Lq, Hq, D).  Softmax in fp32.
    """
    b, lq, hq, d = q.shape
    hkv = k.shape[2]
    rep = hq // hkv
    qg = q.reshape(b, lq, hkv, rep, d)
    scores = jnp.einsum("blgrd,bsgd->bglrs", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) / jnp.sqrt(d).astype(jnp.float32)
    scores = jnp.where(mask[:, None, :, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bglrs,bsgd->blgrd", probs, v.astype(jnp.float32))
    return out.reshape(b, lq, hq, d).astype(q.dtype)


ATTN_Q_CHUNK = 1024


def attention_core(q: jax.Array, keys: jax.Array, vals: jax.Array,
                   q_positions: jax.Array, *, causal: bool,
                   window: Optional[int],
                   kv_valid_len: Optional[jax.Array],
                   mask_override: Optional[jax.Array] = None,
                   q_chunk: int = ATTN_Q_CHUNK) -> jax.Array:
    """Attention with q-chunking for long sequences (XLA-level flash):
    the (Lq × S) score matrix is never materialized beyond one q-chunk —
    essential for 32k+ prefills, where full scores are O(10 GB)/device.
    The chunk body is checkpointed so train backward recomputes scores.
    """
    b, lq, hq, d = q.shape
    s = keys.shape[1]
    if mask_override is not None or lq <= q_chunk:
        if mask_override is None:
            mask_override = attention_mask(q_positions, s, causal=causal,
                                           window=window,
                                           kv_valid_len=kv_valid_len)
        return gqa_attention(q, keys, vals, mask_override)

    pad = (-lq) % q_chunk
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        q_positions = jnp.pad(q_positions, ((0, 0), (0, pad)))
    nc = q.shape[1] // q_chunk
    qs = q.reshape(b, nc, q_chunk, hq, d).swapaxes(0, 1)      # (nc, B, qc, H, D)
    ps = q_positions.reshape(b, nc, q_chunk).swapaxes(0, 1)

    @jax.checkpoint
    def chunk(carry, xs):
        qc, pc = xs
        mask = attention_mask(pc, s, causal=causal, window=window,
                              kv_valid_len=kv_valid_len)
        return carry, gqa_attention(qc, keys, vals, mask)

    _, out = jax.lax.scan(chunk, (), (qs, ps))
    out = out.swapaxes(0, 1).reshape(b, nc * q_chunk, hq, d)
    return out[:, :lq]


def attention_layer(p: Dict, x: jax.Array, *, cfg, positions: jax.Array,
                    kv: Optional[Tuple[jax.Array, jax.Array]] = None,
                    kv_valid_len: Optional[jax.Array] = None,
                    cache_write_fn=None,
                    mask_override: Optional[jax.Array] = None,
                    dense_cache_write: bool = False,
                    ) -> Tuple[jax.Array, Optional[Tuple]]:
    """One attention mixer.

    Without a cache (train / first prefill): self-attention over x.
    With ``kv=(K, V)`` cache arrays of shape (B, S, Hkv, D): new tokens are
    written at ``positions`` (re-prefill / decode) and attention runs over
    the full cache.

    Returns (output, updated_kv or None).
    """
    b, l, _ = x.shape
    hd = cfg.hdim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    q = q.reshape(b, l, cfg.num_heads, hd)
    k = k.reshape(b, l, cfg.num_kv_heads, hd)
    v = v.reshape(b, l, cfg.num_kv_heads, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if cfg.causal:  # encoder-only models use absolute (no) rope here
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    q = constrain(q, "batch", "seq", "heads", "head_dim")

    updated = None
    if kv is None:
        keys, vals = k, v
    elif dense_cache_write:
        # fresh full prefill covering the whole cache (L == S): the
        # "write" is a pure layout change (batch-sharded compute KV →
        # cache sharding), avoiding the scatter XLA can only partition
        # by full rematerialization.  SWA rolling caches (S == window < L)
        # keep the last `window` tokens — position p lands in slot
        # p % window, and the tail slice is exactly slot-aligned.
        s_cache = kv[0].shape[1]
        assert s_cache == l or l % s_cache == 0, (kv[0].shape, l)
        ck = constrain(k[:, l - s_cache:].astype(kv[0].dtype),
                       "batch", "cache_seq", "kv_heads", "head_dim")
        cv = constrain(v[:, l - s_cache:].astype(kv[1].dtype),
                       "batch", "cache_seq", "kv_heads", "head_dim")
        updated = (ck, cv)
        keys, vals = k, v
    else:
        ck, cv = kv
        if cache_write_fn is None:
            cache_write_fn = write_kv_cache
        ck = cache_write_fn(ck, k, positions)
        cv = cache_write_fn(cv, v, positions)
        updated = (ck, cv)
        keys, vals = ck, cv

    out = attention_core(q, keys, vals, positions, causal=cfg.causal,
                         window=cfg.sliding_window,
                         kv_valid_len=kv_valid_len if kv is not None else None,
                         mask_override=mask_override)
    out = out.reshape(b, l, cfg.num_heads * hd)
    out = out @ p["wo"]
    return constrain(out, "batch", "seq", "embed_act"), updated


def packed_attention_layer(p: Dict, x: jax.Array, *, cfg,
                           positions: jax.Array, seg_ids: jax.Array,
                           cu_seqlens: jax.Array, q_offsets: jax.Array,
                           kv_lengths: jax.Array,
                           kv: Tuple[jax.Array, jax.Array],
                           ) -> Tuple[jax.Array, Tuple]:
    """Attention over a packed flat token stream (padding-free prefill).

    x: (T, d) — the concatenated new tokens of every sequence in the
    batch; sequence i owns rows [cu_seqlens[i], cu_seqlens[i+1]).
    positions: (T,) absolute position of each token in ITS sequence
    (history offset + local index); seg_ids: (T,) cache row each token's
    KV is written to; kv: (K, V) caches of shape (B, S, Hkv, D).

    New KV is scatter-written at (seg_ids, positions), then the ragged
    kernel attends each row to its own sequence's cache only.  Returns
    (out (T, d), updated (K, V)).
    """
    from repro.kernels import ops as kernel_ops

    t = x.shape[0]
    hd = cfg.hdim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    q = q.reshape(t, cfg.num_heads, hd)
    k = k.reshape(t, cfg.num_kv_heads, hd)
    v = v.reshape(t, cfg.num_kv_heads, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    q = apply_rope(q[None], positions[None], cfg.rope_theta)[0]
    k = apply_rope(k[None], positions[None], cfg.rope_theta)[0]

    ck = kv[0].at[seg_ids, positions].set(k.astype(kv[0].dtype))
    cv = kv[1].at[seg_ids, positions].set(v.astype(kv[1].dtype))

    out = kernel_ops.ragged_mha(q, ck, cv, cu_seqlens, q_offsets, kv_lengths,
                                causal=cfg.causal)
    out = out.reshape(t, cfg.num_heads * hd) @ p["wo"]
    return out, (ck, cv)


def packed_arena_attention_layer(p: Dict, x: jax.Array, *, cfg,
                                 positions: jax.Array, seg_slots: jax.Array,
                                 slot_map: jax.Array,
                                 cu_seqlens: jax.Array, q_offsets: jax.Array,
                                 kv_lengths: jax.Array,
                                 kv: Tuple[jax.Array, jax.Array],
                                 window: Optional[int] = None,
                                 ) -> Tuple[jax.Array, Tuple]:
    """Attention over a packed flat stream, arena-resident (DESIGN.md §6).

    x: (T, d) — the concatenated new tokens of every segment in the
    step; kv: (K, V) FULL arena buffers of shape (N_slots, S_max, Hkv,
    D); positions: (T,) absolute position of each token in ITS sequence
    (tail rows park at S_max − 1); seg_slots: (T,) arena slot each
    token's KV is written to (tail rows reuse a live slot but write at
    the park position — the scratch row, never live data); slot_map:
    (B,) arena slot per segment for the kernel's KV routing.

    The new KV rows are scatter-written at (seg_slots, positions) —
    O(T) rows, in place under buffer donation — and the arena-resident
    ragged kernel attends each stream row to its own segment's valid
    cache prefix only.  No whole slots are gathered or scattered.
    Returns (out (T, d), updated (K, V) arenas).

    ``window``: sliding-window width (DESIGN.md §7).  The arena slot is
    then window-deep (depth = window + margin < S_max) and the new KV
    rows are ROLLING (modular) writes at position % depth — the
    wraparound overwrites exactly the positions that fell out of every
    query's window, provided depth ≥ window + segment_len − 1 (the
    packing layer enforces segment_len ≤ margin + 1).  Tail rows must
    then park in a dedicated SCRATCH slot (there is no spare row in a
    rolling slot — every row cycles live).  The kernel masks each query
    to (qpos − window, qpos], streaming O(min(cached, window)) rows.
    """
    from repro.kernels import ops as kernel_ops

    t = x.shape[0]
    hd = cfg.hdim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    q = q.reshape(t, cfg.num_heads, hd)
    k = k.reshape(t, cfg.num_kv_heads, hd)
    v = v.reshape(t, cfg.num_kv_heads, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    q = apply_rope(q[None], positions[None], cfg.rope_theta)[0]
    k = apply_rope(k[None], positions[None], cfg.rope_theta)[0]

    write_pos = positions if window is None else positions % kv[0].shape[1]
    ck = kv[0].at[seg_slots, write_pos].set(k.astype(kv[0].dtype))
    cv = kv[1].at[seg_slots, write_pos].set(v.astype(kv[1].dtype))

    out = kernel_ops.ragged_mha_arena(q, ck, cv, slot_map, cu_seqlens,
                                      q_offsets, kv_lengths,
                                      causal=cfg.causal, window=window)
    out = out.reshape(t, cfg.num_heads * hd) @ p["wo"]
    return out, (ck, cv)


def packed_paged_attention_layer(p: Dict, x: jax.Array, *, cfg,
                                 positions: jax.Array,
                                 token_pages: jax.Array,
                                 token_offs: jax.Array,
                                 page_table: jax.Array,
                                 cu_seqlens: jax.Array, q_offsets: jax.Array,
                                 kv_lengths: jax.Array,
                                 kv: Tuple[jax.Array, jax.Array],
                                 window: Optional[int] = None,
                                 ) -> Tuple[jax.Array, Tuple]:
    """Attention over a packed flat stream, PAGED (DESIGN.md §8).

    The paged sibling of :func:`packed_arena_attention_layer`: kv are
    (K, V) page POOLS of shape (N_pages, page_size, Hkv, D) and each
    segment's cache is the ordered page list in its row of
    ``page_table`` (B, P_max) — so pages can be shared across segments
    (radix prefix reuse, COW forks).  positions: (T,) absolute position
    of each token in ITS sequence (rope + causal masking);
    token_pages/token_offs: (T,) physical (page, offset) each token's
    new KV is scatter-written to — pad/tail rows target the reserved
    scratch page at offset page_size − 1, never a live page.

    ``window``: sliding-window width (DESIGN.md §12).  The page table
    is then a RING over its P_max entries: position p lives on ring
    page (p // ps) % P_max — the engine computes token_pages through
    that ring, so the write below is already modular and only the
    kernel mask changes here.

    The write is O(T) rows in place under donation; the paged ragged
    kernel then attends each stream row through its segment's page
    table.  Returns (out (T, d), updated (K, V) pools).
    """
    from repro.kernels import ops as kernel_ops

    t = x.shape[0]
    hd = cfg.hdim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    q = q.reshape(t, cfg.num_heads, hd)
    k = k.reshape(t, cfg.num_kv_heads, hd)
    v = v.reshape(t, cfg.num_kv_heads, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    q = apply_rope(q[None], positions[None], cfg.rope_theta)[0]
    k = apply_rope(k[None], positions[None], cfg.rope_theta)[0]

    with jax.named_scope("laps/kv_write"):
        ck = kv[0].at[token_pages, token_offs].set(k.astype(kv[0].dtype))
        cv = kv[1].at[token_pages, token_offs].set(v.astype(kv[1].dtype))

    out = kernel_ops.ragged_mha_paged(q, ck, cv, page_table, cu_seqlens,
                                      q_offsets, kv_lengths,
                                      causal=cfg.causal, window=window)
    out = out.reshape(t, cfg.num_heads * hd) @ p["wo"]
    return out, (ck, cv)


def paged_decode_layer(p: Dict, x: jax.Array, *, cfg,
                       positions: jax.Array,
                       write_pages: jax.Array, write_offs: jax.Array,
                       page_table: jax.Array, kv_lengths: jax.Array,
                       kv: Tuple[jax.Array, jax.Array],
                       window: Optional[int] = None,
                       ) -> Tuple[jax.Array, Tuple]:
    """Attention for one PAGED decode tick (DESIGN.md §8).

    The paged sibling of :func:`arena_decode_layer`: kv are (K, V) page
    pools (N_pages, page_size, Hkv, D) and each row's cache is its page
    list in ``page_table`` (B, P_max).  positions: (B,) absolute
    position of the new token (rope); write_pages/write_offs: (B,)
    physical (page, offset) its KV lands in — pad rows target the
    scratch page at offset page_size − 1; kv_lengths: (B,) valid cache
    entries including the new row.  ``window`` selects the ring-table
    form (DESIGN.md §12) — write_pages already walk the ring, computed
    by the engine.  Returns (out (B, d), updated pools).
    """
    from repro.kernels import ops as kernel_ops

    b = x.shape[0]
    hd = cfg.hdim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    q = q.reshape(b, cfg.num_heads, hd)
    k = k.reshape(b, cfg.num_kv_heads, hd)
    v = v.reshape(b, cfg.num_kv_heads, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    q = apply_rope(q[:, None], positions[:, None], cfg.rope_theta)[:, 0]
    k = apply_rope(k[:, None], positions[:, None], cfg.rope_theta)[:, 0]

    with jax.named_scope("laps/kv_write"):
        ck = kv[0].at[write_pages, write_offs].set(k.astype(kv[0].dtype))
        cv = kv[1].at[write_pages, write_offs].set(v.astype(kv[1].dtype))

    out = kernel_ops.decode_paged(q, ck, cv, page_table, kv_lengths,
                                  window=window)
    out = out.reshape(b, cfg.num_heads * hd) @ p["wo"]
    return out, (ck, cv)


def arena_decode_layer(p: Dict, x: jax.Array, *, cfg,
                       slot_map: jax.Array, positions: jax.Array,
                       kv_lengths: jax.Array,
                       kv: Tuple[jax.Array, jax.Array],
                       window: Optional[int] = None,
                       ) -> Tuple[jax.Array, Tuple]:
    """Attention for one arena-resident decode tick.

    x: (B, d) — ONE new token per batch row; kv: (K, V) FULL arena
    buffers of shape (N_slots, S, Hkv, D); slot_map: (B,) arena slot of
    each row; positions: (B,) absolute write position of the new token
    (its cached history length; pad rows park at S-1); kv_lengths: (B,)
    valid cache entries including the new row.

    The single new KV row is scatter-written at (slot_map, positions) —
    O(B) rows, in place under buffer donation — and the arena-resident
    kernel attends each row over its own valid prefix only.  No whole
    slots are gathered or scattered.  Returns (out (B, d), updated
    (K, V) arenas).

    ``window``: sliding-window width (DESIGN.md §7).  The arena slot is
    then a window-deep ROLLING cache written modularly at position %
    depth (pad rows must point at the scratch slot — every row of a
    rolling slot cycles live), and the kernel streams O(min(cached,
    window)) rows per generated token.
    """
    from repro.kernels import ops as kernel_ops

    b = x.shape[0]
    hd = cfg.hdim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    q = q.reshape(b, cfg.num_heads, hd)
    k = k.reshape(b, cfg.num_kv_heads, hd)
    v = v.reshape(b, cfg.num_kv_heads, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    q = apply_rope(q[:, None], positions[:, None], cfg.rope_theta)[:, 0]
    k = apply_rope(k[:, None], positions[:, None], cfg.rope_theta)[:, 0]

    write_pos = positions if window is None else positions % kv[0].shape[1]
    ck = kv[0].at[slot_map, write_pos].set(k.astype(kv[0].dtype))
    cv = kv[1].at[slot_map, write_pos].set(v.astype(kv[1].dtype))

    out = kernel_ops.decode_arena(q, ck, cv, slot_map, kv_lengths,
                                  window=window)
    out = out.reshape(b, cfg.num_heads * hd) @ p["wo"]
    return out, (ck, cv)


def write_kv_cache(cache: jax.Array, new: jax.Array, positions: jax.Array) -> jax.Array:
    """Scatter new KV rows into the cache at per-token absolute positions.

    cache: (B, S, Hkv, D); new: (B, L, Hkv, D); positions: (B, L).
    """
    def one(c, n, pos):
        return c.at[pos].set(n.astype(c.dtype))
    return jax.vmap(one)(cache, new, positions)


def init_attention(pb: ParamBuilder, cfg) -> None:
    hd = cfg.hdim
    qd, kvd = cfg.num_heads * hd, cfg.num_kv_heads * hd
    pb.dense("wq", (cfg.d_model, qd), ("embed", "heads"))
    pb.dense("wk", (cfg.d_model, kvd), ("embed", "kv_heads"))
    pb.dense("wv", (cfg.d_model, kvd), ("embed", "kv_heads"))
    pb.dense("wo", (qd, cfg.d_model), ("heads", "embed"))
    if cfg.qkv_bias:
        pb.zeros("bq", (qd,), ("heads",))
        pb.zeros("bk", (kvd,), ("kv_heads",))
        pb.zeros("bv", (kvd,), ("kv_heads",))
    if cfg.qk_norm:
        pb.ones("q_norm", (hd,), (None,))
        pb.ones("k_norm", (hd,), (None,))


# ------------------------------------------------------------------- mlp


def swiglu(p: Dict, x: jax.Array) -> jax.Array:
    h = jax.nn.silu(x @ p["wg"]) * (x @ p["wi"])
    h = constrain(h, "batch", "seq", "mlp")
    return constrain(h @ p["wo"], "batch", "seq", "embed_act")


def init_mlp(pb: ParamBuilder, d_model: int, d_ff: int) -> None:
    pb.dense("wg", (d_model, d_ff), ("embed", "mlp"))
    pb.dense("wi", (d_model, d_ff), ("embed", "mlp"))
    pb.dense("wo", (d_ff, d_model), ("mlp", "embed"))
