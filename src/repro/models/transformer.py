"""Full model assembly for every assigned architecture family.

Layers are stacked in *pattern groups*: the layer pattern of period ``p``
(dense: 1, jamba hybrid: 8) is unrolled inside a ``jax.lax.scan`` body and
parameters are stacked over the ``G = num_layers / p`` groups.  This keeps
HLO size O(pattern) instead of O(num_layers) — essential for dry-run
compile times at 32–64 layers — and gives the remat boundary used in
training (checkpoint per scan body).

Caches (KV for attention layers, (ssm, conv) state for mamba layers) are
pytrees stacked the same way, scanned through as xs/ys.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain, constrain_tree
from repro.models import mamba as mamba_mod
from repro.models.config import ModelConfig
from repro.models.layers import (ParamBuilder, arena_decode_layer,
                                 attention_layer, init_attention, init_mlp,
                                 packed_arena_attention_layer,
                                 packed_attention_layer, packed_paged_attention_layer,
                                 paged_decode_layer, rms_norm, swiglu,
                                 write_kv_cache)
from repro.models.moe import init_moe, moe_dense_reference, moe_layer


def pattern_period(cfg: ModelConfig) -> int:
    if cfg.family == "hybrid":
        import math
        return math.lcm(cfg.attn_layer_period, cfg.moe_layer_period)
    return 1


def num_groups(cfg: ModelConfig) -> int:
    p = pattern_period(cfg)
    assert cfg.num_layers % p == 0, (cfg.num_layers, p)
    return cfg.num_layers // p


# ------------------------------------------------------------------- init


def _init_one_layer(key, cfg: ModelConfig, j: int, abstract: bool = False):
    pb = ParamBuilder(key, cfg.np_dtype, abstract)
    pb.ones("ln1", (cfg.d_model,), (None,))
    mx = pb.sub("mixer")
    if cfg.layer_kind(j) == "attn":
        init_attention(mx, cfg)
    else:
        mamba_mod.init_mamba(mx, cfg)
    if cfg.family == "ssm":
        # mamba2 arch: no separate FFN (the block already mixes channels)
        pass
    else:
        pb.ones("ln2", (cfg.d_model,), (None,))
        ff = pb.sub("ffn")
        if cfg.layer_is_moe(j):
            init_moe(ff, cfg.d_model, cfg.moe_d_ff or cfg.d_ff, cfg.num_experts)
        else:
            init_mlp(ff, cfg.d_model, cfg.d_ff)
    return pb.params, pb.axes


def init_params(cfg: ModelConfig, key=None,
                abstract: bool = False) -> Tuple[Dict, Dict]:
    """Returns (params, logical_axes) with pattern-stacked blocks.

    abstract=True: ShapeDtypeStruct leaves, no allocation (dry-run).
    Otherwise the whole init runs as ONE jitted program that draws each
    stacked leaf directly (vmapped over the group keys), so no per-layer
    copy lives next to its stacked copy: peak device memory is about the
    size of the weights."""
    shapes, axes = _build_params(cfg, None, abstract=True)
    if abstract:
        return shapes, axes
    params = jax.jit(lambda k: _build_params(cfg, k, abstract=False)[0])(key)
    return params, axes


def _build_params(cfg: ModelConfig, key, abstract: bool) -> Tuple[Dict, Dict]:
    p = pattern_period(cfg)
    g = num_groups(cfg)
    keys = [None] * 3 if abstract else list(jax.random.split(key, 3))
    pb = ParamBuilder(keys[0], cfg.np_dtype, abstract)
    pb.dense("embed", (cfg.padded_vocab, cfg.d_model), ("vocab", "embed"),
             scale=0.02)
    group_keys = (None if abstract
                  else jax.random.split(keys[2], (p, g)))
    blocks, blocks_axes = [], []
    for j in range(p):
        lp, axes_j = _init_one_layer(None, cfg, j, abstract=True)
        if abstract:
            stacked = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct((g,) + s.shape, s.dtype), lp)
        else:
            stacked = jax.vmap(
                lambda k, j=j: _init_one_layer(k, cfg, j)[0])(group_keys[j])
        blocks.append(stacked)
        # leading scan dim is unsharded
        blocks_axes.append(jax.tree.map(
            lambda ax: (None,) + tuple(ax),
            axes_j, is_leaf=lambda x: isinstance(x, tuple)))
    pb.params["blocks"] = blocks
    pb.axes["blocks"] = blocks_axes
    pb.ones("final_norm", (cfg.d_model,), (None,))
    pb.dense("lm_head", (cfg.d_model, cfg.padded_vocab), ("embed", "vocab"),
             scale=cfg.d_model ** -0.5)
    return pb.params, pb.axes


def param_axes(cfg: ModelConfig) -> Dict:
    """Logical-axes tree without materializing params."""
    return init_params(cfg, abstract=True)[1]


def param_shapes(cfg: ModelConfig):
    return init_params(cfg, abstract=True)[0]


# ------------------------------------------------------------------ cache


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=None, swa_depth: Optional[int] = None) -> List[Any]:
    """Per-pattern-position cache, stacked over groups.

    attn position: {"k": (G,B,S,Hkv,D), "v": ...}
    ssm  position: {"ssm": (G,B,nh,hd,ds), "conv": (G,B,W-1,C)}

    swa_depth: attention-slot depth for sliding-window configs.  None
    keeps the legacy window-deep rolling cache (min(max_len, window));
    the serving arena passes window + margin (the §7 rolling arena,
    margin absorbing one step's writes before wraparound could alias)
    or max_len (the dense baseline, which masks the window instead of
    rolling).  Always capped at max_len.
    """
    dtype = dtype or cfg.np_dtype
    p = pattern_period(cfg)
    g = num_groups(cfg)
    caches: List[Any] = []
    for j in range(p):
        if cfg.layer_kind(j) == "attn":
            s = max_len
            if cfg.sliding_window is not None:
                s = min(max_len, swa_depth if swa_depth is not None
                        else cfg.sliding_window)
            # k and v must be DISTINCT buffers: donating an aliased pair
            # trips "attempt to donate the same buffer twice" in XLA
            shape = (g, batch, s, cfg.num_kv_heads, cfg.hdim)
            caches.append({"k": jnp.zeros(shape, dtype),
                           "v": jnp.zeros(shape, dtype)})
        else:
            ssm, conv = mamba_mod.init_mamba_cache(cfg, batch, dtype)
            caches.append({"ssm": jnp.broadcast_to(ssm, (g,) + ssm.shape),
                           "conv": jnp.broadcast_to(conv, (g,) + conv.shape)})
    return caches


def cache_logical_axes(cfg: ModelConfig) -> List[Any]:
    """Logical axes for the cache pytree (serve rules shard KV seq)."""
    p = pattern_period(cfg)
    out: List[Any] = []
    for j in range(p):
        if cfg.layer_kind(j) == "attn":
            ax = (None, "batch", "cache_seq", "kv_heads", "head_dim")
            out.append({"k": ax, "v": ax})
        else:
            out.append({"ssm": (None, "batch", "ssm_heads", None, None),
                        "conv": (None, "batch", None, "conv_ch")})
    return out


# ---------------------------------------------------------------- forward


def _block(cfg: ModelConfig, j: int, lp: Dict, x: jax.Array, cache, *,
           positions, seq_valid_len, kv_valid_len, decode: bool,
           rolling: bool, dense_write: bool = False):
    """One pattern-position layer. Returns (x, new_cache, aux)."""
    aux = jnp.zeros((), jnp.float32)
    h = rms_norm(x, lp["ln1"], cfg.norm_eps)
    if cfg.layer_kind(j) == "attn":
        kv = (cache["k"], cache["v"]) if cache is not None else None
        if rolling and kv is not None:
            window = kv[0].shape[1]
            wfn = functools.partial(_rolling_write, window=window)
            from repro.models.layers import rolling_mask
            mix, upd = attention_layer(
                lp["mixer"], h, cfg=cfg, positions=positions, kv=kv,
                kv_valid_len=None, cache_write_fn=wfn,
                mask_override=rolling_mask(positions, window))
        else:
            mix, upd = attention_layer(
                lp["mixer"], h, cfg=cfg, positions=positions, kv=kv,
                kv_valid_len=kv_valid_len, dense_cache_write=dense_write)
        new_cache = {"k": upd[0], "v": upd[1]} if upd is not None else None
    else:
        cc = (cache["ssm"], cache["conv"]) if cache is not None else None
        mix, upd = mamba_mod.mamba_layer(lp["mixer"], h, cfg=cfg, cache=cc,
                                         decode=decode,
                                         valid_len=seq_valid_len)
        new_cache = {"ssm": upd[0], "conv": upd[1]} if upd is not None else None
    x = x + mix
    if cfg.family != "ssm":
        x, a = _ffn(cfg, j, lp, x)
        aux = aux + a
    return x, new_cache, aux


def _ffn(cfg: ModelConfig, j: int, lp: Dict, x: jax.Array
         ) -> Tuple[jax.Array, jax.Array]:
    """Post-mixer FFN residual for one layer.  x: (B, L, d)."""
    aux = jnp.zeros((), jnp.float32)
    h = rms_norm(x, lp["ln2"], cfg.norm_eps)
    if cfg.layer_is_moe(j):
        if cfg.num_experts <= 8 and h.shape[0] * h.shape[1] <= 4096:
            y, aux = moe_dense_reference(lp["ffn"], h,
                                         top_k=cfg.num_experts_per_tok)
        else:
            y, aux = moe_layer(lp["ffn"], h, top_k=cfg.num_experts_per_tok)
    else:
        y = swiglu(lp["ffn"], h)
    return x + y, aux


def _rolling_write(cache, new, positions, *, window):
    return write_kv_cache(cache, new, positions % window)


def forward(params: Dict, cfg: ModelConfig, *,
            tokens: Optional[jax.Array] = None,
            embeds: Optional[jax.Array] = None,
            positions: Optional[jax.Array] = None,
            caches: Optional[List[Any]] = None,
            kv_valid_len: Optional[jax.Array] = None,
            seq_valid_len: Optional[jax.Array] = None,
            rolling: bool = False,
            remat: bool = False,
            logits_slice: Optional[str] = None,
            dense_cache_write: bool = False,
            ) -> Tuple[jax.Array, Optional[List[Any]], jax.Array]:
    """Unified forward.

    tokens: (B, L) int32 — or embeds: (B, L, d) for stub frontends.
    positions: (B, L) absolute positions (defaults arange).
    caches: from :func:`init_cache`; when given, attention writes new KV at
      ``positions`` and mamba layers thread their state (decode inferred
      from L == 1).  Caches ride the layer-scan CARRY and are updated with
      dynamic_update_index_in_dim — in-place under buffer donation, so the
      serving steps never hold two full cache copies.
    dense_cache_write: fresh full prefill covering the entire cache
      (L == S): KV "write" becomes a pure resharding copy.
    logits_slice: None → full (B, L, V) logits; "last" → (B, V) of final
      position only (decode/prefill TTFT path — avoids the full-vocab
      matmul over L).
    Returns (logits, new_caches, moe_aux_loss).
    """
    if embeds is None:
        x = jnp.take(params["embed"], tokens, axis=0)
    else:
        x = embeds.astype(params["embed"].dtype)
    b, l = x.shape[:2]
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(l)[None, :], (b, l))
    x = constrain(x, "batch", "seq", "embed_act")
    decode = l == 1 and caches is not None

    p = pattern_period(cfg)
    has_cache = caches is not None
    cache_axes = cache_logical_axes(cfg) if has_cache else None

    def body(carry, xs):
        if has_cache:
            x, aux, cs_all, g = carry
        else:
            x, aux = carry
            cs_all = None
        lps = xs
        for j in range(p):
            if cs_all is not None:
                cache_j = jax.tree.map(
                    lambda a: jax.lax.dynamic_index_in_dim(
                        a, g, 0, keepdims=False), cs_all[j])
            else:
                cache_j = None
            blk = functools.partial(
                _block, cfg, j, positions=positions,
                seq_valid_len=seq_valid_len, kv_valid_len=kv_valid_len,
                decode=decode, rolling=rolling,
                dense_write=dense_cache_write)
            if remat and p > 1:
                # nested per-layer remat: with a multi-layer pattern body
                # (jamba p=8) a single body-level checkpoint would hold all
                # 8 layers' residuals live during the block's backward
                blk = jax.checkpoint(blk, prevent_cse=False)
            x, nc, a = blk(lps[j], x, cache_j)
            aux = aux + a
            if cs_all is not None:
                upd = jax.tree.map(
                    lambda full, u: jax.lax.dynamic_update_index_in_dim(
                        full, u.astype(full.dtype), g, 0),
                    cs_all[j], nc)
                # pin the loop-carried cache sharding: XLA's propagation
                # through while-carries can decay to replicated (→ tens
                # of GiB of KV rematerialized per device)
                cs_all[j] = constrain_tree(upd, cache_axes[j])
        if has_cache:
            return (x, aux, cs_all, g + 1), None
        return (x, aux), None

    if remat:
        body = jax.checkpoint(body, prevent_cse=False)

    zero = jnp.zeros((), jnp.float32)
    if has_cache:
        carry0 = (x, zero, list(caches), jnp.zeros((), jnp.int32))
        (x, aux, new_caches, _), _ = jax.lax.scan(body, carry0,
                                                  params["blocks"])
    else:
        (x, aux), _ = jax.lax.scan(body, (x, zero), params["blocks"])
        new_caches = None

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    vpad = cfg.padded_vocab - cfg.vocab_size
    if logits_slice == "last":
        x = x[:, -1]
        logits = x @ params["lm_head"]
        logits = constrain(logits, "batch", "vocab")
    else:
        logits = x @ params["lm_head"]
        logits = constrain(logits, "batch", "seq", "vocab")
    if vpad:
        # mask padded vocabulary columns (argmax/softmax safety)
        neg = jnp.concatenate(
            [jnp.zeros((cfg.vocab_size,), logits.dtype),
             jnp.full((vpad,), -1e9, logits.dtype)])
        logits = logits + neg
    return logits, new_caches, aux


# ---------------------------------------------------------------- packed


def _lm_head_logits(params: Dict, cfg: ModelConfig,
                    x: jax.Array) -> jax.Array:
    """Final-norm'd (B, d) rows → (B, V) logits with the padded-vocab
    columns masked (argmax/softmax safety).  ONE implementation shared
    by every serving step that emits one logit row per sequence — the
    packed, packed-arena, and arena-decode paths must never diverge
    here, they are parity-tested against each other."""
    with jax.named_scope("laps/lm_head"):
        logits = x @ params["lm_head"]
        logits = constrain(logits, "batch", "vocab")
        vpad = cfg.padded_vocab - cfg.vocab_size
        if vpad:
            neg = jnp.concatenate(
                [jnp.zeros((cfg.vocab_size,), logits.dtype),
                 jnp.full((vpad,), -1e9, logits.dtype)])
            logits = logits + neg
    return logits


def _scan_serving_stack(params: Dict, cfg: ModelConfig, tokens: jax.Array,
                        caches: List[Any], mix_fn
                        ) -> Tuple[jax.Array, List[Any]]:
    """Shared layer-scan scaffold for the flat-stream serving steps
    (packed prefill, arena packed prefill, arena decode): embed →
    per-group {norm → mix_fn → FFN → cache writeback} → final norm.

    mix_fn(j, layer_params, h, cache_j) → (mix, new_cache_dict) supplies
    the mixer variant for pattern position j — attention (full or
    windowed) returning {"k", "v"}, or an SSM block returning
    {"ssm", "conv"}; everything else — including the cache
    constrain_tree pinning — is identical across the paths and lives
    exactly once.  Returns (final-normed activations, new caches)."""
    with jax.named_scope("laps/embed"):
        x = jnp.take(params["embed"], tokens, axis=0)
    p = pattern_period(cfg)
    cache_axes = cache_logical_axes(cfg)

    def body(carry, lps):
        x, aux, cs_all, g = carry
        for j in range(p):
            # the scan carries every layer's pool: laps/kv_carry names
            # the slice of this layer's pool out of it and back
            with jax.named_scope("laps/kv_carry"):
                cache_j = jax.tree.map(
                    lambda a: jax.lax.dynamic_index_in_dim(
                        a, g, 0, keepdims=False), cs_all[j])
            with jax.named_scope("laps/attn"):
                h = rms_norm(x, lps[j]["ln1"], cfg.norm_eps)
                mix, nc = mix_fn(j, lps[j]["mixer"], h, cache_j)
                x = x + mix
            if cfg.family != "ssm":
                with jax.named_scope("laps/mlp"):
                    x2, a = _ffn(cfg, j, lps[j], x[None])
                x = x2[0]
                aux = aux + a
            with jax.named_scope("laps/kv_carry"):
                full = jax.tree.map(
                    lambda fa, u: jax.lax.dynamic_update_index_in_dim(
                        fa, u.astype(fa.dtype), g, 0), cs_all[j], nc)
                cs_all[j] = constrain_tree(full, cache_axes[j])
        return (x, aux, cs_all, g + 1), None

    zero = jnp.zeros((), jnp.float32)
    carry0 = (x, zero, list(caches), jnp.zeros((), jnp.int32))
    (x, _, new_caches, _), _ = jax.lax.scan(body, carry0, params["blocks"])
    return rms_norm(x, params["final_norm"], cfg.norm_eps), new_caches


# ------------------------------------------------- capability descriptor


@dataclasses.dataclass(frozen=True)
class LayerCapability:
    """Arena capability of ONE pattern position (DESIGN.md §7).

    kind: "attn" (full-attention KV slot), "attn_window" (rolling
    window-deep KV slot + windowed kernel), or "ssm" (recurrent-state
    slot stepped in place).  window is the sliding-window width for
    attn_window positions, None otherwise.
    """
    kind: str
    window: Optional[int] = None


@dataclasses.dataclass(frozen=True)
class ArenaCapability:
    """Per-layer arena-residency descriptor of a model config.

    Replaces the old boolean ``supports_packed`` fallback matrix: every
    CAUSAL architecture is arena-resident (packed prefill + bucketed
    decode through the slot-map kernels), each pattern position routed
    by its :class:`LayerCapability`.  The dense (L, B) grid survives
    only as an explicitly requested measurement baseline and for
    encoder-only models (no serving decode loop at all).
    """
    layers: Tuple[LayerCapability, ...]   # one per pattern position
    causal: bool

    @property
    def packed_ok(self) -> bool:
        """Arena-resident packed prefill + decode are available."""
        return self.causal

    @property
    def pure_attn(self) -> bool:
        """Every mixer is full attention — the only configs the LEGACY
        gathered-cache packed path (forward_packed) can also run."""
        return all(c.kind == "attn" for c in self.layers)

    @property
    def has_window(self) -> bool:
        return any(c.kind == "attn_window" for c in self.layers)

    @property
    def has_ssm(self) -> bool:
        return any(c.kind == "ssm" for c in self.layers)

    @property
    def window(self) -> Optional[int]:
        for c in self.layers:
            if c.kind == "attn_window":
                return c.window
        return None

    @property
    def needs_scratch_slot(self) -> bool:
        """Rolling KV slots have no spare park row (every row cycles
        live) and SSM state has no park position at all — pads must
        target a dedicated scratch slot instead of aliasing a live one."""
        return self.has_window or self.has_ssm


def arena_capability(cfg: ModelConfig) -> ArenaCapability:
    """Per-layer capability descriptor — the §7 routing contract."""
    layers = []
    for j in range(pattern_period(cfg)):
        if cfg.layer_kind(j) != "attn":
            layers.append(LayerCapability("ssm"))
        elif cfg.sliding_window is not None:
            layers.append(LayerCapability("attn_window",
                                          window=cfg.sliding_window))
        else:
            layers.append(LayerCapability("attn"))
    return ArenaCapability(layers=tuple(layers), causal=cfg.causal)


def supports_packed(cfg: ModelConfig) -> bool:
    """LEGACY predicate for the gathered-cache packed path
    (:func:`forward_packed`), which needs pure-attention mixers with a
    full cache.  Arena routing uses :func:`arena_capability` instead —
    SSM and sliding-window configs are arena-resident there."""
    cap = arena_capability(cfg)
    return cap.causal and cap.pure_attn


def forward_packed(params: Dict, cfg: ModelConfig, *,
                   tokens: jax.Array,
                   positions: jax.Array,
                   seg_ids: jax.Array,
                   cu_seqlens: jax.Array,
                   q_offsets: jax.Array,
                   kv_lengths: jax.Array,
                   caches: List[Any],
                   last_idx: jax.Array,
                   ) -> Tuple[jax.Array, List[Any]]:
    """Padding-free forward over a packed flat token stream — the
    continuous-batching step: prefill AND decode segments side by side.

    tokens/positions/seg_ids: (T,) — the concatenation of every
    sequence's new tokens, each token carrying its absolute position
    (history offset + local index) and its cache row; sequence i owns
    rows [cu_seqlens[i], cu_seqlens[i+1]) of the stream.  Rows past
    cu_seqlens[-1] are bucket tail padding (parked positions, junk row).

    A decode segment is simply length 1 with ``q_offsets[i] = H`` (its
    full cached context) and ``kv_lengths[i] = H + 1``: the scatter in
    :func:`packed_attention_layer` appends its KV at position H and the
    ragged kernel attends it over H + 1 keys — identical math to the
    dense decode step, inside the same dispatch as the prefills.

    caches: from :func:`init_cache` with batch = B cache rows.
    last_idx: (B,) flat index of each sequence's final token — ONE logit
    gathered per segment (prefill TTFT and decode next-token alike).
    Returns (last_logits (B, V), new_caches).

    One compiled shape serves EVERY mix of segment kinds and lengths
    summing under the token bucket T — the compile-cache key space is
    |T buckets|, not |lengths| × |depths|, and prefill/decode mixes
    don't multiply it.
    """
    assert supports_packed(cfg), cfg.name

    def mix_fn(j, lp, h, cache_j):
        mix, upd = packed_attention_layer(
            lp, h, cfg=cfg, positions=positions, seg_ids=seg_ids,
            cu_seqlens=cu_seqlens, q_offsets=q_offsets,
            kv_lengths=kv_lengths, kv=(cache_j["k"], cache_j["v"]))
        return mix, {"k": upd[0], "v": upd[1]}

    x, new_caches = _scan_serving_stack(params, cfg, tokens, caches, mix_fn)
    x_last = jnp.take(x, last_idx, axis=0)                     # (B, d)
    return _lm_head_logits(params, cfg, x_last), new_caches


# ------------------------------------------------- arena packed prefill


def forward_packed_arena(params: Dict, cfg: ModelConfig, *,
                         tokens: jax.Array,
                         positions: jax.Array,
                         seg_slots: jax.Array,
                         slot_map: jax.Array,
                         cu_seqlens: jax.Array,
                         q_offsets: jax.Array,
                         kv_lengths: jax.Array,
                         arena: List[Any],
                         last_idx: jax.Array,
                         ) -> Tuple[jax.Array, List[Any]]:
    """Arena-resident packed forward: the :func:`forward_packed` step
    with the KV arena read and written IN PLACE (DESIGN.md §6).

    Same flat-stream contract as :func:`forward_packed` — prefill,
    chunk, and decode segments side by side, one logit gathered per
    segment via ``last_idx`` — but the cache argument is the KVArena
    pytree itself (per pattern position {"k"/"v": (G, N_slots, S_max,
    Hkv, D)}), not a gathered (B, S, Hkv, D) batch.  ``seg_slots (T,)``
    carries each token's arena slot (tail rows reuse a live slot but
    park at S_max − 1, the scratch row); ``slot_map (B,)`` routes each
    segment's KV reads through the kernel's scalar-prefetched index
    maps.  Each layer scatter-writes ONLY the step's new KV rows, so
    per-step HBM traffic is O(history + new) — not the O(b_max · S_max)
    whole-slot gather/scatter of the batch-cache path.  Under buffer
    donation the arena updates in place; the caller swaps the returned
    pytree back into the KVArena.

    Heterogeneous stacks ride the SAME layer scan (DESIGN.md §7): each
    pattern position routes by its :class:`LayerCapability` — full
    attention slots, windowed ROLLING slots (window-deep arena, modular
    writes, O(min(cached, window)) reads), or SSM state slots stepped in
    place at ``slot_map`` (pad segments point at the arena's scratch
    slot).  Returns (last_logits (B, V), new_arena).
    """
    cap = arena_capability(cfg)
    assert cap.packed_ok, cfg.name
    b = slot_map.shape[0]
    if cap.has_ssm:
        # flat → (segment row, local index) bridge for the SSM scan;
        # computed once, shared by every ssm pattern position
        t = tokens.shape[0]
        rows = jnp.arange(t)
        seg = jnp.sum(rows[:, None] >= cu_seqlens[None, 1:], axis=1)
        valid_row = rows < cu_seqlens[-1]
        seg_rows = jnp.clip(seg, 0, b - 1)
        seg_pos = rows - cu_seqlens[seg_rows]
        seg_lens = cu_seqlens[1:] - cu_seqlens[:-1]

    def mix_fn(j, lp, h, cache_j):
        kind = cap.layers[j].kind
        if kind == "ssm":
            return mamba_mod.packed_arena_mamba_layer(
                lp, h, cfg=cfg, slot_map=slot_map, cache=cache_j,
                seg_rows=seg_rows, seg_pos=seg_pos, valid_row=valid_row,
                seg_lens=seg_lens)
        mix, upd = packed_arena_attention_layer(
            lp, h, cfg=cfg, positions=positions, seg_slots=seg_slots,
            slot_map=slot_map, cu_seqlens=cu_seqlens, q_offsets=q_offsets,
            kv_lengths=kv_lengths, kv=(cache_j["k"], cache_j["v"]),
            window=cap.layers[j].window)
        return mix, {"k": upd[0], "v": upd[1]}

    x, new_arena = _scan_serving_stack(params, cfg, tokens, arena, mix_fn)
    x_last = jnp.take(x, last_idx, axis=0)                     # (B, d)
    return _lm_head_logits(params, cfg, x_last), new_arena


# ------------------------------------------------------- paged serving


def forward_packed_paged(params: Dict, cfg: ModelConfig, *,
                         tokens: jax.Array,
                         positions: jax.Array,
                         token_pages: jax.Array,
                         token_offs: jax.Array,
                         page_table: jax.Array,
                         cu_seqlens: jax.Array,
                         q_offsets: jax.Array,
                         kv_lengths: jax.Array,
                         arena: List[Any],
                         last_idx: jax.Array,
                         state_map: Optional[jax.Array] = None,
                         ) -> Tuple[jax.Array, List[Any]]:
    """Paged packed forward: :func:`forward_packed_arena` with the
    per-segment arena SLOT generalized to a per-block PAGE TABLE
    (DESIGN.md §8).

    Same flat-stream contract — prefill, chunk, and decode segments side
    by side, one logit per segment via ``last_idx`` — but the cache is a
    page POOL (per pattern position {"k"/"v": (G, N_pages + 1,
    page_size, Hkv, D)}) and each segment's logical cache is the ordered
    page list in its row of ``page_table (B, P_max)``.  Pages may be
    SHARED between segments (radix prefix reuse, COW forks): sharing is
    read-only by construction — writes land via ``token_pages`` /
    ``token_offs (T,)``, which the PagedKVArena only ever points at
    exclusively-owned pages (pad/tail rows park on the reserved scratch
    page at offset page_size − 1).

    Heterogeneous stacks ride the same scan (DESIGN.md §12): windowed
    positions treat ``page_table`` as a RING (the engine computes
    token_pages through it, the kernel masks to the window); SSM
    positions hold their per-session recurrent state on a STATE PAGE —
    the pool's page axis doubles as the state-slot axis (per ssm
    position {"ssm": (G, N_pages + 1, NH, HD, DS), "conv": ...}) and
    ``state_map (B,)`` names each segment's state page (pads point at
    the scratch page).  Returns (last_logits (B, V), new_pool).
    """
    cap = arena_capability(cfg)
    assert cap.packed_ok, cfg.name
    b = page_table.shape[0]
    if cap.has_ssm:
        assert state_map is not None, "paged SSM needs a state_map"
        # flat → (segment row, local index) bridge for the SSM scan;
        # computed once, shared by every ssm pattern position
        t = tokens.shape[0]
        rows = jnp.arange(t)
        seg = jnp.sum(rows[:, None] >= cu_seqlens[None, 1:], axis=1)
        valid_row = rows < cu_seqlens[-1]
        seg_rows = jnp.clip(seg, 0, b - 1)
        seg_pos = rows - cu_seqlens[seg_rows]
        seg_lens = cu_seqlens[1:] - cu_seqlens[:-1]

    def mix_fn(j, lp, h, cache_j):
        kind = cap.layers[j].kind
        if kind == "ssm":
            return mamba_mod.packed_arena_mamba_layer(
                lp, h, cfg=cfg, slot_map=state_map, cache=cache_j,
                seg_rows=seg_rows, seg_pos=seg_pos, valid_row=valid_row,
                seg_lens=seg_lens)
        mix, upd = packed_paged_attention_layer(
            lp, h, cfg=cfg, positions=positions, token_pages=token_pages,
            token_offs=token_offs, page_table=page_table,
            cu_seqlens=cu_seqlens, q_offsets=q_offsets,
            kv_lengths=kv_lengths, kv=(cache_j["k"], cache_j["v"]),
            window=cap.layers[j].window)
        return mix, {"k": upd[0], "v": upd[1]}

    x, new_arena = _scan_serving_stack(params, cfg, tokens, arena, mix_fn)
    x_last = jnp.take(x, last_idx, axis=0)                     # (B, d)
    return _lm_head_logits(params, cfg, x_last), new_arena


def forward_packed_verify_arena(params: Dict, cfg: ModelConfig, *,
                                tokens: jax.Array,
                                positions: jax.Array,
                                seg_slots: jax.Array,
                                slot_map: jax.Array,
                                cu_seqlens: jax.Array,
                                q_offsets: jax.Array,
                                kv_lengths: jax.Array,
                                arena: List[Any],
                                gather_idx: jax.Array,
                                ) -> Tuple[jax.Array, List[Any]]:
    """Speculative verification step (DESIGN.md §10): the UNCHANGED
    :func:`forward_packed_arena` dispatch, gathering L logits per
    segment instead of one.

    Verification is already the packed mixed step's shape — each decode
    session becomes a length-L re-prefill segment ``[t0, d_1..d_L-1]``
    scored against its arena history — so no new transformer or kernel
    code runs here: ``last_idx`` accepts any flat row-index vector, and
    ``gather_idx (B, L)`` simply names every row of every segment (pad
    segments point at row 0; their logits are discarded).  Row j of a
    segment scores position ``history + j + 1``, i.e. the draft d_{j+1}
    — acceptance walks that (B, L, V) block on host or in the fused
    sampling kernel.  Returns (logits (B, L, V), new_arena).
    """
    b, l = gather_idx.shape
    logits, new_arena = forward_packed_arena(
        params, cfg, tokens=tokens, positions=positions,
        seg_slots=seg_slots, slot_map=slot_map, cu_seqlens=cu_seqlens,
        q_offsets=q_offsets, kv_lengths=kv_lengths, arena=arena,
        last_idx=gather_idx.reshape(-1))
    return logits.reshape(b, l, -1), new_arena


def forward_packed_verify_paged(params: Dict, cfg: ModelConfig, *,
                                tokens: jax.Array,
                                positions: jax.Array,
                                token_pages: jax.Array,
                                token_offs: jax.Array,
                                page_table: jax.Array,
                                cu_seqlens: jax.Array,
                                q_offsets: jax.Array,
                                kv_lengths: jax.Array,
                                arena: List[Any],
                                gather_idx: jax.Array,
                                state_map: Optional[jax.Array] = None,
                                ) -> Tuple[jax.Array, List[Any]]:
    """Paged speculative verification: :func:`forward_packed_paged`
    gathering L logits per segment via ``gather_idx (B, L)`` (see
    :func:`forward_packed_verify_arena`).
    Returns (logits (B, L, V), new_pool)."""
    b, l = gather_idx.shape
    logits, new_arena = forward_packed_paged(
        params, cfg, tokens=tokens, positions=positions,
        token_pages=token_pages, token_offs=token_offs,
        page_table=page_table, cu_seqlens=cu_seqlens,
        q_offsets=q_offsets, kv_lengths=kv_lengths, arena=arena,
        last_idx=gather_idx.reshape(-1), state_map=state_map)
    return logits.reshape(b, l, -1), new_arena


def forward_decode_paged(params: Dict, cfg: ModelConfig, *,
                         tokens: jax.Array,
                         positions: jax.Array,
                         write_pages: jax.Array,
                         write_offs: jax.Array,
                         page_table: jax.Array,
                         kv_lengths: jax.Array,
                         arena: List[Any],
                         state_map: Optional[jax.Array] = None,
                         ) -> Tuple[jax.Array, List[Any]]:
    """One PAGED decode tick: :func:`forward_decode_arena` with the
    per-row slot generalized to a page table (DESIGN.md §8).

    tokens: (B,) last sampled token per row; positions: (B,) absolute
    position of the new token (rope + kv_lengths − 1);
    write_pages/write_offs: (B,) physical (page, offset) its KV lands in
    (pad rows park on the scratch page at offset page_size − 1);
    page_table: (B, P_max); kv_lengths: (B,) valid entries INCLUDING the
    new row.  Heterogeneous stacks route per layer (DESIGN.md §12):
    windowed positions walk the ring table, SSM positions step the
    per-session state page named by ``state_map (B,)`` in place (pads
    point at the scratch page).  Returns (logits, new_pool).
    """
    cap = arena_capability(cfg)
    assert cap.packed_ok, cfg.name

    def mix_fn(j, lp, h, cache_j):
        kind = cap.layers[j].kind
        if kind == "ssm":
            return mamba_mod.arena_decode_mamba_layer(
                lp, h, cfg=cfg, slot_map=state_map, cache=cache_j)
        mix, upd = paged_decode_layer(
            lp, h, cfg=cfg, positions=positions, write_pages=write_pages,
            write_offs=write_offs, page_table=page_table,
            kv_lengths=kv_lengths, kv=(cache_j["k"], cache_j["v"]),
            window=cap.layers[j].window)
        return mix, {"k": upd[0], "v": upd[1]}

    x, new_arena = _scan_serving_stack(params, cfg, tokens, arena, mix_fn)
    return _lm_head_logits(params, cfg, x), new_arena


# ------------------------------------------------------- arena decode


def forward_decode_arena(params: Dict, cfg: ModelConfig, *,
                         tokens: jax.Array,
                         slot_map: jax.Array,
                         write_pos: jax.Array,
                         kv_lengths: jax.Array,
                         arena: List[Any],
                         ) -> Tuple[jax.Array, List[Any]]:
    """One arena-resident decode tick: B sessions advance one token each
    against the KV arena IN PLACE.

    tokens: (B,) int32 — last sampled token per row; slot_map: (B,)
    arena slot each row owns; write_pos: (B,) absolute position of the
    new token (the row's cached history; pad rows park at S_max − 1);
    kv_lengths: (B,) valid cache entries INCLUDING the new row
    (history + 1; pad rows 1).

    arena: the KVArena pytree itself — per pattern position
    {"k"/"v": (G, N_slots, S_max, Hkv, D)}.  Each layer scatter-writes
    the single new KV row at (slot, write_pos) and the arena-resident
    kernel streams only valid cache prefixes, so per-token HBM traffic
    is O(cached_len) — not the O(S_max) whole-slot gather + scatter of
    the dense path.  Under buffer donation the arena updates in place;
    the caller swaps the returned pytree back into the KVArena.

    Returns (logits (B, V), new_arena).  B is a decode-ladder bucket,
    so the compiled-shape space is O(|ladder|), not O(#session-counts).

    Heterogeneous stacks ride the same scan (DESIGN.md §7): windowed
    positions write the new row modularly into the rolling slot and
    stream O(min(cached, window)); SSM positions step their per-slot
    recurrent state in place (pad rows point at the scratch slot).
    """
    cap = arena_capability(cfg)
    assert cap.packed_ok, cfg.name

    def mix_fn(j, lp, h, cache_j):
        kind = cap.layers[j].kind
        if kind == "ssm":
            return mamba_mod.arena_decode_mamba_layer(
                lp, h, cfg=cfg, slot_map=slot_map, cache=cache_j)
        mix, upd = arena_decode_layer(
            lp, h, cfg=cfg, slot_map=slot_map, positions=write_pos,
            kv_lengths=kv_lengths, kv=(cache_j["k"], cache_j["v"]),
            window=cap.layers[j].window)
        return mix, {"k": upd[0], "v": upd[1]}

    x, new_arena = _scan_serving_stack(params, cfg, tokens, arena, mix_fn)
    return _lm_head_logits(params, cfg, x), new_arena
