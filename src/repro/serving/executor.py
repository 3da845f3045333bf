"""Bucketized AOT-executable cache — the TPU analogue of CUDA Graph
capture (§3.1, DESIGN.md §2).

Each shape is lowered + compiled ONCE (``jax.jit(...).lower(...)
.compile()``) and re-dispatched with zero retracing afterwards.  A shape
miss costs a fresh compile — seconds, like the paper's 8–12 s per-graph
capture — which is precisely why the scheduler pads to the captured
grid.  Compile times, hit/miss statistics, and padding-efficiency
counters are recorded for the §4.2 cost analysis.

Two executors share the cache machinery:

  * :class:`BucketExecutor` — the dense (L, B) grid: every batch is
    padded to a captured (length, depth) shape, so the worst-case key
    space is |lengths| × |depths|.
  * :class:`PackedBucketExecutor` — the padding-free packed path: all
    requests are concatenated into one flat token stream bucketed on
    TOTAL tokens only, so the key space is |token buckets|.  Cache rows
    (max_seqs) and the arena S_max are fixed at construction, keeping
    every packed shape independent of the batch composition.
"""
from __future__ import annotations

import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.buckets import (DEFAULT_DECODE_BUCKETS, DEFAULT_TOKEN_BUCKETS,
                                DecodeBucketLadder, TokenBucketLadder)
from repro.models import transformer as tr
from repro.models.config import ModelConfig
from repro.serving import trace


def make_prefill_fn(cfg: ModelConfig) -> Callable:
    """(params, tokens(B,L), positions(B,L), caches, sample_idx(B,)) →
    (last_logits(B,V), new_caches).  Covers first prefill AND re-prefill
    (positions carry the history offset)."""

    def prefill_step(params, tokens, positions, caches, sample_idx):
        logits, new_caches, _ = tr.forward(
            params, cfg, tokens=tokens, positions=positions, caches=caches,
            seq_valid_len=sample_idx + 1)
        last = jnp.take_along_axis(
            logits, sample_idx[:, None, None], axis=1)[:, 0]
        return last, new_caches

    return prefill_step


def make_packed_prefill_fn(cfg: ModelConfig) -> Callable:
    """(params, tokens(T,), positions(T,), seg_ids(T,), cu_seqlens(B+1,),
    q_offsets(B,), kv_lengths(B,), caches, last_idx(B,)) →
    (last_logits(B,V), new_caches).  Padding-free packed prefill."""

    def packed_step(params, tokens, positions, seg_ids, cu_seqlens,
                    q_offsets, kv_lengths, caches, last_idx):
        return tr.forward_packed(
            params, cfg, tokens=tokens, positions=positions,
            seg_ids=seg_ids, cu_seqlens=cu_seqlens, q_offsets=q_offsets,
            kv_lengths=kv_lengths, caches=caches, last_idx=last_idx)

    return packed_step


def make_packed_arena_fn(cfg: ModelConfig) -> Callable:
    """(params, tokens(T,), positions(T,), seg_slots(T,), slot_map(B,),
    cu_seqlens(B+1,), q_offsets(B,), kv_lengths(B,), arena, last_idx(B,))
    → (last_logits(B,V), greedy_ids(B,), new_arena).  Arena-resident
    packed prefill: the KV arena is read in place (slot axis indexed
    inside the kernel) and only the step's new KV rows are written.
    ``greedy_ids`` is the on-device argmax of each row — all-greedy
    steps take their tokens from it without shipping the full-vocab
    logits to host."""

    def packed_step(params, tokens, positions, seg_slots, slot_map,
                    cu_seqlens, q_offsets, kv_lengths, arena, last_idx):
        last, new_arena = tr.forward_packed_arena(
            params, cfg, tokens=tokens, positions=positions,
            seg_slots=seg_slots, slot_map=slot_map, cu_seqlens=cu_seqlens,
            q_offsets=q_offsets, kv_lengths=kv_lengths, arena=arena,
            last_idx=last_idx)
        return last, jnp.argmax(last, axis=-1).astype(jnp.int32), new_arena

    return packed_step


def make_packed_paged_fn(cfg: ModelConfig) -> Callable:
    """(params, tokens(T,), positions(T,), token_pages(T,), token_offs(T,),
    page_table(B,P_max), cu_seqlens(B+1,), q_offsets(B,), kv_lengths(B,),
    arena, last_idx(B,), state_map(B,)) → (last_logits(B,V),
    greedy_ids(B,), new_arena).  Paged packed prefill (DESIGN.md §8/§12):
    the page pool is read in place through a per-block page table, so
    segments can SHARE pages (radix prefix reuse, COW forks) inside one
    step; SSM positions step the state page named by ``state_map``."""

    def packed_step(params, tokens, positions, token_pages, token_offs,
                    page_table, cu_seqlens, q_offsets, kv_lengths, arena,
                    last_idx, state_map):
        last, new_arena = tr.forward_packed_paged(
            params, cfg, tokens=tokens, positions=positions,
            token_pages=token_pages, token_offs=token_offs,
            page_table=page_table, cu_seqlens=cu_seqlens,
            q_offsets=q_offsets, kv_lengths=kv_lengths, arena=arena,
            last_idx=last_idx, state_map=state_map)
        return last, jnp.argmax(last, axis=-1).astype(jnp.int32), new_arena

    return packed_step


def make_packed_verify_arena_fn(cfg: ModelConfig) -> Callable:
    """(params, tokens(T,), positions(T,), seg_slots(T,), slot_map(B,),
    cu_seqlens(B+1,), q_offsets(B,), kv_lengths(B,), arena,
    gather_idx(B,L)) → (logits(B,L,V), greedy_ids(B,L), new_arena).
    Speculative verification (DESIGN.md §10): the unchanged arena
    dispatch gathering EVERY row's logits per segment instead of one.
    ``greedy_ids`` is the per-row on-device argmax — all-greedy
    acceptance walks it without shipping (B, L, V) to host."""

    def verify_step(params, tokens, positions, seg_slots, slot_map,
                    cu_seqlens, q_offsets, kv_lengths, arena, gather_idx):
        logits, new_arena = tr.forward_packed_verify_arena(
            params, cfg, tokens=tokens, positions=positions,
            seg_slots=seg_slots, slot_map=slot_map, cu_seqlens=cu_seqlens,
            q_offsets=q_offsets, kv_lengths=kv_lengths, arena=arena,
            gather_idx=gather_idx)
        return (logits, jnp.argmax(logits, axis=-1).astype(jnp.int32),
                new_arena)

    return verify_step


def make_packed_verify_paged_fn(cfg: ModelConfig) -> Callable:
    """(params, tokens(T,), positions(T,), token_pages(T,), token_offs(T,),
    page_table(B,P_max), cu_seqlens(B+1,), q_offsets(B,), kv_lengths(B,),
    arena, gather_idx(B,L), state_map(B,)) → (logits(B,L,V),
    greedy_ids(B,L), new_pool).  Paged speculative verification
    (DESIGN.md §10)."""

    def verify_step(params, tokens, positions, token_pages, token_offs,
                    page_table, cu_seqlens, q_offsets, kv_lengths, arena,
                    gather_idx, state_map):
        logits, new_arena = tr.forward_packed_verify_paged(
            params, cfg, tokens=tokens, positions=positions,
            token_pages=token_pages, token_offs=token_offs,
            page_table=page_table, cu_seqlens=cu_seqlens,
            q_offsets=q_offsets, kv_lengths=kv_lengths, arena=arena,
            gather_idx=gather_idx, state_map=state_map)
        return (logits, jnp.argmax(logits, axis=-1).astype(jnp.int32),
                new_arena)

    return verify_step


def make_paged_decode_fn(cfg: ModelConfig) -> Callable:
    """(params, tokens(B,), positions(B,), write_pages(B,), write_offs(B,),
    page_table(B,P_max), kv_lengths(B,), arena, state_map(B,)) →
    (logits(B,V), greedy_ids(B,), new_arena).  Paged decode
    (DESIGN.md §8/§12)."""

    def decode_step(params, tokens, positions, write_pages, write_offs,
                    page_table, kv_lengths, arena, state_map):
        logits, new_arena = tr.forward_decode_paged(
            params, cfg, tokens=tokens, positions=positions,
            write_pages=write_pages, write_offs=write_offs,
            page_table=page_table, kv_lengths=kv_lengths, arena=arena,
            state_map=state_map)
        return (logits, jnp.argmax(logits, axis=-1).astype(jnp.int32),
                new_arena)

    return decode_step


def make_decode_fn(cfg: ModelConfig) -> Callable:
    def decode_step(params, tokens, positions, caches):
        logits, new_caches, _ = tr.forward(
            params, cfg, tokens=tokens, positions=positions, caches=caches,
            logits_slice="last")
        return logits, new_caches

    return decode_step


def make_arena_decode_fn(cfg: ModelConfig) -> Callable:
    """(params, tokens(B,), slot_map(B,), write_pos(B,), kv_lengths(B,),
    arena) → (logits(B,V), greedy_ids(B,), new_arena).  Arena-resident
    decode: the KV arena is read in place (slot axis indexed inside the
    kernel) and only the single new KV row per session is written.
    ``greedy_ids`` is the on-device argmax per row — all-greedy ticks
    take their tokens from it without shipping full-vocab logits to
    host (the fused-sampling greedy slice)."""

    def decode_step(params, tokens, slot_map, write_pos, kv_lengths, arena):
        logits, new_arena = tr.forward_decode_arena(
            params, cfg, tokens=tokens, slot_map=slot_map,
            write_pos=write_pos, kv_lengths=kv_lengths, arena=arena)
        return (logits, jnp.argmax(logits, axis=-1).astype(jnp.int32),
                new_arena)

    return decode_step


class _ExecutorBase:
    """Compile-once shape cache + hit/miss + padding-efficiency stats."""

    def __init__(self) -> None:
        self._compiled: Dict[Tuple, Any] = {}
        self.compile_times: Dict[Tuple, float] = {}
        self.hits = 0
        self.misses = 0
        self.useful_tokens = 0     # real prompt tokens executed
        self.total_tokens = 0      # tokens incl. bucket/grid padding
        # per-kind dispatch accounting ("prefill" / "decode" / ...):
        # the aggregate hit rate hides a cold decode path behind a warm
        # prefill path, so each kind reports its own
        self.kind_hits: Dict[str, int] = {}
        self.kind_misses: Dict[str, int] = {}

    # --------------------------------------------------------------- keys
    @staticmethod
    def _key(kind: str, *arrays) -> Tuple:
        def sig(x):
            return tuple((l.shape, str(l.dtype)) for l in jax.tree.leaves(x))
        return (kind,) + tuple(sig(a) for a in arrays)

    def _get(self, kind: str, jitted, args) -> Any:
        key = self._key(kind, *args)
        exe = self._compiled.get(key)
        if exe is None:
            self.misses += 1
            self.kind_misses[kind] = self.kind_misses.get(kind, 0) + 1
            t0 = time.perf_counter()
            # args[1] is the token operand of every step: its shape is
            # the rung
            rung = "x".join(map(str, args[1].shape))
            with trace.span("laps.exec.compile", kind=kind, rung=rung):
                exe = jitted.lower(*args).compile()
            self.compile_times[key] = time.perf_counter() - t0
            self._compiled[key] = exe
        else:
            self.hits += 1
            self.kind_hits[kind] = self.kind_hits.get(kind, 0) + 1
        return exe

    # ------------------------------------------------------------- stats
    def note_padding(self, useful: int, total: int) -> None:
        """Record one step's token accounting: ``useful`` real prompt
        tokens executed inside a shape of ``total`` tokens."""
        self.useful_tokens += int(useful)
        self.total_tokens += int(total)

    @property
    def padded_tokens(self) -> int:
        return self.total_tokens - self.useful_tokens

    @property
    def padding_efficiency(self) -> float:
        """useful / total executed tokens (1.0 = zero padding waste)."""
        return (self.useful_tokens / self.total_tokens
                if self.total_tokens else 1.0)

    def capture_cost(self) -> float:
        """Total 'graph capture' (compile) seconds — §4.2."""
        return sum(self.compile_times.values())

    @property
    def hit_rate(self) -> float:
        total = self.hits + self.misses
        return self.hits / total if total else 0.0

    @property
    def hit_rate_by_kind(self) -> Dict[str, float]:
        """Per-dispatch-kind compile-cache hit rates."""
        out: Dict[str, float] = {}
        for kind in set(self.kind_hits) | set(self.kind_misses):
            h = self.kind_hits.get(kind, 0)
            m = self.kind_misses.get(kind, 0)
            out[kind] = h / (h + m) if (h + m) else 0.0
        return out

    def shapes_by_kind(self) -> Dict[str, int]:
        """Compile-cache size per dispatch kind (key[0] is the kind)."""
        out: Dict[str, int] = {}
        for key in self.compile_times:
            out[key[0]] = out.get(key[0], 0) + 1
        return out

    @property
    def dispatches(self) -> int:
        """Total step dispatches (compile hits + misses) — the unit the
        continuous-batching benchmark counts: fusing decode into the
        packed stream shrinks this without shrinking work done."""
        return self.hits + self.misses


class BucketExecutor(_ExecutorBase):
    """The dense (L, B) bucket-grid executor (pads to captured shapes)."""

    def __init__(self, cfg: ModelConfig):
        super().__init__()
        self.cfg = cfg
        self._prefill = make_prefill_fn(cfg)
        self._decode = make_decode_fn(cfg)
        self._jit_prefill = jax.jit(self._prefill, donate_argnums=(3,))
        self._jit_decode = jax.jit(self._decode, donate_argnums=(3,))

    # ---------------------------------------------------------- dispatch
    def prefill(self, params, tokens, positions, caches, sample_idx):
        exe = self._get("prefill", self._jit_prefill,
                        (params, tokens, positions, caches, sample_idx))
        return exe(params, tokens, positions, caches, sample_idx)

    def decode(self, params, tokens, positions, caches):
        exe = self._get("decode", self._jit_decode,
                        (params, tokens, positions, caches))
        return exe(params, tokens, positions, caches)

    def precapture(self, params, arena_gather, lengths, depths) -> float:
        """Capture the (L, B) grid at init (paper: graphs captured at
        system initialization).  Returns total capture seconds."""
        t0 = time.perf_counter()
        for b in depths:
            caches = arena_gather(list(range(b)))
            for l in lengths:
                tokens = jnp.zeros((b, l), jnp.int32)
                positions = jnp.zeros((b, l), jnp.int32)
                sample_idx = jnp.zeros((b,), jnp.int32)
                self._get("prefill", self._jit_prefill,
                          (params, tokens, positions, caches, sample_idx))
            tok1 = jnp.zeros((b, 1), jnp.int32)
            pos1 = jnp.zeros((b, 1), jnp.int32)
            self._get("decode", self._jit_decode,
                      (params, tok1, pos1, caches))
        return time.perf_counter() - t0


class PackedBucketExecutor(_ExecutorBase):
    """Padding-free packed prefill keyed on a 1-D total-token bucket.

    Every step runs one flat (T,) token stream with ``max_seqs`` cache
    rows gathered from the arena, so the compiled-shape space grows with
    |token_buckets| — not with the (length × depth) cross-product of the
    dense grid.  The only padding is the bucket tail T − Σ len_i.
    """

    def __init__(self, cfg: ModelConfig,
                 token_buckets: Tuple[int, ...] = DEFAULT_TOKEN_BUCKETS,
                 max_seqs: int = 16):
        super().__init__()
        self.capability = tr.arena_capability(cfg)
        if not self.capability.packed_ok:
            raise ValueError(
                f"{cfg.name}: packed serving needs a causal decoder "
                "(encoder-only models have no serving decode loop)")
        self.cfg = cfg
        # scratch-slot arenas (rolling SWA / SSM state, DESIGN.md §7)
        # permanently reserve ONE stream row: bucket-tail tokens park
        # their junk writes in a dummy segment whose slot is the
        # scratch slot.  Folding the reservation into the ladder keeps
        # every consumer — the engine, ServeLoop's fit_decodes, AWD,
        # the simulator — agreeing on the schedulable room, so a fully
        # fused tick still dispatches as ONE packed step.
        self.reserve_pad_row = self.capability.needs_scratch_slot
        if self.reserve_pad_row:
            max_seqs = max_seqs - 1
            assert max_seqs >= 1, \
                "scratch-slot arenas need packed max_seqs >= 2"
        self.ladder = TokenBucketLadder(token_buckets, max_seqs)
        # LEGACY gathered-cache form: whole arena slots copied out and
        # back around the step — pure-attention only (SSM state and
        # rolling SWA slots have no gathered equivalent), kept as the
        # measurement baseline
        self._jit_packed = None
        if self.capability.pure_attn:
            self._packed = make_packed_prefill_fn(cfg)
            self._jit_packed = jax.jit(
                self._packed,
                donate_argnums=(7,))
        # arena-resident form (DESIGN.md §6/§7): the KV + state arenas
        # ride as an in-place argument (donated) instead of gathered
        # cache rows; per-layer routing from the capability descriptor
        self._packed_arena = make_packed_arena_fn(cfg)
        self._jit_packed_arena = jax.jit(
            self._packed_arena,
            donate_argnums=(8,))
        # paged form (DESIGN.md §8/§12): per-block page table instead of
        # a per-segment slot — every packed_ok config (windowed layers
        # walk a ring table, SSM layers step per-session state pages)
        self._packed_paged = make_packed_paged_fn(cfg)
        self._jit_packed_paged = jax.jit(
            self._packed_paged,
            donate_argnums=(9,))
        # speculative verification forms (DESIGN.md §10): the SAME
        # packed dispatch with an L-per-segment logits gather.  Their
        # compile cache is keyed on (token bucket, L) via the
        # gather_idx shape — fixed L keeps the shape space small
        self._verify_arena = make_packed_verify_arena_fn(cfg)
        self._jit_verify_arena = jax.jit(
            self._verify_arena,
            donate_argnums=(8,))
        self._verify_paged = make_packed_verify_paged_fn(cfg)
        self._jit_verify_paged = jax.jit(
            self._verify_paged,
            donate_argnums=(9,))
        # continuous-batching counters: a mixed step fuses decode rows
        # into the same packed stream (and the SAME compiled executable —
        # the shape key is (token bucket, max_seqs), not the segment mix)
        self.mixed_steps = 0
        self.decode_tokens_fused = 0

    # ------------------------------------------------------------ lookup
    @property
    def token_buckets(self) -> Tuple[int, ...]:
        return self.ladder.buckets

    @property
    def max_seqs(self) -> int:
        """Schedulable segments per step (pad-row reservation applied)."""
        return self.ladder.max_seqs

    @property
    def stream_rows(self) -> int:
        """Cache rows of the compiled stream shape: the schedulable
        segments plus the reserved scratch pad row, if any."""
        return self.ladder.max_seqs + (1 if self.reserve_pad_row else 0)

    def bucket_for(self, total_tokens: int) -> Optional[int]:
        """Smallest token bucket ≥ total_tokens (None if off-scale)."""
        return self.ladder.bucket_for(total_tokens)

    # ---------------------------------------------------------- dispatch
    def prefill_packed(self, params, tokens, positions, seg_ids, cu_seqlens,
                       q_offsets, kv_lengths, caches, last_idx):
        assert self._jit_packed is not None, \
            f"{self.cfg.name}: gathered-cache packed path is attention-only"
        args = (params, tokens, positions, seg_ids, cu_seqlens,
                q_offsets, kv_lengths, caches, last_idx)
        exe = self._get("packed_prefill", self._jit_packed, args)
        return exe(*args)

    def mixed_step(self, params, tokens, positions, seg_ids, cu_seqlens,
                   q_offsets, kv_lengths, caches, last_idx, *,
                   n_decode: int = 0):
        """One continuous-batching step: the flat stream carries prefill
        segments AND length-1 decode segments (history offsets point each
        decode row at its full cached context).

        Dispatches through the SAME compile-cache entry as a pure
        prefill of this (token bucket, max_seqs) shape — the executable
        is keyed on shapes only, so prefill, decode, and every mix in
        between share one captured step.  ``n_decode`` feeds the fusion
        counters."""
        if n_decode:
            self.mixed_steps += 1
            self.decode_tokens_fused += int(n_decode)
        return self.prefill_packed(params, tokens, positions, seg_ids,
                                   cu_seqlens, q_offsets, kv_lengths,
                                   caches, last_idx)

    def prefill_packed_arena(self, params, tokens, positions, seg_slots,
                             slot_map, cu_seqlens, q_offsets, kv_lengths,
                             arena, last_idx):
        args = (params, tokens, positions, seg_slots, slot_map, cu_seqlens,
                q_offsets, kv_lengths, arena, last_idx)
        exe = self._get("packed_arena", self._jit_packed_arena, args)
        return exe(*args)

    def mixed_step_arena(self, params, tokens, positions, seg_slots,
                         slot_map, cu_seqlens, q_offsets, kv_lengths,
                         arena, last_idx, *, n_decode: int = 0):
        """One arena-resident continuous-batching step (DESIGN.md §6):
        same flat stream and fusion semantics as :meth:`mixed_step`, but
        the KV arena is an ARGUMENT read in place — the kernel routes
        each segment's KV blocks through ``slot_map`` and the step
        writes only the new rows, so there is no whole-slot gather
        before it and no scatter after it.  The compile cache stays
        keyed on the token bucket (the arena shape is a constant); under
        donation the arena buffers update in place and the caller swaps
        the returned pytree into its KVArena."""
        if n_decode:
            self.mixed_steps += 1
            self.decode_tokens_fused += int(n_decode)
        return self.prefill_packed_arena(params, tokens, positions,
                                         seg_slots, slot_map, cu_seqlens,
                                         q_offsets, kv_lengths, arena,
                                         last_idx)

    def mixed_step_paged(self, params, tokens, positions, token_pages,
                         token_offs, page_table, cu_seqlens, q_offsets,
                         kv_lengths, arena, last_idx, state_map, *,
                         n_decode: int = 0):
        """One PAGED continuous-batching step (DESIGN.md §8): same flat
        stream and fusion semantics as :meth:`mixed_step_arena`, but the
        cache argument is the shared page POOL and each segment's KV is
        routed through its row of ``page_table`` — so segments can share
        prefix pages and a prefix-hit turn streams its full logical
        context while having prefilled only its suffix.  ``state_map``
        (B,) names each segment's SSM state page (scratch for pads /
        pure-attn configs).  The compile cache is keyed on (token
        bucket, P_max); the pool shape is a constant."""
        if n_decode:
            self.mixed_steps += 1
            self.decode_tokens_fused += int(n_decode)
        args = (params, tokens, positions, token_pages, token_offs,
                page_table, cu_seqlens, q_offsets, kv_lengths, arena,
                last_idx, state_map)
        exe = self._get("packed_paged", self._jit_packed_paged, args)
        return exe(*args)

    def verify_step_arena(self, params, tokens, positions, seg_slots,
                          slot_map, cu_seqlens, q_offsets, kv_lengths,
                          arena, gather_idx):
        """One speculative verification dispatch (DESIGN.md §10): the
        arena-resident packed step scoring every session's k-token draft
        segment at once, returning (logits (B, L, V), greedy_ids (B, L),
        new_arena).  Kernel-identical to :meth:`mixed_step_arena` — only
        the final logits gather widens from 1 to L rows per segment."""
        args = (params, tokens, positions, seg_slots, slot_map, cu_seqlens,
                q_offsets, kv_lengths, arena, gather_idx)
        exe = self._get("verify_arena", self._jit_verify_arena, args)
        return exe(*args)

    def verify_step_paged(self, params, tokens, positions, token_pages,
                          token_offs, page_table, cu_seqlens, q_offsets,
                          kv_lengths, arena, gather_idx, state_map):
        """Paged speculative verification dispatch (DESIGN.md §10) —
        :meth:`verify_step_arena` over the shared page pool."""
        args = (params, tokens, positions, token_pages, token_offs,
                page_table, cu_seqlens, q_offsets, kv_lengths, arena,
                gather_idx, state_map)
        exe = self._get("verify_paged", self._jit_verify_paged, args)
        return exe(*args)

    def precapture_paged(self, params, arena, p_max: int) -> Dict[int, float]:
        """Compile every token bucket's paged step at init (|token_buckets|
        shapes; the page pool and P_max are constants).  Lower + compile
        only — the pool is never executed against nor donated away.
        Returns compile seconds per token bucket."""
        b = self.stream_rows
        out: Dict[int, float] = {}
        for t in self.token_buckets:
            t0 = time.perf_counter()
            self._get("packed_paged", self._jit_packed_paged,
                      (params, _ints(t), _ints(t), _ints(t), _ints(t),
                       _ints(b, p_max), _ints(b + 1), _ints(b), _ints(b),
                       arena, _ints(b), _ints(b)))
            out[t] = time.perf_counter() - t0
        return out


class DecodeBucketExecutor(_ExecutorBase):
    """Arena-resident bucketed decode (mirrors :class:`PackedBucketExecutor`
    for the decode regime).

    A decode-only tick runs ONE executable whose batch axis is padded to
    a small decode-seqs ladder rung (default 1/2/4/8/16/32, capped at
    the arena depth), so the compile cache is keyed on the BUCKET — not
    the live session count.  N sessions draining at staggered rates
    compile at most |ladder| shapes instead of one per distinct count.

    The KV arena is an ARGUMENT, read in place: the kernel indexes the
    slot axis through a scalar-prefetched slot map and streams only
    valid cache prefixes, and the step writes back one KV row per
    session — no whole-slot gather/scatter.  Under donation the arena
    buffers update in place; the caller swaps the returned pytree into
    its KVArena.
    """

    def __init__(self, cfg: ModelConfig,
                 decode_buckets: Tuple[int, ...] = DEFAULT_DECODE_BUCKETS,
                 max_seqs: Optional[int] = None):
        super().__init__()
        self.capability = tr.arena_capability(cfg)
        if not self.capability.packed_ok:
            raise ValueError(
                f"{cfg.name}: arena-resident decode needs a causal "
                "decoder (encoder-only models have no decode loop)")
        self.cfg = cfg
        self.ladder = DecodeBucketLadder(decode_buckets, max_seqs)
        self._decode = make_arena_decode_fn(cfg)
        self._jit_decode = jax.jit(
            self._decode, donate_argnums=(5,))
        # paged form (DESIGN.md §8/§12): every packed_ok config —
        # windowed layers walk a ring table, SSM layers step their
        # per-session state page through state_map
        self._decode_paged = make_paged_decode_fn(cfg)
        self._jit_decode_paged = jax.jit(
            self._decode_paged,
            donate_argnums=(7,))

    # ------------------------------------------------------------ lookup
    @property
    def decode_buckets(self) -> Tuple[int, ...]:
        return self.ladder.buckets

    def bucket_for(self, n_seqs: int) -> Optional[int]:
        """Smallest ladder rung ≥ n_seqs (None → dense fallback)."""
        return self.ladder.bucket_for(n_seqs)

    # ---------------------------------------------------------- dispatch
    def decode(self, params, tokens, slot_map, write_pos, kv_lengths,
               arena):
        args = (params, tokens, slot_map, write_pos, kv_lengths, arena)
        exe = self._get("arena_decode", self._jit_decode, args)
        return exe(*args)

    def decode_paged(self, params, tokens, positions, write_pages,
                     write_offs, page_table, kv_lengths, arena, state_map):
        """One PAGED decode tick (DESIGN.md §8/§12): the page pool rides
        in place and each row's KV is routed through its page-table row —
        rows may share prefix pages.  ``state_map`` (B,) names each row's
        SSM state page (scratch for pads / pure-attn configs).  Compile
        cache keyed on the decode bucket × P_max."""
        args = (params, tokens, positions, write_pages, write_offs,
                page_table, kv_lengths, arena, state_map)
        exe = self._get("paged_decode", self._jit_decode_paged, args)
        return exe(*args)

    def precapture_paged(self, params, arena, p_max: int) -> Dict[int, float]:
        """Compile every decode rung's paged step at init — |ladder|
        shapes, vs one per live session count on the dense path.  Lower
        + compile only.  Returns compile seconds per rung."""
        out: Dict[int, float] = {}
        for b in self.decode_buckets:
            t0 = time.perf_counter()
            self._get("paged_decode", self._jit_decode_paged,
                      (params, _ints(b), _ints(b), _ints(b), _ints(b),
                       _ints(b, p_max), _ints(b), arena, _ints(b)))
            out[b] = time.perf_counter() - t0
        return out


def _ints(*shape: int) -> jax.ShapeDtypeStruct:
    """An int32 operand shape: rungs compile from shapes, never data."""
    return jax.ShapeDtypeStruct(shape, jnp.int32)


__all__ = ["BucketExecutor", "PackedBucketExecutor", "DecodeBucketExecutor",
           "DEFAULT_TOKEN_BUCKETS", "DEFAULT_DECODE_BUCKETS",
           "make_prefill_fn", "make_packed_prefill_fn",
           "make_packed_arena_fn", "make_packed_paged_fn",
           "make_packed_verify_arena_fn", "make_packed_verify_paged_fn",
           "make_decode_fn", "make_arena_decode_fn",
           "make_paged_decode_fn"]
