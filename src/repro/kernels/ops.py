"""Jit'd dispatch wrappers for the Pallas kernels.

``use_pallas()`` decides the execution path:
  * TPU backend → compiled Pallas kernels (the production path);
  * CPU/GPU → interpret-mode Pallas (tests) or the jnp oracle (fast path).

The serving engine and model layers call these wrappers, never the
kernels directly, so the whole system runs identically on this CPU
container and on a real pod.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref as ref_mod
from repro.kernels.decode_attn import decode_attn as _decode_pallas
from repro.kernels.decode_attn import decode_attn_arena as _decode_arena_pallas
from repro.kernels.decode_attn import decode_attn_paged as _decode_paged_pallas
from repro.kernels.flash_attn import flash_attn as _flash_pallas
from repro.kernels.ragged_prefill import ragged_prefill_attn as _ragged_pallas
from repro.kernels.ragged_prefill import \
    ragged_prefill_arena as _ragged_arena_pallas
from repro.kernels.ragged_prefill import \
    ragged_prefill_paged as _ragged_paged_pallas
from repro.kernels.sampling import MAX_BIAS  # noqa: F401  (re-export)
from repro.kernels.sampling import fused_sample as _fused_sample_pallas
from repro.kernels.ssd_scan import ssd_scan as _ssd_pallas

_FORCE: Optional[str] = None  # None=auto, "pallas", "ref"


def set_backend(mode: Optional[str]) -> None:
    """mode: None (auto), 'pallas' (interpret off-TPU), or 'ref'."""
    global _FORCE
    _FORCE = mode


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _use_pallas() -> bool:
    if _FORCE == "pallas":
        return True
    if _FORCE == "ref":
        return False
    return on_tpu()


def mha(q, k, v, q_offsets=None, kv_lengths=None, *, causal=True,
        window=None, block_q=128, block_k=128):
    """Prefill / re-prefill attention.  See kernels.flash_attn."""
    if _use_pallas():
        return _flash_pallas(q, k, v, q_offsets, kv_lengths, causal=causal,
                             window=window, block_q=block_q, block_k=block_k,
                             interpret=not on_tpu())
    return ref_mod.ref_flash_attn(q, k, v, q_offsets=q_offsets,
                                  kv_lengths=kv_lengths, window=window,
                                  causal=causal)


def ragged_mha(q, k, v, cu_seqlens, q_offsets=None, kv_lengths=None, *,
               causal=True, block_q=128, block_k=128):
    """Packed padding-free prefill attention.  q: (T, Hq, D) flat stream;
    k, v: (B, S, Hkv, D).  See kernels.ragged_prefill."""
    if _use_pallas():
        return _ragged_pallas(q, k, v, cu_seqlens, q_offsets, kv_lengths,
                              causal=causal, block_q=block_q,
                              block_k=block_k, interpret=not on_tpu())
    return ref_mod.ref_ragged_prefill(q, k, v, cu_seqlens,
                                      q_offsets=q_offsets,
                                      kv_lengths=kv_lengths, causal=causal)


def ragged_mha_arena(q, k, v, slot_map, cu_seqlens, q_offsets=None,
                     kv_lengths=None, *, causal=True, window=None,
                     block_q=128, block_k=128):
    """Arena-resident packed prefill attention.  q: (T, Hq, D) flat
    stream; k, v: (N_slots, S_max, Hkv, D) full arenas; slot_map: (B,)
    arena slot per segment.  ``window`` selects the rolling
    (window-deep, modularly written) arena form.  See
    kernels.ragged_prefill."""
    if _use_pallas():
        return _ragged_arena_pallas(q, k, v, slot_map, cu_seqlens,
                                    q_offsets, kv_lengths, causal=causal,
                                    window=window, block_q=block_q,
                                    block_k=block_k,
                                    interpret=not on_tpu())
    return ref_mod.ref_ragged_prefill_arena(q, k, v, slot_map, cu_seqlens,
                                            q_offsets=q_offsets,
                                            kv_lengths=kv_lengths,
                                            causal=causal, window=window)


def ragged_mha_paged(q, k, v, page_table, cu_seqlens, q_offsets=None,
                     kv_lengths=None, *, causal=True, window=None,
                     block_q=128):
    """Paged packed prefill attention.  q: (T, Hq, D) flat stream;
    k, v: (N_pages, page_size, Hkv, D) full page pools; page_table:
    (B, P_max) physical page per logical kv block — pages may be shared
    between segments (prefix reuse, COW forks).  ``window`` selects the
    ring-table (rolling at page granularity) form.  See
    kernels.ragged_prefill.ragged_prefill_paged."""
    if _use_pallas():
        return _ragged_paged_pallas(q, k, v, page_table, cu_seqlens,
                                    q_offsets, kv_lengths, causal=causal,
                                    window=window, block_q=block_q,
                                    interpret=not on_tpu())
    return ref_mod.ref_ragged_prefill_paged(q, k, v, page_table, cu_seqlens,
                                            q_offsets=q_offsets,
                                            kv_lengths=kv_lengths,
                                            causal=causal, window=window)


def decode(q, k, v, lengths, *, block_k=512):
    """Single-token flash decode.  q: (B, Hq, D)."""
    if _use_pallas():
        return _decode_pallas(q, k, v, lengths, block_k=block_k,
                              interpret=not on_tpu())
    return ref_mod.ref_decode_attn(q, k, v, lengths)


def decode_arena(q, k, v, slot_map, lengths, *, window=None, block_k=512):
    """Arena-resident single-token flash decode.  q: (B, Hq, D);
    k, v: (N_slots, S, Hkv, D) full arenas; slot_map/lengths: (B,).
    ``window`` selects the rolling (window-deep, modularly written)
    arena form.  See kernels.decode_attn.decode_attn_arena."""
    if _use_pallas():
        return _decode_arena_pallas(q, k, v, slot_map, lengths,
                                    window=window, block_k=block_k,
                                    interpret=not on_tpu())
    return ref_mod.ref_decode_attn_arena(q, k, v, slot_map, lengths,
                                         window=window)


def decode_paged(q, k, v, page_table, lengths, *, window=None):
    """Paged single-token flash decode.  q: (B, Hq, D); k, v:
    (N_pages, page_size, Hkv, D) full page pools; page_table: (B, P_max);
    lengths: (B,).  ``window`` selects the ring-table (rolling at page
    granularity) form.  See kernels.decode_attn.decode_attn_paged."""
    if _use_pallas():
        return _decode_paged_pallas(q, k, v, page_table, lengths,
                                    window=window, interpret=not on_tpu())
    return ref_mod.ref_decode_attn_paged(q, k, v, page_table, lengths,
                                         window=window)


def fused_sample(logits, temp, top_k, top_p, bias_ids, bias_vals, u, draft):
    """Fused on-device sampling: bias → temperature → exact top-k →
    tie-inclusive top-p → inverse-CDF draw, plus the speculative
    accept/resample outputs.  logits: (R, V); returns (token (R,) int32,
    p_draft (R,) float32, alt (R,) int32) — full-vocab rows never reach
    host.  See kernels.sampling."""
    if _use_pallas():
        return _fused_sample_pallas(logits, temp, top_k, top_p, bias_ids,
                                    bias_vals, u, draft,
                                    interpret=not on_tpu())
    return ref_mod.ref_fused_sample(logits, temp, top_k, top_p, bias_ids,
                                    bias_vals, u, draft)


def ssd(x, dt, a, bmat, cmat, init_state, *, chunk=128):
    """Chunked SSD scan.  See kernels.ssd_scan."""
    if _use_pallas():
        return _ssd_pallas(x, dt, a, bmat, cmat, init_state, chunk=chunk,
                           interpret=not on_tpu())
    return ref_mod.ref_ssd_scan(x, dt, a, bmat, cmat, init_state=init_state)
