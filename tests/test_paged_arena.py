"""Paged KV arena (DESIGN.md §8): kernel-level parity of the page-table
ragged prefill / decode against the gathered-page oracle on fragmented,
shared, and COW-forked page layouts (GQA/MHA/MQA, interpret mode),
engine-level parity of the paged engine vs the slot-arena engine (logits
to 1e-5 on prefill, mixed, and bucketed decode ticks), radix prefix
reuse producing logits identical to a cold prefill while billing only
the new suffix, the COW-fork regression (satellite of §8: forked
branches match independently prefilled sessions through decode across
page boundaries), and page hygiene — pad rows only ever touch the
reserved scratch page.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.kernels import ops as kernel_ops
from repro.kernels import ref
from repro.kernels.decode_attn import (decode_attn_paged,
                                       pages_per_block, paged_decode_blocks)
from repro.kernels.ragged_prefill import ragged_prefill_paged
from repro.models import transformer as tr
from repro.serving import Engine, EngineConfig

KEY = jax.random.key(41)
TOL = dict(atol=1e-5, rtol=0)
TOL_INTERPRET = dict(atol=2e-5, rtol=0)


def rand(key, shape):
    return jax.random.normal(key, shape, jnp.float32)


def make_stream(lens, hists):
    b = len(lens)
    cu = np.zeros(b + 1, np.int32)
    cu[1:] = np.cumsum(lens)
    off = np.asarray(hists, np.int32)
    kvl = off + np.asarray(lens, np.int32)
    return jnp.asarray(cu), jnp.asarray(off), jnp.asarray(kvl)


def page_layout(rng, npages, ps, p_max, lens, hists, share=None):
    """A fragmented page table: each segment gets ceil((h+l)/ps) DISTINCT
    random pages; ``share=(a, b, k)`` makes segment b reuse segment a's
    first k pages (prefix sharing / COW fork layouts).  Unused table
    entries point at page 0 (always in range; masked by kv_lengths)."""
    table = np.zeros((len(lens), p_max), np.int32)
    free = list(rng.permutation(npages))
    for i, (l, h) in enumerate(zip(lens, hists)):
        need = -(-(h + l) // ps)
        table[i, :need] = [free.pop() for _ in range(need)]
    if share is not None:
        a, b, k = share
        table[b, :k] = table[a, :k]
    return table


# ----------------------------------------------------------- kernel level


@pytest.mark.parametrize("npages,ps,hq,hkv,d,bq", [
    (24, 16, 8, 2, 32, 16),    # GQA
    (16, 8, 4, 4, 64, 8),      # MHA
    (20, 16, 8, 1, 16, 8),     # MQA
])
def test_paged_prefill_kernel_matches_oracle(npages, ps, hq, hkv, d, bq):
    """Fragmented + prefix-shared page layout: the page-table index map
    reads exactly the gathered pages the oracle sees."""
    ks = jax.random.split(KEY, 3)
    rng = np.random.default_rng(npages)
    lens = [5, 9, 4]
    hists = [7, 0, 12]
    p_max = 4
    t = sum(lens) + 3                          # bucket tail rows
    q = rand(ks[0], (t, hq, d))
    k = rand(ks[1], (npages, ps, hkv, d))
    v = rand(ks[2], (npages, ps, hkv, d))
    # segments 0 and 2 share their first page — radix prefix reuse
    table = page_layout(rng, npages, ps, p_max, lens, hists,
                        share=(0, 2, 1))
    cu, off, kvl = make_stream(lens, hists)
    out = ragged_prefill_paged(q, k, v, jnp.asarray(table), cu, off, kvl,
                               block_q=bq)
    want = ref.ref_ragged_prefill_paged(q, k, v, jnp.asarray(table), cu,
                                        q_offsets=off, kv_lengths=kvl)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)
    np.testing.assert_array_equal(np.asarray(out)[sum(lens):], 0.0)


def test_paged_prefill_kernel_full_table():
    """history + new fills every page of the table: the last logical
    block is read fully and nothing past the table is touched."""
    ks = jax.random.split(KEY, 3)
    npages, ps, p_max, hq, hkv, d = 12, 8, 4, 4, 2, 16
    lens, hists = [6, 4], [ps * p_max - 6, 0]
    t = sum(lens)
    q = rand(ks[0], (t, hq, d))
    k = rand(ks[1], (npages, ps, hkv, d))
    v = rand(ks[2], (npages, ps, hkv, d))
    table = page_layout(np.random.default_rng(3), npages, ps, p_max,
                        lens, hists)
    cu, off, kvl = make_stream(lens, hists)
    assert int(kvl[0]) == ps * p_max
    out = ragged_prefill_paged(q, k, v, jnp.asarray(table), cu, off, kvl,
                               block_q=8)
    want = ref.ref_ragged_prefill_paged(q, k, v, jnp.asarray(table), cu,
                                        q_offsets=off, kv_lengths=kvl)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


# (lengths, table width, window) of each decode case; page size 16, so a
# kernel block is 16 pages (256 tokens) wherever the table is that wide
BLK = 16 * pages_per_block(16, 64)
DECODE_CASES = {
    "len-1": ([1], 24, None),
    "len-ps": ([16], 24, None),
    "len-ps+1": ([17], 24, None),
    "one-block": ([BLK], 24, None),
    "one-block+1": ([BLK + 1], 24, None),
    "full-table": ([512 * 16, 700], 512, None),
    "pad-rows": ([40, 1, 300, 1], 24, None),
    "shared-pages": ([300, 300, 290, 20], 24, None),
    "mixed-bucket": ([1, 15, 16, 17, BLK - 1, BLK, BLK + 1, 380], 24,
                     None),
    # an 18-page ring (288 slots) under a 256-token window: the walk
    # starts mid-ring and, once the ring has wrapped, crosses its end
    "ring-before-wrap": ([1, 100, 256, 257, 288], 18, 256),
    "ring-after-wrap": ([289, 300, 545, 600, 1000], 18, 256),
}


def _decode_layout(case, rng):
    """Pool size, page table and lengths of one decode case: distinct
    random pages per row; pad rows (length 1) sit on the scratch page,
    the pool's last, as the engine parks them; in ``shared-pages`` rows
    1 and 2 share row 0's first 16 pages (a whole block) and row 3 its
    first page."""
    lengths, p_max, window = DECODE_CASES[case]
    ps = 16
    held = [min(n, p_max * ps) for n in lengths]
    npages = sum(-(-n // ps) for n in held) + 1
    scratch = npages - 1
    table = page_layout(rng, scratch, ps, p_max, held, [0] * len(held))
    for i, n in enumerate(lengths):
        if n == 1 and case == "pad-rows":
            table[i] = scratch
    if case == "shared-pages":
        table[1, :16] = table[0, :16]
        table[2, :16] = table[0, :16]
        table[3, :1] = table[0, :1]
    return npages, ps, table, np.asarray(lengths, np.int32), window


@pytest.mark.parametrize("case,hq,hkv", [
    pytest.param("cow-fork", 8, 2, id="8-2"),
    pytest.param("cow-fork", 4, 4, id="4-4"),
    pytest.param("cow-fork", 8, 1, id="8-1"),
] + [pytest.param(c, 8, 2, id=c) for c in DECODE_CASES])
def test_paged_decode_kernel_matches_oracle(case, hq, hkv):
    """The page-table walk reads exactly the pages the gathering oracle
    sees: lengths at and around page and block edges, a full table, pad
    rows, shared pages, a mixed bucket, the windowed ring before and
    after it wraps, and a COW-forked layout (``cow-fork``: two rows
    share every prefix page and diverge only on their copied boundary
    page) under GQA, MHA and MQA."""
    ks = jax.random.split(KEY, 3)
    rng = np.random.default_rng(5)
    d = 32
    if case == "cow-fork":
        npages, ps, p_max, window = 20, 16, 4, None
        lengths = np.asarray([37, 37, 9, 51], np.int32)
        table = page_layout(rng, npages, ps, p_max, list(lengths),
                            [0] * len(lengths))
        table[1, :2] = table[0, :2]
        assert table[1, 2] != table[0, 2]
    else:
        npages, ps, table, lengths, window = _decode_layout(case, rng)
    b = len(lengths)
    q = rand(ks[0], (b, hq, d))
    k = rand(ks[1], (npages, ps, hkv, d))
    v = rand(ks[2], (npages, ps, hkv, d))
    out = decode_attn_paged(q, k, v, jnp.asarray(table),
                            jnp.asarray(lengths), window=window)
    want = ref.ref_decode_attn_paged(q, k, v, jnp.asarray(table),
                                     jnp.asarray(lengths), window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


def test_paged_decode_blocks_counts_the_walk():
    """The host's count of blocks walked, which the decode span
    reports: one per started block of a row's pages, at most
    ceil(window / ps) + 1 pages on a ring."""
    ppb = pages_per_block(16, 512)
    assert ppb == 16
    count = functools.partial(paged_decode_blocks, page_size=16, p_max=512)
    assert count([1]) == 1
    assert count([BLK, BLK + 1]) == 1 + 2
    assert count([8192, 1, 1]) == 32 + 2
    ring = functools.partial(paged_decode_blocks, page_size=16, p_max=18,
                             window=256)
    assert ring([256]) == 1                # 16 pages, from entry 0
    assert ring([257]) == 2                # 17 pages: the walk starts at 0
    assert ring([300]) == 2                # starts mid-page, 17 pages
    assert ring([10_000]) == 1             # starts on a page edge
    assert ring([10_001]) == 2


def test_ops_dispatch_paged_backends_agree():
    """ops.ragged_mha_paged / ops.decode_paged: forced-pallas (interpret)
    and forced-ref return the same values."""
    ks = jax.random.split(KEY, 3)
    npages, ps, p_max, hq, hkv, d = 12, 8, 3, 4, 2, 16
    lens, hists = [5, 3], [6, 0]
    q = rand(ks[0], (sum(lens) + 2, hq, d))
    k = rand(ks[1], (npages, ps, hkv, d))
    v = rand(ks[2], (npages, ps, hkv, d))
    table = jnp.asarray(page_layout(np.random.default_rng(1), npages, ps,
                                    p_max, lens, hists))
    cu, off, kvl = make_stream(lens, hists)
    qd = rand(ks[0], (2, hq, d))
    lengths = jnp.asarray([11, 7], jnp.int32)
    try:
        kernel_ops.set_backend("pallas")
        a1 = kernel_ops.ragged_mha_paged(q, k, v, table, cu, off, kvl)
        d1 = kernel_ops.decode_paged(qd, k, v, table[:2], lengths)
        kernel_ops.set_backend("ref")
        a2 = kernel_ops.ragged_mha_paged(q, k, v, table, cu, off, kvl)
        d2 = kernel_ops.decode_paged(qd, k, v, table[:2], lengths)
    finally:
        kernel_ops.set_backend(None)
    np.testing.assert_allclose(np.asarray(a1), np.asarray(a2),
                               **TOL_INTERPRET)
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2),
                               **TOL_INTERPRET)


# ----------------------------------------------------------- engine level


@pytest.fixture(scope="module")
def stack():
    cfg = get_smoke("qwen3-4b")
    params, _ = tr.init_params(cfg, KEY)
    return cfg, params


def build_pair(cfg, params, **paged_kw):
    kw = dict(num_slots=8, max_len=128, chunk_tokens=32, packed=True,
              token_buckets=(64, 128, 256))
    eng = Engine(cfg, params, EngineConfig(**kw, paged_kv=True,
                                           page_size=16, **paged_kw))
    ora = Engine(cfg, params, EngineConfig(**kw, paged_kv=False))
    return eng, ora


def test_paged_engine_matches_slot_engine(stack):
    """Prefill batch, fused mixed tick, and bucketed decode on the paged
    engine reproduce the slot-arena engine logits token for token, with
    zero whole-slot gather/scatter."""
    cfg, params = stack
    eng, ora = build_pair(cfg, params)
    rng = np.random.default_rng(0)
    t1, t2 = (rng.integers(0, cfg.vocab_size, n) for n in (21, 13))
    r1 = eng.step_mixed([(1, t1), (2, t2)], [])
    r2 = ora.step_mixed([(1, t1), (2, t2)], [])
    assert r1.fused and r2.fused and r1.tokens == r2.tokens
    # fused decode rows + a fresh prefill in one tick
    t3 = rng.integers(0, cfg.vocab_size, 9)
    r1 = eng.step_mixed([(3, t3)], [(1, r1.tokens[1]), (2, r1.tokens[2])])
    r2 = ora.step_mixed([(3, t3)], [(1, r2.tokens[1]), (2, r2.tokens[2])])
    assert r1.tokens == r2.tokens
    # bucketed decode ticks
    d1 = eng.decode_batch([1, 2, 3], [r1.tokens[s] for s in (1, 2, 3)],
                          steps=4)
    d2 = ora.decode_batch([1, 2, 3], [r2.tokens[s] for s in (1, 2, 3)],
                          steps=4)
    assert d1 == d2
    for s in (1, 2, 3):
        np.testing.assert_allclose(eng.last_logits[s], ora.last_logits[s],
                                   **TOL)
    st = eng.stats()
    assert st["arena_gathers"] == 0 and st["arena_scatters"] == 0
    assert st["dense_dispatches"] == 0
    eng.arena.audit()


def test_prefix_reuse_matches_cold_prefill(stack):
    """Turn 2 resubmits the full conversation under a fresh session: the
    radix index maps the matched prefix onto turn 1's pages, ONLY the
    suffix is prefilled, and the logits equal a cold prefill of the
    whole conversation to 1e-5."""
    cfg, params = stack
    eng, ora = build_pair(cfg, params)
    rng = np.random.default_rng(1)
    conv1 = rng.integers(0, cfg.vocab_size, 53)
    eng.step_mixed([(10, conv1)], [])
    eng.close_session(10)          # pages stay alive in the radix tree
    assert eng.stats()["prefix_hit_tokens"] == 0
    conv2 = np.concatenate([conv1, rng.integers(0, cfg.vocab_size, 7)])
    assert eng.probe_prefix(conv2) == 48       # 3 full pages of turn 1
    r = eng.step_mixed([(11, conv2)], [])
    ro = ora.step_mixed([(11, conv2)], [])
    assert eng.stats()["prefix_hit_tokens"] == 48
    assert eng.history(11) == len(conv2)
    assert r.tokens[11] == ro.tokens[11]
    np.testing.assert_allclose(eng.last_logits[11], ora.last_logits[11],
                               **TOL)
    # decode continues seamlessly over the adopted pages
    d = eng.decode_batch([11], [r.tokens[11]], steps=3)
    do = ora.decode_batch([11], [ro.tokens[11]], steps=3)
    assert d[11] == do[11]
    eng.arena.audit()


def test_cow_fork_matches_independent_prefill(stack):
    """Satellite regression: two branches COW-forked from one prefix
    produce logits identical (1e-5) to two independently prefilled
    sessions, through decode across ≥ 2 page boundaries; exactly one
    page is COW-copied per diverging branch."""
    cfg, params = stack
    eng, ora = build_pair(cfg, params)
    rng = np.random.default_rng(2)
    prefix = rng.integers(0, cfg.vocab_size, 27)   # partial boundary page
    r = eng.step_mixed([(1, prefix)], [])
    eng.fork_session(1, 2)
    assert eng.arena.pages_of(1) == eng.arena.pages_of(2)
    # both branches decode independently past TWO page boundaries
    # (27 + 22 = 49 crosses 32 and 48); distinct first tokens diverge
    # the branches immediately
    b1 = eng.decode_batch([1], [int(prefix[0])], steps=22)[1]
    b2 = eng.decode_batch([2], [int(prefix[1])], steps=22)[2]
    assert eng.stats()["pages_cow_forked"] >= 1
    assert eng.arena.pages_of(1) != eng.arena.pages_of(2)
    eng.arena.audit()
    # oracle: two slot-engine sessions prefilled independently
    o = ora.step_mixed([(1, prefix), (2, prefix)], [])
    o1 = ora.decode_batch([1], [int(prefix[0])], steps=22)[1]
    o2 = ora.decode_batch([2], [int(prefix[1])], steps=22)[2]
    assert b1 == o1 and b2 == o2
    np.testing.assert_allclose(eng.last_logits[1], ora.last_logits[1],
                               **TOL)
    np.testing.assert_allclose(eng.last_logits[2], ora.last_logits[2],
                               **TOL)


def test_pad_rows_only_touch_scratch_page(stack):
    """Page hygiene: a padded mixed tick (bucket tail + dummy rows)
    leaves every page except the step's own new pages and the reserved
    scratch page bit-identical."""
    cfg, params = stack
    eng, _ = build_pair(cfg, params)
    rng = np.random.default_rng(3)
    eng.step_mixed([(1, rng.integers(0, cfg.vocab_size, 21))], [])
    own = set(eng.arena.pages_of(1))
    before = jax.tree.map(np.array, eng.arena.arena)
    r = eng.step_mixed([(2, rng.integers(0, cfg.vocab_size, 5))], [])
    touched = set(eng.arena.pages_of(2)) | {eng.arena.scratch}
    after = jax.tree.map(np.array, eng.arena.arena)
    keep = np.asarray(sorted(set(range(eng.arena.num_pages + 1))
                             - touched), np.int32)
    assert own <= set(keep.tolist())
    for cb, ca in zip(before, after):
        for part in ("k", "v"):
            np.testing.assert_array_equal(cb[part][:, keep],
                                          ca[part][:, keep])
    eng.arena.audit()


def test_paged_interpret_backend_parity(stack):
    """The paged engine under forced-pallas interpret mode matches the
    jnp-oracle backend on a mixed prefill + decode schedule."""
    cfg, params = stack
    rng = np.random.default_rng(4)
    toks = rng.integers(0, cfg.vocab_size, 19)
    outs = {}
    for backend in ("pallas", "ref"):
        try:
            kernel_ops.set_backend(backend)
            eng, _ = build_pair(cfg, params)
            r = eng.step_mixed([(1, toks)], [])
            d = eng.decode_batch([1], [r.tokens[1]], steps=2)
            outs[backend] = (r.tokens[1], d[1],
                             np.array(eng.last_logits[1]))
        finally:
            kernel_ops.set_backend(None)
    assert outs["pallas"][0] == outs["ref"][0]
    assert outs["pallas"][1] == outs["ref"][1]
    np.testing.assert_allclose(outs["pallas"][2], outs["ref"][2],
                               **TOL_INTERPRET)


def test_paged_engine_guards():
    """§12: paged_kv now covers every packed_ok config (windowed rings,
    SSM state pages) but still demands a causal decoder stack AND the
    packed + arena execution paths — collisions raise a clear
    ValueError at construction, not a deep kernel assert."""
    cfg = get_smoke("qwen3-4b")
    params, _ = tr.init_params(cfg, KEY)
    with pytest.raises(ValueError, match="dense gather fallback"):
        Engine(cfg, params, EngineConfig(paged_kv=True, packed=False))
    with pytest.raises(ValueError, match="dense gather fallback"):
        Engine(cfg, params, EngineConfig(paged_kv=True,
                                         arena_decode=False))
    with pytest.raises(ValueError, match="dense gather fallback"):
        Engine(cfg, params, EngineConfig(paged_kv=True,
                                         arena_prefill=False))
    ecfg = get_smoke("hubert-xlarge")            # encoder-only
    eparams, _ = tr.init_params(ecfg, KEY)
    with pytest.raises(ValueError, match="causal decoder stack"):
        Engine(ecfg, eparams, EngineConfig(paged_kv=True))
    # formerly-excluded architectures now construct paged by default
    for arch in ("mamba2-2.7b", "mixtral-8x7b"):
        acfg = get_smoke(arch)
        aparams, _ = tr.init_params(acfg, KEY)
        aeng = Engine(acfg, aparams, EngineConfig(num_slots=4, max_len=64))
        assert aeng._paged
