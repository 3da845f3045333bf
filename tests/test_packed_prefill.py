"""Padding-free packed prefill: engine parity with the pure forward,
token-bucket compile-cache growth, padding counters, ladder packing,
AWD packed batching, and executor donation-flag handling."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.core.awd import AWDConfig, AWDScheduler
from repro.core.buckets import BucketGrid, TokenBucketLadder
from repro.core.request import Request
from repro.models import transformer as tr
from repro.serving import Engine, EngineConfig, PackedBucketExecutor

KEY = jax.random.key(3)


@pytest.fixture(scope="module")
def qwen():
    cfg = get_smoke("qwen3-4b")
    params, _ = tr.init_params(cfg, KEY)
    return cfg, params


def packed_engine(cfg, params, **kw):
    defaults = dict(num_slots=8, max_len=64, packed=True,
                    token_buckets=(64, 128, 256))
    defaults.update(kw)
    return Engine(cfg, params, EngineConfig(**defaults))


def greedy(params, cfg, seq):
    lo, _, _ = tr.forward(params, cfg, tokens=jnp.asarray(seq, jnp.int32)[None])
    return int(jnp.argmax(lo[0, -1]))


# ---------------------------------------------------------------- engine


@pytest.mark.parametrize("arch", ["qwen3-4b", "qwen2.5-14b"])
def test_packed_matches_pure_forward(arch):
    """Mixed-length packed batch + decode + packed re-prefill all agree
    with the unbatched pure forward (qk_norm and qkv_bias variants)."""
    rng = np.random.default_rng(0)
    cfg = get_smoke(arch)
    params, _ = tr.init_params(cfg, KEY)
    eng = packed_engine(cfg, params)
    lens = [7, 23, 12]
    seqs = [rng.integers(0, cfg.vocab_size, l) for l in lens]
    out = eng.prefill_packed([0, 1, 2], seqs)
    for i, s in enumerate(seqs):
        assert out[i] == greedy(params, cfg, list(s))
    dec = eng.decode_batch([0], [out[0]], steps=2)
    t2 = rng.integers(0, cfg.vocab_size, 9)
    out2 = eng.prefill_packed([0, 1], [t2, rng.integers(0, cfg.vocab_size, 5)])
    ctx = list(seqs[0]) + [out[0]] + dec[0][:1] + list(t2)
    assert out2[0] == greedy(params, cfg, ctx)


def test_packed_compile_cache_keyed_on_token_bucket(qwen):
    """Different length MIXES under one total-token bucket share ONE
    compiled shape; the dense grid compiles one shape per (L, B)."""
    cfg, params = qwen
    rng = np.random.default_rng(1)
    eng = packed_engine(cfg, params)
    mixes = [[7, 23, 12], [40], [3, 3, 3, 3], [16, 16]]   # all ≤ 64 total
    s = 0
    for mix in mixes:
        eng.prefill_packed(list(range(s, s + len(mix))),
                           [rng.integers(0, cfg.vocab_size, l) for l in mix])
        for sess in range(s, s + len(mix)):
            eng.close_session(sess)
        s += len(mix)
    st = eng.stats()
    assert st["packed_shapes"] == 1
    assert eng.packed_executor.hits == len(mixes) - 1
    # one more mix in a bigger bucket → exactly one more shape
    eng.prefill_packed([90, 91], [rng.integers(0, cfg.vocab_size, 61),
                                  rng.integers(0, cfg.vocab_size, 40)])
    assert eng.stats()["packed_shapes"] == 2


def test_packed_beats_grid_padding(qwen):
    """Acceptance: the mixed batch (7, 23, 61, 12) pads ≥2× less through
    the packed path than through the (L, B) grid."""
    cfg, params = qwen
    rng = np.random.default_rng(2)
    lens = [7, 23, 61, 12]
    seqs = [rng.integers(0, cfg.vocab_size, l) for l in lens]

    eng = packed_engine(cfg, params, max_len=128, token_buckets=(64, 128, 256))
    eng.prefill_packed([0, 1, 2, 3], seqs)
    packed_pad = eng.packed_executor.padded_tokens

    grid_bucket = eng.grid.nearest_graph(lens)
    eng2 = Engine(cfg, params, EngineConfig(num_slots=8, max_len=128,
                                            paged_kv=False))
    eng2.prefill_batch([0, 1, 2, 3], seqs, bucket=grid_bucket.key)
    dense_pad = eng2.executor.padded_tokens

    assert sum(lens) == eng.packed_executor.useful_tokens
    assert dense_pad >= 2 * packed_pad, (dense_pad, packed_pad)


def test_packed_fallback_paths(qwen):
    """Capability routing (§7): every CAUSAL arch is packed-servable
    (mamba rides the SSM state arena); encoder-only models raise; and
    off-ladder totals still fall back to the dense path."""
    cfg, params = qwen
    rng = np.random.default_rng(3)
    # mamba: arena-resident packed serving by default
    mcfg = get_smoke("mamba2-2.7b")
    mparams, _ = tr.init_params(mcfg, KEY)
    meng = packed_engine(mcfg, mparams)
    assert meng.packed_executor is not None
    out = meng.prefill_packed([0], [rng.integers(0, mcfg.vocab_size, 6)])
    assert 0 in out
    assert meng.packed_executor.total_tokens > 0
    # encoder-only (no causal decode loop) is the remaining hard wall
    with pytest.raises(ValueError):
        PackedBucketExecutor(get_smoke("hubert-xlarge"))
    # off-ladder total → dense fallback, counters stay on the dense side
    # (a slot-arena concern: the paged pool splits instead, §12)
    eng = packed_engine(cfg, params, token_buckets=(16,), max_len=64,
                        paged_kv=False)
    eng.prefill_packed([0], [rng.integers(0, cfg.vocab_size, 30)])
    assert eng.packed_executor.total_tokens == 0
    assert eng.executor.total_tokens == 30


# ---------------------------------------------------------------- ladder


def test_token_ladder_lookup():
    lad = TokenBucketLadder((64, 128, 256), max_seqs=4)
    assert lad.bucket_for(1) == 64
    assert lad.bucket_for(64) == 64
    assert lad.bucket_for(65) == 128
    assert lad.bucket_for(256) == 256
    assert lad.bucket_for(257) is None
    assert lad.covers(256) and not lad.covers(300)
    assert lad.padding_waste([7, 23, 12]) == pytest.approx(1 - 42 / 64)


# ------------------------------------------------------------------- awd


def test_awd_packed_emits_token_buckets():
    grid = BucketGrid()
    awd = AWDScheduler(grid, AWDConfig(packed=True, token_buckets=(64, 128),
                                       packed_max_seqs=8))
    reqs = [Request(new_tokens=l, arrival=0.0) for l in [7, 23, 31]]
    batch, _ = awd.decide(list(reqs), now=1.0, force=True)
    assert batch is not None and batch.is_packed and batch.uses_graph
    assert batch.token_bucket == 64
    assert batch.padded_tokens == 64
    assert all(r.used_graph and r.padded_to is None for r in batch.requests)


def test_awd_mixed_emit_shrinks_fusion_to_fit_ladder():
    """A near-full batch plus a decode backlog must fuse FEWER decodes
    rather than falling off the packed path entirely: 126 prefill
    tokens + backlog 4 busts the 128 bucket, so exactly 2 fuse."""
    awd = AWDScheduler(BucketGrid(), AWDConfig(packed=True,
                                               token_buckets=(64, 128),
                                               packed_max_seqs=16))
    awd.note_decode_backlog(4)
    batch, _ = awd.decide([Request(new_tokens=126, arrival=0.0)], now=1.0,
                          force=True)
    assert batch is not None and batch.is_packed
    assert batch.token_bucket == 128
    assert batch.decode_tokens == 2
    assert batch.tokens + batch.decode_tokens <= batch.token_bucket


def test_awd_packed_profitability_guard():
    """A batch too small for the token bucket flunks max_pad_ratio and
    falls back to the dense (L, B) grid — a captured shape still beats
    an eager compile of the exact batch shape."""
    grid = BucketGrid()
    awd = AWDScheduler(grid, AWDConfig(packed=True, token_buckets=(512,),
                                       max_pad_ratio=1.5))
    batch, _ = awd.decide([Request(new_tokens=8, arrival=0.0)], now=1.0,
                          force=True)
    assert batch is not None and batch.token_bucket is None
    assert batch.uses_graph and (batch.bucket_len, batch.bucket_depth) == (8, 1)
    # off-grid AND off-bucket → standard unpadded kernel
    awd2 = AWDScheduler(grid, AWDConfig(packed=True, token_buckets=(512,),
                                        max_pad_ratio=1.5))
    batch2, _ = awd2.decide([Request(new_tokens=5, arrival=0.0)], now=1.0,
                            force=True)
    assert batch2 is not None and not batch2.uses_graph
    assert batch2.token_bucket is None and batch2.bucket_len is None


# -------------------------------------------------------------- donation


def test_resolve_donation_respects_explicit_flag():
    # donation is unconditional: every executor donates its cache
    # argument on every backend, so the CPU tests run the chip's path
    import repro.serving.executor as ex_mod
    assert not hasattr(ex_mod, "resolve_donation")
    for cls in (ex_mod.BucketExecutor, ex_mod.PackedBucketExecutor,
                ex_mod.DecodeBucketExecutor):
        assert "donate_cache" not in cls.__init__.__code__.co_varnames


def test_executor_donation_applied_on_cpu(qwen):
    """The cache argument must actually be donated off-TPU too: the
    input cache buffer is invalidated by the step."""
    cfg, params = qwen
    from repro.serving.executor import BucketExecutor
    ex = BucketExecutor(cfg)
    caches = tr.init_cache(cfg, 1, 16)
    tokens = jnp.zeros((1, 4), jnp.int32)
    positions = jnp.tile(jnp.arange(4), (1, 1))
    _, new_caches = ex.prefill(params, tokens, positions, caches,
                               jnp.asarray([3]))
    assert jax.tree.leaves(caches)[0].is_deleted()
    assert not jax.tree.leaves(new_caches)[0].is_deleted()
