"""The main-path Pallas kernels compile for a TPU v5e chip at qwen3-4b
widths (Hq=32, Hkv=8, D=128, page_size=16, bf16).

The chip is described, not attached: the TPU compiler installed next to
JAX lowers and compiles for it and raises what the chip's compiler
would raise (tiling, VMEM).  Nothing runs.  The topology is described
inside a module fixture, never at import, so every test worker collects
the same tests and only the worker given this file loads the TPU
library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import decode_attn, ragged_prefill, sampling

HQ, HKV, D, PS = 32, 8, 128, 16
B, P_MAX, N_PAGES = 8, 128, 1025          # 8 sessions, max_len 2048
WINDOW = 256


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                        # pragma: no cover
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("window", [None, WINDOW])
@pytest.mark.parametrize("tokens", [128, 512])
def test_ragged_prefill_paged_compiles_for_v5e(one_chip, tokens, window):
    s = lambda shape, dt=jnp.int32: _shape(one_chip, shape, dt)
    pool = s((N_PAGES, PS, HKV, D), jnp.bfloat16)
    txt = _compiled_text(
        lambda q, k, v, pt, cu, off, kl: ragged_prefill.ragged_prefill_paged(
            q, k, v, pt, cu, off, kl, window=window, interpret=False),
        s((tokens, HQ, D), jnp.bfloat16), pool, pool, s((B, P_MAX)),
        s((B + 1,)), s((B,)), s((B,)))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("window", [None, WINDOW])
@pytest.mark.parametrize("rows", [1, 8])
def test_decode_attn_paged_compiles_for_v5e(one_chip, rows, window):
    s = lambda shape, dt=jnp.int32: _shape(one_chip, shape, dt)
    pool = s((N_PAGES, PS, HKV, D), jnp.bfloat16)
    txt = _compiled_text(
        lambda q, k, v, pt, kl: decode_attn.decode_attn_paged(
            q, k, v, pt, kl, window=window, interpret=False),
        s((rows, HQ, D), jnp.bfloat16), pool, pool, s((rows, P_MAX)),
        s((rows,)))
    assert "tpu_custom_call" in txt


def test_fused_sample_refused_for_v5e_and_engine_says_so(one_chip,
                                                         monkeypatch):
    """The row block meets the tiling rule, but the inverse-CDF draw
    needs cumsum, which Pallas cannot lower for the chip — so an Engine
    asked for fused sampling on a TPU refuses with a clear error."""
    s = lambda shape, dt=jnp.int32: _shape(one_chip, shape, dt)
    r, v, f32 = 8, 151_936, jnp.float32
    with pytest.raises(NotImplementedError, match="cumsum"):
        jax.jit(sampling.fused_sample).lower(
            s((r, v), f32), s((r,), f32), s((r,)), s((r,), f32),
            s((r, sampling.MAX_BIAS)), s((r, sampling.MAX_BIAS), f32),
            s((r,), f32), s((r,))).compile()

    from repro.configs import get_smoke
    from repro.kernels import ops
    from repro.models import transformer as tr
    from repro.serving import Engine, EngineConfig
    cfg = get_smoke("qwen3-4b")
    params, _ = tr.init_params(cfg, jax.random.key(0))
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    with pytest.raises(ValueError, match="fused_sampling"):
        Engine(cfg, params, EngineConfig(num_slots=2, max_len=64,
                                         fused_sampling=True))
