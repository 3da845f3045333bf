"""The shared serving drive, its CLI, and chip_smoke.py's refusals."""
import importlib.util
import shutil
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.launch import drive, serve
from repro.models import transformer as tr

ROOT = Path(__file__).resolve().parents[1]


def _load_chip_smoke(path: Path):
    spec = importlib.util.spec_from_file_location("chip_smoke_under_test",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def served():
    cfg = get_smoke("qwen3-4b")
    params, _ = tr.init_params(cfg, jax.random.key(0))
    return cfg, params, drive.serve_script(cfg, params, drive.SMOKE_SIZES)


def test_drive_answers_every_request_with_a_prefix_hit(served):
    cfg, _, res = served
    sizes = drive.SMOKE_SIZES
    assert res.requests == sizes.sessions + 1
    assert res.prefix_hit_tokens > 0
    assert res.decoded_tokens == (sizes.sessions + 1) * sizes.decode_steps
    # every rung was compiled before the window: none inside it
    st = res.engine.stats()
    assert st["packed_shapes"] == len(sizes.token_buckets)
    assert st["decode_shapes"] == len(sizes.decode_buckets)
    assert len(res.compile_seconds) == (len(sizes.token_buckets)
                                        + len(sizes.decode_buckets))
    # the long prompt really was chunked over C_l
    assert len(res.prompts[res.long_session]) > sizes.chunk_tokens


def test_drive_tokens_equal_the_forward_greedy_oracle(served):
    cfg, params, res = served
    pad = drive.SMOKE_SIZES.max_len
    fwd = jax.jit(lambda p, t: tr.forward(p, cfg, tokens=t)[0])
    for s, prompt in res.prompts.items():
        ctx = [int(t) for t in prompt]
        want = []
        for _ in res.generated[s]:
            toks = np.zeros((1, pad), np.int32)
            toks[0, :len(ctx)] = ctx
            logits = fwd(params, jnp.asarray(toks))[0, len(ctx) - 1]
            want.append(int(jnp.argmax(logits[:cfg.vocab_size])))
            ctx.append(want[-1])
        assert res.generated[s] == want, s


def test_drive_reference_logits_match_the_engine(served):
    cfg, params, res = served
    sessions = [0, res.long_session, res.followup]
    ref, kernels = drive.reference_logits(
        params, cfg, [drive.engine_context(res, s) for s in sessions])
    assert kernels == 0
    for s, want in zip(sessions, ref):
        got = res.engine.last_logits[s][:cfg.vocab_size]
        assert drive.rel_err(got, want) < 1e-4, s


def test_chip_smoke_main_exits_nonzero_on_cpu(capsys):
    assert jax.devices()[0].platform == "cpu"
    mod = _load_chip_smoke(ROOT / "chip_smoke.py")
    assert mod.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_chip_smoke_alone_exits_nonzero(tmp_path, capsys):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    mod = _load_chip_smoke(tmp_path / "chip_smoke.py")
    assert mod.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_serve_parser_reaches_get_config_without_smoke(monkeypatch):
    assert serve.parse_args([]).smoke is False
    assert serve.parse_args(["--smoke"]).smoke is True
    assert serve.parse_args(["--no-smoke"]).smoke is False
    args = serve.parse_args(["--max-len", "512", "--num-pages", "64"])
    assert (args.max_len, args.num_pages) == (512, 64)

    class Reached(Exception):
        pass

    def fake_get_config(name):
        raise Reached(name)

    monkeypatch.setattr(serve, "get_config", fake_get_config)
    monkeypatch.setattr(drive, "enable_compile_cache", lambda: "unset")
    with pytest.raises(Reached, match="qwen3-4b"):
        serve.main(["--arch", "qwen3-4b"])


def test_compile_cache_dir_from_env_or_checkout(monkeypatch):
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert drive.enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = drive.enable_compile_cache()
        assert path == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_engine_commits_params_and_pool_to_its_device():
    cfg = get_smoke("qwen3-4b")
    params, _ = tr.init_params(cfg, jax.random.key(0))
    dev = jax.devices()[-1]
    eng = drive.build_engine(cfg, params, drive.SMOKE_SIZES, device=dev)
    for leaf in jax.tree.leaves((eng.params, eng.arena.arena)):
        assert leaf.devices() == {dev}
        assert leaf.committed


def test_init_params_draws_each_layer_and_matches_shapes():
    cfg = get_smoke("qwen3-4b").replace(num_layers=3)
    params, axes = tr.init_params(cfg, jax.random.key(1))
    shapes = tr.param_shapes(cfg)
    assert jax.tree.structure(params) == jax.tree.structure(shapes)
    for got, want in zip(jax.tree.leaves(params), jax.tree.leaves(shapes)):
        assert (got.shape, got.dtype) == (want.shape, want.dtype)
    wq = np.asarray(params["blocks"][0]["mixer"]["wq"])
    assert not np.allclose(wq[0], wq[1]) and not np.allclose(wq[1], wq[2])
    again, _ = tr.init_params(cfg, jax.random.key(1))
    assert np.array_equal(np.asarray(again["embed"]),
                          np.asarray(params["embed"]))
    assert axes == tr.param_axes(cfg)
