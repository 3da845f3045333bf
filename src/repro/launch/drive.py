"""The serving drive shared by ``launch/serve.py`` and ``chip_smoke.py``.

``serve_script`` builds an ``Engine`` on the default paged packed path
for a config, compiles every paged rung up front, and serves a fixed
request script through ``ServeLoop``:

  * short prompts, one per session;
  * one long prompt, prefilled in C_l chunks;
  * a second turn that resends a session's transcript plus new tokens
    under a fresh session id, so the radix prefix index serves its
    committed pages;
  * a few decode steps for every request.

It returns the prompts, the generated tokens and the engine's counters.
``reference_logits`` is the oracle the answers are checked against:
``tr.forward`` over the whole context with plain jnp attention, no
cache and no Pallas kernel.

``enable_compile_cache`` points JAX's persistent compilation cache at
``JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads the variable
itself) and at ``.jax_cache/`` in the checkout otherwise.
"""
from __future__ import annotations

import dataclasses
import os
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import H200_QWEN32B, Variant, make_policy
from repro.core.awd import AWDConfig
from repro.models import transformer as tr
from repro.models.config import ModelConfig
from repro.serving import Engine, EngineConfig
from repro.serving.loop import ServeLoop

REPO_ROOT = Path(__file__).resolve().parents[3]
CACHE_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its path.

    A fixed path is part of the cache's key: a directory that moved
    between runs would never hit, so the fallback lives in the checkout,
    never in a temp dir."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


@dataclasses.dataclass(frozen=True)
class DriveSizes:
    """Engine and request-script sizes of one drive."""
    sessions: int = 8
    max_len: int = 2048
    page_size: int = 16
    num_pages: int = 1024
    chunk_tokens: int = 256              # C_l
    token_buckets: Tuple[int, ...] = (128, 512)
    decode_buckets: Tuple[int, ...] = (1, 2, 4, 8)
    short_len: Tuple[int, int] = (40, 120)   # [lo, hi) prompt tokens
    long_len: int = 1000
    followup_len: int = 24               # new tokens of the second turn
    decode_steps: int = 4


SMOKE_SIZES = DriveSizes(max_len=256, num_pages=160, chunk_tokens=32,
                         token_buckets=(64, 128), short_len=(20, 40),
                         long_len=100, followup_len=8)


@dataclasses.dataclass
class DriveResult:
    engine: Engine
    prompts: Dict[int, np.ndarray]       # session -> full prompt served
    generated: Dict[int, List[int]]      # session -> first + decoded tokens
    followup: int                        # the second turn's session id
    long_session: int
    compile_seconds: Dict[str, float]    # rung -> seconds
    requests: int                        # turns answered
    prefix_hit_tokens: int
    decoded_tokens: int


def build_engine(cfg: ModelConfig, params, sizes: DriveSizes,
                 device: Optional[jax.Device] = None) -> Engine:
    return Engine(cfg, params, EngineConfig(
        num_slots=sizes.sessions, max_len=sizes.max_len,
        chunk_tokens=sizes.chunk_tokens, token_buckets=sizes.token_buckets,
        decode_buckets=sizes.decode_buckets, page_size=sizes.page_size,
        num_pages=sizes.num_pages), device=device)


def precapture(engine: Engine) -> Dict[str, float]:
    """Compile every paged rung the script can meet: each token bucket
    of the packed step and each decode bucket.  Returns seconds per
    rung, so that no compile lands inside the serving window."""
    ar = engine.arena
    out = {f"packed_paged T={t}": s for t, s in
           engine.packed_executor.precapture_paged(
               engine.params, ar.arena, ar.max_pages_per_seq).items()}
    out.update({f"paged_decode B={b}": s for b, s in
                engine.decode_executor.precapture_paged(
                    engine.params, ar.arena, ar.max_pages_per_seq).items()})
    return out


def make_loop(engine: Engine, sizes: DriveSizes) -> ServeLoop:
    awd_cfg = AWDConfig(packed=True, token_buckets=sizes.token_buckets,
                        packed_max_seqs=engine.packed_executor.max_seqs)
    policy = make_policy(Variant.PLA_FULL, H200_QWEN32B,
                         threshold=sizes.chunk_tokens,
                         chunk_tokens=sizes.chunk_tokens, awd_cfg=awd_cfg)
    return ServeLoop(engine, policy, slo_ttft=None)


def script_prompts(vocab: int, sizes: DriveSizes,
                   seed: int) -> Dict[int, np.ndarray]:
    """First-turn prompts: shorts on sessions 0..n-2, the long on n-1."""
    rng = np.random.default_rng(seed)
    out = {s: rng.integers(0, vocab, int(rng.integers(*sizes.short_len)))
           for s in range(sizes.sessions - 1)}
    out[sizes.sessions - 1] = rng.integers(0, vocab, sizes.long_len)
    return out


def drain(loop: ServeLoop, max_wall: float) -> None:
    loop.run_until_idle(max_wall=max_wall)
    rep = loop.tracker.report()
    if loop.has_work or rep.abandoned or rep.rejected:
        raise RuntimeError(
            f"serving did not drain: abandoned={rep.abandoned} "
            f"rejected={rep.rejected} still_queued={loop.has_work}")


def serve_script(cfg: ModelConfig, params, sizes: DriveSizes = DriveSizes(),
                 *, seed: int = 0, max_wall: float = 600.0) -> DriveResult:
    engine = build_engine(cfg, params, sizes)
    compile_seconds = precapture(engine)
    loop = make_loop(engine, sizes)
    prompts = script_prompts(cfg.vocab_size, sizes, seed)
    for s, toks in prompts.items():
        loop.submit(s, toks, decode_tokens=sizes.decode_steps)
    drain(loop, max_wall)

    # second turn: the client resends session 0's transcript plus new
    # tokens under a fresh id — its committed full pages are adopted
    # from the radix index instead of being prefilled again
    rng = np.random.default_rng(seed + 1)
    followup = sizes.sessions
    hist = np.concatenate([prompts[0], np.asarray(loop.generated[0][:-1])])
    prompts[followup] = np.concatenate(
        [hist, rng.integers(0, cfg.vocab_size, sizes.followup_len)])
    loop.submit(followup, prompts[followup],
                decode_tokens=sizes.decode_steps)
    drain(loop, max_wall)

    generated = {s: list(loop.generated[s]) for s in prompts}
    stats = engine.stats()
    return DriveResult(
        engine=engine, prompts=prompts, generated=generated,
        followup=followup, long_session=sizes.sessions - 1,
        compile_seconds=compile_seconds,
        requests=loop.tracker.report().n,
        prefix_hit_tokens=int(stats["prefix_hit_tokens"]),
        decoded_tokens=sum(len(g) - 1 for g in generated.values()))


def custom_call_counts(engine: Engine) -> Dict[str, int]:
    """``tpu_custom_call`` occurrences (compiled Pallas kernels) in each
    compiled step, by dispatch kind — 0 where a kernel ran in interpret
    mode or gave way to the jnp oracle."""
    out: Dict[str, int] = {}
    for ex in (engine.packed_executor, engine.decode_executor):
        for key, exe in ex._compiled.items():
            out[key[0]] = out.get(key[0], 0) + \
                exe.as_text().count("tpu_custom_call")
    return out


def reference_logits(params, cfg: ModelConfig,
                     contexts: Sequence[Sequence[int]],
                     pad_to: int = 128) -> Tuple[np.ndarray, int]:
    """Last-position logits of each context from ``tr.forward``: plain
    jnp attention over the whole context, no cache, no Pallas kernel.
    Contexts are right-padded to one multiple of ``pad_to`` so all share
    one compile (causal: the pad never reaches an earlier position).
    Returns ((n, vocab_size) float32 logits, the ``tpu_custom_call``
    count of the compiled reference — 0 proves no kernel ran in it)."""
    lp = -(-max(len(c) for c in contexts) // pad_to) * pad_to
    toks = np.zeros((len(contexts), 1, lp), np.int32)
    for i, c in enumerate(contexts):
        toks[i, 0, :len(c)] = np.asarray(c, np.int32)
    fwd = jax.jit(lambda p, t: tr.forward(p, cfg, tokens=t)[0]).lower(
        params, jax.ShapeDtypeStruct((1, lp), jnp.int32)).compile()
    rows = [np.asarray(fwd(params, jnp.asarray(toks[i]))[
        0, len(c) - 1, :cfg.vocab_size], np.float32)
        for i, c in enumerate(contexts)]
    return np.stack(rows), fwd.as_text().count("tpu_custom_call")


def engine_context(result: DriveResult, session: int) -> List[int]:
    """The context whose next-token logits the engine kept last for
    ``session``: its prompt plus every generated token but the newest."""
    return list(result.prompts[session]) + result.generated[session][:-1]


def rel_err(got: np.ndarray, want: np.ndarray) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))

