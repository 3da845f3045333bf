#!/usr/bin/env python3
"""Chip smoke test: serve qwen3-4b at its full published width in bf16
on TPU through the normal entry points, and check the answers.

    python3 chip_smoke.py             # one chip (the default)
    python3 chip_smoke.py --chips 4   # four one-chip replicas, only

One chip: random params from a seed (36 layers, d_model 2560, 32/8
heads, head_dim 128, vocab 151,936), then ``launch.drive.serve_script``
— ServeLoop → Engine on the paged packed path (packed prefill, paged
decode): short prompts, one long prompt chunked over C_l, a second turn
that hits the radix prefix cache, a few decode steps.  The engine's
last logits of three sessions are compared with ``tr.forward`` (plain
jnp attention, no cache, no Pallas, same bf16 params).

Four chips: four engines, each with its params and page pool on its own
device, behind ``LengthAwareRouter``; the long request prefills on the
prefill-role engine and is handed off to a decode engine on another
chip.  Tokens and last logits are compared with the same requests
served by one engine.

Exits non-zero, printing no result, when JAX finds no TPU or the repo's
``src/`` is not next to this file.  The last line of a passing run is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

SRC = Path(__file__).resolve().parent / "src"

# relative L2 error of the engine's bf16 logits against tr.forward's,
# over the real vocabulary.  The CPU rehearsal of this script on the
# reduced width in bf16 (Pallas kernels in interpret mode) measured at
# most 0.0114 at 2 layers and 0.0260 at 36 layers (0.0270 with the jnp
# oracle kernels); the bound is about three times that.  A wrong kernel
# or page mapping gives errors of order 1.
REL_ERR_BOUND = 0.08


class SmokeFailure(Exception):
    pass


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def one_chip(cfg, sizes, *, seed: int = 0) -> dict:
    """The one-chip phase.  Raises SmokeFailure on any failed check."""
    import jax
    from repro.launch import drive
    from repro.models import transformer as tr

    t0 = time.perf_counter()
    params, _ = tr.init_params(cfg, jax.random.key(seed))
    jax.block_until_ready(params)
    log(f"params built in {time.perf_counter() - t0:.1f}s")
    res = drive.serve_script(cfg, params, sizes, seed=seed)
    for rung, sec in res.compile_seconds.items():
        log(f"compile {rung}: {sec:.1f}s")
    want_requests = sizes.sessions + 1
    log(f"requests answered: {res.requests}/{want_requests}")
    log(f"prefix-hit tokens: {res.prefix_hit_tokens}")
    log(f"decoded tokens: {res.decoded_tokens}")
    check(res.requests == want_requests, "not every request was answered")
    check(res.prefix_hit_tokens > 0, "the second turn missed the prefix cache")
    check(res.decoded_tokens == want_requests * sizes.decode_steps,
          "wrong number of decoded tokens")
    for s, toks in res.generated.items():
        check(all(0 <= t < cfg.vocab_size for t in toks),
              f"session {s} generated a token outside the vocabulary")
    kernels = drive.custom_call_counts(res.engine)
    log(f"tpu_custom_call in compiled steps: {kernels}")

    sessions = [0, res.long_session, res.followup]
    ref, ref_kernels = drive.reference_logits(
        params, cfg, [drive.engine_context(res, s) for s in sessions])
    check(ref_kernels == 0, "the reference ran a Pallas kernel")
    errs = {}
    for s, want in zip(sessions, ref):
        got = res.engine.last_logits[s][:cfg.vocab_size]
        check(bool(np.isfinite(got).all()), f"session {s}: non-finite logits")
        errs[s] = drive.rel_err(got, want)
        log(f"session {s}: reference rel err {errs[s]:.5f} "
            f"(argmax engine {int(np.argmax(got))} "
            f"reference {int(np.argmax(want))})")
    check(max(errs.values()) <= REL_ERR_BOUND,
          f"engine logits differ from tr.forward by more than "
          f"{REL_ERR_BOUND}: {errs}")
    return {"kernels": kernels, "rel_err": errs}


def four_chips(cfg, sizes, devices, *, seed: int = 0) -> dict:
    """The four-chip phase: one engine per device behind the length-aware
    router, a forced cross-chip handoff, compared with one engine."""
    import jax
    from repro.core.routing import LengthAwareRouter
    from repro.launch import drive
    from repro.models import transformer as tr
    from repro.serving import ServeCluster

    check(len(devices) >= 4, f"need 4 devices, found {len(devices)}")
    params, _ = tr.init_params(cfg, jax.random.key(seed))
    prompts = drive.script_prompts(cfg.vocab_size, sizes, seed)

    # the same requests on one engine (device 0) first
    single = drive.build_engine(cfg, params, sizes, device=devices[0])
    drive.precapture(single)
    loop = drive.make_loop(single, sizes)
    for s, toks in prompts.items():
        loop.submit(s, toks, decode_tokens=sizes.decode_steps)
    drive.drain(loop, 600.0)
    want = {s: (list(loop.generated[s]),
                single.last_logits[s][:cfg.vocab_size]) for s in prompts}
    del single, loop

    engines = [drive.build_engine(cfg, params, sizes, device=d)
               for d in devices[:4]]
    t0 = time.perf_counter()
    for e in engines:
        drive.precapture(e)
    log(f"compiled 4 engines' rungs in {time.perf_counter() - t0:.1f}s")
    for i, e in enumerate(engines):
        on = {d for leaf in jax.tree.leaves((e.params, e.arena.arena))
              for d in leaf.devices()}
        check(on == {devices[i]}, f"engine {i} is not placed on device {i}")
    cluster = ServeCluster(
        [drive.make_loop(e, sizes) for e in engines],
        LengthAwareRouter(threshold=sizes.chunk_tokens),
        roles=["prefill", "decode", "decode", "decode"],
        migrate_decodes=True)
    for s, toks in prompts.items():
        cluster.submit(s, toks, decode_tokens=sizes.decode_steps)
    cluster.run_until_idle(max_wall=600.0)
    st = cluster.stats()
    homes = {s: cluster.engine_of(s) for s in prompts}
    log(f"homes: {homes}; migrated sessions: {st['migrated_sessions']}; "
        f"handoff host bytes: {st['handoff_host_bytes']}")
    check(cluster.report().n == len(prompts), "not every request answered")
    check(st["migrated_sessions"] >= 1, "no cross-chip handoff happened")
    check(st["handoff_host_bytes"] == 0, "a handoff went through the host")
    errs = {}
    for s, (toks, logits) in want.items():
        eng = cluster.loops[homes[s]].engine
        check(cluster.generated(s) == toks,
              f"session {s}: tokens differ from the one-engine run")
        errs[s] = drive.rel_err(eng.last_logits[s][:cfg.vocab_size], logits)
    log(f"rel err vs one engine: {errs}")
    check(max(errs.values()) <= REL_ERR_BOUND,
          f"replica logits differ from one engine: {errs}")
    return {"migrated": st["migrated_sessions"], "rel_err": errs}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: {SRC / 'repro'} not found — run from a "
              "checkout of the repo", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    from repro.configs import get_config
    from repro.kernels import ops as kernel_ops
    from repro.launch import drive

    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"compile cache: {drive.enable_compile_cache()}")
    check(kernel_ops._use_pallas() and kernel_ops.on_tpu(),
          "kernels would not run compiled Pallas")
    cfg = get_config("qwen3-4b")
    try:
        if args.chips == 4:
            sizes = dataclasses.replace(drive.DriveSizes(),
                                        token_buckets=(512,),
                                        decode_buckets=(8,))
            four_chips(cfg, sizes, devices, seed=args.seed)
        else:
            out = one_chip(cfg, drive.DriveSizes(), seed=args.seed)
            for kind in ("packed_paged", "paged_decode"):
                check(out["kernels"].get(kind, 0) > 0,
                      f"compiled {kind} step holds no tpu_custom_call")
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    stats = dev.memory_stats() or {}
    log(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
