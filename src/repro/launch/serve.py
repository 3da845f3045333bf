"""Serving launcher: builds a config's params and the paged packed
``Engine``, then serves the shared request script of
``repro.launch.drive`` through ``ServeLoop`` (short prompts, one long
chunked prompt, a prefix-hit second turn, a few decode steps).

The full published config is the default; ``--smoke`` selects the
reduced one for a CPU run.

Example:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b --smoke
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Optional, Sequence

import jax

from repro.configs import get_config, get_smoke
from repro.launch import drive
from repro.models import transformer as tr


def parse_args(argv: Optional[Sequence[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b")
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=False,
                    help="reduced config and sizes (default: full config)")
    ap.add_argument("--max-len", type=int, default=None)
    ap.add_argument("--num-pages", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


def main(argv: Optional[Sequence[str]] = None) -> None:
    args = parse_args(argv)
    cache = drive.enable_compile_cache()
    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    sizes = drive.SMOKE_SIZES if args.smoke else drive.DriveSizes()
    over = {k: v for k, v in (("max_len", args.max_len),
                              ("num_pages", args.num_pages))
            if v is not None}
    sizes = dataclasses.replace(sizes, **over)
    dev = jax.devices()[0]
    print(f"[serve] arch={cfg.name} layers={cfg.num_layers} "
          f"d_model={cfg.d_model} dtype={cfg.dtype} device={dev.platform}/"
          f"{dev.device_kind} compile_cache={cache}")
    t0 = time.perf_counter()
    params, _ = tr.init_params(cfg, jax.random.key(args.seed))
    jax.block_until_ready(params)
    print(f"[serve] params built in {time.perf_counter() - t0:.1f}s")
    res = drive.serve_script(cfg, params, sizes, seed=args.seed)
    for rung, sec in res.compile_seconds.items():
        print(f"[serve] compiled {rung} in {sec:.1f}s")
    print(f"[serve] requests={res.requests} "
          f"prefix_hit_tokens={res.prefix_hit_tokens} "
          f"decoded_tokens={res.decoded_tokens}")
    print(f"[serve] session {res.followup} (second turn) generated: "
          f"{res.generated[res.followup]}")
    print(f"[serve] engine stats: {res.engine.stats()}")


if __name__ == "__main__":
    main()
