#!/usr/bin/env python3
"""Find where a cell saturates, once, by a sweep of offered load on the
chip.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --values 16,24,32 [--key clients] [--warmup <s>]

Runs the cell's set-up, warm-up and window once per value of the
traffic parameter ``--key`` (the closed loop's ``clients`` by default),
in one process, and prints one JSON line per value: requests, output
tokens per second over the window, TTFT and TPOT percentiles, and the
requests open at the window's start and end.  Load is above the knee
where doubling it leaves output tokens per second within the spread of
the runs; the cell's traffic file records the sweep and the load
chosen.  The benchmark's own runs never sweep.
"""
from __future__ import annotations

import time

T_PROC = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def summarize(rec):
    from bench.readers import percentile, tpot_s, ttft_s
    reqs = rec["requests"]
    tt, tp = ttft_s(rec), tpot_s(rec)
    out = {"load": rec["load"], "requests": len(reqs),
           "failed": sum(r["failed"] for r in reqs),
           "open_at_start": rec["open_at_start"],
           "open_at_end": rec["open_at_end"],
           "output_tok_s": rec["output_tokens"] / rec["window_s"],
           "setup_s": rec["setup_s"], "fill": rec["fill"],
           "compiles_in_window": rec["compiles_in_window"],
           "memory_peak_bytes": rec["device"]["memory_peak_bytes"],
           "counters": rec["counters"]}
    for q in (0.5, 0.9, 0.95):
        if tt:
            out[f"ttft_p{int(q * 100)}_ms"] = 1e3 * percentile(tt, q)
        if tp:
            out[f"tpot_p{int(q * 100)}_ms"] = 1e3 * percentile(tp, q)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--values", required=True)
    ap.add_argument("--key", default="clients")
    ap.add_argument("--warmup", type=float, default=None)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(BENCH / ".cache" / "jax")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if jax.devices()[0].platform == "cpu":
        print("the sweep needs an accelerator", file=sys.stderr)
        return 3
    from bench import harness, manifest
    cell = manifest.load_cell(args.workload, ROOT / "BENCHMARK.json")
    for v in args.values.split(","):
        ov = {args.key: int(v) if args.key == "clients" else float(v)}
        if args.warmup is not None:
            ov["warmup_s"] = args.warmup
        rec = harness.serve(cell, args.seed, args.seconds, False,
                            time.monotonic(), overrides=ov)
        print(json.dumps(summarize(rec)), flush=True)
        del rec
    return 0


if __name__ == "__main__":
    sys.exit(main())
