#!/usr/bin/env python3
"""Readings that set a cell's correctness limit, on the chip.

    python3 bench/control.py --workload <cell> --seeds 1,2,...,12 \
        --control-seeds 1,2,3 --seconds 10

For each seed, in one process: the cell's set-up and a short window at
the cell's own load, then the check's sample of finished requests.  It
prints, per seed, the program's reading (the widest gap of a served
token below the float32 reference's best) and, on the control seeds,
the control's reading: the reference computed in fp8 (weights and
matmul inputs) put in the program's place, the gap of the token it
puts first at the same positions.  The limit in the configuration file lies between the
largest program reading and the smallest control reading.  The
benchmark's own runs never run the control.
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


def readings(cell, seed: int, seconds: float, control: bool):
    from bench import check, harness, manifest
    rec = harness.serve(cell, seed, seconds, False, time.monotonic())
    reqs = harness.check_sample(cell, rec)
    cf = cell.config
    prog, ctrl, n = check.gaps(manifest.reference_module(cell),
                               cf["hf_config"], cf["architecture"],
                               rec["params"], reqs,
                               control="fp8" if control else None)
    return {"seed": seed, "program_gap": prog, "control_gap": ctrl,
            "tokens": n, "requests": len(reqs),
            "attempted": len(rec["requests"]),
            "failed": sum(r["failed"] for r in rec["requests"])}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(BENCH / ".cache" / "jax")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if jax.devices()[0].platform == "cpu":
        print("the control readings need an accelerator", file=sys.stderr)
        return 3
    from bench import manifest
    cell = manifest.load_cell(args.workload, ROOT / "BENCHMARK.json")
    ctrl = {int(s) for s in args.control_seeds.split(",") if s}
    for s in (int(x) for x in args.seeds.split(",")):
        print(json.dumps(readings(cell, s, args.seconds, s in ctrl)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
