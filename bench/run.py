#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell, its configuration, traffic and metrics are found by name
from ``BENCHMARK.json`` (``bench/manifest.py``).  The last line of
standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``), ``device``,
``breakdown`` (traced runs) and ``checks``, each number compared with
its limit.  Exits non-zero, printing no result, when JAX finds no
accelerator or fewer chips than the cell asks for.

JAX's persistent compilation cache is kept in ``bench/.cache/jax``
inside the checkout, which the program takes from
``JAX_COMPILATION_CACHE_DIR``; only a checkout's first run compiles.
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
CACHE = BENCH / ".cache" / "jax"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import manifest
    cell = manifest.load_cell(args.workload, ROOT / "BENCHMARK.json")

    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()
    if devices[0].platform == "cpu" or len(devices) < cell.chips:
        print(f"needs {cell.chips} accelerator chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 3

    from bench import harness
    out = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                      T_PROC)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
