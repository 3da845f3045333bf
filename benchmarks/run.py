"""Benchmark harness — one module per paper table/figure.

Prints the ``name,us_per_call,derived`` CSV contract per row, plus a
readable table per bench.  ``--only fig7`` runs a single bench.
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

BENCHES = [
    ("fig1+fig3", "benchmarks.bench_interference"),
    ("fig2", "benchmarks.bench_lengths"),
    ("fig5", "benchmarks.bench_window"),
    ("fig6", "benchmarks.bench_endtoend"),
    ("fig7", "benchmarks.bench_slo"),
    ("fig8", "benchmarks.bench_mix"),
    ("table2", "benchmarks.bench_offline"),
    ("graphs", "benchmarks.bench_graphs"),
    ("kernels", "benchmarks.bench_kernels"),
    ("roofline", "benchmarks.roofline"),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--out", default="reports/bench")
    args = ap.parse_args()
    from repro.launch.drive import enable_compile_cache
    enable_compile_cache()

    os.makedirs(args.out, exist_ok=True)
    all_rows = []
    for name, module in BENCHES:
        if args.only and args.only not in name:
            continue
        t0 = time.perf_counter()
        mod = importlib.import_module(module)
        rows = mod.run()
        dt = time.perf_counter() - t0
        print(f"# === {name} ({module}) [{dt:.1f}s] ===")
        for row in rows:
            us = row.get("mean_ms", 0.0) * 1e3
            derived = ";".join(
                f"{k}={v}" for k, v in row.items()
                if k not in ("bench", "tag", "mean_ms"))
            print(f"{row.get('bench', name)}/{row.get('tag', '')},"
                  f"{us:.1f},{derived}")
        all_rows.extend(rows)
        with open(os.path.join(args.out, "results.json"), "w") as f:
            json.dump(all_rows, f, indent=1, default=str)
    print(f"# wrote {len(all_rows)} rows to {args.out}/results.json")


if __name__ == "__main__":
    main()
