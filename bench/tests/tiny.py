"""A copy of the benchmark with a tiny configuration, for CPU tests.

``make_tree(tmp)`` copies ``bench/`` into ``tmp`` and adds a two-layer
qwen3-shaped configuration (d_model 64, vocab 256) and a closed-loop
chat cell, sized so a whole run takes seconds on the CPU.
"""
from __future__ import annotations

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]

TINY_HF = {"hidden_size": 64, "intermediate_size": 128,
           "num_hidden_layers": 2, "num_attention_heads": 4,
           "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 256,
           "rms_norm_eps": 1e-06, "rope_theta": 1000000.0}

TINY = {
    "name": "tiny", "source": "a two-layer qwen3-shaped test model",
    "hf_config": TINY_HF, "reduced": [],
    "architecture": {"qk_norm": True, "qkv_bias": False,
                     "dtype": "float32"},
    "registry": "qwen3-4b",
    "overrides": {"num_layers": 2, "d_model": 64, "num_heads": 4,
                  "num_kv_heads": 2, "d_ff": 128, "vocab_size": 256,
                  "head_dim": 16, "dtype": "float32"},
    "reference": "dense_gqa",
    "deployment": {"sessions": 4, "max_len": 512, "num_pages": 160},
    "check": {"max_logit_gap": 1e-3},
}

TRAFFIC = {
    "chat": {"kind": "sessions", "clients": 6, "stagger_s": 0.2,
             "warmup_s": 0.5, "check_requests": 3,
             "system_prompts": {"count": 2,
                                "length": {"loguniform": {"min": 32,
                                                          "max": 64}},
                                "popularity": {"zipf": {"n": 2, "a": 1.2}}},
             "turns": {"geometric": {"mean": 2.5}, "max": 4},
             "user_tokens": {"lognormal": {"median": 24, "sigma": 1.0},
                             "min": 4, "max": 200},
             "output_tokens": {"lognormal": {"median": 6, "sigma": 0.5},
                               "min": 2, "max": 12},
             "think_s": {"exponential": {"mean": 0.05}},
             "context_cap": 400},
}


def make_tree(tmp: Path) -> Path:
    """Returns the path of the copy's manifest."""
    dst = Path(tmp) / "bench"
    shutil.copytree(BENCH, dst, ignore=shutil.ignore_patterns(
        ".cache", ".trace", "out", "__pycache__"))
    (dst / "configs" / "tiny.json").write_text(json.dumps(TINY))
    for name, t in TRAFFIC.items():
        (dst / "traffic" / f"tiny-{name}.json").write_text(json.dumps(t))
    man = {
        "configs": [{"name": "tiny", "file": "bench/configs/tiny.json"}],
        "workloads": [{"name": f"tiny.{n}", "config": "tiny",
                       "traffic": f"tiny-{n}", "chips": 1}
                      for n in TRAFFIC],
        "end_to_end": [
            {"name": "output_tok_s", "unit": "tokens/s"},
            {"name": "setup_s", "unit": "s"}],
        "per_layer": [
            {"name": "ttft_p50_ms.sat", "unit": "ms"},
            {"name": "prefix_hit_share", "unit": "%"}],
    }
    path = Path(tmp) / "BENCHMARK.json"
    path.write_text(json.dumps(man))
    return path
