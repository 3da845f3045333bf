"""A copy of the benchmark with a tiny configuration, for CPU tests.

``make_tree(tmp)`` copies ``bench/`` into ``tmp`` and adds a two-layer
qwen3-shaped configuration (d_model 64, vocab 256) and a closed-loop
chat cell, sized so a whole run takes seconds on the CPU.
``add_moe(manifest)`` adds to such a copy, as new files and manifest
entries only, a mixture-of-experts configuration of an architecture the
benchmark does not have (``moe_arch.py``, ``moe_reference.py``).
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


TINY_MOE_HF = {"hidden_size": 64, "intermediate_size": 96,
               "moe_intermediate_size": 32, "num_experts": 8,
               "num_experts_per_tok": 2, "norm_topk_prob": True,
               "num_hidden_layers": 2, "num_attention_heads": 4,
               "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 256,
               "rms_norm_eps": 1e-06, "rope_theta": 1000000.0}

TINY_MOE = dict(
    TINY, name="tiny-moe", source="a two-layer qwen3-moe-shaped test model",
    hf_config=TINY_MOE_HF, registry="qwen3-moe-30b-a3b",
    overrides={"num_layers": 2, "d_model": 64, "num_heads": 4,
               "num_kv_heads": 2, "d_ff": 32, "moe_d_ff": 32,
               "num_experts": 8, "num_experts_per_tok": 2,
               "vocab_size": 256, "head_dim": 16, "dtype": "float32"},
    reference="moe_gqa")


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


def add_moe(man_path: Path, config: dict = TINY_MOE) -> str:
    """Add ``config`` (architecture ``moe_gqa``) and a chat cell on it
    to the copy whose manifest is ``man_path``; returns the cell's
    name."""
    b = Path(man_path).parent / "bench"
    here = Path(__file__).resolve().parent
    shutil.copy(here / "moe_arch.py", b / "arch" / "moe_gqa.py")
    shutil.copy(here / "moe_reference.py", b / "reference" / "moe_gqa.py")
    name = config["name"]
    (b / "configs" / f"{name}.json").write_text(json.dumps(config))
    man = json.loads(Path(man_path).read_text())
    man["configs"].append({"name": name,
                           "file": f"bench/configs/{name}.json"})
    man["workloads"].append({"name": f"{name}.chat", "config": name,
                             "traffic": "tiny-chat", "chips": 1})
    Path(man_path).write_text(json.dumps(man))
    return f"{name}.chat"
