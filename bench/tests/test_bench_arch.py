"""The architecture a configuration names (bench/arch/<name>.py): the
dense module draws the very weights and reads the very numbers the
benchmark drew and read before the counts moved into it, and a
configuration the program disagrees with is refused."""
import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, manifest, trace
from bench.tests import tiny

dense = manifest.arch_by_name(manifest.BENCH_DIR, "dense_gqa")
QWEN3_4B = json.loads(
    (manifest.BENCH_DIR / "configs" / "qwen3-4b.json").read_text())


def digest(tree) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


# sha256 over every leaf's path, dtype, shape and bytes, recorded from
# bench/weights.make before the dense draw moved into its module
@pytest.mark.parametrize("arch,seed,dtype,want", [
    (tiny.TINY["architecture"], 2**35 + 9, jnp.float32,
     "a7782d208f5eb18fd836849fbf633d72fb6951f5acf55bd3196e625a92ac9a60"),
    ({"qk_norm": False, "qkv_bias": True}, 7, jnp.bfloat16,
     "aa5f55ccc5c681a2382cb31aa577ef51bb92baa61c53083eb6bbdbd562824c74"),
], ids=["qk_norm-f32", "qkv_bias-bf16"])
def test_dense_weights_are_bit_identical_to_the_old_draw(arch, seed,
                                                         dtype, want):
    assert digest(dense.make(tiny.TINY_HF, arch, seed, dtype)) == want


def test_dense_shapes_of_qwen3_4b_are_the_programs():
    from repro.configs import get_config
    from repro.models import transformer as tr
    got = dense.shapes(QWEN3_4B["hf_config"], QWEN3_4B["architecture"])
    want = tr.param_shapes(get_config("qwen3-4b"))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert [(a.shape, a.dtype) for a in jax.tree.leaves(got)] == \
        [(a.shape, a.dtype) for a in jax.tree.leaves(want)]


def saved_record():
    """A traced run's record at qwen3-4b widths: decode and packed steps
    as the harness logs them, both kernels and a fusion in the trace."""
    ops = ([trace.Op("decode_attn_paged", "jit_decode_step", 0.0, 0.0113,
                     0)] * 36 * 3
           + [trace.Op("ragged_prefill_paged", "jit_packed_step", 0.0,
                       0.0517, 0)] * 36 * 2
           + [trace.Op("fusion", "jit_decode_step", 0.0, 0.0041, 0)] * 50)
    red = trace.Reduction(window_s=15.047316, busy_s=12.51, devices=1,
                          ops=ops, gaps=[])
    steps = [
        {"kind": "decode", "segs": [(1, 811 + 97 * i) for i in range(16)]},
        {"kind": "packed", "segs": [(256, 0), (180, 1203), (1, 3001),
                                    (1, 517)]},
        {"kind": "decode", "segs": [(1, 4093), (1, 2)] + [(1, 0)] * 3},
        {"kind": "packed", "segs": [(37, 6000), (256, 256)]
         + [(1, 1999)] * 9},
        {"kind": "decode", "segs": [(1, 8191)] * 16}]
    return {"trace": red, "steps": steps, "hf": QWEN3_4B["hf_config"],
            "arch": "dense_gqa", "bench_dir": str(manifest.BENCH_DIR),
            "peak": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}


# read from saved_record() by the readers before the counts moved
# into bench/arch/dense_gqa.py
@pytest.mark.parametrize("metric,want", [
    ("step_mfu.serve", 0.20712054070717165),
    ("decode_attn_roofline", 2.359750152174931),
    ("prefill_attn_roofline", 0.15800762830550066),
])
def test_readers_read_what_they_read_before_the_move(metric, want):
    cell = manifest.load_cell("qwen3-4b.chat-mt-sat")
    assert manifest.metric_reader(cell, metric).read(saved_record()) == want


@pytest.mark.parametrize("key,value", [("num_experts", 16),
                                       ("moe_intermediate_size", 48),
                                       ("norm_topk_prob", False)])
def test_moe_config_the_program_disagrees_with_is_refused(tmp_path, key,
                                                          value):
    man_path = tiny.make_tree(tmp_path)
    cfg = json.loads(json.dumps(tiny.TINY_MOE))
    cfg["hf_config"][key] = value
    cell = manifest.load_cell(tiny.add_moe(man_path, cfg), man_path)
    with pytest.raises(ValueError):
        harness.build(cell, 1)


def test_dense_config_the_program_disagrees_with_is_refused(tmp_path):
    man_path = tiny.make_tree(tmp_path)
    path = tmp_path / "bench" / "configs" / "tiny.json"
    cfg = json.loads(path.read_text())
    cfg["hf_config"]["intermediate_size"] = 130
    path.write_text(json.dumps(cfg))
    with pytest.raises(ValueError, match="d_ff"):
        harness.build(manifest.load_cell("tiny.chat", man_path), 1)

