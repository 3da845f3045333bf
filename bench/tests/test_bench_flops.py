"""Each architecture's counts (bench/arch/<name>.py) and bench/flops.py
against hand-worked shapes."""
import pytest

from bench import flops, manifest, readers
from bench.tests import moe_arch

dense = manifest.arch_by_name(manifest.BENCH_DIR, "dense_gqa")

HF = {"hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
      "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
      "vocab_size": 256}
MOE_HF = dict(HF, intermediate_size=96, moe_intermediate_size=32,
              num_experts=8, num_experts_per_tok=2)
PEAK = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e9}


def test_packed_step_by_hand():
    # one fresh 3-token prompt and one 2-token chunk after 5 cached
    segs = [(3, 0), (2, 5)]
    keys = (1 + 2 + 3) + (6 + 7)
    assert flops.causal_keys(3, 0) + flops.causal_keys(2, 5) == keys
    per_layer = 64 * (64 + 2 * 32) + 64 * 64 + 3 * 64 * 128
    linear = 2 * 5 * per_layer * 2
    attn = 4 * 4 * 16 * keys * 2
    head = 2 * 2 * 64 * 256
    assert dense.step_flops(HF, segs) == linear + attn + head


def test_decode_step_by_hand():
    rows = [(1, 9), (1, 99)]          # two rows with 9 and 99 cached
    per_layer = 64 * 128 + 64 * 64 + 3 * 64 * 128
    want = (2 * 2 * per_layer * 2 + 4 * 4 * 16 * (10 + 100) * 2
            + 2 * 2 * 64 * 256)
    assert dense.step_flops(HF, rows) == want
    # K and V of every visible position, Q in and O out, bf16
    by = dense.attn_bytes(HF, 1, 9)
    assert by == (2 * 10 * 2 * 16 + 2 * 4 * 16) * 2
    [(fl, nb, layers)] = dense.kernel_counts(HF, "decode_attn", rows)
    assert (fl, nb, layers) == (4 * 4 * 16 * 110,
                                (2 * 110 * 2 * 16 + 2 * 2 * 4 * 16) * 2, 2)
    t, bound = flops.least_time(fl, nb, PEAK)
    assert bound == "memory"
    assert t * layers == pytest.approx(max(fl / 1e12, nb / 1e9) * 2)


def test_unknown_kernel_has_no_counts():
    with pytest.raises(KeyError):
        dense.kernel_counts(HF, "expert_matmul", [(1, 9)])


def test_moe_step_counts_the_router_and_k_of_e_experts():
    segs = [(3, 0), (2, 5)]
    keys = (1 + 2 + 3) + (6 + 7)
    attn_proj = 64 * (64 + 2 * 32) + 64 * 64
    router = 64 * 8
    experts = 2 * 3 * 64 * 32          # top-2 of 8, width 32
    linear = 2 * 5 * (attn_proj + router + experts) * 2
    attn = 4 * 4 * 16 * keys * 2
    head = 2 * 2 * 64 * 256
    assert moe_arch.step_flops(MOE_HF, segs) == linear + attn + head
    # the dense width is not counted; every expert is not counted
    wider = dict(MOE_HF, intermediate_size=10_000, num_experts=64)
    assert moe_arch.step_flops(wider, segs) == \
        linear + attn + head + 2 * 5 * 64 * 56 * 2


def test_roofline_of_a_new_kernel_is_one_call():
    """A kernel no reader knows is read through ``kernel_roofline`` with
    its device operation, its step kind and a count function."""
    from bench import trace
    red = trace.Reduction(window_s=1.0, busy_s=0.5, devices=1, gaps=[],
                          ops=[trace.Op("expert_gmm", "m", 0.0, 2e-6, 0)])
    rec = {"trace": red, "peak": PEAK, "hf": HF, "arch": "dense_gqa",
           "bench_dir": str(manifest.BENCH_DIR),
           "steps": [{"kind": "packed", "segs": [(4, 0)]},
                     {"kind": "decode", "segs": [(1, 4)]}]}
    got = readers.kernel_roofline(
        rec, "expert_gmm", "packed",
        lambda mod, hf, segs: [(2.0 * 4 * 2 * 3 * 64 * 32, 0.0, 2)])
    assert got == pytest.approx(100.0 * 2 * 2.0 * 4 * 2 * 3 * 64 * 32
                                / 1e12 / 2e-6)
    assert readers.kernel_roofline(rec, "missing_op", "packed",
                                   lambda *a: [(1.0, 1.0, 1)]) is None


def test_unknown_device_is_an_error():
    assert flops.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        flops.peaks("cpu")
