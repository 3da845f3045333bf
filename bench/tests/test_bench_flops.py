"""bench/flops.py against hand-worked shapes."""
import pytest

from bench import flops

HF = {"hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
      "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
      "vocab_size": 256}
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
    assert flops.step_flops(HF, segs) == linear + attn + head


def test_decode_step_by_hand():
    rows = [(1, 9), (1, 99)]          # two rows with 9 and 99 cached
    per_layer = 64 * 128 + 64 * 64 + 3 * 64 * 128
    want = (2 * 2 * per_layer * 2 + 4 * 4 * 16 * (10 + 100) * 2
            + 2 * 2 * 64 * 256)
    assert flops.step_flops(HF, rows) == want
    # K and V of every visible position, Q in and O out, bf16
    by = flops.attn_bytes(HF, 1, 9)
    assert by == (2 * 10 * 2 * 16 + 2 * 4 * 16) * 2
    t, bound = flops.kernel_least_time(HF, rows, PEAK)
    fl = 4 * 4 * 16 * 110
    nb = (2 * 110 * 2 * 16 + 2 * 2 * 4 * 16) * 2
    assert bound == "memory"
    assert t == pytest.approx(max(fl / 1e12, nb / 1e9) * 2)


def test_unknown_device_is_an_error():
    assert flops.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        flops.peaks("cpu")
