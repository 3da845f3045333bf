"""bench/trace.py on a trace recorded on one TPU v5e: four paged decode
steps of qwen3-4b (16 rows, 36 layers) under the harness's spans."""
import gzip
import shutil

import pytest

from bench import manifest, readers, trace

DATA = manifest.BENCH_DIR / "testdata" / "decode_window.xplane.pb.gz"


@pytest.fixture(scope="module")
def red(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    with gzip.open(DATA) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return trace.reduce(str(path))


def test_window_busy_and_devices(red):
    assert red.devices == 1
    assert red.window_s == pytest.approx(0.317634, abs=1e-6)
    assert red.busy_s == pytest.approx(0.282077, abs=1e-6)
    assert 0 < red.busy_s < red.window_s


def test_kernel_ops_named_and_counted(red):
    assert trace.op_kind("%decode_attn_paged.9 = bf16[16] custom-call()") \
        == "decode_attn_paged"
    ops = [o for o in red.ops if o.name == "decode_attn_paged"]
    assert len(ops) == 4 * 36
    assert {o.module for o in ops} == {"jit_decode_step"}
    assert not any(o.name == "while" for o in red.ops)
    top = red.top_ops()
    assert top[0][0] == "decode_attn_paged" and len(top) == 10


def test_idle_gaps_named_by_host_span(red):
    idle = red.idle_by_span()
    assert idle[0][0] == "bench.decode_batch"
    assert sum(s for _, s in idle) == pytest.approx(
        red.window_s - red.busy_s, rel=1e-9)


def test_roofline_reader_from_segments(red):
    steps = [{"kind": "decode", "segs": [(1, 500)] * 16}] * 4
    rec = {"trace": red, "steps": steps, "hf": {
        "hidden_size": 2560, "intermediate_size": 9728,
        "num_hidden_layers": 36, "num_attention_heads": 32,
        "num_key_value_heads": 8, "head_dim": 128, "vocab_size": 151936},
        "arch": "dense_gqa", "bench_dir": str(manifest.BENCH_DIR),
        "peak": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}
    share = readers.roofline(rec, "decode_attn")
    assert 0 < share < 100
    assert readers.roofline(rec, "prefill_attn") is None
    assert 0 < readers.idle_share(rec) < 100
    assert 0 < readers.step_mfu(rec) < 100


def test_traced_ttft_leaves_out_requests_due_before_the_export():
    from bench import manifest
    from bench.readers import ttft_s
    reqs = [{"due": d, "first": d + w, "end": 99.0}
            for d, w in ((1.0, 12.0), (2.0, 11.0), (20.0, 0.5),
                         (21.0, 0.75))]
    rec = {"requests": reqs, "untraced_from": 13.5}
    assert sorted(ttft_s(rec)) == [0.5, 0.75, 11.0, 12.0]
    assert sorted(ttft_s(rec, untraced=True)) == [0.5, 0.75]
    cell = manifest.load_cell(manifest.json.loads(
        (manifest.ROOT / "BENCHMARK.json").read_text())["workloads"][0]
        ["name"])
    got = manifest.metric_reader(cell, "ttft_p50_ms.sat").read(rec)
    assert got == 500.0
