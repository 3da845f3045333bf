"""Readers of the program's own tracer records (bench/program_trace.py)
and the scheduler's queue-wait reader, on synthetic records and on a
tiny served run."""
import time

import pytest

from bench import harness, manifest, program_trace
from bench.tests import tiny
from repro.serving.trace import Span


def _span(i, name, start, end, parent=None):
    return Span(name, start, end, parent, None, {}, i)


def _program():
    spans = [
        _span(0, "laps.engine.decode", 0.000, 0.010),
        _span(1, "laps.engine.decode.wait", 0.002, 0.006, 0),
        _span(2, "laps.engine.decode", 0.020, 0.024),
        _span(3, "laps.engine.decode.wait", 0.020, 0.021, 2),
        _span(4, "laps.engine.decode", 0.030, 0.037),   # no wait child
        _span(5, "laps.engine.packed", 0.040, 0.060),
        _span(6, "laps.engine.packed.wait", 0.041, 0.055, 5),
        _span(7, "laps.engine.decode", 0.070, None),    # still open
    ]
    return {"spans": spans, "counters": {"laps.engine.d2h_bytes": 912e3}}


def test_host_ms_is_the_span_less_its_wait():
    rec = {"program": _program()}
    # decode: 10 - 4, 4 - 1, 7 - 0 -> median 6 ms
    assert program_trace.host_ms_p50(rec, "decode") == pytest.approx(6.0)
    assert program_trace.host_ms_p50(rec, "packed") == pytest.approx(6.0)
    assert program_trace.host_ms_p50({}, "decode") is None


def test_d2h_kb_per_token():
    rec = {"program": _program(), "output_tokens": 3}
    assert program_trace.d2h_kb_per_token(rec) == pytest.approx(304.0)
    assert program_trace.d2h_kb_per_token({"output_tokens": 3}) is None
    rec["output_tokens"] = 0
    assert program_trace.d2h_kb_per_token(rec) is None


def test_host_idle_share_counts_only_program_spans():
    class Red:
        window_s, devices = 2.0, 1
        gaps = [("laps.engine.decode.fetch", 0.1),
                ("laps.engine.decode.sample", 0.05),
                ("bench.sleep", 0.3), ("outside bench spans", 0.2),
                ("laps.loop.next_work", 0.05)]
    assert program_trace.host_idle_share({"trace": Red()}) == \
        pytest.approx(10.0)
    assert program_trace.host_idle_share({}) is None


def _queue_wait():
    cell = manifest.load_cell("qwen3-4b.chat-mt-sat")
    return manifest.metric_reader(cell, "queue_wait_p50_ms.sat")


def test_queue_wait_reader_on_a_synthetic_record():
    reqs = [{"due": d, "submit": d + 0.5, "dispatch": d + 0.5 + w,
             "end": 99.0}
            for d, w in ((1.0, 9.0), (20.0, 0.010), (21.0, 0.030),
                         (22.0, 0.020))]
    reqs.append({"due": 23.0, "submit": 23.0, "dispatch": None,
                 "end": 23.5})                 # never dispatched
    reqs.append({"due": 24.0, "submit": None, "dispatch": None,
                 "end": 99.0})                 # held by the client
    rec = {"requests": reqs, "untraced_from": 13.5}
    assert _queue_wait().read(rec) == pytest.approx(20.0)
    assert _queue_wait().read({"requests": reqs[:1],
                               "untraced_from": 13.5}) is None


def test_queue_wait_reader_on_a_served_run(tmp_path):
    cell = manifest.load_cell("tiny.chat", tiny.make_tree(tmp_path))
    rec = harness.serve(cell, 2**37 + 5, 1.0, False, time.monotonic())
    got = _queue_wait().read(rec)
    ttft = sorted((r["first"] or r["end"]) - r["due"]
                  for r in rec["requests"])
    assert got is not None and 0 <= got <= 1e3 * ttft[-1]


# -------------------------------------- a traced slice recorded on the chip
# 0.3 s of qwen3-4b.chat-mt-sat on one TPU v5e with the program's tracer
# on: the end of a packed prefill step and the decode ticks after it
# (host lines cut to the bench.* and laps.* spans)
DATA = manifest.BENCH_DIR / "testdata" / "serve_spans.xplane.pb.gz"


@pytest.fixture(scope="module")
def xplane(tmp_path_factory):
    import gzip
    import shutil
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    with gzip.open(DATA) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return str(path)


def test_split_gaps_cuts_each_gap_at_the_span_edges():
    spans = [(0.0, 10.0, "bench.decode_batch"),
             (1.0, 4.0, "laps.engine.decode.fetch"),
             (4.0, 9.0, "laps.engine.decode.sample")]
    got = program_trace.split_gaps([(3.0, 5.0), (8.5, 9.5), (10.5, 11.0)],
                                   spans)
    assert got == [("laps.engine.decode.fetch", 1.0),
                   ("laps.engine.decode.sample", 1.0),
                   ("laps.engine.decode.sample", 0.5),
                   ("bench.decode_batch", 0.5),
                   ("outside bench spans", 0.5)]


def test_program_spans_name_the_engine_gaps(xplane):
    from bench import trace
    old = trace.reduce(xplane)
    new = program_trace.reduce(xplane)
    assert (new.window_s, new.busy_s) == (old.window_s, old.busy_s)
    assert new.window_s == pytest.approx(0.3)
    assert new.busy_s == pytest.approx(0.261532, abs=1e-6)
    assert new.ops == old.ops
    before = dict(old.idle_by_span())
    after = dict(new.idle_by_span(40))
    assert all(k.startswith("bench.") for k in before)
    engine = before["bench.decode_batch"] + before["bench.step_mixed"]
    named = sum(v for k, v in after.items() if k.startswith("laps."))
    assert named >= 0.8 * engine
    assert sum(after.values()) == pytest.approx(sum(before.values()))
    # the exact split: the middle of each gap falls in ``sample`` far
    # more often than the gaps' time does
    assert after["laps.engine.decode.sample"] == pytest.approx(
        0.0077311, rel=1e-3)
    assert after["laps.engine.decode.fetch"] == pytest.approx(
        0.0059931, rel=1e-3)
    assert after["laps.kv.evict"] == pytest.approx(0.0059648, rel=1e-3)
    assert program_trace.host_idle_share({"trace": new}) == \
        pytest.approx(100 * named / new.window_s)


def test_kernel_names_survive_the_pallas_name(xplane):
    kinds = {o.name for o in program_trace.reduce(xplane).ops}
    assert {"decode_attn_paged", "ragged_prefill_paged"} <= kinds
