"""The program's tracer (``serving/trace.py``): off it records nothing
and changes nothing; on, its spans nest as the code runs, on the paged
and the slot-arena engine alike, and its counters add."""
import jax
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.core import H200_QWEN32B, PoolPolicy, Variant, make_policy
from repro.core.routing import LengthAwareRouter
from repro.models import transformer as tr
from repro.serving import Engine, EngineConfig, ServeCluster, trace
from repro.serving.loop import ServeLoop

KEY = jax.random.key(14)
DECODE_CHILDREN = ["assemble", "dispatch", "commit", "wait", "fetch",
                   "sample"]
PACKED_CHILDREN = ["assemble", "dispatch", "wait", "fetch", "sample",
                   "commit"]


@pytest.fixture(autouse=True)
def tracer_off():
    trace.disable()
    trace.drain()
    yield
    trace.disable()
    trace.drain()


def test_off_returns_the_shared_noop_and_records_nothing():
    assert not trace.enabled()
    s = trace.span("laps.x", rows=3)
    assert s is trace.NOOP and trace.span("laps.y") is s
    with s:
        trace.count("laps.c", 5)
    assert trace.drain() == {"spans": [], "counters": {}}


def test_on_nests_inherits_and_counts():
    trace.enable()
    with trace.span("laps.loop.tick", engine=2) as tick:
        with trace.span("laps.loop.run_chunk", rid=7) as chunk:
            with trace.span("laps.engine.packed", bucket=64) as packed:
                trace.count("laps.engine.d2h_bytes", 10)
        with trace.span("laps.loop.refit") as refit:
            trace.count("laps.engine.d2h_bytes", 5)
    out = trace.drain()
    assert [s.name for s in out["spans"]] == [
        "laps.loop.tick", "laps.loop.run_chunk", "laps.engine.packed",
        "laps.loop.refit"]
    assert tick.parent is None
    assert chunk.parent == tick.id and refit.parent == tick.id
    assert packed.parent == chunk.id
    # the request id and the engine pass down to children
    assert (tick.rid, chunk.rid, packed.rid, refit.rid) == (None, 7, 7,
                                                            None)
    assert all(s.args["engine"] == 2 for s in out["spans"])
    assert packed.args["bucket"] == 64
    assert tick.start <= chunk.start <= packed.start <= packed.end \
        <= chunk.end <= refit.start <= refit.end <= tick.end
    assert out["counters"] == {"laps.engine.d2h_bytes": 15}
    assert trace.drain() == {"spans": [], "counters": {}}


def test_traced_decorator_wraps_only_when_on():
    @trace.traced("laps.kv.adopt")
    def f(x):
        return x + 1

    assert f(1) == 2 and trace.drain()["spans"] == []
    trace.enable()
    assert f(2) == 3
    assert [s.name for s in trace.drain()["spans"]] == ["laps.kv.adopt"]


# ------------------------------------------------ a served run, off vs on
@pytest.fixture(scope="module")
def served():
    """The same traffic served twice on a tiny paged engine, tracer off
    then on: shared prefixes (adoption), a prompt longer than a chunk,
    a pool small enough to evict, decode after every prefill."""
    cfg = get_smoke("qwen3-4b")
    params, _ = tr.init_params(cfg, KEY)
    rng = np.random.default_rng(14)
    system = rng.integers(0, cfg.vocab_size, 24)
    turns = [np.concatenate([system, rng.integers(0, cfg.vocab_size, n)])
             for n in (5, 9, 40, 3, 12, 7)]

    def run(on: bool, paged: bool = True):
        engine = Engine(cfg, params, EngineConfig(
            num_slots=4, max_len=96, chunk_tokens=16, page_size=8,
            num_pages=14, paged_kv=paged))
        policy = make_policy(Variant("pla_full"), H200_QWEN32B,
                             threshold=24, chunk_tokens=16)
        loop = ServeLoop(engine, policy, slo_ttft=30.0, refit_every=2)
        if on:
            trace.enable()
        try:
            rids = {}
            for i, t in enumerate(turns):
                s = i % 3
                loop.close_session(s)
                rids[loop.submit(s, t, decode_tokens=4).rid] = s
                loop.run_until_idle(max_wall=120.0)
            recs = trace.drain()
        finally:
            trace.disable()
        st = engine.stats()
        st.pop("capture_seconds")
        return {"tokens": dict(loop.generated), "stats": st,
                "rids": rids, "trace": recs, "engine": engine}

    return run(False), run(True), run(True, paged=False)


def test_tracer_changes_no_token_and_no_stat(served):
    off, on, _ = served
    assert off["trace"]["spans"] == []
    assert on["tokens"] == off["tokens"]
    assert on["stats"] == off["stats"]


def _children(spans, parent):
    return [s.name[len(parent.name) + 1:] for s in spans
            if s.parent == parent.id]


@pytest.mark.parametrize("step,order", [("decode", DECODE_CHILDREN),
                                        ("packed", PACKED_CHILDREN)])
def test_engine_step_children_in_code_order(served, step, order):
    spans = served[1]["trace"]["spans"]
    steps = [s for s in spans if s.name == f"laps.engine.{step}"]
    assert steps
    for s in steps:
        assert _children(spans, s) == order
        assert s.end is not None and s.end >= s.start


def test_spans_cover_every_layer(served):
    spans = served[1]["trace"]["spans"]
    names = {s.name for s in spans}
    assert {"laps.loop.submit", "laps.loop.tick", "laps.loop.next_work",
            "laps.loop.run_batch", "laps.loop.run_chunk",
            "laps.loop.decode_only", "laps.loop.refit", "laps.kv.adopt",
            "laps.kv.evict", "laps.exec.compile"} <= names
    packed = [s for s in spans if s.name == "laps.engine.packed"]
    assert {"segments", "tokens", "bucket", "decode_rows"} <= \
        set(packed[0].args)
    compiles = [s for s in spans if s.name == "laps.exec.compile"]
    assert all(s.args["rung"] for s in compiles)


def test_paged_decode_span_counts_the_kernel_walk(served):
    """``kv_blocks``: the blocks the paged decode kernel walks, one per
    real row here (96 tokens at most fit one block of a 12-page table);
    the slot arena's decode step has no page walk to count."""
    paged = [s for s in served[1]["trace"]["spans"]
             if s.name == "laps.engine.decode"]
    assert paged and all(s.args["kv_blocks"] == s.args["rows"]
                         for s in paged)
    assert all("kv_blocks" not in s.args for s in served[2]["trace"]["spans"]
               if s.name == "laps.engine.decode")


def test_d2h_counter_counts_every_logits_row(served):
    on = served[1]
    eng = on["engine"]
    row = eng.cfg.padded_vocab * np.dtype(
        eng.params["lm_head"].dtype).itemsize
    assert on["trace"]["counters"]["laps.engine.d2h_bytes"] == \
        on["stats"]["logits_rows_shipped"] * row


def test_spans_of_a_request_share_its_id(served):
    on = served[1]
    spans = on["trace"]["spans"]
    chunk_rids = {s.rid for s in spans if s.name == "laps.loop.run_chunk"}
    assert chunk_rids and chunk_rids <= set(on["rids"])
    # the engine step under a chunk carries the chunk's request id
    assert any(s.rid in chunk_rids for s in spans
               if s.name == "laps.engine.packed")


@pytest.mark.parametrize("layout", [1, 2], ids=["paged", "slot_arena"])
def test_engine_children_are_named_under_their_parent(served, layout):
    spans = served[layout]["trace"]["spans"]
    by_id = {s.id: s for s in spans}
    steps = {s.name for s in spans if s.name in ("laps.engine.decode",
                                                 "laps.engine.packed")}
    assert steps == {"laps.engine.decode", "laps.engine.packed"}
    kids = [s for s in spans if s.name.count(".") == 3
            and s.name.startswith("laps.engine.")]
    assert {k.name.rsplit(".", 1)[1] for k in kids} >= {"wait", "fetch",
                                                        "sample"}
    for k in kids:
        assert by_id[k.parent].name == k.name.rsplit(".", 1)[0]


def test_cluster_spans_name_the_route_and_the_replica():
    cfg = get_smoke("qwen3-4b")
    params, _ = tr.init_params(cfg, KEY)

    def loop(pool):
        eng = Engine(cfg, params, EngineConfig(num_slots=4, max_len=96,
                                               chunk_tokens=16,
                                               page_size=8))
        pol = PoolPolicy(H200_QWEN32B, pool=pool, threshold=24,
                         chunk_tokens=16)
        return ServeLoop(eng, pol, slo_ttft=30.0)

    cluster = ServeCluster([loop("long"), loop("short")],
                           LengthAwareRouter(threshold=24),
                           roles=["prefill", "decode"])
    rng = np.random.default_rng(3)
    trace.enable()
    cluster.submit(1, rng.integers(0, cfg.vocab_size, 40))
    cluster.submit(2, rng.integers(0, cfg.vocab_size, 5))
    spans = trace.drain()["spans"]
    assert [s.name for s in spans if s.name.startswith("laps.router")] \
        == ["laps.router.route"] * 2
    submits = [s for s in spans if s.name == "laps.loop.submit"]
    assert [s.args["engine"] for s in submits] == \
        [cluster.engine_of(1), cluster.engine_of(2)] == [0, 1]
