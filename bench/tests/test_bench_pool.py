"""The prefix cache starts the window full, so the window evicts, and
the harness's page check holds a request back instead of letting the
engine exhaust a small pool."""
import json
import time

from bench import harness, manifest
from bench.tests import tiny


def test_fill_leaves_a_full_pool_that_evicts(tmp_path):
    cell = manifest.load_cell("tiny.chat", tiny.make_tree(tmp_path))
    rec = harness.serve(cell, 2**36 + 3, 1.0, False, time.monotonic())
    fill = rec["fill"]
    # full but for a few pages: the last rounds offer transcripts cut to
    # fit, which mostly adopt a cached system prompt
    assert fill["fill_requests"] > 0
    assert fill["free_pages_after_fill"] <= 8
    assert rec["counters"]["pages_evicted"] > 0
    assert harness.verdict(cell, rec)["ok"]


def test_page_check_holds_instead_of_exhausting(tmp_path):
    man = tiny.make_tree(tmp_path)
    cfg_path = tmp_path / "bench" / "configs" / "tiny.json"
    cfg = json.loads(cfg_path.read_text())
    cfg["deployment"]["num_pages"] = 40
    cfg_path.write_text(json.dumps(cfg))
    cell = manifest.load_cell("tiny.chat", man)
    cell.traffic = dict(cell.traffic, user_tokens={
        "lognormal": {"median": 120, "sigma": 0.3}, "min": 60, "max": 200})
    rec = harness.serve(cell, 2**36 + 4, 1.0, False, time.monotonic())
    assert rec["counters"]["requests_held_for_pages"] > 0
    assert not any(r["failed"] for r in rec["requests"])
    assert harness.verdict(cell, rec)["ok"]
