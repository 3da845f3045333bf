"""The control at a size a test run holds: the reference computed with
fp8 weights, in the program's place, reads a gap above the tiny
configuration's limit, while the program (float32 here) reads below."""
import time

from bench import check, harness, manifest
from bench.tests import tiny


def test_fp8_control_fails_where_the_program_passes(tmp_path):
    man = tiny.make_tree(tmp_path)
    cell = manifest.load_cell("tiny.chat", man)
    # replies long enough that some position sits near a tie
    cell.traffic = dict(cell.traffic, output_tokens={
        "lognormal": {"median": 32, "sigma": 0.3}, "min": 16, "max": 64})
    rec = harness.serve(cell, 2**34 + 1, 1.0, False, time.monotonic())
    reqs = check.sample(rec["done"], 1, 8)
    cf = cell.config
    ref = manifest.reference_module(cell)
    limit = cf["check"]["max_logit_gap"]
    prog, ctrl, n = check.gaps(ref, cf["hf_config"], cf["architecture"],
                               rec["params"], reqs, control="fp8")
    assert n >= 200
    assert prog <= limit < ctrl, (prog, limit, ctrl)
