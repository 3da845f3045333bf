"""A configuration, a traffic mix, a generator kind and a per-layer
metric are added as new files plus manifest entries, with no edit to a
file the benchmark already has."""
import json
import time

from bench import harness, manifest
from bench.tests import tiny

NEW_KIND = """
import importlib.util, pathlib
_p = pathlib.Path(__file__).with_name("sessions.py")
_s = importlib.util.spec_from_file_location("sessions_kind", _p)
_m = importlib.util.module_from_spec(_s)
_s.loader.exec_module(_m)


def make_source(params, seed, vocab, max_len):
    # every user message twice as long as the mix says
    p = dict(params)
    d = dict(p["user_tokens"])
    d["lognormal"] = dict(d["lognormal"], median=2 * d["lognormal"]["median"])
    p["user_tokens"] = d
    return _m.make_source(p, seed, vocab, max_len)
"""

NEW_METRIC = '''
def read(rec):
    return float(len(rec["requests"]))
'''


def test_throwaway_config_mix_kind_and_metric(tmp_path):
    man_path = tiny.make_tree(tmp_path)
    b = tmp_path / "bench"
    cfg = json.loads((b / "configs" / "tiny.json").read_text())
    cfg["name"] = "tiny2"
    (b / "configs" / "tiny2.json").write_text(json.dumps(cfg))
    mix = dict(tiny.TRAFFIC["chat"], kind="doubled", clients=5)
    (b / "traffic" / "extra.json").write_text(json.dumps(mix))
    (b / "traffic" / "kinds" / "doubled.py").write_text(NEW_KIND)
    (b / "metrics" / "requests_seen.py").write_text(NEW_METRIC)
    man = json.loads(man_path.read_text())
    man["configs"].append({"name": "tiny2",
                           "file": "bench/configs/tiny2.json"})
    man["workloads"].append({"name": "tiny2.extra", "config": "tiny2",
                             "traffic": "extra", "chips": 1})
    man["per_layer"].append({"name": "requests_seen", "unit": "requests",
                             "workloads": ["tiny2.extra"]})
    man_path.write_text(json.dumps(man))

    cell = manifest.load_cell("tiny2.extra", man_path)
    rec = harness.serve(cell, 2**35 + 9, 0.8, False, time.monotonic())
    per_layer = harness.metrics(cell, rec, trace=True)
    assert per_layer["requests_seen"]["value"] == len(rec["requests"]) > 0
    e2e = harness.metrics(cell, rec, trace=False)
    assert set(e2e) == {"output_tok_s", "setup_s"}
    assert harness.verdict(cell, rec)["ok"]


def test_throwaway_moe_architecture_as_new_files(tmp_path):
    """A configuration of an architecture the benchmark does not have (a
    router over 8 SwiGLU experts, top-2) is served and checked once its
    module, its reference and its config are added as files."""
    man_path = tiny.make_tree(tmp_path)
    name = tiny.add_moe(man_path)
    cell = manifest.load_cell(name, man_path)
    arch = manifest.arch_module(cell)
    assert arch.__file__.endswith("arch/moe_gqa.py")
    rec = harness.serve(cell, 2**33 + 5, 0.8, False, time.monotonic())
    assert rec["arch"] == "moe_gqa" and rec["requests"]
    v = harness.verdict(cell, rec)
    assert v["ok"] and v["tokens"] > 0, v
    ffn = rec["params"]["blocks"][0]["ffn"]
    assert ffn["router"].shape == (2, 64, 8)
    assert ffn["wg"].shape == ffn["wi"].shape == (2, 8, 64, 32)
    assert ffn["wo"].shape == (2, 8, 32, 64)
