"""BENCHMARK.json against the benchmark's contract and its files."""
import json
import re

import pytest

from bench import manifest

MAN = json.loads((manifest.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
METRICS = MAN["end_to_end"] + MAN["per_layer"]


def test_names_and_units_use_allowed_characters():
    names = [m["name"] for m in METRICS] + [w["name"] for w in
                                            MAN["workloads"]]
    names += [c["name"] for c in MAN["configs"]]
    names += [w["traffic"] for w in MAN["workloads"]]
    names += [k for c in MAN["configs"] for k in c["reduced"]]
    assert all(NAME.match(n) for n in names), names
    assert len(set(m["name"] for m in METRICS)) == len(METRICS)
    assert all(UNIT.match(m["unit"]) for m in METRICS)
    assert all(m["better"] in ("lower", "higher") for m in METRICS)


def test_every_per_layer_metric_moves_what_its_cells_report():
    e2e = {m["name"]: m for m in MAN["end_to_end"]}
    cells = [w["name"] for w in MAN["workloads"]]
    for m in MAN["per_layer"]:
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in moved.get("workloads", cells), (m["name"], cell)


def test_every_cell_reports_setup_another_metric_and_a_layer():
    for w in MAN["workloads"]:
        cell = manifest.load_cell(w["name"])
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer


@pytest.mark.parametrize("metric", [m["name"] for m in METRICS])
def test_every_metric_has_a_reader(metric):
    cell = manifest.load_cell(MAN["workloads"][0]["name"])
    assert callable(manifest.metric_reader(cell, metric).read)


def test_cells_find_their_files():
    for w in MAN["workloads"]:
        cell = manifest.load_cell(w["name"])
        assert callable(manifest.traffic_kind(cell).make_source)
        assert callable(manifest.reference_module(cell).logits)
        assert callable(manifest.arch_module(cell).make)
        cf = cell.config
        assert cf["name"] == w["config"]
        assert cf["reference"] and cf["check"]["max_logit_gap"] > 0


def test_configs_state_their_cuts():
    for c in MAN["configs"]:
        cf = json.loads((manifest.ROOT / c["file"]).read_text())
        assert cf["source"] == c["source"]
        assert cf["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert not key.endswith(("_dim", "_rank", "_size"))


def test_program_config_matches_the_file():
    """Each configuration's architecture module says which program
    config attributes its published keys fix; the program agrees."""
    from repro.configs import get_config
    from bench import harness
    for c in MAN["configs"]:
        cf = json.loads((manifest.ROOT / c["file"]).read_text())
        arch = manifest.arch_by_name(manifest.BENCH_DIR, cf["reference"])
        mcfg = get_config(cf["registry"]).replace(**cf["overrides"])
        harness.mirror_check(
            mcfg, arch.program_fields(cf["hf_config"], cf["architecture"]))


def test_weights_match_the_program_layout():
    import jax
    from repro.configs import get_config
    from repro.models import transformer as tr
    for c in MAN["configs"]:
        cf = json.loads((manifest.ROOT / c["file"]).read_text())
        arch = manifest.arch_by_name(manifest.BENCH_DIR, cf["reference"])
        mcfg = get_config(cf["registry"]).replace(**cf["overrides"])
        got = arch.shapes(cf["hf_config"], cf["architecture"],
                          jax.numpy.dtype(cf["architecture"]["dtype"]))
        want = tr.param_shapes(mcfg)
        assert jax.tree.structure(got) == jax.tree.structure(want)
        assert [(a.shape, a.dtype) for a in jax.tree.leaves(got)] == \
            [(a.shape, a.dtype) for a in jax.tree.leaves(want)]
