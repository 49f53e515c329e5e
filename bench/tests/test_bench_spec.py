"""The benchmark is driven by data: a cell, a configuration, a mix and a
per-layer metric added as new files only are found by their names."""
import json
import os
import re

import pytest

from bench import spec as spec_lib
from bench.tests import tiny


def test_contract_shape():
    spec = spec_lib.load_spec()
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert spec["command"] == ["python3", "bench/run.py"]
    assert spec_lib.check_names(spec) == []
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in e2e
    cells = {w["name"] for w in spec["workloads"]}
    for m in spec["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", [])) <= cells
        assert os.path.isfile(os.path.join(spec_lib.BENCH, "metrics",
                                           m["name"] + ".py"))
    for c in spec["configs"]:
        assert c["file"] == f"bench/configs/{c['name']}.json"
        with open(os.path.join(spec_lib.ROOT, c["file"])) as f:
            assert json.load(f)["reduced"] == c["reduced"]


@pytest.mark.parametrize("name", ["tokens_per_s", "a.b-c_1", "_x", "9z"])
def test_good_names(name):
    assert spec_lib.NAME_RE.match(name)


@pytest.mark.parametrize("name", ["", "a b", "a,b", "a/b", "-a", ".a",
                                  "x" * 65, "café"])
def test_bad_names(name):
    assert not spec_lib.NAME_RE.match(name)


@pytest.mark.parametrize("unit,ok", [("tokens/s", True), ("%", True),
                                     ("GB", True), ("us", True),
                                     ("tokens per s", False),
                                     ("µs", False), ("x" * 17, False)])
def test_units(unit, ok):
    assert bool(spec_lib.UNIT_RE.match(unit)) == ok


def test_check_names_reports_offenders():
    spec = {"configs": [{"name": "a b", "reduced": ["x,y"]}],
            "workloads": [{"name": "w", "config": "c/d", "traffic": "t"}],
            "end_to_end": [{"name": "m", "unit": "tokens per s"}],
            "per_layer": []}
    bad = spec_lib.check_names(spec)
    assert ("configs", "name", "a b") in bad
    assert ("configs", "reduced", "x,y") in bad
    assert ("workloads", "config", "c/d") in bad
    assert ("end_to_end", "unit", "tokens per s") in bad


def test_new_files_are_found_by_name(tmp_path):
    root = tiny.make_root(tmp_path)
    # a new per-layer metric: one reader file and one entry
    with open(os.path.join(root, "bench", "metrics", "tiny_probe.py"),
              "w") as f:
        f.write("def read(ctx):\n    return 42.0\n")
    spec = spec_lib.load_spec(root)
    spec["per_layer"].append({
        "name": "tiny_probe", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "kernels",
        "moves": "tokens_per_s", "workloads": ["tiny-lm.sketchy"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    spec = spec_lib.load_spec(root)
    assert spec_lib.check_names(spec) == []
    cell = spec_lib.Cell(spec, "tiny-lm.sketchy", root)
    assert cell.config["name"] == "tiny-lm"
    assert cell.mix["name"] == "tiny-sketchy"
    assert cell.opt_ref.__name__ == "bench_opt_sketchy"
    assert hasattr(cell.model_ref, "loss_sum")
    names = [m["name"] for m in cell.per_layer()]
    assert "tiny_probe" in names and "refresh_ms" in names
    assert cell.metric_reader("tiny_probe").read(None) == 42.0
    other = spec_lib.Cell(spec, "tiny-lm.adam", root)
    assert "tiny_probe" not in [m["name"] for m in other.per_layer()]
    assert "refresh_ms" not in [m["name"] for m in other.per_layer()]
    assert other.opt_ref.__name__ == "bench_opt_adam"
    # an end-to-end metric with a ``workloads`` list is reported there only
    assert {m["name"] for m in other.end_to_end()} == {"tokens_per_s",
                                                       "setup_s"}
    assert {m["name"] for m in cell.end_to_end()} == {
        "tokens_per_s", "step_s_max", "setup_s"}
    # a state-space configuration, its reference and its FLOP count come
    # from new files alone, as the dense one's do
    ssm = spec_lib.Cell(spec, "tiny-ssm.sketchy", root)
    assert ssm.config["family"] == "ssm"
    assert ssm.config["registry"] == "mamba2-370m"
    assert ssm.mix["name"] == "tiny-sketchy"
    assert all(hasattr(ssm.model_ref, f) for f in spec_lib.MODEL_REF_API)
    assert ssm.model_ref.flops_per_token(ssm.config["model"],
                                         ssm.config["seq"]) > 0
    assert "in_proj" in ssm.model_ref.param_shapes(
        ssm.config["model"])["layers"]["mixer"]
    assert {m["name"] for m in ssm.per_layer()} \
        == {m["name"] for m in cell.per_layer()} - {"tiny_probe"}


def test_missing_files_are_errors(tmp_path):
    root = tiny.make_root(tmp_path)
    spec = spec_lib.load_spec(root)
    spec["workloads"].append({"name": "ghost.cell", "config": "ghost",
                              "traffic": "tiny-adam", "chips": 1, "why": "x"})
    with pytest.raises(spec_lib.SpecError):
        spec_lib.Cell(spec, "ghost.cell", root)
    with pytest.raises(spec_lib.SpecError):
        spec_lib.Cell(spec, "no.such.cell", root)
    with pytest.raises(spec_lib.SpecError):
        spec_lib.Cell(spec, "tiny-lm.sketchy", root).metric_reader("nope")


def test_every_cell_has_its_files():
    spec = spec_lib.load_spec()
    for w in spec["workloads"]:
        cell = spec_lib.Cell(spec, w["name"])
        assert set(cell.limits) >= {"loss_gap", "grad_gap", "update_gap"}
        assert re.match(r"^[a-z]", cell.mix["optimizer"])
