"""Finding a cell's files by the names ``BENCHMARK.json`` gives.

Everything that belongs to one configuration, one mix, one per-layer
metric or one cell sits in a file of its own:

* ``bench/configs/<config>.json``  sizes and settings as run, with its plain
  reference ``bench/configs/<config>.py`` beside it, which defines
  ``param_shapes``, ``loss_sum`` and ``flops_per_token``;
* ``bench/mixes/<traffic>.json``   the job: optimizer, its settings, the
  steps of one period;
* ``bench/optimizers/<optimizer>.py``  the optimizer's plain reference;
* ``bench/metrics/<metric>.py``    the reader of one per-layer metric;
* ``bench/limits/<workload>.json`` the limit of each number the check
  compares in that cell.

Adding a cell, a configuration, a mix or a metric adds files and entries;
no file here changes.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


# what every configuration's plain reference defines
MODEL_REF_API = ("param_shapes", "loss_sum", "flops_per_token")


class SpecError(Exception):
    pass


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_spec(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def _file(root, kind, name, ext):
    if not NAME_RE.match(name):
        raise SpecError(f"bad {kind} name {name!r}")
    path = os.path.join(root, "bench", kind, name + ext)
    if not os.path.isfile(path):
        raise SpecError(f"no {kind} file {os.path.relpath(path, root)}")
    return path


def load_module(path: str, name: str):
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One workload of ``BENCHMARK.json`` with its files loaded."""

    def __init__(self, spec: dict, workload: str, root: str = ROOT):
        cells = {w["name"]: w for w in spec["workloads"]}
        if workload not in cells:
            raise SpecError(f"no workload {workload!r} in BENCHMARK.json")
        self.root, self.spec = root, spec
        self.workload = cells[workload]
        self.name = workload
        self.chips = int(self.workload["chips"])
        self.config = load_json(_file(root, "configs",
                                      self.workload["config"], ".json"))
        self.mix = load_json(_file(root, "mixes", self.workload["traffic"],
                                   ".json"))
        self.limits = load_json(_file(root, "limits", workload, ".json"))
        ref_path = _file(root, "configs", self.workload["config"], ".py")
        self.model_ref = load_module(
            ref_path, "bench_ref_" + re.sub(r"\W", "_",
                                            self.workload["config"]))
        missing = [f for f in MODEL_REF_API if not hasattr(self.model_ref, f)]
        if missing:
            raise SpecError(f"{os.path.relpath(ref_path, root)} defines no "
                            f"{', '.join(missing)}")
        self.opt_ref = load_module(
            _file(root, "optimizers", self.mix["optimizer"], ".py"),
            "bench_opt_" + self.mix["optimizer"])

    def end_to_end(self) -> list:
        return [m for m in self.spec["end_to_end"]
                if self.name in m.get("workloads", [self.name])]

    def per_layer(self) -> list:
        """This cell's per-layer metrics: those whose ``workloads`` list
        names it, or, without the key, every cell that reports the metric
        it moves."""
        e2e = {m["name"] for m in self.end_to_end()}
        return [m for m in self.spec["per_layer"]
                if (self.name in m["workloads"] if "workloads" in m
                    else m["moves"] in e2e)]

    def metric_reader(self, name: str):
        return load_module(_file(self.root, "metrics", name, ".py"),
                           "bench_metric_" + re.sub(r"\W", "_", name))


def check_names(spec: dict) -> list:
    """Every name and unit against the characters the contract allows;
    returns the offending entries (empty when all are well formed)."""
    bad = []
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in spec.get(key, []):
            for field in ("name", "config", "traffic"):
                if field in e and not NAME_RE.match(str(e[field])):
                    bad.append((key, field, e[field]))
            if "unit" in e and not UNIT_RE.match(str(e["unit"])):
                bad.append((key, "unit", e["unit"]))
            for r in e.get("reduced", []):
                if not NAME_RE.match(r):
                    bad.append((key, "reduced", r))
    return bad
