"""The dry-run lowering CLI (``launch/dryrun.py``): a production-style mesh
of fake CPU devices, reduced configs, run in subprocesses (the CLI sets the
fake device count before JAX starts).  Kept apart from
test_distributed.py so the two slow files go to different test workers."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = {**os.environ, "PYTHONPATH": os.path.join(REPO, "src")}


@pytest.mark.parametrize("arch,shape", [
    ("paper-lm-100m", "train_4k"),
    ("zamba2-7b", "decode_32k"),
    ("deepseek-moe-16b", "train_4k"),
    ("mamba2-370m", "long_500k"),
])
def test_dryrun_smoke_cells(arch, shape, tmp_path):
    """End-to-end dry-run machinery on a tiny mesh + reduced configs."""
    out = str(tmp_path / "r.json")
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
         "--shape", shape, "--devices", "8", "--mesh", "2x4:data,model",
         "--smoke", "--out", out],
        capture_output=True, text=True, env=ENV, cwd=REPO, timeout=900)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-4000:]
    rep = json.load(open(out))
    assert rep["full"]["compile_s"] > 0
    assert rep["roofline"]["dominant"] in ("compute", "memory", "collective")
    assert rep["cost"]["flops_per_device"] > 0


def test_dryrun_multipod_smoke(tmp_path):
    out = str(tmp_path / "r.json")
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch",
         "paper-lm-100m", "--shape", "train_4k", "--devices", "16",
         "--mesh", "2x2x4:pod,data,model", "--smoke", "--skip-probes",
         "--out", out],
        capture_output=True, text=True, env=ENV, cwd=REPO, timeout=900)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-4000:]
    rep = json.load(open(out))
    assert rep["axes"] == ["pod", "data", "model"]
