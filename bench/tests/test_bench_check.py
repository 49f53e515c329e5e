"""The harness end to end on the CPU at tiny sizes: a sound run is
correct, a run whose timed step is broken underneath is not, the control
fails the limits, and a run without a TPU prints no result."""
import json
import os
import subprocess
import sys

import pytest

from bench import harness, readings, spec as spec_lib
from bench.tests import tiny

SEED = 3_000_000_019       # wider than 32 bits, as a run's seed may be


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench"))


def _run(root, workload, fault=None, trace=0):
    from bench import run as run_mod
    return run_mod.run(["--workload", workload, "--seed", str(SEED),
                        "--seconds", "1", "--trace", str(trace)],
                       require_tpu=False, fault=fault, root=root)


@pytest.mark.parametrize("workload", ["tiny-lm.sketchy", "tiny-lm.adam",
                                      "tiny-ssm.sketchy", "tiny-ssm.adam"])
def test_sound_run_is_correct(root, workload):
    res = _run(root, workload)
    assert res["correct"] is True
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == set(tiny.TINY_LIMITS)
    m = res["metrics"]
    want = {"tokens_per_s", "setup_s"} | (
        {"step_s_max"} if "sketchy" in workload else set())
    assert set(m) == want
    assert m["tokens_per_s"]["value"] > 0 and m["setup_s"]["value"] > 0
    # the step's temporaries are counted in the memory peak
    assert res["device"]["memory_peak_bytes"] > 0
    # whole periods: the Sketchy mix's period is 2 steps
    period = 2 if "sketchy" in workload else 1
    assert res["attempted"] % period == 0 and res["attempted"] >= period


@pytest.mark.parametrize("workload,fault", [
    pytest.param(w, f, id=f if w == "tiny-lm.sketchy" else f"{w}-{f}")
    for w in ("tiny-lm.sketchy", "tiny-ssm.sketchy") for f in harness.FAULTS])
def test_broken_step_is_not_correct(root, workload, fault):
    res = _run(root, workload, fault=fault)
    assert res["correct"] is False, res["checks"]


def _control_verdict(root, workload):
    cell = spec_lib.Cell(spec_lib.load_spec(root), workload, root)
    harness.setup_jax(root, cache=False)
    ref = harness.reference_readings(cell, SEED)
    ctl = harness.reference_readings(cell, SEED, mode="control")
    return harness.verdict(harness.compare(ctl, ref), cell.limits)


def test_control_fails_the_limits(root):
    correct, table = _control_verdict(root, "tiny-lm.sketchy")
    assert correct is False, table


def test_ssm_control_fails_the_limits(root):
    """The Mamba2 reference one precision down (float8 matmuls, the
    recurrence's included) in the program's place."""
    correct, table = _control_verdict(root, "tiny-ssm.sketchy")
    assert correct is False, table


def test_readings_lines(root):
    out = list(readings.readings("tiny-lm.adam", [SEED], [SEED], [SEED],
                                 root=root, require_tpu=False))
    assert [r["kind"] for r in out] == ["program", "control", "half_batch"]
    lim = tiny.TINY_LIMITS
    assert all(out[0]["numbers"][k] <= lim[k] for k in lim)
    assert any(out[2]["numbers"][k] > lim[k] for k in lim)
    # each reading carries the verdict of the cell's limits
    assert [r["correct"] for r in out] == [True, False, False]
    assert out[1]["checks"]["loss_gap"][1] == lim["loss_gap"]


@pytest.mark.parametrize("mode", ["fd_high", "fd_default"])
def test_fd_control_modes_lower_the_matrix_path_alone(root, mode):
    """The FD-only controls keep the model at HIGHEST: the loss of step 0,
    which no optimizer step has touched yet, is the reference's."""
    out = list(readings.readings("tiny-lm.sketchy", [], [SEED],
                                 root=root, require_tpu=False,
                                 control_modes=(mode,)))
    assert [r["kind"] for r in out] == [mode]
    assert set(out[0]["checks"]) == set(tiny.TINY_LIMITS)
    assert out[0]["numbers"]["loss_gap"] < 1e-3


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(spec_lib.BENCH, "run.py"),
         "--workload", "paper-lm-100m.sketchy", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr
