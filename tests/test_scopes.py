"""The train step's layers are named in the compiled program.

The benchmark reads per-layer device time from the profiler trace by the
``op_name`` metadata of each HLO instruction (``bench/trace.py``), so what
it can see is what the program names: ``model/...`` scopes in
``models/model.py``, ``opt/<stage>`` around each stage of
``api.named_chain`` plus ``opt/apply`` and ``opt/grad_norm`` in
``train/trainer.py``, the engine's ``precond/...`` scopes and ``fd/gram``
and ``fd/eigh`` in ``core/fd.py``.  The host loop of ``launch/train.py``
adds spans on the profiler's clock under ``--profile-annotations``.
"""
import dataclasses
import glob
import os
import re

import jax
import jax.numpy as jnp
import pytest

from bench import trace as trace_lib
from repro.configs import registry
from repro.core import api, transform
from repro.core.factory import OptimizerConfig, make_optimizer
from repro.launch import train as train_lib
from repro.models import model as model_lib
from repro.train.trainer import make_train_step

ENGINE_SCOPES = ("precond/update_stats", "precond/refresh",
                 "precond/precondition", "precond/commit",
                 "precond/refresh_launch")
CHECKED_OPS = ("fusion", "dot", "custom-call", "scatter", "reduce")
# instruction name, output shape(s), opcode
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (\(.*?\)|\S+) "
                    r"([\w-]+)\(")


def _largest_output(shape: str) -> int:
    sizes = [1]
    for dims in re.findall(r"\[([\d,]*)\]", shape):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        sizes.append(n)
    return max(sizes)


def _compiled_step(optimizer: str):
    cfg = dataclasses.replace(registry.get_reduced("paper-lm-100m"),
                              remat=True)
    tx = make_optimizer(OptimizerConfig(
        name=optimizer, rank=4, block_size=32, update_every=2,
        weight_decay=1e-4, kernel_backend="xla"))
    params = model_lib.init_params(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": jnp.zeros((4, 64), jnp.int32),
             "labels": jnp.zeros((4, 64), jnp.int32)}
    return make_train_step(cfg, tx).lower(
        params, tx.init(params), batch).compile().as_text()


@pytest.mark.parametrize("optimizer", ["sketchy", "adam"])
def test_train_step_scopes(optimizer):
    hlo = _compiled_step(optimizer)
    scopes = trace_lib.scopes_from_hlo(hlo)
    checked, unscoped, no_metadata = 0, [], 0
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if not m or m.group(3) not in CHECKED_OPS \
                or _largest_output(m.group(2)) <= 1024:
            continue
        if m.group(1) not in scopes:
            # made by the compiler with no metadata (the CPU backend's
            # split reductions, hoisted constants): nothing to name
            no_metadata += 1
            continue
        checked += 1
        text = scopes[m.group(1)]
        if not any(k in text for k in ("model/", "opt/", "precond/")):
            unscoped.append(text)
    assert checked > 50 and no_metadata < 0.05 * checked
    assert unscoped == []

    texts = list(scopes.values())
    # the engine's scopes alone hold precond/, so model_ms (everything
    # outside precond/) keeps its meaning
    assert [t for t in texts if "precond/" in t
            and not any(s in t for s in ENGINE_SCOPES)] == []
    # only the eigendecomposition names eigh (weight_decay's "weigh" is not
    # a match: the name has to start there)
    eigh = [t for t in texts if re.search(r"(?<![a-z])eigh", t.lower())]
    assert all("fd/eigh" in t for t in eigh)
    assert bool(eigh) == (optimizer == "sketchy")
    attention = [t for t in texts if "model/attention" in t]
    assert any("transpose(" in t for t in attention)
    assert any("rematted_computation" in t for t in attention)
    # (opt/grad_norm computes what opt/clip computes, and the compiler
    # keeps one of the two)
    for scope in ("model/embed", "model/mlp", "model/head", "opt/apply",
                  "opt/clip", "opt/direction", "opt/lr"):
        assert any(scope in t for t in texts), scope
    if optimizer == "sketchy":
        assert any("opt/direction/precond/refresh" in t for t in texts)
        assert any("fd/gram" in t for t in texts)


def test_named_chain_stage_scopes():
    assert api.stage_scope("clip") == "opt/clip"
    assert api.stage_scope("momentum") == "opt/momentum"
    assert api.stage_scope("precond") == "opt/direction"

    def mul(c):
        return transform.GradientTransformation(
            lambda p: (), lambda u, s, p=None: (jax.tree.map(
                lambda x: x * c, u), s))

    names = ("clip", "precond", "weight_decay", "lr")
    tx = api.named_chain(*[(n, mul(i + 2.0)) for i, n in enumerate(names)])
    x = jnp.ones((4,))
    jaxpr = jax.make_jaxpr(lambda u: tx.update(u, tx.init(u))[0])(x)
    stacks = [str(e.source_info.name_stack) for e in jaxpr.eqns]
    assert stacks == [api.stage_scope(n) for n in names]


def _train_args(tmp_path, annotate: bool):
    argv = ["--reduced", "--optimizer", "adam", "--steps", "3", "--batch",
            "2", "--seq", "16", "--log-every", "100", "--checkpoint-dir",
            str(tmp_path / "ckpt"), "--checkpoint-every", "1"]
    return train_lib.parse_args(argv + (["--profile-annotations"]
                                        if annotate else []))


def _host_events(log_dir) -> list:
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                            recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend((e.start_ns, e.start_ns + e.duration_ns, e.name)
                           for e in line.events
                           if e.name == "train" or e.name.startswith("train/"))
    return sorted(out)


@pytest.mark.parametrize("annotate", [True, False])
def test_train_loop_spans(tmp_path, annotate):
    with jax.profiler.trace(str(tmp_path / "trace")):
        train_lib.train(_train_args(tmp_path, annotate))
    events = _host_events(tmp_path / "trace")
    if not annotate:
        assert events == []
        return
    steps = [e for e in events if e[2] == "train"]
    assert len(steps) == 3
    for k, (s, e, _) in enumerate(steps):
        inside = [ev[2] for ev in events if ev[2] != "train"
                  and s <= ev[0] and ev[1] <= e]
        # the checkpointer saves after steps 1 and 2 (not after step 0)
        want = ["train/data", "train/dispatch", "train/fetch"] + (
            ["train/checkpoint"] if k else [])
        assert inside == want, (k, inside)
    # the final save, after the last step
    last = [ev for ev in events if ev[0] >= steps[-1][1]]
    assert [ev[2] for ev in last] == ["train/checkpoint"]
