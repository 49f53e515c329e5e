"""The per-layer readers of the program's name scopes (``attention_ms``,
``mlp_ms``, ``head_ms``, ``opt_ms``, ``remat_ms``): on hand-made rows with
values worked out by hand, and on a small trace recorded on a TPU v5e with
the scopes compiled in (``record_small_trace.py``: four steps of the
program's reduced Sketchy step with recomputation on, refresh every second
step)."""
import gzip
import json
import os
from types import SimpleNamespace

import pytest

from bench import flops, spec as spec_lib, trace
from bench.tests import tiny

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "small_trace_scoped.json.gz")
DEV = "/device:TPU:0"
NEW = ("attention_ms", "mlp_ms", "head_ms", "opt_ms", "remat_ms")
LAYERS = ("model/attention", "model/mlp", "model/head", "model/embed",
          "opt/")


def _reader(name):
    return spec_lib.load_module(
        os.path.join(spec_lib.BENCH, "metrics", name + ".py"),
        "bench_metric_" + name)


J = "jit(train_step)/"
STACK = "model/stack/while/body/closed_call/checkpoint/"
# (op_name, ns in plain step 0, ns in the refresh step, ns in plain step 2)
ROWS = [
    (J + "jvp()/model/embed/gather", 10, 10, 10),
    (J + "jvp()/" + STACK + "model/attention/dot_general", 100, 500, 120),
    (J + "transpose(jvp())/" + STACK
     + "rematted_computation/model/attention/exp", 40, 400, 60),
    (J + "transpose(jvp())/" + STACK + "model/attention/transpose", 80, 800,
     100),
    (J + "transpose(jvp())/" + STACK
     + "rematted_computation/model/mlp/dot_general", 30, 300, 30),
    (J + "transpose(jvp())/" + STACK + "model/mlp/transpose", 50, 500, 70),
    (J + "jvp(model/head)/dot_general", 20, 200, 40),
    (J + "jvp()/model/stack/while/body/dynamic_slice", 4, 4, 4),
    (J + "opt/clip/mul", 6, 6, 8),
    (J + "opt/direction/precond/precondition/dot_general", 12, 12, 16),
    (J + "opt/direction/precond/refresh/cond/branch_1_fun/fd/eigh/"
     "jit(eigh)/eigh", 0, 9000, 0),
    (J + "opt/apply/add", 2, 2, 2),
]
# worked out by hand from ROWS: plain steps 0 and 2 only, mean, in ms
EXPECTED = {
    "attention_ms": 1e-6 * ((100 + 40 + 80) + (120 + 60 + 100)) / 2,
    "mlp_ms": 1e-6 * ((30 + 50) + (30 + 70)) / 2,
    "head_ms": 1e-6 * (20 + 40) / 2,
    "opt_ms": 1e-6 * ((6 + 12 + 2) + (8 + 16 + 2)) / 2,
    "remat_ms": 1e-6 * ((40 + 30) + (60 + 30)) / 2,
}
REFRESH = [False, True, False]


def _hand_trace(rows, base=1_000_000):
    ops, modules = [], []
    for k in range(len(REFRESH)):
        t = base + k * 200_000
        modules.append([t, 150_000, "jit_train_step(42)"])
        for i, row in enumerate(rows):
            ns = row[1 + k]
            if ns:
                name = f"%fusion.{i} = f32[8]{{0}} fusion(f32[8]{{0}} %p)"
                ops.append([t + 10_000 * i, ns, name, name + " " + row[0]])
    return trace.Trace(ops={DEV: ops}, modules={DEV: modules},
                       host=[[base - 10, len(REFRESH) * 200_000,
                              "bench/window"]])


def _ctx(tr, refresh):
    return SimpleNamespace(trace=tr, device=DEV,
                           steps=[SimpleNamespace(refresh=r)
                                  for r in refresh])


@pytest.mark.parametrize("name", NEW)
def test_reader_on_hand_made_rows(name):
    ctx = _ctx(_hand_trace(ROWS), REFRESH)
    assert _reader(name).read(ctx) == pytest.approx(EXPECTED[name])
    # a program that names no such scope reads nothing, not zero
    bare = [(J + "jvp()/while/body/dot_general",) + r[1:] for r in ROWS]
    assert _reader(name).read(_ctx(_hand_trace(bare), REFRESH)) is None


@pytest.fixture(scope="module")
def small():
    with gzip.open(DATA, "rt") as f:
        d = json.load(f)
    return trace.Trace.from_json(d), d["refresh"]


def _small_ctx(small):
    tr, refresh = small
    model = spec_lib.load_module(
        os.path.join(spec_lib.BENCH, "configs", "paper-lm-100m.py"),
        "bench_ref_paper_lm_100m")
    shapes = _leaves(model.param_shapes(tiny.TINY_LM["model"]))
    ctx = _ctx(tr, refresh)
    ctx.device = sorted(tr.ops)[0]
    ctx.peak = flops.peaks("TPU v5 lite")
    ctx.pool_groups = flops.pool_groups(shapes, 32)
    ctx.rank = 4
    return ctx


def _leaves(tree):
    if isinstance(tree, dict):
        return [x for v in tree.values() for x in _leaves(v)]
    return [tree]


def test_recorded_scoped_trace(small):
    ctx = _small_ctx(small)
    assert sum(ctx.steps[i].refresh for i in range(4)) == 2
    values = {name: _reader(name).read(ctx) for name in NEW}
    assert all(v is not None and v > 0 for v in values.values()), values
    # the stable kernel names keep the roofline readers matching
    for name in ("gram_roofline", "apply_roofline"):
        v = _reader(name).read(ctx)
        assert v is not None and 0 < v <= 100, (name, v)
    # every operation the program traced carries a scope: what is left
    # was made by the compiler (layout copies of the step's arguments,
    # named by the argument, and async copies, named by nothing)
    dev = ctx.device
    untraced = [t for s, d, n, t in ctx.trace.ops[dev]
                if not any(k in t for k in ("model/", "opt/", "precond/"))
                and "jit(train_step)" in t]
    assert untraced == []
    # the named layers and the optimizer cover most of the plain step's
    # device time; at this size those compiler-made copies and the layer
    # scan's bookkeeping weigh 6 % (at full size under 3 %, PERF.md)
    plain, _ = trace.steps_op_ns(ctx, lambda n, t: True)
    named, _ = trace.steps_op_ns(
        ctx, lambda n, t: any(k in t for k in LAYERS))
    assert sum(named) >= 0.9 * sum(plain)
    # the scope sums agree with the readers: embed is the only other part
    embed, _ = trace.steps_op_ns(ctx, lambda n, t: "model/embed" in t)
    parts = sum(values[k] for k in NEW if k != "remat_ms") \
        + 1e-6 * sum(embed) / len(embed)
    assert parts == pytest.approx(1e-6 * sum(named) / len(named), rel=1e-9)
