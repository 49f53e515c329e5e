"""The reduction from trace to metrics, on a small trace recorded on a TPU
v5e (four steps of the program's reduced Sketchy step, refresh every second
step, inside a ``bench/window`` span) and on hand-made rows."""
import gzip
import json
import os
from types import SimpleNamespace

import pytest

from bench import trace

DATA = os.path.join(os.path.dirname(__file__), "data", "small_trace.json.gz")


@pytest.fixture(scope="module")
def small():
    with gzip.open(DATA, "rt") as f:
        return trace.Trace.from_json(json.load(f))


def _busy_by_sweep(rows, t0, t1):
    """Busy time by an independent sweep over interval edges."""
    edges = sorted({t0, t1} | {s for s, *_ in rows} | {s + d for s, d, *_ in rows})
    busy = 0
    for a, b in zip(edges, edges[1:]):
        if a < t0 or b > t1:
            continue
        mid = (a + b) / 2
        if any(s <= mid < s + d for s, d, *_ in rows):
            busy += b - a
    return busy


def test_union_and_clip():
    rows = [[0, 10], [5, 10], [20, 5], [30, 0], [24, 2]]
    assert trace.union(rows) == [[0, 15], [20, 26], [30, 30]]
    assert trace.clip(trace.union(rows), 3, 22) == [[3, 15], [20, 22]]
    assert trace.busy_ns(rows, 3, 22) == 14


def test_leaves_drop_enclosing_control_flow():
    rows = [[0, 100, "while"], [10, 5, "a"], [20, 5, "b"], [100, 3, "c"]]
    assert trace.leaves(rows) == [[10, 5, "a"], [20, 5, "b"], [100, 3, "c"]]


def test_instruction_and_scopes():
    assert trace.instruction("%fusion.12 = f32[4]{0} fusion(%a)") == "fusion.12"
    hlo = ('  %fusion.3 = f32[2]{0} fusion(f32[2]{0} %p), kind=kLoop, '
           'metadata={op_name="jit(train_step)/precond/refresh/mul" '
           'source_file="x.py"}\n'
           '  ROOT %tuple.1 = (f32[2]{0}) tuple(%fusion.3)\n')
    assert trace.scopes_from_hlo(hlo) == {
        "fusion.3": "jit(train_step)/precond/refresh/mul"}


def test_recorded_trace_shape(small):
    devs = sorted(small.ops)
    assert devs == ["/device:TPU:0"]
    t0, t1 = trace.window(small)
    assert t1 > t0
    assert len(trace.step_modules(small, devs[0])) == 4
    # leaves only: no event on the ops line contains the next one
    rows = small.ops[devs[0]]
    assert all(b[0] >= a[0] + a[1] for a, b in zip(rows, rows[1:]))


def test_recorded_busy_and_idle(small):
    dev = sorted(small.ops)[0]
    t0, t1 = trace.window(small)
    busy, win = trace.busy_and_window_s(small)
    assert win == pytest.approx((t1 - t0) * 1e-9)
    assert busy * 1e9 == pytest.approx(
        _busy_by_sweep(small.ops[dev], t0, t1), abs=1)
    assert 0 < busy < win
    gaps = trace.idle_gaps(small, dev)
    assert gaps and all(g[1] > 0 for g in gaps)
    assert gaps == sorted(gaps, key=lambda g: -g[1])
    assert {g[0] for g in gaps} <= {"bench/data", "bench/dispatch",
                                    "bench/fetch", "idle"}


def test_recorded_scope_and_kernel_time(small):
    dev = sorted(small.ops)[0]
    steps = [SimpleNamespace(refresh=(i % 2 == 0)) for i in range(4)]
    ctx = SimpleNamespace(trace=small, device=dev, steps=steps)
    mods = trace.step_modules(small, dev)

    def brute(pred):
        out = []
        for ms, md, _ in mods:
            out.append(sum(d for s, d, n, t in small.ops[dev]
                           if ms <= s < ms + md and pred(n, t)))
        return out

    for pred in (lambda n, t: "precond/refresh" in t,
                 lambda n, t: "precond/" not in t,
                 lambda n, t: "batched_gram" in t,
                 lambda n, t: "batched_lowrank" in t,
                 lambda n, t: "EighTpu" in t):
        per = trace.per_step_op_ns(small, dev, pred)
        assert per == brute(pred)
    plain, refresh = trace.steps_op_ns(
        ctx, lambda n, t: "precond/refresh" in t)
    # the sketches refresh on even steps only; on the others the refresh
    # scope holds only the untaken branch's bookkeeping
    assert min(refresh) > 100 * max(plain)
    _, eigh = trace.steps_op_ns(ctx, lambda n, t: "EighTpu" in t)
    assert min(eigh) > 0
    gram_plain, gram_refresh = trace.steps_op_ns(
        ctx, lambda n, t: "batched_gram" in t)
    assert min(gram_refresh) > 0 and max(gram_plain) == 0
    apply_plain, apply_refresh = trace.steps_op_ns(
        ctx, lambda n, t: "batched_lowrank" in t)
    assert min(apply_plain) > 0 and min(apply_refresh) > 0
    top = trace.top_ops(small, dev)
    assert len(top) == 10 and top == sorted(top, key=lambda r: -r[1])


def test_step_count_mismatch_is_an_error(small):
    dev = sorted(small.ops)[0]
    ctx = SimpleNamespace(trace=small, device=dev,
                          steps=[SimpleNamespace(refresh=False)] * 3)
    with pytest.raises(ValueError):
        trace.steps_op_ns(ctx, lambda n, t: True)
