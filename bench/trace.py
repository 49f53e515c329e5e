"""Reduction of a profiler trace to the numbers the per-layer metrics read.

Two stages, kept apart so that the second can be checked on a small trace
committed with the tests:

1. ``load(xplane_path, scopes)`` reads the ``.xplane.pb`` file that
   ``jax.profiler`` writes into a plain ``Trace``: per device, every XLA
   operation as ``[start_ns, dur_ns, name, text]`` and every program
   execution as ``[start_ns, dur_ns, name]``; and the harness's host spans
   (``bench/...``) as ``[start_ns, dur_ns, name]``.  On a TPU an
   operation's event is named by its HLO instruction (``%fusion.12 = ...``,
   Pallas kernels as ``%batched_gram_pallas.3 = ...``, the eigensolver as a
   ``custom_call_target="EighTpu"`` call) and carries no name scope, so
   ``text`` joins that name with the instruction's ``op_name`` metadata
   from the compiled program (``scopes_from_hlo``), where the
   ``jax.named_scope`` path of each operation shows.  Control-flow
   operations (``while``, ``conditional``) span their children on the same
   line; only the leaves are kept, so device time is never counted twice.
2. The functions below: the union of busy intervals, the idle share of a
   window, device time by predicate, per step, and the idle gaps labelled
   by the host span that was open.

A step is one execution of the train-step program: on each device the
k-th such execution in the window is the window's k-th step, so no
alignment of host and device clocks is needed for per-step times.
"""
from __future__ import annotations

import dataclasses
import re

STEP_PROGRAM = "train_step"


@dataclasses.dataclass
class Trace:
    ops: dict        # device -> [[start_ns, dur_ns, name, text], ...]
    modules: dict    # device -> [[start_ns, dur_ns, name], ...]
    host: list       # [[start_ns, dur_ns, name], ...]

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        return cls(ops=d["ops"], modules=d["modules"], host=d["host"])


def _is_device(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name \
        and "NON_CORE" not in name


_HLO_LINE = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?'
                       r'metadata=\{[^}]*?op_name="([^"]*)"')


def scopes_from_hlo(hlo_text: str) -> dict:
    """Instruction name -> ``op_name`` metadata of a compiled program's
    HLO text."""
    out = {}
    for line in hlo_text.splitlines():
        m = _HLO_LINE.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def instruction(name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return name.split(" = ", 1)[0].strip().lstrip("%")


def leaves(rows: list) -> list:
    """Drop the events that contain the next one (control flow around its
    body); the events of one line nest and never partly overlap."""
    rows = sorted(rows)
    return [r for i, r in enumerate(rows)
            if i + 1 == len(rows) or rows[i + 1][0] >= r[0] + r[1]]


def load(path: str, scopes: dict = None) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    scopes = scopes or {}
    ops, modules, host = {}, {}, []
    for plane in pd.planes:
        if _is_device(plane.name):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    rows = ops.setdefault(plane.name, [])
                    rows.extend(
                        [int(e.start_ns), int(e.duration_ns), e.name,
                         e.name + " " + scopes.get(instruction(e.name), "")]
                        for e in line.events)
                elif line.name == "XLA Modules":
                    modules.setdefault(plane.name, []).extend(
                        [int(e.start_ns), int(e.duration_ns), e.name]
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench/"):
                        host.append([int(e.start_ns), int(e.duration_ns),
                                     e.name.split("#")[0]])
    ops = {d: leaves(rows) for d, rows in ops.items()}
    for rows in modules.values():
        rows.sort()
    host.sort()
    return Trace(ops=ops, modules=modules, host=host)


# ------------------------------------------------------------ reductions

def union(intervals) -> list:
    """Merged ``[start, end]`` intervals of ``(start, dur, ...)`` rows."""
    out = []
    for s, d, *_ in sorted(intervals):
        e = s + d
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals, t0, t1) -> list:
    return [[max(s, t0), min(e, t1)] for s, e in intervals
            if e > t0 and s < t1]


def busy_ns(ops, t0, t1) -> int:
    return sum(e - s for s, e in clip(union(ops), t0, t1))


def window(trace: Trace) -> tuple:
    """The ``bench/window`` host span as ``(t0_ns, t1_ns)``."""
    spans = [h for h in trace.host if h[2] == "bench/window"]
    if len(spans) != 1:
        raise ValueError(f"expected one bench/window span, found {len(spans)}")
    s, d, _ = spans[0]
    return s, s + d


def busy_and_window_s(trace: Trace) -> tuple:
    """(busy seconds averaged over the devices, window seconds)."""
    t0, t1 = window(trace)
    devs = sorted(trace.ops)
    busy = sum(busy_ns(trace.ops[d], t0, t1) for d in devs) / max(len(devs), 1)
    return busy * 1e-9, (t1 - t0) * 1e-9


def step_modules(trace: Trace, device: str) -> list:
    """The window's executions of the train-step program on ``device``."""
    t0, t1 = window(trace)
    return [m for m in trace.modules.get(device, [])
            if STEP_PROGRAM in m[2] and m[0] >= t0 and m[0] < t1]


def per_step_op_ns(trace: Trace, device: str, pred) -> list:
    """Summed duration of the ops matching ``pred(name, text)`` inside each
    of the window's train-step executions, in order."""
    mods = step_modules(trace, device)
    out = [0] * len(mods)
    ops = trace.ops.get(device, [])
    j = 0
    for k, (ms, md, _) in enumerate(mods):
        me = ms + md
        while j < len(ops) and ops[j][0] < ms:
            j += 1
        i = j
        while i < len(ops) and ops[i][0] < me:
            s, d, name, text = ops[i]
            if pred(name, text):
                out[k] += d
            i += 1
    return out


def idle_gaps(trace: Trace, device: str, top: int = 10) -> list:
    """The longest gaps between busy intervals inside the window, each as
    ``[label, seconds]``: the label is the innermost harness host span open
    at the gap's midpoint (``idle`` where none is)."""
    t0, t1 = window(trace)
    busy = clip(union(trace.ops.get(device, [])), t0, t1)
    edges = [t0] + [x for iv in busy for x in iv] + [t1]
    gaps = [(edges[i + 1] - edges[i], edges[i], edges[i + 1])
            for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    gaps.sort(reverse=True)
    spans = [h for h in trace.host if h[2] != "bench/window"]
    out = []
    for dur, s, e in gaps[:top]:
        mid = (s + e) // 2
        open_ = [h for h in spans if h[0] <= mid < h[0] + h[1]]
        label = min(open_, key=lambda h: h[1])[2] if open_ else "idle"
        out.append([label, dur * 1e-9])
    return out


def top_ops(trace: Trace, device: str, top: int = 10) -> list:
    """The operations that took most device time in the window, by name,
    as ``[name, seconds]``."""
    t0, t1 = window(trace)
    tot = {}
    for s, d, name, _ in trace.ops.get(device, []):
        if t0 <= s < t1:
            tot[name] = tot.get(name, 0) + d
    return [[n, v * 1e-9] for n, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:top]]


def steps_op_ns(ctx, pred) -> tuple:
    """(plain, refresh): per window step, the device time of the ops
    matching ``pred``, split by the step kind ``ctx.steps`` records.  The
    k-th train-step execution on ``ctx.device`` is the window's k-th step."""
    per = per_step_op_ns(ctx.trace, ctx.device, pred)
    if len(per) != len(ctx.steps):
        raise ValueError(f"{len(per)} train-step executions in the trace "
                         f"window for {len(ctx.steps)} steps")
    plain = [v for v, s in zip(per, ctx.steps) if not s.refresh]
    refresh = [v for v, s in zip(per, ctx.steps) if s.refresh]
    return plain, refresh
