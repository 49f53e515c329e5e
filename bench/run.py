#!/usr/bin/env python3
"""The chip benchmark: one cell of ``BENCHMARK.json``, one run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A run builds the cell's training step as ``launch/train.train`` composes it
(``bench/harness.py``), drives its first three steps with the readings the
check takes (step 0 refreshes the sketches, steps 1-2 do not, so both
branches of the step have run), then measures a window of whole periods: a
period is the mix's ``period_steps`` consecutive steps (one refresh each for
Sketchy, one step for Adam), and another period starts only while the time
spent plus the last period's time stays within ``--seconds``.  Nothing
compiles in the window; a compile there fails the run.

After the window: peak device memory is read (the larger of the allocator's
peak of live buffers, which leaves out the scratch an executable takes
while it runs, and the step's own footprint by its compiled
``memory_analysis()``: arguments, outputs and temporaries, less what the
outputs alias), the program's state freed,
and the plain reference (``bench/configs/<config>.py`` and
``bench/optimizers/<optimizer>.py``) replays the first three steps from the
same seed; ``correct`` holds when every number of
``bench/limits/<cell>.json`` is within its limit.  ``--trace 1`` records the
window with the profiler and reports the cell's per-layer metrics
(``bench/metrics/<metric>.py``) in place of the end-to-end ones.

The last line of standard output is one JSON object; the numbers compared
and their limits are the last lines on standard error and the last key of
that object.  Without a TPU, or with fewer chips than the cell asks for,
the run prints no result and exits 2.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import flops, harness, spec as spec_lib  # noqa: E402
from bench import trace as trace_lib  # noqa: E402


class NoChip(Exception):
    pass


@dataclasses.dataclass
class Context:
    """What a per-layer metric reader sees."""
    trace: object
    device: str
    steps: list
    tokens_per_s: float
    chips: int
    peak: dict
    flops_per_token: float
    pool_groups: dict
    rank: int


def window(prog, seconds: float, annotate: bool) -> tuple:
    """Whole periods until the next would overrun ``seconds``; returns the
    step records and the window's length."""
    import jax
    steps = []
    span = jax.profiler.TraceAnnotation("bench/window") if annotate \
        else contextlib.nullcontext()
    with span:
        t0 = time.perf_counter()
        while True:
            tp = time.perf_counter()
            for _ in range(prog.period):
                steps.append(prog.step(annotate))
            now = time.perf_counter()
            if (now - t0) + (now - tp) > seconds:
                break
        elapsed = time.perf_counter() - t0
    return steps, elapsed


def run(argv=None, require_tpu: bool = True, fault: str = None,
        root: str = spec_lib.ROOT) -> dict:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    spec = spec_lib.load_spec(root)
    cell = spec_lib.Cell(spec, args.workload, root)
    jax = harness.setup_jax(root, cache=require_tpu)
    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu"
                        or len(devices) < cell.chips):
        raise NoChip(f"{args.workload} needs {cell.chips} TPU chip(s); JAX "
                     f"found {len(devices)} {devices[0].platform} device(s)")
    used = devices[:cell.chips]
    counter = harness.CompileCounter()

    prog = harness.Program(cell, args.seed, fault=fault)
    readings = prog.check_steps()
    compiles = counter.n

    trace_dir = os.path.join(root, ".bench_cache", "trace",
                             f"{args.workload}.{args.seed}")
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    t_window = time.perf_counter()
    steps, elapsed = window(prog, args.seconds, bool(args.trace))
    if args.trace:
        jax.profiler.stop_trace()
    if counter.n != compiles:
        raise RuntimeError(f"{counter.n - compiles} compile(s) inside the "
                           f"measured window")
    setup_s = t_window - T_START
    stats = [d.memory_stats() or {} for d in used]
    live = max(s.get("peak_bytes_in_use", 0) for s in stats)
    compiled = prog.compiled()
    ma = compiled.memory_analysis()
    footprint = int(ma.argument_size_in_bytes + ma.output_size_in_bytes
                    - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    hlo = compiled.as_text() if args.trace else None
    del compiled
    tokens = len(steps) * prog.batch_size * prog.seq
    prog.free()
    del prog

    ref = harness.reference_readings(cell, args.seed)
    numbers = harness.compare(readings, ref)
    correct, table = harness.verdict(numbers, cell.limits)

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(used),
              "memory_peak_bytes": int(max(live, footprint))}
    result = {"correct": bool(correct), "attempted": len(steps), "failed": 0}
    if args.trace:
        import glob
        path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                                recursive=True))[-1]
        # the executed program's HLO gives each operation its name scope
        tr = trace_lib.load(path, trace_lib.scopes_from_hlo(hlo))
        shutil.rmtree(trace_dir, ignore_errors=True)
        dev = sorted(tr.ops)[0]
        busy, win = trace_lib.busy_and_window_s(tr)
        ctx = Context(
            trace=tr, device=dev, steps=steps,
            tokens_per_s=tokens / elapsed, chips=len(used),
            peak=flops.peaks(devices[0].device_kind),
            **flops.shape_counts(cell))
        metrics = {}
        for m in cell.per_layer():
            v = cell.metric_reader(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        device.update(busy_s=busy, window_s=win)
        result["breakdown"] = {"device_ops": trace_lib.top_ops(tr, dev),
                               "idle_gaps": trace_lib.idle_gaps(tr, dev)}
    else:
        values = {
            "tokens_per_s": tokens / elapsed,
            "step_s_max": max(s.seconds for s in steps),
            "setup_s": setup_s,
        }
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end()}
    result.update(metrics=metrics, device=device, checks=table)
    print(f"bench: memory_peak_bytes {device['memory_peak_bytes']}: "
          f"allocator peak {live}, step footprint {footprint} (arguments "
          f"{ma.argument_size_in_bytes}, outputs {ma.output_size_in_bytes}, "
          f"aliased {ma.alias_size_in_bytes}, temporaries "
          f"{ma.temp_size_in_bytes}), limit "
          f"{stats[0].get('bytes_limit', 'n/a')}", file=sys.stderr)
    return result


def main(argv=None) -> int:
    try:
        result = run(argv)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, (value, limit) in result["checks"].items():
        print(f"check {name} {value!r} limit {limit!r}", file=sys.stderr)
    print(json.dumps(result))
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
