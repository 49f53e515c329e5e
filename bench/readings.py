#!/usr/bin/env python3
"""Readings that the limits of ``bench/limits/<cell>.json`` are set from,
many seeds in one process (so that the programs compile or load once).

    python3 bench/readings.py --workload <cell> --seeds 1,2,... \
        [--control-seeds 7,8,9] [--control-modes control,fd_high] \
        [--half-seeds 7,8,9]

For every ``--seeds`` seed: the program's first three steps against the
plain reference (the lower readings).  For every ``--control-seeds`` seed
and every ``--control-modes`` mode (``bench/reflib.py``; ``control`` by
default, the reference computed one precision below the configuration):
that lower-precision reference in the program's place.  For every
``--half-seeds`` seed: the reference that leaves out half of the batch
rows, in the program's place.  One JSON line per reading on standard
output, with the verdict of the cell's limits on it (``correct`` and each
number beside its limit).  The benchmark's own runs do not run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import harness, spec as spec_lib  # noqa: E402


def _seeds(s: str) -> list:
    return [int(x) for x in s.split(",") if x]


def _reading(cell, seed, kind, numbers, t0) -> dict:
    correct, checks = harness.verdict(numbers, cell.limits)
    return {"seed": seed, "kind": kind, "numbers": numbers,
            "correct": correct, "checks": checks,
            "seconds": time.perf_counter() - t0}


def readings(workload: str, seeds, control_seeds=(), half_seeds=(),
             root: str = spec_lib.ROOT, require_tpu: bool = True,
             control_modes=("control",)):
    """Yield ``{"seed", "kind", "numbers", "correct", "checks", "seconds"}``
    per reading; ``kind`` is ``program``, a control mode or
    ``half_batch``."""
    cell = spec_lib.Cell(spec_lib.load_spec(root), workload, root)
    jax = harness.setup_jax(root, cache=require_tpu)
    if require_tpu and jax.devices()[0].platform != "tpu":
        raise SystemExit("readings: no TPU")
    refs = {}
    for seed in seeds:
        t = time.perf_counter()
        prog = harness.Program(cell, seed)
        got = prog.check_steps()
        prog.free()
        refs[seed] = harness.reference_readings(cell, seed)
        yield _reading(cell, seed, "program",
                       harness.compare(got, refs[seed]), t)
    batch = cell.config["batch"]
    rows = [1.0] * (batch // 2) + [0.0] * (batch - batch // 2)
    groups = [(mode, control_seeds, {"mode": mode}) for mode in control_modes]
    groups.append(("half_batch", half_seeds, {"row_weights": rows}))
    for kind, group, how in groups:
        for seed in group:
            t = time.perf_counter()
            if seed not in refs:
                refs[seed] = harness.reference_readings(cell, seed)
            got = harness.reference_readings(cell, seed, **how)
            yield _reading(cell, seed, kind,
                           harness.compare(got, refs[seed]), t)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", default="")
    p.add_argument("--control-seeds", default="")
    p.add_argument("--control-modes", default="control")
    p.add_argument("--half-seeds", default="")
    a = p.parse_args(argv)
    modes = [m for m in a.control_modes.split(",") if m]
    for r in readings(a.workload, _seeds(a.seeds), _seeds(a.control_seeds),
                      _seeds(a.half_seeds), control_modes=modes):
        print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
