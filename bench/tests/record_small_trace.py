#!/usr/bin/env python3
"""Record the small trace that ``test_bench_scopes.py`` reads, on a TPU.

    python3 bench/tests/record_small_trace.py bench/tests/data/small_trace_scoped.json.gz

The program's reduced Sketchy step (``--reduced``: three layers of width
64, vocabulary 256; block 32, rank 4, refresh every second step, batch 4 x
64) with recomputation on, as the full-size cells run it: two steps warm
it up, then four steps run inside a ``bench/window`` host span with the
harness's per-step spans, under the profiler.  The trace is reduced by
``bench/trace.load`` with the compiled step's name scopes and written as
JSON with one more key, ``refresh``: per window step, whether it refreshed
the sketches (steps 2 and 4 of the run do).
"""
from __future__ import annotations

import dataclasses
import functools
import glob
import gzip
import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

WARMUP, WINDOW, UPDATE_EVERY = 2, 4, 2
ARGV = ["--reduced", "--optimizer", "sketchy", "--block-size", "32",
        "--rank", "4", "--update-every", str(UPDATE_EVERY), "--batch", "4",
        "--seq", "64", "--steps", "20"]


def record(out: str, seed: int = 0) -> dict:
    import jax
    import jax.numpy as jnp
    from bench import data as data_lib, trace as trace_lib
    from repro.core.factory import make_optimizer
    from repro.launch import train as train_lib
    from repro.models import model as model_lib
    from repro.train.trainer import make_train_step

    # JAX's persistent cache keys a program without its metadata, so a
    # cached step would bring back the name scopes of an older program
    jax.config.update("jax_enable_compilation_cache", False)
    args = train_lib.parse_args(ARGV)
    cfg, opt_cfg = train_lib.configs(args)
    cfg = dataclasses.replace(cfg, remat=True)
    tx = make_optimizer(opt_cfg)
    params = jax.jit(functools.partial(model_lib.init_params, cfg))(
        jax.random.PRNGKey(seed))
    state = jax.jit(tx.init)(params)
    step_fn = make_train_step(cfg, tx)
    data = data_lib.SyntheticLM(cfg.vocab_size, args.seq, args.batch, seed)

    def batch(i):
        return {k: jnp.asarray(v) for k, v in data.batch(i).items()}

    for i in range(WARMUP):
        params, state, m = step_fn(params, state, batch(i))
        float(m["loss"])
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    span = jax.profiler.TraceAnnotation
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with span("bench/window"):
        for i in range(WARMUP, WARMUP + WINDOW):
            with span("bench/data"):
                b = batch(i)
            with span("bench/dispatch"):
                params, state, m = step_fn(params, state, b)
            with span("bench/fetch"):
                float(m["loss"])
    jax.profiler.stop_trace()
    hlo = step_fn.lower(params, state, batch(0)).compile().as_text()
    path = sorted(glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    tr = trace_lib.load(path, trace_lib.scopes_from_hlo(hlo))
    shutil.rmtree(tmp, ignore_errors=True)
    d = tr.to_json()
    d["refresh"] = [i % UPDATE_EVERY == 0
                    for i in range(WARMUP, WARMUP + WINDOW)]
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    with gzip.open(out, "wt") as f:
        json.dump(d, f)
    return d


if __name__ == "__main__":
    d = record(sys.argv[1])
    print(json.dumps({dev: len(rows) for dev, rows in d["ops"].items()}))
