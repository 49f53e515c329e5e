#!/usr/bin/env python3
"""Smoke test of the Sketchy training path on a TPU.

    python3 chip_smoke.py            # one chip: kernel phase, train phase
    python3 chip_smoke.py --chips 4  # four chips: sharded vs replicated stats

One process drives the chip(s).  With no accelerator (e.g. under
``JAX_PLATFORMS=cpu``) it exits non-zero before doing anything; it never
falls back to the CPU or to interpreted kernels.  Any failed check in any
phase ends the run with a non-zero exit.  The last line of standard output
is ``{"ok": true, "device": {"platform", "kind", "count"}}``; everything
else is printed before it.  Per-step wall times and peak HBM are printed
for information only: they are not benchmark numbers.

Kernel phase: every batched kernel of the pallas ``KernelSet`` runs on the
chip at the engine's widths (blocks of d 768 and 1024 against the other
width, sketch rank ell 64 and 256, f32 and int8 stacks, f32 and bf16
gradients into the apply) and is compared with a float64 numpy reference
computed on the host.

Train phase: ``launch/train.py`` trains paper-lm-100m at its published
widths with Sketchy (block 1024, rank 64, Pallas kernels) for 12 steps of
batch 8 x 2048 tokens, refresh every 5.  Checked: ``tpu_custom_call`` in
the compiled step, a finite loss after every step and a last loss below
the first, no compile after the first step, and the first optimizer
direction against the seed Sketchy of ``tests/reference_impls.py`` run in
float64 on the host CPU, no farther from it than the f32 seed on the chip
allows (see ``SEED_TOL_FACTOR``).

Four chips: the same training, at block 256 (see ``FOUR_CHIP_ARGV``), with
``--stats-reduction sharded`` on a 4-device data mesh (local FD sketches,
butterfly merge) next to the replicated path on the same batches; checked:
every device holds its copy and did work, the sharded step holds the
butterfly's collective-permutes, and the losses agree.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
import traceback
from concurrent.futures import ThreadPoolExecutor

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))
sys.path.insert(0, os.path.join(REPO, "tests"))   # reference_impls

SEED = 0
# Kernel phase: max |kernel - f64 reference| / max |reference| per call.
# f32 outputs: f32 accumulation over K <= 1792 terms gives about
# sqrt(K) * 2^-24 = 2.5e-6; a single-pass bf16 contraction gives about
# 2^-8.5 / sqrt(K) = 7e-5 on a Gram and more on the apply.
TOL_F32 = 1e-5
# bf16 outputs (the apply keeps the gradient's dtype): output rounding,
# at most half a bf16 ulp, 2^-8 of the element's binade, plus the f32
# error above.
TOL_BF16_OUT = 2.0 ** -8 + TOL_F32
# Train phase: first Sketchy direction, per layer, as the relative
# Frobenius error against the seed Sketchy run in float64 on the host.  In
# f32 this direction is only determined to a few percent in the deeper
# layers: the 64th eigenvalue of G G^T, which sets the scale of the tail
# (rho), lies 1e-5 to 1e-7 below the largest, and an f32 eigh of the Gram
# resolves eigenvalues only to about 1e-7 of the largest.  So the engine
# (Pallas kernels, on the chip) may be at most SEED_TOL_FACTOR times as far
# from the float64 seed as the f32 seed on the same chip is, plus a floor.
# Across 13 f32 rounding realizations of the seed on the CPU (the same
# gradients with rows and columns permuted within each block), this error
# varied by at most 2.1x between any two.
SEED_TOL_FACTOR = 4.0
SEED_TOL_FLOOR = 1e-4
# Four chips, sharded against replicated loss per step.  Steps 0 and 1 see
# identical parameters (the warmup learning rate is 0 at step 0), so only
# the order of the loss reduction differs.  Later, the sharded statistics
# (each shard sketches its local gradients, the butterfly merges) and the
# replicated ones (one sketch of the mean gradient) precondition
# differently; the bound is the one tests/test_distributed.py uses for
# the same comparison.
TOL_SHARDED_FIRST_LOSS = 1e-3
TOL_SHARDED_LOSS = (0.15, 0.05)    # |a - b| < rel * |b| + abs

# Four chips, block 256 (the paper's 1:4 rank-to-block ratio at rank 64):
# at block 1024 each of that phase's two programs compiles XLA's QDWH eigh
# of 832- and 1088-wide Grams, about 7 minutes on the host (compile
# rehearsal for a described v5e:2x2), at four chips' cost; at 256 the
# Grams are 320 wide and the sharded program compiles in about a minute.
# The sharded-statistics path (local FD sketches, butterfly merge, the
# sharded step) is the same code at any block size.
FOUR_CHIP_ARGV = ["--block-size", "256"]

TRAIN_ARGV = ["--arch", "paper-lm-100m", "--optimizer", "sketchy",
              "--block-size", "1024", "--kernel-backend", "auto",
              "--steps", "12", "--update-every", "5", "--batch", "8",
              "--seq", "2048", "--log-every", "1", "--seed", str(SEED)]


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    print(f"  ok: {what}", flush=True)


class CompileCounter:
    """Counts traces and XLA compiles through jax.monitoring."""

    _NAMES = {"/jax/core/compile/jaxpr_trace_duration": "trace",
              "/jax/core/compile/backend_compile_duration": "compile"}

    def __init__(self):
        import jax
        self.events = []     # (event, fun_name, seconds, thread id)
        self.main_thread = threading.get_ident()
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **kw):
        if event in self._NAMES:
            self.events.append((self._NAMES[event], kw.get("fun_name"),
                                duration, threading.get_ident()))

    def mark(self) -> int:
        return len(self.events)


# ---------------------------------------------------------------- kernels

def _rel_err(got, want) -> float:
    import numpy as np
    got = np.asarray(got, np.float64)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def kernel_phase() -> None:
    import numpy as np
    import jax
    import jax.numpy as jnp
    from repro.kernels import registry

    ks = registry.get_kernels("auto")
    check(ks.backend == "pallas", "auto kernel set is pallas")
    rng = np.random.default_rng(SEED)
    N = 4
    f64 = lambda x: np.asarray(x, np.float64)

    def normal(*shape, scale=1.0):
        return (rng.standard_normal(shape) * scale).astype(np.float32)

    def int8(*shape):
        return np.clip(np.round(rng.standard_normal(shape) * 40),
                       -127, 127).astype(np.int8)

    def gram(m):
        return np.swapaxes(m, 1, 2) @ m

    def apply(u, c, b, g):
        return b[:, None, None] * g + u @ (c[:, :, None]
                                           * (np.swapaxes(u, 1, 2) @ g))

    for d in (768, 1024):
        r = 1792 - d          # the other block width of paper-lm-100m
        for ell in (64, 256):
            tag = f"d={d} ell={ell} r={r} N={N}"
            t0 = time.perf_counter()
            # FD Gram of M = [B, G], f32 stack
            a = normal(N, d, ell + r)
            err = _rel_err(ks.batched_gram(jnp.asarray(a)), gram(f64(a)))
            print(f"  batched_gram f32 {tag}: rel err {err!r}")
            check(err <= TOL_F32, f"batched_gram f32 {tag} within {TOL_F32}")
            err = _rel_err(ks.gram(jnp.asarray(a[0])), gram(f64(a[:1]))[0])
            print(f"  gram (single block) {tag}: rel err {err!r}")
            check(err <= TOL_F32, f"gram {tag} within {TOL_F32}")

            # mixed Gram: int8 eigenvector stack, f32 new factor
            vq, colw, an = int8(N, d, ell), np.abs(normal(N, ell)) * 0.01, \
                normal(N, d, r)
            m = np.concatenate([f64(vq) * f64(colw)[:, None, :], f64(an)], 2)
            err = _rel_err(ks.batched_gram_mixed(
                jnp.asarray(vq), jnp.asarray(colw), jnp.asarray(an)), gram(m))
            print(f"  batched_gram_mixed int8 {tag}: rel err {err!r}")
            check(err <= TOL_F32,
                  f"batched_gram_mixed int8 {tag} within {TOL_F32}")

            # low-rank apply, f32 and bf16 gradients
            u = normal(N, d, ell, scale=d ** -0.5)
            c = np.abs(normal(N, ell))
            b = np.abs(normal(N))
            g32 = normal(N, d, r)
            err = _rel_err(ks.batched_lowrank_apply(
                jnp.asarray(u), jnp.asarray(c), jnp.asarray(b),
                jnp.asarray(g32)), apply(f64(u), f64(c), f64(b), f64(g32)))
            print(f"  batched_lowrank_apply f32 {tag}: rel err {err!r}")
            check(err <= TOL_F32,
                  f"batched_lowrank_apply f32 {tag} within {TOL_F32}")
            err = _rel_err(ks.lowrank_apply(
                jnp.asarray(u[0]), jnp.asarray(c[0]), jnp.asarray(b[0]),
                jnp.asarray(g32[0])),
                apply(f64(u[:1]), f64(c[:1]), f64(b[:1]), f64(g32[:1]))[0])
            print(f"  lowrank_apply (single block) {tag}: rel err {err!r}")
            check(err <= TOL_F32, f"lowrank_apply {tag} within {TOL_F32}")
            gbf = jnp.asarray(g32, jnp.bfloat16)
            out = ks.batched_lowrank_apply(
                jnp.asarray(u), jnp.asarray(c), jnp.asarray(b), gbf)
            check(out.dtype == jnp.bfloat16, "bf16 apply keeps bf16")
            err = _rel_err(out.astype(jnp.float32), apply(
                f64(u), f64(c), f64(b), f64(gbf.astype(jnp.float32))))
            print(f"  batched_lowrank_apply bf16 {tag}: rel err {err!r}")
            check(err <= TOL_BF16_OUT,
                  f"batched_lowrank_apply bf16 {tag} within {TOL_BF16_OUT}")

            # apply on the int8 pool storage (scale^2 folded into coeffs)
            scale = (np.abs(normal(N, 1, 1)) * 1e-3 + 1e-4).astype(
                np.float32)
            err = _rel_err(ks.batched_lowrank_apply_quantized(
                jnp.asarray(vq), jnp.asarray(scale), jnp.asarray(c),
                jnp.asarray(b), jnp.asarray(g32)),
                apply(f64(vq) * f64(scale), f64(c), f64(b), f64(g32)))
            print(f"  batched_lowrank_apply_quantized {tag}: rel err "
                  f"{err!r}")
            check(err <= TOL_F32,
                  f"batched_lowrank_apply_quantized {tag} within {TOL_F32}")

            # fused FD write-back: project, then requantize per block
            w_top, w_bot = normal(N, ell, ell) * 0.01, normal(N, r, ell)
            un = f64(vq) @ f64(w_top) + f64(an) @ f64(w_bot)
            values, sc = ks.batched_project_quantize(
                jnp.asarray(vq), jnp.asarray(w_top), jnp.asarray(an),
                jnp.asarray(w_bot))
            values, sc = np.asarray(values), f64(sc)
            check(values.dtype == np.int8, "project_quantize values int8")
            sc_ref = np.max(np.abs(un), axis=(1, 2), keepdims=True) / 127.0
            sc_err = float(np.max(np.abs(sc - sc_ref) / sc_ref))
            q_err = float(np.max(np.abs(f64(values) * sc - un)
                                 / (0.5 * sc + TOL_F32 * 127.0 * sc_ref)))
            print(f"  batched_project_quantize {tag}: scale rel err "
                  f"{sc_err!r}, dequant err / (half step + f32 tol) "
                  f"{q_err!r}")
            check(sc_err <= TOL_F32 and q_err <= 1.0,
                  f"batched_project_quantize {tag} within half an int8 "
                  f"step + {TOL_F32}")
            print(f"  ({tag}: {time.perf_counter() - t0:.1f}s with "
                  f"compiles)")


# ------------------------------------------------------------------ train

def _pick(tree) -> dict:
    """One attention and one MLP weight, every layer."""
    return {"attn.wo": tree["layers"]["attn"]["wo"],
            "mlp.w_up": tree["layers"]["mlp"]["w_up"]}


def _on_chip_shapes(cfg, args):
    """Shapes of the parameters and of one batch, placed on device 0."""
    import numpy as np
    import jax
    from jax.sharding import SingleDeviceSharding
    from repro.models import model as model_lib

    on_chip = SingleDeviceSharding(jax.devices()[0])
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=on_chip),
        jax.eval_shape(
            lambda: model_lib.init_params(cfg, jax.random.PRNGKey(0))))
    batch = {k: jax.ShapeDtypeStruct((args.batch, args.seq), np.int32,
                                     sharding=on_chip)
             for k in ("tokens", "labels")}
    f32 = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, np.float32,
                                       sharding=on_chip), _pick(params))
    return params, batch, f32


def _compile_engine_programs(cfg, ocfg, args):
    """The gradient of step 0 and the engine's first Sketchy direction on
    the picked leaves (f32 gradients in, so f32 directions out)."""
    import jax
    from repro.core import factory
    from repro.models import model as model_lib

    params, batch, f32 = _on_chip_shapes(cfg, args)
    t0 = time.perf_counter()
    grad_fn = jax.jit(jax.grad(lambda p, b: model_lib.loss_fn(cfg, p, b))) \
        .lower(params, batch).compile()
    engine = factory._direction(ocfg, ocfg.beta2)
    direction_fn = jax.jit(lambda g, p: engine.update(
        g, engine.init(p), p)[0]).lower(f32, _pick(params)).compile()
    print(f"  (worker) gradient and engine direction programs compiled in "
          f"{time.perf_counter() - t0:.1f}s", flush=True)
    return grad_fn, direction_fn


def _seed_sketchy(ocfg, state_dtype):
    """The seed Sketchy of tests/reference_impls.py (plain jnp, per leaf)
    at the optimizer's settings."""
    import reference_impls as ref
    from repro.core.sketchy import RankBudget, SketchyConfig

    return ref.seed_sketchy(SketchyConfig(
        rank_budget=RankBudget(min_k=ocfg.rank, max_k=ocfg.rank),
        block_size=ocfg.block_size, beta2=ocfg.beta2,
        update_every=ocfg.update_every, state_dtype=state_dtype))


def _compile_seed_program(cfg, ocfg, args):
    """The f32 seed Sketchy on the picked leaves, for the chip."""
    import jax
    import jax.numpy as jnp

    params, _, f32 = _on_chip_shapes(cfg, args)
    seed = _seed_sketchy(ocfg, jnp.float32)
    t0 = time.perf_counter()
    seed_fn = jax.jit(lambda g, p: seed.update(g, seed.init(p), p)[0]) \
        .lower(f32, _pick(params)).compile()
    print(f"  (worker) seed Sketchy program compiled in "
          f"{time.perf_counter() - t0:.1f}s", flush=True)
    return seed_fn


def _host_f64_seed(ocfg, grads, params) -> dict:
    """The seed Sketchy's first direction in float64 on the host CPU."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    cpu = jax.devices("cpu")[0]
    t0 = time.perf_counter()
    with jax.enable_x64(True):
        seed = _seed_sketchy(ocfg, jnp.float64)
        put = lambda tree: jax.tree.map(
            lambda x: jax.device_put(np.asarray(x, np.float64), cpu), tree)
        out = jax.device_get(jax.jit(lambda g, p: seed.update(
            g, seed.init(p), p)[0])(put(grads), put(params)))
    print(f"  float64 seed Sketchy on the host: "
          f"{time.perf_counter() - t0:.1f}s")
    return out


def _first_direction_check(cfg, ocfg, args, engine_programs, seed_program):
    """The engine's first Sketchy direction (Pallas kernels) and the f32
    seed Sketchy (plain jnp), both on the chip, against the float64 seed on
    the host, all fed the same full-width gradients of step 0, on one
    attention and one MLP weight of every layer."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from repro.data.pipeline import DataConfig, SyntheticLM
    from repro.models import model as model_lib

    t0 = time.perf_counter()
    grad_fn, direction_fn = engine_programs.result()
    seed_fn = seed_program.result()
    print(f"  waited {time.perf_counter() - t0:.1f}s for the worker")
    check("tpu_custom_call" in direction_fn.as_text(),
          "tpu_custom_call in the compiled engine direction")
    params = model_lib.init_params(cfg, jax.random.PRNGKey(args.seed))
    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size,
                                  seq_len=args.seq, global_batch=args.batch,
                                  seed=args.seed))
    batch = {k: jnp.asarray(v) for k, v in data.batch(0).items()}
    grads = jax.tree.map(lambda g: g.astype(jnp.float32),
                         _pick(grad_fn(params, batch)))
    params = _pick(params)
    d_engine = jax.device_get(direction_fn(grads, params))
    d_seed = jax.device_get(seed_fn(grads, params))
    d_exact = _host_f64_seed(ocfg, jax.device_get(grads),
                             jax.device_get(params))

    for name, exact in d_exact.items():
        got = np.asarray(d_engine[name], np.float64)
        seed = np.asarray(d_seed[name], np.float64)
        check(np.all(np.isfinite(got)), f"first direction {name} finite")
        worst = 0.0
        for layer in range(exact.shape[0]):
            norm = np.linalg.norm(exact[layer])
            err = float(np.linalg.norm(got[layer] - exact[layer]) / norm)
            err_seed = float(np.linalg.norm(seed[layer] - exact[layer])
                             / norm)
            tol = SEED_TOL_FACTOR * err_seed + SEED_TOL_FLOOR
            worst = max(worst, err / tol)
            print(f"  first direction {name}[{layer}] vs the float64 seed: "
                  f"engine rel err {err!r}, f32 seed rel err {err_seed!r}, "
                  f"tolerance {tol!r}")
        check(worst <= 1.0, f"first direction {name}: in every layer the "
              f"engine is within {SEED_TOL_FACTOR} x the f32 seed's error "
              f"+ {SEED_TOL_FLOOR} of the float64 seed Sketchy (worst "
              f"err/tol {worst!r})")


def train_args(*extra):
    from repro.launch import train as train_lib
    return train_lib.parse_args(TRAIN_ARGV + list(extra))


def train_phase(counter, engine_programs, seed_program) -> None:
    """The two futures: ``_compile_engine_programs`` and
    ``_compile_seed_program`` on the worker thread."""
    import numpy as np
    import jax
    from repro.kernels import registry
    from repro.launch import train as train_lib

    check(registry.resolve_backend("auto") == "pallas",
          "resolve_backend('auto') == 'pallas'")
    check(registry.interpret_mode() is False, "interpret_mode() is False")
    args = train_args()
    cfg, opt_cfg = train_lib.configs(args)
    marks = []
    t0 = time.perf_counter()
    run = train_lib.train(args, on_step=lambda m: marks.append(
        counter.mark()))
    print(f"  train: {time.perf_counter() - t0:.1f}s for {args.steps} steps")
    compiles = [e for e in counter.events
                if e[:2] == ("compile", "jit(train_step)")]
    print(f"  train_step compile: {[round(e[2], 1) for e in compiles]}s")
    for m in run.metrics:
        print(f"  step {m['step']:2d} loss {m['loss']!r} "
              f"grad_norm {m['grad_norm']!r} wall {m['time_s']!r}s")
    losses = [m["loss"] for m in run.metrics]
    check(len(losses) == args.steps, f"{args.steps} steps ran")
    check(all(np.isfinite(losses)), "loss finite after every step")
    check(losses[-1] < losses[0], f"last loss {losses[-1]!r} below first "
          f"{losses[0]!r}")
    late = [e for e in counter.events[marks[0]:marks[-1]]
            if e[3] == counter.main_thread]
    check(not late, f"no trace or compile after the first step "
          f"(refresh steps 5 and 10 included): {late}")
    stats = jax.devices()[0].memory_stats() or {}
    print(f"  peak HBM: {stats.get('peak_bytes_in_use')!r} B of "
          f"{stats.get('bytes_limit')!r} B")
    batch = {k: jax.ShapeDtypeStruct((args.batch, args.seq), np.int32)
             for k in ("tokens", "labels")}
    text = run.step_fn.lower(run.params, run.opt_state, batch) \
        .compile().as_text()
    n = text.count("tpu_custom_call")
    print(f"  compiled train step: {n} tpu_custom_call sites")
    check(n > 0, "tpu_custom_call in the compiled train step")
    del run, text     # free the trained state before the reference check
    _first_direction_check(cfg, opt_cfg, args, engine_programs,
                           seed_program)


def four_chip_phase() -> None:
    import numpy as np
    import jax
    from repro.launch import train as train_lib

    devices = jax.devices()
    check(len(devices) == 4, "four devices")
    args = train_args(*FOUR_CHIP_ARGV, "--stats-reduction", "sharded")
    t0 = time.perf_counter()
    run = train_lib.train(args)
    print(f"  sharded train: {time.perf_counter() - t0:.1f}s")
    leaves = [x for x in jax.tree.leaves((run.params, run.opt_state))
              if isinstance(x, jax.Array)]
    check(all(x.sharding.device_set == set(devices) for x in leaves),
          f"all {len(leaves)} parameter and optimizer-state arrays live on "
          f"the four devices")
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    print(f"  peak HBM per device after the sharded run: {peaks} B")
    check(min(peaks) > 2 ** 30,
          "every device held its share of the step (peak HBM > 1 GiB)")
    batch = {k: jax.ShapeDtypeStruct((args.batch, args.seq), np.int32)
             for k in ("tokens", "labels")}
    text = run.step_fn.lower(run.params, run.opt_state, batch) \
        .compile().as_text()
    print(f"  sharded step: {text.count('collective-permute')} "
          f"collective-permute, {text.count('tpu_custom_call')} "
          f"tpu_custom_call sites")
    check("collective-permute" in text,
          "butterfly merge rounds (collective-permute) in the sharded step")
    check("tpu_custom_call" in text, "tpu_custom_call in the sharded step")
    sharded = [m["loss"] for m in run.metrics]
    del run, leaves, text

    t0 = time.perf_counter()
    replicated = [m["loss"] for m in
                  train_lib.train(train_args(*FOUR_CHIP_ARGV)).metrics]
    print(f"  replicated train: {time.perf_counter() - t0:.1f}s")
    for i, (a, b) in enumerate(zip(sharded, replicated)):
        print(f"  step {i:2d} loss sharded {a!r} replicated {b!r} "
              f"rel diff {abs(a - b) / abs(b)!r}")
    check(all(np.isfinite(sharded)) and sharded[-1] < sharded[0],
          "sharded loss finite and falling")
    check(all(abs(a - b) <= TOL_SHARDED_FIRST_LOSS * abs(b)
              for a, b in zip(sharded[:2], replicated[:2])),
          f"steps 0-1 (same parameters): losses within "
          f"{TOL_SHARDED_FIRST_LOSS}")
    rel, tol = TOL_SHARDED_LOSS
    check(all(abs(a - b) < rel * abs(b) + tol
              for a, b in zip(sharded, replicated)),
          f"every step: |sharded - replicated| < {rel} * replicated + {tol}")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = p.parse_args(argv)

    # the float64 reference of the first-direction check runs on the host
    # CPU, so a platform list that names the TPU keeps the CPU beside it
    platforms = os.environ.get("JAX_PLATFORMS", "").split(",")
    if "tpu" in platforms and "cpu" not in platforms:
        os.environ["JAX_PLATFORMS"] = ",".join(platforms + ["cpu"])
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              f"device(s)", file=sys.stderr)
        return 1
    print(f"device {dev.device_kind} x{len(devices)}, jax {jax.__version__}, "
          f"compile cache {cache}", flush=True)
    counter = CompileCounter()
    try:
        if args.chips == 1:
            from repro.launch import train as train_lib
            targs = train_args()
            # the reference programs compile, one after the other, on a
            # worker thread meanwhile (the train step's compile is the
            # longer path; a third concurrent compile buys nothing but
            # host memory)
            pool = ThreadPoolExecutor(max_workers=1)
            engine_programs = pool.submit(_compile_engine_programs,
                                          *train_lib.configs(targs), targs)
            seed_program = pool.submit(_compile_seed_program,
                                       *train_lib.configs(targs), targs)
            t0 = time.perf_counter()
            print("kernel phase", flush=True)
            kernel_phase()
            print(f"kernel phase passed in {time.perf_counter() - t0:.1f}s")
            t0 = time.perf_counter()
            print("train phase", flush=True)
            train_phase(counter, engine_programs, seed_program)
            print(f"train phase passed in {time.perf_counter() - t0:.1f}s")
            pool.shutdown()
        else:
            t0 = time.perf_counter()
            print("four-chip phase", flush=True)
            four_chip_phase()
            print(f"four-chip phase passed in "
                  f"{time.perf_counter() - t0:.1f}s")
    except Exception as e:
        # exit now: a reference program may still be compiling on the
        # worker thread, and a failed smoke should not wait for it
        if isinstance(e, SmokeFailure):
            print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        else:
            traceback.print_exc()
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(1)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
