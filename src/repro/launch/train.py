"""End-to-end training launcher.

Runs the training main path — ``make_train_step`` over the configured
optimizer — on whatever devices JAX finds: the CPU for tests, a TPU chip
for ``chip_smoke.py``.  The multi-pod mesh is lowered, not run, by
``dryrun.py``.

Features: config/arch selection, synthetic data pipeline, optimizer choice
(sketchy/shampoo/adam), async atomic checkpointing + restart, straggler
monitor, data-parallel sharded statistics over all local devices.

``train(parse_args(argv))`` is the in-process entry point; ``main`` is the
CLI.  To see where an iteration's time goes, run it under the profiler with
the loop's host spans on (``--profile-annotations``)::

    with jax.profiler.trace(log_dir):
        train(parse_args([..., "--profile-annotations"]))
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
from typing import Any, Callable, NamedTuple, Optional


class TrainRun(NamedTuple):
    """What one ``train`` call leaves behind."""
    step_fn: Callable        # the jitted train step
    params: Any              # final parameters
    opt_state: Any           # final optimizer state
    metrics: list            # per step: step, loss, grad_norm, time_s


def parse_args(argv=None) -> argparse.Namespace:
    """Parse the CLI; ``--rank-budget`` comes back as a ``RankBudget``."""
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="paper-lm-100m")
    p.add_argument("--reduced", action="store_true",
                   help="use the reduced smoke config (CPU-friendly)")
    p.add_argument("--optimizer", default="sketchy",
                   choices=["sketchy", "shampoo", "adam"])
    p.add_argument("--steps", type=int, default=200)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=128)
    p.add_argument("--lr", type=float, default=3e-3)
    p.add_argument("--rank", type=int, default=64)
    p.add_argument("--rank-budget", default=None, metavar="SPEC",
                   help="sketch-rank budget (sketchy only; core/sketchy."
                        "RankBudget): comma-separated key=value pairs from "
                        "total,min_k,max_k,every,policy — e.g. "
                        "'total=2048,min_k=8,max_k=128,policy=rho_greedy'. "
                        "Memory stays at max_k capacity while active rank "
                        "migrates to high-rho blocks; omitted keys use the "
                        "RankBudget defaults and --rank is ignored")
    p.add_argument("--update-every", type=int, default=10)
    p.add_argument("--block-size", type=int, default=1024)
    p.add_argument("--kernel-backend", default="auto",
                   choices=["auto", "xla", "pallas"],
                   help="optimizer kernel path: grid-over-N Pallas batched "
                        "kernels vs pure-XLA refs (auto = pallas on TPU)")
    p.add_argument("--second-moment-dtype", default="fp32",
                   choices=["fp32", "bf16", "int8"],
                   help="storage dtype for pooled second-moment stacks "
                        "between steps (core/quantize.py): fp32 = bitwise "
                        "parity, bf16 = 2x smaller, int8 = per-block "
                        "quantized matrix factors (~4x); compute stays f32")
    p.add_argument("--quantized-epilogue", default="auto",
                   choices=["auto", "off", "on"],
                   help="fused int8 compute (core/api.py): with "
                        "--second-moment-dtype int8, auto fuses dequantize/"
                        "requantize into the pallas kernels (no f32 factor "
                        "stack at the pool boundary); off = always "
                        "dequantize at the boundary; on = force the fused "
                        "math on any backend (sketchy only)")
    p.add_argument("--refresh-schedule", default="synchronized",
                   choices=["synchronized", "staggered"],
                   help="refresh phasing over the pooled block stacks: "
                        "synchronized = all blocks every update-every steps "
                        "(eigh spike); staggered = ~N/update_every blocks "
                        "per step, same amortized cost, flat step time")
    p.add_argument("--refresh-mode", default="inline",
                   choices=["inline", "async"],
                   help="when the refresh lands (core/api.py): inline = "
                        "same step (parity default); async = launched at "
                        "step t into a double-buffered pending slot and "
                        "committed at t+1, so the eigh + butterfly merge "
                        "overlap with the next step's forward/backward")
    p.add_argument("--profile-annotations", action="store_true",
                   help="host trace spans in the training loop: a 'train' "
                        "step annotation per iteration holding train/data, "
                        "train/dispatch, train/fetch and train/checkpoint, "
                        "on the profiler's clock (seen under "
                        "jax.profiler.trace); the compiled step's name "
                        "scopes are always on")
    p.add_argument("--stats-reduction", default="replicated",
                   choices=["replicated", "sharded"],
                   help="second-moment maintenance across data-parallel "
                        "shards (src/repro/distributed/): replicated = every "
                        "device maintains identical stats from mean grads; "
                        "sharded = local FD updates + log-depth butterfly "
                        "sketch merge over the data axis at refresh time "
                        "(sketchy only; needs > 1 device)")
    p.add_argument("--checkpoint-dir", default=None)
    p.add_argument("--checkpoint-every", type=int, default=50)
    p.add_argument("--resume", action="store_true")
    p.add_argument("--log-every", type=int, default=10)
    p.add_argument("--metrics-out", default=None)
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)
    if args.rank_budget:
        from repro.core.sketchy import RankBudget
        from repro.launch.flags import parse_kv_spec
        args.rank_budget = parse_kv_spec(
            args.rank_budget, RankBudget,
            aliases={"every": "realloc_every"},
            error=lambda m: p.error(f"--rank-budget: {m}"))
    return args


def configs(args: argparse.Namespace) -> tuple:
    """The (ModelConfig, OptimizerConfig) pair ``train(args)`` runs."""
    from repro.configs import registry
    from repro.core.factory import OptimizerConfig

    cfg = registry.get_reduced(args.arch) if args.reduced \
        else registry.get_config(args.arch)
    opt_cfg = OptimizerConfig(
        name=args.optimizer, learning_rate=args.lr, total_steps=args.steps,
        rank=args.rank, rank_budget=args.rank_budget,
        block_size=args.block_size,
        update_every=args.update_every, weight_decay=1e-4,
        kernel_backend=args.kernel_backend,
        refresh_schedule=args.refresh_schedule,
        refresh_mode=args.refresh_mode,
        second_moment_dtype=args.second_moment_dtype,
        quantized_epilogue=args.quantized_epilogue,
        stats_reduction=args.stats_reduction)
    return cfg, opt_cfg


def train(args: argparse.Namespace,
          on_step: Optional[Callable[[dict], None]] = None) -> TrainRun:
    """Train for ``args.steps`` steps in this process; ``on_step`` sees
    each step's metrics as soon as the step has finished on the device."""
    import jax
    import jax.numpy as jnp

    from repro.core.factory import make_optimizer
    from repro.data.pipeline import DataConfig, SyntheticLM
    from repro.launch.mesh import make_mesh
    from repro.models import model as model_lib
    from repro.train import checkpoint as ckpt_lib
    from repro.train.elastic import StragglerMonitor
    from repro.train.trainer import make_train_step

    cfg, opt_cfg = configs(args)
    tx = make_optimizer(opt_cfg)

    data = SyntheticLM(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq,
        global_batch=args.batch, seed=args.seed,
        num_codebooks=cfg.num_codebooks,
        embed_dim=0 if cfg.embed_inputs else cfg.d_model))

    key = jax.random.PRNGKey(args.seed)
    params = model_lib.init_params(cfg, key)
    opt_state = tx.init(params)
    start_step = 0

    ckpt = None
    if args.checkpoint_dir:
        ckpt = ckpt_lib.AsyncCheckpointer(args.checkpoint_dir)
        if args.resume and ckpt_lib.latest_step(args.checkpoint_dir) is not None:
            (params, opt_state), start_step, extra = ckpt_lib.restore(
                args.checkpoint_dir, (params, opt_state))
            print(f"resumed from step {start_step}")

    dp_mesh = None
    if args.stats_reduction == "sharded":
        ndev = len(jax.devices())
        if ndev < 2 or args.batch % ndev:
            raise ValueError(
                f"--stats-reduction sharded needs more than one device and a "
                f"batch divisible by the device count; found {ndev} "
                f"device(s) and --batch {args.batch}")
        dp_mesh = make_mesh((ndev,), ("data",))
        print(f"sharded stats over data axis ({ndev} devices)")
    # make_train_step jits with params/opt_state donated; the async
    # checkpointer snapshots to host before the next step consumes them
    step_fn = make_train_step(cfg, tx, data_parallel_mesh=dp_mesh)
    monitor = StragglerMonitor()
    metrics_log = []

    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"arch={cfg.name} params={n_params/1e6:.1f}M optimizer={args.optimizer}")

    if args.profile_annotations:
        step_span = lambda s: jax.profiler.StepTraceAnnotation(
            "train", step_num=s)
        span = jax.profiler.TraceAnnotation
    else:
        step_span = span = lambda _: contextlib.nullcontext()
    for step in range(start_step, args.steps):
        with step_span(step):
            with span("train/data"):
                batch = {k: jnp.asarray(v)
                         for k, v in data.batch(step).items()}
            monitor.start()
            with span("train/dispatch"):
                params, opt_state, metrics = step_fn(params, opt_state, batch)
            with span("train/fetch"):
                loss = float(metrics["loss"])
                dt = monitor.stop()
                gnorm = float(metrics["grad_norm"])
            metrics_log.append({"step": step, "loss": loss,
                                "grad_norm": gnorm, "time_s": dt})
            if on_step is not None:
                on_step(metrics_log[-1])
            if step % args.log_every == 0 or step == args.steps - 1:
                print(f"step {step:5d} loss {loss:.4f} "
                      f"gnorm {gnorm:.3f} {dt*1e3:.0f}ms")
            if ckpt and step and step % args.checkpoint_every == 0:
                with span("train/checkpoint"):
                    ckpt.save(step, (params, opt_state))
    if ckpt:
        with span("train/checkpoint"):
            ckpt.save(args.steps, (params, opt_state))
        ckpt.wait()
    if monitor.flagged:
        print(f"straggler steps flagged: {monitor.flagged} "
              f"(median {monitor.median*1e3:.0f}ms)")
    if args.metrics_out:
        os.makedirs(os.path.dirname(args.metrics_out) or ".", exist_ok=True)
        with open(args.metrics_out, "w") as f:
            json.dump(metrics_log, f, indent=2)
    return TrainRun(step_fn=step_fn, params=params, opt_state=opt_state,
                    metrics=metrics_log)


def main(argv=None) -> None:
    from repro.launch.compile_cache import enable_compile_cache
    args = parse_args(argv)
    enable_compile_cache()
    train(args)


if __name__ == "__main__":
    main()
