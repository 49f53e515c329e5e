"""Serving launcher: continuous batching + FD telemetry + online adaptation.

CPU-scale demo of the full serve loop (32k/500k decode lives in dryrun.py):

  # one-shot demo: submit a batch, drain, print tokens
  python -m repro.launch.serve --batch 4 --new-tokens 12

  # load-generator traffic + FD gradient monitor + S-AdaGrad adaptation
  python -m repro.launch.serve \\
      --traffic shape=step,rate=1.0,ticks=24,step_at=12 \\
      --monitor window=4,ell=8 --adapt lr=0.1,beta2=0.95

The structured flags are ``key=value,...`` specs parsed against the config
dataclasses themselves (launch/flags.py): ``--traffic`` -> TrafficConfig,
``--adapt`` -> AdaptConfig, ``--monitor`` -> MonitorConfig.  With traffic
enabled, each tick submits the generated arrivals, steps the engine, draws
a feedback batch, feeds its head gradient to the monitor, and runs one
adaptation step whenever the window policy says "adapt".
"""
from __future__ import annotations

import argparse


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="paper-lm-100m")
    p.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                   default=True, help="use the registry's reduced config "
                   "(--no-reduced for the full arch)")
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--max-seq", type=int, default=64)
    p.add_argument("--new-tokens", type=int, default=12)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--traffic", default=None, metavar="K=V,...",
                   help="TrafficConfig spec, e.g. shape=step,rate=1,ticks=24")
    p.add_argument("--adapt", default=None, metavar="K=V,...",
                   help="AdaptConfig spec, e.g. lr=0.1,beta2=0.95,ell=8")
    p.add_argument("--monitor", default=None, metavar="K=V,...",
                   help="MonitorConfig spec, e.g. window=4,ell=8")
    args = p.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    import numpy as np
    import jax

    from repro.configs import registry
    from repro.launch.flags import parse_kv_spec
    from repro.models import model as model_lib
    from repro.serve import (AdaptConfig, Engine, GradientMonitor,
                             LoadGenerator, MonitorConfig, OnlineAdapter,
                             Request, ServeConfig, TrafficConfig)

    cfg = registry.get_reduced(args.arch) if args.reduced \
        else registry.get_config(args.arch)
    if not cfg.embed_inputs or cfg.num_codebooks:
        p.error(f"serving supports token-input archs only; {args.arch!r} "
                f"has embed_inputs={cfg.embed_inputs} "
                f"num_codebooks={cfg.num_codebooks}")
    params = model_lib.init_params(cfg, jax.random.PRNGKey(args.seed))
    engine = Engine(cfg, params,
                    ServeConfig(batch=args.batch, max_seq=args.max_seq,
                                seed=args.seed))

    if args.traffic is None:
        # one-shot demo through the session API
        rng = np.random.default_rng(args.seed)
        handles = [engine.submit(Request(
            prompt=rng.integers(0, cfg.vocab_size, size=(8,),
                                dtype=np.int32),
            max_new_tokens=args.new_tokens)) for _ in range(args.batch)]
        engine.drain()
        for h in handles:
            print(f"request {h.id}: prompt={list(map(int, h.request.prompt))}"
                  f" -> {h.tokens}")
        return

    traffic = parse_kv_spec(args.traffic, TrafficConfig,
                            error=lambda m: p.error(f"--traffic: {m}"))
    gen = LoadGenerator(traffic, cfg.vocab_size)

    adapter = monitor = None
    if args.adapt is not None:
        adapter = OnlineAdapter(cfg, params, parse_kv_spec(
            args.adapt, AdaptConfig,
            error=lambda m: p.error(f"--adapt: {m}")))
    if args.monitor is not None:
        if adapter is None:
            adapter = OnlineAdapter(cfg, params)   # gradients for telemetry
        monitor = GradientMonitor(adapter.d, parse_kv_spec(
            args.monitor, MonitorConfig,
            error=lambda m: p.error(f"--monitor: {m}")))

    from repro.data.pipeline import DataConfig, SyntheticLM
    feedback = SyntheticLM(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=16, global_batch=4,
        seed=args.seed + 1))

    handles, adapt_steps = [], 0
    for tick in range(traffic.ticks):
        for req in gen.arrivals(tick):
            handles.append(engine.submit(req))
        engine.step()
        if adapter is not None:
            batch = feedback.batch(tick)
            loss, g = adapter.grad(params, batch)
            if monitor is None:
                run_adapt = True              # no policy: adapt every tick
            else:
                reading = monitor.observe(g)  # None mid-window
                run_adapt = reading is not None and reading.decision == "adapt"
            if run_adapt:
                params, loss = adapter.step(params, batch)
                engine.params = params        # serve the adapted weights
                adapt_steps += 1
    engine.drain()
    done = sorted(handles, key=lambda h: h.id)

    lat = [t1 - t0 for h in done for t0, t1 in
           zip(h.token_times, h.token_times[1:])]
    print(f"served {len(done)} requests, "
          f"{sum(len(h.tokens) for h in done)} tokens over "
          f"{engine.step_count} engine steps")
    if lat:
        print(f"inter-token latency p50={np.percentile(lat, 50)*1e3:.2f}ms "
              f"p99={np.percentile(lat, 99)*1e3:.2f}ms")
    if monitor is not None:
        for r in monitor.readings:
            print(r)
    if adapter is not None:
        print(f"adaptation steps: {adapt_steps} "
              f"(hyperparams: {adapter.hyperparams})")


if __name__ == "__main__":
    main()
