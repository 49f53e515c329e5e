"""Benchmark harness — one benchmark per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (and, with ``--json PATH``,
writes the same rows as a machine-readable JSON list for trajectory files):
  fig1_memory_<opt>        second-moment bytes for a BERT-large-ish layer set
  tbl3_convex_<dataset>    average cumulative online loss per learner
  fig3_spectral_decay      intrinsic dim + top-256 mass of EMA Kron factors
  lem1_fd_error            FD op-norm error vs the Lemma-1 bound
  fig2_lm_quality          small-LM loss after N steps per optimizer
  opt_step_time            wall-time per optimizer step (CPU, small shapes)
  opt_overhead_vs_adam     amortized sketchy step cost as a multiple of
                           adam's on the same block (unitless ratio row —
                           gated with a tolerance by scripts/bench_gate.py)
  opt_step_time_autotuned  pooled pallas step with a freshly force-tuned
                           cache (kernels/autotune.tune_into_cache) vs the
                           untuned bn_stack=1 defaults
  opt_step_time_multileaf  pooled-engine step over a >=100-leaf tree: wall
                           time + compiled-computation (jaxpr eqn) counts vs
                           the per-leaf dispatch baseline
  opt_step_time_kernels    pooled multi-leaf step per kernel_backend
                           ("xla" batched refs vs "pallas" grid-over-N
                           batched kernels; interpret mode on CPU)
  opt_step_time_{inline,async}_refresh  refresh-step direction critical
                           path per refresh_mode: async's one-step-stale
                           pipeline compiles ZERO eigh sites on the
                           direction path (overlap win), donated buffers
  lm_step_time_refresh_schedule  end-to-end reduced-LM step time,
                           synchronized vs staggered refresh phasing
                           (mean + spike max)
  bytes_on_wire_per_refresh  sketch-merge wire bytes per device per refresh
                           (distributed/sketch_merge.py int8 wire, log-depth
                           butterfly) vs the dense fp32 covariance
                           all-reduce at the same depth
  opt_step_time_sharded_stats  engine step under stats_reduction="sharded"
                           on an 8-device host-platform mesh (subprocess:
                           the bench process itself must keep ONE device)
  serve_latency_{constant,step}_traffic  p50/p99 inter-token latency of the
                           continuous-batching engine under load-generator
                           traffic (serve/loadgen.py shapes)
  monitor_overhead_per_window  FD gradient-monitor cost per feedback window
                           (serve/monitor.py: window x fd_update + the
                           window-boundary signal reads)
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

# rows accumulated for --json output: (name, us_per_call, derived)
_ROWS: list = []


def _row(name, us, derived):
    _ROWS.append({"name": name, "us_per_call": round(float(us), 1),
                  "derived": str(derived)})
    print(f"{name},{us:.1f},{derived}")


# ---------------------------------------------------------------------------


def bench_fig1_memory() -> None:
    """Paper Fig. 1: asymptotic optimizer memory, measured exactly on a
    BERT-large-like parameter set (4096x1024 FFN + 1024x1024 attn).  One
    metadata-driven accounting (api.second_moment_bytes over shape structs)
    covers every optimizer expressed through the shared engine."""
    from repro.core import api
    from repro.core.adam import AdamConfig, adam
    from repro.core.shampoo import ShampooConfig, shampoo
    from repro.core.sketchy import RankBudget, SketchyConfig, sketchy

    def _sk(rank, **kw):
        # fixed-rank rows via the primary RankBudget spelling (the bare
        # rank= alias is deprecated)
        return sketchy(SketchyConfig(
            rank_budget=RankBudget(min_k=rank, max_k=rank),
            block_size=1024, **kw))

    params = {
        "ffn_in": jnp.zeros((1024, 4096), jnp.float32),
        "ffn_out": jnp.zeros((4096, 1024), jnp.float32),
        "attn_qkv": jnp.zeros((1024, 3072), jnp.float32),
        "attn_o": jnp.zeros((1024, 1024), jnp.float32),
    }
    t0 = time.perf_counter()
    txs = [
        ("adam", adam(AdamConfig())),
        ("shampoo", shampoo(ShampooConfig(block_size=1024))),
        ("sketchy_l256", _sk(256)),
        ("sketchy_l64", _sk(64)),
        # quantized pool storage (core/quantize.py): the same sketch state
        # held in bf16 / per-block int8 between steps
        ("sketchy_l256_bf16", _sk(256, second_moment_dtype="bf16")),
        ("sketchy_l256_int8", _sk(256, second_moment_dtype="int8")),
        # async refresh pipeline (core/api.py pending slot): transient
        # double buffer, must cost ZERO accounted second-moment bytes —
        # this row is byte-equal to sketchy_l256 and the memory gate blocks
        # on it (scripts/bench_gate.py)
        ("sketchy_l256_async", _sk(256, refresh_mode="async")),
        # rank-budget allocator (core/sketchy.RankBudget): per-block active
        # ranks migrate inside fixed-capacity stacks, so the accounted
        # footprint MUST stay byte-equal to the static sketchy_l256 row —
        # the blocking memory gate holds this invariant (the (N,) int32
        # active-rank vector is role="count", outside the Fig. 1 budget)
        ("sketchy_l256_rank_budget", sketchy(SketchyConfig(
            rank_budget=RankBudget(min_k=64, max_k=256,
                                   policy="rho_greedy"),
            block_size=1024))),
    ]
    rows = [(name, api.second_moment_bytes(jax.eval_shape(tx.init, params)))
            for name, tx in txs]
    us = (time.perf_counter() - t0) * 1e6 / len(rows)
    base = dict(rows)["shampoo"]
    for name, b in rows:
        _row(f"fig1_memory_{name}", us, f"{b}B ({base / b:.1f}x vs shampoo)")


def bench_tbl3_convex(T: int = 400) -> None:
    """Paper Tbl. 3 on synthetic logistic streams (LIBSVM offline-N/A)."""
    from repro.core import sadagrad as oco

    def stream(seed, d, T, kind):
        rng = np.random.default_rng(seed)
        if kind == "lowrank":
            W = np.linalg.qr(rng.normal(size=(d, d // 2)))[0]
            feats = rng.normal(size=(T, d // 2)) @ W.T
        else:
            feats = rng.normal(size=(T, d)) * np.exp(-np.arange(d) / 8.0)
        w = rng.normal(size=d)
        y = np.sign(feats @ w + 0.1 * rng.normal(size=T))
        return feats * y[:, None]

    # jitted ONCE outside the per-step loop — the old per-step
    # ``jax.grad(lambda ...)`` built a fresh traced function every
    # iteration, so the bench measured trace overhead, not step time.
    @jax.jit
    def loss_and_grad(x, a):
        return jax.value_and_grad(
            lambda xx: jnp.log1p(jnp.exp(-a @ xx)))(x)

    for kind in ("decay", "lowrank"):
        A = stream(0, 32, T, kind)
        results = {}
        t0 = time.perf_counter()
        for name in ("s-adagrad", "adagrad", "ogd", "ada-fd", "fd-son",
                     "rfd-son"):
            init, step, needs = oco.LEARNERS[name]
            best = np.inf
            for lr in (0.05, 0.2, 0.5):
                for delta in ((1e-4, 1e-2) if needs["delta"] else (None,)):
                    st = init(32, 10) if needs["ell"] else init(32)  # paper: l=10
                    x = jnp.zeros((32,))
                    tot = 0.0
                    for a in A:
                        aj = jnp.asarray(a, jnp.float32)
                        loss, g = loss_and_grad(x, aj)
                        tot += float(loss)
                        args = (st, x, g, lr) + ((delta,) if delta is not None
                                                 else ())
                        x, st = step(*args)
                    best = min(best, tot / T)
            results[name] = best
        us = (time.perf_counter() - t0) * 1e6 / 6
        order = sorted(results, key=results.get)
        for name, v in results.items():
            _row(f"tbl3_convex_{kind}_{name}", us,
                 f"avg_loss={v:.4f} rank={order.index(name) + 1}")


def bench_fig3_spectral_decay(steps: int = 30) -> None:
    """Paper Fig. 3: EMA Kronecker-factor spectra during a small LM train."""
    from repro.configs.registry import get_reduced
    from repro.core.factory import OptimizerConfig, make_optimizer
    from repro.data.pipeline import DataConfig, SyntheticLM
    from repro.models import model as model_lib
    from repro.train.trainer import make_train_step

    cfg = get_reduced("paper_lm_100m")
    tx = make_optimizer(OptimizerConfig(name="adam", learning_rate=5e-3,
                                        schedule="constant"))
    params = model_lib.init_params(cfg, jax.random.PRNGKey(0))
    state = tx.init(params)
    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=32,
                                  global_batch=8))
    # donate=False: grad_fn reads params before each step in the same loop
    step = jax.jit(make_train_step(cfg, tx, donate=False))
    beta2 = 0.999
    L = None
    t0 = time.perf_counter()
    grad_fn = jax.jit(jax.grad(
        lambda p, b: model_lib.loss_fn(cfg, p, b)))
    for t in range(steps):
        batch = {k: jnp.asarray(v) for k, v in data.batch(t).items()}
        g = grad_fn(params, batch)["layers"]["mlp"]["w_gate"][0]
        GG = np.asarray(g, np.float64) @ np.asarray(g, np.float64).T
        L = GG if L is None else beta2 * L + GG
        params, state, _ = step(params, state, batch)
    us = (time.perf_counter() - t0) * 1e6 / steps
    lam = np.maximum(np.linalg.eigvalsh(L)[::-1], 0)
    d = len(lam)
    intrinsic = lam.sum() / max(lam[0], 1e-12)
    k = max(d // 4, 1)
    topk = lam[:k].sum() / max(lam.sum(), 1e-12)
    _row("fig3_spectral_decay", us,
         f"dim={d} intrinsic_dim={intrinsic:.1f} top{k}_mass={topk:.3f}")


def bench_lem1_fd_error(T: int = 200) -> None:
    from repro.core.fd import fd_covariance, fd_init, fd_update

    rng = np.random.default_rng(0)
    d, ell = 64, 16
    basis = np.linalg.qr(rng.normal(size=(d, d)))[0]
    scales = np.exp(-np.arange(d) / 4.0)
    st = fd_init(d, ell)
    G = np.zeros((d, d))
    t0 = time.perf_counter()
    for _ in range(T):
        g = basis @ (scales * rng.normal(size=d))
        G += np.outer(g, g)
        st = fd_update(st, jnp.asarray(g, jnp.float32))
    us = (time.perf_counter() - t0) * 1e6 / T
    lam = np.maximum(np.linalg.eigvalsh(G)[::-1], 0)
    bound = min(lam[k:].sum() / (ell - k) for k in range(ell))
    err = np.linalg.norm(G - np.asarray(fd_covariance(st)), 2)
    _row("lem1_fd_error", us,
         f"op_err={err:.3f} rho={float(st.rho):.3f} lemma1_bound={bound:.3f}")


def bench_fig2_lm_quality(steps: int = 60) -> None:
    """Paper Fig. 2 analogue: small-LM quality per optimizer, same budget."""
    from repro.configs.registry import get_reduced
    from repro.core.factory import OptimizerConfig, make_optimizer
    from repro.data.pipeline import DataConfig, SyntheticLM
    from repro.models import model as model_lib
    from repro.train.trainer import make_train_step

    from repro.core import api
    from repro.core.sketchy import RankBudget

    cfg = get_reduced("paper_lm_100m")
    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=32,
                                  global_batch=8))
    # the rank_budget row trains at HALF the fixed-rank row's total sketch
    # rank (rho_greedy migration inside max_k=8-capacity stacks) — an
    # advisory quality row, not a gated one.  Block count probed from shape
    # structs so the explicit total tracks the reduced arch.
    params0 = model_lib.init_params(cfg, jax.random.PRNGKey(0))
    probe = make_optimizer(OptimizerConfig(
        name="sketchy", rank=8, block_size=32, update_every=2,
        total_steps=steps, schedule="constant"))
    nblocks = sum(len(g["k"]) for g in api.rank_allocation(
        jax.eval_shape(probe.init, params0))["groups"].values())
    half_budget = RankBudget(total=max(nblocks * 8 // 2, nblocks * 2),
                             min_k=2, max_k=8, policy="rho_greedy",
                             realloc_every=1)
    variants = [("sketchy", 5e-3, None), ("shampoo", 5e-3, None),
                ("adam", 5e-3, None), ("rank_budget", 5e-3, half_budget)]
    for name, lr, budget in variants:
        tx = make_optimizer(OptimizerConfig(
            name="sketchy" if budget is not None else name,
            learning_rate=lr, rank=8, rank_budget=budget, block_size=32,
            update_every=2, total_steps=steps, schedule="constant"))
        params = model_lib.init_params(cfg, jax.random.PRNGKey(0))
        state = tx.init(params)
        step = make_train_step(cfg, tx)   # jitted + donated internally
        t0 = time.perf_counter()
        losses = []
        for t in range(steps):
            batch = {k: jnp.asarray(v) for k, v in data.batch(t).items()}
            params, state, m = step(params, state, batch)
            losses.append(float(m["loss"]))
        us = (time.perf_counter() - t0) * 1e6 / steps
        _row(f"fig2_lm_quality_{name}", us,
             f"loss_first5={np.mean(losses[:5]):.3f} "
             f"loss_last5={np.mean(losses[-5:]):.3f}")


def bench_opt_step_time(iters: int = 20) -> None:
    from repro.core.factory import OptimizerConfig, make_optimizer

    rng = np.random.default_rng(0)
    params = {"w": jnp.asarray(rng.normal(size=(1024, 1024)), jnp.float32)}
    g = {"w": jnp.asarray(rng.normal(size=(1024, 1024)), jnp.float32)}
    times = {}
    for name in ("sketchy", "shampoo", "adam"):
        tx = make_optimizer(OptimizerConfig(name=name, rank=256,
                                            block_size=1024, update_every=10,
                                            schedule="constant"))
        state = tx.init(params)
        upd = jax.jit(lambda g, s, p: tx.update(g, s, p))
        u, state = upd(g, state, params)  # warmup/compile
        t0 = time.perf_counter()
        for _ in range(iters):
            u, state = upd(g, state, params)
        jax.block_until_ready(u)
        us = (time.perf_counter() - t0) * 1e6 / iters
        times[name] = us
        _row(f"opt_step_time_{name}", us, "1024x1024 block, update_every=10")
    # the paper's practical pitch: amortized (update_every=10) Sketchy step
    # cost as a multiple of Adam's on the same block — a unitless ratio, so
    # the bench gate can hold it to a tolerance that raw wall-clock rows on
    # shared runners can't keep
    _row("opt_overhead_vs_adam", times["sketchy"],
         f"ratio={times['sketchy'] / times['adam']:.2f}x sketchy vs adam "
         f"(1024x1024 block, update_every=10 amortized, "
         f"shampoo={times['shampoo'] / times['adam']:.2f}x)")


def _count_prim(jaxpr, substr: str = "") -> int:
    """Call sites of primitives whose name contains ``substr``, recursing
    into sub-jaxprs (cond branches, vmapped/scanned bodies).  With the empty
    substring this is the total equation count — the 'how many compiled
    optimizer computations' measure: per-leaf dispatch multiplies it by the
    leaf count, pooling doesn't."""
    def subs(v):
        if hasattr(v, "eqns"):
            yield v
        elif hasattr(v, "jaxpr"):
            yield from subs(v.jaxpr)
        elif isinstance(v, (list, tuple)):
            for x in v:
                yield from subs(x)

    n = sum(substr in eqn.primitive.name for eqn in jaxpr.eqns)
    for eqn in jaxpr.eqns:
        for v in eqn.params.values():
            for sj in subs(v):
                n += _count_prim(sj, substr)
    return n


def bench_opt_step_time_multileaf(n_leaves: int = 128, iters: int = 10) -> None:
    """Pooled-engine dispatch over a many-leaf tree (the transformer case:
    hundreds of same-shaped parameters).  Derived column reports the pooled
    jaxpr equation count next to the per-leaf baseline (= n_leaves x the
    single-leaf engine's count — what the pre-pool engine compiled)."""
    from repro.core.sketchy import SketchyConfig, sketchy

    rng = np.random.default_rng(0)
    cfg = SketchyConfig(rank=4, block_size=16, update_every=10)
    mk = lambda: jnp.asarray(rng.normal(size=(16, 16)), jnp.float32)
    params = {f"w{i:03d}": mk() for i in range(n_leaves)}
    g = {k: mk() for k in params}
    tx = sketchy(cfg)
    state = tx.init(params)

    pooled_jaxpr = jax.make_jaxpr(lambda gg, s: tx.update(gg, s))(g, state).jaxpr
    pooled_eqns = _count_prim(pooled_jaxpr)
    pooled_eigh = _count_prim(pooled_jaxpr, "eig")
    p1, g1 = {"w": mk()}, {"w": mk()}
    tx1 = sketchy(cfg)
    s1 = tx1.init(p1)
    single_jaxpr = jax.make_jaxpr(lambda gg, s: tx1.update(gg, s))(g1, s1).jaxpr
    per_leaf_eqns = n_leaves * _count_prim(single_jaxpr)
    per_leaf_eigh = n_leaves * _count_prim(single_jaxpr, "eig")

    upd = jax.jit(lambda gg, s: tx.update(gg, s))
    u, st = upd(g, state)   # compile
    jax.block_until_ready(u)
    t0 = time.perf_counter()
    for _ in range(iters):
        u, st = upd(g, st)
    jax.block_until_ready(u)
    us = (time.perf_counter() - t0) * 1e6 / iters
    _row("opt_step_time_multileaf", us,
         f"leaves={n_leaves} pooled_eqns={pooled_eqns} "
         f"per_leaf_eqns={per_leaf_eqns} "
         f"reduction={per_leaf_eqns / pooled_eqns:.1f}x "
         f"eigh_sites={pooled_eigh}_vs_{per_leaf_eigh}")


def bench_opt_step_time_kernels(n_leaves: int = 32, iters: int = 5) -> None:
    """Kernel-backend comparison on the pooled multi-leaf config: the same
    packed (N, bs_m, bs_n) dispatch, once through the pure-XLA batched refs
    and once through the grid-over-N batched Pallas kernels (Mosaic on TPU;
    interpret mode on CPU, where the row is a correctness/overhead probe, not
    a speed claim).  update_every=1 so every step pays the batched gram +
    fused low-rank apply."""
    from repro.core import pool
    from repro.core.sketchy import SketchyConfig, sketchy

    rng = np.random.default_rng(0)
    mk = lambda: jnp.asarray(rng.normal(size=(32, 32)), jnp.float32)
    params = {f"w{i:03d}": mk() for i in range(n_leaves)}
    g = {k: mk() for k in params}
    index = pool.build_index(((32, 32),) * n_leaves, 32)
    for backend in ("xla", "pallas"):
        tx = sketchy(SketchyConfig(rank=8, block_size=32, update_every=1,
                                   kernel_backend=backend))
        state = tx.init(params)
        upd = jax.jit(lambda gg, s: tx.update(gg, s))
        u, st = upd(g, state)   # warmup/compile
        jax.block_until_ready(u)
        t0 = time.perf_counter()
        for _ in range(iters):
            u, st = upd(g, st)
        jax.block_until_ready(u)
        us = (time.perf_counter() - t0) * 1e6 / iters
        _row(f"opt_step_time_kernels_{backend}", us,
             f"leaves={n_leaves} pooled_blocks={index.total_blocks} "
             f"rank=8 block=32 update_every=1")


def bench_opt_step_time_autotuned(n_leaves: int = 32, iters: int = 5) -> None:
    """Shape-aware autotuner payoff (kernels/autotune.py) on the pooled
    pallas step of ``bench_opt_step_time_kernels``: the same engine measured
    with tuning OFF (every kernel pinned to the bn_stack=1 defaults) and
    then with a freshly force-tuned cache (``tune_into_cache`` on the pool
    shapes this config traces) picked up by a fresh tx/jit.  Configs resolve
    at trace time, so the tuned step pays zero per-step lookup cost; the
    derived column carries the untuned baseline and the speedup."""
    from repro.core.sketchy import SketchyConfig, sketchy
    from repro.kernels import autotune

    rng = np.random.default_rng(0)
    mk = lambda: jnp.asarray(rng.normal(size=(32, 32)), jnp.float32)
    params = {f"w{i:03d}": mk() for i in range(n_leaves)}
    g = {k: mk() for k in params}

    def measure() -> float:
        tx = sketchy(SketchyConfig(rank=8, block_size=32, update_every=1,
                                   kernel_backend="pallas"))
        state = tx.init(params)
        upd = jax.jit(lambda gg, s: tx.update(gg, s))
        u, st = upd(g, state)   # warmup/compile
        jax.block_until_ready(u)
        t0 = time.perf_counter()
        for _ in range(iters):
            u, st = upd(g, st)
        jax.block_until_ready(u)
        return (time.perf_counter() - t0) * 1e6 / iters

    # the pool shapes this config traces: gram over [U*sqrt(beta2*s) | G]
    # -> (N, d, ell + bs_n); fused low-rank apply -> (N, d, ell, bs_n)
    specs = [("batched_gram", (n_leaves, 32, 40), "float32"),
             ("batched_lowrank_apply", (n_leaves, 32, 8, 32), "float32")]
    cur = autotune._resolve()
    prev_path, prev_mode = cur["path"], cur["mode"]
    import tempfile
    try:
        autotune.reload(mode="off")
        untuned_us = measure()
        with tempfile.TemporaryDirectory() as tmp:
            autotune.reload(path=os.path.join(tmp, "cache.json"),
                            mode="auto")
            t0 = time.perf_counter()
            autotune.tune_into_cache(specs)
            tune_ms = (time.perf_counter() - t0) * 1e3
            tuned_us = measure()
    finally:
        autotune.reload(path=prev_path, mode=prev_mode)
    _row("opt_step_time_autotuned", tuned_us,
         f"speedup={untuned_us / tuned_us:.2f}x vs untuned bn_stack=1 "
         f"({untuned_us:.1f}us), one-off tune_cost={tune_ms:.0f}ms, "
         f"leaves={n_leaves} rank=8 block=32 pallas")


def bench_opt_step_time_async_refresh(n_leaves: int = 64,
                                      iters: int = 10) -> None:
    """Refresh-step critical path, inline vs async (ISSUE 7 tentpole row).

    What overlapped execution hides is the time from gradient arrival to
    the update DIRECTION being ready — the refresh itself continues in the
    shadow of the next forward/backward.  On the single-stream CPU backend
    that latency is measured by the direction-only program
    ``jit(lambda g, s: tx.update(g, s)[0])``: XLA dead-code-eliminates the
    state outputs, and under async the refresh (eigh + shrink) is dead code
    for the direction — the compiled program has ZERO eigh call sites —
    while inline's direction data-depends on the refresh it just computed.
    Both engines are pinned to a refresh-boundary count (the worst-case
    step; off-boundary steps are identical by construction).  The derived
    column carries the eigh site counts and the full donated steady-state
    step time (refresh amortized over ``update_every``) for both modes.
    """
    from repro.core.sketchy import SketchyConfig, sketchy

    rng = np.random.default_rng(0)
    mk = lambda: jnp.asarray(rng.normal(size=(64, 64)), jnp.float32)
    params = {f"w{i:03d}": mk() for i in range(n_leaves)}
    g = {k: mk() for k in params}
    update_every = 10
    out = {}
    for mode in ("inline", "async"):
        tx = sketchy(SketchyConfig(rank=16, block_size=64,
                                   update_every=update_every,
                                   refresh_mode=mode))
        # full steady-state step (donated opt_state, refresh amortized)
        full = jax.jit(lambda gg, s: tx.update(gg, s), donate_argnums=(1,))
        st = tx.init(params)
        u, st = full(g, st)     # compile + leave count=1
        jax.block_until_ready(u)
        t0 = time.perf_counter()
        for _ in range(iters * update_every):
            u, st = full(g, st)
        jax.block_until_ready(u)
        full_us = (time.perf_counter() - t0) * 1e6 / (iters * update_every)

        # direction-only program at a refresh-boundary count: advance a
        # fresh state to count == update_every, then measure with the state
        # held fixed (every call sees the refresh-due branch)
        st = tx.init(params)
        for _ in range(update_every):
            _, st = jax.jit(lambda gg, s: tx.update(gg, s))(g, st)
        dir_fn = jax.jit(lambda gg, s: tx.update(gg, s)[0])
        # count eigh in the LOWERED program: lowering dead-code-eliminates
        # the discarded state outputs (the traced jaxpr itself keeps them)
        eigh_sites = dir_fn.lower(g, st).as_text().count("eigh")
        u = dir_fn(g, st)       # compile
        jax.block_until_ready(u)
        t0 = time.perf_counter()
        for _ in range(iters):
            u = dir_fn(g, st)
        jax.block_until_ready(u)
        us = (time.perf_counter() - t0) * 1e6 / iters
        out[mode] = (us, full_us, eigh_sites)

    i_us, i_full, i_eigh = out["inline"]
    a_us, a_full, a_eigh = out["async"]
    assert a_eigh == 0, f"async direction path still compiles eigh ({a_eigh})"
    _row("opt_step_time_inline_refresh", i_us,
         f"direction critical path at refresh boundary, eigh_sites={i_eigh} "
         f"full_step={i_full:.1f}us leaves={n_leaves} rank=16 "
         f"update_every={update_every}")
    _row("opt_step_time_async_refresh", a_us,
         f"direction critical path at refresh boundary, eigh_sites={a_eigh} "
         f"full_step={a_full:.1f}us overlap_win={i_us / a_us:.1f}x "
         f"vs inline (donated double buffer)")


def bench_lm_step_time_refresh_schedule(steps: int = 24) -> None:
    """End-to-end step time on the reduced paper_lm_100m, synchronized vs
    staggered refresh phasing (ISSUE 7 satellite): same amortized eigh
    budget, but staggered flattens the every-``update_every``-steps spike
    into ~N/k blocks per step.  Derived reports mean and max step wall time
    per schedule — the max is the spike the staggered schedule removes."""
    from repro.configs.registry import get_reduced
    from repro.core.factory import OptimizerConfig, make_optimizer
    from repro.data.pipeline import DataConfig, SyntheticLM
    from repro.models import model as model_lib
    from repro.train.trainer import make_train_step

    cfg = get_reduced("paper_lm_100m")
    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=32,
                                  global_batch=8))
    out = {}
    for sched in ("synchronized", "staggered"):
        tx = make_optimizer(OptimizerConfig(
            name="sketchy", learning_rate=5e-3, rank=8, block_size=32,
            update_every=4, total_steps=steps, schedule="constant",
            refresh_schedule=sched))
        params = model_lib.init_params(cfg, jax.random.PRNGKey(0))
        state = tx.init(params)
        step = make_train_step(cfg, tx)   # jitted + donated internally
        times = []
        for t in range(steps):
            batch = {k: jnp.asarray(v) for k, v in data.batch(t).items()}
            t0 = time.perf_counter()
            params, state, m = step(params, state, batch)
            jax.block_until_ready(m["loss"])
            times.append(time.perf_counter() - t0)
        times = np.array(times[4:]) * 1e6   # drop compile/warmup steps
        out[sched] = (times.mean(), times.max())
    s_mean, s_max = out["synchronized"]
    g_mean, g_max = out["staggered"]
    _row("lm_step_time_refresh_schedule", g_mean,
         f"staggered mean={g_mean:.0f}us max={g_max:.0f}us vs synchronized "
         f"mean={s_mean:.0f}us max={s_max:.0f}us (reduced paper_lm_100m, "
         f"update_every=4)")


def bench_bytes_on_wire_per_refresh(P: int = 4) -> None:
    """Distributed-FD wire cost (ISSUE 6 acceptance row): bytes each device
    ships per refresh through the log-depth butterfly
    (``distributed/sketch_merge.pack_wire``: deflated column dropped, int8
    values + one fp32 scale + fp32 rho per block, both sketch sides) vs the
    dense alternative — recursive-doubling all-reduce of both d x d fp32
    covariance factors at the same log2(P) depth.  Measured on real packed
    structures, not a formula."""
    from repro.core.fd import fd_init, fd_update_batched, FDState
    from repro.distributed import sketch_merge

    d, ell, N = 256, 64, 1
    rng = np.random.default_rng(0)
    st0 = fd_init(d, ell)
    st = FDState(st0.eigvecs[None], st0.eigvals[None], st0.rho[None])
    st = fd_update_batched(
        st, jnp.asarray(rng.normal(size=(N, d, 8)), jnp.float32))
    t0 = time.perf_counter()
    wire = sketch_merge.pack_wire(st, "int8")
    per_round = sketch_merge.wire_bytes(wire)
    us = (time.perf_counter() - t0) * 1e6
    rounds = (P - 1).bit_length()      # log2(P) butterfly rounds
    sketch_bytes = rounds * 2 * per_round          # left + right sketches
    dense_bytes = rounds * 2 * d * d * 4           # both fp32 covariances
    _row("bytes_on_wire_per_refresh", us,
         f"{sketch_bytes}B on wire (dense_fp32={dense_bytes}B, "
         f"{dense_bytes / sketch_bytes:.1f}x less, P={P} d={d} ell={ell} "
         f"int8 wire, {per_round}B/round/side)")


def bench_opt_step_time_sharded_stats(iters: int = 10) -> None:
    """Engine step wall-time with stats_reduction="sharded" on an 8-device
    host-platform CPU mesh next to the replicated step on the same shapes.
    Runs in a subprocess: this process must keep seeing one device (the
    dry-run contract), and XLA only fakes the device count at startup.  The
    child is pinned to ``JAX_PLATFORMS=cpu`` — the row measures the host
    platform, and a parent that holds a chip keeps it."""
    code = f"""
import time
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, PartitionSpec as P
from repro.core import sketchy as sk
from repro.distributed import reduce as dreduce

rng = np.random.default_rng(0)
params = {{f"w{{i}}": jnp.asarray(rng.normal(size=(64, 64)), jnp.float32)
          for i in range(4)}}
grads = {{k: jnp.asarray(rng.normal(size=(8,) + v.shape), jnp.float32)
         for k, v in params.items()}}
gmean = jax.tree.map(lambda g: g.mean(0), grads)
mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))

def bench(tx, fn, *args):
    state = tx.init(params)
    step = jax.jit(fn)
    out = step(*args, state)            # warmup/compile
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range({iters}):
        out = step(*args, out[1])
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) * 1e6 / {iters}

cfg = dict(rank=16, block_size=64, update_every=2)
tx_r = sk.sketchy(sk.SketchyConfig(**cfg))
us_r = bench(tx_r, lambda g, s: tx_r.update(g, s, params), gmean)

tx_s = sk.sketchy(sk.SketchyConfig(stats_reduction="sharded", **cfg))
def sharded(g, s):
    def body(gl, s):
        gl = jax.tree.map(lambda x: x[0], gl)
        gm = dreduce.pmean(gl, "data")
        with dreduce.local_gradients(gl):
            return tx_s.update(gm, s, params)
    return jax.shard_map(body, mesh=mesh, in_specs=(P("data"), P()),
                     out_specs=(P(), P()), check_vma=False)(g, s)
us_s = bench(tx_s, sharded, grads)
print(f"SHARDED_US={{us_s:.1f}} REPL_US={{us_r:.1f}}")
"""
    env = {**os.environ,
           "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
           "PYTHONPATH": os.pathsep.join(
               [os.path.join(os.path.dirname(os.path.dirname(
                   os.path.abspath(__file__))), "src")] +
               ([os.environ["PYTHONPATH"]]
                if os.environ.get("PYTHONPATH") else []))}
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=900)
    if r.returncode != 0:
        raise RuntimeError("opt_step_time_sharded_stats child failed:\n"
                           + r.stderr[-4000:])
    kv = dict(tok.split("=") for tok in r.stdout.split() if "=" in tok)
    us_s, us_r = float(kv["SHARDED_US"]), float(kv["REPL_US"])
    _row("opt_step_time_sharded_stats", us_s,
         f"8-device butterfly merge, replicated_same_shapes={us_r:.1f}us "
         f"4x(64x64) leaves rank=16 update_every=2")


def bench_serve_latency(ticks: int = 16) -> None:
    """Serve rows (ISSUE 10): the continuous-batching engine driven by the
    deterministic load generator, one row per traffic shape.  ``us_per_call``
    is mean wall time per engine step; the derived column carries p50/p99
    inter-token latency read off the request handles' per-token timestamps —
    the step shape's post-jump p99 is the number the slot-reuse redesign is
    about (queued requests claim freed lanes instead of waiting for the
    whole static batch)."""
    from repro.configs.registry import get_reduced
    from repro.models import model as model_lib
    from repro.serve import (Engine, LoadGenerator, ServeConfig,
                             TrafficConfig)

    cfg = get_reduced("paper_lm_100m")
    params = model_lib.init_params(cfg, jax.random.PRNGKey(0))
    for shape in ("constant", "step"):
        gen = LoadGenerator(TrafficConfig(
            shape=shape, rate=1.0, ticks=ticks, step_at=ticks // 2,
            step_mult=3.0, prompt_len=6, new_tokens=6), cfg.vocab_size)
        eng = Engine(cfg, params, ServeConfig(batch=4, max_seq=32))
        eng.step()   # pay the decode compile outside the timed run
        handles = []
        t0 = time.perf_counter()
        for tick in range(ticks):
            for req in gen.arrivals(tick):
                handles.append(eng.submit(req))
            eng.step()
        done = eng.drain()
        wall = time.perf_counter() - t0
        steps = eng.step_count - 1
        lat = np.array([t1 - ta for h in handles for ta, t1 in
                        zip(h.token_times, h.token_times[1:])])
        p50 = np.percentile(lat, 50) * 1e3 if lat.size else 0.0
        p99 = np.percentile(lat, 99) * 1e3 if lat.size else 0.0
        _row(f"serve_latency_{shape}_traffic", wall * 1e6 / max(steps, 1),
             f"p50={p50:.2f}ms p99={p99:.2f}ms tokens="
             f"{sum(len(h.tokens) for h in handles)} requests={len(handles)} "
             f"steps={steps} batch=4")


def bench_monitor_overhead_per_window(d: int = 4096, windows: int = 20) -> None:
    """Serve-time telemetry cost (ISSUE 10): one full monitor window —
    ``window`` jitted rank-ell fd_updates on a (d,) gradient plus the
    boundary signal reads (leading eig, pressure, drift angle, policy) —
    on the flattened-head gradient size the adaptation loop actually
    monitors."""
    from repro.serve import GradientMonitor, MonitorConfig

    cfg = MonitorConfig(ell=8, window=8, top_k=4)
    mon = GradientMonitor(d, cfg)
    rng = np.random.default_rng(0)
    grads = [rng.standard_normal(d).astype(np.float32)
             for _ in range(cfg.window)]
    for g in grads:     # compile + first boundary
        mon.observe(g)
    t0 = time.perf_counter()
    for _ in range(windows):
        for g in grads:
            mon.observe(g)
    us = (time.perf_counter() - t0) * 1e6 / windows
    per_grad = us / cfg.window
    _row("monitor_overhead_per_window", us,
         f"per_grad={per_grad:.1f}us d={d} ell={cfg.ell} "
         f"window={cfg.window} (fd_update stream + boundary signals)")


def main(argv=None) -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--json", metavar="PATH", default=None,
                   help="also write the rows as a JSON list (machine-"
                        "readable trajectory output, e.g. BENCH_opt.json)")
    args = p.parse_args(argv)

    _ROWS.clear()   # repeat-safe: direct bench_* calls may have accumulated
    print("name,us_per_call,derived")
    bench_fig1_memory()
    bench_lem1_fd_error()
    bench_tbl3_convex()
    bench_fig3_spectral_decay()
    bench_fig2_lm_quality()
    bench_opt_step_time()
    bench_opt_step_time_multileaf()
    bench_opt_step_time_kernels()
    bench_opt_step_time_autotuned()
    bench_opt_step_time_async_refresh()
    bench_lm_step_time_refresh_schedule()
    bench_bytes_on_wire_per_refresh()
    bench_opt_step_time_sharded_stats()
    bench_serve_latency()
    bench_monitor_overhead_per_window()

    if args.json:
        with open(args.json, "w") as f:
            json.dump(_ROWS, f, indent=1)
        print(f"wrote {len(_ROWS)} rows to {args.json}")


if __name__ == "__main__":
    main()
