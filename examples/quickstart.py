"""Quickstart: Sketchy (S-Shampoo) through the unified Preconditioner API.

    PYTHONPATH=src python examples/quickstart.py

What this demonstrates:
  * ``make_optimizer`` builds a labelled ``named_chain`` (clip -> precond ->
    momentum -> lr) wrapped in ``inject_hyperparams`` — the learning rate
    lives in optimizer state.
  * Every state leaf carries a ``StateMeta`` annotation; memory accounting
    and introspection are one metadata traversal, no optimizer-specific
    types anywhere.
  * Hyperparameters can be mutated mid-run (``api.set_hyperparams``) without
    rebuilding or re-jitting the chain — the serve/elastic re-mesh path.

Memory knobs
------------
Second-moment memory (the paper's Fig. 1 quantity) stacks two independent
knobs on top of Adam's O(d^2-per-layer) baseline:

  * ``rank`` — the FD sketch size ell: O((m+n) * ell) per block instead of
    Shampoo's O(m^2 + n^2).
  * ``second_moment_dtype`` — how the second-moment state is *stored*
    between steps (core/quantize.py): ``"fp32"`` (default, bitwise parity),
    ``"bf16"`` (2x smaller), or ``"int8"`` (quantized matrix factors with
    per-block fp32 scales, plus whole-leaf-scaled int8 diag-fallback
    accumulators for vector/scalar params, ~4x smaller).  Compute always
    dequantizes to f32.

Measured via ``api.second_moment_bytes`` on this demo's reduced config
(rank 8, block 32; the per-block fp32 eigenvalue ladders and scales keep the
small-model ratio under 4x — it steepens at paper scale where the matrix
factors dominate):

    OptimizerConfig(name="sketchy", rank=8, ...)                     301.5kB
    OptimizerConfig(..., second_moment_dtype="int8")                  84.2kB  (3.6x)

``main()`` below prints the exact before/after int8 numbers for the current
config (no state materialization — ``jax.eval_shape`` over ``tx.init``).

Rank budget knobs
-----------------
``rank`` pins every block to the same sketch size.  The primary spelling is
``OptimizerConfig(rank_budget=RankBudget(...))`` (core/sketchy.py): one
fixed TOTAL sketch rank shared by all pooled blocks, with a per-block
allocation policy:

  * ``RankBudget(min_k=r, max_k=r, policy="static")`` — what ``rank=r``
    normalizes to; every block at capacity forever, bitwise-identical to
    the pre-budget engine.
  * ``RankBudget(total=K, min_k=..., max_k=..., policy="rho_greedy",
    realloc_every=j)`` — every ``j * update_every`` steps the total K is
    re-poured across blocks by descending escaped-mass pressure
    ``rho / (trace + rho)``: blocks whose sketch drops the most mass grow
    (masked zero columns unmask), over-provisioned blocks shrink by exact
    Robust-FD deflation (dropped eigenvalue mass folds into ``rho``).

Memory does NOT follow the active ranks: stacks are allocated at ``max_k``
capacity and ``second_moment_bytes`` is byte-identical to a static run at
``rank=max_k`` (the ``fig1_memory_sketchy_l256_rank_budget`` row is held
byte-equal to ``sketchy_l256`` by the blocking memory gate).  What moves is
where the *effective* rank sits — measured live via
``api.rank_allocation(opt_state)``, printed below: per pool group the
active ranks ``k``, per-block escaped mass ``rho``, and ``budget_share =
k / K``.  The deprecated ``SketchyConfig(rank=...)`` spelling still works
(DeprecationWarning; see the CHANGES.md migration table), and pre-budget
fixed-rank checkpoints restore into budgeted runs via a migration shim
(train/checkpoint.py).

Distributed sketching
---------------------
Under data parallelism the default (``stats_reduction="replicated"``)
all-reduces dense gradients and has every replica maintain an identical
sketch.  ``OptimizerConfig(stats_reduction="sharded")`` (or
``launch/train.py --stats-reduction sharded``) instead has each shard run
the FD update on its *local* gradients and, at refresh time, merge the
pooled sketch stacks across the ``data`` mesh axis with a log-depth
butterfly of ``fd_merge`` rounds (src/repro/distributed/): each round ships
``~(ell-1) * d`` int8 per block (sqrt(s)-weighted factors on the shared
int8 wire, escaped mass ``rho`` summed alongside) instead of ``d^2`` fp32 —
16x fewer bytes on the wire at d=256, ell=64 (``bytes_on_wire_per_refresh``
benchmark row).  The update direction stays deterministic: with a 1-sized
(or unbound) data axis the sharded path is bitwise-identical to replicated,
and the merged sketch obeys the same FD error bound as a single-stream
sketch of all shards' gradients (tests/test_distributed.py).

Kernel tuning knobs
-------------------
The pooled hot path (batched gram + fused low-rank apply over packed
``(N, bs_m, bs_n)`` stacks) runs through the kernel registry
(``kernels/registry.py``); three knobs control how those kernels execute:

  * ``kernel_backend`` — ``"auto"`` (default: Pallas on TPU, XLA batched
    refs elsewhere; ``REPRO_KERNEL_BACKEND`` env overrides), ``"pallas"``,
    or ``"xla"``.
  * Tile configs come from the shape-aware autotuner
    (``kernels/autotune.py``): each Pallas entry point looks up a measured
    ``(bn_stack, bk, bd, bn)`` winner for its exact (platform, kernel,
    padded pool shape, storage dtype) at *trace* time — tuned steps pay
    zero per-step lookup cost.  ``REPRO_TUNE_MODE`` picks the policy:
    ``"auto"`` (default: use the committed ``kernels/tune_cache.json``
    fixture, fall back to safe defaults on a miss), ``"off"`` (always
    defaults — the pinned-parity baseline), or ``"force"`` (measure and
    persist on every miss).  ``REPRO_TUNE_CACHE`` points at an alternative
    cache file; ``python -m repro.kernels.autotune tune|show|validate``
    maintains one from the command line, and the ``opt_step_time_autotuned``
    benchmark row tracks the payoff vs the untuned defaults.
  * ``quantized_epilogue`` — with ``second_moment_dtype="int8"``, ``"auto"``
    (default) fuses dequantize/requantize into the Pallas kernels whenever
    the pallas backend is resolved and stats are replicated: the int8 pool
    containers flow straight into the batched FD methods (scale-folded
    gram/apply, in-kernel requantized eigenvector stacks), so the f32
    factor stack is never materialized at the pool boundary.  ``"off"``
    always dequantizes at the boundary (the PR-4 baseline numerics);
    ``"on"`` forces the fused math on any backend (the XLA mirror of the
    same scale-folded computation — useful for A/B-ing numerics).  Sketchy
    only; shampoo's root solve keeps f32 factors.

Step-time knobs
---------------
Three independent knobs trade when the eigh-heavy refresh work happens for
wall-clock step time; none of them changes the statistics stream:

  * ``refresh_schedule`` — *which blocks* refresh each step.
    ``"synchronized"`` (default) refreshes every pooled block every
    ``update_every`` steps: one big eigh spike, cheapest mean step time.
    ``"staggered"`` spreads ~N/update_every blocks across every step: same
    amortized cost, flat step-time profile.  Measured end-to-end on the
    reduced paper_lm_100m (``lm_step_time_refresh_schedule`` benchmark
    row): staggered consistently cuts the worst-step spike (~1.7-2.3x
    across runs) while mean step time stays within CPU run-to-run noise
    (synchronized won 2 of 3 runs by ~10-15%), so synchronized stays the
    default — pick staggered when stragglers/latency spikes hurt more
    than throughput (e.g. a synchronous data-parallel pod where the
    slowest step gates everyone).
  * ``refresh_mode`` — *when* the refresh lands.  ``"inline"`` (default)
    computes it on the step's critical path.  ``"async"`` launches it at
    step t from the just-updated stats into a transient double-buffered
    pending slot and commits it at step t+1, so the eigh (and the
    distributed butterfly merge) overlap with the next step's
    forward/backward; the update direction is one refresh stale, but the
    committed statistics are bitwise step-shifted-equal to inline
    (tests/test_async_refresh.py).  The direction's compiled critical path
    drops every eigh call site (``opt_step_time_async_refresh`` row:
    ~15x shorter at refresh boundaries on the multileaf CPU bench).
  * The engine's phases always carry ``precond/...`` name scopes
    (update_stats/refresh/precondition/commit, and the butterfly merge
    rounds), HLO metadata that costs nothing on the device, for reading
    the overlap off a profiler trace.

``make_train_step`` jits with params and optimizer state DONATED
(``donate_argnums=(0, 1)``): the step reuses the input buffers for its
outputs, so even the async pending slot adds no steady-state copies beyond
its double buffer.  Keep references out of donated trees (pass
``donate=False`` if you must reuse an old state).

Serving + online adaptation
---------------------------
The serving surface (``src/repro/serve/``) is a session-style
continuous-batching engine plus the paper's FD machinery re-used as
serve-time telemetry and an online learner:

  * ``Engine.submit(Request) -> handle`` / ``Engine.step()`` /
    ``Engine.drain()`` — each batch lane decodes at its own sequence
    position; a short request frees its lane the step it finishes and the
    next queued request prefills into the wiped slot.  Per-request
    ``max_new_tokens`` and ``temperature`` are honored per lane.  (The old
    one-shot ``Engine.generate`` survives as a deprecated wrapper — see
    the CHANGES.md migration table.)
  * ``GradientMonitor`` (serve/monitor.py) — a per-window FD sketch of the
    live feedback gradients; at each window boundary it reads the leading
    eigenvalue, the escaped-mass pressure ``rho/(trace+rho)``, and the
    drift angle vs the previous window's sketch subspace, then decides
    steady / adapt / pause (pause = suspected bad traffic).
  * ``OnlineAdapter`` (serve/adapt.py) — the S-AdaGrad OCO step over the
    flattened head, built through ``inject_hyperparams`` so
    ``adapter.set_hyperparams(learning_rate=..., beta2=...)`` mutates the
    live values with no retrace.

Driven end-to-end by ``python -m repro.launch.serve --traffic ... --adapt
... --monitor ...`` (deterministic constant/step load shapes from
serve/loadgen.py; the ``serve_latency_*`` benchmark rows come from the
same loop).  ``main()`` below runs a small submit/step/drain session and
one monitored adaptation window.
"""
import collections

import jax
import jax.numpy as jnp

from repro.configs.registry import get_reduced
from repro.core import api
from repro.core.factory import OptimizerConfig, make_optimizer
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.models import model as model_lib
from repro.train.trainer import make_train_step


def state_summary(opt_state) -> str:
    """Bytes per StateMeta role — works for any optimizer on the engine."""
    by_role = collections.Counter()
    for meta, leaf in api.leaves_with_meta(opt_state):
        role = meta.role if meta is not None else "untagged"
        by_role[role] += int(leaf.size) * jnp.dtype(leaf.dtype).itemsize
    return "  ".join(f"{r}={b/1e3:.1f}kB" for r, b in sorted(by_role.items()))


def main():
    cfg = get_reduced("paper_lm_100m")
    params = model_lib.init_params(cfg, jax.random.PRNGKey(0))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"model: {cfg.name} (reduced) — {n_params / 1e6:.2f}M params")

    # The paper's optimizer: FD-sketched Shampoo, rank 256 (rank 8 here for
    # the tiny demo). Second-moment memory is O((m+n)*rank) per block.
    # schedule="constant" keeps the lr a stored state value => mutable below.
    tx = make_optimizer(OptimizerConfig(
        name="sketchy", learning_rate=5e-3, rank=8, block_size=32,
        update_every=2, total_steps=50, schedule="constant"))

    opt_state = tx.init(params)
    print("optimizer state by role:", state_summary(opt_state))
    fp32_bytes = api.second_moment_bytes(opt_state)
    print(f"second-moment bytes (paper Fig. 1 quantity): {fp32_bytes}")

    # memory knob: the same state stored int8 between steps (compute stays
    # f32; eval_shape => no arrays materialized for the comparison)
    tx_int8 = make_optimizer(OptimizerConfig(
        name="sketchy", learning_rate=5e-3, rank=8, block_size=32,
        update_every=2, total_steps=50, schedule="constant",
        second_moment_dtype="int8"))
    int8_bytes = api.second_moment_bytes(jax.eval_shape(tx_int8.init, params))
    print(f"second-moment bytes with second_moment_dtype='int8': "
          f"{int8_bytes} ({fp32_bytes / int8_bytes:.1f}x smaller)")

    # rank-budget introspection: per-pool active sketch ranks (for this
    # static config every block sits at the ladder capacity; under
    # rank_budget=RankBudget(policy="rho_greedy") the same call shows the
    # budget migrating toward high-rho blocks while the bytes above stay
    # fixed at max_k capacity)
    alloc = api.rank_allocation(opt_state)
    print(f"rank allocation (total K = {alloc['total']}):")
    for key, grp in alloc["groups"].items():
        ks = grp["k"]
        share = 100.0 * float(grp["budget_share"].sum())
        print(f"  pool {key}: {len(ks)} blocks, k={ks.min()}..{ks.max()}, "
              f"{share:.0f}% of budget, mean rho {grp['rho'].mean():.2e}")

    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=32,
                                  global_batch=8))
    step = make_train_step(cfg, tx)  # jitted + donated internally

    for t in range(50):
        if t == 30:  # runtime schedule change: decay lr 5x, no chain rebuild
            opt_state = api.set_hyperparams(opt_state, learning_rate=1e-3)
            print(f"step {t:3d}  lr ->",
                  float(api.get_hyperparams(opt_state)["learning_rate"]))
        batch = {k: jnp.asarray(v) for k, v in data.batch(t).items()}
        params, opt_state, m = step(params, opt_state, batch)
        if t % 10 == 0 or t == 49:
            print(f"step {t:3d}  loss {float(m['loss']):.4f}")

    # --- serving + online adaptation (serve/) ------------------------------
    import numpy as np

    from repro.serve import (AdaptConfig, Engine, GradientMonitor,
                             MonitorConfig, OnlineAdapter, Request,
                             ServeConfig)

    engine = Engine(cfg, params, ServeConfig(batch=2, max_seq=32))
    rng = np.random.default_rng(0)
    handles = [engine.submit(Request(
        prompt=rng.integers(0, cfg.vocab_size, size=(6,), dtype=np.int32),
        max_new_tokens=n)) for n in (4, 7, 5)]   # 3 requests, 2 lanes
    engine.drain()                               # slot reuse serves all 3
    for h in handles:
        print(f"served request {h.id}: {len(h.tokens)} tokens "
              f"(lane claimed at step {h.start_step})")

    # feedback batches -> FD monitor -> S-AdaGrad head adaptation
    adapter = OnlineAdapter(cfg, params, AdaptConfig(lr=0.1, beta2=0.95))
    monitor = GradientMonitor(adapter.d, MonitorConfig(window=3, top_k=3))
    for t in range(3):
        fb = {k: jnp.asarray(v) for k, v in data.batch(100 + t).items()}
        loss, g = adapter.grad(params, fb)
        reading = monitor.observe(g)             # closes the window at t=2
    print("monitor:", reading)
    params, loss = adapter.step(params, fb)
    engine.params = params                       # serve the adapted head
    adapter.set_hyperparams(learning_rate=0.02)  # runtime knob, no retrace
    print(f"adapted head, feedback loss {float(loss):.4f}, "
          f"lr -> {adapter.hyperparams['learning_rate']:.3f}")


if __name__ == "__main__":
    main()
