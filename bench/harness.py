"""Driving the program's training step, and the check against the plain
reference.

The program is built as ``launch/train.train`` composes it:
``launch/train.configs`` -> ``core/factory.make_optimizer`` ->
``models/model.init_params`` (one jitted call on the device, from the seed)
-> ``train/trainer.make_train_step`` (parameters and optimizer state
donated).  ``Program.step`` mirrors one iteration of ``train``'s loop: the
batch generated on the host and put on the device, the step dispatched, the
loss fetched (which waits for the device).

The first ``CHECK_STEPS`` steps are the ones the check follows: it keeps the
loss of each, the first gradient as the optimizer holds it after step 0, the
first direction (where the optimizer exposes it), and each leaf's change
over the three steps.  ``reference_readings`` replays them with the plain
reference from the same seed, after the program's state is gone.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import os
import statistics
import sys

import numpy as np

from bench import spec as spec_lib

CHECK_STEPS = 3
# leaves whose reference gradient is below this share of the median leaf's
# move by round-off alone and are left out of the parameter change
ZERO_GRAD_SHARE = 1e-3
FAULTS = ("state_unchanged", "half_batch", "token_altered")


def program_path():
    src = os.path.join(spec_lib.ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def setup_jax(root: str = spec_lib.ROOT, cache: bool = True):
    """Compilation cache at a fixed path inside the checkout, every program
    cached and none evicted, so that only a checkout's first run compiles.
    A size limit from the environment (``JAX_COMPILATION_CACHE_MAX_SIZE``)
    would evict the train step, whose entry is larger than most limits,
    and every run would compile it again."""
    import jax
    if cache:
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(root, ".bench_cache", "jax"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_compilation_cache_max_size", -1)
    else:
        jax.config.update("jax_enable_compilation_cache", False)
    return jax


class CompileCounter:
    """Backend compiles seen through ``jax.monitoring`` (a program loaded
    from the persistent cache is not counted)."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def _train_argv(cell: spec_lib.Cell, seed: int) -> list:
    c = cell.config
    argv = ["--arch", c["registry"], "--batch", str(c["batch"]),
            "--seq", str(c["seq"]), "--seed", str(seed)]
    if c.get("program_reduced"):
        argv.append("--reduced")
    return argv + list(cell.mix["train_argv"])


def _verify(obj, fields: dict, values: dict, what: str):
    """The program's settings must be the file's: a drift fails the run."""
    for field, key in fields.items():
        got, want = getattr(obj, field), values[key]
        if isinstance(want, float) or isinstance(got, float):
            ok = abs(float(got) - float(want)) <= 1e-12 * max(1.0, abs(want))
        else:
            ok = got == want
        if not ok:
            raise spec_lib.SpecError(
                f"{what}: program has {field}={got!r}, the file {key}={want!r}")


@dataclasses.dataclass
class StepRecord:
    loss: float
    seconds: float
    refresh: bool


class Program:
    """The system under test for one cell and one seed."""

    def __init__(self, cell: spec_lib.Cell, seed: int, fault: str = None):
        program_path()
        import jax
        import jax.numpy as jnp
        from repro.core.factory import make_optimizer
        from repro.launch import train as train_lib
        from repro.models import model as model_lib
        from repro.train.trainer import make_train_step
        from bench import data as data_lib, reflib

        self.cell, self.seed, self.fault = cell, seed, fault
        args = train_lib.parse_args(_train_argv(cell, seed))
        cfg, opt_cfg = train_lib.configs(args)
        _verify(cfg, cell.config["program_fields"], cell.config["model"],
                cell.workload["config"])
        _verify(opt_cfg, cell.mix["program_fields"], cell.mix["hyper"],
                cell.workload["traffic"])
        if opt_cfg.name != cell.mix["optimizer"]:
            raise spec_lib.SpecError(f"program optimizer {opt_cfg.name!r}")
        self.cfg, self.opt_cfg = cfg, opt_cfg
        self.batch_size, self.seq = cell.config["batch"], cell.config["seq"]
        self.period = int(cell.mix["period_steps"])
        self.update_every = int(cell.mix["hyper"].get("update_every", 1))
        tx = make_optimizer(opt_cfg)
        self.params = jax.jit(functools.partial(model_lib.init_params, cfg))(
            reflib.seed_key(seed))
        self.opt_state = jax.jit(tx.init)(self.params)
        self.step_fn = self._faulty(make_train_step(cfg, tx, donate=False)) \
            if fault else make_train_step(cfg, tx)
        self.data = data_lib.SyntheticLM(cfg.vocab_size, self.seq,
                                         self.batch_size, seed)
        self.count = 0
        self.jax, self.jnp = jax, jnp

    def _faulty(self, raw):
        """The step broken underneath, for the harness's own tests."""
        import jax
        fault, B, V = self.fault, self.batch_size, self.cfg.vocab_size
        if fault not in FAULTS:
            raise ValueError(f"unknown fault {fault!r}")

        def step(p, s, b):
            if fault == "half_batch":
                b = {k: v[:B // 2] for k, v in b.items()}
            if fault == "token_altered":
                b = dict(b, labels=(b["labels"] + 1) % V)
            new_p, new_s, m = raw(p, s, b)
            if fault == "state_unchanged":
                return p, s, m
            return new_p, new_s, m
        return jax.jit(step)

    def step(self, annotate: bool = False) -> StepRecord:
        """One iteration of ``train``'s loop; ``annotate`` wraps its parts in
        host trace spans."""
        import time
        jax = self.jax
        span = jax.profiler.TraceAnnotation if annotate \
            else contextlib.nullcontext
        t0 = time.perf_counter()
        with span("bench/data"):
            batch = {k: self.jnp.asarray(v)
                     for k, v in self.data.batch(self.count).items()}
        with span("bench/dispatch"):
            self.params, self.opt_state, metrics = self.step_fn(
                self.params, self.opt_state, batch)
        with span("bench/fetch"):
            loss = float(metrics["loss"])
        rec = StepRecord(loss, time.perf_counter() - t0,
                         self.update_every > 1
                         and self.count % self.update_every == 0)
        self.count += 1
        return rec

    def check_steps(self) -> dict:
        """Steps 0..CHECK_STEPS-1 with the readings the check compares."""
        jax = self.jax
        h = self.cell.mix["hyper"]
        opt = self.cell.opt_ref
        p0 = jax.device_get(self.params)
        losses, first = [], {}
        for i in range(CHECK_STEPS):
            losses.append(self.step().loss)
            if i == 0:
                first["grad_sq"] = [float(x) for x in jax.device_get(
                    jax.jit(lambda s, p: opt.program_first_grad_sq(s, p, h))(
                        self.opt_state, self.params))]
                if opt.program_first_direction is not None:
                    first["direction"] = jax.device_get(
                        opt.program_first_direction(self.opt_state, h))
        p3 = jax.device_get(self.params)
        change = [_diff_norm(a, b) for a, b in zip(jax.tree.leaves(p3),
                                                   jax.tree.leaves(p0))]
        return dict(first, losses=losses, change=change)

    def compiled(self):
        """The step program the window ran: lowered again with the live
        arguments, so that the executable in memory is returned rather than
        compiled anew."""
        batch = {k: self.jnp.asarray(v)
                 for k, v in self.data.batch(self.count).items()}
        return self.step_fn.lower(self.params, self.opt_state,
                                  batch).compile()

    def free(self):
        for x in self.jax.tree.leaves((self.params, self.opt_state)):
            x.delete()
        self.params = self.opt_state = self.step_fn = None


def _diff_norm(a, b) -> float:
    return float(np.linalg.norm(np.asarray(a, np.float64).ravel()
                                - np.asarray(b, np.float64).ravel()))


def reference_readings(cell: spec_lib.Cell, seed: int, mode: str = "highest",
                       row_weights=None) -> dict:
    """The plain reference's readings for the same seed: the same weights
    and batches rebuilt from the seed, three steps of the plain model and
    optimizer.  ``row_weights`` (one per batch row) leaves rows out."""
    program_path()
    import jax
    import jax.numpy as jnp
    from bench import data as data_lib, reflib

    c, h = cell.config, cell.mix["hyper"]
    model, opt = c["model"], cell.opt_ref
    dtype = jnp.dtype(c["param_dtype"])
    shapes = cell.model_ref.param_shapes(model)
    params = jax.jit(lambda k: reflib.init_params(shapes, k, dtype))(
        reflib.seed_key(seed))
    treedef = jax.tree.structure(params)
    flat = jax.tree.leaves(params)
    p0 = jax.device_get(flat)
    data = data_lib.SyntheticLM(model["vocab_size"], c["seq"], c["batch"],
                                seed)
    w = np.ones(c["batch"], np.float32) if row_weights is None \
        else np.asarray(row_weights, np.float32)
    loss_grad = reflib.make_loss_and_grad(cell.model_ref.loss_sum, model,
                                          mode, c["reference_rows"])
    update = opt.make_update(h, mode)
    state = opt.init(flat, h)
    losses, out = [], {}
    for i in range(CHECK_STEPS):
        b = data.batch(i)
        loss, grads = loss_grad(jax.tree.unflatten(treedef, flat),
                                jnp.asarray(b["tokens"]),
                                jnp.asarray(b["labels"]), w)
        losses.append(float(loss))
        flat, state, d = update(flat, jax.tree.leaves(grads), state, i)
        if i == 0:
            out["grad_sq"] = opt.ref_first_grad_sq(state, h)
            if opt.program_first_direction is not None:
                out["direction"] = jax.device_get(d)
    change = [_diff_norm(a, b) for a, b in zip(jax.device_get(flat), p0)]
    return dict(out, losses=losses, change=change)


def compare(prog: dict, ref: dict) -> dict:
    """The numbers the check compares, each a gap against the reference:

    * ``loss_gap``: worst relative gap of the three steps' losses;
    * ``grad_gap``: worst leaf's gap between the norms of the first
      gradient, against the reference's norm of that leaf or of the median
      leaf, whichever is larger;
    * ``update_gap``: the same for each leaf's change over the three steps,
      leaving out leaves whose reference gradient is under
      ``ZERO_GRAD_SHARE`` of the median leaf's;
    * ``direction_gap`` (where the optimizer exposes its first direction):
      worst leaf's relative distance between the first directions.
    """
    out = {}
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    out["loss_gap"] = float(np.max(np.abs(lp - lr) / np.abs(lr)))
    gp = np.sqrt(np.maximum(prog["grad_sq"], 0.0))
    gr = np.sqrt(np.maximum(ref["grad_sq"], 0.0))
    med = statistics.median(gr)
    out["grad_gap"] = float(np.max(np.abs(gp - gr) / np.maximum(gr, med)))
    keep = gr >= ZERO_GRAD_SHARE * med
    cp, cr = np.asarray(prog["change"])[keep], np.asarray(ref["change"])[keep]
    out["update_gap"] = float(np.max(np.abs(cp - cr)
                                     / np.maximum(cr, statistics.median(cr))))
    if "direction" in ref and "direction" in prog:
        gaps = [float(np.linalg.norm(np.asarray(a, np.float64)
                                     - np.asarray(b, np.float64))
                      / max(np.linalg.norm(np.asarray(b, np.float64)), 1e-30))
                for a, b, k in zip(prog["direction"], ref["direction"], keep)
                if k]
        out["direction_gap"] = max(gaps)
    return {k: (v if np.isfinite(v) else float("inf")) for k, v in out.items()}


def verdict(numbers: dict, limits: dict) -> tuple:
    """(correct, {name: [value, limit]}) over the numbers the cell's limits
    file names; a number it names that was not read fails."""
    table = {k: [numbers.get(k, float("inf")), float(v)]
             for k, v in limits.items()}
    return all(v <= lim for v, lim in table.values()), table
