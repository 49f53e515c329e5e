"""Trainer-level behaviour: microbatching, optimizer integration, loss
improvement on structured data."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import get_reduced
from repro.core.factory import OptimizerConfig, make_optimizer
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.models import model as model_lib
from repro.train.trainer import make_train_step


def test_microbatching_matches_full_batch():
    cfg = get_reduced("paper_lm_100m")
    tx = make_optimizer(OptimizerConfig(name="adam", learning_rate=1e-2,
                                        schedule="constant", grad_clip=None))
    params = model_lib.init_params(cfg, jax.random.PRNGKey(0))
    state = tx.init(params)
    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=16,
                                  global_batch=8))
    batch = {k: jnp.asarray(v) for k, v in data.batch(0).items()}

    # donate=False: params/state feed both step functions
    full = jax.jit(make_train_step(cfg, tx, donate=False))
    micro = jax.jit(make_train_step(cfg, tx, microbatches=4, donate=False))
    p1, _, m1 = full(params, state, batch)
    p2, _, m2 = micro(params, state, batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=1e-5)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-2, atol=1e-4)


def test_sketchy_trains_lm_loss_down():
    cfg = get_reduced("paper_lm_100m")
    tx = make_optimizer(OptimizerConfig(
        name="sketchy", learning_rate=5e-3, rank=8, block_size=32,
        update_every=2, total_steps=40, schedule="constant",
        weight_decay=0.0))
    params = model_lib.init_params(cfg, jax.random.PRNGKey(0))
    state = tx.init(params)
    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=32,
                                  global_batch=8))
    step = make_train_step(cfg, tx)  # jitted + donated internally
    losses = []
    for t in range(40):
        batch = {k: jnp.asarray(v) for k, v in data.batch(t).items()}
        params, state, m = step(params, state, batch)
        losses.append(float(m["loss"]))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.3, losses[:3] + losses[-3:]


def test_train_step_donates_buffers():
    """make_train_step donates params + opt_state: the inputs are deleted
    after the call (XLA reused their buffers for the outputs) and the live
    array population stays flat across steps — no extra steady-state copy
    of the model or optimizer state, in either refresh mode."""
    cfg = get_reduced("paper_lm_100m")
    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=16,
                                  global_batch=4))
    for mode in ("inline", "async"):
        tx = make_optimizer(OptimizerConfig(
            name="sketchy", learning_rate=1e-3, rank=8, block_size=32,
            update_every=2, total_steps=12, schedule="constant",
            refresh_mode=mode))
        params = model_lib.init_params(cfg, jax.random.PRNGKey(0))
        state = tx.init(params)
        step = make_train_step(cfg, tx)

        batch = {k: jnp.asarray(v) for k, v in data.batch(0).items()}
        old_p, old_s = params, state
        params, state, _ = step(params, state, batch)
        jax.block_until_ready(params)
        # the donated inputs are gone — no second copy survives the step
        assert all(x.is_deleted() for x in jax.tree.leaves(old_p)), mode
        assert all(x.is_deleted() for x in jax.tree.leaves(old_s)), mode

        counts = []
        for t in range(1, 7):
            batch = {k: jnp.asarray(v) for k, v in data.batch(t).items()}
            params, state, m = step(params, state, batch)
            jax.block_until_ready(m["loss"])
            counts.append(sum(not a.is_deleted() for a in jax.live_arrays()))
        # steady state: the live-array population does not grow step over
        # step (donation means outputs alias inputs, nothing accumulates)
        assert max(counts) - min(counts) <= 2, (mode, counts)
        del params, state, old_p, old_s, tx, step


def test_launcher_runs_in_process_and_returns_every_step():
    """``train(parse_args(argv))`` is the launcher's in-process entry point:
    one metrics entry per step, reported to ``on_step`` as each finishes."""
    from repro.launch import train as train_lib
    args = train_lib.parse_args(
        ["--reduced", "--steps", "3", "--batch", "4", "--seq", "16",
         "--rank", "4", "--block-size", "32", "--update-every", "2"])
    seen = []
    run = train_lib.train(args, on_step=seen.append)
    assert [m["step"] for m in run.metrics] == [0, 1, 2]
    assert seen == run.metrics
    assert all(np.isfinite(m["loss"]) for m in run.metrics)


def test_sharded_stats_without_devices_raises():
    """--stats-reduction sharded on one device is an error, not a silent
    fallback to replicated statistics."""
    import pytest
    from repro.launch import train as train_lib
    args = train_lib.parse_args(
        ["--reduced", "--steps", "1", "--batch", "4", "--seq", "16",
         "--stats-reduction", "sharded"])
    with pytest.raises(ValueError, match="more than one device"):
        train_lib.train(args)


def test_compile_cache_dir(monkeypatch, tmp_path):
    """The cache goes where JAX_COMPILATION_CACHE_DIR says (JAX reads it
    itself; the helper sets nothing), else to ``<checkout>/.jax_cache``."""
    from repro.launch import compile_cache
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == prev
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = compile_cache.CACHE_DIR
        assert want.endswith(".jax_cache")
        assert compile_cache.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
