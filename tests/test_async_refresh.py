"""Async (one-step-stale) refresh pipeline: the property that makes
``refresh_mode="async"`` safe is STEP-SHIFTED EQUALITY — after every step t
the async engine's *committed view* (pending slot selected over the live
pool, ``api.committed_pools``) is bitwise identical to the inline engine's
pool state at t, for every refresh schedule, storage dtype and stats
reduction.  Only the update direction is one refresh stale (it is computed
before the step's refresh lands); the statistics stream itself never
diverges.  Plus: the pending double buffer is invisible to memory
accounting and checkpoints."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import api
from repro.core.shampoo import ShampooConfig, shampoo
from repro.core.sketchy import SketchyConfig, sketchy

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PARAMS = {"a": np.float32, "b": np.float32, "c": np.float32}


def _params():
    return {"a": jnp.ones((48, 20), jnp.float32) * 0.1,
            "b": jnp.ones((10,), jnp.float32) * 0.1,
            "c": jnp.ones((70, 30), jnp.float32) * 0.1}


def _grads(t, params):
    k = jax.random.PRNGKey(100 + t)
    return {n: jax.random.normal(jax.random.fold_in(k, i), p.shape,
                                 jnp.float32) * 0.5
            for i, (n, p) in enumerate(sorted(params.items()))}


def _leaves_equal(a, b, msg):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb), msg
    for x, y in zip(la, lb):
        assert np.array_equal(np.asarray(x), np.asarray(y)), msg


def _sketchy_pair(schedule, dtype, **kw):
    mk = lambda mode: sketchy(SketchyConfig(
        rank=6, block_size=16, beta2=0.95, update_every=3,
        refresh_schedule=schedule, refresh_mode=mode,
        second_moment_dtype=dtype, **kw))
    return mk("inline"), mk("async")


@pytest.mark.parametrize("schedule", ["synchronized", "staggered"])
@pytest.mark.parametrize("dtype", ["fp32", "bf16", "int8"])
def test_async_committed_equals_inline(schedule, dtype):
    """Core pipeline property, over several refresh windows: at every step
    the async committed stats == inline stats BITWISE, and the per-leaf
    residue (diag fallback, grafting) is identical unshifted."""
    params = _params()
    tx_i, tx_a = _sketchy_pair(schedule, dtype)
    s_i, s_a = tx_i.init(params), tx_a.init(params)
    step_i = jax.jit(lambda g, s: tx_i.update(g, s, params))
    step_a = jax.jit(lambda g, s: tx_a.update(g, s, params))
    for t in range(8):
        g = _grads(t, params)
        _, s_i = step_i(g, s_i)
        _, s_a = step_a(g, s_a)
        _leaves_equal(api.committed_pools(s_a), s_i.pools,
                      f"committed != inline at step {t}")
        _leaves_equal(s_a.leaves, s_i.leaves,
                      f"leaf residue diverged at step {t}")
        assert all(bool(slot.valid.value)
                   for slot in s_a.pending.values()), t


def test_async_shampoo_parity():
    """Same property on the Shampoo engine (eigh root recompute pipelined
    instead of the FD shrink)."""
    params = _params()
    mk = lambda mode: shampoo(ShampooConfig(
        block_size=16, beta2=0.95, root_every=3, refresh_mode=mode))
    tx_i, tx_a = mk("inline"), mk("async")
    s_i, s_a = tx_i.init(params), tx_a.init(params)
    for t in range(7):
        g = _grads(t, params)
        _, s_i = tx_i.update(g, s_i, params)
        _, s_a = tx_a.update(g, s_a, params)
        _leaves_equal(api.committed_pools(s_a), s_i.pools,
                      f"shampoo committed != inline at step {t}")


def test_async_direction_is_one_refresh_stale():
    """The async direction at the first refresh step still uses the warm-up
    stats (the refresh hasn't committed), then picks it up next step —
    i.e. async actually pipelines instead of degenerating to inline."""
    params = _params()
    tx_i, tx_a = _sketchy_pair("synchronized", "fp32")
    s_i, s_a = tx_i.init(params), tx_a.init(params)
    diverged = False
    for t in range(6):
        g = _grads(t, params)
        d_i, s_i = tx_i.update(g, s_i, params)
        d_a, s_a = tx_a.update(g, s_a, params)
        same = all(np.array_equal(np.asarray(x), np.asarray(y))
                   for x, y in zip(jax.tree.leaves(d_i),
                                   jax.tree.leaves(d_a)))
        if not same:
            diverged = True
    assert diverged, "async directions never lagged inline — no pipelining"


def test_pending_slot_excluded_from_memory_accounting():
    """The double buffer is transient: paper-metric second-moment bytes are
    identical across refresh modes (Fig. 1 numbers don't move)."""
    params = _params()
    for dtype in ("fp32", "int8"):
        tx_i, tx_a = _sketchy_pair("synchronized", dtype)
        b_i = api.second_moment_bytes(jax.eval_shape(tx_i.init, params))
        b_a = api.second_moment_bytes(jax.eval_shape(tx_a.init, params))
        assert b_i == b_a, (dtype, b_i, b_a)


def test_checkpoint_drops_pending_and_cross_restores(tmp_path):
    """Mid-flight checkpoints: the manifest of an async run is identical in
    leaf names to an inline run's (pending never saved); restores work in
    all four (save-mode x restore-mode) directions; a restored async state
    has valid=False (commit no-op) and keeps training."""
    import json

    from repro.train import checkpoint as ck

    params = _params()
    tx_i, tx_a = _sketchy_pair("synchronized", "int8")

    def run(tx, state, t0, t1):
        for t in range(t0, t1):
            _, state = tx.update(_grads(t, params), state, params)
        return state

    # save mid-flight: step 5 is past a refresh, pending is valid
    s_i = run(tx_i, tx_i.init(params), 0, 5)
    s_a = run(tx_a, tx_a.init(params), 0, 5)
    assert all(bool(sl.valid.value) for sl in s_a.pending.values())
    d_i, d_a = str(tmp_path / "inline"), str(tmp_path / "async")
    ck.save(d_i, 5, s_i)
    ck.save(d_a, 5, s_a)

    def names(d):
        with open(os.path.join(d, "step-5", "manifest.json")) as f:
            return [r["name"] for r in json.load(f)["leaves"]]

    assert names(d_i) == names(d_a)
    assert not any("pending" in n for n in names(d_a))

    tmpl_i = jax.eval_shape(tx_i.init, params)
    tmpl_a = jax.eval_shape(tx_a.init, params)
    for src in (d_i, d_a):
        r_i, _, _ = ck.restore(src, tmpl_i)
        assert r_i.pending is None
        _leaves_equal(r_i.pools, s_i.pools, f"{src} -> inline pools")
        r_a, _, _ = ck.restore(src, tmpl_a)
        for slot in r_a.pending.values():
            assert not bool(slot.valid.value)
            assert all(float(jnp.abs(jnp.asarray(v, jnp.float32)).max()) == 0
                       for v in jax.tree.leaves(api.untag(slot.stats)))
        # the zeroed pending commits as a no-op: live pools pass through
        _leaves_equal(api.committed_pools(r_a), r_a.pools, "commit not no-op")
        # resumed async run re-primes and keeps the shifted parity
        s_i2 = run(tx_i, r_i, 5, 9)
        s_a2 = run(tx_a, r_a, 5, 9)
        _leaves_equal(api.committed_pools(s_a2), s_i2.pools,
                      f"{src}: post-restore parity lost")


def test_async_parity_under_sharded_stats():
    """Step-shifted equality composes with stats_reduction="sharded": on a
    4-device data axis the async committed pools match the inline sharded
    engine bitwise at every step (fp32 wire: the merge itself is exact)."""
    env = {**os.environ, "PYTHONPATH": os.path.join(REPO, "src"),
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    r = subprocess.run([sys.executable, "-c", r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core import api, sketchy as sk
from repro.distributed import reduce as dreduce

rng = np.random.default_rng(0)
d = 16
params = {"w": jnp.asarray(rng.normal(size=(d, d)), jnp.float32),
          "v": jnp.asarray(rng.normal(size=(10,)), jnp.float32)}
mesh = jax.make_mesh((4,), ("data",))

def make_step(tx):
    def body(gl, s):
        gl = jax.tree.map(lambda x: x[0], gl)
        gm = dreduce.pmean(gl, "data")
        with dreduce.local_gradients(gl):
            return tx.update(gm, s, params)
    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P("data"), P()),
                             out_specs=(P(), P()), check_vma=False))

for sched in ("synchronized", "staggered"):
    mk = lambda mode: sk.sketchy(sk.SketchyConfig(
        rank=6, block_size=d, beta2=0.9, update_every=2, refresh_mode=mode,
        refresh_schedule=sched, stats_reduction="sharded",
        stats_wire_dtype="fp32"))
    tx_i, tx_a = mk("inline"), mk("async")
    step_i, step_a = make_step(tx_i), make_step(tx_a)
    s_i, s_a = tx_i.init(params), tx_a.init(params)
    for t in range(6):
        k = jax.random.PRNGKey(t)
        g = {n: jax.random.normal(jax.random.fold_in(k, i), (4,) + p.shape,
                                  jnp.float32)
             for i, (n, p) in enumerate(sorted(params.items()))}
        _, s_i = step_i(g, s_i)
        _, s_a = step_a(g, s_a)
        ci = jax.tree.leaves(api.committed_pools(s_a))
        li = jax.tree.leaves(s_i.pools)
        for a, b in zip(ci, li):
            assert np.array_equal(np.asarray(a), np.asarray(b)), (sched, t)
print("SHARDED_ASYNC_PARITY_OK")
"""], capture_output=True, text=True, env=env, cwd=REPO, timeout=900)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-4000:]
    assert "SHARDED_ASYNC_PARITY_OK" in r.stdout
