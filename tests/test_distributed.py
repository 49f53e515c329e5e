"""Distributed substrates that need >1 device: run in subprocesses with
--xla_force_host_platform_device_count (the main pytest process must keep
seeing ONE device, per the dry-run contract)."""
import os
import subprocess
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = {**os.environ, "PYTHONPATH": os.path.join(REPO, "src")}


def _run_py(code: str, devices: int = 8) -> str:
    env = {**ENV,
           "XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}"}
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, cwd=REPO, timeout=900)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-4000:]
    return r.stdout


def test_pipeline_parallel_matches_sequential():
    out = _run_py(r"""
import jax, jax.numpy as jnp, numpy as np
from repro.train.pipeline_parallel import gpipe_apply
mesh = jax.make_mesh((4,), ("pipe",))
rng = np.random.default_rng(0)
L, D = 8, 16
layers = {"w": jnp.asarray(rng.normal(size=(L, D, D)) * 0.2, jnp.float32),
          "b": jnp.asarray(rng.normal(size=(L, D)) * 0.1, jnp.float32)}
def block(p, x):
    return jnp.tanh(x @ p["w"] + p["b"])
x = jnp.asarray(rng.normal(size=(8, 4, D)), jnp.float32)
ref = x
for i in range(L):
    ref = block(jax.tree.map(lambda a: a[i], layers), ref)
got = gpipe_apply(mesh, "pipe", layers, block, x, microbatches=4)
np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)
# gradients flow through the pipeline
def loss(ls):
    return jnp.sum(gpipe_apply(mesh, "pipe", ls, block, x, 4) ** 2)
g = jax.grad(loss)(layers)
assert all(np.isfinite(np.asarray(v)).all() for v in jax.tree.leaves(g))
print("PIPE_OK")
""")
    assert "PIPE_OK" in out


def test_compressed_gradient_allreduce():
    out = _run_py(r"""
import jax, jax.numpy as jnp, numpy as np
from repro.train.compression import compressed_mean_grads
mesh = jax.make_mesh((8,), ("data",))
rng = np.random.default_rng(0)
grads = {"a": jnp.asarray(rng.normal(size=(64, 32)), jnp.float32),
         "b": jnp.asarray(rng.normal(size=(128,)), jnp.float32)}
# replicated input => mean == input; compression error must be small
got = compressed_mean_grads(grads, mesh, ("data",))
for k in grads:
    ref = np.asarray(grads[k])
    err = np.abs(np.asarray(got[k]) - ref).max() / (np.abs(ref).max() + 1e-9)
    assert err < 0.02, (k, err)
print("COMPRESS_OK")
""")
    assert "COMPRESS_OK" in out


def test_elastic_plan_and_remesh():
    out = _run_py(r"""
import jax
from repro.train.elastic import plan_mesh, remesh
plan = plan_mesh(8, model_parallel=2, target_global_batch=64)
assert plan.mesh_shape == (4, 2)
mesh = remesh(plan)
assert mesh.devices.shape == (4, 2)
# lose two devices -> dp shrinks, batch stays divisible
plan = plan_mesh(6, model_parallel=2, target_global_batch=64)
assert plan.mesh_shape == (3, 2)
assert plan.global_batch % 3 == 0
print("ELASTIC_OK")
""")
    assert "ELASTIC_OK" in out


def test_butterfly_merge_under_shard_map():
    """Log-depth ppermute butterfly on a faked 4-device mesh: merged sketch
    == exact union covariance within the FD bound; int8 wire stays close to
    the exact fp32 wire."""
    out = _run_py(r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core.fd import FDState, fd_update_batched, fd_covariance
from repro.distributed import reduce as dreduce

mesh = jax.make_mesh((4,), ("data",))
d, ell, N = 16, 6, 2
rng = np.random.default_rng(0)
G = jnp.asarray(rng.normal(size=(4, N, d, 1)), jnp.float32)

def run(wire):
    def body(Gl):
        st = FDState(jnp.zeros((N, d, ell)), jnp.zeros((N, ell)),
                     jnp.zeros((N,)))
        st = fd_update_batched(st, Gl[0])
        assert dreduce.bound_axis_size("data") == 4
        return dreduce.butterfly_merge_fd(st, axis="data", axis_size=4,
                                          wire_dtype=wire)
    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P("data"),),
                             out_specs=FDState(P(), P(), P()),
                             check_vma=False))(G)

out = run("fp32")
for n in range(N):
    exact = sum(np.outer(G[i, n, :, 0], G[i, n, :, 0]) for i in range(4))
    st_n = FDState(out.eigvecs[n], out.eigvals[n], out.rho[n])
    err = np.linalg.norm(exact - np.asarray(fd_covariance(st_n)), 2)
    assert err <= float(out.rho[n]) * (1 + 1e-4) + 1e-3, (n, err)
out8 = run("int8")
rel = np.abs(np.asarray(out8.eigvals) - np.asarray(out.eigvals)).max() / \
    (np.abs(np.asarray(out.eigvals)).max() + 1e-9)
assert rel < 0.1, rel
print("BUTTERFLY_OK")
""", devices=4)
    assert "BUTTERFLY_OK" in out


def test_sharded_stats_engine_parity_and_bound():
    """Engine acceptance criteria: "sharded" under an unbound axis and on a
    1-sized data axis is BITWISE equal to replicated; on a 4-sized axis the
    merged pool sketch matches the exact (1/P) sum_i G_i G_i^T stream within
    the FD merge error bound."""
    out = _run_py(r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core import api, sketchy as sk
from repro.core.fd import FDState, fd_covariance
from repro.distributed import reduce as dreduce

rng = np.random.default_rng(0)
d = 16
params = {"w": jnp.asarray(rng.normal(size=(d, d)), jnp.float32),
          "v": jnp.asarray(rng.normal(size=(10,)), jnp.float32)}
mk_cfg = lambda **kw: sk.SketchyConfig(rank=6, block_size=d, beta2=0.9,
                                       update_every=1, **kw)
tx_r = sk.sketchy(mk_cfg())
tx_s = sk.sketchy(mk_cfg(stats_reduction="sharded", stats_wire_dtype="fp32"))
state0 = tx_r.init(params)
grads = {"w": jnp.asarray(rng.normal(size=(4, d, d)), jnp.float32),
         "v": jnp.asarray(rng.normal(size=(4, 10)), jnp.float32)}
gmean = jax.tree.map(lambda g: g.mean(0), grads)

def run(tx, g, s, steps=3):
    for _ in range(steps):
        dirs, s = tx.update(g, s, params)
    return dirs, s

# 1) unbound axis: bitwise == replicated
ref = jax.jit(lambda g, s: run(tx_r, g, s))(gmean, state0)
got = jax.jit(lambda g, s: run(tx_s, g, s))(gmean, state0)
for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(got)):
    assert np.array_equal(np.asarray(a), np.asarray(b))
print("UNBOUND_PARITY_OK")

def sharded_run(mesh, g, s):
    def body(gl, s):
        gl = jax.tree.map(lambda x: x[0], gl)
        gm = dreduce.pmean(gl, "data")
        with dreduce.local_gradients(gl):
            return run(tx_s, gm, s)
    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P("data"), P()),
                             out_specs=(P(), P()), check_vma=False))(g, s)

# 2) data-axis size 1: bitwise == replicated
d1 = sharded_run(jax.make_mesh((1,), ("data",)),
                 jax.tree.map(lambda g: g[None], gmean), state0)
for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(d1)):
    assert np.array_equal(np.asarray(a), np.asarray(b))
print("P1_PARITY_OK")

# 3) data-axis size 4: merged sketch obeys the FD bound against the exact
#    (1/P) sum_i G_i G_i^T stream (beta2-decayed across refreshes)
steps, beta2 = 3, 0.9
_, s4 = sharded_run(jax.make_mesh((4,), ("data",)), grads, state0)
S = np.zeros((d, d))
Gw = np.asarray(grads["w"])
for _ in range(steps):
    S = beta2 * S + sum(Gw[i] @ Gw[i].T for i in range(4)) / 4.0
stats = api.pool_stats(api.get_stage(s4, "precond")
                       if isinstance(s4, dict) else s4)
left = stats.left
sk_state = FDState(left.eigvecs[0], left.eigvals[0], left.rho[0])
err = np.linalg.norm(S - np.asarray(fd_covariance(sk_state)), 2)
rho = float(sk_state.rho)
assert err <= rho * (1 + 1e-3) + 1e-2, (err, rho)
print("P4_BOUND_OK", err, rho)
""", devices=4)
    assert "UNBOUND_PARITY_OK" in out
    assert "P1_PARITY_OK" in out
    assert "P4_BOUND_OK" in out


def test_sharded_trainer_end_to_end():
    """make_train_step(data_parallel_mesh=...) trains the reduced LM with
    stats_reduction="sharded" on a 4-device mesh; loss stays finite and
    tracks the replicated run."""
    out = _run_py(r"""
import jax, jax.numpy as jnp, numpy as np
from repro.configs.registry import get_reduced
from repro.core.factory import OptimizerConfig, make_optimizer
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.models import model as model_lib
from repro.train.trainer import make_train_step

cfg = get_reduced("paper_lm_100m")
data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=32,
                              global_batch=8))
params = model_lib.init_params(cfg, jax.random.PRNGKey(0))

def losses(stats_reduction, mesh):
    tx = make_optimizer(OptimizerConfig(
        name="sketchy", learning_rate=1e-3, total_steps=8, rank=8,
        block_size=64, update_every=2, schedule="constant",
        stats_reduction=stats_reduction))
    p, s = params, tx.init(params)
    # donate=False: the module-level `params` feeds both losses() runs
    step = make_train_step(cfg, tx, data_parallel_mesh=mesh, donate=False)
    out = []
    for i in range(6):
        batch = {k: jnp.asarray(v) for k, v in data.batch(i).items()}
        p, s, m = step(p, s, batch)
        out.append(float(m["loss"]))
    return out

mesh = jax.make_mesh((4,), ("data",))
l_shard = losses("sharded", mesh)
l_repl = losses("replicated", None)
assert all(np.isfinite(l_shard)), l_shard
# same batches, same mean grads => trajectories track closely
for a, b in zip(l_shard, l_repl):
    assert abs(a - b) < 0.15 * abs(b) + 0.05, (l_shard, l_repl)
print("TRAINER_SHARDED_OK", l_shard[-1], l_repl[-1])
""", devices=4)
    assert "TRAINER_SHARDED_OK" in out


def test_remesh_opt_state_rebalances_pools():
    """remesh_opt_state routes pooled stacks through the blocks sharding:
    the leading opt_blocks dim is actually distributed on the new mesh, and
    re-balances again when the mesh shrinks."""
    out = _run_py(r"""
import jax, jax.numpy as jnp, numpy as np
from repro.core import api, sketchy as sk
from repro.train.elastic import plan_mesh, remesh, remesh_opt_state

rng = np.random.default_rng(0)
params = {f"w{i}": jnp.asarray(rng.normal(size=(64, 32)), jnp.float32)
          for i in range(4)}   # 4 leaves x 2 blocks of (32, 32) => N=8
tx = sk.sketchy(sk.SketchyConfig(rank=4, block_size=32))
state = tx.init(params)

def pool_leaf_devices(state):
    pools = state.pools
    (key,) = pools
    leaf = pools[key].left.eigvecs
    arr = leaf.value if isinstance(leaf, api.Tagged) else leaf
    return arr.sharding, arr

mesh = remesh(plan_mesh(8, model_parallel=2, target_global_batch=64))
assert mesh.devices.shape == (4, 2)
_, state8 = remesh_opt_state(state, params, mesh)
sh, arr = pool_leaf_devices(state8)
assert sh.spec[0] is not None, sh  # leading blocks dim is sharded
assert len({d for d in arr.devices()}) == 8

# mesh shrinks 8 -> 4 devices: pools re-balance directly
mesh4 = remesh(plan_mesh(4, model_parallel=2, target_global_batch=64))
assert mesh4.devices.shape == (2, 2)
_, state4 = remesh_opt_state(state8, params, mesh4)
sh4, arr4 = pool_leaf_devices(state4)
assert sh4.spec[0] is not None, sh4
assert len({d for d in arr4.devices()}) == 4
np.testing.assert_array_equal(np.asarray(arr4), np.asarray(arr))
print("REMESH_POOLS_OK")
""")
    assert "REMESH_POOLS_OK" in out


def test_merge_sketches_on_shrink():
    """Departing shards' sketch stacks fold into the survivors' via the
    mergeable-sketch primitive (host-side, no mesh needed)."""
    import jax.numpy as jnp
    from repro.core import api
    from repro.core.fd import FDState, fd_covariance, fd_merge_batched
    from repro.train.elastic import merge_sketches_on_shrink

    rng = np.random.default_rng(0)
    d, ell, N = 12, 4, 2

    def mk_stack():
        U = np.linalg.qr(rng.normal(size=(d, ell)))[0]
        s = np.sort(rng.uniform(1, 2, size=ell))[::-1]
        s[-1] = 0.0
        return FDState(
            eigvecs=jnp.asarray(np.stack([U] * N), jnp.float32),
            eigvals=jnp.asarray(np.stack([s] * N), jnp.float32),
            rho=jnp.asarray(rng.uniform(0, 1, size=N), jnp.float32))

    a, b = mk_stack(), mk_stack()
    tag = lambda st: FDState(*(api.tag(x, "second_moment", blocked=True)
                               for x in st))
    merged = merge_sketches_on_shrink([{"pool": tag(a)}, {"pool": tag(b)}])
    direct = fd_merge_batched(a, b)
    got = merged["pool"]
    assert isinstance(got.eigvecs, api.Tagged)  # tags survive the fold
    got_u = FDState(*api.untag(list(got)))
    for n in range(N):
        np.testing.assert_allclose(
            np.asarray(fd_covariance(FDState(got_u.eigvecs[n],
                                             got_u.eigvals[n],
                                             got_u.rho[n]))),
            np.asarray(fd_covariance(FDState(direct.eigvecs[n],
                                             direct.eigvals[n],
                                             direct.rho[n]))), atol=1e-4)
    np.testing.assert_allclose(np.asarray(got_u.rho),
                               np.asarray(direct.rho), rtol=1e-6)


def test_straggler_monitor():
    from repro.train.elastic import StragglerMonitor
    m = StragglerMonitor(window=20, k=3.0)
    # 15 uniform ~10ms steps, slight jitter
    m.times.extend([0.010 + 1e-4 * (i % 3) for i in range(15)])
    m._t0 = __import__("time").perf_counter() - 0.5  # fake a 500ms step
    m.stop()
    assert m.flagged == 1
    m._t0 = __import__("time").perf_counter() - 0.0101  # normal step
    m.stop()
    assert m.flagged == 1
