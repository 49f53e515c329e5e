"""Property tests for the Frequent Directions core (paper Alg. 1, Lemma 1,
Observation 6)."""
import jax
import jax.extend.core as jax_core
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # fall back to the deterministic sampling shim
    from hypothesis_compat import given, settings, strategies as st

from repro.core import fd, quantize
from repro.core.fd import (FDState, fd_apply_inverse_root, fd_covariance,
                           fd_init, fd_merge, fd_merge_batched, fd_update,
                           fd_weighted_factor)
from repro.core.top_eigh import TERMINATION_SIZE, top_eigh

jax.config.update("jax_enable_x64", False)


def _stream(seed, d, T, decay=3.0):
    rng = np.random.default_rng(seed)
    basis = np.linalg.qr(rng.normal(size=(d, d)))[0]
    scales = np.exp(-np.arange(d) / decay)
    return [basis @ (scales * rng.normal(size=d)) for _ in range(T)]


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 10_000), d=st.integers(8, 48),
       ell=st.integers(2, 8), T=st.integers(5, 60))
def test_lemma1_escaped_mass_bound(seed, d, ell, T):
    """rho_{1:T} <= min_k sum_{i>k} lambda_i / (ell - k)  (Lemma 1)."""
    ell = min(ell, d)
    st_ = fd_init(d, ell)
    G = np.zeros((d, d))
    for g in _stream(seed, d, T):
        G += np.outer(g, g)
        st_ = fd_update(st_, jnp.asarray(g, jnp.float32))
    lam = np.maximum(np.linalg.eigvalsh(G)[::-1], 0)
    bound = min(lam[k:].sum() / (ell - k) for k in range(ell))
    assert float(st_.rho) <= bound * (1 + 1e-4) + 1e-5


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_fd_operator_norm_error(seed):
    """||G - Gbar||_op <= rho_{1:T} (FD fundamental guarantee)."""
    d, ell, T = 32, 8, 100
    st_ = fd_init(d, ell)
    G = np.zeros((d, d))
    for g in _stream(seed, d, T):
        G += np.outer(g, g)
        st_ = fd_update(st_, jnp.asarray(g, jnp.float32))
    err = np.linalg.norm(G - np.asarray(fd_covariance(st_)), 2)
    assert err <= float(st_.rho) * (1 + 1e-4) + 1e-4


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000), beta2=st.sampled_from([0.9, 0.99, 0.999]))
def test_ema_fd_obs6(seed, beta2):
    """|| Gbar^{(b2)} - G^{(b2)} ||_op <= rho^{(b2)}_{1:T}  (Obs. 6)."""
    d, ell, T = 24, 6, 80
    st_ = fd_init(d, ell)
    G = np.zeros((d, d))
    for g in _stream(seed, d, T):
        G = beta2 * G + np.outer(g, g)
        st_ = fd_update(st_, jnp.asarray(g, jnp.float32), beta2=beta2)
    err = np.linalg.norm(G - np.asarray(fd_covariance(st_)), 2)
    assert err <= float(st_.rho) * (1 + 1e-4) + 1e-4


def test_sketch_invariants():
    """Eigvecs orthonormal, eigvals descending with zero tail, rho monotone
    (beta2=1)."""
    d, ell = 40, 10
    st_ = fd_init(d, ell)
    prev_rho = 0.0
    rng = np.random.default_rng(0)
    for _ in range(50):
        g = rng.normal(size=d)
        st_ = fd_update(st_, jnp.asarray(g, jnp.float32))
        s = np.asarray(st_.eigvals)
        assert np.all(np.diff(s) <= 1e-4 * max(s.max(), 1.0))
        assert abs(s[-1]) < 1e-4 * max(s.max(), 1.0)
        # pseudo-orthonormal: U^T U == diag with entries in {0, 1}
        # (columns are zero until the stream fills the sketch rank)
        G = np.asarray(st_.eigvecs).T @ np.asarray(st_.eigvecs)
        diag = np.diag(G)
        assert np.all((np.abs(diag - 1) < 5e-3) | (np.abs(diag) < 5e-3))
        off = G - np.diag(diag)
        assert np.abs(off).max() < 5e-3
        assert float(st_.rho) >= prev_rho - 1e-6
        prev_rho = float(st_.rho)


def test_full_rank_exact():
    """ell >= stream rank => sketch is exact and rho == 0 (paper §3.3
    remark: low-rank G_T needs no sketching error)."""
    d, r, ell = 20, 4, 8
    rng = np.random.default_rng(1)
    W = np.linalg.qr(rng.normal(size=(d, r)))[0]
    st_ = fd_init(d, ell)
    G = np.zeros((d, d))
    for _ in range(30):
        g = W @ rng.normal(size=r)
        G += np.outer(g, g)
        st_ = fd_update(st_, jnp.asarray(g, jnp.float32))
    assert float(st_.rho) < 1e-4
    np.testing.assert_allclose(np.asarray(fd_covariance(st_)), G,
                               atol=1e-3 * np.linalg.norm(G, 2))


# ---------------------------------------------------------------- fd_merge
# (distributed sketching: src/repro/distributed/ merges per-shard sketches)


def _sketch(stream, d, ell):
    st_ = fd_init(d, ell)
    for g in stream:
        st_ = fd_update(st_, jnp.asarray(g, jnp.float32))
    return st_


def test_merge_commutative_up_to_sign():
    """a (+) b and b (+) a agree as operators (eigvecs may flip sign)."""
    d, ell = 24, 6
    a = _sketch(_stream(0, d, 30), d, ell)
    b = _sketch(_stream(1, d, 30), d, ell)
    ab, ba = fd_merge(a, b), fd_merge(b, a)
    np.testing.assert_allclose(np.asarray(ab.eigvals), np.asarray(ba.eigvals),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(float(ab.rho), float(ba.rho), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(fd_covariance(ab)),
                               np.asarray(fd_covariance(ba)),
                               atol=1e-3 * max(float(ab.eigvals[0]), 1.0))


def test_merge_associative_on_low_rank():
    """With total rank <= ell the merge is exact, so grouping is immaterial:
    (a+b)+c == a+(b+c) and rho stays 0 (no escaped mass to order)."""
    d, r, ell = 20, 2, 8
    rng = np.random.default_rng(3)
    sketches, G = [], np.zeros((d, d))
    for k in range(3):
        W = np.linalg.qr(rng.normal(size=(d, r)))[0]
        stream = [W @ rng.normal(size=r) for _ in range(15)]
        G += sum(np.outer(g, g) for g in stream)
        sketches.append(_sketch(stream, d, ell))
    a, b, c = sketches
    left = fd_merge(fd_merge(a, b), c)
    right = fd_merge(a, fd_merge(b, c))
    scale = np.linalg.norm(G, 2)
    np.testing.assert_allclose(np.asarray(fd_covariance(left)),
                               np.asarray(fd_covariance(right)),
                               atol=1e-3 * scale)
    np.testing.assert_allclose(np.asarray(fd_covariance(left)), G,
                               atol=1e-3 * scale)
    assert float(left.rho) < 1e-4 * scale
    assert float(right.rho) < 1e-4 * scale


def test_merge_rho_conservation():
    """rho_merged = rho_a + rho_b + rho_t >= rho_a + rho_b: carried masses
    are additive through the merge (Robust FD), never dropped."""
    d, ell = 24, 4
    a = _sketch(_stream(4, d, 60, decay=8.0), d, ell)
    b = _sketch(_stream(5, d, 60, decay=8.0), d, ell)
    m = fd_merge(a, b)
    assert float(m.rho) >= float(a.rho) + float(b.rho) - 1e-5
    # identity participant: merging with an empty sketch changes nothing
    e = fd_init(d, ell)
    m_id = fd_merge(a, e)
    np.testing.assert_allclose(np.asarray(fd_covariance(m_id)),
                               np.asarray(fd_covariance(a)), atol=1e-4)
    np.testing.assert_allclose(float(m_id.rho), float(a.rho), rtol=1e-6)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000), shards=st.integers(2, 5))
def test_merge_disjoint_shards_matches_stream_bound(seed, shards):
    """Sketching k disjoint shards locally and merging matches the exact
    union covariance within the FD guarantee (||G - cov|| <= rho), i.e. the
    merged sketch is as good as a single-stream sketch up to its own
    (additively carried) escaped mass."""
    d, ell, T = 24, 6, 20
    G = np.zeros((d, d))
    sketches = []
    for k in range(shards):
        stream = _stream(seed + k, d, T)
        G += sum(np.outer(g, g) for g in stream)
        sketches.append(_sketch(stream, d, ell))
    merged = sketches[0]
    for s in sketches[1:]:
        merged = fd_merge(merged, s)
    err = np.linalg.norm(G - np.asarray(fd_covariance(merged)), 2)
    assert err <= float(merged.rho) * (1 + 1e-4) + 1e-3
    # and the single-stream sketch of the concatenated stream is within the
    # two sketches' combined escaped mass of the merged one
    single = _sketch([g for k in range(shards)
                      for g in _stream(seed + k, d, T)], d, ell)
    cross = np.linalg.norm(np.asarray(fd_covariance(single)) -
                           np.asarray(fd_covariance(merged)), 2)
    assert cross <= (float(single.rho) + float(merged.rho)) * (1 + 1e-4) + 1e-3


def test_merge_batched_mirrors_single():
    """fd_merge_batched over a stack == fd_merge per block; the wire factor
    drops only the deflated zero column."""
    d, ell, N = 16, 5, 3
    rng = np.random.default_rng(7)
    mk = lambda s: _sketch([rng.normal(size=d) for _ in range(25)], d, ell)
    As, Bs = [mk(0) for _ in range(N)], [mk(1) for _ in range(N)]
    stack = lambda sts: FDState(
        eigvecs=jnp.stack([s.eigvecs for s in sts]),
        eigvals=jnp.stack([s.eigvals for s in sts]),
        rho=jnp.stack([s.rho for s in sts]))
    merged = fd_merge_batched(stack(As), stack(Bs))
    for n in range(N):
        one = fd_merge(As[n], Bs[n])
        np.testing.assert_allclose(
            np.asarray(fd_covariance(FDState(merged.eigvecs[n],
                                             merged.eigvals[n],
                                             merged.rho[n]))),
            np.asarray(fd_covariance(one)), atol=1e-3)
    B = fd_weighted_factor(stack(As), drop_deflated=True)
    assert B.shape == (N, d, ell - 1)
    full = fd_weighted_factor(stack(As))
    np.testing.assert_allclose(np.asarray(full[..., -1]), 0.0, atol=1e-5)


@pytest.mark.parametrize("exponent", [-0.25, -0.5, -1.0])
def test_inverse_root_apply_matches_dense(exponent):
    """(Gbar + (rho+eps)I)^p @ X via factored form == dense eigh result."""
    d, ell = 24, 6
    st_ = fd_init(d, ell)
    rng = np.random.default_rng(2)
    for g in _stream(3, d, 40):
        st_ = fd_update(st_, jnp.asarray(g, jnp.float32))
    eps = 1e-3
    X = jnp.asarray(rng.normal(size=(d, 5)), jnp.float32)
    got = fd_apply_inverse_root(st_, X, exponent=exponent, eps=eps)
    dense = np.asarray(fd_covariance(st_), np.float64) + \
        (float(st_.rho) + eps) * np.eye(d)
    lam, V = np.linalg.eigh(dense)
    want = (V * lam ** exponent) @ V.T @ np.asarray(X, np.float64)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-3, atol=2e-4)


# --- the top-ell eigensolve (core/top_eigh.py, fd._top_eigh) ---------------

def _previous_top_eigh(C, ell):
    """The refresh's solve before it asked for the top ell alone: the full
    spectrum, reversed to descending, clipped, sliced."""
    lam, V = jnp.linalg.eigh(C)
    lam = jnp.maximum(lam[..., ::-1], 0.0)
    V = V[..., ::-1]
    return lam[..., :ell], V[..., :ell]


def _fd_gram_inputs(N=2, d=40, ell=8, r=264, seed=0):
    """A refreshed sketch stack and the next factor; its Grams are ell + r
    wide, past one Jacobi block, so the platform choice is traced."""
    rng = np.random.default_rng(seed)
    A0 = jnp.asarray(rng.normal(size=(N, d, r)), jnp.float32)
    A1 = jnp.asarray(rng.normal(size=(N, d, r)) * np.linspace(0.2, 2, r),
                     jnp.float32)
    state = fd.fd_update_batched(FDState(jnp.zeros((N, d, ell)),
                                         jnp.zeros((N, ell)), jnp.zeros(N)),
                                 A0, beta2=0.999)
    return state, A1


_TOP_PATHS = {
    "update_batched": lambda st, A: fd.fd_update_batched(st, A, beta2=0.999),
    "update_batched_active_k": lambda st, A: fd.fd_update_batched(
        st, A, beta2=0.999, active_k=jnp.array([3, 8])),
    "update_batched_int8": lambda st, A: fd.fd_update_batched(
        st._replace(eigvecs=quantize.quantize_stack(st.eigvecs)), A,
        beta2=0.999),
    "merge_factors_batched": lambda st, A: fd.fd_merge_factors_batched(
        fd_weighted_factor(st), st.rho, A[..., :260], st.rho, ell=8),
    "update_single": lambda st, A: fd_update(
        jax.tree.map(lambda x: x[0], st), A[0], beta2=0.999),
}


@pytest.mark.parametrize("path", sorted(_TOP_PATHS))
def test_top_eigh_matches_previous_expression(path, monkeypatch):
    """Off TPU every FD path returns, bit for bit, what the full solve,
    reversed and sliced, gave it."""
    state, A = _fd_gram_inputs()
    fn = _TOP_PATHS[path]
    got = jax.jit(lambda s, a: fn(s, a))(state, A)
    monkeypatch.setattr(fd, "_top_eigh", _previous_top_eigh)
    want = jax.jit(lambda s, a: fn(s, a))(state, A)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.shape == w.shape and g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def _subjaxprs(eqn):
    """The jaxprs an equation holds (branches, loop bodies, calls)."""
    for p in eqn.params.values():
        for sub in p if isinstance(p, (tuple, list)) else (p,):
            if isinstance(sub, jax_core.ClosedJaxpr):
                sub = sub.jaxpr
            if isinstance(sub, jax_core.Jaxpr):
                yield sub


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs nested in it."""
    for e in jaxpr.eqns:
        yield e
        for sub in _subjaxprs(e):
            yield from _eqns(sub)


@pytest.mark.parametrize("d,r", [(1024, 768), (768, 1024)])
def test_tpu_refresh_solves_only_top_ell(d, r):
    """The Grams of the 100M LM's blocks (832 and 1088 wide, ell 64): on TPU
    the refresh runs the top-ell recursion, whose only ``eigh`` calls are
    its unsorted Jacobi blocks, all under ``fd/eigh``; elsewhere one full
    ``eigh`` of the stack."""
    N, ell = 2, 64
    n = ell + r
    jx = jax.make_jaxpr(fd.fd_update_batched)(
        FDState(jnp.zeros((N, d, ell)), jnp.zeros((N, ell)), jnp.zeros(N)),
        jnp.zeros((N, d, r)))
    (cond,) = [e for e in jx.jaxpr.eqns if e.primitive.name == "cond"
               and "branches_platforms" in e.params]
    platforms = cond.params["branches_platforms"]
    branch = lambda p: cond.params["branches"][platforms.index(p)].jaxpr
    tpu, default = branch(("tpu",)), branch(None)

    full = [e for e in _eqns(default) if e.primitive.name == "eigh"]
    assert [e.invars[0].aval.shape for e in full] == [(N, n, n)]

    blocks = [e for e in _eqns(tpu) if e.primitive.name == "eigh"]
    assert blocks
    for e in blocks:
        assert e.invars[0].aval.shape == (TERMINATION_SIZE, TERMINATION_SIZE)
        assert not e.params["sort_eigenvalues"]
        assert e.params["subset_by_index"] is None
    solver = [e for e in tpu.eqns
              if any(q.primitive.name == "eigh"
                     for sub in _subjaxprs(e) for q in _eqns(sub))]
    assert solver and all("fd/eigh" in str(e.source_info.name_stack)
                          for e in solver)
    assert [v.aval.shape for v in tpu.outvars] == [(N, ell), (N, n, ell)]
    assert [v.aval.shape for v in default.outvars] == [(N, ell), (N, n, ell)]


@pytest.mark.parametrize("k", [8, 30, 48])
def test_top_eigh_recursion_matches_full_recursion(k):
    """``top_eigh``'s skipping, checked where it can run off TPU: with the
    recursion taken down to 1x1 blocks (no Jacobi step, whose CPU stand-in
    sorts and so breaks the padded base case) it returns the full
    recursion's top k, bit for bit, and float64's eigenvalues to
    round-off.  k = 8 skips most of the spectrum, k = 30 keeps a part below
    the first split, k = n skips nothing."""
    from jax._src.tpu.linalg import eigh as qdwh_eigh
    d, ell, r = 96, 8, 40
    n = ell + r
    rng = np.random.default_rng(k)
    Cs = []
    for _ in range(2):
        U = np.linalg.qr(rng.normal(size=(d, ell)))[0]
        s = np.sort(rng.uniform(1, 50, ell))[::-1]
        s[-1] = 0.0
        M = np.concatenate([U * np.sqrt(0.999 * s),
                            rng.normal(size=(d, r)) * rng.uniform(0.1, 1, r)],
                           axis=1).astype(np.float32)
        C = M.T @ M
        Cs.append(0.5 * (C + C.T))
    C = jnp.asarray(np.stack(Cs))
    w, v = jax.jit(lambda C: top_eigh(C, k, termination_size=1))(C)
    assert w.shape == (2, k) and v.shape == (2, n, k)
    for i in range(2):
        fw, fv = qdwh_eigh.eigh(C[i], termination_size=1)
        np.testing.assert_array_equal(np.asarray(w[i]), np.asarray(fw[-k:]))
        np.testing.assert_array_equal(np.asarray(v[i]),
                                      np.asarray(fv[:, -k:]))
        ref = np.linalg.eigvalsh(np.asarray(C[i], np.float64))[-k:]
        np.testing.assert_allclose(np.asarray(w[i]), ref,
                                   rtol=0, atol=1e-5 * ref[-1])
