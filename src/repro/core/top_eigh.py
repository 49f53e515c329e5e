"""Leading eigenpairs of symmetric matrices on TPU (QDWH divide and conquer).

JAX's TPU ``eigh`` (``jax/_src/tpu/linalg/eigh.py``) splits a matrix at the
median of its diagonal into the eigenspaces below and above it (a QDWH polar
decomposition of the shifted matrix), recursing until a block is at most 256
wide, where a Jacobi solve finishes it.  Asked for a slice of the spectrum
(``lax.linalg.eigh(..., subset_by_index=...)``) it skips every subproblem
whose index range misses the slice, but then still sorts the skipped rows'
first column as if it held eigenvalues: stale entries of the parent block,
which can outrank true eigenvalues and take their place in the result (JAX
0.9.0; an FD Gram, whose leading diagonal holds the sketch's eigenvalues,
gets a wrong top eigenpair nearly every time).

``top_eigh`` runs the same recursion for the top ``k`` eigenpairs and marks
each skipped range's eigenvalues ``-inf`` before the final sort, so only
solved eigenpairs are returned.  The subproblems it keeps are the ones the
full solve computes, by the same functions (``split_spectrum`` and the
Jacobi base case of JAX's solver, at the same matmul precision).  Only the
eigenspace below a split is ever skipped: the one above always holds the top
of its block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax._src.tpu.linalg import eigh as _qdwh

# JAX's TPU eigh hands blocks this wide or smaller to the Jacobi solver.
TERMINATION_SIZE = 256


def _bucket_sizes(N: int, termination_size: int) -> list[int]:
    """Padded block sizes of the recursion, as JAX's solver chooses them."""
    cutoff = min(N, termination_size)
    sizes = [cutoff]
    if N > termination_size:
        sizes.append(N)
        i = int(N / 1.98)
        while i > cutoff:
            sizes.append(_qdwh._round_up(i, 32))
            i = i // 2
    return sizes


def _top_work(H: jnp.ndarray, k: int, termination_size: int):
    """Eigenvalues (N,) and eigenvectors (N, N) of one symmetric ``H``, in
    the recursion's order: every eigenpair at ascending index ``N - k`` or
    above is solved, and every skipped range's eigenvalues read ``-inf``."""
    N = H.shape[0]
    lo = N - k
    n = jnp.asarray(N, np.int32)
    zero = jnp.array(0, np.int32)
    agenda = _qdwh.Stack.create(N + 1, _qdwh._Subproblem(zero, zero))
    agenda = agenda.push(_qdwh._Subproblem(offset=zero, size=n))
    eigenvectors = jnp.eye(N, dtype=H.dtype)
    H0_norm = jnp.linalg.norm(_qdwh._mask(H, (n, n)))
    skipped = jnp.full((N, 1), -jnp.inf, H.dtype)

    def base_case(B, offset, b, agenda, blocks, eigenvectors):
        # the Jacobi solver keeps the eigenvalues' order, so the padded
        # block's eigenpairs sit in its top-left corner
        Hb = _qdwh._mask(_qdwh._slice(blocks, (offset, 0), (b, b), (B, B)),
                         (b, b))
        V = _qdwh._slice(eigenvectors, (0, offset), (n, b), (N, B))
        vecs, vals = lax.linalg.eigh(Hb, sort_eigenvalues=False)
        vecs = jnp.dot(V, _qdwh._mask(vecs, (b, b)))
        vals = _qdwh._mask(vals, (b,)).astype(vecs.dtype)
        blocks = _qdwh._update_slice(blocks, vals[:, None], (offset, 0),
                                     (b, 1))
        eigenvectors = _qdwh._update_slice(eigenvectors, vecs, (0, offset),
                                           (n, b))
        return agenda, blocks, eigenvectors

    def recursive_case(B, offset, b, agenda, blocks, eigenvectors):
        Hb = _qdwh._slice(blocks, (offset, 0), (b, b), (B, B))

        def nearly_diagonal_case(agenda, blocks, eigenvectors):
            blocks = _qdwh._update_slice(blocks, jnp.diag(Hb)[:, None],
                                         (offset, 0), (b, 1))
            return agenda, blocks, eigenvectors

        def split_case(agenda, blocks, eigenvectors):
            V = _qdwh._slice(eigenvectors, (0, offset), (n, b), (N, B))
            split_point = jnp.nanmedian(
                _qdwh._mask(jnp.diag(Hb), (b,), np.nan))
            H_minus, V_minus, H_plus, V_plus, rank = _qdwh.split_spectrum(
                Hb, b, split_point, V0=V)
            blocks, eigenvectors, agenda = lax.cond(
                offset + rank > lo,
                lambda: (
                    _qdwh._update_slice(blocks, H_minus, (offset, 0),
                                        (rank, rank)),
                    _qdwh._update_slice(eigenvectors, V_minus, (0, offset),
                                        (n, rank)),
                    agenda.push(_qdwh._Subproblem(offset, rank))),
                lambda: (
                    _qdwh._update_slice(blocks, skipped[:B], (offset, 0),
                                        (rank, 1)),
                    eigenvectors, agenda))
            blocks = _qdwh._update_slice(blocks, H_plus, (offset + rank, 0),
                                         (b - rank, b - rank))
            eigenvectors = _qdwh._update_slice(
                eigenvectors, V_plus, (0, offset + rank), (n, b - rank))
            agenda = agenda.push(_qdwh._Subproblem(offset + rank, b - rank))
            return agenda, blocks, eigenvectors

        # a nearly diagonal block, or one that is numerical noise against
        # the whole matrix, is done (Nakatsukasa and Higham, section 5.2)
        norm = jnp.linalg.norm(Hb)
        eps = jnp.asarray(jnp.finfo(Hb.dtype).eps, dtype=norm.dtype)
        off_diag_norm = jnp.linalg.norm(Hb - jnp.diag(jnp.diag(Hb)))
        return lax.cond((off_diag_norm <= 5 * eps * norm)
                        | (norm < eps * H0_norm),
                        nearly_diagonal_case, split_case,
                        agenda, blocks, eigenvectors)

    sizes = _bucket_sizes(N, termination_size)
    branches = [functools.partial(base_case, sizes[0])] + [
        functools.partial(recursive_case, s) for s in sizes[1:]]
    buckets = jnp.array(sizes, dtype=np.int32)

    def body(state):
        agenda, blocks, eigenvectors = state
        (offset, b), agenda = agenda.pop()
        which = jnp.where(buckets < b, jnp.iinfo(np.int32).max, buckets)
        return lax.switch(jnp.argmin(which), branches, offset, b, agenda,
                          blocks, eigenvectors)

    _, blocks, eigenvectors = lax.while_loop(
        lambda state: ~state[0].empty(), body, (agenda, H, eigenvectors))
    return blocks[:, 0], eigenvectors


def top_eigh(C: jnp.ndarray, k: int, *,
             termination_size: int = TERMINATION_SIZE):
    """The ``k`` largest eigenpairs of each real symmetric ``(..., n, n)``.

    Returns ``(w, v)``: ``w (..., k)`` ascending, ``v (..., n, k)`` with
    ``v[..., :, i]`` the eigenvector of ``w[..., i]`` (``jnp.linalg.eigh``'s
    order, its last ``k`` columns).  The matrices are solved one at a time,
    as JAX's TPU eigh solves a batch.  TPU only in practice: the recursion
    relies on the base-case solver not sorting, which holds for the TPU's
    Jacobi and, elsewhere, only with ``termination_size=1``.
    """
    n = C.shape[-1]
    batch = C.shape[:-2]
    if not 0 < k <= n:
        raise ValueError(f"k={k} outside 1..{n}")

    def one(H):
        with jax.default_matmul_precision("float32"):
            vals, vecs = _top_work(H, k, termination_size)
        idx = jnp.argsort(vals)[n - k:]
        return vals[idx], vecs[:, idx]

    # the operand jnp.linalg.eigh solves (its symmetrize_input)
    C = (C + jnp.swapaxes(C, -1, -2)) / 2
    w, v = lax.map(one, C.reshape((-1, n, n)))
    return w.reshape(batch + (k,)), v.reshape(batch + (n, k))
