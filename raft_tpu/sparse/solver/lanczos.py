"""Thick-restart Lanczos eigensolver.

(ref: cpp/include/raft/sparse/solver/lanczos.cuh:35,60,87 public API (COO +
CSR overloads); impl sparse/solver/detail/lanczos.cuh (799 LoC):
``lanczos_smallest:402`` host-orchestrated thick-restart loop,
``lanczos_aux:248`` Krylov tridiagonalization (cusparse SpMV + cublas
orthogonalization), ``lanczos_solve_ritz:129`` small tridiagonal eig via
eigDC. Runtime entry: cpp/src/raft_runtime/solver/lanczos_solver.cuh:11;
python binding python/pylibraft/pylibraft/sparse/linalg/lanczos.pyx:100.)

TPU re-design: the Krylov build keeps the whole (ncv+1)×n basis resident in
HBM and does FULL re-orthogonalization as two dense [ncv+1,n]×[n] matmuls
per step — MXU work replacing the reference's sequence of dot/axpy cublas
calls (a better hardware fit: one big contraction instead of j small ones,
and unconditionally stable, so the projected matrix is computed as full
Rayleigh-Ritz rather than strict tridiagonal). Masked rows make every step
static-shape, so one restart cycle is a single jitted program
(``lax.fori_loop`` over steps, ``eigh`` on the ncv×ncv projection inside).
The restart loop runs on host with an ``interruptible`` cancellation point
per cycle, exactly like the reference's host hot loop (SURVEY §3.1).
"""

from __future__ import annotations

from functools import partial
from typing import Tuple, Union

import jax
import jax.numpy as jnp

from raft_tpu.core import interruptible, nvtx
from raft_tpu.core.error import expects
from raft_tpu.core.resources import ensure_resources
from raft_tpu.core.sparse_types import COOMatrix, CSRMatrix
from raft_tpu.sparse.solver.lanczos_types import LANCZOS_WHICH, LanczosSolverConfig

Operand = Union[COOMatrix, CSRMatrix, "TiledELL", "TiledPairsSpmv",
                jax.Array]


def _matvec(A, x):
    from raft_tpu.sparse.sharded import ShardedTiledELL
    from raft_tpu.sparse.tiled import TiledELL, TiledPairsSpmv

    if isinstance(A, (COOMatrix, CSRMatrix, TiledELL, TiledPairsSpmv,
                      ShardedTiledELL)):
        from raft_tpu.sparse.linalg import spmv

        return spmv(None, A, x)
    return A @ x


def _restart_cycle_impl(A, V, T0, j0, ncv: int):
    """Build Krylov columns j0..ncv-1 with two-pass full
    reorthogonalization, then Rayleigh-Ritz. Returns
    (theta, S, V, beta_last) — V[ncv] is the normalized residual vector."""
    dtype = V.dtype

    def step(j, carry):
        V, T, _ = carry
        row_mask = (jnp.arange(ncv + 1) <= j)[:, None].astype(dtype)
        Vm = V * row_mask
        w = _matvec(A, V[j])
        h = Vm @ w
        w = w - Vm.T @ h
        h2 = Vm @ w            # second Gram-Schmidt pass (stability)
        w = w - Vm.T @ h2
        h = h + h2
        beta = jnp.linalg.norm(w)
        safe_beta = jnp.where(beta > 0, beta, jnp.asarray(1.0, dtype))
        T = T.at[:, j].set(h[:ncv])
        T = T.at[j, :].set(h[:ncv])
        V = V.at[j + 1].set(w / safe_beta)
        T = jnp.where(j + 1 < ncv,
                      T.at[j + 1, j].set(beta).at[j, j + 1].set(beta), T)
        return V, T, beta

    V, T, beta_last = jax.lax.fori_loop(
        j0, ncv, step, (V, T0, jnp.asarray(0.0, dtype)))
    theta, S = jnp.linalg.eigh((T + T.T) / 2)
    return theta, S, V, beta_last


_restart_cycle = jax.jit(_restart_cycle_impl, static_argnames=("ncv",))


def _select(theta, which: LANCZOS_WHICH, k: int):
    """Indices (ascending positions) of the k wanted ritz values."""
    if which == LANCZOS_WHICH.SA:
        idx = jnp.arange(k)
    elif which == LANCZOS_WHICH.LA:
        idx = jnp.arange(theta.shape[0] - k, theta.shape[0])
    elif which == LANCZOS_WHICH.LM:
        idx = jnp.sort(jnp.argsort(-jnp.abs(theta))[:k])
    else:  # SM
        idx = jnp.sort(jnp.argsort(jnp.abs(theta))[:k])
    return idx


def _restart_select(theta, which: LANCZOS_WHICH, k: int, ncv: int):
    """(indices to KEEP across a thick restart, their static count).

    For the extremal modes the restart keeps exactly the k wanted ritz
    vectors. ``SM`` additionally keeps an EXTREMAL DEFLATION BUFFER of
    the largest-magnitude ritz vectors: restarting with only interior
    approximations discards the converged extremal structure the
    interior convergence depends on — measured on the tier-1 fixture
    (n=60, k=4, ncv=25) the unbuffered restart stalls at relative
    residual ~3e-1 with a spurious eigenvalue, while the buffered one
    converges to 8e-7 in fewer steps. The two index sets are disjoint
    by construction (k smallest-|θ| vs nb largest-|θ| with
    k + nb ≤ ncv), so the count is static — jit-safe."""
    if which != LANCZOS_WHICH.SM:
        return _select(theta, which, k), k
    nb = max(0, min(2 * k + 4, ncv - k - 2))
    sm = jnp.argsort(jnp.abs(theta))[:k]
    lm = jnp.argsort(-jnp.abs(theta))[:nb]
    return jnp.sort(jnp.concatenate([sm, lm])), k + nb


def _residual_estimate(theta, S, beta_last, idx, ncv: int):
    """Ritz residual bound |β·S[m−1,i]| + spectrum scale (shared by both
    solve paths)."""
    resid = jnp.abs(beta_last * S[ncv - 1, idx])
    scale = jnp.maximum(jnp.max(jnp.abs(theta)), 1e-30)
    return resid, scale


def _restart_state(theta, S, V, idx, k: int, ncv: int):
    """Thick restart: wanted ritz vectors + residual direction, projected
    T (shared by both solve paths)."""
    ritz = S[:, idx].T @ V[:ncv]
    V2 = jnp.zeros_like(V).at[:k].set(ritz).at[k].set(V[ncv])
    T0 = jnp.zeros((ncv, ncv), V.dtype).at[
        jnp.arange(k), jnp.arange(k)].set(theta[idx])
    return V2, T0


def _extract_eigvecs(S, V, idx, ncv: int):
    """Final ritz-vector extraction (shared by both solve paths)."""
    eigvecs = (S[:, idx].T @ V[:ncv]).T
    return eigvecs / jnp.linalg.norm(eigvecs, axis=0, keepdims=True)


@partial(jax.jit, static_argnames=("ncv", "k", "which"))
def _solve_jitted(A, V0, tol, max_steps, ncv: int, k: int,
                  which: LANCZOS_WHICH):
    """The whole thick-restart loop as ONE compiled program
    (``lax.while_loop`` over cycles) — no per-cycle host dispatch.
    Returns (vals, vecs, max_relative_residual) so the caller can warn on
    non-convergence. tol/max_steps are traced operands: changing them does
    not recompile."""
    dtype = V0.dtype
    theta, S, V, beta_last = _restart_cycle_impl(
        A, V0, jnp.zeros((ncv, ncv), dtype), jnp.asarray(0, jnp.int32), ncv)

    def _rel_resid(theta, S, beta_last):
        idx = _select(theta, which, k)
        resid, scale = _residual_estimate(theta, S, beta_last, idx, ncv)
        return jnp.max(resid) / scale

    def cond(state):
        theta, S, V, beta_last, steps = state
        return (_rel_resid(theta, S, beta_last) > tol) & (steps < max_steps)

    def body(state):
        theta, S, V, beta_last, steps = state
        ridx, k_r = _restart_select(theta, which, k, ncv)
        V2, T0 = _restart_state(theta, S, V, ridx, k_r, ncv)
        theta, S, V, beta_last = _restart_cycle_impl(
            A, V2, T0, jnp.asarray(k_r, jnp.int32), ncv)
        return theta, S, V, beta_last, steps + (ncv - k_r)

    theta, S, V, beta_last, _ = jax.lax.while_loop(
        cond, body, (theta, S, V, beta_last, jnp.asarray(ncv, jnp.int32)))
    idx = _select(theta, which, k)
    eigvecs = _extract_eigvecs(S, V, idx, ncv)
    return theta[idx], eigvecs, _rel_resid(theta, S, beta_last)


def lanczos_compute_eigenpairs(
    res,
    A: Operand,
    config: LanczosSolverConfig,
    v0=None,
) -> Tuple[jax.Array, jax.Array]:
    """Compute ``config.n_components`` eigenpairs of symmetric A.

    Returns (eigenvalues [k] ascending, eigenvectors [n, k]).
    (ref: sparse/solver/lanczos.cuh:35 — the COO/CSR overloads collapse
    into the Operand union here; dense operands are accepted too, which is
    what the BASELINE "Lanczos on 100k×1k dense" config exercises.)
    """
    res = ensure_resources(res)
    k = config.n_components
    from raft_tpu.sparse.sharded import ShardedTiledELL
    from raft_tpu.sparse.tiled import TiledELL, TiledPairsSpmv

    if isinstance(A, (COOMatrix, CSRMatrix)):
        n = A.shape[0]
        dtype = A.values.dtype
    elif isinstance(A, (TiledELL, TiledPairsSpmv, ShardedTiledELL)):
        n = A.shape[0]
        dtype = A.vals.dtype
    else:
        A = jnp.asarray(A)
        n = A.shape[0]
        dtype = A.dtype
    expects(0 < k < n, "lanczos: need 0 < n_components < n")
    ncv = config.ncv if config.ncv is not None else min(n, max(2 * k + 1, 20))
    ncv = min(max(ncv, k + 2), n)

    key = jax.random.key(config.seed)
    if v0 is None:
        key, sub = jax.random.split(key)
        v0 = jax.random.normal(sub, (n,), dtype)
    v0 = jnp.asarray(v0, dtype)
    V = jnp.zeros((ncv + 1, n), dtype)
    V = V.at[0].set(v0 / jnp.linalg.norm(v0))
    T0 = jnp.zeros((ncv, ncv), dtype)

    jit_loop = config.jit_loop
    if jit_loop is None:
        # AUTO: one compiled program on accelerators (no per-cycle host
        # round-trip); the host loop — cancellation points +
        # stagnation early-exit — stays the CPU default
        jit_loop = jax.default_backend() != "cpu"
    if jit_loop:
        with nvtx.annotate("lanczos_compute_eigenpairs[jit]"):
            vals, vecs, rel_resid = _solve_jitted(
                A, V, jnp.asarray(config.tolerance, dtype),
                jnp.asarray(config.max_iterations, jnp.int32),
                ncv, k, config.which)
        rr = float(rel_resid)
        if rr > config.tolerance:
            from raft_tpu.core.logger import log_warn

            log_warn("lanczos[jit]: stopped with relative residual %.3e > "
                     "tolerance %.3e (max_iterations=%d)", rr,
                     config.tolerance, config.max_iterations)
        return vals, vecs

    j0 = 0
    n_steps = 0
    best_resid = None
    stagnant = 0
    with nvtx.annotate("lanczos_compute_eigenpairs"):
        while True:
            interruptible.yield_()  # cancellation point per restart cycle
            theta, S, V, beta_last = _restart_cycle(
                A, V, T0, jnp.asarray(j0, jnp.int32), ncv)
            n_steps += ncv - j0
            idx = _select(theta, config.which, k)
            resid, scale = _residual_estimate(theta, S, beta_last, idx, ncv)
            max_resid = float(jnp.max(resid))
            if bool(jnp.all(resid <= config.tolerance * scale)):
                break
            if n_steps >= config.max_iterations:
                from raft_tpu.core.logger import log_warn

                log_warn("lanczos: max_iterations=%d reached with relative "
                         "residual %.3e > tolerance %.3e",
                         config.max_iterations, max_resid / float(scale),
                         config.tolerance)
                break
            # stop on TRUE flatline only: 50 cycles without even 0.1%
            # improvement means the fp32 floor was hit (e.g. a large zero
            # eigenvalue cluster); legitimately slow geometric convergence
            # (say 0.995×/cycle) still counts as progress and keeps going
            # up to max_iterations
            if best_resid is None or max_resid < 0.999 * best_resid:
                best_resid = max_resid if best_resid is None else min(
                    best_resid, max_resid)
                stagnant = 0
            else:
                stagnant += 1
                if stagnant >= 50:
                    from raft_tpu.core.logger import log_warn

                    log_warn("lanczos: residual stagnated at %.3e (relative "
                             "%.3e > tolerance %.3e) — fp32 floor reached, "
                             "returning best available eigenpairs",
                             max_resid, max_resid / float(scale),
                             config.tolerance)
                    break
            ridx, k_r = _restart_select(theta, config.which, k, ncv)
            V, T0 = _restart_state(theta, S, V, ridx, k_r, ncv)
            j0 = k_r

    return theta[idx], _extract_eigvecs(S, V, idx, ncv)
