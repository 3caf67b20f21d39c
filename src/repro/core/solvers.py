"""Dense linear-algebra solvers shared by the MTL/DMTL algorithms.

Three solve strategies for the U-update family of equations:

1. ``kron_ridge_solve`` — the paper's own formulation (eq. 9 / eq. 19):
   vectorize and invert the ``(L r, L r)`` Kronecker system. Faithful but
   O(L^3 r^3); kept as the reference implementation.
2. ``sylvester_ridge_solve`` — the same equation ``G U M + c U = R`` solved by
   double eigendecomposition in O(L^3 + r^3). Exact (both G, M symmetric PSD);
   this is a beyond-paper optimization recorded in EXPERIMENTS.md.
3. ``cg_solve`` — matrix-free (preconditioned) conjugate gradients on the
   operator, matmul-only (MXU-friendly); used at backbone scale where even
   L^3 is undesirable.  ``gram_diag_precond`` supplies the Gram-diagonal
   (Jacobi) preconditioner — exact diagonal of the Kronecker operator from
   the G/M diagonals alone — registered in the engine as u_solver="pcg".
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Sequence

import jax
import jax.numpy as jnp

# Every matmul of the ADMM path states its precision.  On a TPU the DEFAULT
# precision of an fp32 matmul is one bf16 MXU pass: on a v5e it moved the
# objective of the first two ADMM iterations by 4e-2 relative and the fits
# went to NaN (chip_smoke.py, as coded vs under HIGHEST).  The CPU computes
# fp32 matmuls in fp32 either way, so CPU numbers do not change.
HIGHEST = jax.lax.Precision.HIGHEST


def mm(a: jax.Array, b: jax.Array) -> jax.Array:
    """``a @ b`` at HIGHEST precision."""
    return jnp.matmul(a, b, precision=HIGHEST)


def ridge_solve(H: jax.Array, T: jax.Array, mu: float) -> jax.Array:
    """Closed-form regularized ELM solve (paper eq. 4): (H^T H + mu I)^-1 H^T T.

    Uses Cholesky; G = H^T H + mu I is SPD for mu > 0.
    """
    L = H.shape[-1]
    G = mm(H.T, H) + mu * jnp.eye(L, dtype=H.dtype)
    rhs = mm(H.T, T)
    cho = jax.scipy.linalg.cho_factor(G)
    return jax.scipy.linalg.cho_solve(cho, rhs)


def _vec_cm(x: jax.Array) -> jax.Array:
    """Column-major vectorization, matching vec(AXB) = (B^T kron A) vec(X)."""
    return x.T.reshape(-1)


def _unvec_cm(v: jax.Array, rows: int, cols: int) -> jax.Array:
    return v.reshape(cols, rows).T


def kron_ridge_solve(
    Gs: jax.Array, Ms: jax.Array, R: jax.Array, c: jax.Array | float
) -> jax.Array:
    """Solve sum_t G_t U M_t + c U = R via the vectorized Kronecker system.

    Gs: (m, L, L) symmetric; Ms: (m, r, r) symmetric; R: (L, r); c scalar.
    This is the paper's eq. (9); eq. (19) is the m=1 case with modified c.
    """
    if Gs.ndim == 2:
        Gs = Gs[None]
        Ms = Ms[None]
    L, r = R.shape
    # vec(G U M) = (M^T kron G) vec(U); M symmetric.
    K = jnp.einsum("tij,tkl->ikjl", Ms, Gs,
                   precision=HIGHEST).reshape(L * r, L * r)
    K = K + c * jnp.eye(L * r, dtype=R.dtype)
    v = jnp.linalg.solve(K, _vec_cm(R))
    return _unvec_cm(v, L, r)


def sylvester_ridge_solve(
    G: jax.Array, M: jax.Array, R: jax.Array, c: jax.Array | float,
    eig_g: tuple[jax.Array, jax.Array] | None = None,
) -> jax.Array:
    """Solve G U M + c U = R for symmetric PSD G (L,L), M (r,r) exactly.

    Eigendecompose G = Qg Dg Qg^T, M = Qm Dm Qm^T; in the eigenbasis the
    operator is diagonal with entries Dg_i Dm_j + c.  ``eig_g`` is an
    optional precomputed eigh(G) — G is iteration-invariant in the ADMM
    loops, so callers hoist it out of the scan.
    """
    if eig_g is None:
        dg, qg = jnp.linalg.eigh(G)
    else:
        dg, qg = eig_g
    dm, qm = jnp.linalg.eigh(M)
    Rt = mm(mm(qg.T, R), qm)
    denom = dg[:, None] * dm[None, :] + c
    return mm(mm(qg, Rt / denom), qm.T)


def cg_solve(
    matvec: Callable[[jax.Array], jax.Array],
    b: jax.Array,
    x0: jax.Array | None = None,
    tol: float = 1e-6,
    maxiter: int = 200,
    precond: Callable[[jax.Array], jax.Array] | None = None,
    return_info: bool = False,
) -> jax.Array:
    """(Preconditioned) conjugate gradients for an SPD operator, jittable
    (lax.while_loop).

    ``precond`` applies M^-1 for an SPD preconditioner M ~ A; the iteration
    is standard PCG (search directions M^-1-conjugate, convergence driven
    by cond(M^-1 A)).  ``precond=None`` is exactly the unpreconditioned
    method.  The stopping rule stays on the TRUE residual ||r||/||b||
    regardless of preconditioning, so both variants return solutions of the
    same accuracy — only the iteration count differs.

    ``return_info=True`` returns ``(x, iters)`` (iterations actually
    taken), the hook the solver benchmarks and the preconditioner tests
    use.
    """
    if x0 is None:
        x0 = jnp.zeros_like(b)
    apply_m = precond if precond is not None else (lambda v: v)
    r0 = b - matvec(x0)
    z0 = apply_m(r0)
    p0 = z0
    rz0 = jnp.vdot(r0, z0, precision=HIGHEST).real
    rs0 = jnp.vdot(r0, r0, precision=HIGHEST).real
    b2 = jnp.maximum(jnp.vdot(b, b, precision=HIGHEST).real, 1e-30)

    def cond(state):
        _, _, _, _, rs, it = state
        return jnp.logical_and(rs / b2 > tol * tol, it < maxiter)

    def body(state):
        x, r, p, rz, rs, it = state
        ap = matvec(p)
        pap = jnp.vdot(p, ap, precision=HIGHEST).real
        alpha = rz / jnp.maximum(pap, 1e-30)
        x = x + alpha * p
        r = r - alpha * ap
        z = apply_m(r)
        rz_new = jnp.vdot(r, z, precision=HIGHEST).real
        rs_new = jnp.vdot(r, r, precision=HIGHEST).real
        p = z + (rz_new / jnp.maximum(rz, 1e-30)) * p
        return x, r, p, rz_new, rs_new, it + 1

    x, _, _, _, _, it = jax.lax.while_loop(
        cond, body, (x0, r0, p0, rz0, rs0, 0)
    )
    return (x, it) if return_info else x


def gram_diag_precond(
    Gs: jax.Array, Ms: jax.Array, c: jax.Array | float
) -> Callable[[jax.Array], jax.Array]:
    """Gram-diagonal (Jacobi) preconditioner of U -> sum_t G_t U M_t + c U.

    The operator's matrix is sum_t M_t^T kron G_t + c I; its exact diagonal
    at entry (l, s) is ``sum_t G_t[l, l] M_t[s, s] + c``, an (L, r) grid
    built from the Gram diagonals alone — O(m (L + r)) setup, elementwise
    O(L r) application.  Effective exactly when diag(G) carries the
    conditioning (feature columns of very different scales, the typical
    un-normalized backbone activation spectrum).
    """
    if Gs.ndim == 2:
        Gs = Gs[None]
        Ms = Ms[None]
    dG = jnp.diagonal(Gs, axis1=-2, axis2=-1)   # (m, L)
    dM = jnp.diagonal(Ms, axis1=-2, axis2=-1)   # (m, r)
    denom = jnp.einsum("tl,ts->ls", dG, dM, precision=HIGHEST) + c
    denom = jnp.maximum(denom, 1e-30)
    return lambda v: v / denom


def sum_sylvester_cg(
    Gs: jax.Array, Ms: jax.Array, R: jax.Array, c: jax.Array | float,
    tol: float = 1e-8, maxiter: int = 500,
    precond: str | None = None, return_info: bool = False,
) -> jax.Array:
    """Matrix-free solve of sum_t G_t U M_t + c U = R with (P)CG.

    ``precond="jacobi"`` enables the Gram-diagonal preconditioner
    (:func:`gram_diag_precond`); ``None`` is plain CG.  ``return_info=True``
    forwards the CG iteration count.
    """
    if Gs.ndim == 2:
        Gs = Gs[None]
        Ms = Ms[None]

    def matvec(u):
        return jnp.einsum("tij,jk,tkl->il", Gs, u, Ms,
                          precision=HIGHEST) + c * u

    if precond is None:
        pc = None
    elif precond == "jacobi":
        pc = gram_diag_precond(Gs, Ms, c)
    else:
        raise ValueError(f"unknown precond {precond!r}; None or 'jacobi'")
    return cg_solve(matvec, R, tol=tol, maxiter=maxiter, precond=pc,
                    return_info=return_info)
