"""The plain reference a run is checked against.  It imports nothing of the
program and takes nothing the program made.

* Statistics: H = sigmoid(X W + b), G = H^T H, R = H^T T, one agent and
  one block of rows at a time on the device, every product at
  ``precision`` (HIGHEST for the reference, HIGH, three bf16 passes, for
  the control), the blocks summed in float64.
* ADMM: DMTL-ELM's Jacobian round (paper eqs. 16, 19, 21) on the host in
  float64, with the U-solve G U M + c U = rhs done through eigh(G) and
  eigh(M).  ``precision="high"`` is the control: float32, every matrix
  product from three bf16 passes (hi*hi + hi*lo + lo*hi).
  ``precision="float32"`` is the same round in plain float32 (a look at
  what float32 alone leaves, not a limit's end).  In both, eigh and solve
  run in float32 too (LAPACK's single-precision routines).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import scipy.linalg.lapack

PRECISIONS = {"highest": jax.lax.Precision.HIGHEST,
              "high": jax.lax.Precision.HIGH}


@functools.partial(jax.jit, static_argnames="precision")
def _agent_stats(X, T, W, b, precision):
    p = PRECISIONS[precision]
    H = jax.nn.sigmoid(jnp.matmul(X.astype(jnp.float32), W, precision=p) + b)
    return (jnp.matmul(H.T, H, precision=p),
            jnp.matmul(H.T, T.astype(jnp.float32), precision=p))


# XLA's HIGHEST dot on a TPU v5e contracting over 65,536 rows came out with
# its trace 1.2e-5 below float64's, on every seed tried; over 8,192 rows it
# agrees to 1e-8 with a Gram kernel that is itself within 2.3e-8 of float64.
# So each block of at most this many rows is one dot, and the blocks are
# summed in float64.
BLOCK_ROWS = 8192


def agent_stats(X, T, W, b, precision="highest"):
    """(G (L, L), R (L, D)) float64 on the host for one agent's rows."""
    G = R = 0.0
    for i in range(0, X.shape[0], BLOCK_ROWS):
        Gi, Ri = _agent_stats(X[i:i + BLOCK_ROWS], T[i:i + BLOCK_ROWS], W, b,
                              precision)
        G = G + np.asarray(Gi, np.float64)
        R = R + np.asarray(Ri, np.float64)
    return G, R


def edges(graph: str, m: int) -> list[tuple[int, int]]:
    """The (s, e) pairs of a named graph over ``m`` agents: ``ring`` links
    t to t + 1 (mod m), ``star`` links agent 0 to every other."""
    if graph == "ring":
        return [(t, (t + 1) % m) for t in range(m)] if m > 2 else [(0, 1)]
    if graph == "star":
        return [(0, t) for t in range(1, m)]
    raise ValueError(f"no reference for graph {graph!r}")


def _bf16(x):
    return x.astype(ml_dtypes.bfloat16).astype(np.float32)


def _mm_high():
    """``a @ b`` from three bf16 passes.  The (L, L) operands (G and its
    eigenvectors) are split once and kept."""
    kept = {}

    def split(x):
        if id(x) in kept:
            return kept[id(x)][1:]
        x32 = x.astype(np.float32)
        hi = _bf16(x32)
        lo = _bf16(x32 - hi)
        if min(x.shape[-2:]) >= 128:
            kept[id(x)] = (x, hi, lo)
        return hi, lo

    def mm(a, b):
        (a_hi, a_lo), (b_hi, b_lo) = split(a), split(b)
        return a_hi @ b_hi + (a_hi @ b_lo + a_lo @ b_hi)

    return mm


def eigh(x):
    """eigh of each symmetric matrix of a stack ``x`` (m, n, n) in ``x``'s
    own precision: numpy's eigh computes in float64 whatever its input, so
    float32 goes through LAPACK's single-precision ``ssyevd``."""
    if x.dtype == np.float64:
        return np.linalg.eigh(x)
    out = [scipy.linalg.lapack.ssyevd(a, lower=1) for a in x]
    if any(info for _, _, info in out):
        raise np.linalg.LinAlgError("ssyevd did not converge")
    return (np.stack([w for w, _, _ in out]),
            np.stack([q for _, q, _ in out]))


def solve(a, b):
    """``a^-1 b`` for each pair of a stack, in ``a``'s own precision."""
    if a.dtype == np.float64:
        return np.linalg.solve(a, b)
    return np.stack([scipy.linalg.solve(ai, bi) for ai, bi in zip(a, b)])


def admm(G, R, t2, edges, U0, A0, *, mu1, mu2, tau, zeta, rho=1.0,
         delta=10.0, gamma_cap=1.0, gamma_floor=0.0, iters,
         precision="float64", eig=None):
    """Run ``iters`` rounds from (U0, A0); returns (U, A, objective per
    iteration).  G (m, L, L), R (m, L, D), t2 (m,), U0 (m, L, r),
    A0 (m, r, D); ``edges`` lists (s, e) pairs; ``eig`` is eigh(G) if the
    caller has it."""
    if precision not in ("float64", "float32", "high"):
        raise ValueError(f"unknown precision {precision!r}")
    dt = np.float64 if precision == "float64" else np.float32
    mm = _mm_high() if precision == "high" else np.matmul
    G, R, t2 = (np.asarray(x, dt) for x in (G, R, t2))
    U, A = np.asarray(U0, dt), np.asarray(A0, dt)
    m, L, r = U.shape
    src = np.array([s for s, _ in edges])
    dst = np.array([e for _, e in edges])
    deg = np.zeros(m, dt)
    np.add.at(deg, src, 1.0)
    np.add.at(deg, dst, 1.0)
    tau_t = tau + deg
    p_t = tau_t - rho * deg                     # prox-linear weight
    c_t = mu1 / m + rho * deg + p_t
    dg, qg = (eigh(G) if eig is None
              else (np.asarray(eig[0], dt), np.asarray(eig[1], dt)))
    qgT = np.ascontiguousarray(np.swapaxes(qg, 1, 2))
    lam = np.zeros((len(edges), L, r), dt)
    eye = np.eye(r, dtype=dt)
    objective = []
    for _ in range(iters):
        neigh = np.zeros_like(U)
        np.add.at(neigh, src, U[dst])
        np.add.at(neigh, dst, U[src])
        ct_lam = np.zeros_like(U)
        np.add.at(ct_lam, src, lam)
        np.subtract.at(ct_lam, dst, lam)
        At = np.swapaxes(A, 1, 2)
        M = mm(A, At)
        rhs = (mm(R, At) + rho * neigh - ct_lam
               + p_t[:, None, None] * U)
        dm, qm = eigh(M)
        rt = mm(mm(qgT, rhs), qm)
        rt = rt / (dg[:, :, None] * dm[:, None, :] + c_t[:, None, None])
        U_new = mm(mm(qg, rt), np.swapaxes(qm, 1, 2))
        Ut = np.swapaxes(U_new, 1, 2)
        Ga = mm(mm(Ut, G), U_new) + (zeta + mu2) * eye
        A_new = solve(Ga, mm(Ut, R) + zeta * A)
        res_old = U[src] - U[dst]
        res_new = U_new[src] - U_new[dst]
        dual = np.sum((res_old - res_new) ** 2, axis=(1, 2))
        primal = np.sum(res_new ** 2, axis=(1, 2))
        gamma = np.minimum(gamma_cap,
                           delta * dual / np.maximum(primal, 1e-12))
        gamma = np.maximum(gamma, gamma_floor)
        gamma = np.where(primal <= 1e-12, gamma_cap, gamma)
        lam = lam + rho * gamma[:, None, None] * res_new
        U, A = U_new, A_new
        objective.append(_objective(G, R, t2, U, A, mu1, mu2, mm))
    return U, A, np.array(objective, np.float64)


def _objective(G, R, t2, U, A, mu1, mu2, mm):
    """Primal objective (paper eq. 12) from the statistics."""
    m = U.shape[0]
    Ut = np.swapaxes(U, 1, 2)
    UA = mm(U, A)
    quad = np.sum(mm(mm(Ut, G), U) * mm(A, np.swapaxes(A, 1, 2)))
    cross = np.sum(R * UA)
    return float(0.5 * (quad - 2.0 * cross + np.sum(t2))
                 + 0.5 * (mu1 / m) * np.sum(U ** 2)
                 + 0.5 * mu2 * np.sum(A ** 2))


def heads(U, A):
    """Each agent's head U_t A_t (m, L, D), float64: the rotation of U that
    A undoes drops out."""
    return np.matmul(np.asarray(U, np.float64), np.asarray(A, np.float64))
