"""Custom-call-free 3x3 SVD via one-sided Jacobi rotations.

Why this exists: ``jnp.linalg.svd`` lowers to a LAPACK/cuSolver custom call on
CPU/GPU and a large QR-iteration HLO on TPU — both have data-dependent or
platform-dependent behaviour. FPPS dedicates a small fixed-latency SVD unit on
the FPGA; the TPU-native analogue is a *fixed iteration count* one-sided
Jacobi sweep: pure element-wise math + 3x3 matmuls, identical HLO on every
backend, deterministic latency, trivially vmappable over batches of
covariances (one per frame-pair in fleet-scale registration).

One-sided Jacobi: orthogonalise the columns of A by right-multiplying Givens
rotations; then ``A V = U Σ``. For 3x3, 8 sweeps x 3 pivots reaches fp32
machine precision (tested against jnp.linalg.svd in tests/test_svd3x3.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_PIVOTS = ((0, 1), (0, 2), (1, 2))


def _jacobi_rotation(a_pp, a_qq, a_pq, eps):
    """Givens (c, s) zeroing the (p,q) off-diagonal of the implicit Gram matrix."""
    # Classic stable formulation (Golub & Van Loan §8.4).
    tau = (a_qq - a_pp) / (2.0 * jnp.where(jnp.abs(a_pq) < eps, eps, a_pq))
    # sign(0) must be +1 here: a_pp == a_qq with a_pq != 0 needs a 45° rotation.
    sgn = jnp.where(tau >= 0.0, 1.0, -1.0)
    t = sgn / (jnp.abs(tau) + jnp.sqrt(1.0 + tau * tau))
    t = jnp.where(jnp.abs(a_pq) < eps, 0.0, t)
    c = 1.0 / jnp.sqrt(1.0 + t * t)
    s = c * t
    return c, s


def _apply_right_rotation(A, V, p, q, c, s):
    """A <- A G, V <- V G where G rotates columns p,q."""
    Ap, Aq = A[:, p], A[:, q]
    A = A.at[:, p].set(c * Ap - s * Aq)
    A = A.at[:, q].set(s * Ap + c * Aq)
    Vp, Vq = V[:, p], V[:, q]
    V = V.at[:, p].set(c * Vp - s * Vq)
    V = V.at[:, q].set(s * Vp + c * Vq)
    return A, V


def svd3x3(M: jax.Array, sweeps: int = 8):
    """SVD of a 3x3 matrix: returns (U, S, Vt) with M = U @ diag(S) @ Vt.

    Singular values are returned sorted descending, matching
    ``jnp.linalg.svd``. U, Vt are orthogonal; no sign convention beyond
    S >= 0 is imposed (same contract as LAPACK).
    """
    dtype = M.dtype
    work = M.astype(jnp.float32)
    eps = jnp.asarray(1e-30, jnp.float32)
    V = jnp.eye(3, dtype=jnp.float32)

    def sweep(carry, _):
        A, V = carry
        for (p, q) in _PIVOTS:
            col_p, col_q = A[:, p], A[:, q]
            a_pp = jnp.dot(col_p, col_p, precision="highest")
            a_qq = jnp.dot(col_q, col_q, precision="highest")
            a_pq = jnp.dot(col_p, col_q, precision="highest")
            c, s = _jacobi_rotation(a_pp, a_qq, a_pq, eps)
            A, V = _apply_right_rotation(A, V, p, q, c, s)
        return (A, V), None

    (work, V), _ = jax.lax.scan(sweep, (work, V), None, length=sweeps)

    # Column norms are the singular values; normalised columns are U.
    s = jnp.sqrt(jnp.sum(work * work, axis=0))
    # Sort descending.
    order = jnp.argsort(-s)
    s = s[order]
    work = work[:, order]
    V = V[:, order]
    # Guard rank-deficient columns (zero singular value -> arbitrary orthonormal
    # dir). Keep Jacobi's columns wherever they are valid — forcing det(U)=+1
    # would corrupt reconstruction for reflections — and only synthesise
    # replacements for (near-)zero singular values, sign-matched to the
    # original column so U @ diag(S) @ Vt is unchanged.
    safe = jnp.maximum(s, 1e-30)
    U = work / safe[None, :]
    tol = 1e-12 * jnp.maximum(s[0], 1e-30)
    u0 = jnp.where(s[0] > tol, U[:, 0], jnp.array([1.0, 0.0, 0.0], jnp.float32))
    u1_raw = U[:, 1] - jnp.dot(U[:, 1], u0, precision="highest") * u0
    u1_norm = jnp.linalg.norm(u1_raw)
    u1 = jnp.where(jnp.logical_and(s[1] > tol, u1_norm > 1e-20),
                   u1_raw / jnp.maximum(u1_norm, 1e-30), _any_orthogonal(u0))
    u2_cross = jnp.cross(u0, u1)
    sign = jnp.where(jnp.dot(u2_cross, U[:, 2], precision="highest") < 0.0, -1.0, 1.0)
    u2 = jnp.where(s[2] > tol, sign * u2_cross, u2_cross)
    U = jnp.stack([u0, u1, u2], axis=1)
    return U.astype(dtype), s.astype(dtype), V.T.astype(dtype)


def _any_orthogonal(u: jax.Array) -> jax.Array:
    """A unit vector orthogonal to u (u assumed unit, possibly axis-aligned)."""
    # Pick the axis least aligned with u, Gram-Schmidt it.
    axis = jnp.eye(3, dtype=u.dtype)[jnp.argmin(jnp.abs(u))]
    v = axis - jnp.dot(axis, u, precision="highest") * u
    return v / jnp.maximum(jnp.linalg.norm(v), 1e-30)


svd3x3_batched = jax.vmap(svd3x3, in_axes=(0,))
