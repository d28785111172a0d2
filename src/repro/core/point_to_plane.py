"""Point-to-plane transformation estimation with robust reweighting.

The Kabsch step (``core.transform.estimate_rigid_transform``) minimises the
point-to-*point* error — the FPPS paper's variant. On the structured scenes
LiDAR actually produces (ground planes, facades), the registration
literature's workhorse is the point-to-*plane* error

    E(T) = Σ w_i ( n_iᵀ (T p_i − q_i) )²

which lets correspondences slide along their local surface instead of
pinning them to a sampled point — typically several-fold fewer iterations
on planar-dominant scenes (DESIGN.md §9; validated by
``benchmarks/convergence.py``).

There is no closed-form SVD solution for E, so we take the standard single
Gauss-Newton step per ICP iteration under the small-angle parameterisation
``R ≈ I + [ω]×``: with the 6-vector ``x = (ω, t)`` and the per-pair
Jacobian row ``a_i = [p_i × n_i ; n_i]`` the normal equations are

    (Σ w_i a_i a_iᵀ) x = − Σ w_i r_i a_i,       r_i = n_iᵀ (p_i − q_i)

a 6×6 solve (``jnp.linalg.solve`` — tiny, deterministic, fully inside the
fused ICP iteration). The step is exponentiated exactly (Rodrigues on ω) so
the returned delta is a proper rigid transform at any step size.

Robust reweighting: IRLS weights from the per-pair residual, applied *on
top of* the max-correspondence-distance gate. ``huber`` downweights the
tail linearly, ``tukey`` rejects it entirely (redescending) — the classic
trade: huber keeps gross-outlier bias bounded, tukey removes it but needs a
sane initialisation. Both operate on whichever residual the active
minimiser actually optimises (euclidean distance for point-to-point, plane
distance for point-to-plane).

Everything is pure JAX, shape-static, and (like the Kabsch path) runs
unchanged under jit / vmap / shard_map.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import transform as tf

ROBUST_KERNELS = ("none", "huber", "tukey")


def robust_weights(residual: jax.Array, kind: str,
                   scale: float) -> jax.Array:
    """IRLS weight per residual. ``residual`` is the *unsigned* per-pair
    error in metres; ``scale`` is the kernel's tuning constant (huber's
    delta / tukey's cutoff c).

      none:  w = 1
      huber: w = min(1, scale / |r|)          (linear tail)
      tukey: w = (1 - (r/scale)²)² for |r|<scale, else 0  (redescending)
    """
    if kind == "none":
        return jnp.ones_like(residual)
    r = jnp.abs(residual)
    s = jnp.asarray(scale, residual.dtype)
    if kind == "huber":
        return jnp.minimum(1.0, s / jnp.maximum(r, 1e-12))
    if kind == "tukey":
        u = r / jnp.maximum(s, 1e-12)
        w = (1.0 - u * u) ** 2
        return jnp.where(u < 1.0, w, 0.0)
    raise ValueError(
        f"unknown robust kernel {kind!r}; expected one of {ROBUST_KERNELS}")


def solve_normal_equations(A: jax.Array, b: jax.Array,
                           damping: float = 1e-6) -> jax.Array:
    """Damped 6x6 Gauss-Newton solve + exact exponentiation — the shared
    epilogue of the XLA path (:func:`solve_point_to_plane`) and the fused
    kernel's pre-accumulated ``(A, b)`` moments (DESIGN.md §11).

    ``A = Σ w a aᵀ`` and ``b = −Σ w r a`` with ``a = [p×n; n]``; the
    damping is Levenberg-style, scaled by mean(diag(A)) so it is
    unit-consistent across the rotation and translation blocks. Returns
    the (4,4) incremental rigid transform (fp32).
    """
    A = A.astype(jnp.float32)
    b = b.astype(jnp.float32)
    lam = damping * jnp.maximum(jnp.trace(A) / 6.0, 1e-12)
    x = jnp.linalg.solve(A + lam * jnp.eye(6, dtype=A.dtype), b)
    omega, t = x[:3], x[3:]
    angle = jnp.linalg.norm(omega)
    R = tf.rotation_from_axis_angle(omega, angle)
    return tf.make_transform(R, t)


def solve_point_to_plane(src: jax.Array, dst: jax.Array,
                         normals: jax.Array,
                         weights: jax.Array | None = None,
                         damping: float = 1e-6) -> jax.Array:
    """One Gauss-Newton step of the point-to-plane objective.

    Args:
      src: (N, 3) source points already carrying the cumulative transform
        (the step is computed about the identity, like the Kabsch path).
      dst: (N, 3) matched target points (dst[i] is src[i]'s NN).
      normals: (N, 3) unit normals at the matched target points. Zero rows
        (invalid normals) contribute nothing — their Jacobian row is zero.
      weights: (N,) gate/robust weights; None means all-ones.
      damping: Levenberg-style diagonal damping, scaled by the mean of
        diag(A) so it is unit-consistent across the rotation and
        translation blocks.

    Returns:
      (4, 4) incremental rigid transform.
    """
    if weights is None:
        weights = jnp.ones(src.shape[:-1], dtype=src.dtype)
    w = weights.astype(jnp.float32)
    p = src.astype(jnp.float32)
    q = dst.astype(jnp.float32)
    n = normals.astype(jnp.float32)
    r = jnp.sum(n * (p - q), axis=-1)                       # (N,)
    a = jnp.concatenate([jnp.cross(p, n), n], axis=-1)      # (N, 6)
    aw = a * w[:, None]
    A = jnp.matmul(aw.T, a, precision="highest")                           # (6, 6) MXU
    b = -jnp.matmul(aw.T, r, precision="highest")                          # (6,)
    return solve_normal_equations(A, b, damping).astype(src.dtype)


def point_to_plane_rmse(src: jax.Array, dst: jax.Array, normals: jax.Array,
                        weights: jax.Array | None = None) -> jax.Array:
    """Weighted RMS of the plane residual n·(p − q) (diagnostic metric)."""
    r = jnp.sum(normals * (src - dst), axis=-1)
    if weights is None:
        return jnp.sqrt(jnp.mean(r * r))
    w = weights.astype(src.dtype)
    return jnp.sqrt(jnp.sum(r * r * w) / jnp.maximum(jnp.sum(w), 1e-12))
