"""SE(3) rigid-transform utilities and Kabsch transform estimation.

Implements the math of FPPS §II: the rigid transform ``T = [[R, t], [0, 1]]``,
its composition/application, and the SVD-based transformation-estimation step
(paper step 2: minimise ``E(R,t) = Σ ||q_i - (R p_i + t)||²``).

Everything here is pure JAX (jit/vmap/scan friendly) and runs identically on
CPU/TPU. The 3×3 SVD uses the custom-call-free Jacobi routine in
``svd3x3.py`` so the whole ICP iteration is a single fused XLA computation
with deterministic latency — the TPU analogue of the paper's dedicated
hardware SVD path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.svd3x3 import svd3x3


def make_transform(R: jax.Array, t: jax.Array) -> jax.Array:
    """Build a 4x4 homogeneous transform from rotation R (3,3), translation
    t (3,)."""
    T = jnp.eye(4, dtype=R.dtype)
    T = T.at[:3, :3].set(R)
    T = T.at[:3, 3].set(t.reshape(3))
    return T


def transform_points(T: jax.Array, points: jax.Array) -> jax.Array:
    """Apply homogeneous transform T (4,4) to points (..., 3).

    This is the paper's "point cloud transformer" stage. Implemented as a
    single matmul so XLA maps it to the MXU.

    Every matmul on the registration path asks for float32 precision: on
    a TPU the default rounds f32 operands to bfloat16, a 0.1 m error at
    50 m from the sensor.
    """
    R = T[:3, :3]
    t = T[:3, 3]
    return jnp.matmul(points, R.T, precision="highest") + t


def rotation_from_axis_angle(axis: jax.Array, angle: jax.Array) -> jax.Array:
    """Rodrigues' formula. axis (3,) need not be normalised."""
    axis = axis / (jnp.linalg.norm(axis) + 1e-12)
    kx, ky, kz = axis[0], axis[1], axis[2]
    K = jnp.array([[0.0, -kz, ky], [kz, 0.0, -kx], [-ky, kx, 0.0]], dtype=axis.dtype)
    eye = jnp.eye(3, dtype=axis.dtype)
    KK = jnp.matmul(K, K, precision="highest")
    return eye + jnp.sin(angle) * K + (1.0 - jnp.cos(angle)) * KK


def random_rigid_transform(key: jax.Array, max_angle: float = 0.5,
                           max_translation: float = 1.0,
                           dtype=jnp.float32) -> jax.Array:
    """Sample a random SE(3) transform (for tests / synthetic data)."""
    k1, k2, k3 = jax.random.split(key, 3)
    axis = jax.random.normal(k1, (3,), dtype=dtype)
    angle = jax.random.uniform(k2, (), dtype=dtype, minval=-max_angle, maxval=max_angle)
    t = jax.random.uniform(k3, (3,), dtype=dtype, minval=-max_translation,
                           maxval=max_translation)
    return make_transform(rotation_from_axis_angle(axis, angle), t)


def estimate_rigid_transform(src: jax.Array, dst: jax.Array,
                             weights: jax.Array | None = None) -> jax.Array:
    """Weighted Kabsch: the rigid T minimising Σ w_i ||dst_i - (R src_i + t)||².

    ``src``/``dst`` are (N, 3) corresponding points (dst[i] is the NN of
    src[i] found by the searcher); ``weights`` (N,) masks out
    correspondences rejected by max_correspondence_distance — this is the
    paper's outlier filter folded into the accumulator.

    This is the "result accumulator" + SVD stage: the cross-covariance is a
    (3,N)@(N,3) matmul (MXU work), the SVD is 3×3 Jacobi (VPU work).
    """
    if weights is None:
        weights = jnp.ones(src.shape[:-1], dtype=src.dtype)
    w = weights.astype(src.dtype)
    wsum = jnp.maximum(jnp.sum(w), 1e-12)
    src_mean = jnp.sum(src * w[..., None], axis=0) / wsum
    dst_mean = jnp.sum(dst * w[..., None], axis=0) / wsum
    src_c = src - src_mean
    dst_c = dst - dst_mean
    # Cross-covariance H = Σ w_i src_c_i dst_c_iᵀ  — a (3,N)x(N,3) matmul.
    H = jnp.matmul((src_c * w[..., None]).T, dst_c, precision="highest")
    U, _, Vt = svd3x3(H)
    # Proper rotation: flip the axis with the smallest singular value if det<0.
    det = jnp.linalg.det(jnp.matmul(Vt.T, U.T, precision="highest"))
    D = jnp.diag(jnp.array([1.0, 1.0, 1.0], dtype=src.dtype)).at[2, 2].set(det)
    R = jnp.matmul(jnp.matmul(Vt.T, D, precision="highest"), U.T, precision="highest")
    t = dst_mean - jnp.matmul(R, src_mean, precision="highest")
    return make_transform(R, t)


def estimate_from_covariance(H: jax.Array, src_mean: jax.Array,
                             dst_mean: jax.Array) -> jax.Array:
    """Kabsch from a pre-accumulated cross-covariance (distributed path).

    In the sharded ICP the per-device partial sums of H / means are psum'd
    first (tiny 3x3 + 3-vector collectives), then every device runs this
    replicated epilogue.
    """
    U, _, Vt = svd3x3(H)
    det = jnp.linalg.det(jnp.matmul(Vt.T, U.T, precision="highest"))
    D = jnp.diag(jnp.array([1.0, 1.0, 1.0], dtype=H.dtype)).at[2, 2].set(det)
    R = jnp.matmul(jnp.matmul(Vt.T, D, precision="highest"), U.T, precision="highest")
    t = dst_mean - jnp.matmul(R, src_mean, precision="highest")
    return make_transform(R, t)


def estimate_from_moments(sw: jax.Array, sp: jax.Array, sq: jax.Array,
                          spq: jax.Array) -> jax.Array:
    """Weighted Kabsch from *raw* (uncentred) moment sums — the fused
    kernel's epilogue (DESIGN.md §11).

    With sw = Σw, sp = Σw·p, sq = Σw·q and spq = Σw·p⊗q, the centred
    cross-covariance is ``H = spq − sp⊗sq / sw`` and the centroids are
    ``sp/sw``, ``sq/sw`` — after which this is exactly
    :func:`estimate_from_covariance`. The subtraction happens on O(1)
    scalars, so the only accumulation error is the kernel's fp32 plane
    sums (same magnitude as the unfused (3,N)@(N,3) matmul).
    """
    wsum = jnp.maximum(sw, 1e-12)
    p_mean = sp / wsum
    q_mean = sq / wsum
    H = spq - jnp.outer(sp, sq) / wsum
    return estimate_from_covariance(H, p_mean, q_mean)


def transform_delta(T: jax.Array) -> jax.Array:
    """Scalar 'how far from identity' metric used for the convergence check.

    Matches PCL's transformationEpsilon semantics: squared norm of the
    incremental transform's deviation from identity (rotation part measured
    by ||R - I||_F², translation by ||t||²).
    """
    R = T[:3, :3]
    t = T[:3, 3]
    return jnp.sum((R - jnp.eye(3, dtype=T.dtype)) ** 2) + jnp.sum(t ** 2)


def rmse(src: jax.Array, dst: jax.Array, weights: jax.Array | None = None) -> jax.Array:
    """Root mean square correspondence error (paper Table III metric)."""
    d2 = jnp.sum((src - dst) ** 2, axis=-1)
    if weights is None:
        return jnp.sqrt(jnp.mean(d2))
    w = weights.astype(src.dtype)
    return jnp.sqrt(jnp.sum(d2 * w) / jnp.maximum(jnp.sum(w), 1e-12))


def rmse_from_moments(T_delta: jax.Array, sw: jax.Array, sp: jax.Array,
                      sq: jax.Array, spq: jax.Array, spp: jax.Array,
                      sqq: jax.Array) -> jax.Array:
    """Post-step weighted RMSE from the fused kernel's moment sums.

    Expands Σw‖Rp + t − q‖² algebraically so the per-point residual never
    has to be materialised:

        Σw‖Rp+t−q‖² = spp + sqq + sw‖t‖² + 2 t·(R sp) − 2 tr(R spq)
                      − 2 t·sq

    (using Σw qᵀRp = tr(R · spq) with spq[i,j] = Σw p_i q_j). Matches
    :func:`rmse` of the transformed pairs to fp32 accumulation tolerance.
    """
    R = T_delta[:3, :3].astype(jnp.float32)
    t = T_delta[:3, 3].astype(jnp.float32)
    hi = "highest"
    total = (spp + sqq + sw * jnp.dot(t, t, precision=hi)
             + 2.0 * jnp.dot(t, jnp.matmul(R, sp, precision=hi), precision=hi)
             - 2.0 * jnp.trace(jnp.matmul(R, spq, precision=hi))
             - 2.0 * jnp.dot(t, sq, precision=hi))
    return jnp.sqrt(jnp.maximum(total, 0.0) / jnp.maximum(sw, 1e-12))
