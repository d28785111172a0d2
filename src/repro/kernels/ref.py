"""Pure-jnp oracles for the FPPS NN-search kernel.

The kernel scores candidates through the augmented expansion (see
nn_search.py for the derivation):

    score[i,j] = src_aug[:, i] · dst_aug[:, j] = ||R p_i + t - q_j||²

``nn_search_ref`` builds the same augmented matrices and takes the full
(N, M) product at once — no tiling, no running reduction — so any kernel
bug in tiling/carry/index bookkeeping diverges from it.
``nn_search_ref_blocked`` replays the kernel's own elementwise arithmetic
(``kernel_scores``) and tie order, so its indices match the kernel's
exactly.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

AUG_ROWS = 8  # fp32 sublane-aligned augmentation height


def augment_target(dst: jax.Array, pad_to: int | None = None) -> jax.Array:
    """(M,3) -> (8, M') constant target augmentation.

    rows 0..2 = -2 q, row 3 = ||q||², row 4 = 1, rows 5..7 = 0.
    Padded columns get row3 = +BIG so they can never win the argmin.
    """
    m = dst.shape[0]
    mp = m if pad_to is None else pad_to
    assert mp >= m
    q = dst.astype(jnp.float32)
    out = jnp.zeros((AUG_ROWS, mp), dtype=jnp.float32)
    out = out.at[0:3, :m].set(-2.0 * q.T)
    out = out.at[3, :m].set(jnp.sum(q * q, axis=-1))
    out = out.at[4, :].set(1.0)
    if mp > m:
        out = out.at[3, m:].set(jnp.float32(1e30))
    return out


def augment_source(src: jax.Array, T: jax.Array | None = None,
                   pad_to: int | None = None) -> jax.Array:
    """(N,3) [+ 4x4 T] -> (8, N') transformed source augmentation.

    p' = R p + t (the paper's point-cloud-transformer stage, folded in);
    rows 0..2 = p', row 3 = 1, row 4 = ||p'||², rows 5..7 = 0.
    """
    n = src.shape[0]
    np_ = n if pad_to is None else pad_to
    assert np_ >= n
    p = src.astype(jnp.float32)
    if T is not None:
        p = (jnp.matmul(p, T[:3, :3].T.astype(jnp.float32), precision="highest")
             + T[:3, 3].astype(jnp.float32))
    out = jnp.zeros((AUG_ROWS, np_), dtype=jnp.float32)
    out = out.at[0:3, :n].set(p.T)
    out = out.at[3, :n].set(1.0)
    out = out.at[4, :n].set(jnp.sum(p * p, axis=-1))
    return out


def nn_search_ref(src: jax.Array, dst: jax.Array,
                  T: jax.Array | None = None):
    """Oracle: exact NN via the full augmented score matrix.

    Returns (d2, idx): (N,) squared distance of (transformed) src point to
    its NN in dst, and the NN's index. Ties resolve to the lowest index
    (same as the kernel's strict-< block carry).
    """
    src_aug = augment_source(src, T)
    dst_aug = augment_target(dst)
    scores = jax.lax.dot_general(
        src_aug, dst_aug, (((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)            # (N, M)
    idx = jnp.argmin(scores, axis=1).astype(jnp.int32)
    d2 = jnp.take_along_axis(scores, idx[:, None], axis=1)[:, 0]
    return jnp.maximum(d2, 0.0), idx


@jax.jit
def kernel_scores(src_aug: jax.Array, dst_aug: jax.Array) -> jax.Array:
    """(N, M) scores as the kernel ranks them: ``‖q‖² - 2 p'·q``, the
    expansion less the query's own ``‖p'‖²``, elementwise in float32 and
    in the kernel's order of operations. Jitted, so that the CPU backend
    contracts its multiply-adds as it does in the interpreted kernel."""
    p = src_aug[:3, :, None]
    t = dst_aug[:4, None, :]
    return ((p[0] * t[0] + p[1] * t[1]) + p[2] * t[2]) + t[3]


def nn_search_ref_blocked(src, dst, T, bn: int, bm: int):
    """Oracle that replays the kernel's arithmetic (``kernel_scores``), its
    padding to bn/bm and its tie order (the smallest index wins) in pure
    jnp — isolates pure Pallas issues (BlockSpec, carries, program_id)
    from math issues."""
    n, m = src.shape[0], dst.shape[0]
    src_aug = augment_source(src, T, pad_to=n + (-n % bn))
    dst_aug = augment_target(dst, pad_to=m + (-m % bm))
    scores = kernel_scores(src_aug, dst_aug)
    idx = jnp.argmin(scores, axis=1).astype(jnp.int32)
    best = jnp.take_along_axis(scores, idx[:, None], 1)[:, 0] + src_aug[4]
    return jnp.maximum(best[:n], 0.0), idx[:n]
