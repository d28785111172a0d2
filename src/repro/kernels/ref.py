"""Pure-jnp oracles for the FPPS NN-search kernel.

The kernel computes squared distances through an *augmented inner product*
(see nn_search.py for the derivation):

    score[i,j] = src_aug[:, i] · dst_aug[:, j] = ||R p_i + t - q_j||²

The oracle builds the same augmented matrices and takes the full (N, M)
product at once — no tiling, no running reduction — so any kernel bug in
tiling/carry/index bookkeeping diverges from it.
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


def nn_search_ref_blocked(src, dst, T, bn: int, bm: int):
    """Oracle with the kernel's exact blocking/carry semantics (padding, block
    argmin, strict-< cross-block update) but in pure jnp — isolates pure
    Pallas issues (BlockSpec, revisiting, program_id) from math issues."""
    n, m = src.shape[0], dst.shape[0]
    n_pad, m_pad = -n % bn, -m % bm
    src_aug = augment_source(src, T, pad_to=n + n_pad)
    dst_aug = augment_target(dst, pad_to=m + m_pad)
    best_d2 = jnp.full((n + n_pad,), jnp.inf, jnp.float32)
    best_idx = jnp.zeros((n + n_pad,), jnp.int32)
    for j in range((m + m_pad) // bm):
        dblk = dst_aug[:, j * bm:(j + 1) * bm]
        scores = jax.lax.dot_general(src_aug, dblk, (((0,), (0,)), ((), ())),
                                     precision=jax.lax.Precision.HIGHEST,
                                     preferred_element_type=jnp.float32)
        larg = jnp.argmin(scores, axis=1).astype(jnp.int32)
        lmin = jnp.take_along_axis(scores, larg[:, None], 1)[:, 0]
        upd = lmin < best_d2
        best_d2 = jnp.where(upd, lmin, best_d2)
        best_idx = jnp.where(upd, j * bm + larg, best_idx)
    return jnp.maximum(best_d2[:n], 0.0), best_idx[:n]
