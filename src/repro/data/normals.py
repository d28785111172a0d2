"""Per-point surface-normal estimation over voxel-grid neighbourhoods.

Point-to-plane ICP (DESIGN.md §9) needs a unit normal per *target* point.
This module estimates them the classic way — fit a plane to each point's
local neighbourhood and take the plane normal — but with the repo's
static-shape discipline so the whole thing jits, vmaps over frame batches,
and composes with the shape-bucket collator:

  * neighbourhoods come from the PR-2 counting-sort
    :class:`repro.data.voxelize.VoxelGrid` via
    :func:`repro.core.nn_search_grid.gather_candidates` — the same bounded
    (2·rings+1)³ candidate machinery the grid NN searcher uses, so the
    per-point cost is O(27·K), never O(M);
  * the local covariance is accumulated in *query-relative* coordinates
    (``x - p``), which kills the catastrophic cancellation a raw-moment
    accumulation would suffer at scene scale (coords ~50 m, covariances
    ~voxel² — six fp32 digits apart);
  * the smallest-eigenvalue direction comes from the custom-call-free 3×3
    Jacobi SVD (``repro.core.svd3x3``) — symmetric PSD input, so the last
    right-singular vector is the minimal-variance axis;
  * outputs follow the collate conventions: a fixed (N, 3) normal array
    plus an (N,) validity mask. Invalid rows (too few neighbours, padded
    input rows, degenerate neighbourhoods) carry **zero** normals, so even
    mask-unaware consumers are safe — a zero normal contributes nothing to
    the point-to-plane normal equations.

Two neighbourhood modes:

  * ``"knn"`` (default) — the k nearest candidates (PCL's
    ``setKSearch``), selected by ``lax.top_k`` over the candidate ring;
  * ``"radius"`` — every candidate within ``radius`` metres. This is the
    mode the Pallas moment-sweep kernel (``repro.kernels.normals``)
    implements, since a fixed gate streams; parity between the two
    implementations is pinned in ``tests/test_normals.py``.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.nn_search_grid import gather_candidates
from repro.core.svd3x3 import svd3x3
from repro.data.voxelize import VoxelGrid, build_voxel_grid

# Candidate slots whose d2 exceeds this are sentinel/masked slots (their
# coordinates sit at ~1e15; real scene distances are < 1e4 m²).
_SENTINEL_D2 = 1.0e12

# Default lattice: matches the pyramid's finest-level grid so a target
# frame can share one VoxelGrid between normal estimation and grid NN.
DEFAULT_GRID_DIMS: tuple[int, int, int] = (128, 128, 32)


class NormalParams(NamedTuple):
    """Static normal-estimation configuration (hashable — engines key their
    jit caches on it alongside ``ICPParams``)."""

    k: int = 16                    # neighbours per point ("knn" mode)
    radius: float = 1.0            # gate in metres ("radius" mode)
    neighborhood: str = "knn"      # "knn" | "radius"
    voxel_size: float = 1.0        # candidate-grid cell edge
    grid_dims: tuple[int, int, int] = DEFAULT_GRID_DIMS
    max_per_cell: int = 32         # candidate capacity per cell
    rings: int = 1                 # neighbourhood half-width in cells
    min_neighbors: int = 3         # plane fit needs >= 3 points
    chunk: int = 2048              # query rows processed per sweep


def accumulate_moments(rel: jax.Array, w: jax.Array):
    """Weighted moment sums of query-relative offsets.

    Args:
      rel: (..., C, 3) candidate offsets ``x_j - p`` for each query.
      w:   (..., C) weights (0/1 masks or robust weights).

    Returns:
      (cnt, s, ss): (...,) Σw, (..., 3) Σw·rel, (..., 3, 3) Σw·rel·relᵀ.
    """
    wf = w.astype(jnp.float32)
    relf = rel.astype(jnp.float32)
    cnt = jnp.sum(wf, axis=-1)
    s = jnp.sum(relf * wf[..., None], axis=-2)
    ss = jnp.einsum("...ci,...cj->...ij", relf * wf[..., None], relf,
                    precision="highest")
    return cnt, s, ss


def moments_to_normals(cnt: jax.Array, s: jax.Array, ss: jax.Array, *,
                       min_neighbors: int = 3):
    """Covariance eigen-decomposition: moment sums -> (normals, valid).

    The covariance ``E[rel·relᵀ] - mean·meanᵀ`` is shift-invariant, so the
    same epilogue serves both the XLA path and the Pallas moment kernel
    (which accumulates in query-relative coordinates). Invalid rows (fewer
    than ``min_neighbors`` samples, or a neighbourhood too degenerate to
    define a plane) return a **zero** normal.
    """
    denom = jnp.maximum(cnt, 1.0)
    mean = s / denom[..., None]
    cov = ss / denom[..., None, None] - mean[..., :, None] * mean[..., None, :]
    # Symmetrise fp roundoff; Jacobi assumes nothing but it keeps U ~ V.
    cov = 0.5 * (cov + jnp.swapaxes(cov, -1, -2))
    _, sing, Vt = jax.vmap(svd3x3)(cov.reshape(-1, 3, 3))
    sing = sing.reshape(cov.shape[:-2] + (3,))
    normal = Vt[:, 2, :].reshape(cov.shape[:-2] + (3,))
    norm = jnp.linalg.norm(normal, axis=-1, keepdims=True)
    normal = normal / jnp.maximum(norm, 1e-30)
    # A plane needs spread in two directions: the middle singular value of
    # a collinear (or empty) neighbourhood collapses to ~0. The threshold
    # is *relative* to the dominant spread — fp32 covariance roundoff
    # leaves an absolute floor of ~eps·σ₀² on σ₁ even for exact lines.
    valid = ((cnt >= min_neighbors)
             & (sing[..., 0] > 1e-12)
             & (sing[..., 1] > 1e-5 * sing[..., 0]))
    return jnp.where(valid[..., None], normal, 0.0), valid


def orient_normals(points: jax.Array, normals: jax.Array,
                   viewpoint: jax.Array | None = None) -> jax.Array:
    """Flip each normal toward ``viewpoint`` (default: the sensor origin).

    Scans are in the sensor frame here, so orienting toward the origin is
    PCL's ``flipNormalTowardsViewpoint`` with the default viewpoint.
    """
    if viewpoint is None:
        viewpoint = jnp.zeros((3,), points.dtype)
    to_vp = viewpoint - points
    flip = jnp.sum(normals * to_vp, axis=-1) < 0.0
    return jnp.where(flip[..., None], -normals, normals)


def _chunk_moments(points, grid: VoxelGrid, params: NormalParams):
    """Moment sums for every query row, swept ``params.chunk`` rows at a
    time so the (chunk, 27K, 3) candidate tile — not an (N, 27K, 3)
    monster — is the peak live buffer (the normals analogue of the brute
    searcher's target chunking)."""
    n = points.shape[0]
    chunk = min(params.chunk, n)
    pad = (-n) % chunk
    pts = jnp.concatenate(
        [points, jnp.full((pad, 3), 1e15, points.dtype)], axis=0)
    blocks = pts.reshape(-1, chunk, 3)

    def one_block(blk):
        cand_pts, _, cand_valid = gather_candidates(
            blk, grid, params.max_per_cell, params.rings)
        rel = cand_pts - blk[:, None, :].astype(jnp.float32)
        d2 = jnp.sum(rel * rel, axis=-1)
        if params.neighborhood == "knn":
            k = min(params.k, d2.shape[1])
            neg_d2, sel = jax.lax.top_k(-d2, k)
            w = (-neg_d2) < _SENTINEL_D2
            rel_sel = jnp.take_along_axis(rel, sel[..., None], axis=1)
        elif params.neighborhood == "radius":
            w = cand_valid & (d2 <= params.radius ** 2)
            rel_sel = rel
        else:
            raise ValueError(
                f"unknown neighborhood {params.neighborhood!r}; "
                f"expected 'knn' or 'radius'")
        return accumulate_moments(rel_sel, w)

    cnt, s, ss = jax.lax.map(one_block, blocks)
    return (cnt.reshape(-1)[:n], s.reshape(-1, 3)[:n],
            ss.reshape(-1, 3, 3)[:n])


def estimate_normals(points: jax.Array,
                     params: NormalParams = NormalParams(), *,
                     valid: jax.Array | None = None,
                     viewpoint: jax.Array | None = None,
                     grid: VoxelGrid | None = None):
    """Estimate a unit normal per point of one (N, 3) cloud.

    Args:
      points: (N, 3) cloud (tolerates collate padding when ``valid`` marks
        it — padded rows get zero normals and ``False`` validity).
      params: static :class:`NormalParams`.
      valid: optional (N,) mask of real rows.
      viewpoint: (3,) orientation viewpoint; default sensor origin.
      grid: optional pre-built :class:`VoxelGrid` over ``points`` (reuse
        the pyramid's resident grid); built here when absent.

    Returns:
      (normals, normal_valid): ((N, 3) f32 unit normals — zero rows where
      invalid — and the (N,) bool mask).
    """
    pts = points.astype(jnp.float32)
    if grid is None:
        grid = build_voxel_grid(pts, params.voxel_size, params.grid_dims,
                                valid=valid)
    cnt, s, ss = _chunk_moments(pts, grid, params)
    normals, nvalid = moments_to_normals(cnt, s, ss,
                                         min_neighbors=params.min_neighbors)
    normals = orient_normals(pts, normals, viewpoint)
    if valid is not None:
        nvalid = nvalid & valid
        normals = jnp.where(nvalid[..., None], normals, 0.0)
    return normals, nvalid


def default_target_normals(target: jax.Array,
                           valid: jax.Array | None = None) -> jax.Array:
    """Trace-scope target normals with the default config — the shared
    entry point for every ICP path that auto-estimates when the plane
    minimiser is selected without explicit normals (``core.icp`` and the
    engines; the pyramid uses its own grid-matched params instead).

    Must run on the *true* cloud with its *true* valid mask, before any
    sentinel-masking of padded rows — sentinel rows at 1e6 m would
    otherwise pollute boundary-cell neighbourhoods in the grid.
    """
    normals, _ = estimate_normals(target, NormalParams(), valid=valid)
    return normals


def estimate_normals_batch(points: jax.Array,
                           params: NormalParams = NormalParams(), *,
                           valid: jax.Array | None = None,
                           viewpoint: jax.Array | None = None):
    """vmap of :func:`estimate_normals` over a (B, N, 3) frame batch."""

    def one(pts, v):
        return estimate_normals(pts, params, valid=v, viewpoint=viewpoint)

    if valid is None:
        valid = jnp.ones(points.shape[:2], dtype=bool)
    return jax.vmap(one)(points, valid)
