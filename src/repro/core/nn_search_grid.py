"""Grid-bucketed nearest-neighbour search (DESIGN.md §8).

The brute-force sweep (``repro.core.nn_search``) scans all M target points
per query; this module scans only the **27-neighbourhood** of the query's
voxel — a bounded candidate set gathered through the counting-sort tables
of :class:`repro.data.voxelize.VoxelGrid`. With ``K = max_per_cell`` the
per-query cost drops from O(M) to O(27·K), and everything stays
static-shape/dense so it vectorizes exactly like the brute sweep.

Exactness contract (the one the tests pin down):

  * If the query's true nearest neighbour lies within ``voxel_size`` of it
    and its cell did not overflow ``max_per_cell``, grid NN returns the
    *identical* (d2, idx) as the exact searcher: a point within one voxel
    length is necessarily inside the 3x3x3 neighbourhood.
  * In ICP terms: choose ``voxel_size >= max_correspondence_distance`` and
    every correspondence that can pass the gate is found exactly; pairs the
    grid misses are pairs the gate would reject anyway, so they carry zero
    Kabsch weight either way.
  * Overflowing cells truncate to their first ``max_per_cell`` points (in
    stable original order) — the returned neighbour is then still inside
    the same cell, i.e. within one cell diagonal of the true one.

Queries with an *empty* neighbourhood get ``d2 = +inf`` (gated out of ICP),
or — with ``exact_fallback=True`` — a brute-force answer computed lazily
via ``lax.cond`` only when at least one such row exists. Queries *outside*
the ``dims`` lattice resolve through the same path: their cell coords are
kept unclipped (``cell_coords(..., clip=False)``), so only lattice cells
their neighbourhood window genuinely overlaps contribute candidates — a
query more than ``rings`` cells past the lattice edge reports an empty
hood (counted by ``GridQueryStats.out_of_lattice``) instead of being
silently matched against boundary-cell residents. The fallback is
meant for standalone/query use; inside vmapped ICP both branches of a cond
execute, so the pyramid engine relies on the gate semantics instead.

Distances are computed directly as ``sum((p - q)²)`` — the candidate tile
is too narrow for the matmul expansion to pay off, and the direct form is
exact (no cancellation), so no epilogue recompute is needed.
"""
from __future__ import annotations

import functools
import itertools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.data.voxelize import VoxelGrid, cell_coords, linear_cell_ids


class GridQueryStats(NamedTuple):
    """Diagnostics of one candidate-gather pass (all scalars, jittable).

    ``overflow_frac``: fraction of queries with at least one in-bounds
    neighbour cell truncated by ``max_per_cell`` — the silent-drop case the
    exactness contract documents. ``empty_frac``: fraction of queries with
    an empty neighbourhood (the rows that come back ``d2 = inf``).
    ``dropped_frac``: truncated candidates as a fraction of all candidates
    the neighbourhoods actually hold — how much of the scene the sweep
    never saw. ``out_of_lattice``: fraction of queries whose own cell lies
    outside the ``dims`` lattice entirely — the moving-ego failure mode
    (ISSUE 5): such rows used to clip into boundary cells and return
    confidently-wrong neighbours; they now resolve to the empty-hood path
    and this counter makes the miss observable per frame.
    """

    overflow_frac: jax.Array
    empty_frac: jax.Array
    dropped_frac: jax.Array
    out_of_lattice: jax.Array


@functools.lru_cache(maxsize=None)
def _neighbor_offsets(rings: int) -> tuple:
    """Static (2r+1)³ neighbourhood offsets; rings=1 is the 27-cell case."""
    span = range(-rings, rings + 1)
    return tuple(itertools.product(span, span, span))

# Far-but-finite coordinate for masked candidate slots: d2 ~ 1e30 stays
# inside fp32 and never wins against any metric-scale candidate (same
# reasoning as the collate/nn_search sentinels — no inf, no NaN path).
_MASK_COORD = 1.0e15


def _candidate_slots(src: jax.Array, grid: VoxelGrid, max_per_cell: int,
                     rings: int):
    """Rows of ``grid.points`` holding each query's candidates, and which
    of the ``(N, C*K)`` slots are real: see :func:`gather_candidates`."""
    dims = grid.dims
    # clip=False: a query outside the lattice keeps its true out-of-range
    # cell, so its neighbourhood window only picks up lattice cells it
    # *geometrically* overlaps (none, once it is > rings cells away). The
    # old clipped coords teleported far queries into boundary cells and
    # returned their residents as confident neighbours.
    icq = cell_coords(src, grid.origin, grid.voxel_size, dims,
                      clip=False)                                # (N, 3)
    off = jnp.asarray(_neighbor_offsets(rings), jnp.int32)       # (C, 3)
    nbr = icq[:, None, :] + off[None]                            # (N, 27, 3)
    in_bounds = jnp.all(
        (nbr >= 0) & (nbr < jnp.asarray(dims, jnp.int32)), axis=-1)
    cid = linear_cell_ids(jnp.clip(nbr, 0, jnp.asarray(dims, jnp.int32) - 1),
                          dims)                                  # (N, 27)
    start = grid.start[cid]
    cnt = jnp.where(in_bounds, jnp.minimum(grid.count[cid], max_per_cell), 0)
    k = jnp.arange(max_per_cell, dtype=jnp.int32)
    pos = start[..., None] + k                                   # (N, 27, K)
    cand_valid = k < cnt[..., None]
    pos = jnp.where(cand_valid, pos, 0)
    n = src.shape[0]
    ck = off.shape[0] * max_per_cell
    pos = pos.reshape(n, ck)
    cand_valid = cand_valid.reshape(n, ck)
    return pos, cand_valid


def gather_candidates(src: jax.Array, grid: VoxelGrid, max_per_cell: int,
                      rings: int = 1):
    """Gather each query's (2·rings+1)³-neighbourhood candidate set.

    Returns ``(cand_pts, cand_idx, cand_valid)`` with shapes
    ((N, C*K, 3), (N, C*K), (N, C*K)) for C = (2·rings+1)³; masked slots
    carry far-sentinel coordinates so consumers may skip the mask in the
    distance argmin. ``cand_idx`` is in the *original* target ordering.
    ``rings`` trades cell occupancy against neighbourhood width: the
    guaranteed-exact radius is ``rings * voxel_size``, so rings=2 with a
    half-size voxel covers the same radius with ~4x fewer points per cell
    (useful against ``max_per_cell`` overflow on dense surfaces).
    """
    pos, cand_valid = _candidate_slots(src, grid, max_per_cell, rings)
    cand_pts = jnp.where(cand_valid[..., None], grid.points[pos],
                         jnp.asarray(_MASK_COORD, jnp.float32))
    return cand_pts, grid.point_ids[pos], cand_valid


def gather_candidate_planes(src: jax.Array, grid: VoxelGrid,
                            max_per_cell: int, rings: int = 1):
    """:func:`gather_candidates` with the coordinates as three ``(N, C*K)``
    planes. On a TPU an ``(N, C*K, 3)`` array is laid out with its 3-wide
    minor dimension padded to 128 lanes, 40x its size; at the paper's
    scan size that does not fit the chip."""
    pos, cand_valid = _candidate_slots(src, grid, max_per_cell, rings)
    mask = jnp.asarray(_MASK_COORD, jnp.float32)
    planes = tuple(jnp.where(cand_valid, grid.points[:, a][pos], mask)
                   for a in range(3))
    return planes, grid.point_ids[pos], cand_valid


def neighborhood_stats(src: jax.Array, grid: VoxelGrid,
                       max_per_cell: int = 32,
                       rings: int = 1) -> GridQueryStats:
    """Quantify what :func:`gather_candidates` would drop for these queries.

    Pure table lookups on the grid's per-cell counts — no candidate gather,
    so it is cheap enough to run per frame as a quality signal (the pyramid
    engine exposes it as :meth:`~repro.core.pyramid.PyramidEngine.polish_stats`).
    """
    dims = grid.dims
    dims_arr = jnp.asarray(dims, jnp.int32)
    icq = cell_coords(src, grid.origin, grid.voxel_size, dims, clip=False)
    off = jnp.asarray(_neighbor_offsets(rings), jnp.int32)
    nbr = icq[:, None, :] + off[None]
    in_bounds = jnp.all((nbr >= 0) & (nbr < dims_arr), axis=-1)
    cid = linear_cell_ids(jnp.clip(nbr, 0, dims_arr - 1), dims)
    cnt = jnp.where(in_bounds, grid.count[cid], 0)               # (N, C)
    kept = jnp.minimum(cnt, max_per_cell)
    dropped = jnp.sum(cnt - kept, axis=1).astype(jnp.float32)    # (N,)
    total = jnp.sum(cnt, axis=1).astype(jnp.float32)
    n = jnp.asarray(src.shape[0], jnp.float32)
    in_lattice = jnp.all((icq >= 0) & (icq < dims_arr), axis=-1)
    return GridQueryStats(
        overflow_frac=jnp.sum(jnp.any(cnt > max_per_cell, axis=1)) / n,
        empty_frac=jnp.sum(jnp.sum(kept, axis=1) == 0) / n,
        dropped_frac=jnp.sum(dropped) / jnp.maximum(jnp.sum(total), 1.0),
        out_of_lattice=jnp.sum(jnp.logical_not(in_lattice)) / n)


def nn_search_grid(src: jax.Array, grid: VoxelGrid, *,
                   max_per_cell: int = 32,
                   rings: int = 1,
                   exact_fallback: bool = False,
                   dst: jax.Array | None = None,
                   dst_valid: jax.Array | None = None,
                   chunk: int = 2048,
                   return_points: bool = False,
                   with_stats: bool = False):
    """NN of each src point among its grid neighbourhood candidates.

    Args:
      src: (N, 3) queries.
      grid: the target's :func:`build_voxel_grid` result (built once per
        frame — the spatial analogue of the Pallas resident target).
      max_per_cell: static per-cell candidate capacity (K). C*K is the
        whole per-query sweep (C = 27 for rings=1).
      rings: neighbourhood half-width in cells; exact radius is
        ``rings * voxel_size`` (see :func:`gather_candidates`).
      exact_fallback: brute-force rows whose neighbourhood is empty (needs
        ``dst``; runs under ``lax.cond`` so the full sweep only executes
        when such a row exists).
      dst / dst_valid / chunk: fallback inputs, matching ``nn_search``.
      return_points: additionally return the matched points (fused winner
        gather — see ``core.icp._default_correspond_fn``).
      with_stats: additionally return a :class:`GridQueryStats` — the
        overflow/empty/dropped diagnostics that were previously invisible
        (inf rows and truncated cells fail silently otherwise).

    Returns:
      (d2, idx[, matched][, stats]): exact squared distances (``+inf`` for
      empty neighbourhoods without fallback), int32 indices into the
      original target ordering, optionally the (N, 3) matched points, and
      optionally the gather diagnostics.
    """
    cand_pts, cand_idx, cand_valid = gather_candidates(src, grid,
                                                       max_per_cell, rings)
    srcf = src.astype(jnp.float32)
    diff = srcf[:, None, :] - cand_pts
    d2 = jnp.sum(diff * diff, axis=-1)                           # (N, 27K)
    slot = jnp.argmin(d2, axis=1)
    rows = jnp.arange(src.shape[0])
    best_d2 = d2[rows, slot]
    best_idx = cand_idx[rows, slot]
    matched = cand_pts[rows, slot]
    has_cand = jnp.any(cand_valid, axis=1)
    best_d2 = jnp.where(has_cand, best_d2, jnp.inf)
    best_idx = jnp.where(has_cand, best_idx, 0)

    if exact_fallback:
        if dst is None:
            raise ValueError("exact_fallback=True requires dst")
        from repro.core.nn_search import nn_search

        def brute(_):
            d2_b, idx_b, pts_b = nn_search(srcf, dst, chunk=chunk,
                                           dst_valid=dst_valid,
                                           return_points=True)
            # both cond branches must agree on dtype; the grid path's
            # candidate points are always f32
            return d2_b, idx_b, pts_b.astype(jnp.float32)

        def keep(_):
            return best_d2, best_idx, matched

        fb_d2, fb_idx, fb_pts = jax.lax.cond(
            jnp.any(~has_cand), brute, keep, operand=None)
        best_d2 = jnp.where(has_cand, best_d2, fb_d2)
        best_idx = jnp.where(has_cand, best_idx, fb_idx)
        matched = jnp.where(has_cand[:, None], matched, fb_pts)

    out = [jnp.maximum(best_d2, 0.0), best_idx]
    if return_points:
        out.append(matched)
    if with_stats:
        out.append(neighborhood_stats(src, grid, max_per_cell, rings))
    return tuple(out)


def grid_nn_fn(grid: VoxelGrid, *, max_per_cell: int = 32, rings: int = 1):
    """Resident-grid searcher with the ``core.icp`` ``nn_fn`` contract.

    Like ``kernels.ops.resident_nn_fn``, the expensive per-frame structure
    (here: the voxel grid) is closed over at trace scope, outside the ICP
    iteration loop; the returned closure ignores its second argument. It
    returns the fused 3-tuple so the hot loop does a single winner gather.
    """

    def nn_fn(src, _target=None):
        return nn_search_grid(src, grid, max_per_cell=max_per_cell,
                              rings=rings, return_points=True)

    return nn_fn
