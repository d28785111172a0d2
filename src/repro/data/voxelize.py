"""Voxel-grid hashing: jit-compatible spatial structure for point clouds.

Two structures, both fully static-shape so they compose with jit / vmap /
the shape-bucket collator (DESIGN.md §8):

  * :func:`voxel_downsample` — centroid reduction over occupied voxels.
    Cells are compacted by a sort + first-occurrence cumsum (no dense cell
    table needed), centroids accumulate via ``segment_sum``, and the output
    has a fixed ``max_points`` capacity with a validity mask — the same
    masking convention as ``repro.data.collate`` (invalid rows carry the
    far ``PAD_SENTINEL`` so even mask-unaware consumers stay correct).

  * :func:`build_voxel_grid` — a sorted-cell-id index over the cloud plus a
    dense per-cell (start, count) table, the classic GPU "counting sort"
    grid. Cell ids linearize a static ``dims`` lattice anchored at a
    per-cloud origin; the table supports O(1) lookup of any cell's point
    range, which is what the 27-neighbourhood gather in
    ``repro.core.nn_search_grid`` consumes.

Static-capacity semantics (everything here is a *bounded* structure):

  * ``voxel_downsample`` drops occupied cells beyond ``max_points``
    (deterministically, in cell-id sort order) — callers size the capacity
    for their scene, and the validity mask reports the real occupancy.
  * ``build_voxel_grid`` stores every valid point; capacity truncation
    happens at *query* time (``max_per_cell`` in the searcher), not here.
  * *Stored* points outside the ``dims`` lattice clip into the boundary
    cells (their coordinates stay exact, so distances computed from them
    are still right; only their neighbourhood membership degrades — size
    ``dims`` to the scene). *Queries* are different: the grid searcher
    resolves them with ``cell_coords(..., clip=False)`` so an
    out-of-lattice query sees an (honest) empty neighbourhood and is
    reported / brute-falled-back, never silently matched through a
    boundary cell it does not belong to. The rolling submap
    (``repro.data.submap``) re-anchors its origin so streaming queries
    stay inside the lattice in the first place.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.data.collate import PAD_SENTINEL


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class VoxelGrid:
    """Sorted counting-sort grid over one point cloud.

    ``points``/``point_ids`` are the cloud reordered by linearized cell id
    (invalid/padded rows sort to the tail and are unreachable through the
    table); ``start``/``count`` are dense per-cell tables of length
    ``prod(dims)``. ``dims`` is static (pytree aux data) so a VoxelGrid can
    cross jit boundaries without retracing on metadata.
    """

    points: jax.Array      # (M, 3) f32, sorted by cell id
    point_ids: jax.Array   # (M,) i32 — original row of each sorted point
    start: jax.Array       # (C,) i32 — first sorted row of each cell
    count: jax.Array       # (C,) i32 — valid points in each cell
    origin: jax.Array      # (3,) f32 — lattice anchor (cell [0,0,0] corner)
    voxel_size: jax.Array  # scalar f32
    dims: tuple[int, int, int]  # static lattice extent (nx, ny, nz)

    def tree_flatten(self):
        return ((self.points, self.point_ids, self.start, self.count,
                 self.origin, self.voxel_size), self.dims)

    @classmethod
    def tree_unflatten(cls, dims, leaves):
        return cls(*leaves, dims=dims)

    @property
    def num_cells(self) -> int:
        nx, ny, nz = self.dims
        return nx * ny * nz


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class VoxelMap:
    """A target cloud whose points sit on a voxel lattice: at most
    ``max_per_cell`` of them in each cell of the ``dims`` lattice of
    ``voxel_size`` cells anchored at ``origin`` (a map of
    ``repro.data.submap`` holds one centroid a cell). The slot engines
    search such a target through its lattice; other engines register onto
    ``points`` as onto any cloud.

    ``points`` ``(M, 3)`` and ``origin`` ``(3,)`` are the leaves, with a
    leading lane axis in a batch; the rest is static (pytree aux data),
    so a map crosses jit and vmap boundaries like :class:`VoxelGrid`."""

    points: jax.Array
    origin: jax.Array
    voxel_size: float
    dims: tuple[int, int, int]
    max_per_cell: int

    def tree_flatten(self):
        return ((self.points, self.origin),
                (self.voxel_size, self.dims, self.max_per_cell))

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves, *aux)


def cell_coords(points: jax.Array, origin: jax.Array, voxel_size,
                dims: tuple[int, int, int], *, clip: bool = True) -> jax.Array:
    """(…,3) points -> (…,3) int32 lattice coords.

    With ``clip=True`` (the build-time convention) coordinates clip into
    ``dims``. ``clip=False`` keeps the true (possibly out-of-range) coords
    so query-side consumers can *detect* out-of-lattice points instead of
    silently treating them as boundary-cell residents — the searcher bug
    this distinction fixes (see ``repro.core.nn_search_grid``). The float
    coordinate is pre-clamped to the int32-safe range so far sentinels
    (±1e6 pads, 1e15 mask coords) stay finite, ordinary out-of-range ints.
    """
    ic_f = jnp.floor((points - origin) / voxel_size)
    ic = jnp.clip(ic_f, -2.0 ** 30, 2.0 ** 30).astype(jnp.int32)
    if clip:
        ic = jnp.clip(ic, 0, jnp.asarray(dims, jnp.int32) - 1)
    return ic


def linear_cell_ids(ic: jax.Array, dims: tuple[int, int, int]) -> jax.Array:
    """(…,3) lattice coords -> (…,) linearized ids (row-major, z fastest)."""
    _, ny, nz = dims
    return (ic[..., 0] * ny + ic[..., 1]) * nz + ic[..., 2]


def _masked_min(points: jax.Array, valid: jax.Array | None) -> jax.Array:
    """(M,3) min over valid rows; +inf rows never win."""
    if valid is None:
        return jnp.min(points, axis=0)
    big = jnp.asarray(jnp.inf, points.dtype)
    return jnp.min(jnp.where(valid[:, None], points, big), axis=0)


def _default_origin(points, valid, voxel_size):
    """Snap the valid-point minimum down to the voxel lattice, with half a
    voxel of slack so boundary points never land at a negative coord."""
    v = jnp.asarray(voxel_size, points.dtype)
    lo = _masked_min(points, valid) - 0.5 * v
    return jnp.floor(lo / v) * v


def voxel_downsample(points: jax.Array, voxel_size, *,
                     max_points: int,
                     valid: jax.Array | None = None,
                     origin: jax.Array | None = None,
                     with_stats: bool = False):
    """Centroid voxel downsample with static output capacity.

    Args:
      points: (M, 3) cloud.
      voxel_size: cell edge length (metres); may be traced.
      max_points: static output capacity. Occupied cells beyond it are
        dropped deterministically (highest cell ids first — the sort tail),
        so an undersized capacity degrades to a subsample, never an error.
      valid: optional (M,) bool — padded rows (``repro.data.collate``) are
        excluded from every centroid.
      origin: optional (3,) lattice anchor; default snaps the valid min to
        the voxel lattice.
      with_stats: also return the number of occupied cells that did NOT
        fit the capacity — the saturation signal a bare validity mask
        cannot express (a full mask reads the same whether the budget
        exactly fit or silently truncated).

    Returns:
      (centroids, out_valid): ((max_points, 3) f32, (max_points,) bool).
      Invalid output rows carry ``PAD_SENTINEL`` coordinates, matching the
      collator's convention, so downstream searchers need no special cases.
      With ``with_stats=True``, ``(centroids, out_valid, dropped)`` where
      ``dropped`` is an int32 scalar (0 when every occupied cell fit).
    """
    m = points.shape[0]
    cap = min(int(max_points), m)
    v = jnp.asarray(voxel_size, jnp.float32)
    pts = points.astype(jnp.float32)
    if origin is None:
        origin = _default_origin(pts, valid, v)
    ic = jnp.floor((pts - origin) / v).astype(jnp.int32)
    if valid is not None:
        # Push padded rows past every real cell so they sort to the tail.
        ic = jnp.where(valid[:, None], ic, jnp.int32(2 ** 30))
    # lexsort: last key is primary -> (x, y, z) major-to-minor cell order.
    order = jnp.lexsort((ic[:, 2], ic[:, 1], ic[:, 0]))
    ics = ic[order]
    ps = pts[order]
    vs = (valid[order] if valid is not None
          else jnp.ones((m,), dtype=bool))
    prev = jnp.roll(ics, 1, axis=0)
    new_cell = jnp.any(ics != prev, axis=-1).at[0].set(True)
    seg = jnp.cumsum(new_cell.astype(jnp.int32)) - 1      # compacted cell idx
    # Occupied-cell count BEFORE the capacity scatter drops the overflow:
    # valid rows carry compacted indices 0..occupied-1 (invalid rows sit in
    # tail cells of their own and are masked out here).
    occupied = jnp.max(jnp.where(vs, seg, -1)) + 1
    dropped = jnp.maximum(occupied - cap, 0).astype(jnp.int32)
    # Invalid rows (and overflow cells) scatter out of range -> dropped.
    seg = jnp.where(vs, seg, cap)
    ones = vs.astype(jnp.float32)
    sums = jax.ops.segment_sum(ps * ones[:, None], seg, num_segments=cap)
    cnt = jax.ops.segment_sum(ones, seg, num_segments=cap)
    out_valid = cnt > 0
    centroids = sums / jnp.maximum(cnt, 1.0)[:, None]
    centroids = jnp.where(out_valid[:, None], centroids,
                          jnp.asarray(PAD_SENTINEL, jnp.float32))
    if cap < int(max_points):  # honour the requested static capacity
        pad = int(max_points) - cap
        centroids = jnp.concatenate(
            [centroids, jnp.full((pad, 3), PAD_SENTINEL, jnp.float32)])
        out_valid = jnp.concatenate([out_valid, jnp.zeros((pad,), bool)])
    if with_stats:
        return centroids, out_valid, dropped
    return centroids, out_valid


def build_voxel_grid(points: jax.Array, voxel_size,
                     dims: tuple[int, int, int], *,
                     valid: jax.Array | None = None,
                     origin: jax.Array | None = None) -> VoxelGrid:
    """Counting-sort voxel grid over ``points`` (the once-per-frame build).

    ``dims`` is static (it sizes the dense tables); ``origin`` defaults to
    the valid-point minimum snapped to the lattice, so a ``dims`` lattice of
    ``dims * voxel_size`` metres anchored at the cloud covers the scene.
    Invalid rows are excluded from the tables entirely — they can never be
    returned as candidates.
    """
    nx, ny, nz = dims
    num_cells = nx * ny * nz
    v = jnp.asarray(voxel_size, jnp.float32)
    pts = points.astype(jnp.float32)
    if origin is None:
        origin = _default_origin(pts, valid, v)
    ids = linear_cell_ids(cell_coords(pts, origin, v, dims), dims)
    if valid is not None:
        ids = jnp.where(valid, ids, num_cells)  # tail id: dropped below
        ones = valid.astype(jnp.int32)
    else:
        ones = jnp.ones(ids.shape, jnp.int32)
    order = jnp.argsort(ids)  # stable: within-cell order = original order
    count = jax.ops.segment_sum(ones, ids, num_segments=num_cells)
    start = jnp.concatenate(
        [jnp.zeros((1,), count.dtype), jnp.cumsum(count)[:-1]])
    return VoxelGrid(points=pts[order], point_ids=order.astype(jnp.int32),
                     start=start.astype(jnp.int32),
                     count=count.astype(jnp.int32),
                     origin=origin.astype(jnp.float32), voxel_size=v,
                     dims=(nx, ny, nz))
