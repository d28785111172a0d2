"""Rolling local voxel submap for streaming scan-to-map odometry.

Frame-to-frame odometry chains per-pair errors into an unbounded random
walk; the classic fix (and the regime the paper's KITTI numbers live in)
is registering each scan against a persistent *local map*. This module is
that map, built from the repo's own static-shape primitives:

  * **insert** — each registered scan is fused into the map by one
    ``voxel_downsample`` pass over ``concat(map, scan)``: per occupied
    voxel the centroid of old map points and new scan points, i.e. the
    map both *grows* (new cells) and *refines* (revisited cells average
    across frames, beating single-scan sensor noise). Capacity is static
    (``SubmapParams.capacity`` rows + validity mask, collate sentinel
    conventions), so the fuse is one jitted executable for the whole
    stream.
  * **eviction** — cells farther than ``evict_radius`` from the current
    ego position drop out of the fuse, bounding memory to the local
    neighbourhood exactly like the paper's on-chip target residency
    bounds the NN search space.
  * **re-anchoring** — the lattice origin snaps to the voxel grid centred
    on the current ego position every insert. This is what makes the
    out-of-lattice fix (``cell_coords(..., clip=False)``) matter at
    system scale: queries from a moving ego stay *inside* ``dims``, so
    the grid searcher never has to fall back, while anything the ego
    outran is reported honestly instead of matched to a boundary cell.

**Storage modes** (``SubmapParams.storage``, DESIGN.md §14): the resident
cell payload is either

  * ``"fp32"`` — world-frame points + a bool validity mask (the seed
    layout, byte-for-byte: 13 B per cell row), or
  * ``"fp16"`` — half-precision offsets *relative to the fp32 lattice
    origin*, with invalid rows parked at +inf so validity derives from
    ``isfinite`` and no separate mask is stored: 6 B per cell row,
    2.17x more resident submaps per byte. Offsets are guaranteed
    non-negative and bounded by ``dims * voxel_size`` (the fuse's
    in-lattice filter), so the half-precision quantization error is
    ≤ half an ulp at the far lattice edge (~1.6 cm at 64 m for the
    default lattice) — sensor-noise scale, averaged out further by the
    centroid fuse. Accumulation always runs in fp32: every fuse decodes
    to world-frame fp32, runs the exact fp32 fuse math, and re-encodes.

The state itself is a plain tuple of arrays (``empty_state`` /
``fuse_state`` / ``state_views``) so fleet-scale consumers — the sharded
registration service — can hold thousands of submaps as stacked,
device-sharded leaves without going through per-stream objects; the
:class:`Submap` class is the single-stream host-facing wrapper over the
same functions.

The map lives in map/world frame (frame 0 of the stream); callers
transform scans by their estimated pose before inserting
(``repro.core.odometry.OdometryPipeline`` does this per frame).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.data.collate import PAD_SENTINEL
from repro.data.voxelize import (VoxelGrid, VoxelMap, build_voxel_grid,
                                 voxel_downsample)

STORAGE_MODES = ("fp32", "fp16")


class SubmapParams(NamedTuple):
    """Static submap configuration (hashable: jit-cache friendly).

    ``dims * voxel_size`` is the lattice extent in metres — size it to
    cover the eviction sphere (``2 * evict_radius``) or the in-lattice
    filter will evict before the distance filter does. ``capacity`` is the
    static point budget; occupied voxels beyond it are dropped
    deterministically by ``voxel_downsample`` (the sticky
    ``Submap.dropped_cells`` counter reports it — a saturated budget no
    longer hides behind a healthy-looking 1.0 occupancy). ``storage``
    picks the resident payload layout (module docstring): ``"fp32"`` is
    the seed-exact layout, ``"fp16"`` the memory-lean one.
    """

    voxel_size: float = 0.5
    capacity: int = 16384
    dims: tuple[int, int, int] = (192, 192, 48)   # 96 m x 96 m x 24 m
    evict_radius: float = 45.0
    storage: str = "fp32"

    def voxel_map(self, points, origin) -> VoxelMap:
        """A map of these parameters as a registration target:
        ``points`` on the lattice anchored at ``origin`` (``(M, 3)`` and
        ``(3,)``, or with a leading lane axis). Every fuse keeps one
        centroid a cell. The fp16 offsets can round a centroid onto its
        cell's upper face, which is the next cell's: two points a cell
        then, as the fleet scenes' maps show (three or more would take a
        centroid within half an ulp of a cell edge)."""
        return VoxelMap(points=points, origin=origin,
                        voxel_size=float(self.voxel_size),
                        dims=tuple(self.dims),
                        max_per_cell=2 if self.storage == "fp16" else 1)


# -- functional state API (fleet-batchable) ---------------------------------
#
# A submap's device state is a tuple of arrays:
#   fp32: (points (cap,3) f32 world-frame, valid (cap,) bool, origin (3,))
#   fp16: (store  (cap,3) f16 origin-relative offsets,        origin (3,))
# The origin is always the LAST leaf; fp16 validity derives from isfinite
# on the stored offsets (+inf rows are the invalid sentinels). Every
# function here is jit-safe with ``params`` static, and vmaps cleanly —
# the sharded service stacks these leaves into (S, ...) fleet arrays.

def empty_state(params: SubmapParams) -> tuple:
    """The idle (no points) state tuple for ``params``."""
    cap = int(params.capacity)
    origin = jnp.zeros((3,), jnp.float32)
    if params.storage == "fp16":
        store = jnp.full((cap, 3), jnp.inf, jnp.float16)
        return store, origin
    points = jnp.full((cap, 3), PAD_SENTINEL, jnp.float32)
    valid = jnp.zeros((cap,), bool)
    return points, valid, origin


def state_views(state: tuple, params: SubmapParams):
    """Decode a state tuple to registration-target form:
    ``(points f32 world-frame, valid bool, origin)``. Invalid rows carry
    ``PAD_SENTINEL`` (collate conventions) in both modes. The fp32 mode
    returns its leaves untouched — zero device ops, bit-identity with the
    seed layout; fp16 decodes ``origin + offset`` in fp32."""
    if params.storage == "fp16":
        store, origin = state
        valid = jnp.isfinite(store[:, 0])
        points = jnp.where(valid[:, None],
                           origin + store.astype(jnp.float32),
                           jnp.asarray(PAD_SENTINEL, jnp.float32))
        return points, valid, origin
    points, valid, origin = state
    return points, valid, origin


def encode_state(points, valid, origin, params: SubmapParams) -> tuple:
    """Pack decoded ``(points, valid, origin)`` into the storage layout."""
    if params.storage == "fp16":
        store = jnp.where(valid[:, None], points - origin,
                          jnp.asarray(jnp.inf, jnp.float32))
        return store.astype(jnp.float16), origin
    return points, valid, origin


def _fuse_core(map_pts, map_valid, new_pts, new_valid, center,
               params: SubmapParams):
    """One insert+evict+re-anchor step on decoded fp32 state, fully
    static-shape. Returns ``(points, valid, origin, dropped_cells)`` at
    ``params.capacity`` rows — the exact seed fuse math plus the
    occupied-cell overflow count."""
    v = jnp.asarray(params.voxel_size, jnp.float32)
    dims = jnp.asarray(params.dims, jnp.float32)
    # Re-anchor: lattice centred on the ego, snapped to the voxel grid so
    # cell membership is stable across inserts that don't move far.
    origin = jnp.floor((center - 0.5 * dims * v) / v) * v
    pts = jnp.concatenate([map_pts, new_pts.astype(jnp.float32)], axis=0)
    valid = jnp.concatenate([map_valid, new_valid], axis=0)
    # Evict by distance from the ego (sentinel pad rows are far anyway)…
    d2 = jnp.sum((pts - center) ** 2, axis=-1)
    valid = valid & (d2 <= params.evict_radius ** 2)
    # …and drop anything outside the re-anchored lattice, so every stored
    # point has honest cell membership (no build-time boundary clipping).
    ic = jnp.floor((pts - origin) / v)
    valid = valid & jnp.all((ic >= 0) & (ic < dims), axis=-1)
    fused, fused_valid, dropped = voxel_downsample(
        pts, v, max_points=params.capacity, valid=valid, origin=origin,
        with_stats=True)
    return fused, fused_valid, origin, dropped


def fuse_state(state: tuple, new_pts, new_valid, center,
               params: SubmapParams):
    """Fuse a world-frame scan into a state tuple. Returns
    ``(state', occupied, dropped)`` — occupied is the post-fuse valid-cell
    count, dropped the occupied cells the capacity could not hold. The
    fuse math runs in fp32 in both storage modes (fp16 decodes first and
    re-encodes after), so the only fp16-vs-fp32 divergence is the stored
    offsets' quantization."""
    map_pts, map_valid, _ = state_views(state, params)
    fused, fused_valid, origin, dropped = _fuse_core(
        map_pts, map_valid, new_pts, new_valid, center, params)
    new_state = encode_state(fused, fused_valid, origin, params)
    return new_state, jnp.sum(fused_valid), dropped


@functools.partial(jax.jit, static_argnames=("params",))
def _fuse_state_jit(state, new_pts, new_valid, center,
                    params: SubmapParams):
    return fuse_state(state, new_pts, new_valid, center, params)


# Scan-level map stages, batched over lanes. The registration service runs
# them over its fleet, and a standalone pipeline runs the same executables
# with its frame at lane 0 of as many lanes (``lane0_batch``): a lane of a
# batched program does not depend on the other lanes, so the two agree bit
# for bit on any backend, where an eager or unbatched evaluation of the
# same math need not round the same way. (``core.transform`` is imported
# where used: ``repro.core`` imports this module.)

def lane0_batch(x, fill, lanes: int) -> jax.Array:
    """``x`` at lane 0 of a ``(lanes, ...)`` batch, ``fill`` (broadcast to
    ``x``'s shape) in every other lane."""
    x = jnp.asarray(x)
    first = (jnp.arange(lanes) == 0).reshape((lanes,) + (1,) * x.ndim)
    return jnp.where(first, x[None], jnp.asarray(fill, x.dtype))


def fuse_scan(state: tuple, scan, valid, pose, params: SubmapParams):
    """Fuse a sensor-frame ``scan`` taken at ``pose`` (4x4): the world-
    frame scan through :func:`fuse_state`, re-anchored on the pose's
    position. Returns ``(state', occupied, dropped)``."""
    from repro.core.transform import transform_points
    return fuse_state(state, transform_points(pose, scan), valid,
                      pose[:3, 3], params)


def outside_lattice_frac(pose, scan, valid, origin, params: SubmapParams):
    """Fraction of the valid rows of a sensor-frame ``scan``, placed at
    ``pose``, that fall outside the lattice anchored at ``origin`` — the
    low-overlap/teleport signal. A bounds check; no grid build."""
    from repro.core.transform import transform_points
    c = jnp.floor((transform_points(pose, scan) - origin)
                  / params.voxel_size)
    inb = jnp.all((c >= 0) & (c < jnp.asarray(params.dims, jnp.float32)),
                  axis=-1)
    n_valid = jnp.maximum(jnp.sum(valid), 1)
    return jnp.sum(jnp.logical_and(valid, ~inb)) / n_valid


def fuse_lane(state: tuple, scan, valid, pose, accept,
              params: SubmapParams):
    """One lane's accept-gated :func:`fuse_scan`. A lane that does not
    accept passes its state through bit-unchanged and drops no cells;
    occupancy reports the kept state."""
    fused, occ, dropped = fuse_scan(state, scan, valid, pose, params)
    kept = jax.tree_util.tree_map(
        lambda new, old: jnp.where(accept, new, old), fused, state)
    occ_kept = jnp.where(accept, occ, jnp.sum(state_views(kept, params)[1]))
    return kept, occ_kept, jnp.where(accept, dropped, 0)


@functools.partial(jax.jit, static_argnames=("params",),
                   donate_argnums=(0,))
def fuse_lanes(state_b: tuple, scan_b, valid_b, pose_b, accept_b,
               params: SubmapParams):
    """:func:`fuse_lane` over stacked ``(L, ...)`` state tuples. The
    incoming state is donated: the largest arrays of a fleet reuse their
    device allocation in place. Returns ``(state_b', occupied_b,
    dropped_b)``."""
    return jax.vmap(
        lambda st, s, v, p, a: fuse_lane(st, s, v, p, a, params))(
            state_b, scan_b, valid_b, pose_b, accept_b)


@functools.partial(jax.jit, static_argnames=("params",))
def outside_lattice_fracs(pose_b, scan_b, valid_b, origin_b,
                          params: SubmapParams):
    """:func:`outside_lattice_frac` over ``(L, ...)`` lanes."""
    return jax.vmap(
        lambda T, s, v, o: outside_lattice_frac(T, s, v, o, params))(
            pose_b, scan_b, valid_b, origin_b)


@functools.partial(jax.jit, static_argnames=("params",))
def _fuse(map_pts, map_valid, new_pts, new_valid, center,
          params: SubmapParams):
    """Seed-signature fuse (fp32 layout in, fp32 layout out) — kept for
    callers that manage bare (points, valid, origin) triples."""
    fused, fused_valid, origin, _ = _fuse_core(
        map_pts, map_valid, new_pts, new_valid, center, params)
    return fused, fused_valid, origin


def state_bytes(params: SubmapParams) -> int:
    """Device bytes of one resident submap's cell payload (origin leaf
    excluded — 12 B either way). The fp32/fp16 ratio here is the
    memory-lean headline: 13 B/row -> 6 B/row."""
    return sum(leaf.nbytes for leaf in empty_state(params)[:-1])


class Submap:
    """Rolling local map: static-capacity fused cloud + validity mask.

    Host-facing stateful wrapper over the jitted fuse step; one instance
    per stream. ``points``/``valid`` follow collate conventions (invalid
    rows carry ``PAD_SENTINEL``), so the map drops straight into the
    engine layer as a registration target, mask-aware or not. Both are
    decoded views over :attr:`state` (identity in fp32 mode).

    ``dropped_cells`` is the sticky saturation counter: the running total
    of occupied voxels the capacity budget could not hold across every
    insert. A healthy map keeps it at 0; a saturated one grows it while
    ``occupancy()`` sits at a deceptively clean 1.0.
    """

    def __init__(self, params: SubmapParams = SubmapParams()):
        if params.storage not in STORAGE_MODES:
            raise ValueError(f"storage must be one of {STORAGE_MODES}, "
                             f"got {params.storage!r}")
        self.params = params
        self.state = empty_state(params)
        self.frames_inserted = 0
        self.dropped_cells = 0

    def insert(self, points, center, valid=None) -> None:
        """Fuse a (N, 3) map-frame cloud; evict + re-anchor around
        ``center`` (the current ego position in map frame, (3,))."""
        pts = jnp.asarray(points, jnp.float32)
        if valid is None:
            valid = jnp.ones((pts.shape[0],), bool)
        else:
            valid = jnp.asarray(valid, bool)
        self.state, _, dropped = _fuse_state_jit(
            self.state, pts, valid, jnp.asarray(center, jnp.float32),
            self.params)
        self.frames_inserted += 1
        self.dropped_cells += int(dropped)

    def insert_scan(self, scan, pose, valid, lanes: int = 1) -> None:
        """Fuse a (N, 3) sensor-frame scan taken at ``pose`` (4x4, map
        frame) through :func:`fuse_lanes` at lane 0 of ``lanes``; evict +
        re-anchor around the pose's position."""
        idle = empty_state(self.params)
        state_b, _, dropped = fuse_lanes(
            tuple(lane0_batch(leaf, fill, lanes)
                  for leaf, fill in zip(self.state, idle)),
            lane0_batch(jnp.asarray(scan, jnp.float32), PAD_SENTINEL, lanes),
            lane0_batch(jnp.asarray(valid, bool), False, lanes),
            lane0_batch(jnp.asarray(pose, jnp.float32), jnp.eye(4), lanes),
            lane0_batch(True, False, lanes), self.params)
        self.state = tuple(leaf[0] for leaf in state_b)
        self.frames_inserted += 1
        self.dropped_cells += int(dropped[0])

    # -- decoded views -----------------------------------------------------
    @property
    def points(self) -> jax.Array:
        """Decoded (capacity, 3) f32 cell centroids (invalid rows junk)."""
        return state_views(self.state, self.params)[0]

    @property
    def valid(self) -> jax.Array:
        """(capacity,) bool mask of occupied cells."""
        return state_views(self.state, self.params)[1]

    @property
    def origin(self) -> jax.Array:
        """Lattice anchor of the rolling window, (3,) f32 world coords."""
        return self.state[-1]

    # -- registration-target views ----------------------------------------
    def target(self):
        """(points, valid) — feed to ``RegistrationEngine.register``."""
        pts, valid, _ = state_views(self.state, self.params)
        return pts, valid

    def grid(self) -> VoxelGrid:
        """Counting-sort grid over the live map (anchored at the rolling
        origin, so in-radius queries are guaranteed in-lattice)."""
        return build_voxel_grid(self.points, self.params.voxel_size,
                                self.params.dims, valid=self.valid,
                                origin=self.origin)

    # -- diagnostics -------------------------------------------------------
    @property
    def size(self) -> int:
        """Occupied voxels (valid map points)."""
        return int(jnp.sum(self.valid))

    def occupancy(self) -> float:
        """Fraction of the static capacity in use (1.0 = budget saturated
        — check ``dropped_cells`` to tell an exact fit from silent
        truncation; grow ``capacity`` or shrink ``evict_radius``)."""
        return self.size / int(self.params.capacity)
