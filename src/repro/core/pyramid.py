"""Coarse-to-fine ICP pyramid over voxel-downsampled clouds (DESIGN.md §8).

The brute-force engines spend every one of their (up to) 50 iterations on
the full O(N·M) sweep, even the early ones that only need a rough gradient
direction. The pyramid splits the schedule:

  * **coarse levels** — both clouds are voxel-downsampled to centroids
    (``repro.data.voxelize.voxel_downsample``) and a few cheap iterations
    run brute force on the tiny clouds, with the correspondence gate
    widened proportionally to the voxel size. Large initial misalignments
    (a scenario class plain ICP handles poorly — its basin of attraction
    is roughly one gate radius) converge here for a fraction of a full
    sweep's cost.
  * **finest level** — full-resolution polish where the O(M) sweep is
    replaced by grid-bucketed NN (``repro.core.nn_search_grid``): the
    voxel grid is built once per frame at trace scope — the spatial
    analogue of the Pallas engine's resident augmented target — and each
    iteration gathers only 27-neighbourhood candidates. With
    ``grid_voxel >= max_correspondence_distance`` every gate-passing
    correspondence is found exactly, so the fixed point matches brute
    force (validated in ``benchmarks/nn_sweep.py``).

Exposed both as :func:`icp_pyramid` (drop-in next to ``core.icp.icp``) and
as the ``"pyramid"`` entry in the engine registry, so drivers opt in with
``get_engine("pyramid")`` / ``FppsICP(engine="pyramid")``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.engine import RegistrationEngine, register_engine
from repro.core.icp import (ICPParams, ICPResult, icp, icp_fixed_iterations,
                            scrub_nonfinite)
from repro.core.nn_search_grid import (GridQueryStats, grid_nn_fn,
                                       neighborhood_stats)
from repro.data.voxelize import build_voxel_grid, voxel_downsample

# Coarse schedule entries: (voxel_size_m, iterations[, max_points]).
# Default: ONE coarse pass — 4 m centroids, capped at 8192 points — then
# the full-resolution grid polish. Frame-to-frame motion needs nothing
# coarser (and coarse iterations are pure overhead once the finest level
# is grid-accelerated); large-misalignment workloads should widen the
# schedule, e.g. ((8.0, 8, 4096), (4.0, 8, 8192)). max_points is the
# static downsample capacity (clamped to the cloud size at trace time).
DEFAULT_LEVELS: tuple = ((4.0, 6, 8192),)

# Finest-level voxel-grid lattice: 128 m x 128 m x 32 m at 1 m cells covers
# the synthetic KITTI protocol's range-gated frames; anchored per-cloud.
DEFAULT_GRID_DIMS: tuple[int, int, int] = (128, 128, 32)


def _norm_level(level, cloud_size: int):
    """(voxel, iters[, max_points]) -> (voxel, iters, capacity<=cloud)."""
    if len(level) == 2:
        voxel, iters = level
        cap = cloud_size
    else:
        voxel, iters, cap = level
    return float(voxel), int(iters), min(int(cap), cloud_size)


def icp_pyramid(source: jax.Array, target: jax.Array,
                params: ICPParams = ICPParams(), *,
                levels: tuple = DEFAULT_LEVELS,
                grid_dims: tuple[int, int, int] = DEFAULT_GRID_DIMS,
                grid_voxel: float | None = None,
                max_per_cell: int = 32,
                rings: int = 1,
                initial_transform: jax.Array | None = None,
                src_valid: jax.Array | None = None,
                dst_valid: jax.Array | None = None,
                fixed: bool = False,
                use_kernel: bool = False,
                interpret: bool = False) -> ICPResult:
    """Coarse-to-fine ICP: ``levels`` coarse passes, then a full-resolution
    grid-NN polish of ``params.max_iterations`` iterations.

    Each coarse level voxel-downsamples *both* clouds, widens the gate to
    ``max(gate, 1.5 * voxel)`` (centroids sit up to half a cell diagonal
    from the surface they summarise), and warm-starts the next level with
    its cumulative transform. ``fixed=True`` selects the scan-based finest
    loop (for vmap/batching); ``use_kernel=True`` routes the finest-level
    candidate sweep through the Pallas kernel
    (``repro.kernels.nn_search_grid``), interpretable off-TPU.

    Returns the finest level's :class:`ICPResult` (its iteration count and
    rmse describe the polish stage, like the engines' results describe
    their single loop).
    """
    n, m = source.shape[0], target.shape[0]
    # Scrub before the coarse downsamples and the polish grid build: a
    # single NaN row would poison the lattice origin (min over a NaN is
    # NaN) and every centroid its cell touches.
    source, src_valid = scrub_nonfinite(source, src_valid)
    target, dst_valid = scrub_nonfinite(target, dst_valid)
    T = (jnp.eye(4, dtype=source.dtype) if initial_transform is None
         else initial_transform)

    for level in levels:
        voxel, iters, cap = _norm_level(level, m)
        src_l, sv_l = voxel_downsample(source, voxel,
                                       max_points=min(cap, n),
                                       valid=src_valid)
        dst_l, dv_l = voxel_downsample(target, voxel, max_points=cap,
                                       valid=dst_valid)
        # Coarse levels stay point-to-point, no robust reweighting: voxel
        # centroids don't lie on the surfaces they summarise, so plane
        # residuals (and fine-scale robust scales) are meaningless there —
        # the coarse job is a cheap basin capture, the polish does quality.
        # (fused=False too: the tiny downsampled clouds would waste the
        # fused kernel's grid build; only the full-resolution polish fuses.)
        p_l = params._replace(
            max_iterations=iters,
            max_correspondence_distance=max(
                params.max_correspondence_distance, 1.5 * voxel),
            minimizer="point_to_point", robust_kernel="none", fused=False)
        res = icp_fixed_iterations(src_l, dst_l, p_l, initial_transform=T,
                                   src_valid=sv_l, dst_valid=dv_l)
        T = res.T

    gv = (float(grid_voxel) if grid_voxel is not None
          else max(1.0, params.max_correspondence_distance))
    grid = build_voxel_grid(target, gv, grid_dims, valid=dst_valid)
    if params.fused:
        nn_fn = None  # the fused kernel replaces the whole polish stage
    elif use_kernel:
        from repro.kernels.nn_search_grid import grid_kernel_nn_fn
        nn_fn = grid_kernel_nn_fn(grid, max_per_cell=max_per_cell,
                                  rings=rings, interpret=interpret)
    else:
        nn_fn = grid_nn_fn(grid, max_per_cell=max_per_cell, rings=rings)

    if params.minimizer == "point_to_plane":
        # Polish goes plane: estimate target normals once at trace scope,
        # reusing the resident polish grid as the neighbourhood structure
        # (one counting-sort build serves both NN and normals).
        from repro.data.normals import NormalParams, estimate_normals
        np_l = NormalParams(voxel_size=gv, grid_dims=tuple(grid_dims),
                            max_per_cell=max_per_cell, rings=rings)
        normals, _ = estimate_normals(target, np_l, valid=dst_valid,
                                      grid=grid)
    else:
        normals = None

    runner = icp_fixed_iterations if fixed else icp
    if params.fused:
        # Fused polish: the resident grid (and the normals, for the plane
        # minimiser) feed the single-pass moment kernel directly — same
        # exactness contract as the grid searcher it replaces.
        from repro.kernels.fused_icp import make_fused_fn
        fused_fn = make_fused_fn(grid, params, normals,
                                 max_per_cell=max_per_cell, rings=rings,
                                 interpret=interpret)
        return runner(source, None, params, initial_transform=T,
                      fused_fn=fused_fn, src_valid=src_valid)

    def correspond(src_t):
        d2, idx, matched = nn_fn(src_t)
        if normals is None:
            return d2, matched
        return d2, matched, jnp.take(normals, idx, axis=0)

    return runner(source, None, params, initial_transform=T,
                  correspond_fn=correspond, src_valid=src_valid)


def polish_stats(source: jax.Array, target: jax.Array,
                 params: ICPParams = ICPParams(), *,
                 grid_dims: tuple[int, int, int] = DEFAULT_GRID_DIMS,
                 grid_voxel: float | None = None,
                 max_per_cell: int = 32, rings: int = 1,
                 dst_valid: jax.Array | None = None) -> GridQueryStats:
    """Overflow/empty diagnostics of the polish stage's candidate gather.

    The grid NN silently truncates overflowing cells and returns ``inf``
    for empty neighbourhoods (the documented exactness contract); this
    builds the exact grid the polish would use and counts both effects for
    the given source, so callers can check a scene/config before trusting
    the pyramid result — or log it per frame in production.
    """
    gv = (float(grid_voxel) if grid_voxel is not None
          else max(1.0, params.max_correspondence_distance))
    grid = build_voxel_grid(target, gv, grid_dims, valid=dst_valid)
    return neighborhood_stats(source, grid, max_per_cell, rings)


class PyramidEngine(RegistrationEngine):
    """Coarse-to-fine engine: voxel pyramid + resident-grid finest level.

    All pyramid knobs are static constructor kwargs (hashable, so named
    ``get_engine("pyramid", ...)`` instances stay shared singletons with
    persistent jit caches):

      levels:        coarse schedule, ((voxel_m, iters[, max_points]), ...)
      grid_dims:     finest-level lattice extent (cells per axis)
      grid_voxel:    finest-level cell size; None -> max(1.0, gate) so the
                     27-neighbourhood provably covers the gate radius
      max_per_cell:  candidate capacity per cell (overflow truncates)
      use_kernel:    run the finest candidate sweep as the Pallas kernel
                     (interpret mode off-TPU, like the "pallas" engine)
    """

    name = "pyramid"

    def __init__(self, chunk: int = 2048, levels: tuple = DEFAULT_LEVELS,
                 grid_dims: tuple[int, int, int] = DEFAULT_GRID_DIMS,
                 grid_voxel: float | None = None, max_per_cell: int = 32,
                 rings: int = 1, use_kernel: bool = False,
                 interpret: bool | None = None):
        super().__init__(chunk)
        self._levels = tuple(tuple(lv) for lv in levels)
        self._grid_dims = tuple(grid_dims)
        self._grid_voxel = grid_voxel
        self._max_per_cell = max_per_cell
        self._rings = rings
        self._use_kernel = use_kernel
        self._interpret = interpret

    def _interp(self) -> bool:
        from repro.kernels.common import default_interpret
        return default_interpret(self._interpret)

    def _pyramid_kwargs(self):
        return dict(levels=self._levels, grid_dims=self._grid_dims,
                    grid_voxel=self._grid_voxel,
                    max_per_cell=self._max_per_cell, rings=self._rings,
                    use_kernel=self._use_kernel, interpret=self._interp())

    def polish_stats(self, source, target,
                     params: ICPParams | None = None, *,
                     dst_valid=None) -> GridQueryStats:
        """Candidate-gather diagnostics of this engine's polish stage (see
        :func:`polish_stats`) — counts the cell-overflow drops and empty
        (inf) rows the registration itself absorbs silently."""
        params = self._default_params(params)
        return polish_stats(jnp.asarray(source, jnp.float32),
                            jnp.asarray(target, jnp.float32), params,
                            grid_dims=self._grid_dims,
                            grid_voxel=self._grid_voxel,
                            max_per_cell=self._max_per_cell,
                            rings=self._rings, dst_valid=dst_valid)

    def _build_single(self, params: ICPParams):
        kw = self._pyramid_kwargs()

        def run(src, dst, T0, sv, dv):
            self._note_trace("single", params, src.shape, dst.shape)
            return icp_pyramid(src, dst, params, initial_transform=T0,
                               src_valid=sv, dst_valid=dv, **kw)

        return self._jit(run, "single")

    def _build_batch(self, params: ICPParams):
        kw = self._pyramid_kwargs()

        def run(src_b, dst_b, T0, sv, dv):
            self._note_trace("batch", params, src_b.shape, dst_b.shape)
            if T0 is None:
                T0 = jnp.broadcast_to(jnp.eye(4, dtype=src_b.dtype),
                                      (src_b.shape[0], 4, 4))

            def one(src, dst, T0_, sv_, dv_):
                # fixed=True: under vmap a while_loop would run every lane
                # to the worst trip count anyway; the scan's freeze mask
                # keeps per-pair early-convergence semantics.
                return icp_pyramid(src, dst, params, initial_transform=T0_,
                                   src_valid=sv_, dst_valid=dv_,
                                   fixed=True, **kw)

            return jax.vmap(one)(src_b, dst_b, T0, sv, dv)

        return self._jit(run, "batch")


register_engine("pyramid", PyramidEngine)
