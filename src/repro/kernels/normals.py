"""Pallas TPU kernel: neighbourhood moment sweep for normal estimation.

Same streaming shape as the grid-NN candidate sweep
(``kernels/nn_search_grid.py``): the XLA side gathers each query's
(2·rings+1)³ candidate ring from the :class:`repro.data.voxelize.VoxelGrid`
tables, and the kernel does the dense part in VMEM — here a **radius-gated
moment accumulation** instead of a running min:

  * grid = (N/bn, CK/bc): query blocks "parallel", the candidate axis
    innermost/"arbitrary" carrying ten running sums per query — the count
    and the first/second moments of the *query-relative* offsets
    (Σw, Σw·d, Σw·d·dᵀ with d = x − p).
  * relative coordinates are formed in-kernel (candidate plane minus the
    query's broadcast column), so the accumulated second moments are
    ~radius² in magnitude — no scene-scale cancellation, and the fp32 sums
    stay exact to ~1e-6 relative even over hundreds of candidates.
  * masked candidate slots arrive pre-filled with far-sentinel coordinates
    (``core.nn_search_grid``), so the radius gate ``d² ≤ r²`` rejects them
    with no separate mask input — the finite-sentinel trick again.
  * per (bc, bn) tile the work is elementwise multiplies + a sublane
    reduction (VPU; queries run along lanes, ``kernels.common``); there is
    no shared operand for the MXU, exactly like the NN candidate sweep it
    mirrors.

The eigen-decomposition epilogue is shared with the XLA path
(:func:`repro.data.normals.moments_to_normals`), so the kernel's contract
ends at the ten moment planes and parity holds to fp32 tolerance.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.nn_search_grid import _MASK_COORD, gather_candidates
from repro.data.normals import (NormalParams, moments_to_normals,
                                orient_normals)
from repro.data.voxelize import VoxelGrid, build_voxel_grid
from repro.kernels.common import (candidate_planes, pallas_call_kwargs,
                                  query_rows, round_up as _round_up)

# Output order of the moment planes: count, Σdx, Σdy, Σdz, then the six
# unique entries of the symmetric second-moment matrix.
_MOMENTS = ("cnt", "sx", "sy", "sz", "sxx", "syy", "szz", "sxy", "sxz", "syz")


def _moment_sweep_kernel(qx_ref, qy_ref, qz_ref, cx_ref, cy_ref, cz_ref,
                         *out_refs, r2: float):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        for ref in out_refs:
            ref[...] = jnp.zeros_like(ref)

    dx = cx_ref[...] - qx_ref[...]
    dy = cy_ref[...] - qy_ref[...]
    dz = cz_ref[...] - qz_ref[...]
    d2 = dx * dx + dy * dy + dz * dz
    w = (d2 <= r2).astype(jnp.float32)
    planes = (w, w * dx, w * dy, w * dz,
              w * dx * dx, w * dy * dy, w * dz * dz,
              w * dx * dy, w * dx * dz, w * dy * dz)
    for ref, plane in zip(out_refs, planes):
        ref[...] += jnp.sum(plane, axis=0, keepdims=True)


def moment_sweep_kernel(q: jax.Array, cand: jax.Array, radius: float, *,
                        bn: int = 256, bc: int = 128,
                        interpret: bool | None = None):
    """Radius-gated moment sums over per-query candidate sets.

    Args:
      q: (N, 3) queries; N must be a multiple of bn.
      cand: (N, CK, 3) candidate coordinates (masked slots = far sentinel);
        CK must be a multiple of bc.
      radius: neighbourhood gate in metres (static).

    Returns:
      (cnt, s, ss): (N,) counts, (N, 3) first moments, (N, 3, 3) symmetric
      second moments — all of the query-relative offsets.
    """
    n, ck = cand.shape[0], cand.shape[1]
    assert n % bn == 0, (n, bn)
    assert ck % bc == 0, (ck, bc)
    grid = (n // bn, ck // bc)
    kernel = functools.partial(_moment_sweep_kernel,
                               r2=float(radius) ** 2)
    out_shape = tuple(jax.ShapeDtypeStruct((1, n), jnp.float32)
                      for _ in _MOMENTS)
    qspec = pl.BlockSpec((1, bn), lambda i, j: (0, i))
    cspec = pl.BlockSpec((bc, bn), lambda i, j: (j, i))
    call = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[qspec, qspec, qspec, cspec, cspec, cspec],
        out_specs=tuple(qspec for _ in _MOMENTS),
        out_shape=out_shape,
        **pallas_call_kwargs(interpret, ("parallel", "arbitrary")),
    )
    cnt, sx, sy, sz, sxx, syy, szz, sxy, sxz, syz = (
        row[0] for row in call(*query_rows(q), *candidate_planes(cand)))
    s = jnp.stack([sx, sy, sz], axis=-1)
    ss = jnp.stack([
        jnp.stack([sxx, sxy, sxz], axis=-1),
        jnp.stack([sxy, syy, syz], axis=-1),
        jnp.stack([sxz, syz, szz], axis=-1),
    ], axis=-2)
    return cnt, s, ss


def estimate_normals_pallas(points: jax.Array,
                            params: NormalParams = NormalParams(
                                neighborhood="radius"), *,
                            valid: jax.Array | None = None,
                            viewpoint: jax.Array | None = None,
                            grid: VoxelGrid | None = None,
                            bn: int = 256, bc: int = 128,
                            interpret: bool | None = None):
    """Radius-mode normal estimation with the moment sweep as a kernel.

    Same contract as ``repro.data.normals.estimate_normals`` with
    ``neighborhood="radius"`` (the k-NN top-k selection is data-dependent
    control flow the streaming kernel deliberately avoids); parity with the
    XLA radius path is pinned in ``tests/test_normals.py``.
    """
    if params.neighborhood != "radius":
        raise ValueError("the Pallas moment sweep is radius-mode only; "
                         f"got neighborhood={params.neighborhood!r}")
    pts = points.astype(jnp.float32)
    if grid is None:
        grid = build_voxel_grid(pts, params.voxel_size, params.grid_dims,
                                valid=valid)
    cand_pts, _, _ = gather_candidates(pts, grid, params.max_per_cell,
                                       params.rings)
    n, ck = cand_pts.shape[0], cand_pts.shape[1]
    n_pad, ck_pad = _round_up(n, bn), _round_up(ck, bc)
    if n_pad > n or ck_pad > ck:
        cand_pts = jnp.pad(cand_pts,
                           ((0, n_pad - n), (0, ck_pad - ck), (0, 0)),
                           constant_values=_MASK_COORD)
        pts_p = jnp.pad(pts, ((0, n_pad - n), (0, 0)))
    else:
        pts_p = pts
    cnt, s, ss = moment_sweep_kernel(pts_p, cand_pts, params.radius,
                                     bn=bn, bc=bc, interpret=interpret)
    cnt, s, ss = cnt[:n], s[:n], ss[:n]
    normals, nvalid = moments_to_normals(cnt, s, ss,
                                         min_neighbors=params.min_neighbors)
    normals = orient_normals(pts, normals, viewpoint)
    if valid is not None:
        nvalid = nvalid & valid
        normals = jnp.where(nvalid[..., None], normals, 0.0)
    return normals, nvalid
