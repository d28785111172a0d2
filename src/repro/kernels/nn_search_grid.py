"""Pallas TPU kernel: grid-bucketed NN candidate sweep.

The brute-force kernel (``kernels/nn_search.py``) streams *target tiles*
through VMEM against a resident source block; this kernel streams
*candidate tiles*. The XLA side gathers each query's 27-neighbourhood from
the :class:`repro.data.voxelize.VoxelGrid` tables into a dense per-query
candidate matrix (a data-dependent gather the XLA scatter/gather engine is
the right tool for), then the kernel does the dense part — distance + the
running (min, argmin) carry — in VMEM:

  * grid = (N/bn, CK/bc): query blocks are "parallel", the candidate axis
    is innermost/"arbitrary" carrying the running min, exactly like the
    brute kernel's target axis. Candidates run along sublanes and queries
    along lanes (``kernels.common``).
  * the candidate set is per-query, so the distance tile is an *elementwise*
    (bc, bn) op on coordinate planes (VPU work) rather than a matmul — with
    CK = 27*max_per_cell ≈ a few hundred, there is no shared-operand
    structure for the MXU to exploit, and the whole sweep is tiny compared
    to the O(M) brute tile stream it replaces.
  * masked candidate slots arrive pre-filled with far-sentinel coordinates
    (see ``core.nn_search_grid``), so the kernel needs no mask input and no
    NaN path — the same finite-sentinel trick as everywhere else.

The kernel returns the winning *slot* per query; the wrapper maps slots
back through the gather tables to original target indices and recomputes
the winner distance directly (exact, no cancellation).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.nn_search_grid import _MASK_COORD, gather_candidates
from repro.data.voxelize import VoxelGrid
from repro.kernels.common import (argmin_rows, candidate_planes,
                                  pallas_call_kwargs, query_rows,
                                  round_up as _round_up)


def _grid_nn_kernel(qx_ref, qy_ref, qz_ref, cx_ref, cy_ref, cz_ref,
                    best_d2_ref, best_slot_ref, *, bc: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        best_d2_ref[...] = jnp.full_like(best_d2_ref, jnp.inf)
        best_slot_ref[...] = jnp.zeros_like(best_slot_ref)

    # (bc, bn) distance tile from coordinate planes: pure VPU.
    dx = qx_ref[...] - cx_ref[...]
    dy = qy_ref[...] - cy_ref[...]
    dz = qz_ref[...] - cz_ref[...]
    d2 = dx * dx + dy * dy + dz * dz
    local_min, local_arg = argmin_rows(d2)
    # Strict < keeps the earliest slot on ties (first-match semantics).
    improved = local_min < best_d2_ref[...]
    best_d2_ref[...] = jnp.where(improved, local_min, best_d2_ref[...])
    best_slot_ref[...] = jnp.where(improved, j * bc + local_arg,
                                   best_slot_ref[...])


def candidate_sweep_kernel(q: jax.Array, cand: jax.Array, *,
                           bn: int = 512, bc: int = 256,
                           interpret: bool | None = None):
    """Masked rowwise argmin over per-query candidate sets.

    Args:
      q: (N, 3) queries; N must be a multiple of bn.
      cand: (N, CK, 3) candidate coordinates (masked slots = sentinel);
        CK must be a multiple of bc.
    Returns:
      (best_d2, best_slot): (N,) fp32 (unclamped) and (N,) int32 slot into
      the candidate axis.
    """
    n, ck = cand.shape[0], cand.shape[1]
    assert n % bn == 0, (n, bn)
    assert ck % bc == 0, (ck, bc)
    grid = (n // bn, ck // bc)
    kernel = functools.partial(_grid_nn_kernel, bc=bc)
    out_shape = (jax.ShapeDtypeStruct((1, n), jnp.float32),
                 jax.ShapeDtypeStruct((1, n), jnp.int32))
    qspec = pl.BlockSpec((1, bn), lambda i, j: (0, i))
    cspec = pl.BlockSpec((bc, bn), lambda i, j: (j, i))
    call = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[qspec, qspec, qspec, cspec, cspec, cspec],
        out_specs=(qspec, qspec),
        out_shape=out_shape,
        **pallas_call_kwargs(interpret, ("parallel", "arbitrary")),
    )
    best_d2, best_slot = call(*query_rows(q), *candidate_planes(cand))
    return best_d2[0], best_slot[0]


def nn_search_grid_pallas(src: jax.Array, grid: VoxelGrid, *,
                          max_per_cell: int = 32, rings: int = 1,
                          bn: int = 512, bc: int = 256,
                          interpret: bool | None = None,
                          return_points: bool = False):
    """Grid NN with the candidate sweep run as a Pallas kernel.

    Same contract as ``core.nn_search_grid.nn_search_grid`` (without the
    exact fallback — empty neighbourhoods return ``d2 = +inf``): exact
    wherever the true NN is within ``rings * voxel_size`` and its cell
    didn't overflow.
    """
    n = src.shape[0]
    cand_pts, cand_idx, cand_valid = gather_candidates(src, grid,
                                                       max_per_cell, rings)
    ck = cand_pts.shape[1]
    n_pad, ck_pad = _round_up(n, bn), _round_up(ck, bc)
    if n_pad > n or ck_pad > ck:
        cand_pts = jnp.pad(cand_pts,
                           ((0, n_pad - n), (0, ck_pad - ck), (0, 0)),
                           constant_values=_MASK_COORD)
        src_p = jnp.pad(src.astype(jnp.float32), ((0, n_pad - n), (0, 0)))
    else:
        src_p = src.astype(jnp.float32)
    best_d2, best_slot = candidate_sweep_kernel(src_p, cand_pts, bn=bn,
                                                bc=bc, interpret=interpret)
    best_d2, best_slot = best_d2[:n], jnp.clip(best_slot[:n], 0, ck - 1)
    rows = jnp.arange(n)
    best_idx = cand_idx[rows, best_slot]
    matched = cand_pts[:n][rows, best_slot]
    has_cand = jnp.any(cand_valid, axis=1)
    # Recompute the winner distance directly (exact) where a winner exists.
    diff = src.astype(jnp.float32) - matched
    exact = jnp.sum(diff * diff, axis=-1)
    best_d2 = jnp.where(has_cand, exact, jnp.inf)
    best_idx = jnp.where(has_cand, best_idx, 0)
    if return_points:
        return jnp.maximum(best_d2, 0.0), best_idx, matched
    return jnp.maximum(best_d2, 0.0), best_idx


def grid_kernel_nn_fn(grid: VoxelGrid, *, max_per_cell: int = 32,
                      rings: int = 1, bn: int = 512, bc: int = 256,
                      interpret: bool | None = None):
    """Resident-grid Pallas searcher with the ``core.icp`` nn_fn contract
    (the voxel grid, like the augmented target, lives at trace scope)."""

    def nn_fn(src, _target=None):
        return nn_search_grid_pallas(src, grid, max_per_cell=max_per_cell,
                                     rings=rings, bn=bn, bc=bc,
                                     interpret=interpret,
                                     return_points=True)

    return nn_fn
