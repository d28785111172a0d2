"""Pallas TPU kernel: FPPS brute-force NN search.

FPGA -> TPU mapping (DESIGN.md §2):

  * The paper's systolic PE array computes all pairwise distances between a
    register-buffered source tile and a streamed/broadcast target batch. On
    TPU the distance grid *is* a matmul on the 128x128 MXU once rewritten as
    an augmented inner product:

        d2[i,j] = ||R p_i + t - q_j||²
                = p'·p' - 2 p'·q + q·q                        (p' = R p + t)
                = [p'x p'y p'z 1 ||p'||² 0 0 0] · [-2qx -2qy -2qz ||q||² 1 0 0 0]ᵀ

    Both augmented operands are (8, len) — 8 is the fp32 sublane tile, so the
    contraction is exactly one MXU pass per (bn x bm) tile.

  * The transform stage (paper's "point cloud transformer") is folded
    algebraically into the *source* augmentation: O(N) work per ICP
    iteration on the 4k-point query cloud, while the (8, M) target
    augmentation is built ONCE per frame and stays resident — the analogue
    of FPPS parking the whole target cloud in BRAM across all 50 iterations.

  * The paper's MIN block (running min + candidate-index registers) is the
    (best_d2, best_idx) output pair revisited across the target-block grid
    axis: the output BlockSpec ignores the inner grid index, so the same
    VMEM tile is read-modify-written as target blocks stream through —
    Pallas's grid pipeline provides the FIFO-style overlap of the paper's
    4-stage streaming design (load of block j+1 overlaps compute of j).

  * The paper's comparison tree (CMP TR) is the in-tile `min`/`argmin`
    reduction on the VPU. The score tile is laid out (bm, bn) — targets on
    sublanes, queries on lanes — so the reduction runs over sublanes and
    the per-query (best_d2, best_idx) carry is a lane-dense (1, bn) row
    (``kernels.common``).

Grid: (N/bn, M/bm), target axis innermost ("arbitrary" semantics — it
carries the running min; the source axis is "parallel").
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.nn_search import merge_two_best
from repro.kernels.common import argmin_rows, pallas_call_kwargs

AUG_ROWS = 8
# The custom call's name in a profiler trace (``nn_search.<n>``), by which
# the benchmark reads the kernel's device time.
KERNEL_NAME = "nn_search"


def _nn_kernel(src_ref, dst_ref, *out_refs, bm: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        for ref in out_refs:
            ref[...] = (jnp.full_like(ref, jnp.inf)
                        if ref.dtype == jnp.float32 else jnp.zeros_like(ref))

    # (8, bm) x (8, bn) -> (bm, bn): one MXU tile, fp32 accumulation.
    scores = jax.lax.dot_general(
        dst_ref[...], src_ref[...], (((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    # CMP-tree stage: per-query best and runner-up over the bm candidates.
    v1, a1 = argmin_rows(scores)
    rows = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0)
    v2, a2 = argmin_rows(jnp.where(rows == a1, jnp.inf, scores))
    # MIN-block stage: the running two best; the earliest index wins ties,
    # matching the oracle's first-match semantics.
    merged = merge_two_best(tuple(ref[...] for ref in out_refs),
                            (v1, j * bm + a1, v2, j * bm + a2))
    for ref, val in zip(out_refs, merged):
        ref[...] = val


def nn_search_kernel(src_aug: jax.Array, dst_aug: jax.Array,
                     *, bn: int = 512, bm: int = 1024,
                     interpret: bool | None = None):
    """Run the NN kernel on pre-augmented operands.

    Args:
      src_aug: (8, N) from ``ref.augment_source`` — N must be a multiple of bn.
      dst_aug: (8, M) from ``ref.augment_target`` — M must be a multiple of bm.
      bn, bm: VMEM tile sizes, multiples of 128. Defaults give tiles of
        src 8*512*4 = 16 KiB, dst 8*1024*4 = 32 KiB, scores 1024*512*4 =
        2 MiB.
      interpret: tri-state (``kernels.common``): None = auto (compiled on
        TPU, interpreter elsewhere).
    Returns:
      (best_d2, best_idx, next_d2, next_idx): the best and runner-up
      expansion scores, (N,) fp32 (unclamped), and their (N,) int32
      target indices.
    """
    n = src_aug.shape[1]
    m = dst_aug.shape[1]
    assert src_aug.shape[0] == AUG_ROWS and dst_aug.shape[0] == AUG_ROWS
    assert n % bn == 0, (n, bn)
    assert m % bm == 0, (m, bm)
    grid = (n // bn, m // bm)

    kernel = functools.partial(_nn_kernel, bm=bm)
    out_shape = (jax.ShapeDtypeStruct((1, n), jnp.float32),
                 jax.ShapeDtypeStruct((1, n), jnp.int32)) * 2
    in_specs = [
        pl.BlockSpec((AUG_ROWS, bn), lambda i, j: (0, i)),
        pl.BlockSpec((AUG_ROWS, bm), lambda i, j: (0, j)),
    ]
    out_specs = (pl.BlockSpec((1, bn), lambda i, j: (0, i)),) * 4
    call = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        name=KERNEL_NAME,
        **pallas_call_kwargs(interpret, ("parallel", "arbitrary")),
    )
    return tuple(row[0] for row in call(src_aug, dst_aug))


def vmem_bytes(bn: int, bm: int) -> dict:
    """Static VMEM budget of one grid step (the Table II analogue)."""
    src = AUG_ROWS * bn * 4
    dst = AUG_ROWS * bm * 4
    scores = bn * bm * 4
    outs = 2 * bn * (4 + 4)
    return {
        "src_tile": src, "dst_tile": dst, "scores": scores, "outputs": outs,
        "total_single": src + dst + scores + outs,
        # in/out tiles are double-buffered by the pipeline; scores is scratch.
        "total_double_buffered": 2 * (src + dst + outs) + scores,
    }
