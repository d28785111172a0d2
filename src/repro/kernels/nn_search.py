"""Pallas TPU kernel: FPPS brute-force NN search.

FPGA -> TPU mapping (DESIGN.md §2):

  * The paper's systolic PE array scores every (source, target) pair of a
    register-buffered source tile against a streamed target batch. Here
    the score is the augmented expansion with ``‖p'‖²`` left out, since it
    is the same for every candidate of one query:

        d2[i,j] = ||R p_i + t - q_j||²
                = ‖p'‖² + (p'x·(-2qx) + p'y·(-2qy) + p'z·(-2qz) + ‖q‖²)

    three multiplies and three adds a candidate, in float32 on the VPU.
    A register block holds ``_ROWS`` queries on sublanes, each coordinate
    broadcast across the 128 lanes, against 128 target rows on lanes read
    from the lane-dense ``(8, M)`` target augmentation with a sublane
    broadcast. The MXU lost this work: a contraction 8 deep fills 8/128 of
    its depth, float32 at HIGHEST takes six bfloat16 passes, and the
    ``(bm, bn)`` product spilled into VMEM, which the two-best reduction
    then read and wrote in full passes.

  * The transform stage (paper's "point cloud transformer") is folded
    algebraically into the *source* augmentation: O(N) work per ICP
    iteration on the 4k-point query cloud, while the (8, M) target
    augmentation is built ONCE per frame and stays resident — the analogue
    of FPPS parking the whole target cloud in BRAM across all 50 iterations.

  * The paper's MIN block and comparison tree are lane-wise running
    accumulators: each (query, lane) keeps its best and runner-up score
    and index in registers while a ``fori_loop`` walks the target block's
    128-lane chunks, and in VMEM scratch of shape ``(bn, 128)`` between
    target blocks. A lane sees its candidates in increasing index order
    and replaces only on a strictly smaller score, so it holds the two
    earliest-smallest. The comparison tree's root is one cross-lane merge
    on the last target block, which breaks ties by the smallest index.
    Pallas's grid pipeline provides the FIFO-style overlap of the paper's
    4-stage streaming design (load of block j+1 overlaps compute of j).

The outputs stay lane-dense ``(1, N)`` rows (``kernels.common``): the
merge transposes 128 queries' lane positions onto sublanes and reduces
over them.

Grid: (N/bn, M/bm), target axis innermost ("arbitrary" semantics — it
carries the running two best; the source axis is "parallel").
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import default_interpret, pallas_call_kwargs

AUG_ROWS = 8
# The custom call's name in a profiler trace (``nn_search.<n>``), by which
# the benchmark reads the kernel's device time.
KERNEL_NAME = "nn_search"

_LANES = 128
# Queries a register block holds: 4 vregs a carry, so the block's three
# query broadcasts and four carries take 28 of the 64 vregs.
_ROWS = 32
# 128-lane target chunks a loop step scores (at most; a divisor of bm/128).
_UNROLL = 8
_NO_INDEX = jnp.iinfo(jnp.int32).max


def target_block(interpret: bool | None = None) -> int:
    """Default target block ``bm``: 4096 rows on the chip, where fewer grid
    steps and carry round trips pay; 1024 in the interpreter, whose loop
    steps cost more than their elements and whose targets are small."""
    return 1024 if default_interpret(interpret) else 4096


def _score(q, t):
    """``‖q‖² - 2 p'·q`` of ``(height, 128)`` query broadcasts ``q`` against
    the 128 target rows of the ``(8, 128)`` augmented chunk ``t``."""
    px, py, pz = q
    return ((px * t[0:1] + py * t[1:2]) + pz * t[2:3]) + t[3:4]


def _two_best(acc, s, base):
    """Fold the scores ``s`` of the chunk at target row ``base`` into the
    lane-wise running two best, which hold chunk bases (a lane adds its
    own offset at the merge); a strictly smaller score replaces, so the
    earlier index keeps a tie."""
    b1, i1, b2, i2 = acc
    lt1 = s < b1
    lt2 = s < b2
    return (jnp.where(lt1, s, b1), jnp.where(lt1, base, i1),
            jnp.where(lt1, b1, jnp.where(lt2, s, b2)),
            jnp.where(lt1, i1, jnp.where(lt2, base, i2)))


def _lex_min(v, i):
    """Smallest ``v`` over sublanes, and the smallest index holding it."""
    lo = jnp.min(v, axis=0, keepdims=True)
    return lo, jnp.min(jnp.where(v == lo, i, _NO_INDEX), axis=0,
                       keepdims=True)


def _blocks(bn: int, bm: int, interpret: bool) -> tuple[int, int, int]:
    """(queries a merge slab holds, queries a register block holds,
    128-lane chunks a loop step scores) for the block sizes bn and bm."""
    slab = min(bn, _LANES)
    # The interpreter runs each op of the body on the host, so it takes
    # register blocks of a whole slab, which amortize the per-op cost.
    return (slab, slab if interpret else _ROWS,
            math.gcd(bm // _LANES, _UNROLL))


def _nn_kernel(src_ref, dst_ref, *refs, bm: int, slab: int, height: int,
               unroll: int):
    out_refs, q_refs, acc_refs = refs[:4], refs[4:7], refs[7:]
    j = pl.program_id(1)
    bn = src_ref.shape[1]

    def slabs(body):
        """Run ``body(queries)`` over the block's query slabs."""
        def step(s, carry):
            body(pl.ds(pl.multiple_of(s * slab, slab), slab))
            return carry
        jax.lax.fori_loop(0, bn // slab, step, 0)

    @pl.when(j == 0)
    def _init():
        def broadcast(qs):
            # Row k's queries on sublanes, each across all lanes.
            for k, ref in enumerate(q_refs):
                ref[qs, :] = jnp.broadcast_to(src_ref[k:k + 1, qs],
                                              (_LANES, slab)).T
        slabs(broadcast)
        for ref in acc_refs:
            ref[...] = (jnp.full_like(ref, jnp.inf)
                        if ref.dtype == jnp.float32 else jnp.zeros_like(ref))

    chunks = bm // _LANES

    def block(r, carry):
        rows = pl.ds(pl.multiple_of(r * height, height), height)
        q = tuple(ref[rows, :] for ref in q_refs)

        def chunk(c, acc):
            for u in range(unroll):
                k = c * unroll + u
                t = dst_ref[:, pl.ds(pl.multiple_of(k * _LANES, _LANES),
                                     _LANES)]
                acc = _two_best(acc, _score(q, t), j * bm + k * _LANES)
            return acc

        acc = jax.lax.fori_loop(0, chunks // unroll, chunk,
                                tuple(ref[rows, :] for ref in acc_refs))
        for ref, val in zip(acc_refs, acc):
            ref[rows, :] = val
        return carry

    jax.lax.fori_loop(0, bn // height, block, 0)

    @pl.when(j == pl.num_programs(1) - 1)
    def _merge():
        lane = jax.lax.broadcasted_iota(jnp.int32, (_LANES, slab), 0)

        def merge(qs):
            # Lane positions onto sublanes, the slab's queries onto lanes.
            b1, i1, b2, i2 = (ref[qs, :].T for ref in acc_refs)
            i1, i2 = i1 + lane, i2 + lane
            v1, a1 = _lex_min(b1, i1)
            win = i1 == a1
            v2, a2 = _lex_min(jnp.where(win, b2, b1), jnp.where(win, i2, i1))
            pn = src_ref[4:5, qs]
            for ref, val in zip(out_refs, (v1 + pn, a1, v2 + pn, a2)):
                ref[:, qs] = val
        slabs(merge)


def _scratch_shapes(bn: int) -> list:
    """Three query-broadcast planes, then the lane-wise best and runner-up
    scores and indices."""
    return ([pltpu.VMEM((bn, _LANES), jnp.float32)] * 3
            + [pltpu.VMEM((bn, _LANES), dtype)
               for dtype in (jnp.float32, jnp.int32) * 2])


def nn_search_kernel(src_aug: jax.Array, dst_aug: jax.Array,
                     *, bn: int = 512, bm: int | None = None,
                     interpret: bool | None = None):
    """Run the NN kernel on pre-augmented operands.

    Args:
      src_aug: (8, N) from ``ref.augment_source`` — N must be a multiple of bn.
      dst_aug: (8, M) from ``ref.augment_target`` — M must be a multiple of bm.
      bn, bm: query and target block sizes: bm a multiple of 128 (None:
        ``target_block``), bn of 128 or a divisor of 128 that holds whole
        register blocks (``vmem_bytes`` gives the VMEM they take).
      interpret: tri-state (``kernels.common``): None = auto (compiled on
        TPU, interpreter elsewhere).
    Returns:
      (best_d2, best_idx, next_d2, next_idx): the best and runner-up
      expansion scores, (N,) fp32 (unclamped), and their (N,) int32
      target indices; the earliest index wins ties.
    """
    n = src_aug.shape[1]
    m = dst_aug.shape[1]
    bm = target_block(interpret) if bm is None else bm
    assert src_aug.shape[0] == AUG_ROWS and dst_aug.shape[0] == AUG_ROWS
    assert n % bn == 0, (n, bn)
    assert m % bm == 0 and bm % _LANES == 0, (m, bm)
    grid = (n // bn, m // bm)
    interpret = default_interpret(interpret)
    slab, height, unroll = _blocks(bn, bm, interpret)
    assert bn % slab == 0 and slab % height == 0, (bn, height)

    kernel = functools.partial(_nn_kernel, bm=bm, slab=slab, height=height,
                               unroll=unroll)
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
        scratch_shapes=_scratch_shapes(bn),
        name=KERNEL_NAME,
        **pallas_call_kwargs(interpret, ("parallel", "arbitrary")),
    )
    return tuple(row[0] for row in call(src_aug, dst_aug))


def vmem_bytes(bn: int, bm: int) -> dict:
    """Static VMEM budget of one grid step (the Table II analogue)."""
    src = AUG_ROWS * bn * 4
    dst = AUG_ROWS * bm * 4
    outs = 4 * bn * 4  # four (1, bn) rows, laid out in (1, 128) tiles
    queries = 3 * bn * _LANES * 4
    carries = 4 * bn * _LANES * 4
    return {
        "src_tile": src, "dst_tile": dst, "outputs": outs,
        "query_scratch": queries, "carry_scratch": carries,
        "total_single": src + dst + outs + queries + carries,
        # in/out tiles are double-buffered by the pipeline; scratch is not.
        "total_double_buffered": 2 * (src + dst + outs) + queries + carries,
    }
