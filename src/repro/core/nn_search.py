"""Brute-force exact nearest-neighbour search — the heart of FPPS.

The paper replaces k-d trees with a fully parallel exact search (Discussion
§V-A: tree traversal is sequential/branchy; brute force is dense, regular and
pipelineable). On TPU this argument is even stronger: the pairwise-distance
expansion

    ||p - q||² = ||p||² + ||q||² - 2 p·q

turns the O(N·M) distance grid into an (N,3)x(3,M) matmul — MXU work — plus
rank-1 updates, and the argmin is a lane reduction on the VPU.

Two implementations:
  * this module — pure XLA (jnp) with explicit target-chunking so the peak
    memory stays bounded; used by the default path, the distributed path, and
    the dry-run (it lowers on any backend).
  * ``repro.kernels.nn_search`` — the Pallas TPU kernel with explicit VMEM
    BlockSpec tiling and a fused transform prologue (validated in interpret
    mode against ``repro.kernels.ref``).

Both return (min_dist_sq, argmin_index) exactly. The expansion's
cancellation at scene scale (terms of ~3000 m² cancelling to ~1e-3 m²)
costs about 1e-3 m² of absolute error, enough to swap near-tied
neighbours, so both searchers keep the two best candidates by expansion
score and pick between them on the directly computed distance
(:func:`merge_two_best`, :func:`closer_of_two`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def pairwise_sq_dists(src: jax.Array, dst: jax.Array) -> jax.Array:
    """(N,3),(M,3) -> (N,M) squared distances via the matmul expansion."""
    # fp32 accumulation: metric data; see DESIGN.md §2 (precision note).
    sn = jnp.sum(src * src, axis=-1, keepdims=True)          # (N,1)
    dn = jnp.sum(dst * dst, axis=-1, keepdims=True).T        # (1,M)
    cross = jnp.matmul(src, dst.T, precision="highest")                # MXU
    d2 = sn + dn - 2.0 * cross
    return jnp.maximum(d2, 0.0)  # clamp fp roundoff


def merge_two_best(best, new):
    """Running two-best merge: ``best`` and ``new`` are ``(d1, i1, d2,
    i2)`` score/index pairs with ``d1 <= d2``. Returns the two smallest of
    the four; on equal scores the running (earlier) entry wins, matching
    the first-match argmin."""
    b1, j1, b2, j2 = best
    v1, i1, v2, i2 = new
    lead = v1 < b1
    alt_d = jnp.where(lead, jnp.where(v2 < b1, v2, b1),
                      jnp.where(v1 < b2, v1, b2))
    alt_i = jnp.where(lead, jnp.where(v2 < b1, i2, j1),
                      jnp.where(v1 < b2, i1, j2))
    return (jnp.where(lead, v1, b1), jnp.where(lead, i1, j1), alt_d, alt_i)


def closer_of_two(src, dst, i1, i2, has_second):
    """Of each query's two candidates ``dst[i1]`` and ``dst[i2]`` (the
    second only where ``has_second``), the one nearer by the directly
    computed distance; ties keep ``i1``. Returns ``(d2, idx, points)``."""
    def direct(i):
        pts = jnp.take(dst, i, axis=0)
        diff = src - pts
        return jnp.sum(diff * diff, axis=-1).astype(jnp.float32), pts

    e1, p1 = direct(i1)
    e2, p2 = direct(i2)
    use2 = jnp.logical_and(has_second, e2 < e1)
    return (jnp.where(use2, e2, e1), jnp.where(use2, i2, i1),
            jnp.where(use2[:, None], p2, p1))


def nn_search(src: jax.Array, dst: jax.Array, *, chunk: int = 2048,
              dst_valid: jax.Array | None = None,
              score_dtype: str = "fp32",
              return_points: bool = False):
    """Exact NN of each src point in dst.

    Args:
      src: (N, 3) query points.
      dst: (M, 3) target cloud.
      chunk: target-cloud chunk size — bounds the (N, chunk) live tile, the
        XLA analogue of the kernel's BlockSpec. M need not divide chunk.
      dst_valid: optional (M,) bool mask for padded target slots.
      score_dtype: "fp32" (exact, default) or "bf16" — halves the distance
        -tile HBM traffic (§Perf iteration A2). bf16 scores can mis-rank
        near-tied candidates (~1e-2 relative); ICP accuracy parity under
        bf16 is validated empirically in the benchmark suite and it stays
        opt-in.
      return_points: additionally return the gathered winner points
        ``dst[idx]``. The exact-d2 epilogue already gathers them, so this
        lets ICP's correspondence stage reuse that gather instead of
        issuing a second ``jnp.take`` over the target cloud.

    Returns:
      (d2, idx[, points]): (N,) squared distance to NN, (N,) int32 index
      into dst, and with ``return_points`` the (N, 3) matched points.
    """
    n = src.shape[0]
    m = dst.shape[0]
    pad = (-m) % chunk
    if pad:
        # Large-but-FINITE padding: inf coords would produce inf-inf = NaN in
        # the matmul expansion and force a full NaN-scrub read+write pass
        # over every (N, chunk) distance tile (~1/3 of the sweep's HBM
        # traffic — §Perf iteration A1). 1e15 keeps padded d2 ~1e30, far
        # beyond any metric scene, with no NaN path.
        dst = jnp.concatenate(
            [dst, jnp.full((pad, 3), jnp.asarray(1e15, dst.dtype))], axis=0)
        if dst_valid is not None:
            dst_valid = jnp.concatenate(
                [dst_valid, jnp.zeros((pad,), dtype=bool)], axis=0)
    m_padded = dst.shape[0]
    n_chunks = m_padded // chunk
    dst_chunks = dst.reshape(n_chunks, chunk, 3)
    valid_chunks = (dst_valid.reshape(n_chunks, chunk)
                    if dst_valid is not None else None)

    sn = jnp.sum(src * src, axis=-1)  # (N,) hoisted out of the scan
    lowp = score_dtype == "bf16"
    src_c = src.astype(jnp.bfloat16) if lowp else src

    def body(carry, xs):
        if valid_chunks is None:
            dchunk, base = xs
            valid = None
        else:
            dchunk, base, valid = xs
        dn = jnp.sum(dchunk * dchunk, axis=-1)                # (chunk,)
        if lowp:
            # bf16 tile end-to-end: the MXU emits bf16, the (N, chunk)
            # buffer and its argmin read are half-width.
            cross = jax.lax.dot_general(
                src_c, dchunk.astype(jnp.bfloat16).T,
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.bfloat16)
            d2 = (sn.astype(jnp.bfloat16)[:, None]
                  + dn.astype(jnp.bfloat16)[None, :] - 2.0 * cross)
        else:
            cross = jnp.matmul(src, dchunk.T, precision="highest")  # (N, chunk) MXU
            d2 = sn[:, None] + dn[None, :] - 2.0 * cross
        if valid is not None:
            d2 = jnp.where(valid[None, :], d2, jnp.asarray(jnp.inf, d2.dtype))
        i1 = jnp.argmin(d2, axis=1)
        cols = jax.lax.broadcasted_iota(jnp.int32, d2.shape, 1)
        rest = jnp.where(cols == i1[:, None], jnp.asarray(jnp.inf, d2.dtype),
                         d2)
        i2 = jnp.argmin(rest, axis=1)

        def score(tile, i):
            return jnp.take_along_axis(tile, i[:, None],
                                       axis=1)[:, 0].astype(jnp.float32)

        new = (score(d2, i1), base + i1.astype(jnp.int32),
               score(rest, i2), base + i2.astype(jnp.int32))
        return merge_two_best(carry, new), None

    # Carry pinned to fp32: scores are always cast to fp32, so an
    # src.dtype carry would silently upcast (or mis-compare) for bf16
    # callers.
    inf = jnp.full((n,), jnp.inf, dtype=jnp.float32)
    zero = jnp.zeros((n,), dtype=jnp.int32)
    init = (inf, zero, inf, zero)
    bases = jnp.arange(n_chunks, dtype=jnp.int32) * chunk
    xs = ((dst_chunks, bases) if valid_chunks is None
          else (dst_chunks, bases, valid_chunks))
    (score1, idx1, score2, idx2), _ = jax.lax.scan(body, init, xs)
    # Pick between the two best by the direct distance, which is also the
    # returned d2 (exact). Keep inf where nothing was valid.
    exact, best_idx, matched = closer_of_two(src, dst, idx1, idx2,
                                             jnp.isfinite(score2))
    best_d2 = jnp.where(jnp.isinf(score1), score1, exact)
    if return_points:
        return jnp.maximum(best_d2, 0.0), best_idx, matched
    return jnp.maximum(best_d2, 0.0), best_idx


@functools.partial(jax.jit, static_argnames=("chunk",))
def nn_search_jit(src, dst, chunk: int = 2048):
    return nn_search(src, dst, chunk=chunk)
