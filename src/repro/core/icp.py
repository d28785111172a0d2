"""Jittable ICP — the full FPPS pipeline as one fused XLA computation.

Mirrors the paper's four stages per iteration (§II):
  1. correspondence estimation  -> nn_search (brute force, exact)
  2. transformation estimation  -> masked Kabsch (covariance accumulator + SVD)
  3. point-cloud update         -> transform_points (kept implicit: we always
                                   transform the *original* source by the
                                   cumulative T, avoiding drift from repeated
                                   rounding of the cloud itself)
  4. convergence check          -> transform_delta(T_j) < epsilon, or
                                   iteration cap (paper: 50)

The whole loop is a ``lax.while_loop`` so a frame registration is a single
device program — the TPU analogue of the paper's "all data stays on-chip".

Correspondence rejection: the paper's setMaxCorrespondenceDistance filter is
a weight mask fed to the weighted Kabsch step (zero-weight pairs contribute
nothing to the covariance), exactly like PCL's behaviour of dropping
out-of-range pairs.

Minimiser choice (DESIGN.md §9): ``minimizer="point_to_plane"`` swaps the
Kabsch step for the linearised point-to-plane Gauss-Newton step
(``core.point_to_plane``), which needs per-correspondence target normals —
either supplied by the caller (``target_normals``) or estimated once at
trace scope from the target cloud (``repro.data.normals``). Robust
reweighting (``robust_kernel``) applies to either minimiser, on top of the
distance gate.
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import transform as tf
from repro.core.nn_search import nn_search
from repro.core.point_to_plane import (robust_weights, solve_normal_equations,
                                       solve_point_to_plane)
from repro.data.collate import PAD_SENTINEL

MINIMIZERS = ("point_to_point", "point_to_plane")


def scrub_nonfinite(points: jax.Array | None,
                    valid: jax.Array | None = None):
    """Sentinel-mask non-finite rows at the engine boundary (DESIGN.md §12).

    A single NaN/Inf row would otherwise poison every downstream
    accumulation it touches: the matmul distance expansion (NaN spreads
    along its whole row), voxel-grid origin/cell assignment
    (``floor(NaN)``), and the fused moment sums. One elementwise pass
    replaces such rows with the far ``PAD_SENTINEL`` (the exact convention
    collate pads already use — never wins an argmin, always fails the
    gate) and drops them from ``valid`` so minimiser weights and inlier
    denominators exclude them.

    Works on (..., N, 3) clouds with (..., N) masks; ``points=None``
    passes through (engines with correspond/fused closures may have no
    target cloud). For all-finite inputs the rewrite is the identity, so
    clean-path results are bit-identical.
    """
    if points is None:
        return None, valid
    finite = jnp.all(jnp.isfinite(points), axis=-1)
    valid = finite if valid is None else jnp.logical_and(valid, finite)
    points = jnp.where(valid[..., None], points,
                       jnp.asarray(PAD_SENTINEL, points.dtype))
    return points, valid


class ICPParams(NamedTuple):
    max_iterations: int = 50
    max_correspondence_distance: float = 1.0
    transformation_epsilon: float = 1e-5
    chunk: int = 2048  # target-cloud tile size for the NN sweep
    score_dtype: str = "fp32"  # "bf16": half-width distance tiles (§Perf A2)
    minimizer: str = "point_to_point"  # | "point_to_plane" (DESIGN.md §9)
    robust_kernel: str = "none"        # | "huber" | "tukey"
    robust_scale: float = 0.5          # huber delta / tukey cutoff, metres
    fused: bool = False  # single-pass Pallas iteration (DESIGN.md §11)


class ICPState(NamedTuple):
    T: jax.Array           # (4,4) cumulative transform
    delta: jax.Array       # last incremental transform_delta
    rmse: jax.Array        # inlier RMSE of the last iteration
    iteration: jax.Array   # int32
    inlier_frac: jax.Array
    degenerate: jax.Array  # bool: an iteration saw zero gate/robust weight


class ICPResult(NamedTuple):
    T: jax.Array
    rmse: jax.Array
    iterations: jax.Array
    converged: jax.Array
    inlier_frac: jax.Array
    degenerate: jax.Array


# Total weight below this is "no correspondence evidence at all": the
# minimiser systems are singular (Kabsch covariance / Gauss-Newton normal
# matrix of all-zero weights), so the iteration freezes instead of solving.
_DEGENERATE_WEIGHT_SUM = 1e-6


def _icp_iteration(source, state: ICPState, params: ICPParams,
                   correspond_fn: Callable,
                   src_valid: jax.Array | None = None):
    """One ICP iteration. ``correspond_fn(src_t) -> (d2, matched)`` — or
    ``(d2, matched, normals)`` for the point-to-plane minimiser — supplies
    correspondences; for the distributed engine ``matched`` (and the winner
    normals) are gathered *values* (cross-shard index gathers never happen).

    ``src_valid`` (N,) masks padded source rows (shape-bucketed batching):
    they get zero minimiser weight and are excluded from the inlier
    fraction's denominator, so a padded registration is numerically
    identical to the unpadded one.
    """
    src_t = tf.transform_points(state.T, source)
    out = correspond_fn(src_t)
    normals = out[2] if len(out) == 3 else None
    d2, matched = out[0], out[1]
    weights = (d2 <= params.max_correspondence_distance ** 2).astype(source.dtype)
    if src_valid is not None:
        weights = weights * src_valid.astype(source.dtype)
    plane = params.minimizer == "point_to_plane"
    if plane and normals is None:
        raise ValueError("minimizer='point_to_plane' needs matched normals: "
                         "pass target_normals (or a correspond_fn returning "
                         "a (d2, matched, normals) triple)")
    if params.robust_kernel != "none":
        # IRLS weight from the residual the active minimiser optimises.
        if plane:
            residual = jnp.abs(jnp.sum(normals * (src_t - matched), axis=-1))
        else:
            residual = jnp.sqrt(jnp.maximum(d2, 0.0))
        weights = weights * robust_weights(residual, params.robust_kernel,
                                           params.robust_scale)
    # Zero-inlier freeze: when the gate (or robust reweighting) rejects
    # every correspondence the minimiser systems are singular — the Kabsch
    # covariance and the Gauss-Newton normal matrix are all zeros, so a
    # solve would produce an arbitrary (or NaN) step and the cumulative
    # product would lock it in. Freeze instead: identity delta (the loop
    # terminates), rmse = +inf (there is no inlier error to report), and a
    # sticky ``degenerate`` flag so callers can tell this apart from
    # genuine convergence.
    degenerate = jnp.sum(weights) <= _DEGENERATE_WEIGHT_SUM
    if plane:
        T_step = solve_point_to_plane(src_t, matched, normals, weights)
    else:
        T_step = tf.estimate_rigid_transform(src_t, matched, weights)
    T_delta = jnp.where(degenerate, jnp.eye(4, dtype=source.dtype), T_step)
    # cumulative product, paper eq. (3)
    T_new = jnp.matmul(T_delta, state.T, precision="highest")
    delta = tf.transform_delta(T_delta)
    err = jnp.where(degenerate, jnp.asarray(jnp.inf, source.dtype),
                    tf.rmse(tf.transform_points(T_delta, src_t), matched,
                            weights))
    if src_valid is None:
        inlier_frac = jnp.mean(weights)
    else:
        denom = jnp.maximum(jnp.sum(src_valid.astype(source.dtype)), 1.0)
        inlier_frac = jnp.sum(weights) / denom
    return ICPState(T=T_new, delta=delta, rmse=err,
                    iteration=state.iteration + 1, inlier_frac=inlier_frac,
                    degenerate=jnp.logical_or(state.degenerate, degenerate))


def _fused_icp_iteration(source, state: ICPState, params: ICPParams,
                         fused_fn: Callable,
                         src_valid: jax.Array | None = None):
    """One ICP iteration through the fused Pallas moment kernel
    (``repro.kernels.fused_icp``, DESIGN.md §11).

    ``fused_fn(src_t, src_valid)`` runs correspondence search, gating,
    IRLS weighting and moment accumulation as a single device pass and
    returns the Σ-moments (:class:`PointMoments` / :class:`PlaneMoments`);
    this host epilogue only does the O(1) solve and bookkeeping. The
    semantics mirror :func:`_icp_iteration` exactly: same weights, same
    degenerate freeze, same post-step rmse against the *pre-step*
    correspondences (computed algebraically via ``rmse_from_moments``).
    """
    src_t = tf.transform_points(state.T, source)
    m = fused_fn(src_t, src_valid)
    degenerate = m.sw <= _DEGENERATE_WEIGHT_SUM
    if params.minimizer == "point_to_plane":
        T_step = solve_normal_equations(m.A, m.b).astype(source.dtype)
    else:
        T_step = tf.estimate_from_moments(m.sw, m.sp, m.sq,
                                          m.spq).astype(source.dtype)
    T_delta = jnp.where(degenerate, jnp.eye(4, dtype=source.dtype), T_step)
    T_new = jnp.matmul(T_delta, state.T, precision="highest")
    delta = tf.transform_delta(T_delta)
    err = jnp.where(degenerate, jnp.asarray(jnp.inf, source.dtype),
                    tf.rmse_from_moments(T_delta, m.sw, m.sp, m.sq, m.spq,
                                         m.spp, m.sqq).astype(source.dtype))
    if src_valid is None:
        denom = jnp.asarray(source.shape[0], source.dtype)
    else:
        denom = jnp.maximum(jnp.sum(src_valid.astype(source.dtype)), 1.0)
    inlier_frac = (m.sw / denom).astype(source.dtype)
    return ICPState(T=T_new, delta=delta, rmse=err,
                    iteration=state.iteration + 1, inlier_frac=inlier_frac,
                    degenerate=jnp.logical_or(state.degenerate, degenerate))


def _resolve_fused_fn(target, params: ICPParams, fused_fn,
                      dst_valid, target_normals):
    """Default fused iteration when ``params.fused`` is set without an
    explicit ``fused_fn``: resident counting-sort grid over the target
    (+ trace-scope normals for the plane minimiser)."""
    if fused_fn is not None:
        return fused_fn
    if target is None:
        raise ValueError("params.fused needs a target cloud (or an explicit "
                         "fused_fn) to build the resident grid from")
    if params.minimizer == "point_to_plane" and target_normals is None:
        target_normals = _auto_target_normals(target, dst_valid)
    from repro.kernels.fused_icp import default_fused_fn
    return default_fused_fn(target, params, dst_valid=dst_valid,
                            target_normals=target_normals)


def _default_correspond_fn(target: jax.Array, params: ICPParams,
                           nn_fn: Callable | None,
                           dst_valid: jax.Array | None = None,
                           target_normals: jax.Array | None = None) -> Callable:
    if nn_fn is None:
        # Fused winner gather: the exact-d2 epilogue inside nn_search
        # already gathers dst[idx], so ask for the points and skip the
        # second jnp.take over the target that the generic path needs.
        def nn_fn(s, t):
            return nn_search(s, t, chunk=params.chunk,
                             score_dtype=params.score_dtype,
                             dst_valid=dst_valid, return_points=True)
    elif dst_valid is not None:
        # Custom searchers (Pallas kernel, user callables) take only
        # (src, dst): mask padded target rows by moving them far outside any
        # metric scene, so they can never win the argmin nor pass the gate.
        target = jnp.where(dst_valid[:, None], target,
                           jnp.asarray(1e6, target.dtype))

    def correspond(src_t):
        # Searchers may return (d2, idx) or the fused (d2, idx, points).
        out = nn_fn(src_t, target)
        if len(out) == 3:
            d2, idx, matched = out
        else:
            d2, idx = out
            matched = jnp.take(target, idx, axis=0)
        if target_normals is None:
            return d2, matched
        # Winner normals ride the same index gather (invalid-normal rows
        # are zero vectors, which the plane solve ignores by construction).
        return d2, matched, jnp.take(target_normals, idx, axis=0)

    return correspond


def _check_minimizer(params: ICPParams) -> None:
    if params.minimizer not in MINIMIZERS:
        raise ValueError(f"unknown minimizer {params.minimizer!r}; "
                         f"expected one of {MINIMIZERS}")


def _auto_target_normals(target: jax.Array | None,
                         dst_valid: jax.Array | None):
    """Estimate target normals at trace scope (once per frame) when the
    plane minimiser is selected but the caller supplied none."""
    if target is None:
        raise ValueError("minimizer='point_to_plane' needs a target cloud "
                         "(or explicit target_normals) to estimate normals "
                         "from")
    from repro.data.normals import default_target_normals
    return default_target_normals(target, dst_valid)


def icp(source: jax.Array, target: jax.Array | None,
        params: ICPParams = ICPParams(),
        initial_transform: jax.Array | None = None,
        nn_fn: Callable | None = None,
        correspond_fn: Callable | None = None,
        src_valid: jax.Array | None = None,
        dst_valid: jax.Array | None = None,
        target_normals: jax.Array | None = None,
        fused_fn: Callable | None = None) -> ICPResult:
    """Run ICP aligning ``source`` (N,3) onto ``target`` (M,3).

    ``nn_fn`` lets callers swap the correspondence engine: the local XLA
    brute force (default), the Pallas kernel wrapper, or the shard_map
    distributed searcher. It must return (d2, idx) for (src, target).
    ``correspond_fn`` overrides the whole correspondence stage (src_t ->
    (d2, matched points[, matched normals])); target may then be None.
    ``src_valid`` (N,) / ``dst_valid`` (M,) mask padded rows of
    shape-bucketed clouds (see ``repro.data.collate``).
    ``target_normals`` (M,3) feeds the point-to-plane minimiser; when the
    plane minimiser is selected without them they are estimated from the
    target once at trace scope (``repro.data.normals`` defaults).

    With ``params.fused`` the whole iteration body runs through the fused
    Pallas moment kernel instead (``repro.kernels.fused_icp``):
    ``fused_fn(src_t, src_valid) -> moments`` replaces the correspondence
    stage entirely (``nn_fn``/``correspond_fn`` are then unused); when no
    ``fused_fn`` is supplied a resident-grid default is built from
    ``target`` at trace scope.

    Non-finite rows in either cloud are sentinel-masked at this boundary
    (:func:`scrub_nonfinite`) — a NaN point changes the inlier
    denominator, never the transform.
    """
    _check_minimizer(params)
    source, src_valid = scrub_nonfinite(source, src_valid)
    target, dst_valid = scrub_nonfinite(target, dst_valid)
    if params.fused:
        fused_fn = _resolve_fused_fn(target, params, fused_fn, dst_valid,
                                     target_normals)
    elif correspond_fn is None:
        if params.minimizer == "point_to_plane" and target_normals is None:
            target_normals = _auto_target_normals(target, dst_valid)
        correspond_fn = _default_correspond_fn(target, params, nn_fn,
                                               dst_valid, target_normals)
    if initial_transform is None:
        initial_transform = jnp.eye(4, dtype=source.dtype)

    init = ICPState(T=initial_transform,
                    delta=jnp.asarray(jnp.inf, source.dtype),
                    rmse=jnp.asarray(jnp.inf, source.dtype),
                    iteration=jnp.asarray(0, jnp.int32),
                    inlier_frac=jnp.asarray(0.0, source.dtype),
                    degenerate=jnp.asarray(False))

    def cond(state: ICPState):
        return jnp.logical_and(state.iteration < params.max_iterations,
                               state.delta > params.transformation_epsilon)

    def body(state: ICPState):
        if params.fused:
            return _fused_icp_iteration(source, state, params, fused_fn,
                                        src_valid)
        return _icp_iteration(source, state, params, correspond_fn, src_valid)

    final = jax.lax.while_loop(cond, body, init)
    converged = jnp.logical_and(final.delta <= params.transformation_epsilon,
                                jnp.logical_not(final.degenerate))
    return ICPResult(T=final.T, rmse=final.rmse, iterations=final.iteration,
                     converged=converged, inlier_frac=final.inlier_frac,
                     degenerate=final.degenerate)


def icp_fixed_iterations(source, target, params: ICPParams = ICPParams(),
                         initial_transform=None, nn_fn=None,
                         correspond_fn=None, src_valid=None,
                         dst_valid=None, target_normals=None,
                         fused_fn=None) -> ICPResult:
    """Unrolled-depth variant via lax.scan — fixed cost, used for the dry-run
    and roofline (while_loop trip counts are data-dependent; scan gives the
    compiler a static schedule, mirroring the paper's fixed 50-iteration cap)."""
    _check_minimizer(params)
    source, src_valid = scrub_nonfinite(source, src_valid)
    target, dst_valid = scrub_nonfinite(target, dst_valid)
    if params.fused:
        fused_fn = _resolve_fused_fn(target, params, fused_fn, dst_valid,
                                     target_normals)
    elif correspond_fn is None:
        if params.minimizer == "point_to_plane" and target_normals is None:
            target_normals = _auto_target_normals(target, dst_valid)
        correspond_fn = _default_correspond_fn(target, params, nn_fn,
                                               dst_valid, target_normals)
    if initial_transform is None:
        initial_transform = jnp.eye(4, dtype=source.dtype)
    init = ICPState(T=initial_transform,
                    delta=jnp.asarray(jnp.inf, source.dtype),
                    rmse=jnp.asarray(jnp.inf, source.dtype),
                    iteration=jnp.asarray(0, jnp.int32),
                    inlier_frac=jnp.asarray(0.0, source.dtype),
                    degenerate=jnp.asarray(False))

    def step(state, _):
        # Freeze once converged (weights of the no-op: keep state).
        active = state.delta > params.transformation_epsilon
        if params.fused:
            new = _fused_icp_iteration(source, state, params, fused_fn,
                                       src_valid)
        else:
            new = _icp_iteration(source, state, params, correspond_fn,
                                 src_valid)
        state = jax.tree_util.tree_map(
            lambda a, b: jnp.where(active, b, a), state, new)
        return state, None

    final, _ = jax.lax.scan(step, init, None, length=params.max_iterations)
    converged = jnp.logical_and(final.delta <= params.transformation_epsilon,
                                jnp.logical_not(final.degenerate))
    return ICPResult(T=final.T, rmse=final.rmse, iterations=final.iteration,
                     converged=converged, inlier_frac=final.inlier_frac,
                     degenerate=final.degenerate)


def icp_batch(sources: jax.Array, targets: jax.Array,
              params: ICPParams = ICPParams(),
              initial_transforms: jax.Array | None = None,
              nn_fn: Callable | None = None,
              src_valid: jax.Array | None = None,
              dst_valid: jax.Array | None = None,
              target_normals: jax.Array | None = None) -> ICPResult:
    """Batched multi-frame ICP: vmap of the scan-based fixed-iteration loop.

    Registers ``sources[k]`` (B,N,3) onto ``targets[k]`` (B,M,3) in one
    device program — the "target stays resident, iterations stream" shape of
    the paper (§II) lifted to a whole frame sequence, so one compiled
    executable amortises dispatch and keeps the MXU fed between frames.

    Uses ``icp_fixed_iterations`` because under vmap a while_loop would run
    every lane for the worst lane's trip count anyway; the per-pair freeze
    mask inside the scan body preserves each pair's early-convergence
    semantics, so results match per-pair ``icp`` to float tolerance.

    ``src_valid`` (B,N) / ``dst_valid`` (B,M) mask bucket padding from
    ``repro.data.collate.collate_pairs``; ``initial_transforms`` is an
    optional (B,4,4) warm start; ``target_normals`` is an optional (B,M,3)
    normal batch (auto-estimated per frame at trace scope when the plane
    minimiser is on). Returns an ``ICPResult`` whose every leaf has a
    leading batch axis.
    """
    b = sources.shape[0]
    if initial_transforms is None:
        initial_transforms = jnp.broadcast_to(
            jnp.eye(4, dtype=sources.dtype), (b, 4, 4))

    def one(src, dst, T0, sv, dv, tn):
        return icp_fixed_iterations(src, dst, params, T0, nn_fn=nn_fn,
                                    src_valid=sv, dst_valid=dv,
                                    target_normals=tn)

    return jax.vmap(one)(sources, targets, initial_transforms,
                         src_valid, dst_valid, target_normals)
