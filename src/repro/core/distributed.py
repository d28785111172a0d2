"""Multi-device FPPS: shard_map registration entry points.

The production scale-out path is **stream sharding** (DESIGN.md §14): a
1-D ``("streams",)`` device mesh where each device owns a contiguous
block of independent odometry streams — their scans, their registrations,
AND their resident submaps. Streams never exchange data, so the shard
body (:func:`stream_sharded_icp`: ``vmap(icp)`` over the device's lane
block) contains **zero collectives**; the only device-boundary traffic is
the host's bulk result fetch, once per fleet round. This is what the
``sharded-slots`` engine and the sharded registration service run on.

Two **legacy single-frame** configurations predate it (DESIGN.md §4) and
are kept for the workloads stream sharding does not cover — registrations
whose *individual* target cloud outgrows one device:

1. **Point-sharded fleet mode** (`batched_icp_sharded`): a batch of
   frame-pairs sharded over ``("pod", "data")``; within each frame the
   *target* cloud is sharded over ``"model"``. Per ICP iteration the only
   collectives are an all-gather of per-shard winner (distance, point)
   candidates over ``model`` — the cross-shard generalisation of the
   paper's CMP comparison tree; the Kabsch moments are computed
   redundantly on every model-rank from the gathered winners (replicated
   math on 4k points beats a psum round-trip).

2. **Giant-frame mode** (`icp_sharded`): one registration whose target
   cloud is sharded over *every* device — city-scale map-to-scan
   alignment. Same combine, wider axis.

Design note (legacy combine): we gather winner *points*, never indices. A
global-index gather (`dst[idx]` across shards) would be an all-to-all with
data-dependent addressing; gathering the (d2, xyz) winner tuple is a
dense, fixed-size all-gather of n·4 floats per shard — exactly the kind
of regular collective the paper's streaming philosophy calls for.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.icp import ICPParams, ICPResult, icp, icp_fixed_iterations
from repro.core.nn_search import nn_search
from repro.data.voxelize import VoxelMap


# -- stream sharding (the production scale-out path) ------------------------

def streams_mesh(devices: int | None = None) -> Mesh:
    """The 1-D ``("streams",)`` device mesh stream sharding runs on.

    ``devices`` takes the first N local devices (None = all). Device ``d``
    owns lane block ``[d*L, (d+1)*L)`` of every ``(S, ...)`` fleet array
    placed with ``P("streams")`` — the slot->device mapping the sharded
    registration service builds its placement policy on.
    """
    devs = jax.devices()
    n = len(devs) if devices is None else int(devices)
    if not 1 <= n <= len(devs):
        raise ValueError(f"devices must be in [1, {len(devs)}], got {n}")
    return Mesh(np.array(devs[:n]), ("streams",))


def stream_sharded_icp(mesh: Mesh, src_b: jax.Array, dst_b: jax.Array,
                       params: ICPParams = ICPParams(), *,
                       initial_transforms: jax.Array | None = None,
                       src_valid: jax.Array | None = None,
                       dst_valid: jax.Array | None = None,
                       nn_fn=None) -> ICPResult:
    """S independent registrations sharded over a ``("streams",)`` mesh.

    Every ``(S, ...)`` input shards along its lane axis; each device runs
    ``vmap(icp)`` over its own contiguous block of ``S / D`` lanes. There
    are NO collectives in the body — lanes are independent by
    construction — so a lane's result is bitwise identical for any mesh
    size serving the same lanes-per-device block width (the weak-scaling
    parity contract the sharded service's tests assert). Masks/warm
    starts default to all-ones / identity; ``nn_fn`` swaps the
    correspondence searcher exactly as in ``core.icp.icp``, and a
    :class:`~repro.data.voxelize.VoxelMap` batch of targets is searched
    lane by lane through its lattice (``core.engine.slot_lane``).

    Call it inside ``jax.jit`` for one fused executable (the
    ``sharded-slots`` engine does); inputs not already placed with
    ``P("streams")`` are resharded automatically at the jit boundary.
    """
    S = src_b.shape[0]
    D = mesh.shape["streams"]
    if S % D:
        raise ValueError(f"lane count {S} must divide the streams mesh "
                         f"size {D}")
    if initial_transforms is None:
        initial_transforms = jnp.broadcast_to(
            jnp.eye(4, dtype=src_b.dtype), (S, 4, 4))
    if src_valid is None:
        src_valid = jnp.ones(src_b.shape[:2], bool)
    on_lattice = isinstance(dst_b, VoxelMap)
    if dst_valid is None:
        points = dst_b.points if on_lattice else dst_b
        dst_valid = jnp.ones(points.shape[:2], bool)

    from repro.core.engine import slot_lane
    spec = P("streams")
    # a voxel map's points and origins shard with their lanes
    out_specs = ICPResult(T=spec, rmse=spec, iterations=spec,
                          converged=spec, inlier_frac=spec, degenerate=spec,
                          lattice_overflow=spec if on_lattice else None)
    fn = jax.shard_map(jax.vmap(slot_lane(params, nn_fn)), mesh=mesh,
                       in_specs=(spec,) * 5, out_specs=out_specs,
                       check_vma=False)
    return fn(src_b, dst_b, initial_transforms, src_valid, dst_valid)


# -- legacy point-sharded paths ---------------------------------------------


def _local_correspond(src_t: jax.Array, dst_local: jax.Array,
                      chunk: int, axis_names: Sequence[str],
                      score_dtype: str = "fp32",
                      normals_local: jax.Array | None = None):
    """Local exact NN + cross-shard winner combine.

    Returns (d2, matched_points[, matched_normals]) replicated across
    ``axis_names``. Winner normals ride the same dense all-gather as the
    winner points — the (d2, xyz, nxyz) tuple is still a fixed-size
    regular collective, no cross-shard index gather.
    """
    d2, idx_local = nn_search(src_t, dst_local, chunk=chunk,
                              score_dtype=score_dtype)
    matched_local = jnp.take(dst_local, idx_local, axis=0)        # (n, 3)
    cols = [d2[:, None], matched_local]
    if normals_local is not None:
        cols.append(jnp.take(normals_local, idx_local, axis=0))   # (n, 3)
    cand = jnp.concatenate(cols, axis=1)                          # (n, 4|7)
    for ax in axis_names:  # combine one axis at a time: live buffer stays (S,n,C)
        gathered = jax.lax.all_gather(cand, ax)                   # (S, n, C)
        win = jnp.argmin(gathered[..., 0], axis=0)                # (n,)
        cand = jnp.take_along_axis(gathered, win[None, :, None], axis=0)[0]
    if normals_local is None:
        return cand[:, 0], cand[:, 1:4]
    return cand[:, 0], cand[:, 1:4], cand[:, 4:7]


def distributed_nn_search(mesh: Mesh, src: jax.Array, dst: jax.Array,
                          *, target_axes: Sequence[str] = ("model",),
                          chunk: int = 2048):
    """Sharded exact NN (d2, global idx) — for tests/benchmarks.

    src is replicated; dst is sharded along its first dim over target_axes.
    """
    axes = tuple(target_axes)

    def body(src_rep, dst_local):
        m_local = dst_local.shape[0]
        d2, idx_local = nn_search(src_rep, dst_local, chunk=chunk)
        # global index = shard offset + local index
        offset = jnp.zeros((), jnp.int32)
        stride = m_local
        for ax in reversed(axes):
            offset = offset + jax.lax.axis_index(ax).astype(jnp.int32) * stride
            stride = stride * jax.lax.axis_size(ax)
        cand = jnp.concatenate(
            [d2[:, None], (idx_local + offset)[:, None].astype(d2.dtype)], axis=1)
        for ax in axes:
            g = jax.lax.all_gather(cand, ax)                      # (S, n, 2)
            win = jnp.argmin(g[..., 0], axis=0)
            cand = jnp.take_along_axis(g, win[None, :, None], axis=0)[0]
        return cand[:, 0], cand[:, 1].astype(jnp.int32)

    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(), P(axes)),
                       out_specs=(P(), P()), check_vma=False)
    return fn(src, dst)


def icp_sharded(mesh: Mesh, source: jax.Array, target: jax.Array,
                params: ICPParams = ICPParams(),
                *, target_axes: Sequence[str] = ("data", "model"),
                fixed_iterations: bool = False,
                dst_normals: jax.Array | None = None) -> ICPResult:
    """LEGACY giant-frame ICP: one registration, target sharded over
    target_axes (city-scale map-to-scan; see module docstring for when to
    prefer stream sharding).

    ``dst_normals`` (M, 3) — required for ``minimizer="point_to_plane"`` —
    is sharded alongside the target; estimate it on the *unsharded* cloud
    (shard-local estimation would degrade at shard boundaries).
    """
    axes = tuple(target_axes)
    if params.minimizer == "point_to_plane" and dst_normals is None:
        raise ValueError("icp_sharded with minimizer='point_to_plane' "
                         "needs dst_normals (estimate on the full target)")

    def body(src_rep, dst_local, nrm_local=None):
        cfn = functools.partial(_local_correspond, dst_local=dst_local,
                                chunk=params.chunk, axis_names=axes,
                                score_dtype=params.score_dtype,
                                normals_local=nrm_local)
        runner = icp_fixed_iterations if fixed_iterations else icp
        return runner(src_rep, None, params, correspond_fn=cfn)

    out_specs = ICPResult(T=P(), rmse=P(), iterations=P(), converged=P(),
                          inlier_frac=P(), degenerate=P())
    if dst_normals is None:
        fn = jax.shard_map(body, mesh=mesh, in_specs=(P(), P(axes)),
                           out_specs=out_specs, check_vma=False)
        return fn(source, target)
    fn = jax.shard_map(body, mesh=mesh, in_specs=(P(), P(axes), P(axes)),
                       out_specs=out_specs, check_vma=False)
    return fn(source, target, dst_normals)


def batched_icp_sharded(mesh: Mesh, src_batch: jax.Array,
                        dst_batch: jax.Array,
                        params: ICPParams = ICPParams(),
                        *, frame_axes: Sequence[str] = ("data",),
                        target_axes: Sequence[str] = ("model",),
                        fixed_iterations: bool = True,
                        src_valid: jax.Array | None = None,
                        dst_normals: jax.Array | None = None) -> ICPResult:
    """LEGACY point-sharded fleet mode: (F, N, 3) sources, (F, M, 3)
    targets. Kept (and regression-tested against the xla engine) for
    frames whose individual target cloud outgrows one device; for
    fleet-scale serving use :func:`stream_sharded_icp` / the
    ``sharded-slots`` engine, which shards *streams* with zero
    collectives instead of paying the per-iteration winner all-gather.

    Frames shard over ``frame_axes`` (use ("pod", "data") on the multi-pod
    mesh); each frame's target shards over ``target_axes``. Defaults to the
    scan-based fixed-iteration ICP: under vmap a while_loop would run every
    frame for the worst frame's trip count anyway, and the static schedule
    is what the dry-run/roofline analyses.

    ``src_valid`` (F, N) zero-weights bucket-padded source rows (see
    ``repro.data.collate``); padded *target* rows must carry far-sentinel
    coordinates so the local argmin never picks them — the per-shard winner
    combine has no mask channel by design (the (d2, xyz) tuple stays dense).
    ``dst_normals`` (F, M, 3) — required for the plane minimiser — shards
    like the targets and rides the winner combine as three extra columns.
    """
    f_axes, t_axes = tuple(frame_axes), tuple(target_axes)
    if src_valid is None:
        src_valid = jnp.ones(src_batch.shape[:2], dtype=src_batch.dtype)
    if params.minimizer == "point_to_plane" and dst_normals is None:
        raise ValueError("batched_icp_sharded with "
                         "minimizer='point_to_plane' needs dst_normals "
                         "(estimate per frame on the unsharded targets)")

    def body(src_b, dst_b, sv_b, nrm_b=None):
        def one(src, dst_local, sv, nrm_local):
            cfn = functools.partial(_local_correspond, dst_local=dst_local,
                                    chunk=params.chunk, axis_names=t_axes,
                                    score_dtype=params.score_dtype,
                                    normals_local=nrm_local)
            runner = icp_fixed_iterations if fixed_iterations else icp
            return runner(src, None, params, correspond_fn=cfn, src_valid=sv)
        if nrm_b is None:
            return jax.vmap(lambda s, d, v: one(s, d, v, None))(
                src_b, dst_b, sv_b)
        return jax.vmap(one)(src_b, dst_b, sv_b, nrm_b)

    out_specs = ICPResult(T=P(f_axes), rmse=P(f_axes), iterations=P(f_axes),
                          converged=P(f_axes), inlier_frac=P(f_axes),
                          degenerate=P(f_axes))
    if dst_normals is None:
        fn = jax.shard_map(body, mesh=mesh,
                           in_specs=(P(f_axes), P(f_axes, t_axes), P(f_axes)),
                           out_specs=out_specs, check_vma=False)
        return fn(src_batch, dst_batch, src_valid)
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(f_axes), P(f_axes, t_axes), P(f_axes),
                             P(f_axes, t_axes)),
                       out_specs=out_specs, check_vma=False)
    return fn(src_batch, dst_batch, src_valid, dst_normals)


def shard_inputs(mesh: Mesh, src_batch, dst_batch,
                 frame_axes=("data",), target_axes=("model",)):
    """Place host arrays with the shardings batched_icp_sharded expects."""
    s_src = NamedSharding(mesh, P(tuple(frame_axes)))
    s_dst = NamedSharding(mesh, P(tuple(frame_axes), tuple(target_axes)))
    return jax.device_put(src_batch, s_src), jax.device_put(dst_batch, s_dst)
