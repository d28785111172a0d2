"""Unified registration engine layer (DESIGN.md §3).

One abstraction owns everything that used to be scattered across
``core/api.py`` (per-call nn_fn construction), ``kernels/ops.py`` (target
residency) and ``core/distributed.py`` (fleet sharding):

  * **engine selection** — a string registry ("xla", "pallas",
    "distributed") plus user callables, resolved by :func:`get_engine`;
  * **persistent compilation caches** — each engine instance holds its
    jitted registration executables keyed by ``(kind, ICPParams)``; jit's
    own per-shape cache supplies the shape dimension, and shape-bucketed
    padding (``repro.data.collate``) keeps the number of distinct shapes
    small. Trace-time counters (:attr:`RegistrationEngine.trace_count`)
    make recompiles observable, which is what the regression tests assert;
  * **once-per-frame target preparation** — the Pallas engine builds the
    (8, M) augmented target at frame scope, outside the per-iteration
    loop body (the paper's target-cloud-in-BRAM analogue);
  * **batched multi-frame ICP** — :meth:`RegistrationEngine.register_batch`
    runs a whole padded frame-pair batch as one device program via
    ``core.icp.icp_batch``; :meth:`RegistrationEngine.register_pairs`
    collates and registers a list of pairs in ``fpps.pairs`` profiler
    spans and counts the work in :meth:`RegistrationEngine.report`.

Typical use::

    engine = get_engine("pallas")
    batch = collate_pairs([(src0, dst0), (src1, dst1), ...])
    res = engine.register_batch(batch.src, batch.dst, params,
                                src_valid=batch.src_valid,
                                dst_valid=batch.dst_valid)
    # res.T[k] is the 4x4 transform of pair k
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.icp import (ICPParams, ICPResult, icp, icp_batch,
                            icp_fixed_iterations, scrub_nonfinite)
from repro.core.nn_search_grid import lattice_nn_fn
from repro.data.collate import PAD_SENTINEL, bucket_size
from repro.data.voxelize import VoxelMap


def _cloud(target):
    """A target's points: a :class:`VoxelMap`'s, or the target itself."""
    return target.points if isinstance(target, VoxelMap) else target


def _mask_invalid(points: jax.Array, valid: jax.Array | None) -> jax.Array:
    """Move masked rows to the far sentinel so no searcher can match them."""
    if valid is None:
        return points
    return jnp.where(valid[..., None], points,
                     jnp.asarray(PAD_SENTINEL, points.dtype))


def _pad_device(points: jax.Array, size: int):
    """Device-side analogue of ``collate.pad_cloud`` — no host round-trip."""
    n = points.shape[0]
    padded = jnp.concatenate(
        [points, jnp.full((size - n, 3), PAD_SENTINEL, points.dtype)], axis=0)
    valid = jnp.arange(size) < n
    return padded, valid


def _target_normals(dst: jax.Array, params: ICPParams,
                    valid: jax.Array | None):
    """Trace-scope target normals for the plane minimiser, or None.

    Engines that sentinel-mask their target *before* the ICP loop (pallas,
    distributed) must estimate normals first, from the true valid mask —
    see ``default_target_normals``.
    """
    if params.minimizer != "point_to_plane":
        return None
    from repro.data.normals import default_target_normals
    return default_target_normals(dst, valid)


class RegistrationEngine:
    """Base engine: owns jit caches, bucketing, and the register API.

    Subclasses pick the correspondence searcher by overriding
    :meth:`_nn_fn` (simple swaps) or the ``_build_single``/``_build_batch``
    factories (engines that need frame-scope target preparation).
    """

    name = "base"

    def __init__(self, chunk: int = 2048):
        self._chunk = chunk
        self._cache: dict = {}     # (kind, ICPParams) -> jitted executable
        self._traces: list = []    # (kind, ICPParams, shapes) per (re)trace
        # register_pairs counters (see ``report``)
        self._pair_counts = dict.fromkeys(
            ("pair_batches", "pairs", "pair_iterations_run",
             "pair_iterations", "target_rows", "target_rows_padded"), 0)
        self._pending_iterations: list = []  # device (B,) arrays, unread

    # -- introspection -----------------------------------------------------
    @property
    def trace_count(self) -> int:
        """Number of times any cached executable was (re)traced — i.e.
        compiled. Stable across repeated same-shape calls; grows by one per
        new (kind, params, shape-bucket) combination."""
        return len(self._traces)

    @property
    def traces(self) -> tuple:
        """The trace log behind :attr:`trace_count`: one ``(kind,
        params-key, *shape-buckets)`` tuple per compilation, in order —
        what the retrace-freedom tests diff before and after a run."""
        return tuple(self._traces)

    def setup(self) -> None:
        """Backend init hook (the paper's .xclbin load). Idempotent."""
        _ = jax.devices()

    # -- subclass hooks ----------------------------------------------------
    def _nn_fn(self, params: ICPParams) -> Callable | None:
        """Correspondence searcher ``(src, dst) -> (d2, idx)``; None means
        the default XLA brute force inside ``core.icp``."""
        return None

    def _note_trace(self, kind: str, params: ICPParams, *shapes) -> None:
        self._traces.append((kind, params, shapes))

    def _jit(self, run: Callable, kind: str):
        """``jax.jit(run)`` under the module name ``jit_<engine>_<kind>``,
        so a profiler trace tells this engine's executables apart from
        the slot engines' batch executable, which keeps ``jit_run``."""
        run.__name__ = run.__qualname__ = (
            f"{self.name}_{kind}".replace("-", "_"))
        return jax.jit(run)

    def _build_single(self, params: ICPParams):
        nn_fn = self._nn_fn(params)

        def run(src, dst, T0, sv, dv):
            self._note_trace("single", params, src.shape, dst.shape)
            return icp(src, dst, params, T0, nn_fn=nn_fn,
                       src_valid=sv, dst_valid=dv)

        return self._jit(run, "single")

    def _build_batch(self, params: ICPParams):
        nn_fn = self._nn_fn(params)

        def run(src_b, dst_b, T0, sv, dv):
            self._note_trace("batch", params, src_b.shape, dst_b.shape)
            return icp_batch(src_b, dst_b, params, T0, nn_fn=nn_fn,
                             src_valid=sv, dst_valid=dv)

        return self._jit(run, "batch")

    def _executable(self, kind: str, params: ICPParams):
        key = (kind, params)
        fn = self._cache.get(key)
        if fn is None:
            build = self._build_single if kind == "single" else self._build_batch
            fn = build(params)
            self._cache[key] = fn
        return fn

    def _default_params(self, params: ICPParams | None) -> ICPParams:
        if params is None:
            return ICPParams(chunk=self._chunk)
        return params

    # -- public API --------------------------------------------------------
    def register(self, source, target, params: ICPParams | None = None,
                 initial_transform=None, *, src_valid=None, dst_valid=None,
                 bucket: bool = True) -> ICPResult:
        """Register one (N,3) source onto one (M,3) target.

        With ``bucket=True`` (default) both clouds are padded up to the next
        shape bucket before hitting the jitted executable, so a stream of
        slightly-varying frame sizes reuses one compilation per bucket
        instead of one per exact size. Padding happens device-side — an
        already-bucket-sized device array passes through with zero copies.

        ``src_valid``/``dst_valid`` let callers who manage their own
        static-capacity padding (e.g. the rolling submap of
        ``repro.data.submap``, whose invalid rows already carry the far
        sentinel) pass masks directly; the clouds then go through at their
        given shapes, no re-bucketing. ``initial_transform`` is cast to
        f32 so a float64 warm start cannot poison the f32 trace.

        ``target`` may be a :class:`~repro.data.voxelize.VoxelMap`, a
        cloud on a voxel lattice: the slot engines search its lattice,
        the others register onto its points as onto any cloud. The
        correspondences within the gate are the same either way.
        """
        params = self._default_params(params)
        src = jnp.asarray(source, dtype=jnp.float32)
        dst = jnp.asarray(_cloud(target), dtype=jnp.float32)
        if initial_transform is not None:
            initial_transform = jnp.asarray(initial_transform, jnp.float32)
        if src_valid is not None or dst_valid is not None:
            sv = None if src_valid is None else jnp.asarray(src_valid, bool)
            dv = None if dst_valid is None else jnp.asarray(dst_valid, bool)
        else:
            sv = dv = None
            if bucket:
                n_b = bucket_size(src.shape[0])
                m_b = bucket_size(dst.shape[0])
                if (src.shape[0], dst.shape[0]) != (n_b, m_b):
                    src, sv = _pad_device(src, n_b)
                    dst, dv = _pad_device(dst, m_b)
        fn = self._executable("single", params)
        return fn(src, dst, initial_transform, sv, dv)

    def register_batch(self, sources, targets,
                       params: ICPParams | None = None, *,
                       src_valid=None, dst_valid=None,
                       initial_transforms=None) -> ICPResult:
        """Register a (B,N,3) source batch onto a (B,M,3) target batch in a
        single compiled program. Masks come from ``collate_pairs``; every
        ``ICPResult`` leaf gains a leading batch axis. ``targets`` may be
        a batch of voxel maps, as in :meth:`register`."""
        fn = self._executable("batch", self._default_params(params))
        if initial_transforms is not None:
            # f32 pin: a float64 warm-start batch must not poison the trace
            initial_transforms = jnp.asarray(initial_transforms, jnp.float32)
        return fn(jnp.asarray(sources, dtype=jnp.float32),
                  self._batch_targets(targets),
                  initial_transforms,
                  None if src_valid is None else jnp.asarray(src_valid, bool),
                  None if dst_valid is None else jnp.asarray(dst_valid, bool))

    def _batch_targets(self, targets):
        return jnp.asarray(_cloud(targets), dtype=jnp.float32)

    def register_pairs(self, pairs, params: ICPParams | None = None,
                       initial_transforms=None):
        """Collate variable-size [(src, dst), ...] and register as one batch.

        Returns (ICPResult, CollatedBatch) — the batch carries the true
        per-frame sizes for unpadding downstream. The call runs inside a
        ``fpps.pairs`` profiler span (``batch=`` its ordinal, ``pairs=``
        its size) holding ``fpps.pairs.collate`` and
        ``fpps.pairs.dispatch``, and adds to :meth:`report`'s counters;
        it waits for nothing on the device.
        """
        from repro.data.collate import collate_pairs
        counts = self._pair_counts
        with TraceAnnotation("fpps.pairs", batch=counts["pair_batches"],
                             pairs=len(pairs)):
            with TraceAnnotation("fpps.pairs.collate"):
                batch = collate_pairs(pairs)
            with TraceAnnotation("fpps.pairs.dispatch"):
                res = self.register_batch(
                    batch.src, batch.dst, params,
                    src_valid=batch.src_valid, dst_valid=batch.dst_valid,
                    initial_transforms=initial_transforms)
        b = len(batch.dst_sizes)
        counts["pair_batches"] += 1
        counts["pairs"] += b
        counts["pair_iterations_run"] += (
            b * self._default_params(params).max_iterations)
        counts["target_rows"] += sum(batch.dst_sizes)
        counts["target_rows_padded"] += b * batch.dst.shape[1]
        self._pending_iterations.append(res.iterations)
        return res, batch

    def report(self) -> dict:
        """Counters of :meth:`register_pairs` since the engine was made:

          * ``pair_batches``, ``pairs`` — calls and the pairs they held;
          * ``pair_iterations_run`` — pairs x ``max_iterations``: the
            pair-iterations the fixed-length batch scan computes, a
            converged pair riding on frozen until the scan ends (the
            slot engines' loop stops with its slowest pair: there it is
            an upper bound);
          * ``pair_iterations`` — the ICP iterations the pairs asked for
            (``ICPResult.iterations``, summed), read from the device here
            and not in ``register_pairs``, so this call waits for the
            batches still running;
          * ``target_rows``, ``target_rows_padded`` — the targets' points
            and the rows the collated batches gave them (each batch pads
            every target to the bucket of its largest).

        ``1 - pair_iterations / pair_iterations_run`` is the share of the
        batch executable's pair-iterations spent on converged pairs."""
        counts = self._pair_counts
        for its in self._pending_iterations:
            counts["pair_iterations"] += int(np.asarray(its).sum())
        self._pending_iterations.clear()
        return dict(counts)


class XLAEngine(RegistrationEngine):
    """Default engine: chunked brute-force NN in pure XLA (runs anywhere)."""

    name = "xla"
    # Base behaviour is exactly this engine; _nn_fn -> None selects the
    # chunked searcher in core.icp with native dst_valid masking.


class PallasEngine(RegistrationEngine):
    """TPU Pallas kernel engine (interpret mode off-TPU).

    The target augmentation is built once per frame at trace scope via
    ``kernels.ops.resident_nn_fn`` — each ICP iteration only augments the
    small source cloud and runs the NN kernel against the resident target.

    ``params.fused`` swaps the whole iteration body for the single-pass
    moment kernel (``repro.kernels.fused_icp``, DESIGN.md §11): a resident
    counting-sort grid replaces the augmented target, and search + gate +
    IRLS weight + moment accumulation run as one Pallas pass per
    iteration; the unfused path above stays the fallback. The fused tile
    config defaults to ``DEFAULT_CONFIG`` — override with the ``fused_*``
    constructor kwargs. ``max_per_cell`` bounds the points gathered per
    grid cell; the fused search is exact only where no cell holds more.
    The densest 1 m cell of a full-size scan of the generated scenes
    (sequences 0-3) holds 112 points, hence 128.
    """

    name = "pallas"

    def __init__(self, chunk: int = 2048, bn: int = 512,
                 bm: int | None = None, interpret: bool | None = None,
                 grid_dims: tuple[int, int, int] = (128, 128, 32),
                 grid_voxel: float | None = None, max_per_cell: int = 128,
                 rings: int = 1, fused_bn: int | None = None,
                 fused_bc: int | None = None,
                 fused_prune: bool | None = None):
        super().__init__(chunk)
        self._bn, self._bm = bn, bm
        self._interpret = interpret  # None: auto (interpret unless on TPU)
        self._grid_dims = tuple(grid_dims)
        self._grid_voxel = grid_voxel
        self._max_per_cell = max_per_cell
        self._rings = rings
        self._fused_bn, self._fused_bc = fused_bn, fused_bc
        self._fused_prune = fused_prune

    def _interp(self) -> bool:
        from repro.kernels.common import default_interpret
        return default_interpret(self._interpret)

    def _fused_kwargs(self) -> dict:
        return dict(grid_dims=self._grid_dims, grid_voxel=self._grid_voxel,
                    max_per_cell=self._max_per_cell, rings=self._rings,
                    bn=self._fused_bn, bc=self._fused_bc,
                    prune=self._fused_prune, interpret=self._interpret)

    def _make_fused_fn(self, dst, params: ICPParams, dv, normals):
        from repro.kernels.fused_icp import default_fused_fn
        return default_fused_fn(dst, params, dst_valid=dv,
                                target_normals=normals,
                                **self._fused_kwargs())

    def _build_single(self, params: ICPParams):
        from repro.kernels.ops import resident_nn_fn
        interpret = self._interp()

        def run(src, dst, T0, sv, dv):
            self._note_trace("single", params, src.shape, dst.shape)
            # Scrub before any frame-scope prep: a NaN row would poison
            # the normal estimation and the resident target/grid builds.
            src, sv = scrub_nonfinite(src, sv)
            dst, dv = scrub_nonfinite(dst, dv)
            normals = _target_normals(dst, params, dv)
            if params.fused:
                fused_fn = self._make_fused_fn(dst, params, dv, normals)
                return icp(src, dst, params, T0, fused_fn=fused_fn,
                           src_valid=sv, target_normals=normals)
            dst = _mask_invalid(dst, dv)
            nn_fn = resident_nn_fn(dst, bn=self._bn, bm=self._bm,
                                   interpret=interpret)
            return icp(src, dst, params, T0, nn_fn=nn_fn, src_valid=sv,
                       target_normals=normals)

        return self._jit(run, "single")

    def _build_batch(self, params: ICPParams):
        from repro.kernels.ops import resident_nn_fn
        interpret = self._interp()

        def run(src_b, dst_b, T0, sv, dv):
            self._note_trace("batch", params, src_b.shape, dst_b.shape)
            if T0 is None:
                T0 = jnp.broadcast_to(jnp.eye(4, dtype=src_b.dtype),
                                      (src_b.shape[0], 4, 4))

            def one(src, dst, T0_, sv_, dv_):
                src, sv_ = scrub_nonfinite(src, sv_)
                dst, dv_ = scrub_nonfinite(dst, dv_)
                normals = _target_normals(dst, params, dv_)
                if params.fused:
                    fused_fn = self._make_fused_fn(dst, params, dv_, normals)
                    return icp_fixed_iterations(src, dst, params, T0_,
                                                fused_fn=fused_fn,
                                                src_valid=sv_,
                                                target_normals=normals)
                dst = _mask_invalid(dst, dv_)
                nn_fn = resident_nn_fn(dst, bn=self._bn, bm=self._bm,
                                       interpret=interpret)
                return icp_fixed_iterations(src, dst, params, T0_,
                                            nn_fn=nn_fn, src_valid=sv_,
                                            target_normals=normals)

            return jax.vmap(one)(src_b, dst_b, T0, sv, dv)

        return self._jit(run, "batch")


class DistributedEngine(RegistrationEngine):
    """Fleet-mode engine: frames shard over "data", targets over "model".

    Wraps ``core.distributed.batched_icp_sharded`` on a mesh spanning the
    available devices (or a caller-supplied mesh). Warm starts are applied
    by pre-transforming sources and composing the result (mathematically
    identical to an initial transform).
    """

    name = "distributed"

    def __init__(self, chunk: int = 2048, mesh=None,
                 frame_axes=("data",), target_axes=("model",)):
        super().__init__(chunk)
        self._mesh = mesh
        self._frame_axes = tuple(frame_axes)
        self._target_axes = tuple(target_axes)

    def _get_mesh(self):
        if self._mesh is None:
            from jax.sharding import Mesh
            devs = np.array(jax.devices())
            self._mesh = Mesh(devs.reshape(len(devs), 1), ("data", "model"))
        return self._mesh

    def setup(self) -> None:
        """Backend init hook: build the (data, model) device mesh once."""
        self._get_mesh()

    def _build_batch(self, params: ICPParams):
        from repro.core.distributed import batched_icp_sharded
        mesh = self._get_mesh()
        frame_div = 1
        for ax in self._frame_axes:
            frame_div *= mesh.shape[ax]

        def run(src_b, dst_b, T0, sv, dv):
            self._note_trace("batch", params, src_b.shape, dst_b.shape)
            # Scrub before sharding/normals: NaN rows must not cross the
            # shard_map boundary or reach the per-frame normal estimate.
            src_b, sv = scrub_nonfinite(src_b, sv)
            dst_b, dv = scrub_nonfinite(dst_b, dv)
            b = src_b.shape[0]
            # The frame axis must divide the mesh's frame_axes extent; pad
            # by repeating frame 0 and slice the results back off.
            pad = (-b) % frame_div

            def rep(x):
                if x is None or pad == 0:
                    return x
                return jnp.concatenate(
                    [x, jnp.repeat(x[:1], pad, axis=0)], axis=0)

            src_b, dst_b, T0, sv, dv = map(rep, (src_b, dst_b, T0, sv, dv))
            if params.minimizer == "point_to_plane":
                # Normals come from the *unsharded* per-frame targets (and
                # the true valid mask), before the sentinel masking below.
                normals = jax.vmap(
                    lambda d, v: _target_normals(d, params, v))(
                        dst_b, dv if dv is not None
                        else jnp.ones(dst_b.shape[:2], bool))
            else:
                normals = None
            dst_b = _mask_invalid(dst_b, dv)
            if T0 is not None:
                # warm start: register T0(src) and compose T_result @ T0.
                R, t = T0[:, :3, :3], T0[:, :3, 3]
                src_b = (jnp.einsum("bnj,bij->bni", src_b, R, precision="highest")
                         + t[:, None, :])
            res = batched_icp_sharded(mesh, src_b, dst_b, params,
                                      frame_axes=self._frame_axes,
                                      target_axes=self._target_axes,
                                      src_valid=sv, dst_normals=normals)
            if T0 is not None:
                res = res._replace(T=jnp.einsum("bij,bjk->bik", res.T, T0,
                                                precision="highest"))
            if pad:
                res = jax.tree_util.tree_map(lambda x: x[:b], res)
            return res

        return self._jit(run, "batch")

    def _build_single(self, params: ICPParams):
        batch_fn = self._build_batch(params)

        def run(src, dst, T0, sv, dv):
            res = batch_fn(src[None], dst[None],
                           None if T0 is None else T0[None],
                           None if sv is None else sv[None],
                           None if dv is None else dv[None])
            return jax.tree_util.tree_map(lambda x: x[0], res)

        return run  # batch_fn is already jitted


def slot_lane(params: ICPParams, nn_fn: Callable | None):
    """One lane of the slot executable, ``(src, dst, T0, sv, dv) ->
    ICPResult``. A :class:`VoxelMap` target is searched through its
    lattice table, built here, outside the ICP loop
    (``core.nn_search_grid.lattice_nn_fn``), and the result carries the
    table's ``lattice_overflow``; a plain cloud is searched with
    ``nn_fn`` (None: the brute-force sweep)."""

    def one(src, dst, T0, sv, dv):
        if not isinstance(dst, VoxelMap):
            return icp(src, dst, params, T0, nn_fn=nn_fn, src_valid=sv,
                       dst_valid=dv)
        points, dv = scrub_nonfinite(dst.points, dv)
        lattice_fn, overflow = lattice_nn_fn(
            dataclasses.replace(dst, points=points), dv,
            params.max_correspondence_distance)
        res = icp(src, points, params, T0, nn_fn=lattice_fn, src_valid=sv,
                  dst_valid=dv)
        return res._replace(lattice_overflow=overflow)

    return one


class SlotEngine(RegistrationEngine):
    """Fixed-width slot-batch engine backing the multi-stream registration
    service (DESIGN.md §13).

    Every registration — the service's S-stream fleet step AND a lone
    single-frame :meth:`register` call — runs through ONE jitted
    ``vmap(icp)`` executable of exactly ``slots`` lanes. The batched
    ``while_loop`` stops when every lane's convergence predicate is false,
    and per-lane freeze masks (the ``vmap``-induced ``select`` on each
    state update) keep converged or inactive lanes bit-frozen while live
    lanes iterate. Single-frame calls embed the frame at lane 0 among
    sentinel-masked inactive lanes (which degenerate-freeze after one
    iteration) and slice lane 0 back out; a vmapped lane is bitwise
    independent of lane position and of the other lanes' contents, so a
    per-stream :class:`~repro.core.odometry.OdometryPipeline` on this
    engine reproduces the service's poses bit-for-bit — the service
    parity contract.
    """

    name = "slots"

    def __init__(self, chunk: int = 2048, slots: int = 8):
        super().__init__(chunk)
        self.slots = int(slots)

    def _build_batch(self, params: ICPParams):
        one = slot_lane(params, self._nn_fn(params))

        def run(src_b, dst_b, T0, sv, dv):
            self._note_trace("batch", params, src_b.shape,
                             _cloud(dst_b).shape)
            if T0 is None:
                T0 = jnp.broadcast_to(jnp.eye(4, dtype=src_b.dtype),
                                      (src_b.shape[0], 4, 4))
            return jax.vmap(one)(src_b, dst_b, T0, sv, dv)

        # module name ``jit_run``: the name a trace reads the slot
        # executable by
        return jax.jit(run)

    def _batch_targets(self, targets):
        """Voxel-map targets stay maps: each lane searches its map's
        lattice (:func:`slot_lane`), and the result's
        ``lattice_overflow[s]`` counts lane s's map cells holding more
        than ``max_per_cell`` points (0 keeps that search exact)."""
        return jax.tree_util.tree_map(
            lambda x: jnp.asarray(x, dtype=jnp.float32), targets)

    def register(self, source, target, params: ICPParams | None = None,
                 initial_transform=None, *, src_valid=None, dst_valid=None,
                 bucket: bool = True) -> ICPResult:
        """Register one (N,3)/(M,3) pair through the S-lane slot
        executable: the pair occupies lane 0, the remaining ``slots - 1``
        lanes carry sentinel rows with all-False masks (degenerate-frozen
        after one iteration), and lane 0 of the batched result is
        returned. Same bucketing semantics as the base engine; crucially
        the executable is the SAME one the service's fleet step compiles,
        so this path never adds a trace (for a :class:`VoxelMap` target,
        the one the service's voxel-map batch compiles)."""
        lattice = target if isinstance(target, VoxelMap) else None
        target = _cloud(target)
        params = self._default_params(params)
        src = jnp.asarray(source, dtype=jnp.float32)
        dst = jnp.asarray(target, dtype=jnp.float32)
        if src_valid is None and dst_valid is None and bucket:
            n_b = bucket_size(src.shape[0])
            m_b = bucket_size(dst.shape[0])
            if (src.shape[0], dst.shape[0]) != (n_b, m_b):
                src, src_valid = _pad_device(src, n_b)
                dst, dst_valid = _pad_device(dst, m_b)
        sv = (jnp.ones(src.shape[0], bool) if src_valid is None
              else jnp.asarray(src_valid, bool))
        dv = (jnp.ones(dst.shape[0], bool) if dst_valid is None
              else jnp.asarray(dst_valid, bool))
        T0 = (jnp.eye(4, dtype=jnp.float32) if initial_transform is None
              else jnp.asarray(initial_transform, jnp.float32))
        lane = jnp.arange(self.slots) == 0
        sentinel = jnp.asarray(PAD_SENTINEL, jnp.float32)
        src_b = jnp.where(lane[:, None, None], src[None], sentinel)
        dst_b = jnp.where(lane[:, None, None], dst[None], sentinel)
        sv_b = jnp.logical_and(lane[:, None], sv[None])
        dv_b = jnp.logical_and(lane[:, None], dv[None])
        T0_b = jnp.broadcast_to(T0[None], (self.slots, 4, 4))
        if lattice is not None:
            origin = jnp.asarray(lattice.origin, jnp.float32)
            dst_b = dataclasses.replace(
                lattice, points=dst_b,
                origin=jnp.broadcast_to(origin, (self.slots, 3)))
        fn = self._executable("batch", params)
        res = fn(src_b, dst_b, T0_b, sv_b, dv_b)
        return jax.tree_util.tree_map(lambda x: x[0], res)


class ShardedSlotEngine(SlotEngine):
    """Device-parallel slot engine: the ``slots`` executable under
    ``shard_map`` over a 1-D ``("streams",)`` mesh (DESIGN.md §14).

    The fleet width is ``devices * lanes_per_device``; each device runs
    the SAME ``vmap(icp)`` block over its own ``lanes_per_device`` lanes,
    with zero collectives in the body (streams are independent). Because
    the per-device block program is fixed by ``lanes_per_device`` alone,
    a lane's result is bitwise identical across mesh sizes at EQUAL block
    width — a D=8, L=1 fleet reproduces a single-device (D=1, L=1)
    reference's per-stream poses exactly (weak-scaling parity), which is
    the sharded service's acceptance contract. Across different widths
    (say L=1 vs L=8) XLA may tile each lane's point-axis reductions
    differently, so agreement is fp-tolerance, not bitwise.

    Inherits the ``SlotEngine`` lane-0 embedding: a single-frame
    :meth:`register` call runs through the same S-lane sharded
    executable, so a standalone ``OdometryPipeline`` on this engine is
    still the service's bit-exact reference. ``devices=0`` (the default,
    kept an int so the ``get_engine`` singleton key stays hashable) means
    all local devices.
    """

    name = "sharded-slots"

    def __init__(self, chunk: int = 2048, lanes_per_device: int = 2,
                 devices: int = 0):
        from repro.core.distributed import streams_mesh
        self.devices = int(devices) or jax.device_count()
        self.lanes_per_device = int(lanes_per_device)
        super().__init__(chunk, slots=self.devices * self.lanes_per_device)
        self._mesh = streams_mesh(self.devices)

    @property
    def mesh(self):
        """The ``("streams",)`` mesh every executable is sharded over."""
        return self._mesh

    def sharding(self):
        """``NamedSharding`` for lane-major fleet arrays: place ``(S,...)``
        inputs with this to avoid a reshard at the jit boundary."""
        from jax.sharding import NamedSharding, PartitionSpec
        return NamedSharding(self._mesh, PartitionSpec("streams"))

    def _build_batch(self, params: ICPParams):
        from repro.core.distributed import stream_sharded_icp
        nn_fn = self._nn_fn(params)
        mesh = self._mesh

        def run(src_b, dst_b, T0, sv, dv):
            self._note_trace("batch", params, src_b.shape,
                             _cloud(dst_b).shape)
            # shard_map's in_specs are fixed-arity: normalize the optional
            # masks/warm starts here (identical defaults to SlotEngine's).
            if sv is None:
                sv = jnp.ones(src_b.shape[:2], bool)
            if dv is None:
                dv = jnp.ones(_cloud(dst_b).shape[:2], bool)
            return stream_sharded_icp(mesh, src_b, dst_b, params,
                                      initial_transforms=T0,
                                      src_valid=sv, dst_valid=dv,
                                      nn_fn=nn_fn)

        # module name ``jit_run``: the name a trace reads the slot
        # executable by
        return jax.jit(run)


class CallableEngine(RegistrationEngine):
    """Adapter for a user-supplied ``nn_fn(src, dst) -> (d2, idx)``."""

    name = "callable"

    def __init__(self, nn_fn: Callable, chunk: int = 2048):
        super().__init__(chunk)
        self._user_nn_fn = nn_fn

    def _nn_fn(self, params: ICPParams) -> Callable:
        return self._user_nn_fn


# -- registry ---------------------------------------------------------------
_ENGINES: dict[str, Callable[..., RegistrationEngine]] = {}
_SHARED: dict = {}  # (name, sorted kwargs) -> engine instance


def register_engine(name: str, factory: Callable[..., RegistrationEngine]):
    """Register an engine factory under ``name`` (last write wins)."""
    _ENGINES[name] = factory
    _SHARED.clear()
    return factory


def available_engines() -> tuple[str, ...]:
    """Registered engine names, sorted — the valid ``get_engine`` specs
    (and the CLI ``--engine`` choices)."""
    return tuple(sorted(_ENGINES))


def get_engine(spec, **kwargs) -> RegistrationEngine:
    """Resolve an engine spec: a RegistrationEngine instance (passed
    through), a registered name, or a bare ``nn_fn`` callable.

    Named engines with hashable kwargs are process-wide singletons, so the
    compilation caches are shared: constructing ``FppsICP()`` per frame
    (the PCL-style pattern the drivers use) reuses one compiled executable
    instead of recompiling per instance. Instantiate the engine class
    directly for a private cache.
    """
    if isinstance(spec, RegistrationEngine):
        return spec
    if isinstance(spec, str):
        try:
            factory = _ENGINES[spec]
        except KeyError:
            raise ValueError(
                f"unknown engine {spec!r}; available: {available_engines()}"
            ) from None
        try:
            key = (spec, tuple(sorted(kwargs.items())))
            engine = _SHARED.get(key)
            if engine is None:
                engine = _SHARED[key] = factory(**kwargs)
            return engine
        except TypeError:  # unhashable kwarg (e.g. an explicit mesh)
            return factory(**kwargs)
    if callable(spec):
        return CallableEngine(spec, **kwargs)
    raise TypeError(f"engine spec must be a name, callable or "
                    f"RegistrationEngine, got {type(spec).__name__}")


register_engine("xla", XLAEngine)
register_engine("pallas", PallasEngine)
register_engine("distributed", DistributedEngine)
register_engine("slots", SlotEngine)
register_engine("sharded-slots", ShardedSlotEngine)

# Imported for its side effect: registers the "pyramid" engine. Lives in
# its own module (it pulls in the voxel/grid-NN stack); bottom import keeps
# the pyramid -> engine -> pyramid cycle harmless.
from repro.core import pyramid as _pyramid  # noqa: E402,F401
