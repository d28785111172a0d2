"""Multi-stream registration service: N odometry streams, one compiled
program per round (DESIGN.md §13), optionally sharded over a device mesh
(DESIGN.md §14).

The paper's headline number is a *runtime-weighted* speedup across a
workload mix (§IV) — a shared-accelerator framing. This module is that
layer for the repo: a fleet of vehicles (streams) funnels scans into a
fixed set of ``slots``, and every service round runs the whole fleet's
data plane as three batched executables (vmapped scrub + downsample, one
``SlotEngine`` fleet registration, vmapped submap fuse with buffer
donation) regardless of how many streams are live. The control plane —
health verdicts, the recovery cascade, accept/quarantine bookkeeping —
stays host-side per stream, reusing :class:`~repro.core.odometry.
OdometryPipeline` verbatim, so the service inherits every robustness
behaviour of PR 5–7 without forking the policy code.

**Sharded mode** (``ServiceConfig.devices=D``): the same round runs under
``shard_map`` over a 1-D ``("streams",)`` device mesh. Each device owns a
contiguous block of ``slots / D`` slot lanes AND their resident submaps —
the fleet's map state lives device-resident as sharded ``(S, ...)``
arrays (``repro.data.submap`` state tuples) instead of per-stream host
objects, and the prepare/register/probe/fuse executables all run inside
the shard body with **zero cross-device collectives** (streams are
independent by construction). Host-boundary traffic per round is the
bulk classification fetch, ONE bulk registration+probe health fetch, and
the fuse's occupancy epilogue — all batched, none per-stream. The host
control plane is unchanged: per-stream pipelines see the fleet state
through :class:`_LaneSubmap` views. Admission is mesh-aware (least-loaded
device block) and a retired slot's lane state is reset in place, so
join/retire churn never retraces and never leaks a predecessor's map.

Retrace avoidance is structural, not best-effort: all device arrays are
fixed-shape — ``(slots, scan_capacity, 3)`` staged scans,
``(slots, scan_budget, 3)`` downsampled sources, ``(slots, capacity, 3)``
map targets — and idle or non-registering lanes ride along with all-False
validity masks (they degenerate-freeze after one ICP iteration inside the
batched ``while_loop``). Admitting a stream, retiring one, or dropping
frames under backpressure therefore never changes a traced shape; after
the first round, ``engine.trace_count`` is constant by construction and
the tests assert it.

Bit-exactness contract: a standalone ``OdometryPipeline`` built from
:attr:`RegistrationService.stream_config` and fed the same (staged)
frames produces bit-identical poses and diagnostics — the single-frame
path embeds into the *same* S-lane executable (``SlotEngine.register``),
and a vmapped lane is bitwise independent of lane index and of the other
lanes' contents. In sharded mode the contract extends across mesh sizes
at equal block width: the per-device program is fixed by
``slots / devices`` alone, so a D=8, one-lane-per-device fleet
reproduces a single-device one-lane reference's per-stream poses
bit-for-bit (weak-scaling parity — see ``ShardedSlotEngine``; across
*different* block widths agreement is fp-tolerance, since XLA may tile a
lane's point-axis reductions differently).

Observability: every public call and each stage of a round runs inside
a named ``jax.profiler.TraceAnnotation`` span (``fpps.*``; free when no
profiler runs), and ``service_report()`` counts the slot executable's
lane-iterations — asked for, and computed while the slowest lane of a
block kept the vmapped loop running.

Typical use::

    svc = RegistrationService(ServiceConfig(slots=8))      # single-device
    svc = RegistrationService(ServiceConfig(slots=16, devices=8))
    for vid in vehicle_ids:
        svc.admit(vid)
    while streaming:
        for vid, scan in poll_sensors():
            svc.submit(vid, scan)            # staging (async)
        for vid, (pose, diag) in svc.step().items():
            publish(vid, pose, diag)
"""
from __future__ import annotations

import functools
from collections import deque
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.engine import get_engine
from repro.core.odometry import (KIND_REGISTER, FrameDiagnostics,
                                 OdometryConfig, OdometryPipeline,
                                 prepare_scan, prepare_scans)
from repro.data.collate import PAD_SENTINEL, bucket_size, pad_cloud
from repro.data.submap import (SubmapParams, empty_state, fuse_lane,
                               fuse_lanes, outside_lattice_frac,
                               outside_lattice_fracs, state_views)


class ServiceConfig(NamedTuple):
    """Service-level configuration on top of a shared per-stream
    :class:`~repro.core.odometry.OdometryConfig`.

    ``slots`` is the fleet width of every batched executable — admitted
    streams bind to a slot, further admissions wait (``admission=
    "queue"``) or fail (``"reject"``). ``scan_capacity`` is the staged
    raw-scan row budget (rounded up to a collate bucket); larger scans
    are rejected at ``submit``. ``max_queue`` bounds the per-stream
    staging queue; on overflow ``drop_policy`` evicts the ``"oldest"``
    staged frame (keep freshest — the odometry default) or refuses the
    ``"newest"`` submission. All streams share one odometry config —
    one config means one ``ICPParams``/shape family, which is what keeps
    the fleet inside a single compiled program.

    ``devices`` switches the service to device-sharded mode (module
    docstring): the fleet round runs under ``shard_map`` over the first
    ``devices`` local devices, each owning ``slots / devices`` lanes and
    their resident submaps. ``None`` (default) is the single-device
    path, byte-for-byte the pre-sharding service.
    """

    slots: int = 8
    scan_capacity: int = 4096
    max_queue: int = 4
    drop_policy: str = "oldest"
    admission: str = "queue"
    odometry: OdometryConfig = OdometryConfig()
    devices: int | None = None


class StreamReport(NamedTuple):
    """Per-stream service accounting, returned by ``report``/``close``:
    submit/process/drop counters, quarantine + cascade-escape totals, the
    health-verdict histogram, and the last output pose (None before the
    first processed frame)."""

    stream_id: str
    frames_submitted: int
    frames_processed: int
    frames_dropped: int
    frames_quarantined: int
    cascade_escapes: int
    health_counts: dict
    final_pose: np.ndarray | None


class _StagedFrame(NamedTuple):
    # staged scan padded to (scan_capacity, 3) + mask. Single-device mode
    # stages device-resident (async transfer overlaps the in-flight
    # round); sharded mode stages host-side so each round issues ONE
    # sharded transfer that lands every lane on its owning device.
    pts: object
    valid: object
    seq: int


class _Stream:
    """Host-side stream record: its pipeline, staging queue, counters."""

    def __init__(self, stream_id: str):
        self.id = stream_id
        self.pipe: OdometryPipeline | None = None
        self.queue: deque[_StagedFrame] = deque()
        self.slot: int | None = None
        self.submitted = 0
        self.dropped = 0
        self.cascade_escapes = 0


class _LaneSubmap:
    """Duck-typed Submap view over one lane of the sharded fleet state.

    The host control plane (cascade tiers, lattice probes, occupancy
    diagnostics) reads per-stream map state through the same attribute
    surface as :class:`~repro.data.submap.Submap`; this view resolves
    those reads against the service's sharded ``(S, ...)`` fleet arrays
    at the stream's *current* slot (rebinding-safe). Occupancy and the
    sticky ``dropped_cells`` counter are host caches updated from each
    batched fuse's epilogue, so control-plane reads cost no device
    fetch. All writes go through the service's batched fuse —
    ``insert`` is therefore a usage error here."""

    def __init__(self, svc: "RegistrationService", stream: _Stream):
        self._svc = svc
        self._stream = stream
        self.params: SubmapParams = svc.stream_config.submap
        self.frames_inserted = 0
        self.dropped_cells = 0
        self._occupied = 0

    def _lane_state(self) -> tuple:
        lane = self._stream.slot
        if lane is None:
            raise RuntimeError(f"stream {self._stream.id!r} has no slot "
                               f"bound; its lane state does not exist yet")
        return tuple(leaf[lane] for leaf in self._svc._fleet)

    @property
    def origin(self):
        return self._lane_state()[-1]

    @property
    def points(self):
        return state_views(self._lane_state(), self.params)[0]

    @property
    def valid(self):
        return state_views(self._lane_state(), self.params)[1]

    def target(self):
        pts, valid, _ = state_views(self._lane_state(), self.params)
        return pts, valid

    @property
    def size(self) -> int:
        return self._occupied

    def occupancy(self) -> float:
        return self._occupied / int(self.params.capacity)

    def insert(self, *a, **k):
        raise RuntimeError("sharded service submaps are fused in the "
                           "batched fleet round, never inserted per-stream")


# -- sharded executables (one per mesh + static config, cached) -------------

_SPEC = P("streams")


@functools.lru_cache(maxsize=None)
def _sharded_prepare(mesh, voxel: float, budget: int):
    def body(pts_l, valid_l):
        src, sv = jax.vmap(
            lambda p, v: prepare_scan(p, v, voxel, budget))(pts_l, valid_l)
        return src, sv, jnp.sum(sv, axis=1)

    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(_SPEC, _SPEC),
                                 out_specs=(_SPEC, _SPEC, _SPEC),
                                 check_vma=False))


@functools.lru_cache(maxsize=None)
def _sharded_lattice(mesh, params: SubmapParams):
    def body(T_l, src_l, sv_l, origin_l):
        return jax.vmap(
            lambda T, s, v, o: outside_lattice_frac(T, s, v, o, params))(
                T_l, src_l, sv_l, origin_l)

    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(_SPEC,) * 4,
                                 out_specs=_SPEC, check_vma=False))


def _state_spec(params: SubmapParams) -> tuple:
    return tuple(_SPEC for _ in empty_state(params))


@functools.lru_cache(maxsize=None)
def _sharded_fuse(mesh, params: SubmapParams):
    sspec = _state_spec(params)

    def body(state_l, src_l, sv_l, pose_l, acc_l):
        return jax.vmap(
            lambda st, s, v, p, a: fuse_lane(st, s, v, p, a, params))(
                state_l, src_l, sv_l, pose_l, acc_l)

    return jax.jit(jax.shard_map(body, mesh=mesh,
                                 in_specs=(sspec, _SPEC, _SPEC, _SPEC, _SPEC),
                                 out_specs=(sspec, _SPEC, _SPEC),
                                 check_vma=False),
                   donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def _sharded_target_views(mesh, params: SubmapParams):
    """Sharded decode of fleet state to registration-target form. The
    fp32 layout needs no decode (the service uses its leaves directly);
    this executable exists for fp16, where the engine's target is
    ``origin + offset`` per lane — the same ``state_views`` formula the
    standalone pipeline evaluates, so lanes stay bit-identical."""
    def body(state_l):
        pts, valid, _ = jax.vmap(
            lambda st: state_views(st, params))(state_l)
        return pts, valid

    return jax.jit(jax.shard_map(body, mesh=mesh,
                                 in_specs=(_state_spec(params),),
                                 out_specs=(_SPEC, _SPEC), check_vma=False))


@functools.lru_cache(maxsize=None)
def _sharded_reset(mesh, params: SubmapParams):
    """Reset one lane of the sharded fleet state to idle (retire path).
    Elementwise select along the lane axis — shard-local, no collectives;
    ``lane`` is traced so every retire reuses one executable."""
    out_sh = tuple(NamedSharding(mesh, _SPEC) for _ in empty_state(params))

    def run(state_b, lane):
        idle = empty_state(params)
        S = state_b[-1].shape[0]
        hit = jnp.arange(S) == lane
        return tuple(
            jnp.where(hit.reshape((S,) + (1,) * (leaf.ndim - 1)),
                      idle_leaf[None], leaf)
            for leaf, idle_leaf in zip(state_b, idle))

    return jax.jit(run, donate_argnums=(0,), out_shardings=out_sh)


class RegistrationService:
    """Continuous-batching front end over the odometry stack: admit
    streams into slots, stage frames, and run the whole fleet's round as
    one compiled step (see module docstring for the lifecycle and the
    sharded mode).

    The service is single-threaded and deterministic: ``step()`` pops at
    most one staged frame per active stream in slot order, so identical
    submission sequences produce identical outputs, drops included.
    """

    def __init__(self, config: ServiceConfig = ServiceConfig()):
        if config.drop_policy not in ("oldest", "newest"):
            raise ValueError(f"drop_policy must be 'oldest' or 'newest', "
                             f"got {config.drop_policy!r}")
        if config.admission not in ("queue", "reject"):
            raise ValueError(f"admission must be 'queue' or 'reject', "
                             f"got {config.admission!r}")
        cap = bucket_size(config.scan_capacity)
        self.config = config._replace(scan_capacity=cap)
        self._sharded = config.devices is not None
        self._devices = int(config.devices) if self._sharded else 1
        sp = self.stream_config.submap
        if self._sharded:
            D = self._devices
            if D < 1 or D > jax.device_count():
                raise ValueError(f"devices must be in "
                                 f"[1, {jax.device_count()}], got {D}")
            if config.slots % D:
                raise ValueError(f"slots={config.slots} must divide evenly "
                                 f"over devices={D}")
            self.engine = get_engine("sharded-slots",
                                     lanes_per_device=config.slots // D,
                                     devices=D)
            self._mesh = self.engine.mesh
            self._sharding = self.engine.sharding()
            # fleet-resident sharded map state: each device holds its lane
            # block's submaps for the whole service lifetime
            idle_np = [np.asarray(leaf) for leaf in empty_state(sp)]
            S = config.slots
            self._fleet = tuple(
                jax.device_put(
                    np.broadcast_to(leaf, (S,) + leaf.shape).copy(),
                    self._sharding)
                for leaf in idle_np)
            # host-side staged-scan fillers (one sharded transfer per round)
            self._idle_pts = np.full((cap, 3), PAD_SENTINEL, np.float32)
            self._idle_valid = np.zeros((cap,), bool)
        else:
            self.engine = get_engine("slots", slots=config.slots)
            self._mesh = self._sharding = None
            self._fleet = None
            # device-resident idle-lane fillers (staged-scan shaped)
            self._idle_pts = jnp.full((cap, 3), PAD_SENTINEL, jnp.float32)
            self._idle_valid = jnp.zeros((cap,), bool)
        self._idle_state = empty_state(sp)   # one idle lane (map shaped)
        self._streams: dict[str, _Stream] = {}
        self._slots: list[str | None] = [None] * config.slots
        self._pending: deque[str] = deque()
        self.rounds = 0
        self.frames_processed = 0
        self.frames_dropped = 0
        self.cascade_escapes = 0
        # lane-iteration counters of the slot executable (see
        # ``service_report``)
        self.lane_iterations = 0
        self.lane_iterations_run = 0
        self.loop_iterations = 0
        # lattice-search counters (see ``service_report``)
        self.lattice_searches = 0
        self.lattice_overflow_cells = 0
        self._eye = np.eye(4, dtype=np.float32)

    @property
    def stream_config(self) -> OdometryConfig:
        """The per-stream odometry config, normalized onto the shared
        slot engine (sharded or not). A standalone
        ``OdometryPipeline(stream_config)`` is the service's bit-exact
        single-stream reference in either mode."""
        if self.config.devices is not None:
            D = int(self.config.devices)
            return self.config.odometry._replace(
                engine="sharded-slots",
                engine_kwargs=(("lanes_per_device", self.config.slots // D),
                               ("devices", D)))
        return self.config.odometry._replace(
            engine="slots",
            engine_kwargs=(("slots", self.config.slots),))

    # -- admission ---------------------------------------------------------
    def _free_lane(self) -> int | None:
        """Pick the slot a new stream binds. Single-device: first free.
        Sharded: first free lane on the least-loaded device block, so
        live streams spread across the mesh instead of saturating device
        0's block while the rest idle (ties break toward the lower
        device index — deterministic)."""
        if not self._sharded:
            return next((i for i, s in enumerate(self._slots) if s is None),
                        None)
        L = self.config.slots // self._devices
        best = None
        for d in range(self._devices):
            block = self._slots[d * L:(d + 1) * L]
            free = next((d * L + i for i, s in enumerate(block)
                         if s is None), None)
            if free is None:
                continue
            load = sum(1 for s in block if s is not None)
            if best is None or load < best[0]:
                best = (load, free)
        return None if best is None else best[1]

    def _make_stream(self, stream_id: str) -> _Stream:
        stream = _Stream(stream_id)
        submap = _LaneSubmap(self, stream) if self._sharded else None
        stream.pipe = OdometryPipeline(self.stream_config, submap=submap)
        return stream

    def admit(self, stream_id: str) -> bool:
        """Admit a new stream. Returns True if a slot was bound now,
        False if the stream was queued behind a full fleet
        (``admission="queue"``); raises RuntimeError when the fleet is
        full under ``admission="reject"``. Frames may be submitted while
        queued — they stage and wait."""
        with TraceAnnotation("fpps.admit", stream=stream_id):
            return self._admit(stream_id)

    def _admit(self, stream_id: str) -> bool:
        if stream_id in self._streams:
            raise ValueError(f"stream {stream_id!r} already admitted")
        stream = self._make_stream(stream_id)
        lane = self._free_lane()
        if lane is None:
            if self.config.admission == "reject":
                raise RuntimeError(
                    f"service full: {self.config.slots} slots bound, "
                    f"admission policy is 'reject'")
            self._streams[stream_id] = stream
            self._pending.append(stream_id)
            return False
        self._streams[stream_id] = stream
        self._slots[lane] = stream_id
        stream.slot = lane
        return True

    def close(self, stream_id: str) -> StreamReport:
        """Retire a stream: free its slot (rebinding the oldest pending
        stream, if any), drop its state, and return the final
        :class:`StreamReport`. Un-stepped staged frames are discarded
        (counted as dropped). In sharded mode the lane's resident map
        state is reset to idle in place — the next stream bound to this
        slot must never see its predecessor's map."""
        with TraceAnnotation("fpps.close", stream=stream_id):
            return self._close(stream_id)

    def _close(self, stream_id: str) -> StreamReport:
        stream = self._streams.pop(stream_id)
        stream.dropped += len(stream.queue)
        self.frames_dropped += len(stream.queue)
        report = self._report(stream)
        if stream.slot is not None:
            if self._sharded:
                reset = _sharded_reset(self._mesh, self.stream_config.submap)
                self._fleet = reset(self._fleet,
                                    jnp.int32(stream.slot))
            self._slots[stream.slot] = None
            while self._pending:
                nxt = self._pending.popleft()
                if nxt in self._streams:
                    self._slots[stream.slot] = nxt
                    self._streams[nxt].slot = stream.slot
                    break
        else:
            # stream was still pending; drop it from the wait queue lazily
            self._pending = deque(s for s in self._pending
                                  if s != stream_id)
        return report

    # -- staging -----------------------------------------------------------
    def stage_scan(self, scan, valid=None):
        """Pad a raw (n, 3) scan to the service's ``scan_capacity`` rows
        (collate sentinel conventions); returns host ``(padded, valid)``.
        This is exactly what ``submit`` stages, exposed so a reference
        ``OdometryPipeline`` can be fed bit-identical input."""
        pts = np.asarray(scan, np.float32)
        if pts.ndim != 2 or pts.shape[1] != 3:
            raise ValueError(f"scan must be (n, 3), got {pts.shape}")
        cap = self.config.scan_capacity
        if pts.shape[0] > cap:
            raise ValueError(f"scan of {pts.shape[0]} points exceeds "
                             f"scan_capacity={cap}")
        padded, pvalid = pad_cloud(pts, cap)
        if valid is not None:
            pvalid = pvalid.copy()
            pvalid[:pts.shape[0]] &= np.asarray(valid, bool)
        return padded, pvalid

    def submit(self, stream_id: str, scan, valid=None) -> bool:
        """Stage one sensor-frame scan for ``stream_id``. Single-device
        mode transfers the padded scan to the device immediately (JAX
        dispatch is async, so staging overlaps the in-flight round's
        compute — the double-buffering half of the transfer story; the
        fuse's buffer donation is the other half). Sharded mode stages
        host-side: the round start issues ONE sharded transfer that
        lands every lane directly on its owning device, instead of
        bouncing per-frame copies through the default device. Returns
        True if the frame is queued; False if backpressure dropped it
        (``drop_policy="newest"``). Dropping the *oldest* staged frame
        still returns True — the submitted frame survived, an older one
        paid."""
        with TraceAnnotation("fpps.submit", stream=stream_id):
            return self._submit(stream_id, scan, valid)

    def _submit(self, stream_id: str, scan, valid) -> bool:
        stream = self._streams[stream_id]
        padded, pvalid = self.stage_scan(scan, valid)
        if self._sharded:
            staged = _StagedFrame(pts=padded, valid=pvalid,
                                  seq=stream.submitted)
        else:
            staged = _StagedFrame(pts=jax.device_put(padded),
                                  valid=jax.device_put(pvalid),
                                  seq=stream.submitted)
        stream.submitted += 1
        if len(stream.queue) >= self.config.max_queue:
            stream.dropped += 1
            self.frames_dropped += 1
            if self.config.drop_policy == "newest":
                return False
            stream.queue.popleft()
        stream.queue.append(staged)
        return True

    # -- the fleet round ---------------------------------------------------
    def _stack_states(self, work, S):
        """Per-round stack of every lane's map state (single-device mode
        only — sharded mode's fleet state is already device-resident)."""
        n_leaves = len(self._idle_state)
        return tuple(
            jnp.stack([work[i][0].pipe.submap.state[k] if i in work
                       else self._idle_state[k] for i in range(S)])
            for k in range(n_leaves))

    def step(self) -> dict:
        """Run one service round: pop at most one staged frame per active
        stream (slot order), execute the batched data plane — vmapped
        prepare, one fleet registration, vmapped probe, one bulk fetch,
        per-stream completion, one vmapped fuse — and return
        ``{stream_id: (pose, FrameDiagnostics)}`` for every frame
        processed this round. Streams with empty queues idle at zero
        marginal device cost (their lanes are mask-dead). In sharded
        mode every stage runs inside the shard body over the streams
        mesh; the structure is identical.

        The round and each of its stages run inside named profiler spans
        (``fpps.step`` with ``round=``, then ``fpps.prepare``,
        ``fpps.classify``, ``fpps.register``, one ``fpps.complete`` per
        stream with ``stream=`` and ``frame=``, ``fpps.fuse``); each
        blocking device->host fetch sits in a ``fpps.wait.<stage>``
        child span, so a trace separates the host waiting on the chip
        from host work while the chip idles."""
        with TraceAnnotation("fpps.step", round=self.rounds):
            return self._step()

    def _step(self) -> dict:
        work = {}
        for lane, sid in enumerate(self._slots):
            if sid is None:
                continue
            stream = self._streams[sid]
            if stream.queue:
                work[lane] = (stream, stream.queue.popleft())
        if not work:
            return {}
        self.rounds += 1

        # 1. staged-scan stack -> vmapped scrub + downsample (data plane)
        with TraceAnnotation("fpps.prepare"):
            src_b, sv_b, n_valid = self._prepare(work)

        # 2. host classification: which lanes register this round
        with TraceAnnotation("fpps.classify"):
            preps = {}
            for lane, (stream, _) in work.items():
                preps[lane] = stream.pipe.prepare_frame(
                    None, downsampled=(src_b[lane], sv_b[lane],
                                       int(n_valid[lane])))
            reg_lanes = [lane for lane, p in preps.items()
                         if p.kind == KIND_REGISTER and not p.skip_primary]

        # 3-4. one fleet registration through the slot executable, the
        #      batched health probe and ONE bulk device->host fetch
        res_host = lat_host = None
        if reg_lanes:
            with TraceAnnotation("fpps.register"):
                res_host, lat_host = self._register(work, preps, reg_lanes,
                                                    src_b, sv_b)

        # 5. host control plane: per-stream completion (cascade, accept,
        #    quarantine) with the fuse deferred into one batched call
        outputs = {}
        fuse_reqs = {}
        for lane, (stream, _) in work.items():
            prep = preps[lane]
            with TraceAnnotation("fpps.complete", stream=stream.id,
                                 frame=prep.frame):
                if lane in reg_lanes:
                    lane_res = jax.tree_util.tree_map(lambda x: x[lane],
                                                      res_host)
                    lat = float(lat_host[lane])
                else:
                    lane_res, lat = None, None
                pose, diag, fuse_req = stream.pipe.complete_frame(
                    prep, lane_res, lattice_frac=lat, defer_fuse=True,
                    defer_bootstrap=self._sharded)
            if prep.kind == KIND_REGISTER and diag.recovery_tier > 0:
                stream.cascade_escapes += 1
                self.cascade_escapes += 1
            if fuse_req is not None:
                fuse_reqs[lane] = fuse_req
            outputs[stream.id] = (pose, diag)
            self.frames_processed += 1

        # 6. one vmapped fuse over the fleet's submaps (donated buffers)
        if fuse_reqs:
            with TraceAnnotation("fpps.fuse"):
                self._fuse(work, fuse_reqs, outputs, src_b, sv_b)
        return outputs

    def _prepare(self, work: dict):
        """Stack the round's staged scans (idle lanes get the filler) and
        run the vmapped scrub + downsample; returns ``(src_b, sv_b,
        n_valid)`` with ``n_valid`` fetched to the host."""
        odo = self.stream_config
        S = self.config.slots
        if self._sharded:
            pts_b = jax.device_put(
                np.stack([work[i][1].pts if i in work else self._idle_pts
                          for i in range(S)]), self._sharding)
            valid_b = jax.device_put(
                np.stack([work[i][1].valid if i in work
                          else self._idle_valid for i in range(S)]),
                self._sharding)
            prepare = _sharded_prepare(self._mesh, odo.scan_voxel,
                                       odo.scan_budget)
            src_b, sv_b, nv_b = prepare(pts_b, valid_b)
        else:
            pts_b = jnp.stack([work[i][1].pts if i in work
                               else self._idle_pts for i in range(S)])
            valid_b = jnp.stack([work[i][1].valid if i in work
                                 else self._idle_valid for i in range(S)])
            src_b, sv_b, nv_b = prepare_scans(pts_b, valid_b,
                                               odo.scan_voxel,
                                               odo.scan_budget)
        with TraceAnnotation("fpps.wait.prepare"):
            n_valid = np.asarray(nv_b)
        return src_b, sv_b, n_valid

    def _register(self, work: dict, preps: dict, reg_lanes: list, src_b,
                  sv_b):
        """Register ``reg_lanes`` in one call of the slot executable, each
        lane searching its map's lattice, probe the lattice for every
        lane, and fetch both to the host in one ``device_get``; returns
        ``(res_host, lat_host)`` and adds the round to the lane-iteration
        and lattice counters."""
        odo = self.stream_config
        S = self.config.slots
        active = np.zeros((S,), bool)
        active[reg_lanes] = True
        if self._sharded:
            active_d = jax.device_put(active, self._sharding)
            sub = odo.submap
            if sub.storage == "fp32":
                dst_b, dv_b = self._fleet[0], self._fleet[1]
            else:
                views = _sharded_target_views(self._mesh, sub)
                dst_b, dv_b = views(self._fleet)
            origin_b = self._fleet[-1]
        else:
            active_d = jnp.asarray(active, bool)
            dst_b = jnp.stack([
                work[i][0].pipe.submap.points if i in work
                else state_views(self._idle_state, odo.submap)[0]
                for i in range(S)])
            dv_b = jnp.stack([
                work[i][0].pipe.submap.valid if i in work
                else state_views(self._idle_state, odo.submap)[1]
                for i in range(S)])
            origin_b = jnp.stack([
                work[i][0].pipe.submap.origin if i in work
                else self._idle_state[-1] for i in range(S)])
        T0_b = np.stack([preps[i].T0 if i in preps else self._eye
                         for i in range(S)])
        res = self.engine.register_batch(
            src_b, odo.submap.voxel_map(dst_b, origin_b), odo.params,
            src_valid=jnp.logical_and(sv_b, active_d[:, None]),
            dst_valid=jnp.logical_and(dv_b, active_d[:, None]),
            initial_transforms=T0_b)
        if self._sharded:
            probe = _sharded_lattice(self._mesh, odo.submap)
            lat_b = probe(res.T, src_b, sv_b, origin_b)
        else:
            lat_b = outside_lattice_fracs(res.T, src_b, sv_b, origin_b,
                                          odo.submap)
        with TraceAnnotation("fpps.wait.register"):
            res_host, lat_host = jax.device_get((res, lat_b))
        self._count_iterations(res_host.iterations, reg_lanes)
        self.lattice_searches += len(reg_lanes)
        self.lattice_overflow_cells += int(
            res_host.lattice_overflow[reg_lanes].sum())
        return res_host, lat_host

    def _count_iterations(self, iterations: np.ndarray,
                          reg_lanes: list) -> None:
        """Lane-iteration counters of one slot-executable call, from the
        fetched per-lane ``ICPResult.iterations``. Each device block's
        vmapped ``while_loop`` runs until its slowest lane stops, so it
        computes ``lanes_per_device x`` that lane's iterations whatever
        the other lanes needed."""
        lanes = self.config.slots // self._devices
        slowest = iterations.reshape(self._devices, lanes).max(axis=1)
        self.lane_iterations += int(iterations[reg_lanes].sum())
        self.lane_iterations_run += lanes * int(slowest.sum())
        self.loop_iterations += int(slowest.max())

    def _fuse(self, work: dict, fuse_reqs: dict, outputs: dict, src_b,
              sv_b) -> None:
        """One vmapped fuse of the accepted frames into their maps, then
        the per-lane write-back of map state and diagnostics (amends
        ``outputs`` in place)."""
        odo = self.stream_config
        S = self.config.slots
        accept = np.zeros((S,), bool)
        accept[list(fuse_reqs)] = True
        pose_np = np.stack([fuse_reqs[i].pose if i in fuse_reqs
                            else self._eye for i in range(S)])
        mcap = int(odo.submap.capacity)
        if self._sharded:
            # the fuse sources ARE this round's prepared batch
            # (every FuseRequest.src is its lane's src_b slice)
            fuse = _sharded_fuse(self._mesh, odo.submap)
            self._fleet, occ_b, drop_b = fuse(
                self._fleet, src_b, sv_b,
                jax.device_put(pose_np, self._sharding),
                jax.device_put(accept, self._sharding))
        else:
            state_b, occ_b, drop_b = fuse_lanes(
                self._stack_states(work, S),
                jnp.stack([fuse_reqs[i].src if i in fuse_reqs
                           else src_b[i] for i in range(S)]),
                jnp.stack([fuse_reqs[i].sv if i in fuse_reqs
                           else sv_b[i] for i in range(S)]),
                jnp.asarray(pose_np, jnp.float32),
                jnp.asarray(accept, bool), odo.submap)
        with TraceAnnotation("fpps.wait.fuse"):
            occ, drop = np.asarray(occ_b), np.asarray(drop_b)
        for lane in fuse_reqs:
            stream = work[lane][0]
            sub = stream.pipe.submap
            if self._sharded:
                sub._occupied = int(occ[lane])
            else:
                sub.state = tuple(leaf[lane] for leaf in state_b)
            sub.frames_inserted += 1
            sub.dropped_cells += int(drop[lane])
            pose, diag = outputs[stream.id]
            diag = stream.pipe.amend_diagnostics(
                diag.frame, map_occupancy=float(occ[lane]) / mcap,
                dropped_cells=sub.dropped_cells)
            outputs[stream.id] = (pose, diag)

    def sync(self) -> None:
        """Block until every in-flight device computation for the fleet
        (registration, fuse writebacks) has completed. Outputs returned by
        ``step`` are already host-side; this exists for benchmarks that
        must charge the async fuse tail to the round that issued it."""
        with TraceAnnotation("fpps.sync"):
            self._sync()

    def _sync(self) -> None:
        if self._sharded:
            jax.block_until_ready(self._fleet)
            return
        for sid in self._slots:
            if sid is not None:
                sub = self._streams[sid].pipe.submap
                jax.block_until_ready(sub.state)

    def drain(self, max_rounds: int | None = None) -> dict:
        """Step until every active stream's queue is empty (or
        ``max_rounds``); returns ``{stream_id: [(pose, diag), ...]}``
        accumulated in round order."""
        out: dict[str, list] = {}
        rounds = 0
        while any(self._streams[sid].queue for sid in self._slots
                  if sid is not None):
            if max_rounds is not None and rounds >= max_rounds:
                break
            for sid, res in self.step().items():
                out.setdefault(sid, []).append(res)
            rounds += 1
        return out

    # -- observability -----------------------------------------------------
    def _report(self, stream: _Stream) -> StreamReport:
        pipe = stream.pipe
        return StreamReport(
            stream_id=stream.id,
            frames_submitted=stream.submitted,
            frames_processed=len(pipe.diagnostics),
            frames_dropped=stream.dropped,
            frames_quarantined=pipe.quarantined_count,
            cascade_escapes=stream.cascade_escapes,
            health_counts=pipe.health_counts(),
            final_pose=pipe.poses[-1] if pipe.poses else None)

    def report(self, stream_id: str) -> StreamReport:
        """Current :class:`StreamReport` for one stream (active or
        pending), without retiring it."""
        return self._report(self._streams[stream_id])

    def service_report(self) -> dict:
        """Fleet-level counters: rounds run, frames processed/dropped,
        cascade escapes, live/pending stream counts, the device count the
        fleet is sharded over (1 = single-device mode), the slot
        engine's trace count (constant after warmup = the retrace-free
        invariant), and the slot executable's lane iterations:

          * ``lane_iterations`` — ICP iterations of the registered lanes,
            summed over lanes and rounds (the work asked for);
          * ``lane_iterations_run`` — lane-iterations the executable
            computed: per round and device block, ``lanes_per_device x``
            the block's slowest lane (converged and idle lanes ride the
            loop frozen until it ends);
          * ``loop_iterations`` — the rounds' trip counts (the slowest
            lane's iterations), summed;
          * ``lattice_searches`` — registered lanes whose correspondences
            came from their map's lattice table, summed over rounds;
          * ``lattice_overflow_cells`` — map cells those lanes' tables
            found holding more points than a cell slot keeps, summed: the
            only way the lattice search can miss a neighbour inside the
            gate, so 0 says the search was exact;
          * ``slots`` — the fleet width.

        ``1 - lane_iterations / lane_iterations_run`` is the share of the
        executable's lane-iterations spent on lanes already converged or
        idle. All are counted from the fetched results, with no transfer
        of their own."""
        return {
            "rounds": self.rounds,
            "frames_processed": self.frames_processed,
            "frames_dropped": self.frames_dropped,
            "cascade_escapes": self.cascade_escapes,
            "active_streams": sum(1 for s in self._slots if s is not None),
            "pending_streams": len(self._pending),
            "devices": self._devices,
            "trace_count": self.engine.trace_count,
            "lane_iterations": self.lane_iterations,
            "lane_iterations_run": self.lane_iterations_run,
            "loop_iterations": self.loop_iterations,
            "lattice_searches": self.lattice_searches,
            "lattice_overflow_cells": self.lattice_overflow_cells,
            "slots": self.config.slots,
        }

    def diagnostics(self, stream_id: str) -> list[FrameDiagnostics]:
        """The per-frame diagnostics history of one stream."""
        return list(self._streams[stream_id].pipe.diagnostics)
