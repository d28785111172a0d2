"""Streaming scan-to-map odometry on the engine layer (DESIGN.md §10, §12).

The paper's headline numbers are measured on KITTI odometry *streams*, not
isolated frame pairs; this module is the streaming subsystem that turns
per-frame registration into a trajectory:

  * **scan-to-map** — each incoming scan registers against the rolling
    local submap (``repro.data.submap``) instead of the previous scan, so
    per-frame error stops compounding into a random walk: the map is the
    common anchor, and revisited structure refines it.
  * **constant-velocity warm start** — the motion model predicts each
    frame's pose from the tracked inter-frame velocity
    (``T_pred = T_k @ v``, ``v = T_{k-1}^{-1} T_k`` after each accepted
    frame) and feeds it through ``initial_transform``, cutting iterations
    on smooth motion and keeping the basin of attraction centred under
    fast motion. On a *rejected* frame the velocity **decays toward
    identity** (``velocity_decay`` per frame) — without the decay a
    multi-frame sensor dropout has the platform coasting at full speed
    forever, and the prediction error compounds geometrically.
  * **health-gated recovery cascade** (§12) — every registration is
    distilled into a :class:`~repro.core.health.RegistrationHealth`
    verdict (inlier mass, final RMSE, degeneracy, pose jump vs. the
    motion model, scan-outside-map fraction). A non-OK frame walks a
    bounded retry ladder instead of being trusted or dropped outright:

      tier 1 ``widen``      same map, widened gate + coarser pyramid
                            schedule (occlusion/dropout shrink overlap;
                            a wider basin re-acquires it)
      tier 2 ``fallback``   engine fallback to the unfused dense-XLA
                            path with the warm start *discarded* (a
                            poisoned motion-model prediction is the
                            failure being escaped)
      tier 3 ``wide_basin`` wide-basin relocalization: very coarse-to-
                            fine schedule, 4x gate, restarted from the
                            last accepted pose
      tier 4 (implicit)     coast on the decayed motion model and
                            **quarantine** the frame — the pose is a
                            prediction, the scan is NOT fused, so one
                            bad frame cannot poison the anchor every
                            later frame registers against

    The first tier that comes back OK wins; if none does, the least-bad
    SUSPECT attempt (fewest tripped signals, then smallest jump from the
    prediction) is accepted as the *output* pose but the scan is
    **quarantined** — not fused into the map — so a merely-plausible
    pose cannot poison the anchor; only an all-FAILED ladder coasts.
    The ladder is bounded: at most ``1 + len(recovery_tiers)``
    registrations per frame.
  * **sensor-boundary scrubbing** — NaN/Inf rows are scrubbed off the
    scan before anything (even the voxel downsample's min-derived lattice
    origin) can see them.

Per-frame diagnostics (iterations, inlier fraction, map occupancy,
health verdict, recovery tier, accept/quarantine) are first-class
outputs — a stream you cannot observe is a stream you cannot trust.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine import get_engine
from repro.core.health import (FAILED, OK, SUSPECT, HealthThresholds,
                               RegistrationHealth, assess_registration,
                               normal_equation_condition)
from repro.core.icp import ICPParams, scrub_nonfinite
from repro.data.collate import PAD_SENTINEL
from repro.data.normals import NormalParams, estimate_normals
from repro.data.submap import (Submap, SubmapParams, lane0_batch,
                               outside_lattice_fracs)
from repro.data.voxelize import voxel_downsample

# Retry-tier pyramid schedules: ((voxel_m, iters, max_points), ...).
# ``widen`` doubles the basin (coarse 8 m level re-acquires overlap lost
# to occlusion/dropout); ``wide_basin`` starts at 16 m — a relocalization
# sweep for when even the widened gate cannot see the map.
_WIDEN_LEVELS = ((8.0, 8, 4096), (4.0, 6, 8192))
_WIDE_BASIN_LEVELS = ((16.0, 8, 2048), (8.0, 8, 4096), (4.0, 6, 8192))

DEFAULT_RECOVERY_TIERS = ("widen", "fallback", "wide_basin")


class OdometryConfig(NamedTuple):
    """Pipeline configuration. ``params.max_iterations`` is the per-frame
    iteration cap (the paper's 50 is generous for warm-started streaming;
    30 keeps worst-case latency bounded). ``scan_voxel``/``scan_budget``
    shape the voxel-downsampled registration source — the same cloud that
    is fused into the map on acceptance.

    Size ``scan_budget`` ABOVE the scan's occupied-voxel count:
    ``voxel_downsample`` drops overflow cells deterministically from the
    cell-id sort tail, which is a *spatially biased* truncation (the +x
    end of the scene vanishes first) — poison for odometry. Same for
    ``submap.capacity`` vs the eviction ball (watch ``map_occupancy``).

    With ``recovery=True`` the health thresholds decide accept/reject
    (``min_inlier_frac`` is subsumed by ``thresholds``); with it off the
    pipeline keeps the legacy degenerate/``min_inlier_frac`` guard. The
    velocity decay applies either way — it fixes a bug, not a feature.
    """

    # Pyramid engine, polish-only: the finest-level grid NN gives O(27K)
    # correspondence against the resident map AND gates scan points whose
    # map neighbourhood is empty (the frontier a moving ego constantly
    # creates) through the honest d2=inf path instead of dragging the pose
    # toward the map boundary.
    engine: str = "pyramid"
    engine_kwargs: tuple = (("levels", ()),)
    # Huber by default: residual frontier points that DO land within one
    # grid cell of mapped space still pull backward; huber bounds that pull
    # (a redescending kernel or a tight gate instead lets the ground plane
    # slide on cold starts — see DESIGN.md §10).
    params: ICPParams = ICPParams(max_iterations=30,
                                  max_correspondence_distance=1.0,
                                  transformation_epsilon=1e-5,
                                  robust_kernel="huber", robust_scale=0.3)
    submap: SubmapParams = SubmapParams(voxel_size=0.75, capacity=24576,
                                        dims=(128, 128, 32),
                                        evict_radius=40.0)
    scan_voxel: float = 0.75
    scan_budget: int = 8192
    motion_model: bool = True
    min_inlier_frac: float = 0.2
    # -- degradation & recovery (§12) -------------------------------------
    recovery: bool = True
    recovery_tiers: tuple = DEFAULT_RECOVERY_TIERS
    thresholds: HealthThresholds = HealthThresholds()
    velocity_decay: float = 0.5
    # Cold-start grace: frames below this index are health-labelled but
    # never retried — frame 1 registers against a one-scan map with no
    # velocity estimate, so its rmse/jump signals read SUSPECT on clean
    # input (cold-start truth, not a fault).
    warmup_frames: int = 2
    # Per-frame scan-observability probe: normals on the downsampled scan
    # -> 6x6 plane normal-equation conditioning. Pose-independent (sensor
    # frame), computed once per frame; it is the only signal that sees a
    # sector crop *before* the pose slides (residual metrics read fine
    # while the unconstrained direction drifts).
    observability_probe: bool = True


class FrameDiagnostics(NamedTuple):
    frame: int
    iterations: int
    inlier_frac: float
    rmse: float
    degenerate: bool
    accepted: bool          # False: pose fell back to the motion model
    map_occupancy: float    # submap capacity in use after this frame
    health: str = OK        # RegistrationHealth verdict for this frame
    recovery_tier: int = 0  # 0 primary; 1..N retry tier; N+1 coasted
    pose_jump: float = 0.0  # metres vs. the motion-model prediction
    quarantined: bool = False   # scan withheld from the map
    dropped_cells: int = 0  # sticky submap-saturation counter (running
                            # total of occupied voxels the capacity budget
                            # dropped — distinguishes a clean 1.0
                            # occupancy from silent truncation)


# Frame classification out of prepare_frame: which half of the frame
# lifecycle (register / bootstrap-the-map / coast-an-empty-scan) the
# completion step must run. Strings, not an enum, so diagnostics and the
# service's per-lane bookkeeping stay greppable.
KIND_BOOTSTRAP = "bootstrap"
KIND_EMPTY = "empty"
KIND_REGISTER = "register"


class PreparedFrame(NamedTuple):
    """Device-side half of one frame, produced by
    :meth:`OdometryPipeline.prepare_frame`: the scrubbed + voxel-
    downsampled scan, its validity mask, and the host-side classification
    (bootstrap / empty / register) plus warm-start prediction. The
    registration service batches many of these through one executable;
    :meth:`OdometryPipeline.complete_frame` consumes one plus its
    registration result."""

    frame: int
    kind: str               # KIND_BOOTSTRAP | KIND_EMPTY | KIND_REGISTER
    src: jax.Array          # (scan_budget, 3) downsampled sensor-frame scan
    sv: jax.Array           # (scan_budget,) validity mask
    T0: np.ndarray          # warm-start prediction (identity off-register)
    reacquire: bool = False     # first frame after a coast streak
    skip_primary: bool = False  # reacquire + tiers: ladder only, no primary


class FuseRequest(NamedTuple):
    """Deferred map-fusion work order returned by
    :meth:`OdometryPipeline.complete_frame` under ``defer_fuse=True``: the
    accepted frame's downsampled scan (sensor frame), mask, and output
    pose. The service executes these as ONE vmapped submap fuse across
    all streams instead of per-stream inserts."""

    src: jax.Array
    sv: jax.Array
    pose: np.ndarray


def prepare_scan(pts, valid, voxel: float, budget: int):
    """Sensor-boundary stage of one frame: scrub NaN/Inf rows, then
    voxel-downsample to at most ``budget`` points."""
    pts, valid = scrub_nonfinite(pts, valid)
    return voxel_downsample(pts, voxel, max_points=budget, valid=valid)


@functools.partial(jax.jit, static_argnames=("voxel", "budget"))
def prepare_scans(pts_b, valid_b, voxel: float, budget: int):
    """:func:`prepare_scan` over ``(L, ...)`` lanes (see the lane note in
    ``data/submap.py``). Returns ``(src_b, sv_b, n_valid_b)``."""
    src_b, sv_b = jax.vmap(
        lambda p, v: prepare_scan(p, v, voxel, budget))(pts_b, valid_b)
    return src_b, sv_b, jnp.sum(sv_b, axis=1)


@functools.partial(jax.jit, static_argnames=("nparams",))
def _scan_plane_system(src: jax.Array, sv: jax.Array,
                       nparams: NormalParams) -> jax.Array:
    """6x6 plane normal matrix of the scan against its own estimated
    normals — the observability probe's device half (one executable per
    scan-budget shape)."""
    normals, nvalid = estimate_normals(src, nparams, valid=sv)
    w = jnp.logical_and(sv, nvalid).astype(jnp.float32)
    a = jnp.concatenate([jnp.cross(src, normals), normals], axis=-1)
    return jnp.matmul((a * w[:, None]).T, a, precision="highest")


def _compose(a, b) -> np.ndarray:
    """``a @ b`` of 4x4 rigid transforms, in float64 on fresh copies,
    rounded to float32. A float32 product of the same values can round
    differently depending on where the operands sit in memory (the host
    BLAS picks its kernel by alignment); the service hands its pipelines
    views into one fleet-wide result buffer, so a float32 product would
    break their bit-for-bit parity with a standalone pipeline."""
    return (np.asarray(a, np.float64) @ np.asarray(b, np.float64)).astype(
        np.float32)


def _decay_toward_identity(T: np.ndarray, factor: float) -> np.ndarray:
    """Shrink a rigid motion: translation scaled by ``factor``, rotation
    angle scaled by ``factor`` about the same axis (Rodrigues)."""
    T = np.asarray(T, np.float64)
    R = T[:3, :3]
    cos = np.clip((np.trace(R) - 1.0) / 2.0, -1.0, 1.0)
    angle = float(np.arccos(cos))
    out = np.eye(4)
    if angle > 1e-8:
        axis = np.array([R[2, 1] - R[1, 2], R[0, 2] - R[2, 0],
                         R[1, 0] - R[0, 1]])
        axis /= max(np.linalg.norm(axis), 1e-12)
        K = np.array([[0.0, -axis[2], axis[1]],
                      [axis[2], 0.0, -axis[0]],
                      [-axis[1], axis[0], 0.0]])
        a = angle * factor
        out[:3, :3] = np.eye(3) + np.sin(a) * K + (1 - np.cos(a)) * (K @ K)
    out[:3, 3] = factor * T[:3, 3]
    return out.astype(np.float32)


class OdometryPipeline:
    """Stateful scan-to-map odometry: feed sensor-frame scans in order,
    read back poses (sensor -> frame-0/map) and per-frame diagnostics.

        pipe = OdometryPipeline(OdometryConfig(engine="xla"))
        for scan in scans:                       # (N_k, 3) numpy, any N_k
            pose, diag = pipe.process(scan)

    All heavy work runs through the shared engine layer: the submap's
    static capacity means every frame after the first hits one compiled
    executable (one shape, one ``ICPParams``), and the warm start is
    threaded through the engine's ``initial_transform`` argument. Retry
    tiers are named ``get_engine`` singletons, so their jit caches persist
    across frames and across pipeline instances.
    """

    def __init__(self, config: OdometryConfig = OdometryConfig(),
                 submap: Submap | None = None):
        self.config = config
        kwargs = dict(config.engine_kwargs)
        if config.engine != "pyramid":
            # the default engine_kwargs select the pyramid's polish-only
            # schedule; they don't apply to other engine constructors
            kwargs.pop("levels", None)
        self.engine = get_engine(config.engine, **kwargs)
        # Per-frame device stages run at lane 0 of the engine's per-device
        # slot block, so a pipeline on a service's slot engine runs the
        # service's own executables (one lane for other engines).
        self._lanes = getattr(self.engine, "lanes_per_device",
                              getattr(self.engine, "slots", 1))
        # ``submap`` lets a fleet owner substitute a view over shared
        # device state (the sharded service's lane views) for the default
        # per-stream map; anything duck-typing Submap's read surface works.
        self.submap = Submap(config.submap) if submap is None else submap
        self.poses: list[np.ndarray] = []
        self.diagnostics: list[FrameDiagnostics] = []
        # inter-frame velocity v = T_{k-1}^{-1} T_k, decayed on rejection
        self._velocity = np.eye(4, dtype=np.float32)
        self._coast_streak = 0       # consecutive frames without a pose fix
        self.recovery_count = 0      # sticky: frames that left tier 0
        self.quarantined_count = 0   # sticky: frames withheld from the map

    # -- motion model ------------------------------------------------------
    def _predict(self) -> np.ndarray:
        """Constant-velocity pose prediction for the incoming frame."""
        if len(self.poses) < 2 or not self.config.motion_model:
            return self.poses[-1]
        return _compose(self.poses[-1], self._velocity)

    # -- health ------------------------------------------------------------
    def _out_of_lattice_frac(self, res, src, sv) -> float:
        """Fraction of the (pose-transformed) scan outside the submap
        lattice — the low-overlap/teleport signal. Pure bounds check
        against the rolling lattice; no grid build."""
        L = self._lanes
        frac = outside_lattice_fracs(
            lane0_batch(jnp.asarray(res.T, jnp.float32), jnp.eye(4), L),
            lane0_batch(src, PAD_SENTINEL, L), lane0_batch(sv, False, L),
            lane0_batch(self.submap.origin, 0.0, L), self.submap.params)
        return float(frac[0])

    def _assess(self, res, T0, src, sv, condition: float | None = None,
                trust_prediction: bool = True,
                out_of_lattice: float | None = None) -> RegistrationHealth:
        # The jump signal needs a real prediction: with <2 poses (or the
        # motion model off) T0 is just the last pose, and "jump" would
        # penalize genuine ego motion. Reacquire mode also drops it —
        # after a coast the prediction is exactly what is no longer
        # trusted, and a *correct* re-acquisition necessarily jumps away
        # from it. ``out_of_lattice`` lets the service supply the probe
        # from its batched (vmapped) evaluation; per-frame callers leave
        # it None and pay the eager probe here.
        predicted = (T0 if trust_prediction and self.config.motion_model
                     and len(self.poses) >= 2 else None)
        if out_of_lattice is None:
            out_of_lattice = self._out_of_lattice_frac(res, src, sv)
        return assess_registration(
            res, predicted=predicted, thresholds=self.config.thresholds,
            out_of_lattice=out_of_lattice, condition=condition)

    def _scan_condition(self, src, sv) -> float | None:
        """Observability of the scan itself (pose-independent, once per
        frame): conditioning of its 6x6 plane system."""
        if not self.config.observability_probe:
            return None
        A = np.asarray(_scan_plane_system(src, sv, NormalParams()),
                       np.float64)
        return normal_equation_condition(A)

    # -- recovery tiers ----------------------------------------------------
    def _tier_attempt(self, name: str, src, sv, map_pts, map_valid, T0):
        """Run one named retry tier; returns its ICPResult."""
        cfg = self.config
        if name == "widen":
            engine = get_engine("pyramid", levels=_WIDEN_LEVELS)
            params = cfg.params._replace(
                max_correspondence_distance=(
                    2.0 * cfg.params.max_correspondence_distance),
                robust_scale=2.0 * cfg.params.robust_scale)
            init = T0                      # keep the warm start
        elif name == "fallback":
            engine = get_engine("xla")
            params = cfg.params
            init = self.poses[-1]          # warm start discarded
        elif name == "wide_basin":
            engine = get_engine("pyramid", levels=_WIDE_BASIN_LEVELS)
            params = cfg.params._replace(
                max_correspondence_distance=(
                    4.0 * cfg.params.max_correspondence_distance),
                robust_scale=2.0 * cfg.params.robust_scale)
            init = self.poses[-1]          # relocalize from last good pose
        else:
            raise ValueError(f"unknown recovery tier {name!r}; "
                             f"known: {DEFAULT_RECOVERY_TIERS}")
        return engine.register(src, map_pts, params, initial_transform=init,
                               src_valid=sv, dst_valid=map_valid)

    def _cascade(self, src, sv, map_pts, map_valid, T0,
                 condition: float | None = None, reacquire: bool = False,
                 primary=None, out_of_lattice: float | None = None):
        """Primary attempt + bounded retry ladder. Returns
        (result_or_None, health, tier): ``None`` result means coast.

        ``reacquire=True`` (the frame after a coast) skips the primary:
        the prediction's uncertainty has outgrown the narrow gate, so the
        primary's basin need not contain the truth — it locks onto an
        alias that *reads* healthy (small jump vs. the equally-stale
        prediction, ordinary rmse). The coarse-first retry schedules are
        built for exactly this uncertainty, so the ladder starts there.

        ``primary`` (with its batched ``out_of_lattice`` probe) is the
        service path: the tier-0 registration already ran inside the
        fleet-wide executable, so the ladder only spends per-stream
        registrations when that result's health gates it here.
        """
        cfg = self.config
        attempts = []
        if not (reacquire and cfg.recovery_tiers):
            res = primary
            if res is None:
                res = self.engine.register(src, map_pts, cfg.params,
                                           initial_transform=T0,
                                           src_valid=sv, dst_valid=map_valid)
            health = self._assess(res, T0, src, sv, condition,
                                  out_of_lattice=out_of_lattice)
            if health.ok or not cfg.recovery:
                return res, health, 0
            attempts.append((0, res, health))
        for tier, name in enumerate(cfg.recovery_tiers, start=1):
            r = self._tier_attempt(name, src, sv, map_pts, map_valid, T0)
            h = self._assess(r, T0, src, sv, condition,
                             trust_prediction=not reacquire)
            if h.ok:
                return r, h, tier
            attempts.append((tier, r, h))
        # No rung is OK: take the least-bad SUSPECT — fewest tripped
        # signals, then smallest jump from the prediction. NEVER compare
        # inlier mass across tiers: a widened gate inflates it by
        # construction, so the worst pose would win. Ties keep the
        # earliest tier (the primary's narrow-gate estimate).
        suspects = [a for a in attempts if a[2].verdict == SUSPECT]
        if suspects:
            tier, r, h = min(suspects,
                             key=lambda a: (len(a[2].reasons),
                                            a[2].pose_jump_m))
            return r, h, tier
        # every rung FAILED: coast (tier N+1), report the primary's health
        return None, attempts[0][2], len(cfg.recovery_tiers) + 1

    # -- streaming API -----------------------------------------------------
    def prepare_frame(self, scan, valid=None,
                      downsampled=None) -> PreparedFrame:
        """Device-side frame ingest + host classification, without the
        registration: scrub NaN/Inf rows, voxel-downsample to the scan
        budget, predict the warm start, and decide whether this frame
        bootstraps the map, coasts (no usable returns), or registers.

        ``downsampled=(src, sv, n_valid)`` skips the scrub/downsample —
        the service path, which runs that stage as one vmapped executable
        across every stream and hands each pipeline its own lane. The
        lane must be bit-identical to what this method would compute
        (guaranteed: a vmapped lane of the same program is).
        """
        cfg = self.config
        if downsampled is None:
            pts = jnp.asarray(scan, jnp.float32)
            valid = (jnp.ones(pts.shape[0], bool) if valid is None
                     else jnp.asarray(valid, bool))
            L = self._lanes
            src_b, sv_b, nv_b = prepare_scans(
                lane0_batch(pts, PAD_SENTINEL, L),
                lane0_batch(valid, False, L), cfg.scan_voxel,
                cfg.scan_budget)
            src, sv, n_valid = src_b[0], sv_b[0], int(nv_b[0])
        else:
            src, sv, n_valid = downsampled
        frame = len(self.poses)
        if frame == 0:
            return PreparedFrame(frame=frame, kind=KIND_BOOTSTRAP, src=src,
                                 sv=sv, T0=np.eye(4, dtype=np.float32))
        if n_valid == 0:
            return PreparedFrame(frame=frame, kind=KIND_EMPTY, src=src,
                                 sv=sv, T0=np.asarray(self._predict(),
                                                      np.float32))
        reacquire = (cfg.recovery and frame >= cfg.warmup_frames
                     and self._coast_streak > 0)
        return PreparedFrame(frame=frame, kind=KIND_REGISTER, src=src,
                             sv=sv, T0=np.asarray(self._predict(),
                                                  np.float32),
                             reacquire=reacquire,
                             skip_primary=(reacquire
                                           and bool(cfg.recovery_tiers)))

    def complete_frame(self, prep: PreparedFrame, result=None, *,
                       lattice_frac: float | None = None,
                       defer_fuse: bool = False,
                       defer_bootstrap: bool = False):
        """Host-side frame completion: health assessment, recovery
        cascade, accept/quarantine bookkeeping, map fusion. Returns
        ``(pose, diagnostics, fuse_request)``.

        ``result`` is the primary registration's ICPResult for
        ``KIND_REGISTER`` frames (None when ``prep.skip_primary`` — the
        cascade ladder runs without a tier 0). ``lattice_frac``
        optionally supplies the out-of-lattice probe for that primary
        result (the service's batched probe). With ``defer_fuse=True`` an
        accepted fusable frame returns a :class:`FuseRequest` instead of
        inserting into the submap — the caller owns the fuse and must
        then patch ``diag.map_occupancy`` (reported here as the pre-fuse
        value). ``defer_bootstrap=True`` (sharded service: the fleet's
        submaps live in sharded device state no per-stream insert can
        write) extends the deferral to the bootstrap frame's first
        insert, as a ``FuseRequest`` with the identity pose.
        """
        cfg = self.config
        frame, src, sv, T0 = prep.frame, prep.src, prep.sv, prep.T0
        fuse_req = None
        if prep.kind == KIND_BOOTSTRAP:
            pose = np.eye(4, dtype=np.float32)
            if defer_fuse and defer_bootstrap:
                fuse_req = FuseRequest(src=src, sv=sv, pose=pose)
                occ = -1.0
            else:
                self.submap.insert(src, center=np.zeros(3, np.float32),
                                   valid=sv)
                occ = self.submap.occupancy()
            diag = FrameDiagnostics(frame=0, iterations=0, inlier_frac=1.0,
                                    rmse=0.0, degenerate=False, accepted=True,
                                    map_occupancy=occ,
                                    dropped_cells=self.submap.dropped_cells)
        elif prep.kind == KIND_EMPTY:
            # dropped frame (no usable returns): coast without spending a
            # registration, quarantine, decay the velocity
            pose = np.asarray(T0, np.float32)
            self._velocity = _decay_toward_identity(self._velocity,
                                                    cfg.velocity_decay)
            self._coast_streak += 1
            tier = len(cfg.recovery_tiers) + 1 if cfg.recovery else 0
            if tier > 0:
                self.recovery_count += 1
            self.quarantined_count += 1
            diag = FrameDiagnostics(frame=frame, iterations=0,
                                    inlier_frac=0.0, rmse=float("inf"),
                                    degenerate=True, accepted=False,
                                    map_occupancy=self.submap.occupancy(),
                                    health=FAILED, recovery_tier=tier,
                                    quarantined=True,
                                    dropped_cells=self.submap.dropped_cells)
        else:
            reacquire = prep.reacquire
            if cfg.recovery and frame >= cfg.warmup_frames:
                condition = self._scan_condition(src, sv)
                map_pts, map_valid = self.submap.target()
                res, health, tier = self._cascade(
                    src, sv, map_pts, map_valid, T0, condition,
                    reacquire=reacquire, primary=result,
                    out_of_lattice=lattice_frac)
                accepted = res is not None
            else:
                res = result
                health = self._assess(res, T0, src, sv,
                                      out_of_lattice=lattice_frac)
                tier = 0
                accepted = (not bool(res.degenerate)
                            and float(res.inlier_frac)
                            >= cfg.min_inlier_frac)
            # A SUSPECT pose is good enough to *output* (jump-bounded by
            # the thresholds) but not good enough to FUSE: one wrong scan
            # in the submap poisons the anchor every later frame registers
            # against, which is how transient faults become permanent
            # drift. Legacy mode (recovery off) keeps fuse == accept.
            fused = accepted and (not cfg.recovery or health.verdict == OK)
            self._coast_streak = 0 if accepted else self._coast_streak + 1
            if accepted:
                pose = np.asarray(res.T, np.float32)
                prev = self.poses[-1]
                if not reacquire:
                    self._velocity = _compose(
                        np.linalg.inv(np.asarray(prev, np.float64)), pose)
                # else: the previous (coasted) pose was wrong, so the pose
                # delta is correction + motion entangled — the decayed
                # coast velocity is the better motion estimate; keep it.
                if fused:
                    if defer_fuse:
                        fuse_req = FuseRequest(src=src, sv=sv, pose=pose)
                    else:
                        self.submap.insert_scan(src, pose, sv,
                                                lanes=self._lanes)
            else:
                pose = np.asarray(T0, np.float32)
                # decay the motion model: coasting frames must bleed speed
                # or a dropout burst extrapolates at full velocity forever
                self._velocity = _decay_toward_identity(self._velocity,
                                                        cfg.velocity_decay)
            if tier > 0:
                self.recovery_count += 1
            if not fused:
                self.quarantined_count += 1
            last = res if res is not None else None
            diag = FrameDiagnostics(
                frame=frame,
                iterations=int(last.iterations) if last is not None else 0,
                inlier_frac=(float(last.inlier_frac)
                             if last is not None else 0.0),
                rmse=float(last.rmse) if last is not None else float("inf"),
                degenerate=(bool(last.degenerate)
                            if last is not None else True),
                accepted=accepted,
                map_occupancy=(-1.0 if fuse_req is not None
                               else self.submap.occupancy()),
                health=health.verdict, recovery_tier=tier,
                pose_jump=health.pose_jump_m,
                quarantined=not fused,
                dropped_cells=self.submap.dropped_cells)
        self.poses.append(pose)
        self.diagnostics.append(diag)
        return pose, diag, fuse_req

    def amend_diagnostics(self, frame: int,
                          **fields) -> FrameDiagnostics:
        """Patch the stored diagnostics for ``frame`` (service use: fill
        ``map_occupancy`` after a deferred batched fuse). Returns the
        amended record."""
        idx = next(i for i, d in enumerate(self.diagnostics)
                   if d.frame == frame)
        self.diagnostics[idx] = self.diagnostics[idx]._replace(**fields)
        return self.diagnostics[idx]

    def process(self, scan, valid=None) -> tuple[np.ndarray, FrameDiagnostics]:
        """Ingest one sensor-frame scan; returns (pose, diagnostics).

        ``valid`` is an optional (N,) row mask (collate conventions).
        NaN/Inf rows are scrubbed here, before even the voxel downsample's
        min-derived lattice origin can see them. This is
        :meth:`prepare_frame` + primary registration +
        :meth:`complete_frame` in sequence — the single-stream spelling of
        the same lifecycle the registration service runs batched.
        """
        prep = self.prepare_frame(scan, valid)
        res = None
        if prep.kind == KIND_REGISTER and not prep.skip_primary:
            map_pts, map_valid = self.submap.target()
            res = self.engine.register(prep.src, map_pts, self.config.params,
                                       initial_transform=prep.T0,
                                       src_valid=prep.sv,
                                       dst_valid=map_valid)
        pose, diag, _ = self.complete_frame(prep, res)
        return pose, diag

    def run(self, scans) -> tuple[np.ndarray, list[FrameDiagnostics]]:
        """Process a whole sequence; returns ((F,4,4) poses, diagnostics)."""
        for scan in scans:
            self.process(scan)
        return np.stack(self.poses), list(self.diagnostics)

    # -- stream-level summaries -------------------------------------------
    def mean_iterations(self) -> float:
        """Mean ICP iterations over registered frames (frame 0 excluded)."""
        its = [d.iterations for d in self.diagnostics if d.frame > 0]
        return float(np.mean(its)) if its else 0.0

    def rejected_frames(self) -> int:
        return sum(1 for d in self.diagnostics if not d.accepted)

    def health_counts(self) -> dict[str, int]:
        """Verdict histogram over the stream (``{"ok": ..., ...}``)."""
        out = {OK: 0, SUSPECT: 0, FAILED: 0}
        for d in self.diagnostics:
            out[d.health] += 1
        return out

    def tier_counts(self) -> dict[int, int]:
        """Histogram of the recovery tier each frame settled at."""
        out: dict[int, int] = {}
        for d in self.diagnostics:
            out[d.recovery_tier] = out.get(d.recovery_tier, 0) + 1
        return out
