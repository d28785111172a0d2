"""Multi-stream registration service: admission, retirement, drops,
retrace-freedom, and bit-exact parity with the standalone pipeline.

Every test shares ONE service configuration (slots, bucket shapes,
ICPParams), so the slot engine singleton compiles its executables once
for the whole module — the trace-counter assertions then measure the
service's behaviour, not per-test compilation. ``recovery=False`` keeps
the control plane on the legacy accept guard: no cascade tiers means no
extra per-tier engines compile inside the tests.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ICPParams
from repro.core.odometry import OdometryConfig, OdometryPipeline
from repro.data.pointcloud import SceneConfig, sequence_scans
from repro.data.submap import (Submap, SubmapParams, empty_state,
                               fuse_lanes)
from repro.serve.registration_service import (RegistrationService,
                                              ServiceConfig)

SCENE = SceneConfig(n_ground=300, n_walls=220, n_poles=60, n_clutter=70,
                    extent=12.0, sensor_range=16.0)
ODO = OdometryConfig(
    params=ICPParams(max_iterations=6, max_correspondence_distance=1.0,
                     chunk=512, robust_kernel="huber", robust_scale=0.3),
    submap=SubmapParams(voxel_size=0.75, capacity=1024, dims=(48, 48, 16),
                        evict_radius=12.0),
    scan_budget=256, recovery=False)
SLOTS = 4


def _service(**over):
    cfg = ServiceConfig(slots=SLOTS, scan_capacity=1024, odometry=ODO,
                        **over)
    return RegistrationService(cfg)


def _fleet_scans(n_streams, frames, base_seq=0):
    return {f"veh{s}": sequence_scans(base_seq + s, frames, SCENE)
            for s in range(n_streams)}


def _drive(svc, fleet):
    """Submit every stream's frames wave-by-wave; returns
    {sid: [(pose, diag), ...]} in frame order."""
    out = {sid: [] for sid in fleet}
    frames = max(len(f) for f in fleet.values())
    for f in range(frames):
        for sid, scans in fleet.items():
            if f < len(scans):
                svc.submit(sid, scans[f])
        for sid, res in svc.step().items():
            out[sid].append(res)
    return out


# -- bit-exact parity ------------------------------------------------------

def test_service_matches_standalone_pipeline_bitwise():
    """The acceptance contract: every stream of a clean fleet produces
    the same poses AND the same diagnostics, bit for bit, as a
    standalone OdometryPipeline(stream_config) replay."""
    svc = _service()
    fleet = _fleet_scans(3, 5)
    for sid in fleet:
        svc.admit(sid)
    staged = {sid: [svc.stage_scan(sc) for sc in scans]
              for sid, scans in fleet.items()}
    out = _drive(svc, fleet)
    for sid, frames in staged.items():
        ref = OdometryPipeline(svc.stream_config)
        for f, (padded, valid) in enumerate(frames):
            pose_ref, diag_ref = ref.process(padded, valid)
            pose_svc, diag_svc = out[sid][f]
            np.testing.assert_array_equal(np.asarray(pose_svc),
                                          np.asarray(pose_ref))
            assert diag_svc == diag_ref


def test_standalone_map_fuse_matches_fleet_fuse_bitwise():
    """A standalone pipeline fuses its map with ``Submap.insert_scan`` at
    lane 0 of the fleet's lane count; the service fuses every stream in
    one batched call. Lane 0 does not depend on what the other lanes
    hold, so the maps agree bit for bit."""
    params = ODO.submap
    rng = np.random.default_rng(3)
    scans = rng.uniform(-10, 10, (2, 256, 3)).astype(np.float32)
    valid = np.ones((2, 256), bool)
    poses = np.stack([np.eye(4, dtype=np.float32)] * 2)
    a = 0.3
    poses[0, :3, :3] = [[np.cos(a), -np.sin(a), 0],
                        [np.sin(a), np.cos(a), 0], [0, 0, 1]]
    poses[:, :3, 3] = [[1.7, -0.4, 0.05], [-3.0, 2.0, 0.0]]
    sub = Submap(params)
    sub.insert_scan(scans[0], poses[0], valid[0], lanes=2)
    fleet, _, _ = fuse_lanes(
        tuple(jnp.stack([leaf, leaf]) for leaf in empty_state(params)),
        jnp.asarray(scans), jnp.asarray(valid), jnp.asarray(poses),
        jnp.array([True, True]), params)
    for mine, lanes in zip(sub.state, fleet):
        np.testing.assert_array_equal(np.asarray(mine), np.asarray(lanes[0]))


# -- retrace avoidance -----------------------------------------------------

def test_midflight_join_does_not_retrace():
    svc = _service()
    fleet = _fleet_scans(2, 3)
    for sid in fleet:
        svc.admit(sid)
    _drive(svc, fleet)
    traces = svc.engine.trace_count
    svc.admit("late")                    # joins a warm fleet mid-flight
    late_scans = sequence_scans(7, 3, SCENE)
    out = _drive(svc, {"late": late_scans})
    assert len(out["late"]) == 3
    assert svc.engine.trace_count == traces


def test_churn_never_retraces_after_warmup():
    """Joins, retires, drops, and empty queues all ride through the same
    fixed-shape executables: zero trace growth across the whole churn."""
    svc = _service(max_queue=1)
    fleet = _fleet_scans(2, 2)
    for sid in fleet:
        svc.admit(sid)
    _drive(svc, fleet)                   # warmup: compiles everything
    traces = svc.engine.trace_count
    svc.admit("joiner")
    scans = sequence_scans(5, 4, SCENE)
    for f in range(4):
        svc.submit("joiner", scans[f])
        svc.submit("joiner", scans[f])   # overflow: deterministic drop
        svc.step()
    svc.close("veh0")
    svc.step()                           # round with an empty slot
    assert svc.frames_dropped > 0
    assert svc.engine.trace_count == traces


# -- admission / retirement ------------------------------------------------

def test_converged_stream_retires_and_slot_is_reused():
    svc = _service()
    fleet = _fleet_scans(SLOTS, 2)
    for sid in fleet:
        assert svc.admit(sid) is True
    _drive(svc, fleet)
    assert svc.admit("pending") is False          # fleet full: queued
    report = svc.close("veh0")
    assert report.frames_processed == 2
    assert report.final_pose is not None
    # the freed slot rebinds the pending stream immediately
    assert svc.service_report()["active_streams"] == SLOTS
    assert svc.service_report()["pending_streams"] == 0
    out = _drive(svc, {"pending": sequence_scans(9, 2, SCENE)})
    assert len(out["pending"]) == 2
    with pytest.raises(KeyError):
        svc.report("veh0")               # retired streams are gone


def test_admission_reject_policy_raises():
    svc = _service(admission="reject")
    for s in range(SLOTS):
        svc.admit(f"veh{s}")
    with pytest.raises(RuntimeError, match="service full"):
        svc.admit("overflow")


def test_duplicate_admit_raises():
    svc = _service()
    svc.admit("veh0")
    with pytest.raises(ValueError, match="already admitted"):
        svc.admit("veh0")


# -- backpressure ----------------------------------------------------------

def test_drop_oldest_keeps_freshest_frames():
    svc = _service(max_queue=2)
    svc.admit("veh0")
    scans = sequence_scans(0, 4, SCENE)
    assert all(svc.submit("veh0", sc) for sc in scans)  # oldest pay
    report = svc.report("veh0")
    assert report.frames_submitted == 4
    assert report.frames_dropped == 2
    # the survivors are the two freshest: their downsampled sources
    # match a pipeline replay of scans[2:] (frames 2 and 3)
    ref = OdometryPipeline(svc.stream_config)
    for sc in scans[2:]:
        ref.process(*svc.stage_scan(sc))
    out = svc.drain()
    assert len(out["veh0"]) == 2
    np.testing.assert_array_equal(np.asarray(out["veh0"][-1][0]),
                                  np.asarray(ref.poses[-1]))


def test_drop_newest_refuses_submission():
    svc = _service(max_queue=2, drop_policy="newest")
    svc.admit("veh0")
    scans = sequence_scans(0, 4, SCENE)
    results = [svc.submit("veh0", sc) for sc in scans]
    assert results == [True, True, False, False]
    assert svc.report("veh0").frames_dropped == 2


def test_drops_are_deterministic():
    reports = []
    for _ in range(2):
        svc = _service(max_queue=1)
        svc.admit("veh0")
        scans = sequence_scans(0, 4, SCENE)
        for sc in scans:
            svc.submit("veh0", sc)
            svc.submit("veh0", sc)
        svc.drain()
        reports.append(svc.report("veh0"))
    assert reports[0].frames_dropped == reports[1].frames_dropped
    np.testing.assert_array_equal(reports[0].final_pose,
                                  reports[1].final_pose)


def test_close_counts_unstepped_frames_as_dropped():
    svc = _service()
    svc.admit("veh0")
    for sc in sequence_scans(0, 3, SCENE):
        svc.submit("veh0", sc)
    report = svc.close("veh0")
    assert report.frames_dropped == 3
    assert report.frames_processed == 0


# -- degraded input through the service ------------------------------------

def test_empty_scan_coasts_and_quarantines():
    svc = _service()
    svc.admit("veh0")
    scans = sequence_scans(0, 3, SCENE)
    out = _drive(svc, {"veh0": scans})
    empty = np.full((64, 3), np.nan, np.float32)
    svc.submit("veh0", empty)
    pose, diag = svc.step()["veh0"]
    assert diag.quarantined and diag.iterations == 0
    assert np.all(np.isfinite(np.asarray(pose)))
    assert out["veh0"]                   # earlier frames were fine


def test_oversized_scan_rejected_at_submit():
    svc = _service()
    svc.admit("veh0")
    big = np.zeros((svc.config.scan_capacity + 1, 3), np.float32)
    with pytest.raises(ValueError, match="exceeds"):
        svc.submit("veh0", big)


# -- device-sharded mode ---------------------------------------------------
# D=1 here (single-device CI interpreter); tests/test_multidevice.py runs
# the same contracts on an 8-device host-platform fleet in a subprocess.

def _sharded_service(**over):
    over.setdefault("odometry", ODO)
    cfg = ServiceConfig(slots=SLOTS, scan_capacity=1024, devices=1, **over)
    return RegistrationService(cfg)


def test_sharded_service_matches_standalone_pipeline_bitwise():
    """The weak-scaling parity contract at its D=1 corner: the shard_map'd
    round (sharded fleet state, host staging, batched fuse into resident
    submaps) reproduces a standalone replay bit for bit — poses AND
    diagnostics."""
    svc = _sharded_service()
    fleet = _fleet_scans(3, 5)
    for sid in fleet:
        svc.admit(sid)
    staged = {sid: [svc.stage_scan(sc) for sc in scans]
              for sid, scans in fleet.items()}
    out = _drive(svc, fleet)
    for sid, frames in staged.items():
        ref = OdometryPipeline(svc.stream_config)
        for f, (padded, valid) in enumerate(frames):
            pose_ref, diag_ref = ref.process(padded, valid)
            pose_svc, diag_svc = out[sid][f]
            np.testing.assert_array_equal(np.asarray(pose_svc),
                                          np.asarray(pose_ref))
            assert diag_svc == diag_ref


def test_sharded_churn_never_retraces():
    """Joins, retires (with in-place lane resets), drops, and empty
    queues: the sharded executables are fixed-shape too, so churn never
    grows the trace count."""
    svc = _sharded_service(max_queue=1)
    fleet = _fleet_scans(2, 2)
    for sid in fleet:
        svc.admit(sid)
    _drive(svc, fleet)
    traces = svc.engine.trace_count
    svc.admit("joiner")
    scans = sequence_scans(5, 4, SCENE)
    for f in range(4):
        svc.submit("joiner", scans[f])
        svc.submit("joiner", scans[f])
        svc.step()
    svc.close("veh0")                    # lane reset + empty slot round
    svc.step()
    assert svc.frames_dropped > 0
    assert svc.engine.trace_count == traces


def test_sharded_close_resets_lane_state():
    """A stream bound to a retired stream's slot must never see its
    predecessor's resident submap: the successor's whole trajectory
    replays bit-identically against a fresh standalone pipeline (stale
    fleet state would poison its bootstrap fuse and every frame after)."""
    svc = _sharded_service()
    fleet = _fleet_scans(SLOTS, 3)
    for sid in fleet:
        svc.admit(sid)
    _drive(svc, fleet)
    freed = svc._streams["veh0"].slot
    svc.close("veh0")
    svc.admit("fresh")
    assert svc._streams["fresh"].slot == freed   # the lane is reused
    scans = sequence_scans(11, 3, SCENE)
    staged = [svc.stage_scan(sc) for sc in scans]
    out = _drive(svc, {"fresh": scans})
    ref = OdometryPipeline(svc.stream_config)
    for f, (padded, valid) in enumerate(staged):
        pose_ref, diag_ref = ref.process(padded, valid)
        np.testing.assert_array_equal(np.asarray(out["fresh"][f][0]),
                                      np.asarray(pose_ref))
        assert out["fresh"][f][1] == diag_ref


def test_fp16_sharded_service_matches_fp16_standalone():
    """Memory-lean resident submaps through the sharded service: the
    fp16 fleet round is still bit-identical to an fp16 standalone replay
    (both decode, fuse in fp32, re-encode through the same code path)."""
    odo16 = ODO._replace(submap=ODO.submap._replace(storage="fp16"))
    svc = _sharded_service(odometry=odo16)
    fleet = _fleet_scans(2, 4)
    for sid in fleet:
        svc.admit(sid)
    staged = {sid: [svc.stage_scan(sc) for sc in scans]
              for sid, scans in fleet.items()}
    out = _drive(svc, fleet)
    for sid, frames in staged.items():
        ref = OdometryPipeline(svc.stream_config)
        for f, (padded, valid) in enumerate(frames):
            pose_ref, diag_ref = ref.process(padded, valid)
            np.testing.assert_array_equal(np.asarray(out[sid][f][0]),
                                          np.asarray(pose_ref))
            assert out[sid][f][1] == diag_ref


def test_dropped_cells_surface_in_service_diagnostics():
    """A capacity-starved stream's saturation is visible per frame in
    FrameDiagnostics.dropped_cells, identically in the service round and
    the standalone replay (legacy single-device mode)."""
    odo_tiny = ODO._replace(submap=ODO.submap._replace(capacity=64))
    svc = RegistrationService(ServiceConfig(slots=SLOTS, scan_capacity=1024,
                                            odometry=odo_tiny))
    svc.admit("veh0")
    scans = sequence_scans(0, 2, SCENE)
    staged = [svc.stage_scan(sc) for sc in scans]
    out = _drive(svc, {"veh0": scans})
    ref = OdometryPipeline(svc.stream_config)
    diags_ref = [ref.process(p, v)[1] for p, v in staged]
    assert out["veh0"][0][1].dropped_cells > 0   # bootstrap already drops
    for (_, diag_svc), diag_ref in zip(out["veh0"], diags_ref):
        assert diag_svc == diag_ref
