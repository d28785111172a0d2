"""Spans and counters inside the registration service's round: every
``fpps.*`` span appears, nested as the service documents; per-stream
spans carry their identifiers; the lane-iteration counters add up; the
profiler changes no result; and the recovery tiers' executables trace
under names of their own, apart from the slot executable's ``jit_run``.

The traced service runs with recovery on (thresholds loose enough for
these sparse scenes that clean frames fuse), and one stream sends a
frame of random points so that the cascade tries its tiers (their
executables compile once for the module)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from bench import spans

from repro.core import ICPParams
from repro.core.engine import (DistributedEngine, PallasEngine,
                               ShardedSlotEngine, SlotEngine, XLAEngine)
from repro.core.health import HealthThresholds
from repro.core.odometry import OdometryConfig
from repro.core.pyramid import PyramidEngine
from repro.data.pointcloud import SceneConfig, sequence_scans
from repro.data.submap import SubmapParams
from repro.serve.registration_service import (RegistrationService,
                                              ServiceConfig)

SCENE = SceneConfig(n_ground=300, n_walls=220, n_poles=60, n_clutter=70,
                    extent=12.0, sensor_range=16.0)
ODO = OdometryConfig(
    params=ICPParams(max_iterations=10, max_correspondence_distance=1.0,
                     chunk=512, robust_kernel="huber", robust_scale=0.3),
    submap=SubmapParams(voxel_size=0.75, capacity=1024, dims=(48, 48, 16),
                        evict_radius=12.0),
    scan_budget=256,
    thresholds=HealthThresholds(suspect_inlier_frac=0.05,
                                failed_inlier_frac=0.02, suspect_rmse=1.2,
                                failed_rmse=3.0, suspect_condition=4e4))
SLOTS = 4
FRAMES = 5
GARBAGE = ("veh0", 3)        # (stream, frame) sent as random points

# Each span the round opens, and the span it runs inside.
PARENT = {
    "fpps.prepare": "fpps.step", "fpps.wait.prepare": "fpps.prepare",
    "fpps.classify": "fpps.step", "fpps.register": "fpps.step",
    "fpps.wait.register": "fpps.register", "fpps.complete": "fpps.step",
    "fpps.wait.scan_probe": "fpps.complete",
    "fpps.cascade": "fpps.complete", "fpps.tier": "fpps.cascade",
    "fpps.fuse": "fpps.step", "fpps.wait.fuse": "fpps.fuse",
}
ROUND_ORDER = ("fpps.prepare", "fpps.classify", "fpps.register",
               "fpps.complete", "fpps.fuse")
OUTSIDE = ("fpps.submit", "fpps.sync", "fpps.admit", "fpps.close")


def _scans(garbage: bool) -> dict:
    fleet = {f"veh{s}": sequence_scans(s, FRAMES, SCENE) for s in range(3)}
    if garbage:
        sid, f = GARBAGE
        rng = np.random.default_rng(5)
        fleet[sid][f] = rng.uniform(-12, 12, fleet[sid][f].shape).astype(
            np.float32)
    return fleet


def _drive(devices, fleet: dict, odometry=ODO):
    """Admit, submit, step and sync every wave, then close one stream;
    returns the service and ``{sid: [(pose, diag), ...]}``."""
    svc = RegistrationService(ServiceConfig(
        slots=SLOTS, scan_capacity=1024, odometry=odometry,
        devices=devices))
    for sid in fleet:
        svc.admit(sid)
    out = {sid: [] for sid in fleet}
    for f in range(FRAMES):
        for sid, scans in fleet.items():
            svc.submit(sid, scans[f])
        for sid, res in svc.step().items():
            out[sid].append(res)
        svc.sync()
    svc.close("veh2")
    return svc, out


@pytest.fixture(scope="module", params=[None, 1],
                ids=["single-device", "sharded"])
def traced(request, tmp_path_factory):
    """One drive with a garbage frame under the profiler, the same drive
    without it, and the spans read back from the trace."""
    devices = request.param
    fleet = _scans(garbage=True)
    _, plain = _drive(devices, fleet)
    log_dir = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(log_dir))
    try:
        svc, out = _drive(devices, fleet)
    finally:
        jax.profiler.stop_trace()
    program = spans.load(log_dir)["program"]
    return {"devices": devices, "svc": svc, "out": out, "plain": plain,
            "program": [(n, s, s + d, meta) for n, s, d, meta in program]}


def _inside(child, parents) -> bool:
    return any(p[1] <= child[1] and child[2] <= p[2] for p in parents)


def test_every_span_appears_nested_as_listed(traced):
    by_name: dict = {}
    for sp in traced["program"]:
        by_name.setdefault(sp[0], []).append(sp)
    assert set(by_name) == set(PARENT) | set(OUTSIDE) | {"fpps.step"}
    for name, parent in PARENT.items():
        assert all(_inside(sp, by_name[parent]) for sp in by_name[name]), name
    for name in OUTSIDE:
        assert not any(_inside(sp, by_name["fpps.step"])
                       for sp in by_name[name]), name
    assert len(by_name["fpps.step"]) == FRAMES
    assert [sp[3]["round"] for sp in sorted(by_name["fpps.step"],
                                            key=lambda sp: sp[1])] == list(
        range(FRAMES))
    for step in by_name["fpps.step"]:
        firsts = [min((sp[1] for sp in by_name[name] if _inside(sp, [step])),
                      default=None) for name in ROUND_ORDER]
        seen = [t for t in firsts if t is not None]
        assert seen == sorted(seen)
    assert {sp[3]["tier"] for sp in by_name["fpps.tier"]} <= set(
        ODO.recovery_tiers)
    assert {sp[3]["stream"] for sp in by_name["fpps.submit"]} == {
        "veh0", "veh1", "veh2"}


def test_complete_spans_name_their_stream_and_frame(traced):
    done = sorted((sp[3]["stream"], sp[3]["frame"]) for sp in
                  traced["program"] if sp[0] == "fpps.complete")
    want = sorted((sid, d.frame) for sid, frames in traced["out"].items()
                  for _, d in frames)
    assert done == want


def test_the_cascade_tried_its_tiers_on_the_garbage_frame(traced):
    sid, f = GARBAGE
    assert traced["out"][sid][f][1].health != "ok"
    frame = [sp for sp in traced["program"] if sp[0] == "fpps.complete"
             and sp[3]["stream"] == sid and sp[3]["frame"] == f]
    tiers = [sp[3]["tier"] for sp in traced["program"]
             if sp[0] == "fpps.tier" and _inside(sp, frame)]
    assert tiers == list(ODO.recovery_tiers)


def test_profiler_leaves_poses_and_diagnostics_bit_identical(traced):
    for sid, frames in traced["plain"].items():
        assert len(frames) == len(traced["out"][sid]) == FRAMES
        for (p0, d0), (p1, d1) in zip(frames, traced["out"][sid]):
            np.testing.assert_array_equal(np.asarray(p0), np.asarray(p1))
            assert d0 == d1


@pytest.mark.parametrize("devices", [None, 1],
                         ids=["single-device", "sharded"])
def test_lane_iterations_count_the_slot_executable(devices):
    """With recovery off every frame from the second on registers in the
    slot executable alone, so the frames' iterations add up to
    ``lane_iterations``; one device block runs every lane to the slowest
    lane's iterations."""
    svc, out = _drive(devices, _scans(garbage=False),
                      odometry=ODO._replace(recovery=False))
    diags = [d for frames in out.values() for _, d in frames]
    rep = svc.service_report()
    assert rep["lane_iterations"] == sum(d.iterations for d in diags
                                         if d.frame > 0)
    assert rep["slots"] == SLOTS
    assert rep["lane_iterations_run"] == SLOTS * rep["loop_iterations"]
    registered_rounds = FRAMES - 1
    assert (registered_rounds <= rep["loop_iterations"]
            <= registered_rounds * ODO.params.max_iterations)
    assert rep["lane_iterations"] < rep["lane_iterations_run"]


def test_counters_read_each_device_block_apart():
    """Sharded blocks run their own loops: two blocks of two lanes whose
    slowest lanes stop after 7 and 3 iterations compute 2 x (7 + 3)
    lane-iterations, and the round's trip count is 7."""
    svc = RegistrationService(ServiceConfig(slots=SLOTS, odometry=ODO))
    svc._devices = 2               # the block arithmetic, without 2 devices
    svc._count_iterations(np.array([7, 1, 3, 2]), [0, 2, 3])
    rep = svc.service_report()
    assert (rep["lane_iterations"], rep["lane_iterations_run"],
            rep["loop_iterations"]) == (12, 20, 7)


def _module_name(fn, *args) -> str:
    text = fn.lower(*args).as_text()
    return text.split("module @", 1)[1].split(" ", 1)[0]


PARAMS = ICPParams(max_iterations=2, chunk=64)
ENGINES = {
    "xla": XLAEngine,
    "pyramid": lambda: PyramidEngine(levels=((8.0, 2, 64),)),
    "pallas": PallasEngine,
    "distributed": DistributedEngine,
    "slots": lambda: SlotEngine(slots=2),
    "sharded-slots": lambda: ShardedSlotEngine(lanes_per_device=2,
                                               devices=1),
}


@pytest.mark.parametrize("engine,kind,name", [
    ("xla", "single", "jit_xla_single"),
    ("xla", "batch", "jit_xla_batch"),
    ("pyramid", "single", "jit_pyramid_single"),
    ("pyramid", "batch", "jit_pyramid_batch"),
    ("pallas", "single", "jit_pallas_single"),
    ("distributed", "batch", "jit_distributed_batch"),
    ("slots", "batch", "jit_run"),
    ("sharded-slots", "batch", "jit_run"),
])
def test_registration_executables_trace_under_their_own_names(engine, kind,
                                                              name):
    """Only the slot engines' batch executable keeps ``jit_run``; a trace
    names every other registration executable ``jit_<engine>_<kind>``."""
    rng = np.random.default_rng(0)
    pts = jnp.asarray(rng.uniform(-5, 5, (2, 64, 3)), jnp.float32)
    T0 = jnp.broadcast_to(jnp.eye(4, dtype=jnp.float32), (2, 4, 4))
    mask = jnp.ones((2, 64), bool)
    fn = ENGINES[engine]()._executable(kind, PARAMS)
    args = ((pts, pts, T0, mask, mask) if kind == "batch"
            else (pts[0], pts[0], T0[0], mask[0], mask[0]))
    assert _module_name(fn, *args) == name


@pytest.mark.parametrize("engine", ["slots", "sharded-slots"])
def test_slot_executable_on_a_lattice_keeps_its_name(engine):
    """The lattice search builds its tables inside ``jit_run``, the module
    ``icp_device_ms.fleet`` reads."""
    rng = np.random.default_rng(0)
    pts = jnp.asarray(rng.uniform(-5, 5, (2, 64, 3)), jnp.float32)
    T0 = jnp.broadcast_to(jnp.eye(4, dtype=jnp.float32), (2, 4, 4))
    mask = jnp.ones((2, 64), bool)
    voxel_map = ODO.submap.voxel_map(pts, jnp.full((2, 3), -6.0))
    fn = ENGINES[engine]()._executable("batch", PARAMS)
    assert _module_name(fn, pts, voxel_map, T0, mask, mask) == "jit_run"


@pytest.mark.parametrize("devices", [None, 1],
                         ids=["single-device", "sharded"])
def test_lattice_counters_count_the_registered_lanes(devices):
    """Every frame the slot executable registers searched its map's
    lattice, and no centroid map overflows a cell."""
    svc, out = _drive(devices, _scans(garbage=False),
                      odometry=ODO._replace(recovery=False))
    rep = svc.service_report()
    assert rep["lattice_searches"] == sum(
        1 for frames in out.values() for _, d in frames if d.frame > 0)
    assert rep["lattice_overflow_cells"] == 0


def test_lattice_overflow_reads_a_planted_point():
    """A second point planted in an occupied cell of a stream's map is
    the one overflow the next round's search reports."""
    odo = ODO._replace(recovery=False)
    svc = RegistrationService(ServiceConfig(slots=SLOTS, scan_capacity=1024,
                                            odometry=odo))
    scans = sequence_scans(0, 3, SCENE)
    svc.admit("veh0")
    for f in range(2):
        svc.submit("veh0", scans[f])
        svc.step()
    sub = svc._streams["veh0"].pipe.submap
    points, valid, origin = sub.state
    free = int(np.argmin(np.asarray(valid)))
    sub.state = (points.at[free].set(points[0] + 0.01),
                 valid.at[free].set(True), origin)
    svc.submit("veh0", scans[2])
    svc.step()
    rep = svc.service_report()
    assert (rep["lattice_searches"], rep["lattice_overflow_cells"]) == (2, 1)
