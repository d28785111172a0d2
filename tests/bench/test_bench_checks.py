"""The comparison that decides ``correct``, at a size a test run holds:
a sound run passes it, and it fails with the timed path broken
underneath (``bench.faults``: an answer altered where it is produced,
half of the batch left out, the state left unchanged, the map's
centroids moved) and with the lower-precision control in the program's
place."""
import bench_tiny
import numpy as np
import pytest
from bench import control, faults, reference
from bench.drivers import fleet


def test_sound_run_is_correct():
    result, lines = bench_tiny.run("fleet")
    assert result["correct"], lines
    assert result["attempted"] > 0 and result["failed"] == 0
    assert list(result)[-1] == "checks"
    checks = result["checks"]
    assert checks["window_compiles"]["value"] == 0
    # the maps of the checked streams were read back and compared
    assert checks["map_key_mismatch"]["value"] is not None
    assert checks["map_centroid_gap"]["value"] is not None


@pytest.mark.parametrize("fault", faults.FAULTS)
def test_broken_fleet_path_is_not_correct(monkeypatch, fault):
    faults.plant(fault, monkeypatch.setattr)
    result, lines = bench_tiny.run("fleet")
    assert not result["correct"], lines


@pytest.mark.parametrize("fault, number", [
    ("unchanged", "map_key_mismatch"), ("map_shifted", "map_centroid_gap")])
def test_map_faults_fail_the_map_numbers(monkeypatch, fault, number):
    """A map fault is caught by the map's own numbers, not only by the
    poses that a wrong map moves."""
    faults.plant(fault, monkeypatch.setattr)
    result, _ = bench_tiny.run("fleet")
    c = result["checks"][number]
    limit = float(c["limit"].split()[-1])
    assert c["value"] is None or c["value"] > limit


def test_lower_precision_control_is_not_correct():
    """The reference computed with bfloat16 matrix products, in the
    program's place over the small cell's traffic, fails the comparison
    (``bench/control.py`` runs the same at the cell's own size)."""
    _, cfg_name, mix_name = bench_tiny.CELLS["fleet"]
    cfg, mix = bench_tiny.load(cfg_name), bench_tiny.load(mix_name)
    numbers = control.control_numbers("tiny", bench_tiny.SEED, ("bf16",),
                                      workers=0, config=cfg, mix=mix)
    assert not numbers["bf16"]["correct"], numbers


def test_control_reference_itself_passes():
    """The same check with the float64 reference in the program's place
    reads no gap: the comparison is exact where the answers are."""
    rng = np.random.default_rng(0)
    dst = rng.uniform(-20, 20, (3000, 3))
    src = dst[:500] + 0.01
    a = reference.icp(src, dst, np.eye(4), max_iterations=20, gate=1.0,
                      eps=1e-10)
    b = reference.icp(src, dst, np.eye(4), max_iterations=20, gate=1.0,
                      eps=1e-10)
    assert np.array_equal(a["T"], b["T"])
    np.testing.assert_allclose(a["T"][:3, 3], [-0.01] * 3, atol=1e-6)


def test_reference_counts_the_cells_its_capacity_drops():
    """The reference map keeps the first cells in (x, y, z) order and
    counts the rest as dropped, frame after frame."""
    odo = bench_tiny.load("tiny-fleet")["odometry"]
    odo = dict(odo, submap=dict(odo["submap"], capacity=50))
    ref = reference.Odometry(odo)
    grid = np.stack(np.meshgrid(np.arange(10), np.arange(10), [0],
                                indexing="ij"), -1).reshape(-1, 3)
    pts = (grid * 0.75 + 0.3).astype(np.float64)
    ref._fuse(pts, np.eye(4))
    assert ref.map.shape[0] == 50 and ref.dropped == 50
    assert np.all(ref.map[:, 0] < 5 * 0.75)        # the low-x half kept
    ref._fuse(pts, np.eye(4))
    assert ref.dropped == 100


def test_map_gaps_count_cells_and_centroids():
    v = 0.75
    want = (np.arange(40)[:, None] * np.array([v, 0, 0])) + 0.3
    same = fleet.map_gaps(want.copy(), want, v)
    assert same == {"keys": 0.0, "centroid": 0.0}
    moved = fleet.map_gaps(want + [0.1, 0, 0], want, v)
    assert moved["keys"] == 0.0
    assert moved["centroid"] == pytest.approx(0.1)
    fewer = fleet.map_gaps(want[:30], want, v)
    assert fewer["keys"] == pytest.approx(10 / 40)
    other = fleet.map_gaps(want[:30] + [0, 5 * v, 0], want, v)
    assert other["keys"] == pytest.approx(70 / 40)
    assert other["centroid"] == float("inf")


def test_readings_plant_a_fault_for_one_run_only(monkeypatch):
    """``bench/readings.py`` drives whole runs with a fault planted, and
    takes the fault out again after the run."""
    from bench import readings
    from bench import run as bench_run

    from repro.core.engine import SlotEngine
    orig_run, orig_reg = bench_run.run_cell, SlotEngine.register_batch
    _, cfg, mix = bench_tiny.CELLS["fleet"]

    def tiny(workload, seed, seconds, trace, **kw):
        return orig_run("tiny", seed, bench_tiny.SECONDS["fleet"], trace,
                        require_tpu=False, workers=0,
                        config=bench_tiny.load(cfg), mix=bench_tiny.load(mix),
                        benchmark=bench_tiny.benchmark("fleet"), **kw)

    monkeypatch.setattr(bench_run, "run_cell", tiny)
    line = readings._line("fleet-s8-replay", bench_tiny.SEED, 1.0, "half")
    assert line["fault"] == "half" and line["correct"] is False
    assert line["numbers"]["pose_gap"] > 0.05
    assert SlotEngine.register_batch is orig_reg
    assert line["counters"]["frames"] > 0
