"""The benchmark harness without a chip: discovery of its files by name,
deterministic traffic, the kernel counts, the device check and the
limits."""
import json
import os
import shutil
import subprocess
import sys

import bench_tiny
import numpy as np
import pytest
from bench import harness, scene

ROOT = bench_tiny.ROOT


def test_benchmark_names_files_that_exist():
    b = harness.load_benchmark()
    for c in b["configs"]:
        cfg = harness.load_config(c["name"])
        assert (ROOT / c["file"]).is_file()
        assert cfg["name"] == c["name"]
        harness.load_driver(cfg["driver"])
    for w in b["workloads"]:
        harness.load_traffic(w["traffic"])
    for m in b["per_layer"]:
        assert callable(harness.load_reader(m["name"]).read)
    assert harness.peaks("TPU v5 lite")["flops_per_s"] == 197e12


def test_every_cell_reports_setup_another_metric_and_a_layer():
    b = harness.load_benchmark()
    for w in b["workloads"]:
        e2e = {m["name"] for m in harness.cell_metrics(b, w["name"],
                                                       "end_to_end")}
        layers = harness.cell_metrics(b, w["name"], "per_layer")
        assert "setup_s" in e2e and len(e2e) >= 2
        assert layers
        assert all(m["moves"] in e2e for m in layers)


@pytest.fixture
def bench_copy(tmp_path, monkeypatch):
    """The benchmark's files in a scratch folder the harness reads."""
    dst = tmp_path / "bench"
    shutil.copytree(harness.BENCH, dst,
                    ignore=shutil.ignore_patterns("__pycache__"))
    monkeypatch.setattr(harness, "BENCH", dst)
    return dst


@pytest.mark.parametrize("kind", ["config", "traffic", "metric", "counts"])
def test_a_new_file_is_found_by_name(bench_copy, kind):
    if kind == "config":
        cfg = json.loads((bench_copy / "configs" /
                          "kitti-hdl64-fleet.json").read_text())
        cfg["name"] = "added-config"
        (bench_copy / "configs" / "added-config.json").write_text(
            json.dumps(cfg))
        assert harness.load_config("added-config")["name"] == "added-config"
    elif kind == "traffic":
        (bench_copy / "traffic" / "added-mix.json").write_text(
            json.dumps({"drives": [3], "stagger": 0}))
        assert harness.load_traffic("added-mix")["drives"] == [3]
    elif kind == "metric":
        (bench_copy / "metrics" / "added_metric.fleet.py").write_text(
            "def read(ctx):\n    return 42.0\n")
        assert harness.load_reader("added_metric.fleet").read({}) == 42.0
    else:
        (bench_copy / "counts").mkdir(exist_ok=True)
        (bench_copy / "counts" / "added_kernel.py").write_text(
            "def flops(call):\n    return 1.0\n\n"
            "def bytes_moved(call):\n    return 2.0\n")
        assert harness.load_counts("added_kernel").bytes_moved({}) == 2.0
        assert harness.load_counts("no_such_kernel") is None


def test_unknown_names_are_errors():
    with pytest.raises(harness.BenchError):
        harness.load_config("no-such-config")
    with pytest.raises(harness.BenchError):
        harness.cell(harness.load_benchmark(), "no-such-cell")
    with pytest.raises(harness.BenchError):
        harness.peaks("TPU v99")


SMALL_SCENE = {"n_ground": 3000, "n_walls": 1400, "n_poles": 600,
               "n_clutter": 500, "extent": 30.0, "sensor_range": 35.0,
               "noise_std": 0.02}


def test_traffic_is_deterministic_in_the_seed():
    _, cfg_name, mix_name = bench_tiny.CELLS["fleet"]
    cfg = dict(bench_tiny.load(cfg_name), scene=SMALL_SCENE)
    mix = bench_tiny.load(mix_name)
    driver = harness.load_driver("fleet")

    def make(seed):
        scans = [scene.scan(*job) for job in driver.jobs(cfg, mix, seed)]
        return driver.traffic(cfg, mix, seed, scans)

    def arrays(t):
        return [a for lane in t["lanes"] for a in lane]

    a, b, c = make(2**31 + 5), make(2**31 + 5), make(7)
    assert a["drives"] == b["drives"] and a["start"] == b["start"]
    assert all(np.array_equal(x, y) for x, y in zip(arrays(a), arrays(b)))
    # another seed draws other points for the same drives
    assert sorted(a["drives"]) == sorted(c["drives"])
    for d in a["drives"]:
        lane_a = a["lanes"][a["drives"].index(d)]
        lane_c = c["lanes"][c["drives"].index(d)]
        assert len(lane_a) == len(lane_c) == cfg["drive_frames"]
        assert not np.array_equal(lane_a[0], lane_c[0])
        assert a["start"][a["drives"].index(d)] == mix["stagger"] * d % len(
            lane_a)


def test_judge_holds_each_number_to_its_limit():
    ok, checks = harness.judge({"a": 0.5, "b": 3, "c": float("nan")},
                               {"a": {"max": 1.0}, "b": {"min": 4},
                                "c": {"max": 1.0}, "d": {"max": 1.0}})
    assert not ok
    assert [checks[k]["ok"] for k in "abcd"] == [True, False, False, False]
    ok, _ = harness.judge({"a": 0.5}, {"a": {"max": 1.0}})
    assert ok


def _run_cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "fleet-s8-replay",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_without_a_tpu():
    out = _run_cli(ROOT)
    assert out.returncode != 0
    assert "TPU" in out.stderr
    assert out.stdout.strip() == ""


def test_run_refuses_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run_cli(tmp_path, {"PYTHONPATH": ""})
    assert out.returncode != 0
    assert out.stdout.strip() == ""


def test_sub_seeds_are_stable_and_fit_32_bits():
    from bench import gen
    a = gen.sub_seed(2**33 + 7, "points")
    assert a == gen.sub_seed(2**33 + 7, "points")
    assert a != gen.sub_seed(2**33 + 7, "lanes")
    assert 0 <= a < 2**32


class _Diag:
    def __init__(self, dropped):
        self.dropped_cells = dropped


def _stream(first, frames, dropped=()):
    diags = [_Diag(d) for d in dropped] or [_Diag(0)] * frames
    return {"first": first, "poses": [None] * frames, "diags": diags}


def test_check_picks_long_streams_drawn_from_the_seed():
    from types import SimpleNamespace

    from bench.drivers import fleet
    streams = [_stream(0, 12), _stream(3, 3), _stream(0, 0), _stream(0, 15),
               _stream(9, 11)]
    f = SimpleNamespace(streams=streams)
    for seed in (1, 2**31 + 3, 12345):
        picked = fleet.pick_streams(f, seed, 2)
        assert len(picked) == 2
        assert all(len(s["poses"]) >= 10 for s in picked)
        assert picked == fleet.pick_streams(f, seed, 2)
    # too few long streams: the longest, never an empty one
    f = SimpleNamespace(streams=[_stream(0, 4), _stream(0, 0), _stream(0, 2)])
    assert [len(s["poses"]) for s in fleet.pick_streams(f, 5, 2)] == [4, 2]


def test_counters_find_where_each_map_first_drops_a_cell():
    from types import SimpleNamespace

    from bench.drivers import fleet
    streams = [_stream(0, 4, [0, 0, 5, 9]), _stream(6, 3, [0, 2, 4]),
               _stream(0, 2, [0, 0])]
    c = fleet.counters(SimpleNamespace(streams=streams), {})
    assert c == {"frames": 9, "dropped_cells": 13,
                 "first_drop_frame": [2, None]}
    reader = harness.load_reader("map_dropped_cells.fleet")
    assert reader.read({"counters": c}) == pytest.approx(13 / 9)
    assert reader.read({"counters": {"frames": 0}}) is None
