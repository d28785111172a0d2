"""The reduction from profiler trace to per-layer metrics, checked on
small traces recorded on a TPU v5 lite (``trace-*.json``: the events
``bench.tracefile.load`` returns, with short-lived ops left out)."""
import json

import bench_tiny
import numpy as np
import pytest
from bench import harness, tracefile


def _fixture(cell):
    return json.loads((bench_tiny.HERE / f"trace-{cell}.json").read_text())


def _busy_by_bins(ops, lo, hi):
    """Device-busy time by a 1 us occupancy grid: an independent count
    of the union that ``tracefile.summarize`` merges."""
    grid = np.zeros(int((hi - lo) // 1000) + 2, bool)
    for _, s, d, _ in ops:
        a = max(0, int((s - lo) // 1000))
        b = min(grid.size, int(-(-(min(s + d, hi) - lo) // 1000)))
        grid[a:b] = True
    return grid.sum() * 1000.0


def test_window_and_busy_time():
    ev = _fixture("fleet-s8-replay")
    t = tracefile.summarize(ev)
    rounds = sorted((s, s + d) for n, s, d, _ in ev["spans"]
                    if n == tracefile.ROUND_SPAN)
    assert t["window_ns"] == rounds[-1][1] - rounds[0][0]
    want = _busy_by_bins(ev["ops"], rounds[0][0], rounds[-1][1])
    assert abs(t["busy_ns"] - want) <= 2000.0 * len(ev["ops"])
    assert 0 < t["busy_ns"] <= t["window_ns"]
    assert len(t["rounds"]) == len(rounds)
    assert sum(r["device_busy"] for r in t["rounds"]) <= t["busy_ns"] + 1
    b = tracefile.breakdown(t)
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert not any(tracefile.opcode(n) in tracefile.CONTROL_FLOW
                   for n, _ in b["device_ops"])


def _ctx(cell):
    ev = _fixture(cell)
    return {"trace": tracefile.summarize(ev), "rounds": ev["rounds"],
            "counters": {}, "device_kind": "TPU v5 lite",
            "harness": harness}


def test_fleet_readers():
    ctx = _ctx("fleet-s8-replay")
    t = ctx["trace"]
    n = len(t["rounds"])
    icp = harness.load_reader("icp_device_ms.fleet").read(ctx)
    assert icp == pytest.approx(t["modules"]["jit_run"] / n * 1e-6)
    prep = harness.load_reader("prepare_device_ms.fleet").read(ctx)
    assert prep == pytest.approx(t["modules"]["jit_prepare_scans"] / n
                                 * 1e-6)
    host = harness.load_reader("round_host_ms.fleet").read(ctx)
    spans = [r["end"] - r["start"] - r["device_busy"] for r in t["rounds"]]
    assert host == pytest.approx(np.mean(spans) * 1e-6)
    idle = harness.load_reader("device_idle_share.fleet").read(ctx)
    assert idle == pytest.approx(100 * (1 - t["busy_ns"] / t["window_ns"]))
    ctx["counters"] = {"frames": 40, "dropped_cells": 3000}
    dropped = harness.load_reader("map_dropped_cells.fleet").read(ctx)
    assert dropped == pytest.approx(75.0)


def test_readers_find_nothing_in_an_empty_trace():
    ctx = {"trace": None, "rounds": [], "counters": {},
           "device_kind": "TPU v5 lite", "harness": harness}
    b = harness.load_benchmark()
    for m in b["per_layer"]:
        assert harness.load_reader(m["name"]).read(ctx) is None


def test_op_names_are_shortened():
    assert tracefile.op_name(
        "%fusion.3 = f32[8,32]{1,0:T(8,128)} fusion(f32[8]{0} %a)"
    ) == "fusion.3 fusion"
    assert tracefile.op_name(
        '%closed_call.1 = (f32[8]{0}) custom-call(%a), '
        'custom_call_target="tpu_custom_call"'
    ) == "closed_call.1 tpu_custom_call"
    assert tracefile.module_name("jit_run(1234)") == "jit_run"
