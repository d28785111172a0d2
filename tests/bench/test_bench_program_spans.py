"""The program's own spans in a profiler trace (``bench/spans.py``),
checked on small synthetic traces and on the recorded ones: a trace
without ``fpps.*`` spans reads exactly as ``bench/tracefile.py`` reads
it, and on a trace with them each span's host and idle time and each
idle gap's attribution agree with independent counts."""
import json

import bench_tiny
import numpy as np
import pytest
from bench import harness, spans, tracefile


def _fixture(name):
    return json.loads((bench_tiny.HERE / f"trace-{name}.json").read_text())


def _ctx(summary):
    return {"trace": summary, "rounds": [{}, {}], "counters": {},
            "device_kind": "TPU v5 lite", "harness": harness}


@pytest.mark.parametrize("program", [None, []], ids=["absent", "empty"])
def test_a_trace_without_program_spans_reads_as_before(program):
    """The summary, the breakdown and every accepted reader give exactly
    what ``tracefile`` gives, and no reading of the program appears."""
    ev = _fixture("fleet-s8-replay")
    if program is not None:
        ev["program"] = program
    want = tracefile.summarize(_fixture("fleet-s8-replay"))
    got = spans.summarize(ev)
    assert json.dumps(got) == json.dumps(want)
    assert (json.dumps(tracefile.breakdown(got))
            == json.dumps(tracefile.breakdown(want)))
    for m in harness.load_benchmark()["per_layer"]:
        reader = harness.load_reader(m["name"])
        assert reader.read(_ctx(got)) == reader.read(_ctx(want)), m["name"]
    readings = spans.per_round(got, spans.counters({"rounds": 3},
                                                   {"rounds": 5}))
    assert not {"control_plane_ms", "icp_lane_waste",
                "icp_ms_per_iteration"} & set(readings)
    assert readings["idle_named_share"] == 0


def _synthetic():
    """Two rounds of 100 ns; the device busy in [10, 30] and [60, 110];
    program spans nested as the service opens them."""
    return {
        "ops": [["a fusion", 10, 20, 0], ["b fusion", 60, 50, 0]],
        "modules": [["jit_run", 10, 20, 0], ["jit_fuse_lanes", 60, 50, 0]],
        "spans": [["bench.round", 0, 100, -1], ["bench.round", 100, 100, -1],
                  ["bench.step", 0, 100, -1]],
        "program": [["fpps.step", 0, 95, {"round": 0}],
                    ["fpps.complete", 30, 25, {"stream": "a", "frame": 1}],
                    ["fpps.wait.scan_probe", 31, 4, {}],
                    ["fpps.step", 100, 90, {"round": 1}],
                    ["fpps.complete", 115, 20, {"stream": "a", "frame": 2}]],
    }


def test_idle_gaps_go_to_the_shortest_program_span_covering_half():
    s = spans.summarize(_synthetic())
    # gaps: [0,10] in fpps.step, [30,60] 25 of 30 in fpps.complete,
    # [110,200] 80 of 90 in fpps.step(round 1), which covers more than
    # half while fpps.complete covers 20 of 90
    assert s["gaps"] == [(10.0, "fpps.step"), (30.0, "fpps.complete"),
                         (90.0, "fpps.step")]
    assert s["program"]["fpps.complete"] == {
        "count": 2, "host_ns": 45.0, "idle_ns": 45.0}
    assert s["program"]["fpps.wait.scan_probe"]["idle_ns"] == 4.0
    assert s["program"]["fpps.step"] == {"count": 2, "host_ns": 185.0,
                                         "idle_ns": 120.0}


def test_a_gap_no_program_span_covers_half_of_goes_to_the_benchmark():
    ev = _synthetic()
    ev["program"] = [sp for sp in ev["program"] if sp[0] != "fpps.step"]
    s = spans.summarize(ev)
    assert [w for _, w in s["gaps"]] == ["step", "fpps.complete", "round"]


def test_per_round_readings_of_the_synthetic_trace():
    s = spans.summarize(_synthetic())
    counts = spans.counters(
        {"lane_iterations": 5, "lane_iterations_run": 8,
         "loop_iterations": 1},
        {"lane_iterations": 35, "lane_iterations_run": 88,
         "loop_iterations": 11})
    r = spans.per_round(s, counts)
    assert r["rounds"] == 2
    assert r["control_plane_ms"] == pytest.approx(22.5e-6)
    assert r["icp_lane_waste"] == pytest.approx(100 * (1 - 30 / 80))
    assert r["icp_ms_per_iteration"] == pytest.approx(20 / 10 * 1e-6)
    assert r["idle_named_share"] == 1.0
    assert r["spans"]["fpps.complete"]["count"] == 1.0


def test_covered_length_by_bisection_matches_a_direct_sum():
    merged = [[0, 5], [10, 20], [30, 31], [40, 60]]
    cov = spans._Covered(merged)
    for lo in range(-2, 64, 3):
        for hi in range(lo, 66, 4):
            assert cov(lo, hi) == tracefile.covered(merged, lo, hi)


def _grid(intervals, lo, hi):
    """A 1 us occupancy grid of ``[lo, hi]``: an independent count of the
    unions the reduction merges."""
    grid = np.zeros(int((hi - lo) // 1000) + 2, bool)
    for s, e in intervals:
        a = max(0, int((s - lo) // 1000))
        b = min(grid.size, int(-(-(min(e, hi) - lo) // 1000)))
        grid[a:b] = True
    return grid


def test_recorded_program_spans_agree_with_independent_counts():
    """Two traced rounds of the real cell (TPU v5 lite): each span's host
    and idle time, and each gap's span, counted again directly."""
    ev = _fixture("fleet-s8-replay-spans")
    s = spans.summarize(ev)
    marks = sorted((st, st + d) for n, st, d, _ in ev["spans"]
                   if n == tracefile.ROUND_SPAN)
    lo, hi = marks[0][0], marks[-1][1]
    busy = _grid([(st, st + d) for _, st, d, _ in ev["ops"]], lo, hi)
    tol = 2000.0 * (len(ev["ops"]) + len(ev["program"]))
    names = {n for n, *_ in ev["program"]}
    assert set(s["program"]) == names
    for name in names:
        mine = [(max(st, lo), min(st + d, hi)) for n, st, d, _ in
                ev["program"] if n == name and st + d > lo and st < hi]
        got = s["program"][name]
        assert got["count"] == len(mine)
        assert got["host_ns"] == pytest.approx(sum(e - b for b, e in mine))
        idle = (_grid(mine, lo, hi) & ~busy).sum() * 1000.0
        assert abs(got["idle_ns"] - idle) <= tol, name
    gaps = spans.idle_gaps(tracefile.union(
        (st, st + d) for _, st, d, _ in ev["ops"]), lo, hi)
    program = [(n, max(st, lo), min(st + d, hi))
               for n, st, d, _ in ev["program"]]
    for (length, what), (g0, g1) in zip(s["gaps"], gaps):
        assert length == g1 - g0
        fits = [sp for sp in program
                if 2 * (min(sp[2], g1) - max(sp[1], g0)) >= g1 - g0]
        if fits:
            assert what == min(fits, key=lambda sp: sp[2] - sp[1])[0]
        else:
            assert not what.startswith(spans.PREFIX)


def test_recorded_program_readings():
    ev = _fixture("fleet-s8-replay-spans")
    s = spans.summarize(ev)
    r = spans.per_round(s, ev["counters"])
    c = ev["counters"]
    done = sum(d for n, _, d, _ in ev["program"] if n == "fpps.complete")
    assert r["control_plane_ms"] == pytest.approx(done / 2 * 1e-6)
    assert r["icp_lane_waste"] == pytest.approx(
        100 * (1 - c["lane_iterations"] / c["lane_iterations_run"]))
    assert r["icp_ms_per_iteration"] == pytest.approx(
        s["modules"]["jit_run"] / c["loop_iterations"] * 1e-6)
    assert 0 < r["icp_lane_waste"] < 100
    assert r["idle_named_share"] >= 0.9
    b = tracefile.breakdown(s)
    assert all(n.startswith(spans.PREFIX) for n, _ in b["idle_gaps"])
