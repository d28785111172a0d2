"""The ``pairs`` cells without a chip: a small pairs cell end to end on
the CPU, its traffic, the NN kernel's counts, the per-layer readers on a
trace recorded on a TPU v5 lite, and the comparison that decides
``correct`` failing with the timed path broken (``bench/pairs_readings``'s
faults) or with the lower-precision control in the program's place."""
import copy
import json

import bench_tiny
import numpy as np
import pytest
from bench import harness, pairs_readings, scene, tracefile
from bench import run as bench_run
from bench.drivers import pairs as driver

REAL = "pairs-b8"
SEED = 2**31 + 17
SECONDS = 3.0


def _config():
    return bench_tiny.load("tiny-pairs")


def _mix():
    return bench_tiny.load("tiny-pairs-mix")


def _benchmark() -> dict:
    """BENCHMARK.json with the real pairs cell renamed ``tiny``."""
    b = json.loads((bench_tiny.ROOT / "BENCHMARK.json").read_text())
    for w in b["workloads"]:
        if w["name"] == REAL:
            w["name"] = "tiny"
    for key in ("end_to_end", "per_layer"):
        for m in b[key]:
            if "workloads" in m:
                m["workloads"] = ["tiny" if x == REAL else x
                                  for x in m["workloads"]]
    return b


def run(trace=False, seconds=SECONDS, seed=SEED, keep=None):
    """One whole run of the small pairs cell on the CPU."""
    return bench_run.run_cell(
        "tiny", seed, seconds, trace, require_tpu=False, workers=0,
        config=copy.deepcopy(_config()), mix=_mix(), benchmark=_benchmark(),
        keep=keep)


def _traffic(seed, config=None, mix=None):
    config, mix = config or _config(), mix or _mix()
    scans = [scene.scan(*job) for job in driver.jobs(config, mix, seed)]
    return driver.traffic(config, mix, seed, scans)


def test_sound_pairs_run_is_correct():
    keep: dict = {}
    result, lines = run(keep=keep)
    assert result["correct"], lines
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"frames_per_s", "setup_s"}
    checks = result["checks"]
    assert list(result)[-1] == "checks"
    assert checks["window_compiles"]["value"] == 0
    assert checks["pairs_compared"]["value"] == 6     # the whole pool
    c = keep["counters"]
    assert c["pairs"] == result["attempted"] == len(c["nn_calls"])
    assert c["pair_iterations"] == sum(x["iterations"]
                                       for x in c["nn_calls"])
    assert c["pair_iterations_run"] == 50 * c["pairs"]


def test_the_tiny_pool_meets_two_buckets_and_setup_warms_both():
    """Set-up runs one batch of each target bucket the cycle meets, so
    the window compiles nothing."""
    t = _traffic(SEED)
    from repro.data.collate import bucket_size
    buckets = {bucket_size(max(t["pool"][i]["dst"].shape[0] for i in b))
               for b in t["batches"]}
    assert len(buckets) == 2
    state = driver.Pairs(_config(), t)
    assert set(state.buckets()) == buckets


def test_traced_pairs_run_reports_the_counter_metric():
    """On the CPU the trace holds no TPU ops: the device readers give a
    chip-less reading, the kernel's roofline none, and the waste comes
    from the engine's counters."""
    keep: dict = {}
    result, _ = run(trace=True, keep=keep)
    assert result["correct"]
    m = result["metrics"]
    assert "nn_kernel_roofline.pairs" not in m
    c = keep["counters"]
    assert m["pair_iteration_waste.pairs"]["value"] == pytest.approx(
        100 * (1 - c["pair_iterations"] / c["pair_iterations_run"]))
    assert "batch_host_ms.pairs" in m


def test_batches_cycle_through_the_pool_in_order():
    order = driver.batch_order(90, 8)
    assert len(order) == 45
    assert order[0] == list(range(8))
    assert order[11] == [88, 89, 0, 1, 2, 3, 4, 5]
    flat = [i for b in order for i in b]
    assert all(flat.count(i) == 4 for i in range(90))
    assert driver.batch_order(6, 4) == [[0, 1, 2, 3], [4, 5, 0, 1],
                                       [2, 3, 4, 5]]


def test_pairs_traffic_is_deterministic_in_the_seed():
    a, b, c = _traffic(2**31 + 5), _traffic(2**31 + 5), _traffic(7)
    assert a["batches"] == b["batches"] == c["batches"]
    assert [(p["seq"], p["frame"]) for p in a["pool"]] == [
        (p["seq"], p["frame"]) for p in c["pool"]] == [
        (s, f) for s in _mix()["sequences"] for f in range(3)]
    for pa, pb, pc in zip(a["pool"], b["pool"], c["pool"]):
        assert np.array_equal(pa["src"], pb["src"])
        assert np.array_equal(pa["dst"], pb["dst"])
        assert pa["src"].shape == (_config()["samples"], 3)
        assert not np.array_equal(pa["dst"][:50], pc["dst"][:50])
    # each source is a sample of the next frame's scan
    nxt = scene.scan(1, 1, _config()["scene"], driver.gen.sub_seed(
        2**31 + 5, "points"))
    rows = {tuple(r) for r in nxt}
    assert all(tuple(r) in rows for r in a["pool"][0]["src"])


def test_nn_counts_against_hand_sums():
    counts = harness.load_counts("nn_search")
    call = {"n": 4096, "m": 120000, "iterations": 7}
    assert counts.flops(call) == 16 * 4096 * 120000 * 7
    assert counts.bytes_moved(call) == (8 * 120000 * 4
                                        + 7 * (8 * 4096 * 4 + 16 * 4096))
    assert counts.flops(dict(call, iterations=0)) == 0
    assert counts.bytes_moved(dict(call, iterations=0)) == 8 * 120000 * 4


def _fixture_ctx():
    ev = json.loads((bench_tiny.HERE / "trace-pairs-b8.json").read_text())
    return {"trace": tracefile.summarize(ev), "rounds": ev["rounds"],
            "counters": ev["counters"], "device_kind": "TPU v5 lite",
            "harness": harness}, ev


def test_pairs_readers_on_a_recorded_trace():
    ctx, ev = _fixture_ctx()
    t = ctx["trace"]
    n = len(t["rounds"])
    assert n == 2
    icp = harness.load_reader("pair_icp_device_ms.pairs").read(ctx)
    assert icp == pytest.approx(t["modules"]["jit_pallas_batch"] / n * 1e-6)
    host = harness.load_reader("batch_host_ms.pairs").read(ctx)
    spans = [r["end"] - r["start"] - r["device_busy"] for r in t["rounds"]]
    assert host == pytest.approx(np.mean(spans) * 1e-6)
    idle = harness.load_reader("device_idle_share.pairs").read(ctx)
    assert idle == pytest.approx(100 * (1 - t["busy_ns"] / t["window_ns"]))
    c = ev["counters"]
    waste = harness.load_reader("pair_iteration_waste.pairs").read(ctx)
    assert waste == pytest.approx(
        100 * (1 - c["pair_iterations"] / c["pair_iterations_run"]))
    # the roofline share by hand: flops bound every pair here
    kernel_ns = sum(d for name, s, d, _ in ev["ops"]
                    if name.startswith("nn_search")
                    and name.endswith(tracefile.KERNEL_CALL)
                    and t["rounds"][0]["start"] <= s
                    <= t["rounds"][-1]["end"])
    flops = sum(16 * x["n"] * x["m"] * x["iterations"]
                for x in c["nn_calls"])
    roof = harness.load_reader("nn_kernel_roofline.pairs").read(ctx)
    assert roof == pytest.approx(100 * flops / 197e12 / (kernel_ns * 1e-9))
    assert 0 < roof < 100


def test_roofline_needs_the_named_kernel():
    ctx, _ = _fixture_ctx()
    reader = harness.load_reader("nn_kernel_roofline.pairs")
    ctx["trace"] = dict(ctx["trace"], ops={
        k.replace("nn_search", "closed_call"): v
        for k, v in ctx["trace"]["ops"].items()})
    assert reader.read(ctx) is None
    assert reader.read(dict(ctx, counters={})) is None


@pytest.mark.parametrize("fault", pairs_readings.FAULTS)
def test_broken_pairs_path_is_not_correct(monkeypatch, fault):
    pairs_readings.plant(fault, monkeypatch.setattr)
    result, lines = run()
    assert not result["correct"], lines
    c = result["checks"]
    assert c["window_compiles"]["value"] == 0
    # the fault's own number fails, not the window
    assert c["pairs_compared"]["value"] == 6


def test_lower_precision_control_is_not_correct():
    numbers = pairs_readings.control_numbers(
        "tiny", SEED, ("bf16",), workers=0, config=_config(), mix=_mix())
    assert not numbers["bf16"]["correct"], numbers


def test_check_compares_only_pairs_the_reference_converges():
    t = _traffic(SEED)
    cfg = _config()
    picked = []
    for i, pair in enumerate(t["pool"]):
        ref = driver.reference_pair(cfg, pair)
        picked.append({"index": i, "T": ref["T"], "rmse": ref["rmse"]})
    exact = driver.check(cfg, t, picked)
    assert exact["pose_gap_p90"] == 0 and exact["rmse_gap"] == 0
    assert exact["pairs_compared"] == exact["converged_compared"] == 6
    capped = dict(cfg, params=dict(cfg["params"], max_iterations=2))
    none = driver.check(capped, t, picked)
    assert none["converged_compared"] == 0
    assert none["pose_gap_p90"] is None and none["rmse_gap"] is None
    ok, _ = harness.judge(dict(none, window_compiles=0), cfg["limits"])
    assert not ok


def test_readings_plant_a_fault_for_one_run_only(monkeypatch):
    from repro.core.engine import RegistrationEngine
    orig_run, orig = bench_run.run_cell, RegistrationEngine.register_pairs

    def tiny(workload, seed, seconds, trace, **kw):
        return orig_run("tiny", seed, SECONDS, trace, require_tpu=False,
                        workers=0, config=_config(), mix=_mix(),
                        benchmark=_benchmark(), **kw)

    monkeypatch.setattr(bench_run, "run_cell", tiny)
    line = pairs_readings.run_line(REAL, SEED, 1.0, "altered")
    assert line["fault"] == "altered" and line["correct"] is False
    assert line["numbers"]["pose_gap_p90"] == pytest.approx(0.3, abs=0.01)
    assert RegistrationEngine.register_pairs is orig
    assert line["counters"]["pairs"] > 0
