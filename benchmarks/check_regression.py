"""Benchmark regression guard: re-run the quick sweeps, compare against
the committed baselines, fail loudly on a >20% regression.

    PYTHONPATH=src python -m benchmarks.check_regression        # or:
    make bench-guard

Baselines are the committed ``BENCH_nn.json`` / ``BENCH_throughput.json``
/ ``BENCH_odometry.json`` / ``BENCH_robustness.json`` /
``BENCH_scaleout.json`` at the repo root. The guard re-measures in quick
mode (small scenes, so it finishes in CI minutes) and compares only
metrics that are *comparable* across the two configurations:

  * **ratio metrics** (grid-NN speedup at a shared M, batched-vs-looped
    throughput speedup, scan-to-map fps speedup) — hardware-speed-
    independent to first order, since numerator and denominator are
    measured in the same process on the same machine. Guarded at
    ``current >= (1 - tolerance) * baseline``. Timed ratio metrics are the
    **median of 3 repeated measurements**: wall clock on this container
    swings ~15% run-to-run against a 20% tolerance, so a single shot is
    one bad scheduler tick from a false red; the repeats share the
    process-wide jit cache, so only the first pays compilation.
  * **correctness metrics** (gated NN agreement, batch-vs-loop transform
    agreement, pyramid parity, odometry drift) — machine-independent and
    deterministic at fixed seeds; taken single-shot from the first run,
    guarded relative to baseline or against absolute error bounds.

Wall-clock *absolute* numbers are deliberately not compared: the committed
baselines may come from a different machine. The quick re-run writes its
reports to ``BENCH_*_guard.json`` scratch paths so the committed baselines
are never clobbered.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import statistics
import sys

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent
NN_BASELINE = REPO_ROOT / "BENCH_nn.json"
THROUGHPUT_BASELINE = REPO_ROOT / "BENCH_throughput.json"
ODOMETRY_BASELINE = REPO_ROOT / "BENCH_odometry.json"
ROBUSTNESS_BASELINE = REPO_ROOT / "BENCH_robustness.json"
SCALEOUT_BASELINE = REPO_ROOT / "BENCH_scaleout.json"
DEFAULT_TOLERANCE = 0.20
# Median-of-N for timed ratio metrics (see module docstring). Absolute /
# correctness metrics stay single-shot — they are deterministic, repeats
# only add CI minutes.
TIMED_REPEATS = 3


def _median(runs: list[dict], extract) -> float:
    return float(statistics.median(extract(r) for r in runs))


class Guard:
    def __init__(self, tolerance: float):
        self.tolerance = tolerance
        self.checks: list[tuple[str, float, float, bool]] = []

    def ratio(self, name: str, current: float, baseline: float,
              tolerance: float | None = None):
        """current may not fall more than ``tolerance`` below baseline.

        Per-metric ``tolerance`` overrides the default for metrics whose
        *measurement* noise exceeds it (documented at the call site).
        """
        tol = self.tolerance if tolerance is None else tolerance
        ok = current >= (1.0 - tol) * baseline
        self.checks.append((name, current, baseline, ok))

    def absolute(self, name: str, current: float, bound: float):
        """current must stay under an absolute bound (error metrics)."""
        ok = current <= bound
        self.checks.append((name, current, bound, ok))

    def report(self) -> bool:
        width = max(len(c[0]) for c in self.checks)
        all_ok = True
        for name, cur, ref, ok in self.checks:
            status = "ok  " if ok else "FAIL"
            print(f"{status} {name:<{width}} current={cur:.4g} "
                  f"ref={ref:.4g}")
            all_ok &= ok
        return all_ok


def check_nn(guard: Guard) -> None:
    from benchmarks import nn_sweep
    from repro.data.pointcloud import SceneConfig

    baseline = json.loads(NN_BASELINE.read_text())
    base_rows = {(s["m"], s["rings"]): s for s in baseline["sweeps"]}
    # Re-measure at M=16384 rings=1 with the baseline's query count
    # (speedup amortises fixed gather cost over N, so N must match for the
    # ratio to be comparable) — but a CI-fast scene.
    scene = SceneConfig(n_ground=40_000, n_walls=30_000, n_poles=8_000,
                        n_clutter=9_000, extent=40.0, sensor_range=45.0)

    def measure() -> dict:
        nn_sweep.run(sizes=(16_384,), samples=4096, parity=False,
                     scene=scene,
                     mitigation=False,  # rings=2 row isn't compared — skip
                     out_json=str(REPO_ROOT / "BENCH_nn_guard.json"))
        return json.loads((REPO_ROOT / "BENCH_nn_guard.json").read_text())

    runs = [measure() for _ in range(TIMED_REPEATS)]
    ref = base_rows[(16_384, 1)]
    guard.ratio("nn/grid_speedup_m16k",
                _median(runs, lambda r: r["sweeps"][0]["speedup"]),
                ref["speedup"])
    # agreement is deterministic, not timed: single-shot from the first run
    guard.ratio("nn/agree_gated_m16k", runs[0]["sweeps"][0]["agree_gated"],
                ref["agree_gated"])
    # Fused single-pass iteration vs the unfused pallas iteration (ISSUE-6):
    # a same-process median-of-3 ratio like grid_speedup. The fused side is
    # a few interpret grid steps whose Python dispatch swings more than big
    # compiled sweeps on shared CI hardware, hence the wider band.
    if "fused_iter_speedup" in ref:
        guard.ratio("nn/fused_iter_speedup_m16k",
                    _median(runs,
                            lambda r: r["sweeps"][0]["fused_iter_speedup"]),
                    ref["fused_iter_speedup"], tolerance=0.5)
    # Pyramid-vs-brute ICP parity from the committed full run is an
    # absolute contract (the ISSUE-2 acceptance bound), re-assert it.
    par = baseline.get("parity")
    if par is not None:
        guard.absolute("nn/parity_rot_committed", par["rot_err"], 1e-3)
        guard.absolute("nn/parity_trans_committed", par["trans_err"], 1e-3)
        if "fused_rot_err" in par:  # ISSUE-6 fused-engine parity contract
            guard.absolute("nn/parity_fused_rot_committed",
                           par["fused_rot_err"], 1e-3)
            guard.absolute("nn/parity_fused_trans_committed",
                           par["fused_trans_err"], 1e-3)


def check_throughput(guard: Guard) -> None:
    from benchmarks import registration_throughput

    baseline = json.loads(THROUGHPUT_BASELINE.read_text())

    def measure() -> dict:
        # full-mode config (tiny clouds, seconds of work) so batch/iters
        # match the committed baseline exactly and the speedup ratio is
        # comparable
        registration_throughput.run(
            batch=baseline["batch"], n=baseline["n"], m=baseline["m"],
            iters=baseline["iters"],
            out_json=str(REPO_ROOT / "BENCH_throughput_guard.json"))
        return json.loads(
            (REPO_ROOT / "BENCH_throughput_guard.json").read_text())

    runs = [measure() for _ in range(TIMED_REPEATS)]
    # The looped path is dispatch-dominated on these tiny clouds and its
    # wall clock swings ~2.5x run-to-run on shared CI hardware, so even
    # the median-of-3 speedup ratio keeps a wider band — a genuine
    # regression (batching collapses toward 1x) still lands far below 40%
    # of any healthy baseline, while scheduler noise does not.
    guard.ratio("throughput/batched_speedup",
                _median(runs, lambda r: r["speedup"]),
                baseline["speedup"], tolerance=0.6)
    # batch-vs-loop agreement is a hard correctness bound, not a trend
    guard.absolute("throughput/transform_agreement",
                   runs[0]["max_abs_transform_diff"], 1e-4)


def check_odometry(guard: Guard) -> None:
    from benchmarks import odometry_drift

    baseline = json.loads(ODOMETRY_BASELINE.read_text())
    # One full re-run of the baseline config (~2 min steady state). No
    # TIMED_REPEATS here: the stream's fps is already a mean over >= 12
    # steady-state frames per sequence, which medians out scheduler ticks
    # the way a single batched-call timing cannot — and drift / iteration
    # counts are deterministic at fixed seeds, so repeats add nothing.
    odometry_drift.run(
        seqs=tuple(baseline["seqs"]), frames=baseline["frames"],
        samples=baseline["samples"], iters=baseline["iters"],
        engine=baseline["engine"],
        out_json=str(REPO_ROOT / "BENCH_odometry_guard.json"))
    current = json.loads(
        (REPO_ROOT / "BENCH_odometry_guard.json").read_text())
    # Deterministic trajectory metrics: tight default tolerance.
    guard.absolute("odometry/final_drift_s2m",
                   current["drift_final_s2m_max"], 0.5)
    guard.ratio("odometry/drift_advantage",
                current["drift_advantage_min"],
                baseline["drift_advantage_min"])
    guard.ratio("odometry/warm_iter_speedup",
                current["warm_iter_speedup"],
                baseline["warm_iter_speedup"])
    # Wall-clock throughput: only the runtime-weighted *speedup* is
    # guarded — a same-process ratio, first-order machine-independent
    # like throughput/batched_speedup. The absolute fps_weighted number
    # is recorded in BENCH_odometry.json for trend reading but never
    # compared across machines (module policy above).
    guard.ratio("odometry/runtime_weighted_speedup",
                current["runtime_weighted_speedup"],
                baseline["runtime_weighted_speedup"], tolerance=0.4)


def check_robustness(guard: Guard) -> None:
    from benchmarks import robustness

    baseline = json.loads(ROBUSTNESS_BASELINE.read_text())
    # One full re-run of the committed config (it is already CI-sized;
    # see benchmarks.robustness). Everything guarded here is
    # deterministic at fixed seeds — drifts, improvement ratios and tier
    # histograms are exact replays, not timings — so no TIMED_REPEATS.
    robustness.run(
        seq=baseline["seq"], frames=baseline["frames"],
        burst=tuple(baseline["burst"]), seed=baseline["seed"],
        out_json=str(REPO_ROOT / "BENCH_robustness_guard.json"))
    current = json.loads(
        (REPO_ROOT / "BENCH_robustness_guard.json").read_text())
    # The cascade may not tax clean streams: same absolute drift bound as
    # the odometry guard.
    guard.absolute("robustness/clean_drift",
                   current["clean"]["final_drift_m"], 0.5)
    # The headline contract: at least as many fault families must keep
    # their >=2x cascade advantage as the committed baseline shows.
    guard.ratio("robustness/families_2x",
                float(current["families_2x"]), float(baseline["families_2x"]),
                tolerance=0.0)
    # Per winning family, the drift-improvement factor may not collapse.
    for name, fam in baseline["per_family"].items():
        if fam["meets_2x"]:
            guard.ratio(f"robustness/{name}_drift_x",
                        current["per_family"][name]["drift_improvement"],
                        fam["drift_improvement"])


def check_device_sweep(guard: Guard) -> None:
    from benchmarks import device_sweep

    baseline = json.loads(SCALEOUT_BASELINE.read_text())
    # One quick-mode subprocess re-run (the sweep must initialise jax with
    # a forced 8-device host platform, which this already-initialised
    # 1-device process cannot — device_sweep respawns itself). Quick mode
    # sweeps the D=1 and D=8 endpoints, which is exactly what the scaling
    # ratio needs; median-of-repeats lives inside the sweep itself, so no
    # TIMED_REPEATS wrapper — each extra repeat would pay the subprocess's
    # full compile again instead of sharing a jit cache.
    current = device_sweep.run_subprocess(quick=True)
    d_lo, d_hi = min(current["devices"]), max(current["devices"])
    scaling = (current["sweep"][str(d_hi)]["aggregate_fps"]
               / current["sweep"][str(d_lo)]["aggregate_fps"])
    # Weak-scaling retention is same-process fps(D=8)/fps(D=1); its D=1
    # denominator is a dispatch-dominated per-round regime that swings
    # run to run — hence the wide band.
    guard.ratio("scaleout/scaling_x", scaling, baseline["scaling_x"],
                tolerance=0.5)
    # The fleet-batching headline: one fused round vs the eager
    # per-stream loop on the same 8-stream workload (dispatch-dominated
    # denominator again — same wide band).
    guard.ratio("scaleout/fused_vs_sequential",
                current["fused_vs_sequential_x"],
                baseline["fused_vs_sequential_x"], tolerance=0.5)
    # Hard structural contracts, identical to the in-sweep asserts: the
    # guard re-states them so a weakened assert cannot slip a regression
    # past CI.
    guard.absolute("scaleout/parity_max_abs",
                   current["parity_max_abs"], 0.0)
    guard.absolute("scaleout/retraces_after_warmup",
                   float(current["retraces_after_warmup"]), 0.0)
    # Deterministic memory layout: the fp16 headline may not erode below
    # the 1.9x acceptance floor (tolerance=0.0 → hard floor at 1.9).
    guard.ratio("scaleout/submap_bytes_ratio",
                current["submap_bytes_ratio"], 1.9, tolerance=0.0)
    # fp16 drift re-measured on the quick stream: same absolute band the
    # odometry guard enforces for fp32.
    guard.absolute("scaleout/fp16_drift_final",
                   current["fp16_drift_final_m"], 0.5)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tolerance", type=float, default=DEFAULT_TOLERANCE,
                    help="allowed fractional regression (default 0.20)")
    ap.add_argument("--only",
                    choices=["nn", "throughput", "odometry", "robustness",
                             "device_sweep"],
                    default=None)
    args = ap.parse_args(argv)
    guard = Guard(args.tolerance)
    if args.only in (None, "nn"):
        check_nn(guard)
    if args.only in (None, "throughput"):
        check_throughput(guard)
    if args.only in (None, "odometry"):
        check_odometry(guard)
    if args.only in (None, "robustness"):
        check_robustness(guard)
    if args.only in (None, "device_sweep"):
        check_device_sweep(guard)
    ok = guard.report()
    if not ok:
        print(f"\nbench-guard: regression beyond "
              f"{args.tolerance:.0%} tolerance", file=sys.stderr)
        return 1
    print("\nbench-guard: all metrics within tolerance")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
