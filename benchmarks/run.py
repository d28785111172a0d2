"""Benchmark harness: one module per paper table. Prints
``name,us_per_call,derived`` CSV.

  table3 -> registration_accuracy    (Table III: RMSE parity)
  table4 -> registration_latency     (Table IV: latency + acceleration)
  table2 -> kernel_resources         (Table II: resource budget)
  power  -> power_efficiency         (§IV-D: perf/W, modeled)
  roofline -> roofline_report        (dry-run roofline summaries)
  throughput -> registration_throughput (looped vs batched frames/sec;
                                         also writes BENCH_throughput.json)
  nn_sweep -> nn_sweep               (brute vs grid-bucketed NN sweep;
                                         also writes BENCH_nn.json)
  convergence -> convergence         (p2p vs p2plane vs pyramid iteration
                                         counts; writes BENCH_convergence.json)
  odometry -> odometry_drift         (scan-to-map vs frame-to-frame drift +
                                         runtime-weighted frames/s;
                                         writes BENCH_odometry.json)
  robustness -> robustness           (fault matrix x recovery cascade
                                         ON/OFF; writes BENCH_robustness.json)
  service -> service_throughput      (multi-stream fleet rounds vs the
                                         sequential per-stream loop;
                                         writes BENCH_service.json)
  scaleout -> device_sweep           (sharded fleet over a forced 8-device
                                         host platform, run in a subprocess;
                                         writes BENCH_scaleout.json)

``--quick`` runs every suite in smoke mode (reduced scenes, 2 frames,
fewer iterations) so CI can exercise all entry points in seconds.
"""
from __future__ import annotations

import argparse
import sys
import traceback

from benchmarks import (convergence, device_sweep, kernel_resources,
                        nn_sweep, odometry_drift, power_efficiency,
                        registration_accuracy, registration_latency,
                        registration_throughput, robustness,
                        roofline_report, service_throughput)
from benchmarks.common import QUICK_SCENE, emit
from repro.compile_cache import enable_compile_cache

SUITES = {
    "table3": registration_accuracy.run,
    "table4": registration_latency.run,
    "table2": kernel_resources.run,
    "power": power_efficiency.run,
    "roofline": roofline_report.run,
    "throughput": registration_throughput.run,
    "nn_sweep": nn_sweep.run,
    "convergence": convergence.run,
    "odometry": odometry_drift.run,
    "robustness": robustness.run,
    "service": service_throughput.run,
    # run_harness respawns the sweep in a subprocess: this process's jax
    # is already initialised with 1 device, the sweep needs a forced 8.
    "scaleout": device_sweep.run_harness,
}

# Smoke-mode kwargs per suite (reduced scenes, 2 frames, short loops).
# Suites absent here are already static/fast (table2, roofline).
QUICK_KWARGS = {
    "table3": dict(n_seqs=2, samples=512, scene=QUICK_SCENE),
    "table4": dict(n_seqs=2, samples=512, iters=10, scene=QUICK_SCENE),
    "power": dict(n_seqs=2, samples=512, iters=10, scene=QUICK_SCENE),
    "throughput": dict(quick=True),
    "service": dict(quick=True),
    "scaleout": dict(quick=True),
}
# Suites whose smoke mode is a different entry point, not just kwargs.
QUICK_SUITES = {"nn_sweep": nn_sweep.run_quick,
                "convergence": convergence.run_quick,
                "odometry": odometry_drift.run_quick,
                "robustness": robustness.run_quick}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", choices=sorted(SUITES), default=None)
    ap.add_argument("--quick", action="store_true",
                    help="smoke mode: reduced scenes / 2 frames per suite")
    args = ap.parse_args(argv)
    enable_compile_cache()
    failed = []
    for name, fn in SUITES.items():
        if args.only and name != args.only:
            continue
        kwargs = QUICK_KWARGS.get(name, {}) if args.quick else {}
        if args.quick and name in QUICK_SUITES:
            fn, kwargs = QUICK_SUITES[name], {}
        try:
            emit(fn(**kwargs))
        except Exception as e:  # report and continue; fail at the end
            failed.append((name, e))
            print(f"{name}/ERROR,0.0,{type(e).__name__}: {e}")
            traceback.print_exc(file=sys.stderr)
    if failed:
        raise SystemExit(f"benchmark suites failed: {[n for n, _ in failed]}")


if __name__ == "__main__":
    main()
