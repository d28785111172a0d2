"""The readings that a cell's limits are set from, at the cell's own size
and load, in one process so that set-up is paid once: whole runs of the
cell (``bench/run.py``'s own path, window included) on a dozen seeds of
the sound program, then with each fault of ``bench/faults.py`` planted
in the timed path, on a few seeds each.

    python bench/readings.py --workload <cell> --seconds 51 \\
        --seeds 11 12 13 --faults altered half --fault-seeds 21 22 23 \\
        --fault-seconds 40

Prints one JSON line a run: the seed, the fault (or null), ``correct``,
every number compared, the end-to-end metrics and the driver's
counters. It runs on a TPU only, like the benchmark, and the
benchmark's own runs do not run it.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def _line(workload, seed, seconds, fault) -> dict:
    from bench import faults
    from bench import run as bench_run
    undo = faults.plant(fault) if fault else None
    keep: dict = {}
    t0 = time.perf_counter()
    try:
        result, _ = bench_run.run_cell(workload, seed, seconds, False,
                                       keep=keep)
    finally:
        if undo:
            undo()
    return {"seed": seed, "fault": fault, "correct": result["correct"],
            "numbers": keep["numbers"],
            "metrics": {k: v["value"] for k, v in result["metrics"].items()},
            "counters": keep["counters"],
            "memory_peak_bytes": result["device"]["memory_peak_bytes"],
            "run_s": time.perf_counter() - t0}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--faults", nargs="*", default=[])
    ap.add_argument("--fault-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--fault-seconds", type=float, default=None)
    args = ap.parse_args(argv)
    runs = [(s, None, args.seconds) for s in args.seeds]
    runs += [(s, f, args.fault_seconds or args.seconds)
             for f in args.faults for s in args.fault_seeds]
    for seed, fault, seconds in runs:
        print(json.dumps(_line(args.workload, seed, seconds, fault),
                         default=str), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
