"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Generates the cell's traffic from the seed (in worker processes that
never touch JAX), builds the system under test, compiles every program
the window will run (set-up), measures for ``--seconds``, then checks
what the window produced against the plain reference. The last line of
standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` with ``--trace
1``), and last ``checks``, each number compared beside its limit; the
same checks are the last lines of standard error. With ``--trace 0`` the
metrics are the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics.

It runs on a TPU only: where JAX finds none, or fewer chips than the cell
needs, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, workers: int | None = None,
             config: dict | None = None, mix: dict | None = None,
             benchmark: dict | None = None, t_start: float | None = None,
             keep: dict | None = None):
    """One run of one cell; returns the result dict and the check lines.

    ``require_tpu=False``, ``workers=0`` and the ``config``/``mix``/
    ``benchmark`` overrides exist for the tests, which drive a whole run
    on the CPU at a small size. ``keep``, where given, receives the
    driver's counters and the numbers compared."""
    from bench import gen, harness, tracefile
    t_start = time.perf_counter() if t_start is None else t_start
    benchmark = benchmark or harness.load_benchmark()
    cell = harness.cell(benchmark, workload)
    config = config or harness.load_config(cell["config"])
    mix = mix or harness.load_traffic(cell["traffic"])
    driver = harness.load_driver(config["driver"])
    jobs = driver.jobs(config, mix, seed)
    pending = gen.start(jobs, workers) if workers != 0 else None
    try:
        if require_tpu:
            harness.require_chips(cell["chips"])
    except BaseException:
        if pending is not None:
            pending[0].shutdown(wait=True, cancel_futures=True)
        raise
    if pending is None:
        from bench import scene
        scans = [scene.scan(*job) for job in jobs]
    else:
        scans = gen.collect(pending)
    traffic = driver.traffic(config, mix, seed, scans)
    if require_tpu:
        harness.enable_compile_cache()
    counter = harness.CompileCounter()
    state = driver.setup(config, traffic, seed)
    setup_s = time.perf_counter() - t_start

    log_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    tracer = harness.Tracer(trace, log_dir) if trace else harness.Untraced()
    counter.start()
    try:
        record = driver.window(state, seconds, tracer)
    finally:
        tracer.close()
    window_compiles = counter.stop()
    device = harness.device_info(cell["chips"])
    attempted, failed = driver.attempted_failed(state, record)
    picked = driver.pick(state, seed)
    driver.release(state)
    numbers = driver.check(config, traffic, picked)
    numbers["window_compiles"] = window_compiles
    correct, checks = harness.judge(numbers, config["limits"])
    if keep is not None:
        keep.update(counters=driver.counters(state, record), numbers=numbers)

    result = {"correct": correct, "attempted": attempted, "failed": failed}
    if not trace:
        values = driver.metrics(record)
        values["setup_s"] = setup_s
        chosen = harness.cell_metrics(benchmark, workload, "end_to_end")
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]}
                             for m in chosen
                             if values.get(m["name"]) is not None}
        result["device"] = device
    else:
        try:
            summary = tracefile.summarize(tracefile.load(log_dir))
        finally:
            shutil.rmtree(log_dir, ignore_errors=True)
        ctx = {"trace": summary, "counters": driver.counters(state, record),
               "rounds": tracer.rounds, "device_kind": device["kind"],
               "harness": harness}
        result["metrics"] = {}
        for m in harness.cell_metrics(benchmark, workload, "per_layer"):
            value = harness.load_reader(m["name"]).read(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        if summary is not None:
            device["busy_s"] = summary["busy_ns"] * 1e-9
            device["window_s"] = summary["window_ns"] * 1e-9
            result["breakdown"] = tracefile.breakdown(summary)
        result["device"] = device
    result["checks"] = {n: {"value": c["value"], "limit": c["limit"]}
                        for n, c in checks.items()}
    return result, harness.check_lines(checks)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        import repro  # noqa: F401  (the system under test must be here)
        from bench.harness import BenchError
    except ImportError as e:
        print(f"bench: cannot import the system under test: {e}",
              file=sys.stderr)
        return 2
    try:
        result, lines = run_cell(args.workload, args.seed, args.seconds,
                                 bool(args.trace), t_start=T_START)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    for line in lines:
        print(line, file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
