"""The readings that the ``pairs`` cells' limits are set from, and what
a batch's time is spent on, at a cell's own size, in one process:

  * whole runs of the cell (``bench/run.py``'s own path) on sound seeds,
    then with each fault below planted in the timed path;
  * the lower-precision control: the plain reference with its matrix
    products at ``bf16`` (one bfloat16 pass) and ``bf16x3`` (three) in
    the program's place over each seed's traffic, through the same
    comparison (no chip needed);
  * traced windows with the program's ``fpps.pairs`` spans
    (``bench/spans.py``'s reduction), the cell's per-layer readers, and
    optionally the first two batches as a recorded trace for the tests.

    python bench/pairs_readings.py --workload pairs-b8 --seconds 51 \\
        --seeds 11 12 --faults altered half --fault-seeds 21 22 \\
        --fault-seconds 30 --control-seeds 31 32 --traced-seeds 41 \\
        [--fixture tests/bench/trace-pairs-b8.json]

Faults, each planted for one run:

  * ``altered``: every pose moved 0.3 m in x where ``register_pairs``
    returns it;
  * ``half``: the upper half of each batch left at the identity, as if
    never registered;
  * ``runner_up``: the NN step takes the kernel's runner-up candidate
    (``kernels.ops``'s pick between the two best);
  * ``gate_ignored``: every correspondence counts, whatever its distance.

Prints one JSON line a run. Runs and traced windows need a TPU, like
the benchmark; the benchmark's own runs run none of this.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

FAULTS = ("altered", "half", "runner_up", "gate_ignored")
PRECISIONS = ("bf16", "bf16x3")


def _broken_pairs(orig, fault):
    import jax.numpy as jnp

    def broken(self, pairs, params=None, initial_transforms=None):
        if fault == "gate_ignored":
            params = self._default_params(params)._replace(
                max_correspondence_distance=float("inf"))
            return orig(self, pairs, params, initial_transforms)
        res, batch = orig(self, pairs, params, initial_transforms)
        T = jnp.asarray(res.T)
        if fault == "altered":
            return res._replace(T=T.at[:, 0, 3].add(0.3)), batch
        half = T.shape[0] // 2
        return res._replace(T=T.at[half:].set(jnp.eye(4, dtype=T.dtype))), \
            batch

    return broken


def _runner_up(orig):
    import jax.numpy as jnp

    def broken(src, dst, i1, i2, has_second):
        # the runner-up where there is one, offered as the only candidate
        return orig(src, dst, jnp.where(has_second, i2, i1), i1,
                    jnp.zeros_like(has_second))

    return broken


def plant(fault: str, setattr_=setattr):
    """Plant ``fault`` in the program; returns a function that removes
    it. ``setattr_`` may be a test's ``monkeypatch.setattr``. Plant it
    before the engine builds its executables (the driver's set-up)."""
    from repro.core.engine import RegistrationEngine
    from repro.kernels import ops
    if fault == "runner_up":
        owner, name = ops, "closer_of_two"
        new = _runner_up(getattr(owner, name))
    elif fault in FAULTS:
        owner, name = RegistrationEngine, "register_pairs"
        new = _broken_pairs(getattr(owner, name), fault)
    else:
        raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")
    orig = getattr(owner, name)
    setattr_(owner, name, new)
    return lambda: setattr(owner, name, orig)


def _cell(workload: str, config=None, mix=None):
    """The cell's configuration, mix and driver (``config`` and ``mix``
    given: those, with no cell looked up)."""
    from bench import harness
    if config is None or mix is None:
        cell = harness.cell(harness.load_benchmark(), workload)
        config = harness.load_config(cell["config"])
        mix = harness.load_traffic(cell["traffic"])
    return config, mix, harness.load_driver(config["driver"])


def control_numbers(workload: str, seed: int, precisions=PRECISIONS,
                    workers: int | None = None, config: dict | None = None,
                    mix: dict | None = None) -> dict:
    """``{precision: numbers}``: the lowered reference over one seed's
    first pass of the pool, in the program's place. The ``config``/
    ``mix`` overrides and ``workers=0`` are for the tests' small cells."""
    from bench import gen, harness, scene
    config, mix, driver = _cell(workload, config, mix)
    jobs = driver.jobs(config, mix, seed)
    scans = ([scene.scan(*job) for job in jobs] if workers == 0
             else gen.collect(gen.start(jobs, workers)))
    traffic = driver.traffic(config, mix, seed, scans)
    picked = [{"index": i} for i in range(len(traffic["pool"]))]
    out = {}
    for precision in precisions:
        numbers = driver.check(config, traffic, picked, precision)
        numbers["window_compiles"] = 0
        correct, _ = harness.judge(numbers, config["limits"])
        out[precision] = dict(numbers, correct=correct)
    return out


def run_line(workload: str, seed: int, seconds: float, fault) -> dict:
    """One whole run of the cell, with ``fault`` planted (or None)."""
    from bench import run as bench_run
    undo = plant(fault) if fault else None
    keep: dict = {}
    t0 = time.perf_counter()
    try:
        result, _ = bench_run.run_cell(workload, seed, seconds, False,
                                       keep=keep)
    finally:
        if undo:
            undo()
    counters = {k: v for k, v in keep["counters"].items() if k != "nn_calls"}
    return {"seed": seed, "fault": fault, "correct": result["correct"],
            "numbers": keep["numbers"],
            "metrics": {k: v["value"] for k, v in result["metrics"].items()},
            "counters": counters,
            "memory_peak_bytes": result["device"]["memory_peak_bytes"],
            "run_s": time.perf_counter() - t0}


def fixture_counters(driver, state, rec, batches: int = 2) -> dict:
    """The counters of the first ``batches`` batches of a window, as the
    recorded trace's readers need them."""
    calls = driver.counters(state, {"report": ({}, {}),
                                    "kept": rec["kept"][:batches]})
    calls = calls["nn_calls"]
    return {"pairs": len(calls),
            "pair_iterations": sum(c["iterations"] for c in calls),
            "pair_iterations_run": len(calls) * state.params.max_iterations,
            "nn_calls": calls}


def traced_line(workload: str, seed: int, seconds: float,
                fixture_path=None) -> dict:
    """One traced window: per batch, host and idle ms of each program
    span and device ms of each module (``bench/spans.py``), the idle
    breakdown, the counters and the cell's per-layer readings."""
    from bench import gen, harness, spans, tracefile
    config, mix, driver = _cell(workload)
    cell = harness.cell(harness.load_benchmark(), workload)
    t0 = time.perf_counter()
    scans = gen.collect(gen.start(driver.jobs(config, mix, seed)))
    harness.require_chips(cell["chips"])
    harness.enable_compile_cache()
    traffic = driver.traffic(config, mix, seed, scans)
    state = driver.setup(config, traffic, seed)
    out = {"seed": seed, "setup_s": time.perf_counter() - t0}
    log_dir = tempfile.mkdtemp(prefix="pairs-trace-")
    tracer = harness.Tracer(True, log_dir)
    try:
        rec = driver.window(state, seconds, tracer)
    finally:
        tracer.close()
    events = spans.load(log_dir)
    summary = spans.summarize(events)
    counters = driver.counters(state, rec)
    device = harness.device_info(cell["chips"])
    ctx = {"trace": summary, "counters": counters, "rounds": tracer.rounds,
           "device_kind": device["kind"], "harness": harness}
    b = harness.load_benchmark()
    out["frames_per_s"] = driver.metrics(rec)["frames_per_s"]
    out["readings"] = {m["name"]: harness.load_reader(m["name"]).read(ctx)
                       for m in harness.cell_metrics(b, workload,
                                                     "per_layer")}
    out["counters"] = {k: v for k, v in counters.items() if k != "nn_calls"}
    out["per_batch"] = spans.per_round(summary, {})
    out["kernel_ops"] = {k: v for k, v in summary["ops"].items()
                         if tracefile.opcode(k) == tracefile.KERNEL_CALL}
    out["breakdown"] = tracefile.breakdown(summary, top=20)
    out["busy_s"] = summary["busy_ns"] * 1e-9
    out["window_s"] = summary["window_ns"] * 1e-9
    out["memory_peak_bytes"] = device["memory_peak_bytes"]
    if fixture_path is not None:
        fx = spans.fixture(events)
        fx["counters"] = fixture_counters(driver, state, rec)
        fx["source"] = (f"two traced {workload} batches with the program's "
                        f"spans, {device['kind']} (ops of the second batch "
                        f"under 1 ms left out)")
        fx["rounds"] = [{}, {}]
        pathlib.Path(fixture_path).write_text(json.dumps(fx))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--faults", nargs="*", default=[])
    ap.add_argument("--fault-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--fault-seconds", type=float, default=None)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--traced-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--fixture", default=None)
    args = ap.parse_args(argv)
    for seed in args.control_seeds:
        for precision, numbers in control_numbers(args.workload,
                                                  seed).items():
            print(json.dumps({"seed": seed, "control": precision,
                              **numbers}), flush=True)
    runs = [(s, None, args.seconds) for s in args.seeds]
    runs += [(s, f, args.fault_seconds or args.seconds)
             for f in args.faults for s in args.fault_seeds]
    for seed, fault, seconds in runs:
        print(json.dumps(run_line(args.workload, seed, seconds, fault),
                         default=str), flush=True)
    for k, seed in enumerate(args.traced_seeds):
        print(json.dumps(traced_line(args.workload, seed, args.seconds,
                                     args.fixture if k == 0 else None),
                         default=str), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
