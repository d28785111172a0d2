"""The lower-precision control of a cell's comparison, at the cell's own
size: the plain reference computed with its matrix products at
``bf16`` (one bfloat16 pass) and ``bf16x3`` (three passes) stands in the
program's place, over the traffic of each seed, and goes through the
same comparison. The control has to come out as not correct; its
readings are the upper ends of the limits in ``PERF.md``.

    python bench/control.py --workload <cell> --seeds 1 2 3

Prints one JSON line per seed and precision. The benchmark's own runs do
not run it. It needs no chip (the reference runs on the host).
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

PRECISIONS = ("bf16", "bf16x3")


def control_numbers(workload: str, seed: int, precisions=PRECISIONS,
                    workers: int | None = None, config: dict | None = None,
                    mix: dict | None = None) -> dict:
    """``{precision: numbers}`` for one seed of ``workload``. The
    ``config``/``mix`` overrides and ``workers=0`` (generate in this
    process) are for the tests' small cells."""
    import numpy as np

    from bench import gen, harness, scene
    if config is None or mix is None:
        cell = harness.cell(harness.load_benchmark(), workload)
        config = harness.load_config(cell["config"])
        mix = harness.load_traffic(cell["traffic"])
    driver = harness.load_driver(config["driver"])
    jobs = driver.jobs(config, mix, seed)
    scans = ([scene.scan(*job) for job in jobs] if workers == 0
             else gen.collect(gen.start(jobs, workers)))
    traffic = driver.traffic(config, mix, seed, scans)
    rng = np.random.default_rng(gen.sub_seed(seed, "check"))
    lanes = rng.choice(len(traffic["lanes"]), config["check"]["streams"],
                       replace=False)
    picked = [{"lane": int(i), "first": 0,
               "poses": [None] * len(traffic["lanes"][i])}
              for i in sorted(lanes)]
    out = {}
    for precision in precisions:
        numbers = driver.check(config, traffic, picked, precision)
        numbers["window_compiles"] = 0
        correct, _ = harness.judge(numbers, config["limits"])
        out[precision] = dict(numbers, correct=correct)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    for seed in args.seeds:
        for precision, numbers in control_numbers(args.workload,
                                                  seed).items():
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "precision": precision, **numbers}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
