"""Small versions of the benchmark's cells for tests on the CPU: the same
drivers, reference and checks at a few thousand points per scan."""
import copy
import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

CELLS = {"fleet": ("fleet-s8-replay", "tiny-fleet", "tiny-fleet-mix")}
SEED = 2**31 + 17


def load(name: str) -> dict:
    return json.loads((HERE / f"{name}.json").read_text())


def benchmark(kind: str) -> dict:
    """BENCHMARK.json with the real cell renamed ``tiny``."""
    real = CELLS[kind][0]
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in b["workloads"]:
        if w["name"] == real:
            w["name"] = "tiny"
    for key in ("end_to_end", "per_layer"):
        for m in b[key]:
            if "workloads" in m:
                m["workloads"] = ["tiny" if x == real else x
                                  for x in m["workloads"]]
    return b


SECONDS = {"fleet": 6.0}


def run(kind: str, seconds: float | None = None, trace: bool = False,
        seed: int = SEED, config: dict | None = None):
    """One whole run of the small cell on the CPU (no chip check)."""
    from bench import run as bench_run
    _, cfg, mix = CELLS[kind]
    return bench_run.run_cell(
        "tiny", seed, seconds or SECONDS[kind], trace, require_tpu=False,
        workers=0, config=copy.deepcopy(config or load(cfg)), mix=load(mix),
        benchmark=benchmark(kind))
