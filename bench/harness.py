"""What every cell shares: finding its files by name, the device check,
the compile cache, the count of compilations inside the window, the
traced window, the per-layer readers and the result line.

Files are found by the names in ``BENCHMARK.json``:

  * ``bench/configs/<config>.json`` — a deployment: its ``driver`` (a
    module of ``bench/drivers``), scene, sizes, the numbers ``correct``
    compares and their ``limits``;
  * ``bench/traffic/<traffic>.json`` — a mix's parameters, read by
    ``bench/gen.py``;
  * ``bench/metrics/<metric>.py`` — a per-layer reader, ``read(ctx)``
    returning a number or None;
  * ``bench/counts/<kernel>.py`` — a kernel's operations and bytes per
    call, ``flops(call)`` and ``bytes_moved(call)``;
  * ``bench/peaks.json`` — device peaks keyed by ``device_kind``.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import pathlib

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


class BenchError(Exception):
    """The benchmark cannot run this cell here."""


def load_benchmark(root=ROOT) -> dict:
    return json.loads((pathlib.Path(root) / "BENCHMARK.json").read_text())


def cell(benchmark: dict, name: str) -> dict:
    for w in benchmark["workloads"]:
        if w["name"] == name:
            return w
    raise BenchError(f"no workload {name!r} in BENCHMARK.json")


def _named_file(folder: str, name: str, suffix: str) -> pathlib.Path:
    path = BENCH / folder / f"{name}{suffix}"
    if not path.is_file():
        raise BenchError(f"no {folder[:-1]} file {path.relative_to(ROOT)}")
    return path


def load_config(name: str) -> dict:
    return json.loads(_named_file("configs", name, ".json").read_text())


def load_traffic(name: str) -> dict:
    return json.loads(_named_file("traffic", name, ".json").read_text())


def _load_module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_driver(name: str):
    return _load_module(_named_file("drivers", name, ".py"))


def load_reader(metric: str):
    return _load_module(_named_file("metrics", metric, ".py"))


def load_counts(kernel: str):
    """The count module of ``kernel``, or None: a kernel with no count
    file gets no roofline share."""
    path = BENCH / "counts" / f"{kernel}.py"
    return _load_module(path) if path.is_file() else None


def peaks(device_kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())
    if device_kind not in table["devices"]:
        raise BenchError(f"no peaks for device kind {device_kind!r} in "
                         f"bench/peaks.json")
    return table["devices"][device_kind]


def cell_metrics(benchmark: dict, workload: str, kind: str) -> list:
    """The metrics of ``kind`` (``end_to_end`` or ``per_layer``) this cell
    reports: those with no ``workloads`` key and those that list it."""
    return [m for m in benchmark[kind]
            if workload in m.get("workloads", [workload])]


# -- the device ----------------------------------------------------------------

def require_chips(chips: int) -> None:
    """Refuse to run anywhere but on ``chips`` or more TPU chips."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchError(f"JAX finds no TPU (platform "
                         f"{devices[0].platform!r}); nothing is measured")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips; JAX finds "
                         f"{len(devices)}")


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says), every program in it."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_info(chips: int) -> dict:
    import jax
    devices = jax.devices()
    peak = 0
    for d in devices[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


class CompileCounter:
    """Counts JAX traces and compilations: in the window there should be
    none. Every new program or shape is traced before it compiles or is
    read from the cache."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax.monitoring
        self.count = 0
        self._on = False
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, _secs, **_kw):
        if self._on and name in self.EVENTS:
            self.count += 1

    def start(self) -> None:
        self.count, self._on = 0, True

    def stop(self) -> int:
        self._on = False
        return self.count


class Tracer:
    """The profiler over a ``--trace 1`` window: every round inside a
    ``bench.round`` span (and any finer ``span`` a driver opens), written
    to ``log_dir``."""

    def __init__(self, enabled: bool, log_dir: str | None = None):
        self.enabled = enabled
        self.log_dir = log_dir
        self.rounds: list[dict] = []
        self._started = False
        self._done = False

    @contextlib.contextmanager
    def round(self, **info):
        if not self.enabled or self._done:
            yield
            return
        import jax
        if not self._started:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(self.log_dir, profiler_options=opts)
            self._started = True
        with jax.profiler.TraceAnnotation("bench.round"):
            yield
        self.rounds.append(info)

    def span(self, name: str):
        """A host span inside a round, for attributing idle gaps."""
        if not self.enabled or self._done or not self._started:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation("bench." + name)

    def close(self) -> None:
        if self.enabled and self._started and not self._done:
            import jax
            jax.profiler.stop_trace()
        self._done = True


class Untraced(Tracer):
    def __init__(self):
        super().__init__(False)


# -- correctness -------------------------------------------------------------

def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Hold each number to its limit: ``{"max": x}`` means at most x,
    ``{"min": x}`` at least x. A number that is missing or not finite
    fails. Returns ``(correct, {name: {"value", "limit", "ok"}})``."""
    out = {}
    for name, lim in limits.items():
        v = numbers.get(name)
        if "max" in lim:
            bound, ok = lim["max"], v is not None and np.isfinite(v) and (
                v <= lim["max"])
            shown = f"<= {bound}"
        else:
            bound, ok = lim["min"], v is not None and np.isfinite(v) and (
                v >= lim["min"])
            shown = f">= {bound}"
        out[name] = {"value": v, "limit": shown, "ok": bool(ok)}
    return all(c["ok"] for c in out.values()), out


def check_lines(checks: dict) -> list[str]:
    return [f"check {name}: {c['value']} (limit {c['limit']}) "
            f"{'ok' if c['ok'] else 'FAILED'}" for name, c in checks.items()]
