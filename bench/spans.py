"""The program's own spans (``fpps.*``) in a profiler trace, beside the
benchmark's, and a traced run that reads them.

    python bench/spans.py --workload fleet-s8-replay --seconds 51 \\
        --seeds 11 12 [--untraced] [--fixture tests/bench/<name>.json]

The registration service names each stage of its round with a
``jax.profiler.TraceAnnotation`` (``fpps.step``, ``fpps.prepare``,
``fpps.register``, ``fpps.complete``, ``fpps.wait.*``, ...; see
``serve/registration_service.py``) and counts the slot executable's
lane-iterations in ``service_report()``. The benchmark's reduction
(``bench/tracefile.py``) keeps only its own ``bench.*`` spans; this
module adds the program's:

  * :func:`load` is ``tracefile.load`` plus ``"program"``: the host
    events named ``fpps.*``, each ``[name, start_ns, duration_ns,
    metadata]``;
  * :func:`summarize` is ``tracefile.summarize`` plus ``"program"``: for
    each span name its count, its host time inside the window and the
    device-idle time inside the union of its spans. Each idle gap is put
    down to the shortest program span that covers at least half of it,
    and to the benchmark's span, as ``tracefile`` does, where none does.
    With no program spans in the trace the summary is
    ``tracefile.summarize``'s, unchanged.

Run as a script it measures each seed's window as ``bench/run.py`` does
(same traffic, set-up and closed-loop rounds), traced, and prints one
JSON line a seed: the traced ``frames_per_s``, the per-round host and
idle ms of every program span, device ms per module, the idle breakdown,
and the lane-iteration counters over the window. ``--untraced`` first
runs an untraced window on the same seed, for the cost of tracing;
``--fixture`` writes the first seed's first two traced rounds as a small
recorded trace for the tests. It runs on a TPU only, and the benchmark's
own runs do not run it.
"""
from __future__ import annotations

import argparse
import bisect
import json
import pathlib
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import tracefile  # noqa: E402

PREFIX = "fpps."
COUNTERS = ("lane_iterations", "lane_iterations_run", "loop_iterations")


def load(log_dir) -> dict:
    """``tracefile.load``'s events of the newest trace under ``log_dir``
    and the program's spans under ``"program"``."""
    from jax.profiler import ProfileData
    events = tracefile.load(log_dir)
    files = sorted(pathlib.Path(log_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    data = ProfileData.from_file(str(files[-1]))
    events["program"] = [
        [e.name, e.start_ns, e.duration_ns, dict(e.stats)]
        for plane in data.planes
        if tracefile._device_index(plane.name) is None
        for line in plane.lines for e in line.events
        if e.name.startswith(PREFIX)]
    return events


def idle_gaps(busy, lo, hi) -> list:
    """The gaps of ``[lo, hi]`` that the merged ``busy`` intervals leave,
    in order (``tracefile.summarize``'s gaps)."""
    gaps, prev = [], lo
    for s, e in list(busy) + [[hi, hi]]:
        if prev < s <= hi:
            gaps.append((prev, min(s, hi)))
        prev = max(prev, e)
    return gaps


class _Covered:
    """Length of an interval that sorted, disjoint intervals cover, by
    bisection."""

    def __init__(self, merged):
        self.merged = merged
        self.starts = [s for s, _ in merged]
        self.ends = [e for _, e in merged]
        self.prefix = [0.0]
        for s, e in merged:
            self.prefix.append(self.prefix[-1] + (e - s))

    def __call__(self, lo, hi) -> float:
        i = bisect.bisect_right(self.ends, lo)      # first to end after lo
        j = bisect.bisect_left(self.starts, hi)     # those starting before hi
        if j <= i:
            return 0.0
        total = self.prefix[j] - self.prefix[i]
        total -= max(0, lo - self.merged[i][0])
        total -= max(0, self.merged[j - 1][1] - hi)
        return float(total)


def attribute(gaps, spans) -> list:
    """For each gap ``(g0, g1)`` the shortest span ``(name, start, end)``
    that covers at least half of it, or None. Such a span holds the
    gap's midpoint, so one sweep over both in time order finds it."""
    spans = sorted(spans, key=lambda sp: sp[1])
    out, active, j = [], [], 0
    for g0, g1 in gaps:
        mid = 0.5 * (g0 + g1)
        while j < len(spans) and spans[j][1] <= mid:
            active.append(spans[j])
            j += 1
        active = [sp for sp in active if sp[2] >= mid]
        fits = [sp for sp in active
                if 2 * (min(sp[2], g1) - max(sp[1], g0)) >= g1 - g0]
        out.append(min(fits, key=lambda sp: sp[2] - sp[1])
                   if fits else None)
    return out


def summarize(events: dict) -> dict | None:
    """``tracefile.summarize`` with the program's spans: ``"program"``
    maps each span name to ``{"count", "host_ns", "idle_ns"}`` over the
    window, and ``"gaps"`` names the program span an idle gap fell in
    (``fpps.<name>``) where one covers at least half of it."""
    summary = tracefile.summarize(events)
    program = events.get("program") or []
    if summary is None or not program:
        return summary
    lo, hi = summary["rounds"][0]["start"], summary["rounds"][-1]["end"]
    devices = sorted({ev[3] for ev in events["ops"]}) or [0]
    busy0 = tracefile.union((s, s + d) for _, s, d, dv in events["ops"]
                            if dv == devices[0])
    gaps = idle_gaps(busy0, lo, hi)
    assert len(gaps) == len(summary["gaps"])
    spans = [(n, max(s, lo), min(s + d, hi)) for n, s, d, _ in program
             if s + d > lo and s < hi]
    idle = _Covered(tracefile.union(gaps))
    per: dict = {}
    for name in sorted({n for n, _, _ in spans}):
        mine = [(s, e) for n, s, e in spans if n == name]
        per[name] = {"count": len(mine),
                     "host_ns": float(sum(e - s for s, e in mine)),
                     "idle_ns": sum(idle(s, e) for s, e in
                                    tracefile.union(mine))}
    summary["program"] = per
    summary["gaps"] = [(length, sp[0] if sp else what)
                       for (length, what), sp in
                       zip(summary["gaps"], attribute(gaps, spans))]
    return summary


def counters(before: dict, after: dict) -> dict:
    """The lane-iteration counters' change between two
    ``service_report()`` readings; empty where the program has none."""
    if not all(k in after for k in COUNTERS):
        return {}
    return {k: after[k] - before[k] for k in COUNTERS}


def per_round(summary: dict, counts: dict) -> dict:
    """Per-round readings of a summarized window: host and idle ms of
    each program span, device ms of each module, the share of idle time
    put down to program spans, the host ms inside ``fpps.complete``
    (the control plane), and the slot executable's lane waste and
    device ms per loop iteration."""
    n = len(summary["rounds"])
    idle = sum(length for length, _ in summary["gaps"])
    named = sum(length for length, what in summary["gaps"]
                if what.startswith(PREFIX))
    program = summary.get("program", {})
    out = {"rounds": n,
           "spans": {k: {"count": v["count"] / n,
                         "host_ms": v["host_ns"] / n * 1e-6,
                         "idle_ms": v["idle_ns"] / n * 1e-6}
                     for k, v in program.items()},
           "modules_ms": {k: v / n * 1e-6 for k, v in
                          sorted(summary["modules"].items(),
                                 key=lambda kv: -kv[1])},
           "idle_ms": idle / n * 1e-6,
           "idle_named_share": named / idle if idle else None}
    if "fpps.complete" in program:
        out["control_plane_ms"] = (program["fpps.complete"]["host_ns"] / n
                                   * 1e-6)
    if counts.get("lane_iterations_run"):
        out["icp_lane_waste"] = 100.0 * (
            1.0 - counts["lane_iterations"] / counts["lane_iterations_run"])
    if counts.get("loop_iterations") and "jit_run" in summary["modules"]:
        out["icp_ms_per_iteration"] = (summary["modules"]["jit_run"]
                                       / counts["loop_iterations"] * 1e-6)
    return out


# -- the traced run ----------------------------------------------------------

def _window(fleet, seconds: float, tracer) -> dict:
    """``bench/drivers/fleet.window``'s closed loop, with the service's
    report read after every round (a dict of counters: no device
    work)."""
    fleet.admit_all(fleet.start)
    reports = [fleet.svc.service_report()]
    poses = 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        with tracer.round():
            poses += fleet.round(tracer)
        reports.append(fleet.svc.service_report())
    elapsed = time.perf_counter() - t0
    fleet.close_all()
    fleet.streams.clear()
    return {"frames_per_s": poses / elapsed, "reports": reports}


def fixture(events: dict, rounds: int = 2, short_ns: float = 1e6) -> dict:
    """The first ``rounds`` rounds of a loaded trace, as the tests'
    recorded traces hold them: device events of later rounds shorter
    than ``short_ns`` left out to keep the file small."""
    marks = sorted((s, s + d) for n, s, d, _ in events["spans"]
                   if n == tracefile.ROUND_SPAN)[:rounds]
    lo, hi, first_end = marks[0][0], marks[-1][1], marks[0][1]

    def keep(ev, device):
        s, d = ev[1], ev[2]
        inside = s + d >= lo and s <= hi
        return inside and (not device or s <= first_end or d >= short_ns)

    return {"ops": [e for e in events["ops"] if keep(e, True)],
            "modules": [e for e in events["modules"] if keep(e, True)],
            "spans": [e for e in events["spans"] if keep(e, False)],
            "program": [e for e in events["program"] if keep(e, False)]}


def measure(workload: str, seed: int, seconds: float, untraced: bool,
            fixture_path=None) -> dict:
    """One seed: traffic, set-up, (an untraced window,) a traced window,
    and its per-round readings."""
    from bench import gen, harness
    cell = harness.cell(harness.load_benchmark(), workload)
    config = harness.load_config(cell["config"])
    mix = harness.load_traffic(cell["traffic"])
    driver = harness.load_driver(config["driver"])
    t0 = time.perf_counter()
    scans = gen.collect(gen.start(driver.jobs(config, mix, seed)))
    harness.require_chips(cell["chips"])
    harness.enable_compile_cache()
    fleet = driver.setup(config, driver.traffic(config, mix, seed, scans),
                         seed)
    out = {"seed": seed, "setup_s": time.perf_counter() - t0}
    if untraced:
        out["untraced_frames_per_s"] = _window(
            fleet, seconds, harness.Untraced())["frames_per_s"]
    log_dir = tempfile.mkdtemp(prefix="spans-trace-")
    tracer = harness.Tracer(True, log_dir)
    try:
        rec = _window(fleet, seconds, tracer)
    finally:
        tracer.close()
    events = load(log_dir)
    summary = summarize(events)
    reports = rec["reports"]
    out["frames_per_s"] = rec["frames_per_s"]
    out["counters"] = counters(reports[0], reports[-1])
    out.update(per_round(summary, out["counters"]))
    out["breakdown"] = tracefile.breakdown(summary, top=20)
    out["memory_peak_bytes"] = harness.device_info(
        cell["chips"])["memory_peak_bytes"]
    if fixture_path is not None:
        rec_fx = fixture(events)
        rec_fx["counters"] = counters(reports[0], reports[2])
        rec_fx["source"] = (f"two traced {workload} rounds with the "
                            f"program's spans, "
                            f"{harness.device_info(1)['kind']} (ops of "
                            f"the second round under 1 ms left out)")
        rec_fx["rounds"] = [{}, {}]
        pathlib.Path(fixture_path).write_text(json.dumps(rec_fx))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--untraced", action="store_true")
    ap.add_argument("--fixture", default=None)
    args = ap.parse_args(argv)
    for k, seed in enumerate(args.seeds):
        line = measure(args.workload, seed, args.seconds, args.untraced,
                       args.fixture if k == 0 else None)
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
