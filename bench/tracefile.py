"""Reduction of a JAX profiler trace to the numbers the per-layer readers
need.

Two steps, so the second can be checked on a small recorded trace:

  * :func:`load` reads an ``.xplane.pb`` with ``jax.profiler.ProfileData``
    into plain events: each device plane's XLA ops and XLA modules, and
    the benchmark's own host spans (names starting ``bench.``);
  * :func:`summarize` turns those events into the window, the device's
    busy time, time per module and per op name, each round's span and
    the device time inside it (with each op name's count and time), and
    the idle gaps.

Times are in nanoseconds on the profiler's clock; host spans and device
events share it.
"""
from __future__ import annotations

import bisect
import pathlib
import re

SPAN_PREFIX = "bench."
KERNEL_CALL = "tpu_custom_call"
CONTROL_FLOW = ("while", "conditional", "call")
ROUND_SPAN = "bench.round"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def load(log_dir) -> dict:
    """Events of the newest trace under ``log_dir``: ``{"ops": [...],
    "modules": [...], "spans": [...]}``, each event ``[name, start_ns,
    duration_ns, device]`` (device is ``-1`` for host spans)."""
    from jax.profiler import ProfileData
    files = sorted(pathlib.Path(log_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(str(files[-1]))
    out = {"ops": [], "modules": [], "spans": []}
    for plane in data.planes:
        device = _device_index(plane.name)
        for line in plane.lines:
            if device is None:
                out["spans"] += [[e.name, e.start_ns, e.duration_ns, -1]
                                 for e in line.events
                                 if e.name.startswith(SPAN_PREFIX)]
            elif line.name == OPS_LINE:
                out["ops"] += [[op_name(e.name), e.start_ns, e.duration_ns,
                                device] for e in line.events]
            elif line.name == MODULES_LINE:
                out["modules"] += [[module_name(e.name), e.start_ns,
                                    e.duration_ns, device]
                                   for e in line.events]
    return out


_OPCODE = re.compile(r"\s([a-z][\w-]*)\(")


def op_name(text: str) -> str:
    """An XLA op event's HLO text (``%fusion.3 = f32[8]{0} fusion(%a)``)
    shortened to its name and opcode (``fusion.3 fusion``); a Pallas
    kernel's custom call reads ``<name> tpu_custom_call``."""
    head, _, rest = text.partition(" = ")
    name = head.strip().lstrip("%")
    if not rest:
        return name
    if f'custom_call_target="{KERNEL_CALL}"' in rest:
        return f"{name} {KERNEL_CALL}"
    m = _OPCODE.search(rest)
    return f"{name} {m.group(1)}" if m else name


def opcode(name: str) -> str:
    return name.rsplit(" ", 1)[-1]


def _device_index(plane_name: str):
    """Device ordinal of a TPU plane (``/device:TPU:3`` -> 3), else None."""
    head = "/device:TPU:"
    if not plane_name.startswith(head):
        return None
    tail = plane_name[len(head):]
    return int(tail) if tail.isdigit() else None


def union(intervals) -> list:
    """Merge ``(start, end)`` intervals; returns them sorted, disjoint."""
    merged: list = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def covered(merged, lo, hi) -> float:
    """Length of ``[lo, hi]`` that the merged intervals cover."""
    return float(sum(max(0, min(e, hi) - max(s, lo)) for s, e in merged))


def summarize(events: dict) -> dict | None:
    """Window, busy time, per-module and per-op time, rounds and idle
    gaps of a loaded trace; None if it holds no round span."""
    rounds = sorted((s, s + d) for n, s, d, _ in events["spans"]
                    if n == ROUND_SPAN)
    if not rounds:
        return None
    lo, hi = rounds[0][0], rounds[-1][1]
    devices = sorted({ev[3] for ev in events["ops"]}) or [0]
    busy_by_dev = {dev: union((s, s + d) for _, s, d, dv in events["ops"]
                              if dv == dev) for dev in devices}
    busy = sum(covered(b, lo, hi) for b in busy_by_dev.values()) / len(
        devices)
    per_module: dict = {}
    for name, s, d, _ in events["modules"]:
        if lo <= s <= hi:
            per_module[name] = per_module.get(name, 0.0) + d
    per_op: dict = {}
    for name, s, d, _ in events["ops"]:
        if lo <= s <= hi and opcode(name) not in CONTROL_FLOW:
            per_op[name] = per_op.get(name, 0.0) + d
    busy0 = busy_by_dev[devices[0]]
    round_info = [{"start": s, "end": e, "device_busy": covered(busy0, s, e),
                   "ops": {}} for s, e in rounds]
    starts = [r["start"] for r in round_info]
    for name, s, d, dev in events["ops"]:
        i = bisect.bisect_right(starts, s) - 1
        if (dev == devices[0] and i >= 0 and s <= round_info[i]["end"]
                and opcode(name) not in CONTROL_FLOW):
            count, ns = round_info[i]["ops"].get(name, (0, 0.0))
            round_info[i]["ops"][name] = (count + 1, ns + d)
    spans = [(n, s, s + d) for n, s, d, _ in events["spans"]
             if n != ROUND_SPAN]
    gaps = []
    prev = lo
    for s, e in busy0 + [[hi, hi]]:
        if s > prev and s <= hi:
            gaps.append((prev, min(s, hi)))
        prev = max(prev, e)
    return {"window_ns": float(hi - lo), "busy_ns": busy,
            "devices": len(devices), "modules": per_module, "ops": per_op,
            "rounds": round_info,
            "gaps": [(g1 - g0, host_activity(spans, g0, g1))
                     for g0, g1 in gaps]}


def module_name(name: str) -> str:
    """``jit_run(123)`` -> ``jit_run``: the module's name without the
    program id the trace appends."""
    return name.split("(", 1)[0]


def host_activity(spans, lo, hi) -> str:
    """The benchmark span that covers most of ``[lo, hi]``; ``"round"``
    where no span finer than the round does."""
    best, best_len = "round", 0.0
    for name, s, e in spans:
        c = max(0, min(e, hi) - max(s, lo))
        if c > best_len or (c == best_len and c > 0):
            best, best_len = name[len(SPAN_PREFIX):], c
    return best


def breakdown(summary: dict, top: int = 10) -> dict:
    """The device ops that took most time (control-flow ops, which hold
    others, left out) and the idle time by what the host was doing, in
    seconds."""
    ops = sorted(summary["ops"].items(), key=lambda kv: -kv[1])[:top]
    by_activity: dict = {}
    for length, what in summary["gaps"]:
        by_activity[what] = by_activity.get(what, 0.0) + length
    gaps = sorted(by_activity.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, t * 1e-9] for n, t in ops],
            "idle_gaps": [[n, t * 1e-9] for n, t in gaps]}
