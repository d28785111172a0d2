"""Fleet driver: per-vehicle scan-to-map odometry through the program's
``RegistrationService``, closed loop.

Each round every lane submits the next frame of its drive, then
``step()`` and ``sync()`` run and the poses are on the host. A lane
whose drive ends closes its stream and admits a fresh one that replays
the drive from frame 0, so set-up stays the same however fast the
service is. The check compares the poses, the verdicts and the maps of
streams drawn from the seed with the plain reference.
"""
from __future__ import annotations

import time

import numpy as np
from bench import gen, harness, reference

WARMUP_ROUNDS = 5        # frame 2 compiles the probe; frame 3 the tiers
GARBAGE_ROUND = 3        # the round whose lane-0 frame escalates


def odometry_config(odo: dict):
    """The program's ``OdometryConfig`` for the configuration's dict."""
    from repro.core.health import HealthThresholds
    from repro.core.icp import ICPParams
    from repro.core.odometry import OdometryConfig
    from repro.data.submap import SubmapParams
    sub = dict(odo["submap"])
    sub["dims"] = tuple(sub["dims"])
    return OdometryConfig(
        params=ICPParams(**odo["params"]), submap=SubmapParams(**sub),
        scan_voxel=odo["scan_voxel"], scan_budget=odo["scan_budget"],
        motion_model=odo["motion_model"],
        min_inlier_frac=odo["min_inlier_frac"], recovery=odo["recovery"],
        recovery_tiers=tuple(odo["recovery_tiers"]),
        thresholds=HealthThresholds(**{k: float(v) for k, v in
                                       odo["thresholds"].items()}),
        velocity_decay=odo["velocity_decay"],
        warmup_frames=odo["warmup_frames"],
        observability_probe=odo["observability_probe"])


class Fleet:
    """One service and the lanes' drives; ``round`` runs one round."""

    def __init__(self, config: dict, traffic: dict):
        from repro.serve.registration_service import (RegistrationService,
                                                      ServiceConfig)
        self.config = config
        self.lanes = traffic["lanes"]
        self.start = traffic["start"]
        self.svc = RegistrationService(ServiceConfig(
            slots=config["slots"], scan_capacity=config["scan_capacity"],
            odometry=odometry_config(config["odometry"])))
        self.streams: list[dict] = []
        self.live: list[dict | None] = [None] * config["slots"]

    def _admit(self, lane: int, first: int) -> None:
        rec = {"id": f"lane{lane}.{len(self.streams)}", "lane": lane,
               "first": first, "next": first, "poses": [], "diags": []}
        self.svc.admit(rec["id"])
        self.streams.append(rec)
        self.live[lane] = rec

    def admit_all(self, start=None) -> None:
        for lane in range(len(self.lanes)):
            self._admit(lane, 0 if start is None else start[lane])

    def _close(self, lane: int) -> None:
        """Close the lane's stream, keeping a device copy of its map for
        the check (an asynchronous copy: no fetch, no wait)."""
        import jax.numpy as jnp
        rec = self.live[lane]
        sub = self.svc._streams[rec["id"]].pipe.submap
        rec["map"] = (jnp.copy(sub.points), jnp.copy(sub.valid))
        self.svc.close(rec["id"])
        self.live[lane] = None

    def close_all(self) -> None:
        for lane, rec in enumerate(self.live):
            if rec is not None:
                self._close(lane)

    def round(self, tracer=None, replace=None) -> int:
        """One closed-loop round; returns the number of poses emitted.
        ``replace`` maps a lane to a scan sent in place of its next
        frame (warm-up only)."""
        tracer = tracer or harness.Untraced()
        with tracer.span("submit"):
            for lane, rec in enumerate(self.live):
                scan = self.lanes[lane][rec["next"]]
                if replace and lane in replace:
                    scan = replace[lane]
                self.svc.submit(rec["id"], scan)
        with tracer.span("step"):
            out = self.svc.step()
        with tracer.span("sync"):
            self.svc.sync()
        for lane, rec in enumerate(self.live):
            if rec["id"] in out:
                pose, diag = out[rec["id"]]
                rec["poses"].append(np.array(pose, np.float64))
                rec["diags"].append(diag)
            rec["next"] += 1
            if rec["next"] == len(self.lanes[lane]):
                with tracer.span("close_admit"):
                    self._close(lane)
                    self._admit(lane, 0)
        return len(out)


def jobs(config, mix, seed):
    return gen.fleet_jobs(config, mix, seed)


def traffic(config, mix, seed, scans):
    return gen.fleet_traffic(config, mix, seed, scans)


def setup(config: dict, traffic: dict, seed: int) -> Fleet:
    """Build the service and compile every program the window runs:
    the service round, the scan probe (frame 2), and every recovery tier
    (a lane-0 frame of random points in round 4 fails them all)."""
    fleet = Fleet(config, traffic)
    fleet.admit_all()
    rng = np.random.default_rng(gen.sub_seed(seed, "warmup"))
    n = fleet.lanes[0][GARBAGE_ROUND].shape[0]
    garbage = rng.uniform(-40.0, 40.0, (n, 3)).astype(np.float32)
    for r in range(WARMUP_ROUNDS):
        fleet.round(replace={0: garbage} if r == GARBAGE_ROUND else None)
    fleet.close_all()
    fleet.streams.clear()
    return fleet


def window(fleet: Fleet, seconds: float, tracer) -> dict:
    """Closed-loop rounds until ``seconds`` have passed; the round that
    is running then completes and counts."""
    fleet.admit_all(fleet.start)
    poses = submitted = 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        with tracer.round():
            poses += fleet.round(tracer)
        submitted += len(fleet.lanes)
    elapsed = time.perf_counter() - t0
    return {"elapsed": elapsed, "poses": poses, "submitted": submitted}


def metrics(rec: dict) -> dict:
    return {"frames_per_s": rec["poses"] / rec["elapsed"]}


def counters(fleet: Fleet, rec: dict) -> dict:
    """Map saturation over the window's streams: frames, the occupied
    cells the maps' capacity dropped (``FrameDiagnostics.dropped_cells``
    is each stream's running total), and for each stream that started at
    its drive's first frame, the frame whose fuse first dropped a cell
    (None where none did)."""
    first_drop = []
    for s in fleet.streams:
        if s["first"] == 0 and s["diags"]:
            first_drop.append(next((k for k, d in enumerate(s["diags"])
                                    if d.dropped_cells > 0), None))
    return {"frames": sum(len(s["diags"]) for s in fleet.streams),
            "dropped_cells": sum(s["diags"][-1].dropped_cells
                                 for s in fleet.streams if s["diags"]),
            "first_drop_frame": first_drop}


def attempted_failed(fleet: Fleet, rec: dict) -> tuple[int, int]:
    ok = sum(1 for s in fleet.streams for p in s["poses"]
             if np.all(np.isfinite(p)))
    return rec["submitted"], rec["submitted"] - ok


def release(fleet: Fleet) -> None:
    """Drop the service and its device state before the reference runs."""
    fleet.svc = None


def program_frames(rec: dict, capacity: int) -> list:
    """A stream's outputs as ``(pose, escalated, fused, occupied,
    dropped)``; ``dropped`` is the running total of map cells the
    capacity dropped."""
    return [(pose, d.recovery_tier > 0, not d.quarantined,
             round(d.map_occupancy * capacity), d.dropped_cells)
            for pose, d in zip(rec["poses"], rec["diags"])]


def control_frames(config: dict, drive: list, first: int, count: int,
                   precision: str) -> tuple[list, np.ndarray]:
    """The reference at a lower ``precision`` in the program's place:
    its frames and its map after the last of them."""
    ctl = reference.Odometry(config["odometry"], precision)
    out = []
    for k in range(count):
        f = ctl.frame(drive[first + k])
        out.append((f["pose"], f["escalate"], f["fused"], f["occupied"],
                    f["dropped"]))
        if f["escalate"]:
            break
    return out, ctl.map


def map_gaps(got: np.ndarray, want: np.ndarray, voxel: float) -> dict:
    """Two maps of voxel centroids on one lattice (both anchor at
    multiples of ``voxel``): the share of occupied cells not in both, of
    the reference's count, and the 90th percentile of the centroid gap
    (m) over the cells in both."""
    def keyed(pts):
        keys = np.floor(np.asarray(pts, np.float64) / voxel).astype(np.int64)
        return {tuple(k): i for i, k in enumerate(keys)}
    a, b = keyed(got), keyed(want)
    both = [k for k in b if k in a]
    apart = len(a) + len(b) - 2 * len(both)
    if both:
        gap = np.linalg.norm(np.asarray(got, np.float64)[[a[k] for k in both]]
                             - np.asarray(want, np.float64)[[b[k] for k in both]],
                             axis=1)
        centroid = float(np.percentile(gap, 90))
    else:
        centroid = float("inf")
    return {"keys": apart / max(len(b), 1), "centroid": centroid}


def compare_stream(config: dict, drive: list, first: int, frames: list,
                   final_map: np.ndarray | None = None) -> dict:
    """Replay a stream's frames through the reference and compare: the
    widest pose gap; the widest relative gap in the map's occupied cells
    counted before the capacity applies (kept plus dropped); the frames
    whose verdict (escalate, fuse) differs; and, where ``final_map`` is
    given and the replay reached the stream's last frame, the map's
    cells and centroids against the reference's. The comparison stops
    at the first frame either side escalates or that differs."""
    ref = reference.Odometry(config["odometry"])
    pose_gap = cells_gap = 0.0
    mismatch = compared = 0
    for k, (pose, escalated, fused, occupied, dropped) in enumerate(frames):
        out = ref.frame(drive[first + k])
        if out["escalate"] or escalated:
            mismatch += int(out["escalate"] != escalated)
            break
        compared += 1
        pose_gap = max(pose_gap, float(np.abs(pose - out["pose"]).max()))
        want = out["occupied"] + out["dropped"]
        cells_gap = max(cells_gap, abs(occupied + dropped - want)
                        / max(want, 1))
        if fused != out["fused"]:
            mismatch += 1
            break
    per = {"pose_gap": pose_gap, "cells_gap": cells_gap,
           "mismatch": mismatch, "compared": compared}
    if final_map is not None and compared == len(frames):
        per["map"] = map_gaps(final_map, ref.map,
                              config["odometry"]["submap"]["voxel_size"])
    return per


def pick_streams(fleet: Fleet, seed: int, count: int,
                 least: int = 10) -> list:
    """The streams the check replays, drawn from the seed among those
    with ``least`` frames or more in the window (the longest where too
    few have)."""
    longest = sorted((s for s in fleet.streams if s["poses"]),
                     key=lambda s: -len(s["poses"]))
    pool = [s for s in longest if len(s["poses"]) >= least]
    if len(pool) < count:
        pool = longest[:count]
    rng = np.random.default_rng(gen.sub_seed(seed, "check"))
    idx = rng.choice(len(pool), min(count, len(pool)), replace=False)
    return [pool[i] for i in sorted(idx)]


def pick(fleet: Fleet, seed: int) -> list:
    """The checked streams, each with its map after its last frame read
    back to the host (valid rows). Closes every stream still open."""
    fleet.close_all()
    picked = pick_streams(fleet, seed, fleet.config["check"]["streams"])
    for s in picked:
        points, valid = (np.asarray(a) for a in s["map"])
        s["map"] = points[valid].astype(np.float64)
    return picked


def check(config: dict, traffic: dict, picked: list,
          precision=None) -> dict:
    """The numbers compared, for ``harness.judge``. With ``precision``
    the lowered reference stands in the program's place (the control).
    A map number that no stream could give is missing, and fails."""
    cap = config["odometry"]["submap"]["capacity"]
    per = []
    for s in picked:
        drive = traffic["lanes"][s["lane"]]
        if precision is None:
            frames, final = program_frames(s, cap), s.get("map")
        else:
            frames, final = control_frames(config, drive, s["first"],
                                           len(s["poses"]), precision)
        per.append(compare_stream(config, drive, s["first"], frames, final))
    maps = [p["map"] for p in per if "map" in p]
    return {
        "pose_gap": max((p["pose_gap"] for p in per), default=float("inf")),
        "map_cells_gap": max((p["cells_gap"] for p in per),
                             default=float("inf")),
        "map_key_mismatch": max((m["keys"] for m in maps), default=None),
        "map_centroid_gap": max((m["centroid"] for m in maps),
                                default=None),
        "verdict_mismatch": sum(p["mismatch"] for p in per),
        "frames_compared": sum(p["compared"] for p in per),
    }
