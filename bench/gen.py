"""The one traffic generator: turns a configuration's scene and a traffic
mix's parameters (``bench/traffic/<name>.json``) into scans, from the
run's seed alone.

Scans are generated in worker processes that import numpy and
``bench.scene`` only, started before the parent touches JAX, so they
never contend for the chip. The same seed gives the same scans.

Fleet mixes name ``drives`` (one sequence per vehicle) and ``stagger``
(drive d's first stream joins at frame ``stagger * d``); a drive is the
configuration's ``drive_frames`` frames long. The run seed draws every
scan's points and places the drives on the service's lanes; the layout
of each sequence's world and its path are fixed by the sequence.
"""
from __future__ import annotations

import multiprocessing
import os
import zlib
from concurrent.futures import ProcessPoolExecutor

import numpy as np
from bench import scene


def _scan_job(args):
    seq, frame, scene_cfg, seed = args
    return scene.scan(seq, frame, scene_cfg, seed)


def sub_seed(seed: int, *parts) -> int:
    """A stable 32-bit seed for one part of the traffic (crc32 of the
    run's seed and the part's name): the same on every platform."""
    key = "/".join(str(p) for p in (seed,) + parts).encode()
    return int(zlib.crc32(key))


def start(jobs: list, workers: int | None = None):
    """Start generating ``jobs`` (``(seq, frame, scene, seed)`` tuples) in
    spawned worker processes; returns ``(executor, futures)``. Call
    before importing JAX, and collect with :func:`collect`."""
    if workers is None:
        workers = max(1, min(len(jobs), (os.cpu_count() or 2) - 1, 12))
    ex = ProcessPoolExecutor(max_workers=workers,
                             mp_context=multiprocessing.get_context("spawn"))
    return ex, [ex.submit(_scan_job, job) for job in jobs]


def collect(pending) -> list:
    """Results of :func:`start`, in job order; stops the workers."""
    ex, futures = pending
    try:
        return [f.result() for f in futures]
    finally:
        ex.shutdown(wait=True)


def fleet_jobs(config: dict, mix: dict, seed: int) -> list:
    points = sub_seed(seed, "points")
    return [(seq, f, config["scene"], points)
            for seq in mix["drives"] for f in range(config["drive_frames"])]


def fleet_traffic(config: dict, mix: dict, seed: int, scans: list) -> dict:
    """Per-lane drives: ``lanes[i]`` is the list of scans lane i replays,
    ``start[i]`` the frame its first stream joins at, and ``drives[i]``
    the drive it holds, placed by the run seed. Each drive keeps its own
    start, so every seed sends the same sequences in every round, on
    other lanes."""
    frames = config["drive_frames"]
    drives = [list(scans[d * frames:(d + 1) * frames])
              for d in range(len(mix["drives"]))]
    place = np.random.default_rng(sub_seed(seed, "lanes")).permutation(
        len(drives))
    return {"lanes": [drives[d] for d in place],
            "start": [(mix.get("stagger", 0) * int(d)) % frames
                      for d in place],
            "drives": [int(d) for d in place]}
