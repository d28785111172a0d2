"""Pairs driver: the FPPS §IV-A protocol (sampled source scan onto the
full target scan, from the identity) through the program's
``RegistrationEngine.register_pairs``, closed loop, one batch in flight.

The pool is the mix's ``sequences`` x ``pairs_per_sequence`` consecutive
pairs, source frame f+1 onto target frame f; batch k holds pool pairs
``batch * k`` to ``batch * k + batch - 1`` (mod the pool), in pool order.
The seed draws every scan's points and each source's sample, never the
pool or the order, so every seed sends the same batches and meets the
same target buckets. The timed path runs from host arrays in to poses on
the host, collate and upload included. The check compares the window's
first pass over the pool with the plain reference.
"""
from __future__ import annotations

import math
import time

import numpy as np
from bench import gen, harness, reference


def jobs(config, mix, seed):
    points = gen.sub_seed(seed, "points")
    return [(seq, f, config["scene"], points) for seq in mix["sequences"]
            for f in range(mix["pairs_per_sequence"] + 1)]


def batch_order(pool: int, batch: int) -> list:
    """Pool indices of each batch of one cycle: batch k holds pairs
    ``batch * k + i`` mod ``pool``; the cycle ends where it repeats."""
    cycle = pool // math.gcd(pool, batch)
    return [[(batch * k + i) % pool for i in range(batch)]
            for k in range(cycle)]


def traffic(config, mix, seed, scans):
    """``pool[i]`` is ``{"seq", "frame", "src", "dst"}``: ``samples``
    points of frame ``frame + 1`` drawn without replacement, and the
    whole scan of frame ``frame``; ``batches`` the cycle's pool indices."""
    per = mix["pairs_per_sequence"] + 1
    pool = []
    for s, seq in enumerate(mix["sequences"]):
        frames = scans[s * per:(s + 1) * per]
        for f in range(per - 1):
            src = frames[f + 1]
            rng = np.random.default_rng(gen.sub_seed(seed, "sample", seq, f))
            pick = np.sort(rng.choice(src.shape[0], config["samples"],
                                      replace=False))
            pool.append({"seq": seq, "frame": f, "src": src[pick],
                         "dst": frames[f]})
    return {"pool": pool, "batches": batch_order(len(pool), config["batch"])}


class Pairs:
    """The engine, its parameters and the traffic; ``batch`` registers
    batch k and returns its result on the device and its poses on the
    host."""

    def __init__(self, config: dict, traffic: dict):
        from repro.core.engine import get_engine
        from repro.core.icp import ICPParams
        # the registered engine's class at its defaults, with a compile
        # cache of this run's own
        self.engine = type(get_engine(config["engine"]))()
        if not hasattr(self.engine, "report"):
            raise harness.BenchError(
                "the program's RegistrationEngine has no report(): it "
                "cannot run this cell")
        self.params = ICPParams(**config["params"])
        self.pool = traffic["pool"]
        self.batches = traffic["batches"]

    def pairs(self, k: int) -> list:
        return [(self.pool[i]["src"], self.pool[i]["dst"])
                for i in self.batches[k % len(self.batches)]]

    def batch(self, k: int, tracer=None):
        tracer = tracer or harness.Untraced()
        with tracer.span("register"):
            res, _ = self.engine.register_pairs(self.pairs(k), self.params)
        with tracer.span("fetch"):
            poses = np.asarray(res.T)
        return res, poses

    def buckets(self) -> dict:
        """The first batch of the cycle for each target bucket."""
        from repro.data.collate import bucket_size
        first: dict = {}
        for k, idx in enumerate(self.batches):
            m = bucket_size(max(self.pool[i]["dst"].shape[0] for i in idx))
            first.setdefault(m, k)
        return first


def setup(config: dict, traffic: dict, seed: int) -> Pairs:
    """Build the engine and compile the batch executable at every target
    bucket the cycle meets, on a batch of that bucket."""
    state = Pairs(config, traffic)
    for k in state.buckets().values():
        state.batch(k)
    return state


def window(state: Pairs, seconds: float, tracer) -> dict:
    """Closed-loop batches until ``seconds`` have passed; the batch that
    is running then completes and counts. Each batch's result stays on
    the device for the check and the counters, read after the window."""
    before = state.engine.report()
    kept, finite = [], 0
    k = 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        with tracer.round(batch=k):
            res, poses = state.batch(k, tracer)
        finite += int(np.isfinite(poses).all(axis=(1, 2)).sum())
        kept.append((k, res))
        k += 1
    elapsed = time.perf_counter() - t0
    state.kept = kept
    return {"elapsed": elapsed, "pairs": k * len(state.batches[0]),
            "finite": finite, "kept": kept,
            "report": (before, state.engine.report())}


def metrics(rec: dict) -> dict:
    return {"frames_per_s": rec["pairs"] / rec["elapsed"]}


def attempted_failed(state: Pairs, rec: dict) -> tuple[int, int]:
    return rec["pairs"], rec["pairs"] - rec["finite"]


def counters(state: Pairs, rec: dict) -> dict:
    """The engine's ``report()`` counters over the window, and each
    window pair's NN work as ``nn_calls``: valid source points ``n``,
    valid target points ``m`` and the ``iterations`` it ran."""
    before, after = rec["report"]
    out = {key: after[key] - before[key] for key in after}
    calls = []
    for k, res in rec["kept"]:
        its = np.asarray(res.iterations)
        for slot, i in enumerate(state.batches[k % len(state.batches)]):
            calls.append({"n": int(state.pool[i]["src"].shape[0]),
                          "m": int(state.pool[i]["dst"].shape[0]),
                          "iterations": int(its[slot])})
    out["nn_calls"] = calls
    return out


def release(state: Pairs) -> None:
    """Drop the engine and its executables before the reference runs."""
    state.engine = None


def pick(state: Pairs, seed: int) -> list:
    """The window's first result for each pool pair, in pool order: the
    first pass over the pool (what the window returned, where it
    returned less), read back to the host."""
    first: dict = {}
    for k, res in state.kept:
        host = {f: np.asarray(getattr(res, f))
                for f in ("T", "rmse", "iterations", "degenerate")}
        for slot, i in enumerate(state.batches[k % len(state.batches)]):
            first.setdefault(i, {"index": i, **{f: v[slot] for f, v in
                                                host.items()}})
        if len(first) == len(state.pool):
            break
    return [first[i] for i in sorted(first)]


def reference_pair(config: dict, pair: dict, tree=None,
                   precision=None) -> dict:
    """The plain reference's registration of one pool pair, from the
    identity, at the configuration's parameters (unweighted)."""
    p = config["params"]
    return reference.icp(pair["src"], pair["dst"], np.eye(4),
                         max_iterations=p["max_iterations"],
                         gate=p["max_correspondence_distance"],
                         eps=p["transformation_epsilon"], huber=None,
                         tree=tree, precision=precision)


def check(config: dict, traffic: dict, picked: list,
          precision=None) -> dict:
    """The numbers compared, for ``harness.judge``, over the pairs the
    reference converges (fewer than ``max_iterations`` iterations, not
    degenerate): the 90th percentile of the widest |T - T_ref| entry,
    and the gap between the mean rmse and the reference's. With
    ``precision`` the lowered reference stands in the program's place
    (the control). No converged pair leaves both numbers missing."""
    from scipy.spatial import cKDTree
    cap = config["params"]["max_iterations"]
    gaps, rmse, rmse_ref = [], [], []
    for got in picked:
        pair = traffic["pool"][got["index"]]
        tree = cKDTree(np.asarray(pair["dst"], np.float64))
        ref = reference_pair(config, pair, tree)
        if ref["iterations"] >= cap or ref["degenerate"]:
            continue
        if precision is not None:
            got = reference_pair(config, pair, tree, precision)
        gaps.append(float(np.abs(np.asarray(got["T"], np.float64)
                                 - ref["T"]).max()))
        rmse.append(float(got["rmse"]))
        rmse_ref.append(ref["rmse"])
    return {
        "pose_gap_p90": float(np.percentile(gaps, 90)) if gaps else None,
        "rmse_gap": (abs(float(np.mean(rmse)) - float(np.mean(rmse_ref)))
                     if gaps else None),
        "pairs_compared": len(picked),
        "converged_compared": len(gaps),
    }
