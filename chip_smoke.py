"""Smoke run of the registration stack on a TPU, through its public entry
points, at the size of the paper's workload.

    python chip_smoke.py               # one chip: paper protocol + fleet
    python chip_smoke.py --four-chips  # four chips: sharded fleet only

Phases (one process; the chip belongs to it alone):

  * paper protocol (§IV-A): 4 frame pairs of the full ~1.3e5-point scene,
    4096 sampled source points against the full target, 50 iterations,
    1.0 m gate, 1e-5 epsilon, through ``get_engine("pallas")`` with the
    brute-force NN kernel and with the fused iteration kernel. Poses must
    agree with the ``xla`` engine, mean RMSE with the k-d tree baseline,
    and the executables must hold compiled kernels (``tpu_custom_call``).
  * fleet service: 8 streams of full-size scans through
    ``RegistrationService`` for 7 rounds at the ``OdometryConfig``
    defaults: no retrace after round 2, finite poses, and stream ``veh0``
    bit-identical to a standalone ``OdometryPipeline`` replay.
  * ``--four-chips``: the sharded fleet (``devices=4``) against the
    single-device fleet, its last device block bit-identical to a
    one-device fleet of the same lane width, one shard per device, and
    no collectives in the compiled round.

The times printed are those of a smoke run (compilation included), not
benchmark numbers. The last line of standard output is a JSON object
with ``"ok": true`` and the device; it is printed only when every phase
passed on a TPU, and the exit code is non-zero otherwise.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time
import traceback

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core import ICPParams, get_engine  # noqa: E402
from repro.core.baseline import kdtree_icp  # noqa: E402
from repro.core.odometry import OdometryConfig, OdometryPipeline  # noqa: E402
from repro.data.pointcloud import (SceneConfig,  # noqa: E402
                                   frame_pair_from_world, gt_pose,
                                   make_world, sequence_scans)
from repro.serve.registration_service import (  # noqa: E402
    RegistrationService, ServiceConfig)

PAPER_PARAMS = ICPParams(max_iterations=50, max_correspondence_distance=1.0,
                         transformation_epsilon=1e-5)
POSE_TOL = 1e-4         # max |dT| between engines
RMSE_TOL = 0.01         # the paper's accuracy criterion against PCL
CROSS_WIDTH_TOL = 1e-4  # sharded vs single-device fleet (different widths)
COLLECTIVES = ("all-reduce", "all-gather", "all-to-all", "reduce-scatter",
               "collective-permute")


class SmokeFailure(Exception):
    """A check of the smoke run did not hold."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    print(f"  check ok: {what}", flush=True)


def _batch_hlo(engine, params, batch) -> str:
    """Optimised HLO of the engine's batch executable for ``batch``."""
    fn = engine._executable("batch", params)
    return fn.lower(batch.src, batch.dst, None, batch.src_valid,
                    batch.dst_valid).compile().as_text()


def paper_protocol(cfg: SceneConfig = SceneConfig(), pairs: int = 4,
                   samples: int = 4096, seq: int = 0,
                   params: ICPParams = PAPER_PARAMS) -> None:
    """§IV-A frame pairs through the Pallas engine, unfused and fused."""
    world = make_world(seq, cfg)
    tasks = [frame_pair_from_world(world, seq, f, cfg, samples)
             for f in range(pairs)]
    frames = [(s, d) for s, d, _ in tasks]
    print(f"paper protocol: {pairs} pairs, {samples} source points, "
          f"targets of {min(d.shape[0] for _, d in frames)}-"
          f"{max(d.shape[0] for _, d in frames)} points", flush=True)
    t0 = time.perf_counter()
    base = [kdtree_icp(s, d) for s, d in frames]
    base_rmse = float(np.mean([b.rmse for b in base]))
    print(f"  kdtree baseline (host): mean rmse {base_rmse:.5f} "
          f"in {time.perf_counter() - t0:.2f} s", flush=True)
    t0 = time.perf_counter()
    ref, _ = get_engine("xla").register_pairs(frames, params)
    ref_T = np.asarray(ref.T)
    print(f"  xla engine: mean rmse {float(np.mean(ref.rmse)):.5f} in "
          f"{time.perf_counter() - t0:.2f} s (compile included)", flush=True)
    for label, p in (("pallas", params),
                     ("pallas fused", params._replace(fused=True))):
        engine = get_engine("pallas")
        t0 = time.perf_counter()
        res, batch = engine.register_pairs(frames, p)
        T = np.asarray(res.T)
        dt = time.perf_counter() - t0
        rmse = float(np.mean(res.rmse))
        d_pose = float(np.abs(T - ref_T).max())
        print(f"  {label}: mean rmse {rmse:.5f}, iterations "
              f"{np.asarray(res.iterations).tolist()}, max|dT| vs xla "
              f"{d_pose:.3e}, {dt:.2f} s (compile included)", flush=True)
        check(bool(np.all(np.isfinite(T))), f"{label}: poses finite")
        check(d_pose <= POSE_TOL,
              f"{label}: max|dT| vs xla {d_pose:.3e} <= {POSE_TOL}")
        check(abs(rmse - base_rmse) <= RMSE_TOL,
              f"{label}: |mean rmse - kdtree| {abs(rmse - base_rmse):.5f} "
              f"<= {RMSE_TOL}")
        check("tpu_custom_call" in _batch_hlo(engine, p, batch),
              f"{label}: executable runs the compiled kernel")


def _fleet(streams: int, frames: int, cfg: SceneConfig) -> dict:
    return {f"veh{s}": sequence_scans(s, frames, cfg)
            for s in range(streams)}


def _drive(svc: RegistrationService, fleet: dict, rounds: int,
           on_round=None) -> dict:
    """Submit one frame per stream per round; ``{sid: [(pose, diag)]}``."""
    out = {sid: [] for sid in fleet}
    for r in range(rounds):
        t0 = time.perf_counter()
        for sid, scans in fleet.items():
            svc.submit(sid, scans[r])
        for sid, res in svc.step().items():
            out[sid].append(res)
        svc.sync()
        if on_round is not None:
            on_round(r + 1, time.perf_counter() - t0)
    return out


def _capacity(fleet: dict) -> int:
    return max(sc.shape[0] for scans in fleet.values() for sc in scans)


def fleet_service(cfg: SceneConfig = SceneConfig(), streams: int = 8,
                  rounds: int = 7,
                  odometry: OdometryConfig = OdometryConfig()) -> None:
    """The served path: one ``RegistrationService`` round per frame wave."""
    fleet = _fleet(streams, rounds, cfg)
    svc = RegistrationService(ServiceConfig(
        slots=streams, scan_capacity=_capacity(fleet), odometry=odometry))
    for sid in fleet:
        svc.admit(sid)
    print(f"fleet service: {streams} streams x {rounds} rounds, scans of "
          f"{min(s.shape[0] for v in fleet.values() for s in v)}-"
          f"{_capacity(fleet)} points, scan_budget {odometry.scan_budget}, "
          f"submap {odometry.submap.capacity} rows", flush=True)
    traces = {}

    def on_round(r, seconds):
        traces[r] = svc.engine.trace_count
        print(f"  round {r}: {seconds:.3f} s, trace_count {traces[r]}",
              flush=True)

    out = _drive(svc, fleet, rounds, on_round)
    check(all(traces[r] == traces[2] for r in traces if r >= 2),
          f"no retrace after round 2 ({traces})")
    poses = np.stack([np.asarray(p) for v in out.values() for p, _ in v])
    check(bool(np.all(np.isfinite(poses))), "every pose finite")
    for s, sid in enumerate(fleet):
        pose = np.asarray(out[sid][-1][0])
        drift = float(np.linalg.norm(pose[:3, 3]
                                     - gt_pose(s)(rounds - 1)[:3, 3]))
        rep = svc.report(sid)
        print(f"  {sid}: drift {drift:.4f} m after {rounds - 1} frames, "
              f"health {rep.health_counts}, quarantined "
              f"{rep.frames_quarantined}, escapes {rep.cascade_escapes}",
              flush=True)
    ref = OdometryPipeline(svc.stream_config)
    for f, scan in enumerate(fleet["veh0"]):
        pose_ref, diag_ref = ref.process(*svc.stage_scan(scan))
        pose_svc, diag_svc = out["veh0"][f]
        check(np.array_equal(np.asarray(pose_svc), np.asarray(pose_ref))
              and diag_svc == diag_ref,
              f"veh0 frame {f}: bit-identical to the standalone pipeline")


def _round_hlo(svc: RegistrationService) -> str:
    """Optimised HLO of the sharded fleet's registration executable."""
    odo = svc.stream_config
    S = svc.config.slots
    sh = svc.engine.sharding()

    def spec(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    n, m = odo.scan_budget, odo.submap.capacity
    fn = svc.engine._executable("batch", odo.params)
    return fn.lower(spec(S, n, 3), spec(S, m, 3), spec(S, 4, 4),
                    spec(S, n, dtype=bool),
                    spec(S, m, dtype=bool)).compile().as_text()


def four_chip_fleet(cfg: SceneConfig = SceneConfig(), devices: int = 4,
                    slots: int = 8, rounds: int = 7,
                    odometry: OdometryConfig = OdometryConfig()) -> None:
    """The sharded fleet (``ServiceConfig(devices=D)``) on ``devices``."""
    check(jax.device_count() >= devices,
          f"{jax.device_count()} devices >= {devices}")
    fleet = _fleet(slots, rounds, cfg)
    cap = _capacity(fleet)
    lanes = slots // devices

    def service(d, n_slots, streams):
        svc = RegistrationService(ServiceConfig(
            slots=n_slots, scan_capacity=cap, odometry=odometry, devices=d))
        for sid in streams:
            svc.admit(sid)
        t0 = time.perf_counter()
        out = _drive(svc, {sid: fleet[sid] for sid in streams}, rounds)
        print(f"  devices={d} slots={n_slots}: {rounds} rounds in "
              f"{time.perf_counter() - t0:.2f} s (compile included)",
              flush=True)
        return svc, out

    print(f"four-chip fleet: {slots} streams over {devices} devices "
          f"({lanes} lanes each) x {rounds} rounds", flush=True)
    svc_d, out_d = service(devices, slots, list(fleet))
    _, out_1 = service(None, slots, list(fleet))
    worst = 0.0
    for sid in fleet:
        for (p_d, g_d), (p_1, g_1) in zip(out_d[sid], out_1[sid]):
            worst = max(worst, float(np.abs(np.asarray(p_d)
                                            - np.asarray(p_1)).max()))
            check((g_d.accepted, g_d.health, g_d.quarantined)
                  == (g_1.accepted, g_1.health, g_1.quarantined),
                  f"{sid} frame {g_d.frame}: same verdict as one device")
    check(worst <= CROSS_WIDTH_TOL,
          f"sharded vs single-device poses: max|dT| {worst:.3e} <= "
          f"{CROSS_WIDTH_TOL}")
    last = [sid for sid in fleet
            if svc_d._streams[sid].slot // lanes == devices - 1]
    _, out_l = service(1, lanes, last)
    for sid in last:
        same = all(np.array_equal(np.asarray(a[0]), np.asarray(b[0]))
                   and a[1] == b[1] for a, b in zip(out_d[sid], out_l[sid]))
        check(same, f"{sid} (last device block): bit-identical to a "
                    f"one-device fleet of {lanes} lanes")
    for leaf in svc_d._fleet:
        devs = {s.device for s in leaf.addressable_shards}
        check(len(leaf.addressable_shards) == devices
              and len(devs) == devices,
              f"fleet array {leaf.shape}: one shard on each of {devices} "
              f"devices")
    hlo = _round_hlo(svc_d)
    found = [op for op in COLLECTIVES if op in hlo]
    check(not found, f"compiled fleet round has no collectives {found}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded fleet on four chips")
    args = ap.parse_args(argv)
    enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX sees {dev.platform}); nothing run",
              file=sys.stderr)
        return 1
    print(f"smoke run on {jax.device_count()} x {dev.device_kind} "
          f"(times include compilation; not benchmark numbers)", flush=True)
    phases = ([four_chip_fleet] if args.four_chips
              else [paper_protocol, fleet_service])
    failed = []
    for phase in phases:
        t0 = time.perf_counter()
        try:
            phase()
        except Exception:  # report every phase, fail at the end
            traceback.print_exc()
            failed.append(phase.__name__)
        print(f"phase {phase.__name__}: "
              f"{'FAILED' if phase.__name__ in failed else 'passed'} in "
              f"{time.perf_counter() - t0:.1f} s", flush=True)
    if failed:
        print(f"chip_smoke: failed phases {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
