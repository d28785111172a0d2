"""Scale-out sweep: the sharded registration service over a device mesh.

Weak-scaling shape (DESIGN.md §14): every device owns ``lanes_per_device``
slot lanes AND their resident submaps, so a D-device fleet serves
``D * lanes_per_device`` streams with the SAME per-device program a
single device runs. The sweep measures, per device count:

  * **aggregate fps** — frames completed per second across the whole
    fleet, median of ``repeats`` full runs (the repo's median-of-3
    convention for timed metrics; repeats share the jit cache).
  * **scaling retention** — ``fps(D) / fps(1)``. Read this number for
    what CPU CI can actually measure: the forced host-platform "devices"
    all share ONE physical core, so per-device executions serialise and
    a D-device round does D devices' worth of compute plus D per-device
    dispatches on the same silicon — wall-clock SPEEDUP from D is
    physically impossible here. What the retention ratio bounds is the
    *sharding tax*: how much aggregate throughput survives spreading the
    fleet across D serialised device runtimes (1.0 = free). Near-linear
    scaling in D is a >=D-core/multi-chip claim; on real hardware the
    per-device executions this sweep serialises run concurrently.
  * **strong 8-stream block + sequential baseline** — the §IV-style
    deployment comparison that IS meaningful on one core: the same
    8-stream workload as one fused fleet round (D=1 x 8 lanes and
    D=8 x 1 lane) vs eight eager per-stream pipelines. The fleet round
    amortises per-frame dispatch + host round-trips; this is where the
    >=3x aggregate-throughput headline lives.

Also recorded, because they are acceptance criteria, not vibes:

  * **parity** — a D=max service stream vs a standalone single-device
    (one-lane) pipeline replay: max abs pose diff MUST be exactly 0.0
    (weak-scaling parity at equal block width, see ``ShardedSlotEngine``).
  * **retraces** — engine trace-count delta across join/retire churn at
    D=max; MUST be 0.
  * **submap bytes** — per-resident-submap device bytes, fp32 vs fp16
    layout; the ratio MUST be >= 1.9 (the memory-lean headline).
  * **fp16 drift** — final trajectory drift of a 30-frame fp16 scan-to-
    map stream vs ground truth; MUST stay inside the 0.5 m guard band
    the odometry benchmark enforces for fp32 (plus the fp16-vs-fp32 final
    pose gap, which should be centimetres).

Run it as a MODULE (``python -m benchmarks.device_sweep``): the
``__main__`` guard below forces an 8-device host platform BEFORE jax
initialises. From an already-initialised (1-device) process, use
:func:`run_subprocess`, which respawns this module cleanly — that is what
``benchmarks.run`` and ``benchmarks.check_regression`` do.

Writes BENCH_scaleout.json (committed baseline; ``--quick`` writes
BENCH_scaleout_quick.json so the baseline is never clobbered).
"""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys
import tempfile
import time

_FORCE_FLAG = "--xla_force_host_platform_device_count=8"

if __name__ == "__main__":
    # Must happen before the jax import below — harmless if already set.
    if _FORCE_FLAG not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " " + _FORCE_FLAG)

import jax  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.common import QUICK_SCENE, emit  # noqa: E402
from benchmarks.odometry_drift import ODO_SCENE, ODO_SUBMAP  # noqa: E402
from repro.core import ICPParams  # noqa: E402
from repro.core.odometry import OdometryConfig, OdometryPipeline  # noqa: E402
from repro.data.pointcloud import (SceneConfig, gt_pose,  # noqa: E402
                                   sequence_scans)
from repro.data.submap import SubmapParams, state_bytes  # noqa: E402
from repro.serve.registration_service import (RegistrationService,  # noqa: E402
                                              ServiceConfig)

JSON_PATH = pathlib.Path("BENCH_scaleout.json")
REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent

# Small scene: the service regime is warm-started streaming against a
# compact local submap, where per-frame host overhead (what the service
# amortizes) is comparable to registration compute.
SERVICE_SCENE = SceneConfig(n_ground=800, n_walls=600, n_poles=150,
                            n_clutter=150, extent=15.0, sensor_range=20.0)
QUICK_SERVICE_SCENE = SceneConfig(n_ground=300, n_walls=220, n_poles=60,
                                  n_clutter=70, extent=12.0,
                                  sensor_range=16.0)


def _bench_odometry(iters: int, budget: int) -> OdometryConfig:
    """Streaming-regime odometry config shared by every path: fixed
    iteration count (eps=0), small downsample budget, compact submap."""
    return OdometryConfig(
        engine="xla", engine_kwargs=(),
        params=ICPParams(max_iterations=iters,
                         max_correspondence_distance=1.0,
                         transformation_epsilon=0.0, chunk=512,
                         robust_kernel="huber", robust_scale=0.3),
        submap=SubmapParams(voxel_size=1.5, capacity=512, dims=(32, 32, 12),
                            evict_radius=12.0),
        scan_voxel=1.5, scan_budget=budget, recovery=False)


def _staged_fleet(svc: RegistrationService, n_streams: int, frames: int,
                  scene: SceneConfig):
    """Per-stream staged (padded, valid) frame lists — the bit-identical
    input both the service and the sequential loops consume."""
    fleet = {}
    for s in range(n_streams):
        scans = sequence_scans(s, frames, scene)
        fleet[f"veh{s}"] = [svc.stage_scan(scan) for scan in scans]
    return fleet


def _run_sequential(odo: OdometryConfig, fleet: dict, warm: int,
                    timed: int):
    """The baseline: one standalone per-stream pipeline each, processed
    frame-by-frame in a host loop. Returns per-call times (s)."""
    pipes = {sid: OdometryPipeline(odo) for sid in fleet}
    for f in range(warm):
        for sid, staged in fleet.items():
            pipes[sid].process(*staged[f])
    calls = []
    for f in range(warm, warm + timed):
        for sid, staged in fleet.items():
            t0 = time.perf_counter()
            pipes[sid].process(*staged[f])
            calls.append(time.perf_counter() - t0)
    return calls


def _svc_config(odo, devices: int, lanes: int, max_queue: int,
                storage: str = "fp32") -> ServiceConfig:
    sub = odo.submap._replace(storage=storage)
    return ServiceConfig(slots=devices * lanes, scan_capacity=2048,
                         max_queue=max_queue,
                         odometry=odo._replace(submap=sub), devices=devices)


def _time_fleet(cfg_svc: ServiceConfig, fleet: dict, warm: int,
                timed: int) -> tuple[float, int]:
    """One full fleet run: warm rounds, then ``timed`` timed rounds.
    Returns (aggregate_fps, retraces_after_warmup)."""
    svc = RegistrationService(cfg_svc)
    for sid in fleet:
        svc.admit(sid)
    for f in range(warm):
        for sid, staged in fleet.items():
            svc.submit(sid, *staged[f])
        svc.step()
    svc.sync()
    traces = svc.engine.trace_count
    t0 = time.perf_counter()
    for f in range(warm, warm + timed):
        for sid, staged in fleet.items():
            svc.submit(sid, *staged[f])
        svc.step()
    svc.sync()
    dt = time.perf_counter() - t0
    return len(fleet) * timed / dt, svc.engine.trace_count - traces


def _parity_and_churn(cfg_svc: ServiceConfig, fleet: dict,
                      frames: int) -> tuple[float, int]:
    """D=max parity vs a single-device one-lane standalone replay, then
    join/retire churn on the same warm service. Returns
    (parity_max_abs, churn_retraces)."""
    svc = RegistrationService(cfg_svc)
    sids = list(fleet)
    for sid in sids:
        svc.admit(sid)
    ref_cfg = svc.stream_config._replace(
        engine_kwargs=(("lanes_per_device", 1), ("devices", 1)))
    ref = OdometryPipeline(ref_cfg)
    probe = sids[0]
    worst = 0.0
    for f in range(frames):
        for sid in sids:
            svc.submit(sid, *fleet[sid][f])
        out = svc.step()
        pose_ref, _ = ref.process(*fleet[probe][f])
        worst = max(worst, float(np.abs(np.asarray(out[probe][0]) -
                                        np.asarray(pose_ref)).max()))
    traces = svc.engine.trace_count
    svc.close(sids[-1])                      # retire: in-place lane reset
    svc.admit("churn-join")                  # join a warm fleet
    for f in range(2):
        for sid in (probe, "churn-join"):
            svc.submit(sid, *fleet[sids[-1]][f])
        svc.step()
    return worst, svc.engine.trace_count - traces


def _fp16_drift(frames: int, quick: bool) -> dict:
    """Scan-to-map stream, fp32 vs fp16 resident submap: final drift vs
    ground truth (the odometry benchmark's 0.5 m guard band) and the
    cross-storage final pose gap. Reuses the odometry bench's scene and
    submap sizing so the band means the same thing here."""
    if quick:
        scene = QUICK_SCENE
        sub = SubmapParams(voxel_size=0.75, capacity=4096,
                           dims=(96, 96, 36), evict_radius=30.0)
        cfg = OdometryConfig(
            engine="xla",
            params=ICPParams(max_iterations=10,
                             max_correspondence_distance=1.0,
                             transformation_epsilon=1e-5,
                             robust_kernel="huber", robust_scale=0.3),
            submap=sub, scan_budget=2048)
    else:
        # The odometry benchmark's guarded scan-to-map config exactly
        # (pyramid engine, 30-iteration cap, full scan budget): the
        # 0.5 m band is calibrated against it, so reusing it is what
        # makes "fp16 stays inside the band" mean something.
        scene, sub = ODO_SCENE, ODO_SUBMAP
        base = OdometryConfig(submap=sub, scan_budget=4096)
        cfg = base._replace(engine="pyramid",
                            params=base.params._replace(max_iterations=30))
    scans = sequence_scans(2, frames, scene)
    gt = gt_pose(2)
    out, finals = {}, {}
    for storage in ("fp32", "fp16"):
        pipe = OdometryPipeline(cfg._replace(
            submap=sub._replace(storage=storage)))
        poses, _ = pipe.run(scans)
        finals[storage] = poses[-1]
        out[f"{storage}_drift_final_m"] = float(np.linalg.norm(
            poses[-1][:3, 3] - gt(frames - 1)[:3, 3]))
    out["fp16_vs_fp32_gap_m"] = float(np.linalg.norm(
        finals["fp16"][:3, 3] - finals["fp32"][:3, 3]))
    return out


def run(devices: tuple = (1, 2, 4, 8), lanes_per_device: int = 1,
        frames: int = 12, warm: int = 4, iters: int = 4, budget: int = 128,
        repeats: int = 3, quick: bool = False,
        out_json: str | None = None):
    scene = SERVICE_SCENE
    drift_frames = 12
    if quick:
        devices, frames, warm, iters, repeats = (1, 8), 5, 2, 3, 1
        drift_frames = 5
        scene = QUICK_SERVICE_SCENE
        if out_json is None:
            # never clobber the committed baseline from smoke mode
            out_json = "BENCH_scaleout_quick.json"
    d_max = max(devices)
    if jax.device_count() < d_max:
        raise RuntimeError(
            f"device sweep needs {d_max} devices, found "
            f"{jax.device_count()} — run as 'python -m "
            f"benchmarks.device_sweep' (the __main__ guard forces an "
            f"8-device host platform) or via run_subprocess()")
    odo = _bench_odometry(iters, budget)
    probe = RegistrationService(_svc_config(odo, d_max, lanes_per_device,
                                            warm + frames))
    fleet = _staged_fleet(probe, d_max * lanes_per_device, warm + frames,
                          scene)

    rows, sweep = [], {}
    for d in devices:
        cfg_svc = _svc_config(odo, d, lanes_per_device, warm + frames)
        sub_fleet = dict(list(fleet.items())[:d * lanes_per_device])
        runs = [_time_fleet(cfg_svc, sub_fleet, warm, frames)
                for _ in range(repeats)]
        fps = float(np.median([r[0] for r in runs]))
        retr = max(r[1] for r in runs)
        sweep[d] = {"aggregate_fps": fps, "retraces_after_warmup": retr}
        rows.append((f"scaleout/fleet_d{d}",
                     1e6 / fps * d * lanes_per_device,
                     f"{fps:.1f} frames/s aggregate;"
                     f"{d * lanes_per_device} streams"))

    scaling = sweep[d_max]["aggregate_fps"] / sweep[min(devices)][
        "aggregate_fps"]

    # Strong 8-stream block: the same d_max*L-stream workload fused onto
    # ONE device (d_max*L lanes in one vmap block) plus the eager
    # sequential per-stream baseline. On this serialised host the fused
    # round vs the eager loop is the deployment comparison that can
    # honestly show a >=3x aggregate win.
    n_streams = d_max * lanes_per_device
    cfg_one = _svc_config(odo, 1, n_streams, warm + frames)
    one_runs = [_time_fleet(cfg_one, fleet, warm, frames)
                for _ in range(repeats)]
    one_fps = float(np.median([r[0] for r in one_runs]))
    seq_calls = _run_sequential(odo, fleet, warm, frames)
    seq_fps = len(seq_calls) / sum(seq_calls)
    fused_vs_seq = one_fps / seq_fps

    parity, churn_retraces = _parity_and_churn(
        _svc_config(odo, d_max, lanes_per_device, warm + frames), fleet,
        min(frames, 6))
    retraces = max(churn_retraces, max(r[1] for r in one_runs),
                   max(v["retraces_after_warmup"] for v in sweep.values()))

    b32 = state_bytes(odo.submap)
    b16 = state_bytes(odo.submap._replace(storage="fp16"))
    drift = _fp16_drift(drift_frames, quick)

    summary = {
        "devices": list(devices), "lanes_per_device": lanes_per_device,
        "frames": frames, "warm": warm, "iters": iters,
        "scan_budget": budget, "repeats": repeats,
        "sweep": {str(d): v for d, v in sweep.items()},
        "scaling_x": scaling,
        "scaling_note": "forced host-platform devices share one physical "
                        "core: per-device executions serialise, so "
                        "scaling_x bounds the sharding tax (1.0 = free), "
                        "it cannot show parallel speedup here",
        "strong_8stream": {
            "streams": n_streams,
            "fused_d1_fps": one_fps,
            f"sharded_d{d_max}_fps": sweep[d_max]["aggregate_fps"],
            "sequential_fps": seq_fps,
        },
        "fused_vs_sequential_x": fused_vs_seq,
        "parity_max_abs": parity,
        "retraces_after_warmup": retraces,
        "bytes_per_submap_fp32": b32, "bytes_per_submap_fp16": b16,
        "submap_bytes_ratio": b32 / b16,
        "submaps_per_gib_fp16": int(2**30 / b16),
        "drift_frames": drift_frames, **drift,
    }
    path = JSON_PATH if out_json is None else pathlib.Path(out_json)
    path.write_text(json.dumps(summary, indent=2))

    rows += [
        (f"scaleout/scaling_d{d_max}_vs_d{min(devices)}", 0.0,
         f"{scaling:.2f}x aggregate fps retained (weak scaling, "
         f"{lanes_per_device} lane/device, serialised host devices)"),
        (f"scaleout/fused_d1_s{n_streams}", 1e6 / one_fps * n_streams,
         f"{one_fps:.1f} frames/s aggregate;one fused device round"),
        (f"scaleout/sequential_s{n_streams}", 1e6 / seq_fps * n_streams,
         f"{seq_fps:.1f} frames/s;eager per-stream loop"),
        (f"scaleout/fused_vs_sequential_s{n_streams}", 0.0,
         f"{fused_vs_seq:.2f}x aggregate fps (the fleet-batching win)"),
        ("scaleout/parity_max_abs", 0.0,
         f"{parity:.1e} vs single-device pipeline (must be 0.0)"),
        ("scaleout/retraces_after_warmup", 0.0,
         f"{retraces} across churn (must be 0)"),
        ("scaleout/submap_bytes", 0.0,
         f"fp32={b32}B fp16={b16}B ratio={b32 / b16:.2f}x "
         f"(must be >=1.9x)"),
        ("scaleout/fp16_drift_final", 0.0,
         f"{drift['fp16_drift_final_m']:.3f}m over {drift_frames} frames "
         f"(guard band 0.5m); fp16-vs-fp32 gap "
         f"{drift['fp16_vs_fp32_gap_m']:.3f}m"),
    ]
    assert parity == 0.0, f"sharded parity broke: {parity}"
    assert retraces == 0, f"sharded service retraced: {retraces}"
    assert b32 / b16 >= 1.9, f"fp16 layout only {b32 / b16:.2f}x leaner"
    assert drift["fp16_drift_final_m"] <= 0.5, \
        f"fp16 drift {drift['fp16_drift_final_m']:.3f}m outside guard band"
    if not quick:
        # One core: D devices' rounds serialise, so the honest floors are
        # a bounded sharding tax and the fused-round throughput win over
        # the eager loop (the >=3x aggregate headline lives in the fused
        # round).
        assert scaling >= 0.4, \
            f"sharding tax too high: only {scaling:.2f}x retained at " \
            f"D={d_max} on a serialised host"
        assert fused_vs_seq >= 2.0, \
            f"fused fleet round only {fused_vs_seq:.2f}x the eager loop"
    return rows


def run_subprocess(quick: bool = False, timeout: int = 1800,
                   **kwargs) -> dict:
    """Run the sweep in a fresh interpreter (which self-forces the
    8-device host platform) and return the summary dict. This is the
    only way to run it from a process whose jax already initialised with
    1 device. ``kwargs`` forward to :func:`run` via --config."""
    with tempfile.NamedTemporaryFile(suffix=".json", delete=False) as tmp:
        out = tmp.name
    cmd = [sys.executable, "-m", "benchmarks.device_sweep", "--json", out]
    if quick:
        cmd.append("--quick")
    if kwargs:
        cmd += ["--config", json.dumps(kwargs)]
    # The sweep models forced host devices: keep the child off the chip.
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": os.pathsep.join(
               p for p in (str(REPO_ROOT / "src"),
                           os.environ.get("PYTHONPATH")) if p)}
    proc = subprocess.run(cmd, capture_output=True, text=True,
                          timeout=timeout, env=env, cwd=str(REPO_ROOT))
    if proc.returncode != 0:
        raise RuntimeError(f"device sweep subprocess failed:\n"
                           f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}")
    summary = json.loads(pathlib.Path(out).read_text())
    os.unlink(out)
    return summary


def run_harness(quick: bool = False):
    """benchmarks.run entry point: subprocess the sweep (the harness
    parent is a 1-device interpreter) and re-emit its headline rows."""
    s = run_subprocess(quick=quick)
    d_lo, d_hi = min(s["devices"]), max(s["devices"])
    return [
        (f"scaleout/fleet_d{d}",
         1e6 / s["sweep"][str(d)]["aggregate_fps"] * d * s[
             "lanes_per_device"],
         f"{s['sweep'][str(d)]['aggregate_fps']:.1f} frames/s aggregate")
        for d in s["devices"]
    ] + [
        (f"scaleout/scaling_d{d_hi}_vs_d{d_lo}", 0.0,
         f"{s['scaling_x']:.2f}x aggregate fps retained"),
        ("scaleout/fused_vs_sequential", 0.0,
         f"{s['fused_vs_sequential_x']:.2f}x aggregate fps"),
        ("scaleout/parity_max_abs", 0.0, f"{s['parity_max_abs']:.1e}"),
        ("scaleout/submap_bytes", 0.0,
         f"ratio={s['submap_bytes_ratio']:.2f}x"),
        ("scaleout/fp16_drift_final", 0.0,
         f"{s['fp16_drift_final_m']:.3f}m"),
    ]


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--json", default=None,
                    help="summary output path (default BENCH_scaleout.json)")
    ap.add_argument("--config", default=None,
                    help="JSON dict of run() kwargs (subprocess plumbing)")
    args = ap.parse_args()
    kw = json.loads(args.config) if args.config else {}
    if "devices" in kw:
        kw["devices"] = tuple(kw["devices"])
    emit(run(quick=args.quick, out_json=args.json, **kw))
