"""Point-cloud registration driver — the paper's application, end to end.

    PYTHONPATH=src python -m repro.launch.registration --seq 0 --frames 5
    PYTHONPATH=src python -m repro.launch.registration --mode scan_to_map

``--mode pairwise`` (default) replicates the FPPS evaluation protocol
(§IV-A): per frame, 4096 points sampled from the source cloud, full target
cloud as the NN space, max 50 iterations, 1.0 m gate, 1e-5 epsilon;
reports RMSE + latency for our engine and the k-d tree CPU baseline. The
whole sequence runs through the unified engine layer as ONE batched
registration (``RegistrationEngine.register_pairs``): frames are collated
into shape buckets and registered by a single compiled executable, so
per-frame numbers below share one compile. ``--per-frame`` falls back to
the looped Table-I API path for comparison.

``--mode scan_to_map`` runs the streaming odometry pipeline
(``repro.core.odometry``): rolling submap target, constant-velocity warm
starts, per-frame diagnostics — the production stream shape of the
paper's KITTI workload.

``--mode serve`` runs a scripted *fleet*: ``--streams`` concurrent
odometry streams multiplexed through the multi-stream registration
service (``repro.serve.registration_service``, DESIGN.md §13) — every
frame wave is one compiled fleet round, and the summary reports
per-stream drift/health plus aggregate frames/s and the engine trace
count (constant after warmup). ``--faults`` in this mode degrades only
the first stream, demonstrating that one sick vehicle quarantines
without touching its peers (the default fleet already includes the
fast-highway outlier seq 1 as a natural degraded stream):

    PYTHONPATH=src python -m repro.launch.registration \\
        --mode serve --streams 4 --frames 6
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import FppsICP, ICPParams, get_engine
from repro.core.baseline import kdtree_icp
from repro.data.pointcloud import (SceneConfig, frame_pair_from_world,
                                   gt_pose, make_world, sequence_scans)


def run_scan_to_map(args, cfg, params):
    """Streaming scan-to-map odometry over a resampled scan stream."""
    from repro.core.odometry import OdometryConfig, OdometryPipeline
    from repro.data.corruption import apply_faults, parse_fault_spec

    faults = parse_fault_spec(args.faults) if args.faults else None
    scans = sequence_scans(args.seq, args.frames + 1, cfg)
    pipe = OdometryPipeline(OdometryConfig(
        engine=args.engine, params=params._replace(max_iterations=30)))
    gt = gt_pose(args.seq)
    pipe.process(scans[0])           # frame 0 initialises the map, clean
    rows = []
    for frame in range(1, args.frames + 1):
        scan, valid = scans[frame], None
        if faults is not None:
            scan, valid = apply_faults(scan, faults, seed=args.fault_seed,
                                       frame=frame)
        t0 = time.time()
        pose, diag = pipe.process(scan, valid=valid)
        t_frame = time.time() - t0
        drift = float(np.linalg.norm(pose[:3, 3] - gt(frame)[:3, 3]))
        rows.append((frame, diag.iterations, diag.inlier_frac, t_frame, drift))
        flags = diag.health + (" tier %d" % diag.recovery_tier
                               if diag.recovery_tier else "")
        if diag.quarantined:
            flags += " quarantined"
        print(f"frame {frame}: iters {diag.iterations:2d} "
              f"inliers {diag.inlier_frac:.2f} "
              f"map occ {diag.map_occupancy:.2f} | t {t_frame * 1e3:7.1f}ms | "
              f"drift {drift:.3f} m | {flags}")
    steady = [r[3] for r in rows[2:]] or [rows[-1][3]]
    health = pipe.health_counts()
    tiers = pipe.tier_counts()
    print(f"\nscan_to_map engine={args.engine}: {args.frames} frames, "
          f"steady-state {np.mean(steady) * 1e3:.1f} ms/frame "
          f"({1.0 / np.mean(steady):.2f} frames/s), "
          f"final drift {rows[-1][4]:.3f} m, "
          f"rejected {pipe.rejected_frames()}")
    print(f"health ok/suspect/failed: {health['ok']}/{health['suspect']}/"
          f"{health['failed']} | tiers "
          + " ".join(f"{t}:{n}" for t, n in sorted(tiers.items()))
          + f" | recovered {pipe.recovery_count}"
          f" quarantined {pipe.quarantined_count}"
          + (f" | faults '{args.faults}'" if faults is not None else ""))
    return rows


def run_serve(args, cfg, params):
    """Scripted fleet through the multi-stream registration service:
    one compiled round per frame wave, per-stream verdicts host-side."""
    from repro.core.odometry import OdometryConfig
    from repro.data.corruption import apply_faults, parse_fault_spec
    from repro.data.submap import SubmapParams
    from repro.serve.registration_service import (RegistrationService,
                                                  ServiceConfig)

    faults = parse_fault_spec(args.faults) if args.faults else None
    # Vehicles scan distinct worlds (``--seq + s``) at each sequence's own
    # ground-truth speed, so the fleet mixes easy urban streams with the
    # 2.5 m/frame highway outlier (seq 1) whose cold start outruns the 1 m
    # gate — the demo's point is that its SUSPECT verdicts stay confined
    # to that stream. Full size is the paper's scene with the
    # ``OdometryConfig`` submap defaults; ``--reduced`` shrinks both, as
    # the round multiplies every shape by ``--streams``.
    odo = OdometryConfig(params=params._replace(max_iterations=30))
    if args.reduced:
        cfg = SceneConfig(n_ground=2500, n_walls=1800, n_poles=450,
                          n_clutter=450, extent=25.0, sensor_range=30.0)
        odo = odo._replace(
            submap=SubmapParams(voxel_size=0.75, capacity=8192,
                                dims=(96, 96, 24), evict_radius=25.0),
            scan_budget=4096)
    fleet = {}
    for s in range(args.streams):
        scans = sequence_scans(args.seq + s, args.frames + 1, cfg)
        frames = [(scans[0], None)]      # frame 0 seeds the map, clean
        for f, scan in enumerate(scans[1:], start=1):
            if faults is not None and s == 0:
                # degrade ONLY the first stream: the service story is
                # that its quarantine never leaks into the peers
                frames.append(apply_faults(scan, faults,
                                           seed=args.fault_seed, frame=f))
            else:
                frames.append((scan, None))
        fleet[f"veh{s}"] = frames

    cap = max(sc.shape[0] for frames in fleet.values() for sc, _ in frames)
    svc = RegistrationService(ServiceConfig(
        slots=args.streams, scan_capacity=cap, odometry=odo))
    for sid in fleet:
        svc.admit(sid)

    times, last = [], {}
    for f in range(args.frames + 1):
        t0 = time.time()
        for sid, frames in fleet.items():
            svc.submit(sid, *frames[f])
        last.update(svc.step())
        svc.sync()
        times.append(time.time() - t0)

    gts = {f"veh{s}": gt_pose(args.seq + s) for s in range(args.streams)}
    reports = []
    for sid in fleet:
        rep = svc.report(sid)
        pose, _ = last[sid]
        drift = float(np.linalg.norm(pose[:3, 3]
                                     - gts[sid](args.frames)[:3, 3]))
        hc = rep.health_counts
        reports.append(rep)
        print(f"{sid}: drift {drift:.3f} m | health ok/suspect/failed "
              f"{hc['ok']}/{hc['suspect']}/{hc['failed']} | "
              f"quarantined {rep.frames_quarantined} "
              f"dropped {rep.frames_dropped} "
              f"escapes {rep.cascade_escapes}")
    steady = times[2:] or times          # first rounds pay compilation
    sr = svc.service_report()
    print(f"\nserve: {args.streams} streams x {args.frames} frames, "
          f"steady-state {np.mean(steady) * 1e3:.1f} ms/round "
          f"({args.streams / np.mean(steady):.1f} frames/s aggregate) | "
          f"rounds {sr['rounds']} traces {sr['trace_count']} "
          f"dropped {sr['frames_dropped']}"
          + (f" | faults '{args.faults}' on veh0" if faults is not None
             else ""))
    return reports


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=0)
    ap.add_argument("--frames", type=int, default=5)
    ap.add_argument("--samples", type=int, default=4096)
    ap.add_argument("--engine", default="xla",
                    choices=["xla", "pallas", "distributed", "pyramid"])
    ap.add_argument("--minimizer", default="point_to_point",
                    choices=["point_to_point", "point_to_plane"],
                    help="error metric: paper's point-to-point Kabsch or "
                         "the plane-aware Gauss-Newton step (DESIGN.md §9)")
    ap.add_argument("--robust", default=None,
                    choices=["none", "huber", "tukey"],
                    help="IRLS robust reweighting on top of the gate "
                         "(default: none for pairwise, huber for "
                         "scan_to_map — DESIGN.md §10)")
    ap.add_argument("--robust-scale", type=float, default=None,
                    help="robust kernel scale in metres (default: 0.5 "
                         "pairwise, 0.3 scan_to_map)")
    ap.add_argument("--mode", default="pairwise",
                    choices=["pairwise", "scan_to_map", "serve"],
                    help="pairwise: batched frame-pair protocol (§IV-A); "
                         "scan_to_map: streaming odometry pipeline; "
                         "serve: --streams concurrent streams through the "
                         "multi-stream registration service (always on "
                         "the slot engine; --engine is ignored)")
    ap.add_argument("--streams", type=int, default=4,
                    help="serve mode: fleet width (= service slots)")
    ap.add_argument("--fused", action="store_true",
                    help="single-pass fused iteration kernel "
                         "(ICPParams.fused, DESIGN.md §11)")
    ap.add_argument("--faults", default=None,
                    help="scan_to_map only: comma-separated fault spec "
                         "injected into every streamed frame, e.g. "
                         "'dropout:0.3,occlusion:90deg,nan:10' "
                         "(repro.data.corruption)")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed for the deterministic fault injectors")
    ap.add_argument("--per-frame", action="store_true",
                    help="loop FppsICP.align() per frame instead of one batch")
    ap.add_argument("--reduced", action="store_true",
                    help="smaller synthetic scenes (fast CI)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = (SceneConfig(n_ground=9000, n_walls=6000, n_poles=1800,
                       n_clutter=1700, extent=40.0, sensor_range=45.0)
           if args.reduced else SceneConfig())
    # Per-mode defaults, overridden only by an *explicit* flag: huber
    # bounds the map-frontier pull in the streaming regime (DESIGN.md
    # §10), while the pairwise protocol (§IV-A) stays unweighted.
    streaming = args.mode in ("scan_to_map", "serve")
    robust = args.robust if args.robust is not None else (
        "huber" if streaming else "none")
    robust_scale = args.robust_scale if args.robust_scale is not None else (
        0.3 if streaming else 0.5)
    params = ICPParams(max_iterations=50, max_correspondence_distance=1.0,
                       transformation_epsilon=1e-5,
                       minimizer=args.minimizer, robust_kernel=robust,
                       robust_scale=robust_scale, fused=args.fused)

    if args.mode == "serve":
        return run_serve(args, cfg, params)
    if args.mode == "scan_to_map":
        return run_scan_to_map(args, cfg, params)

    world = make_world(args.seq, cfg)  # built once for the whole sequence
    pairs = [frame_pair_from_world(world, args.seq, f, cfg, args.samples)
             for f in range(args.frames)]

    if args.per_frame:
        reg = FppsICP(engine=args.engine)  # one adapter: caches persist
        Ts, rmses = [], []
        t0 = time.time()
        for src, dst, _ in pairs:
            reg.setInputSource(src)
            reg.setInputTarget(dst)
            reg.setMaxCorrespondenceDistance(1.0)
            reg.setMaxIterationCount(50)
            reg.setTransformationEpsilon(1e-5)
            reg.setMinimizer(args.minimizer)
            reg.setRobustKernel(robust, robust_scale)
            Ts.append(reg.align())
            rmses.append(reg.getFitnessScore())
        t_ours = time.time() - t0
    else:
        engine = get_engine(args.engine)
        t0 = time.time()
        res, _batch = engine.register_pairs([(s, d) for s, d, _ in pairs],
                                            params)
        import jax
        jax.block_until_ready(res.T)
        t_ours = time.time() - t0
        Ts = [np.asarray(res.T[i]) for i in range(args.frames)]
        rmses = [float(res.rmse[i]) for i in range(args.frames)]

    rows = []
    t_base_total = 0.0
    for frame, (src, dst, T_gt) in enumerate(pairs):
        t0 = time.time()
        base = kdtree_icp(src, dst)
        t_base = time.time() - t0
        t_base_total += t_base
        t_err = float(np.linalg.norm(Ts[frame][:3, 3] - T_gt[:3, 3]))
        rows.append((frame, rmses[frame], base.rmse, t_ours / args.frames,
                     t_base, t_err))
        print(f"frame {frame}: rmse ours={rows[-1][1]:.4f} "
              f"kdtree={rows[-1][2]:.4f} | t ours={t_ours/args.frames*1e3:7.1f}ms "
              f"kdtree={t_base*1e3:7.1f}ms | trans err {t_err:.3f} m")
    d = np.array([[r[1], r[2]] for r in rows])
    mode = "per-frame loop" if args.per_frame else "batched"
    print(f"\nmean RMSE ours={d[:,0].mean():.4f} kdtree={d[:,1].mean():.4f} "
          f"delta={abs(d[:,0].mean()-d[:,1].mean()):.4f} (paper: <=0.01)")
    print(f"{mode} engine={args.engine}: {args.frames} frames in {t_ours:.2f}s "
          f"({args.frames/t_ours:.2f} frames/s) vs kdtree {t_base_total:.2f}s")
    return rows


if __name__ == "__main__":
    main()
