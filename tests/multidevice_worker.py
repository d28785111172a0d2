"""Subprocess worker: the device-sharded registration service on an
8-device host-platform fleet (DESIGN.md §14).

Run via tests/test_multidevice.py — NOT imported by pytest directly (it
must set XLA_FLAGS before jax initialises, which would poison the main
process). Exits non-zero on any mismatch; prints MULTIDEVICE-OK last.
"""
import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.compat import make_mesh  # noqa: E402
from repro.core import ICPParams, get_engine, icp_fixed_iterations  # noqa: E402
from repro.core.distributed import (batched_icp_sharded,  # noqa: E402
                                    shard_inputs, stream_sharded_icp,
                                    streams_mesh)
from repro.core.odometry import OdometryConfig, OdometryPipeline  # noqa: E402
from repro.core.transform import (random_rigid_transform,  # noqa: E402
                                  transform_points)
from repro.data.pointcloud import SceneConfig, sequence_scans  # noqa: E402
from repro.data.submap import SubmapParams  # noqa: E402
from repro.serve.registration_service import (RegistrationService,  # noqa: E402
                                              ServiceConfig)

SCENE = SceneConfig(n_ground=300, n_walls=220, n_poles=60, n_clutter=70,
                    extent=12.0, sensor_range=16.0)
ODO = OdometryConfig(
    params=ICPParams(max_iterations=6, max_correspondence_distance=1.0,
                     chunk=512, robust_kernel="huber", robust_scale=0.3),
    submap=SubmapParams(voxel_size=0.75, capacity=1024, dims=(48, 48, 16),
                        evict_radius=12.0),
    scan_budget=256, recovery=False)
SLOTS = 8


def _drive(svc, fleet):
    out = {sid: [] for sid in fleet}
    frames = max(len(f) for f in fleet.values())
    for f in range(frames):
        for sid, scans in fleet.items():
            if f < len(scans):
                svc.submit(sid, scans[f])
        for sid, res in svc.step().items():
            out[sid].append(res)
    return out


def main():
    assert jax.device_count() == 8, jax.devices()
    fleet = {f"veh{s}": sequence_scans(s, 5, SCENE) for s in range(6)}

    # --- D=8 service == single-device reference, bit for bit -------------
    # Weak-scaling parity is per BLOCK WIDTH (lanes_per_device): a D=8,
    # L=1 lane runs the same (1, ...)-shaped program as a D=1, L=1
    # single-stream pipeline, so per-stream poses AND diagnostics are
    # bit-identical to that single-device reference.
    svc8 = RegistrationService(ServiceConfig(
        slots=SLOTS, scan_capacity=1024, odometry=ODO, devices=8))
    for sid in fleet:
        svc8.admit(sid)
    out8 = _drive(svc8, fleet)
    ref_cfg = svc8.stream_config._replace(
        engine_kwargs=(("lanes_per_device", 1), ("devices", 1)))
    for sid, scans in fleet.items():
        assert len(out8[sid]) == len(scans)
        ref = OdometryPipeline(ref_cfg)
        for f, sc in enumerate(scans):
            pose_ref, diag_ref = ref.process(*svc8.stage_scan(sc))
            np.testing.assert_array_equal(np.asarray(out8[sid][f][0]),
                                          np.asarray(pose_ref))
            assert out8[sid][f][1] == diag_ref, (sid, f)
    rep = svc8.service_report()
    assert rep["devices"] == 8 and rep["frames_processed"] == 30
    print("sharded service D=8 == single-device reference OK")

    # --- D=8 vs a D=1 8-lane service: fp-tolerance agreement --------------
    # Different block widths (L=1 vs L=8) tile the per-lane point-axis
    # reductions differently on CPU, so across WIDTHS agreement is fp-
    # tolerance, not bitwise (the docs state exactly this caveat).
    svc1 = RegistrationService(ServiceConfig(
        slots=SLOTS, scan_capacity=1024, odometry=ODO, devices=1))
    for sid in fleet:
        svc1.admit(sid)
    out1 = _drive(svc1, fleet)
    for sid in fleet:
        for (p1, d1), (p8, d8) in zip(out1[sid], out8[sid]):
            np.testing.assert_allclose(np.asarray(p8), np.asarray(p1),
                                       atol=1e-4)
            assert (d8.accepted, d8.health, d8.quarantined) == \
                   (d1.accepted, d1.health, d1.quarantined)
    print("sharded service D=8 ~= D=1 (cross-width) OK")

    # --- mesh-aware placement spreads streams across device blocks -------
    # 6 streams over 8 devices x 1 lane: every stream gets its own block
    slots = sorted(svc8._streams[sid].slot for sid in fleet)
    assert len(set(slots)) == len(fleet), slots
    print("mesh-aware placement OK")

    # --- churn at D=8: lane reset + join never retrace --------------------
    traces = svc8.engine.trace_count
    svc8.close("veh0")
    svc8.admit("late")
    late = sequence_scans(9, 3, SCENE)
    got = _drive(svc8, {"late": late})
    assert len(got["late"]) == 3
    assert svc8.engine.trace_count == traces
    # the recycled lane replays a fresh standalone pipeline bit-for-bit
    ref = OdometryPipeline(svc8.stream_config)
    for f, sc in enumerate(late):
        pose_ref, diag_ref = ref.process(*svc8.stage_scan(sc))
        np.testing.assert_array_equal(np.asarray(got["late"][f][0]),
                                      np.asarray(pose_ref))
        assert got["late"][f][1] == diag_ref
    print("D=8 churn retrace-free + lane reset OK")

    # --- fp16 resident submaps at D=8 -------------------------------------
    odo16 = ODO._replace(submap=ODO.submap._replace(storage="fp16"))
    svc16 = RegistrationService(ServiceConfig(
        slots=SLOTS, scan_capacity=1024, odometry=odo16, devices=8))
    sub_fleet = {sid: fleet[sid] for sid in list(fleet)[:3]}
    for sid in sub_fleet:
        svc16.admit(sid)
    out16 = _drive(svc16, sub_fleet)
    for sid in sub_fleet:
        assert len(out16[sid]) == 5
        assert out16[sid][-1][1].map_occupancy > 0.0
    print("D=8 fp16 OK")

    # --- stream sharding primitive: D=8 == vmapped single device ----------
    params = ICPParams(max_iterations=10, chunk=256)
    keys = jax.random.split(jax.random.PRNGKey(7), 8)
    srcs, dsts = [], []
    for k in keys:
        ka, kb, kc = jax.random.split(k, 3)
        tgt = jax.random.uniform(ka, (1024, 3), minval=-10, maxval=10)
        T = random_rigid_transform(kb, max_angle=0.1, max_translation=0.3)
        s = transform_points(jnp.linalg.inv(T), tgt)
        srcs.append(s + 0.002 * jax.random.normal(kc, s.shape))
        dsts.append(tgt)
    src_b, dst_b = jnp.stack(srcs), jnp.stack(dsts)
    res8 = stream_sharded_icp(streams_mesh(8), src_b, dst_b, params)
    # weak-scaling parity: each D=8 lane (a width-1 block) is bitwise
    # identical to the same lane run alone on one device (also width 1)
    mesh1 = streams_mesh(1)
    for i in range(8):
        ref = stream_sharded_icp(mesh1, src_b[i:i + 1], dst_b[i:i + 1],
                                 params)
        np.testing.assert_array_equal(np.asarray(res8.T[i]),
                                      np.asarray(ref.T[0]))
        np.testing.assert_array_equal(np.asarray(res8.rmse[i]),
                                      np.asarray(ref.rmse[0]))
    print("stream_sharded_icp D=8 == per-lane single device OK")

    # --- legacy point-sharded path vs the xla engine (2-device mesh) ------
    mesh2 = make_mesh((2, 1), ("data", "model"))
    sb, db = shard_inputs(mesh2, src_b[:4], dst_b[:4])
    res_leg = batched_icp_sharded(mesh2, sb, db, params,
                                  frame_axes=("data",),
                                  target_axes=("model",))
    eng = get_engine("xla")
    for i in range(4):
        ref = icp_fixed_iterations(srcs[i], dsts[i], params)
        np.testing.assert_allclose(np.asarray(res_leg.T[i]),
                                   np.asarray(ref.T), atol=1e-4)
        res_e = eng.register(srcs[i], dsts[i], params)
        np.testing.assert_allclose(np.asarray(res_leg.T[i]),
                                   np.asarray(res_e.T), atol=1e-4)
    print("legacy batched_icp_sharded vs xla engine OK")


if __name__ == "__main__":
    main()
    print("MULTIDEVICE-OK")
