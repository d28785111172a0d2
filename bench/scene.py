"""The benchmark's own copy of the synthetic KITTI-like scan recipe.

A frozen copy of the program's scene generator
(``repro.data.pointcloud``), so that the traffic a cell sends cannot
change when the program changes.
Pure numpy: worker processes that generate traffic never import JAX.

A scene holds a ground plane, building facades, poles and clutter; a
scan is the range-gated, noisy view from the ego pose of one frame of a
sequence. Ten motion profiles stand in for KITTI odometry 00-09.
"""
from __future__ import annotations

import numpy as np

# (speed m/frame, yaw-rate rad/frame) per sequence.
SEQ_PROFILES = {
    0: (0.8, 0.010), 1: (2.5, 0.002), 2: (1.0, 0.008), 3: (0.7, 0.020),
    4: (1.8, 0.001), 5: (0.9, 0.012), 6: (1.5, 0.006), 7: (0.6, 0.015),
    8: (1.1, 0.009), 9: (1.6, 0.005),
}

SCENE_KEYS = ("n_ground", "n_walls", "n_poles", "n_clutter", "extent",
              "sensor_range", "noise_std")


def _rot_z(yaw: float) -> np.ndarray:
    c, s = np.cos(yaw), np.sin(yaw)
    return np.array([[c, -s, 0.0], [s, c, 0.0], [0.0, 0.0, 1.0]])


def make_world(seq: int, scene: dict, point_seed: int) -> np.ndarray:
    """World points of sequence ``seq``: the layout is pinned by ``seq``,
    the surface samples are drawn from ``point_seed``."""
    rng = np.random.default_rng(1000 + seq)
    prng = np.random.default_rng(2_000_000_000 + point_seed)
    e = scene["extent"]
    g_xy = prng.uniform(-2 * e, 2 * e, size=(scene["n_ground"], 2))
    g_z = 0.05 * np.sin(0.08 * g_xy[:, 0]) * np.cos(0.05 * g_xy[:, 1])
    ground = np.column_stack([g_xy, g_z])
    walls = []
    n_buildings = 14
    per = scene["n_walls"] // n_buildings
    for _ in range(n_buildings):
        cx = rng.uniform(-2 * e, 2 * e)
        cy = (rng.uniform(-e, e)
              + np.sign(rng.standard_normal()) * rng.uniform(8, 20))
        w, h = rng.uniform(8, 25), rng.uniform(4, 12)
        axis = rng.integers(0, 2)
        u = prng.uniform(-w / 2, w / 2, per)
        z = prng.uniform(0, h, per)
        if axis == 0:
            pts = np.column_stack([cx + u, np.full(per, cy), z])
        else:
            pts = np.column_stack([np.full(per, cx), cy + u, z])
        walls.append(pts)
    n_poles_obj = 60
    per_pole = scene["n_poles"] // n_poles_obj
    px = rng.uniform(-2 * e, 2 * e, n_poles_obj)
    py = rng.uniform(-e, e, n_poles_obj)
    poles = []
    for i in range(n_poles_obj):
        theta = prng.uniform(0, 2 * np.pi, per_pole)
        r = rng.uniform(0.05, 0.25)
        z = prng.uniform(0, rng.uniform(2, 6), per_pole)
        poles.append(np.column_stack([px[i] + r * np.cos(theta),
                                      py[i] + r * np.sin(theta), z]))
    n_cl = scene["n_clutter"]
    clutter = np.column_stack([prng.uniform(-2 * e, 2 * e, n_cl),
                               prng.uniform(-e, e, n_cl),
                               np.abs(prng.normal(0.5, 0.5, n_cl))])
    return np.concatenate([ground, np.concatenate(walls),
                           np.concatenate(poles), clutter])


def ego_pose(seq: int, frame: int) -> tuple[np.ndarray, np.ndarray]:
    """Ground-truth (R, t) of the ego at ``frame`` of sequence ``seq``."""
    speed, yaw_rate = SEQ_PROFILES[seq % 10]
    yaw = yaw_rate * frame
    if abs(yaw_rate) < 1e-9:
        x, y = speed * frame, 0.0
    else:
        radius = speed / yaw_rate
        x = radius * np.sin(yaw)
        y = radius * (1.0 - np.cos(yaw))
    return _rot_z(yaw), np.array([x, y, 0.0])


def scan(seq: int, frame: int, scene: dict, seed: int) -> np.ndarray:
    """Sensor-frame scan of ``frame``: a freshly sampled world, range-gated
    at the sensor and with range noise. Float32 ``(n, 3)``."""
    world = make_world(seq, scene, point_seed=seed * 65_537 + frame)
    rng = np.random.default_rng(seed * 100_003 + seq * 1009 + frame)
    R, t = ego_pose(seq, frame)
    local = (world - t) @ R
    pts = local[np.linalg.norm(local, axis=1) <= scene["sensor_range"]]
    pts = pts + rng.normal(0.0, scene["noise_std"], pts.shape)
    return pts.astype(np.float32)
