"""Plain reference of the registration semantics, in float64 numpy.

Written from the semantics the configurations state, not from the
program, and importing nothing of it:

  * voxel downsample: per occupied voxel of edge ``v`` (lattice anchored
    at ``floor((min - v/2) / v) * v`` unless given), the centroid of its
    points; cells in (x, y, z) lexicographic order, the first ``cap``
    kept;
  * ICP: exact nearest neighbour (k-d tree), the correspondence gate, an
    optional Huber weight ``min(1, scale / r)``, weighted Kabsch, the
    cumulative transform, stop once ``|R-I|^2 + |t|^2 <= eps`` or at the
    iteration cap;
  * scan-to-map odometry: constant-velocity warm start, the health
    grading of each registration, and the map fuse (evict beyond the
    radius, keep the lattice around the ego, voxel centroids, capacity).

``precision`` lowers every matrix product to what a TPU does below
float32: ``"bf16"`` rounds both operands to bfloat16 (one pass),
``"bf16x3"`` splits them into bfloat16 halves and sums three passes; the
rest then runs in float32. That is the control that the comparison must
fail; ``None`` is the reference itself.
"""
from __future__ import annotations

import numpy as np
from scipy.spatial import cKDTree


def to_bf16(x) -> np.ndarray:
    """Round to the nearest bfloat16 (ties to even), kept as float32."""
    u = np.ascontiguousarray(x, np.float32).view(np.uint32)
    r = (u + np.uint32(0x7FFF) + ((u >> 16) & np.uint32(1))) \
        & np.uint32(0xFFFF0000)
    return r.view(np.float32)


def matmul(a, b, precision: str | None = None) -> np.ndarray:
    """``a @ b`` in float64, or as a TPU computes it at ``precision``."""
    if precision is None:
        return np.asarray(a, np.float64) @ np.asarray(b, np.float64)
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    a_hi, b_hi = to_bf16(a), to_bf16(b)
    if precision == "bf16":
        return a_hi @ b_hi
    if precision == "bf16x3":
        a_lo, b_lo = to_bf16(a - a_hi), to_bf16(b - b_hi)
        return a_hi @ b_hi + (a_hi @ b_lo + a_lo @ b_hi)
    raise ValueError(f"unknown precision {precision!r}")


def _dtype(precision):
    return np.float64 if precision is None else np.float32


def apply(T, points, precision=None) -> np.ndarray:
    """Rigid ``T`` (4x4) applied to ``(n, 3)`` points."""
    T = np.asarray(T, _dtype(precision))
    return matmul(points, T[:3, :3].T, precision) + T[:3, 3]


def voxel_downsample(points, voxel: float, cap: int, origin=None):
    """Centroids of the occupied voxels; returns ``(centroids, occupied)``
    with at most ``cap`` rows and the occupied-cell count before the
    capacity applied."""
    pts = np.asarray(points, np.float64)
    if pts.shape[0] == 0:
        return pts.reshape(0, 3), 0
    if origin is None:
        origin = np.floor((pts.min(axis=0) - 0.5 * voxel) / voxel) * voxel
    ic = np.floor((pts - origin) / voxel).astype(np.int64)
    cells, inv = np.unique(ic, axis=0, return_inverse=True)
    inv = inv.ravel()
    sums = np.zeros((cells.shape[0], 3))
    np.add.at(sums, inv, pts)
    cent = sums / np.bincount(inv)[:, None]
    return cent[:cap], int(cells.shape[0])


def kabsch(src, dst, w, precision=None) -> np.ndarray:
    """Weighted rigid fit: the T minimising sum w |dst - (R src + t)|^2."""
    dt = _dtype(precision)
    src, dst, w = (np.asarray(a, dt) for a in (src, dst, w))
    wsum = max(float(w.sum()), 1e-12)
    ms = (src * w[:, None]).sum(0) / wsum
    md = (dst * w[:, None]).sum(0) / wsum
    H = matmul(((src - ms) * w[:, None]).T, dst - md, precision)
    U, _, Vt = np.linalg.svd(np.asarray(H, np.float64))
    D = np.eye(3)
    D[2, 2] = np.linalg.det(Vt.T @ U.T)
    T = np.eye(4)
    T[:3, :3] = Vt.T @ D @ U.T
    T[:3, 3] = md - T[:3, :3] @ ms
    return T


def icp(src, dst, T0, *, max_iterations: int, gate: float, eps: float,
        huber: float | None = None, tree=None, precision=None) -> dict:
    """Point-to-point ICP of ``src`` onto ``dst`` from ``T0``."""
    src = np.asarray(src, np.float64)
    dst = np.asarray(dst, np.float64)
    tree = cKDTree(dst) if tree is None else tree
    T = np.asarray(T0, np.float64).copy()
    n_valid = max(src.shape[0], 1)
    rmse, inlier, degenerate, it = float("inf"), 0.0, False, 0
    delta = float("inf")
    while it < max_iterations and delta > eps:
        src_t = np.asarray(apply(T, src, precision), np.float64)
        d, idx = tree.query(src_t, workers=-1)
        matched = dst[idx]
        w = (d * d <= gate * gate).astype(np.float64)
        if huber is not None:
            w = w * np.minimum(1.0, huber / np.maximum(d, 1e-12))
        wsum = float(w.sum())
        if wsum <= 1e-6:
            degenerate = True
            Td = np.eye(4)
            rmse = float("inf")
        else:
            Td = kabsch(src_t, matched, w, precision)
            moved = apply(Td, src_t)
            rmse = float(np.sqrt((np.sum((moved - matched) ** 2, axis=1)
                                  * w).sum() / max(wsum, 1e-12)))
        T = np.asarray(matmul(Td, T, precision), np.float64)
        delta = float(np.sum((Td[:3, :3] - np.eye(3)) ** 2)
                      + np.sum(Td[:3, 3] ** 2))
        inlier = wsum / n_valid
        it += 1
    return {"T": T, "rmse": rmse, "inlier_frac": inlier,
            "degenerate": degenerate, "iterations": it}


def pose_jump(T, T_ref) -> tuple[float, float]:
    """(metres, radians) between two poses."""
    dt = float(np.linalg.norm(T[:3, 3] - T_ref[:3, 3]))
    R = T[:3, :3] @ T_ref[:3, :3].T
    return dt, float(np.arccos(np.clip((np.trace(R) - 1.0) / 2.0,
                                       -1.0, 1.0)))


def health_ok(res: dict, predicted, out_of_lattice: float,
              th: dict) -> bool:
    """True where no graded signal reaches its suspect threshold (the
    scan-observability signal is not graded here; see ``PERF.md``)."""
    if res["degenerate"] or not np.all(np.isfinite(res["T"])):
        return False
    if not np.isfinite(res["rmse"]) or res["rmse"] >= th["suspect_rmse"]:
        return False
    if res["inlier_frac"] <= th["suspect_inlier_frac"]:
        return False
    if out_of_lattice >= th["suspect_out_of_lattice"]:
        return False
    if predicted is not None:
        jt, jr = pose_jump(res["T"], predicted)
        if jt >= th["suspect_pose_jump"] or jr >= th["suspect_rot_jump"]:
            return False
    return True


def _decay(T, factor: float) -> np.ndarray:
    """Rigid motion shrunk toward identity (angle and translation)."""
    R = T[:3, :3]
    angle = float(np.arccos(np.clip((np.trace(R) - 1.0) / 2.0, -1.0, 1.0)))
    out = np.eye(4)
    if angle > 1e-8:
        axis = np.array([R[2, 1] - R[1, 2], R[0, 2] - R[2, 0],
                         R[1, 0] - R[0, 1]])
        axis /= max(np.linalg.norm(axis), 1e-12)
        K = np.array([[0.0, -axis[2], axis[1]], [axis[2], 0.0, -axis[0]],
                      [-axis[1], axis[0], 0.0]])
        a = angle * factor
        out[:3, :3] = np.eye(3) + np.sin(a) * K + (1 - np.cos(a)) * K @ K
    out[:3, 3] = factor * T[:3, 3]
    return out


class Odometry:
    """Scan-to-map odometry of one vehicle, as the fleet configuration
    states it. :meth:`frame` returns ``{"pose", "escalate", "fused",
    "occupied", "dropped"}``: the map's occupied cells after the frame,
    and the running total of occupied cells the map's capacity has
    dropped. ``escalate`` is True where the health grading sends the
    frame to the recovery tiers, which this reference does not follow
    (the comparison stops at such a frame). ``map`` holds the map's
    voxel centroids."""

    def __init__(self, odo: dict, precision=None):
        self.c = odo
        self.precision = precision
        self.map = np.zeros((0, 3))
        self.origin = np.zeros(3)
        self.poses: list[np.ndarray] = []
        self.velocity = np.eye(4)
        self.dropped = 0

    def _fuse(self, src, pose) -> None:
        m = self.c["submap"]
        v, dims = m["voxel_size"], np.asarray(m["dims"], np.float64)
        center = pose[:3, 3]
        origin = np.floor((center - 0.5 * dims * v) / v) * v
        pts = np.concatenate([self.map, apply(pose, src, self.precision)])
        keep = np.sum((pts - center) ** 2, axis=1) <= m["evict_radius"] ** 2
        ic = np.floor((pts - origin) / v)
        keep &= np.all((ic >= 0) & (ic < dims), axis=1)
        self.map, cells = voxel_downsample(pts[keep], v, m["capacity"],
                                           origin)
        self.dropped += max(cells - self.map.shape[0], 0)
        self.origin = origin

    def _outside(self, T, src) -> float:
        m = self.c["submap"]
        c = np.floor((apply(T, src) - self.origin) / m["voxel_size"])
        inb = np.all((c >= 0) & (c < np.asarray(m["dims"])), axis=1)
        return float(np.sum(~inb)) / max(src.shape[0], 1)

    def frame(self, scan) -> dict:
        c = self.c
        src, _ = voxel_downsample(scan, c["scan_voxel"], c["scan_budget"])
        k = len(self.poses)
        if k == 0:
            pose = np.eye(4)
            self._fuse(src, pose)
            self.poses.append(pose)
            return self._out(pose, False, True)
        T0 = (self.poses[-1] if k < 2
              else self.poses[-1] @ self.velocity)
        p = c["params"]
        res = icp(src, self.map, T0, max_iterations=p["max_iterations"],
                  gate=p["max_correspondence_distance"],
                  eps=p["transformation_epsilon"],
                  huber=(p["robust_scale"] if p["robust_kernel"] == "huber"
                         else None), precision=self.precision)
        ok = health_ok(res, T0 if k >= 2 else None,
                       self._outside(res["T"], src), c["thresholds"])
        if k >= c["warmup_frames"]:
            if not ok:
                return self._out(None, True, False)
            accepted = True
        else:
            accepted = (not res["degenerate"]
                        and res["inlier_frac"] >= c["min_inlier_frac"])
        fused = accepted and ok
        if accepted:
            pose = res["T"]
            self.velocity = np.linalg.inv(self.poses[-1]) @ pose
            if fused:
                self._fuse(src, pose)
        else:
            pose = T0
            self.velocity = _decay(self.velocity, c["velocity_decay"])
        self.poses.append(pose)
        return self._out(pose, False, fused)

    def _out(self, pose, escalate: bool, fused: bool) -> dict:
        return {"pose": pose, "escalate": escalate, "fused": fused,
                "occupied": self.map.shape[0], "dropped": self.dropped}
