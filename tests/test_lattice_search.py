"""The slot executable's lattice search against its brute-force sweep.

A voxel map holds one centroid a lattice cell, so every map point within
the ICP gate of a query lies in the lattice columns ``lattice_columns``
names around the query's column. Searching those cells must give each query that passes
the gate the same squared distance and the same matched point as the
sweep, and every other query weight 0 on both paths; the registrations
then agree. One 4-lane batch runs both ways, and each lane is a case:
a scan inside the map's lattice, one registered partly off the lattice,
an all-sentinel lane and a lane whose map is empty.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ICPParams
from repro.core.engine import SlotEngine
from repro.core.nn_search import nn_search
from repro.core.nn_search_grid import (lattice_columns, lattice_nn_fn,
                                       lattice_table)
from repro.core.odometry import prepare_scan
from repro.core.transform import transform_points
from repro.data.collate import PAD_SENTINEL
from repro.data.pointcloud import SceneConfig, ego_pose, sequence_scans
from repro.data.submap import SubmapParams, empty_state, fuse_scan

SCENE = SceneConfig(n_ground=900, n_walls=700, n_poles=150, n_clutter=200,
                    extent=14.0, sensor_range=18.0)
SUBMAP = SubmapParams(voxel_size=0.75, capacity=2048, dims=(48, 48, 16),
                      evict_radius=16.0)
PARAMS = ICPParams(max_iterations=8, max_correspondence_distance=1.0,
                   chunk=512, robust_kernel="huber", robust_scale=0.3)
GATE2 = PARAMS.max_correspondence_distance ** 2
BUDGET = 1024
LANES = ("in_lattice", "leaves_lattice", "sentinel_lane", "empty_map")


def _pose(seq, frame, shift=(0.0, 0.0, 0.0)):
    R, t = ego_pose(seq, frame)
    T = np.eye(4, dtype=np.float32)
    T[:3, :3], T[:3, 3] = R, t + np.asarray(shift)
    return T


@pytest.fixture(scope="module")
def fleet():
    """Four lanes of (scan, map, warm start) and both paths' results."""
    scans = sequence_scans(0, 4, SCENE)
    prep = jax.jit(lambda p: prepare_scan(p, None, 0.75, BUDGET))
    state = empty_state(SUBMAP)
    for f in range(3):
        src, sv = prep(scans[f])
        state, _, _ = fuse_scan(state, src, sv, jnp.asarray(_pose(0, f)),
                                SUBMAP)
    map_pts, map_valid, origin = state
    src, sv = prep(scans[3])
    # 14 m past the truth puts the scan's far side beyond the lattice edge
    T0 = np.stack([_pose(0, 3, (0.3, -0.2, 0.05)), _pose(0, 3, (14.0, 0, 0)),
                   _pose(0, 3), _pose(0, 3)])
    lanes = len(LANES)
    src_b = jnp.stack([src] * lanes)
    sv_b = jnp.stack([sv, sv, jnp.zeros_like(sv), sv])
    src_b = src_b.at[2].set(PAD_SENTINEL)
    dst_b = jnp.stack([map_pts] * lanes)
    dv_b = jnp.stack([map_valid] * 3 + [jnp.zeros_like(map_valid)])
    origin_b = jnp.stack([origin] * lanes)
    eng = SlotEngine(slots=lanes)
    sweep = eng.register_batch(src_b, dst_b, PARAMS, src_valid=sv_b,
                               dst_valid=dv_b, initial_transforms=T0)
    lattice = eng.register_batch(src_b, SUBMAP.voxel_map(dst_b, origin_b),
                                 PARAMS, src_valid=sv_b, dst_valid=dv_b,
                                 initial_transforms=T0)
    return dict(src=src_b, sv=sv_b, dst=dst_b, dv=dv_b, origin=origin_b,
                T0=T0, sweep=sweep, lattice=lattice)


@pytest.mark.parametrize("lane", range(len(LANES)), ids=LANES)
def test_lattice_search_matches_sweep_within_gate(fleet, lane):
    src, sv = fleet["src"][lane], fleet["sv"][lane]
    dst, dv = fleet["dst"][lane], fleet["dv"][lane]
    q = transform_points(jnp.asarray(fleet["T0"][lane]), src)
    d2_s, _, pts_s = nn_search(q, dst, chunk=512, dst_valid=dv,
                               return_points=True)
    nn_fn, overflow = lattice_nn_fn(
        SUBMAP.voxel_map(dst, fleet["origin"][lane]), dv,
        PARAMS.max_correspondence_distance)
    d2_l, _, pts_l = nn_fn(q)
    assert int(overflow) == int(fleet["lattice"].lattice_overflow[lane]) == 0
    assert fleet["sweep"].lattice_overflow is None
    d2_s, d2_l = np.asarray(d2_s), np.asarray(d2_l)
    sv = np.asarray(sv)
    gated = (d2_s <= GATE2) & sv
    if LANES[lane] in ("in_lattice", "leaves_lattice"):
        assert gated.sum() > 100, "case premise: correspondences to check"
    if LANES[lane] == "leaves_lattice":
        assert np.isinf(d2_l[sv]).sum() > 50, \
            "case premise: queries off the lattice"
    # identical distances and points wherever the sweep passes the gate
    np.testing.assert_array_equal(d2_l[gated], d2_s[gated])
    np.testing.assert_array_equal(np.asarray(pts_l)[gated],
                                  np.asarray(pts_s)[gated])
    # and every other query weighs 0 on both paths
    assert not np.any((d2_l <= GATE2) & sv & ~gated)
    sweep, lat = (jax.tree_util.tree_map(lambda x: np.asarray(x[lane]),
                                         fleet[path])
                  for path in ("sweep", "lattice"))
    np.testing.assert_allclose(lat.T, sweep.T, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(lat.inlier_frac, sweep.inlier_frac,
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(lat.rmse, sweep.rmse, rtol=1e-5, atol=1e-6)
    assert int(lat.iterations) == int(sweep.iterations)
    assert bool(lat.degenerate) == bool(sweep.degenerate)
    if LANES[lane] in ("sentinel_lane", "empty_map"):
        assert bool(lat.degenerate)


def test_lattice_columns_keep_the_cells_within_reach():
    """1.0 m on 0.75 m cells: the 9 inner columns and the 12 with one
    offset at ±2; a gate of one voxel needs the 9 alone."""
    cols = lattice_columns(1.0, 0.75)
    assert len(cols) == 21 and len(set(cols)) == 21
    assert all(sum(abs(a) == 2 for a in d) <= 1 for d in cols)
    assert len(lattice_columns(0.75, 0.75)) == 9


@pytest.mark.parametrize("planted", [0, 1], ids=["centroid_map", "planted"])
def test_lattice_overflow_counts_crowded_cells(planted):
    """A centroid map's table holds every point; a second point planted
    in an occupied cell is the one overflow."""
    pts = np.full((64, 3), PAD_SENTINEL, np.float32)
    valid = np.zeros(64, bool)
    cells = np.array([[1, 2, 3], [4, 4, 4], [7, 1, 0]])
    pts[:3] = (cells + 0.5) * 0.75
    valid[:3] = True
    if planted:
        pts[3] = pts[1] + 0.1
        valid[3] = True
    table, overflow = lattice_table(
        SUBMAP.voxel_map(jnp.asarray(pts), jnp.zeros(3, jnp.float32)),
        jnp.asarray(valid))
    assert int(overflow) == planted
    slots = table.shape[1] // 4                # the last block: map rows
    assert int(jnp.sum(table[:, 3 * slots:] >= 0)) == 3
