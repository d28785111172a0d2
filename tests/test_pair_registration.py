"""Pair registration through ``RegistrationEngine.register_pairs`` (the
FPPS §IV-A protocol: a sampled source scan onto the full target scan,
from the identity): the xla and pallas engines agree with the float64
reference (``bench.reference.icp``); ``report()`` counts the batches,
pairs, iterations and target rows; the ``fpps.pairs`` spans appear,
nested; and neither the spans nor the profiler change a pose."""
import jax
import numpy as np
import pytest
from bench import reference, scene, spans

from repro.core import ICPParams
from repro.core.engine import PallasEngine, XLAEngine
from repro.data.collate import bucket_size, collate_pairs

SCENE = {"n_ground": 3000, "n_walls": 1400, "n_poles": 600,
         "n_clutter": 500, "extent": 30.0, "sensor_range": 35.0,
         "noise_std": 0.02}
SEED = 2**31 + 29
# (sequence, target frame): the source is the next frame. Targets of
# 2085, 1653 and 1771 points, in the 3072 and 2048 buckets.
PAIRS = ((4, 1), (8, 1), (5, 0))
SAMPLES = 512
PARAMS = ICPParams(max_iterations=50, max_correspondence_distance=1.0,
                   transformation_epsilon=1e-5)
# float32 transforms of points up to 35 m: a rounding of ~2e-6 m an
# operation, over at most 50 iterations, stays under 1e-4
POSE_TOL = 1e-4


@pytest.fixture(scope="module")
def pairs():
    out = []
    for seq, f in PAIRS:
        src = scene.scan(seq, f + 1, SCENE, SEED)
        dst = scene.scan(seq, f, SCENE, SEED)
        pick = np.random.default_rng(SEED + seq).choice(
            src.shape[0], SAMPLES, replace=False)
        out.append((src[np.sort(pick)], dst))
    return out


@pytest.fixture(scope="module")
def refs(pairs):
    return [reference.icp(s, d, np.eye(4), max_iterations=50, gate=1.0,
                          eps=1e-5) for s, d in pairs]


def test_targets_span_two_buckets(pairs):
    assert len({bucket_size(d.shape[0]) for _, d in pairs}) == 2


@pytest.mark.parametrize("engine", [XLAEngine, PallasEngine],
                         ids=["xla", "pallas"])
def test_register_pairs_matches_the_reference(pairs, refs, engine):
    res, batch = engine().register_pairs(pairs, PARAMS)
    assert batch.dst.shape[1] == 3072
    T, its = np.asarray(res.T), np.asarray(res.iterations)
    converged = [k for k, r in enumerate(refs)
                 if r["iterations"] < 50 and not r["degenerate"]]
    assert converged == [0, 1, 2]
    for k in converged:
        assert np.abs(T[k] - refs[k]["T"]).max() <= POSE_TOL
        assert its[k] == refs[k]["iterations"]
        assert float(res.rmse[k]) == pytest.approx(refs[k]["rmse"],
                                                   abs=POSE_TOL)


def test_register_pairs_is_collate_then_register_batch(pairs):
    """The spans and counters leave the registration as it was: one
    collated batch through ``register_batch``, bit for bit."""
    eng = XLAEngine()
    res, _ = eng.register_pairs(pairs, PARAMS)
    b = collate_pairs(pairs)
    want = eng.register_batch(b.src, b.dst, PARAMS, src_valid=b.src_valid,
                              dst_valid=b.dst_valid)
    for got, ref in zip(res, want):
        if ref is not None:
            np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_report_counts_batches_pairs_iterations_and_rows(pairs):
    eng = XLAEngine()
    assert set(eng.report().values()) == {0}
    res1, _ = eng.register_pairs(pairs, PARAMS)
    res2, _ = eng.register_pairs(pairs[1:], PARAMS._replace(
        max_iterations=20))
    rep = eng.report()
    sizes = [d.shape[0] for _, d in pairs]
    assert rep == {
        "pair_batches": 2, "pairs": 5,
        "pair_iterations_run": 3 * 50 + 2 * 20,
        "pair_iterations": int(np.asarray(res1.iterations).sum()
                               + np.asarray(res2.iterations).sum()),
        "target_rows": sum(sizes) + sum(sizes[1:]),
        "target_rows_padded": 3 * 3072 + 2 * 2048}
    assert 0 < rep["pair_iterations"] < rep["pair_iterations_run"]
    assert eng.report() == rep          # reading twice counts nothing twice


@pytest.fixture(scope="module")
def traced(pairs, tmp_path_factory):
    """Two batches on one engine without the profiler, the same two on
    a fresh engine under it, and the program spans of the trace."""
    def drive():
        eng = XLAEngine()
        out = [eng.register_pairs(pairs, PARAMS)[0],
               eng.register_pairs(pairs[:2], PARAMS)[0]]
        return [np.asarray(r.T) for r in out]

    plain = drive()
    log_dir = tmp_path_factory.mktemp("pairs-trace")
    jax.profiler.start_trace(str(log_dir))
    try:
        poses = drive()
    finally:
        jax.profiler.stop_trace()
    program = [(n, s, s + d, meta)
               for n, s, d, meta in spans.load(log_dir)["program"]]
    return {"plain": plain, "poses": poses, "program": program}


def test_pairs_spans_appear_nested(traced):
    by_name: dict = {}
    for sp in traced["program"]:
        by_name.setdefault(sp[0], []).append(sp)
    assert set(by_name) == {"fpps.pairs", "fpps.pairs.collate",
                            "fpps.pairs.dispatch"}
    outer = sorted(by_name["fpps.pairs"], key=lambda sp: sp[1])
    assert [(sp[3]["batch"], sp[3]["pairs"]) for sp in outer] == [
        (0, 3), (1, 2)]
    for name in ("fpps.pairs.collate", "fpps.pairs.dispatch"):
        inner = sorted(by_name[name], key=lambda sp: sp[1])
        assert len(inner) == 2
        for o, i in zip(outer, inner):
            assert o[1] <= i[1] and i[2] <= o[2]
    for c, d in zip(sorted(by_name["fpps.pairs.collate"]),
                    sorted(by_name["fpps.pairs.dispatch"])):
        assert c[2] <= d[1]


def test_profiler_leaves_pair_poses_bit_identical(traced):
    for a, b in zip(traced["plain"], traced["poses"]):
        np.testing.assert_array_equal(a, b)
