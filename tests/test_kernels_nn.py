"""Pallas NN-search kernel vs pure-jnp oracle (interpret mode on CPU).

Per-kernel requirement: sweep shapes/dtypes and assert_allclose against the
ref.py oracle. idx is checked by *distance equivalence* (fp ties may resolve
to either index legally) plus exact match against the blocked oracle, which
replays the kernel's tie-break semantics bit-for-bit at the index level.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.transform import random_rigid_transform
from repro.kernels import nn_search
from repro.kernels.nn_search import (nn_search_kernel, target_block,
                                     vmem_bytes)
from repro.kernels.ops import make_frame_engine, nn_search_pallas
from repro.kernels.ref import (augment_source, augment_target, kernel_scores,
                               nn_search_ref, nn_search_ref_blocked)

SHAPES = [
    (128, 256, 128, 256),      # single tile
    (256, 1024, 128, 256),     # multi-tile both axes
    (300, 1000, 128, 256),     # ragged -> padding path
    (512, 4096, 512, 1024),    # production tile sizes
    (1, 130_000, 128, 1024),   # paper's per-point candidate count (~130k)
    (1024, 313, 256, 256),     # target smaller than one tile
]


@pytest.mark.parametrize("n,m,bn,bm", SHAPES)
def test_kernel_vs_oracle(n, m, bn, bm):
    key = jax.random.PRNGKey(n * 7 + m)
    k1, k2, k3 = jax.random.split(key, 3)
    src = jax.random.uniform(k1, (n, 3), minval=-60, maxval=60)
    dst = jax.random.uniform(k2, (m, 3), minval=-60, maxval=60)
    T = random_rigid_transform(k3)
    d2_k, idx_k = nn_search_pallas(src, dst, T, bn=bn, bm=bm, interpret=True)
    d2_ref, idx_ref = nn_search_ref(src, dst, T)
    np.testing.assert_allclose(np.asarray(d2_k), np.asarray(d2_ref),
                               rtol=1e-5, atol=1e-2)
    # Blocked oracle replays tiling/tie-break exactly -> idx must be equal.
    d2_b, idx_b = nn_search_ref_blocked(src, dst, T, bn, bm)
    np.testing.assert_array_equal(np.asarray(idx_k), np.asarray(idx_b))
    assert idx_k.dtype == jnp.int32
    assert bool(jnp.all((idx_k >= 0) & (idx_k < m)))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.float16])
def test_kernel_dtype_sweep(dtype):
    """Points arrive in various dtypes; augmentation is fp32 — results must
    match the oracle fed the same (cast) points."""
    key = jax.random.PRNGKey(11)
    k1, k2 = jax.random.split(key)
    src = jax.random.uniform(k1, (256, 3), minval=-20, maxval=20).astype(dtype)
    dst = jax.random.uniform(k2, (512, 3), minval=-20, maxval=20).astype(dtype)
    d2_k, idx_k = nn_search_pallas(src, dst, None, bn=128, bm=256,
                                   interpret=True)
    d2_ref, idx_ref = nn_search_ref(src, dst, None)
    np.testing.assert_allclose(np.asarray(d2_k), np.asarray(d2_ref),
                               rtol=1e-4, atol=1e-2)
    np.testing.assert_array_equal(np.asarray(idx_k), np.asarray(idx_ref))


def test_no_transform_equals_identity_transform():
    key = jax.random.PRNGKey(3)
    k1, k2 = jax.random.split(key)
    src = jax.random.normal(k1, (128, 3))
    dst = jax.random.normal(k2, (256, 3))
    a = nn_search_pallas(src, dst, None, bn=128, bm=256, interpret=True)
    b = nn_search_pallas(src, dst, jnp.eye(4), bn=128, bm=256, interpret=True)
    np.testing.assert_allclose(np.asarray(a[0]), np.asarray(b[0]), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1]))


def test_frame_engine_matches_one_shot():
    """The once-per-frame pre-augmented engine must agree with the one-shot
    wrapper (production ICP uses the engine)."""
    key = jax.random.PRNGKey(9)
    k1, k2, k3 = jax.random.split(key, 3)
    src = jax.random.uniform(k1, (200, 3), minval=-10, maxval=10)
    dst = jax.random.uniform(k2, (700, 3), minval=-10, maxval=10)
    T = random_rigid_transform(k3)
    engine = make_frame_engine(dst, bn=128, bm=256, interpret=True)
    d2_e, idx_e = engine(src, T)
    d2_o, idx_o = nn_search_pallas(src, dst, T, bn=128, bm=256, interpret=True)
    # Engine jits the target augmentation separately -> different XLA fusion
    # -> last-ulp differences are legitimate; require distance equivalence.
    np.testing.assert_allclose(np.asarray(d2_e), np.asarray(d2_o),
                               rtol=1e-4, atol=1e-4)
    same = np.asarray(idx_e) == np.asarray(idx_o)
    if not same.all():
        # Any index disagreement must be a floating-point tie.
        np.testing.assert_allclose(np.asarray(d2_e)[~same],
                                   np.asarray(d2_o)[~same],
                                   rtol=1e-4, atol=1e-4)


def test_padded_targets_never_win():
    """All real targets far away + padding nearby-in-index: argmin must still
    land on a real point."""
    src = jnp.zeros((128, 3))
    dst = jnp.full((100, 3), 50.0)  # pads to 256 with +1e30 bias
    d2, idx = nn_search_pallas(src, dst, None, bn=128, bm=256, interpret=True)
    assert bool(jnp.all(idx < 100))
    np.testing.assert_allclose(np.asarray(d2), 7500.0, rtol=1e-5)


def test_augmentation_identities():
    key = jax.random.PRNGKey(21)
    k1, k2 = jax.random.split(key)
    src = jax.random.normal(k1, (64, 3))
    dst = jax.random.normal(k2, (64, 3))
    sa = augment_source(src)
    da = augment_target(dst)
    scores = jax.lax.dot_general(sa, da, (((0,), (0,)), ((), ())))
    ref = jnp.sum((src[:, None] - dst[None]) ** 2, -1)
    np.testing.assert_allclose(np.asarray(scores), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_vmem_budget_fits():
    """Default tiles must fit VMEM with double buffering (v5e ~128 MiB)."""
    b = vmem_bytes(512, target_block(False))
    assert b["total_double_buffered"] < 16 * 2 ** 20  # << 128 MiB: headroom for
    # the compiler's own buffers and future fusion.


def _aug(src, dst, bn, bm, T=None):
    n, m = src.shape[0], dst.shape[0]
    return (augment_source(src, T, pad_to=n + (-n % bn)),
            augment_target(dst, pad_to=m + (-m % bm)))


def _full_sort_two_best(src_aug, dst_aug):
    """Each query's two smallest scores by a full stable sort of all its
    candidates (the earliest index first among equal scores), in the
    kernel's arithmetic; ``‖p'‖²`` added back as the kernel returns it."""
    scores = np.asarray(kernel_scores(src_aug, dst_aug))
    order = np.argsort(scores, axis=1, kind="stable")[:, :2]
    top = np.take_along_axis(scores, order, axis=1)
    pn = np.asarray(src_aug[4])
    return top[:, 0] + pn, order[:, 0], top[:, 1] + pn, order[:, 1]


def _assert_two_best(got, want):
    d1, i1, d2, i2 = (np.asarray(x) for x in got)
    np.testing.assert_array_equal(i1, want[1])
    np.testing.assert_array_equal(i2, want[3])
    np.testing.assert_allclose(d1, want[0], rtol=1e-6, atol=0)
    np.testing.assert_allclose(d2, want[2], rtol=1e-6, atol=0)


@pytest.mark.parametrize("n,m,bn,bm", [
    (128, 256, 128, 256),      # single block
    (256, 1024, 128, 256),     # several blocks both axes
    (300, 1000, 128, 256),     # ragged -> padding path
    (200, 130, 128, 256),      # target smaller than one block
], ids=["single", "multi", "ragged", "padded"])
def test_runner_up_matches_full_sort(n, m, bn, bm):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(n + 3 * m), 3)
    src = jax.random.uniform(k1, (n, 3), minval=-60, maxval=60)
    dst = jax.random.uniform(k2, (m, 3), minval=-60, maxval=60)
    src_aug, dst_aug = _aug(src, dst, bn, bm, random_rigid_transform(k3))
    got = nn_search_kernel(src_aug, dst_aug, bn=bn, bm=bm, interpret=True)
    _assert_two_best(got, _full_sort_two_best(src_aug, dst_aug))
    assert bool(jnp.all(got[3][:n] < m)) and bool(jnp.all(got[1] != got[3]))


def test_chip_blocks_match_full_sort(monkeypatch):
    """The interpreter takes larger blocks than the chip (``_blocks``);
    the chip's register blocks and unrolled chunk loop, interpreted, give
    the same two best, over two target blocks of two loop steps each."""
    chip = nn_search._blocks
    monkeypatch.setattr(nn_search, "_blocks",
                        lambda bn, bm, interpret: chip(bn, bm, False))
    assert nn_search._blocks(256, 2048, True) != chip(256, 2048, True)
    assert 2048 // 128 // chip(256, 2048, False)[2] == 2  # loop steps
    k1, k2 = jax.random.split(jax.random.PRNGKey(23))
    src = jax.random.uniform(k1, (300, 3), minval=-60, maxval=60)
    dst = jax.random.uniform(k2, (4000, 3), minval=-60, maxval=60)
    src_aug, dst_aug = _aug(src, dst, 256, 2048)
    got = nn_search_kernel(src_aug, dst_aug, bn=256, bm=2048, interpret=True)
    _assert_two_best(got, _full_sort_two_best(src_aug, dst_aug))


@pytest.mark.parametrize("copies", [
    (200, 40),          # two lanes of one chunk
    (5, 133),           # one lane, consecutive chunks
    (700, 260),         # two target blocks
    (259, 3),           # one lane, two target blocks
    (600, 77, 333),     # three blocks, three lanes
    (261, 5, 133),      # three copies in one lane
], ids=["lanes", "chunks", "blocks", "lane_blocks", "three", "lane_three"])
def test_duplicate_targets_earliest_index_wins(copies):
    """Copies of one target point tie exactly, wherever the kernel meets
    them: the best is the earliest copy and the runner-up the next."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(len(copies)))
    dst = jax.random.uniform(k1, (768, 3), minval=20, maxval=60)
    point = jnp.array([1.0, -2.0, 0.5])
    dst = dst.at[jnp.array(copies)].set(point)
    src = point + jax.random.uniform(k2, (128, 3), minval=-0.5, maxval=0.5)
    src_aug, dst_aug = _aug(src, dst, 128, 256)
    got = nn_search_kernel(src_aug, dst_aug, bn=128, bm=256, interpret=True)
    first, second = sorted(copies)[:2]
    np.testing.assert_array_equal(np.asarray(got[1]), first)
    np.testing.assert_array_equal(np.asarray(got[3]), second)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(got[2]))
    _assert_two_best(got, _full_sort_two_best(src_aug, dst_aug))


def test_padded_target_rows_never_returned():
    """Padding rows (score biased by 1e30) lose to any real row, for the
    best and the runner-up; with one real row the runner-up is padding
    and reads 1e30 or more."""
    src = jax.random.uniform(jax.random.PRNGKey(4), (128, 3), maxval=0.1)
    far = jnp.full((130, 3), 90.0)  # every real row far, padding at 0
    src_aug, dst_aug = _aug(src, far, 128, 512)
    _, i1, _, i2 = nn_search_kernel(src_aug, dst_aug, bn=128, bm=512,
                                    interpret=True)
    assert bool(jnp.all(i1 < 130)) and bool(jnp.all(i2 < 130))
    src_aug, dst_aug = _aug(src, far[:1], 128, 256)
    d1, i1, d2, _ = nn_search_kernel(src_aug, dst_aug, bn=128, bm=256,
                                     interpret=True)
    assert bool(jnp.all(i1 == 0)) and bool(jnp.all(d1 < 1e29))
    assert bool(jnp.all(d2 >= 1e29))


def test_vmap_over_pairs_equals_single_calls():
    """The batch executable vmaps the kernel over frame pairs; each pair
    must come out as its own call does."""
    keys = jax.random.split(jax.random.PRNGKey(17), 6)
    srcs = jnp.stack([jax.random.uniform(k, (256, 3), minval=-30, maxval=30)
                      for k in keys[:3]])
    dsts = jnp.stack([jax.random.uniform(k, (512, 3), minval=-30, maxval=30)
                      for k in keys[3:]])
    src_aug = jax.vmap(augment_source)(srcs)
    dst_aug = jax.vmap(augment_target)(dsts)
    run = lambda s, d: nn_search_kernel(s, d, bn=128, bm=256, interpret=True)
    batched = jax.vmap(run)(src_aug, dst_aug)
    for p in range(3):
        for b, one in zip(batched, run(src_aug[p], dst_aug[p])):
            np.testing.assert_array_equal(np.asarray(b[p]), np.asarray(one))


def test_kernel_scores_in_float32_without_a_contraction():
    """The kernel scores elementwise on the VPU: its body has no
    ``dot_general`` and no dtype below 32 bits."""
    jaxpr = str(jax.make_jaxpr(
        lambda s, d: nn_search_kernel(s, d, bn=128, bm=256, interpret=False))(
            jnp.zeros((8, 128)), jnp.zeros((8, 256))))
    assert "select_n" in jaxpr  # the kernel's body is in the text
    assert "dot_general" not in jaxpr
    dtypes = set(re.findall(r"\b([a-z]+\d+)\[", jaxpr))
    assert dtypes <= {"f32", "i32", "float32", "int32"}, dtypes
