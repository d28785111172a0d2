"""jit'd public wrappers around the Pallas NN kernel.

Handles padding to tile multiples, the once-per-frame target augmentation,
and the per-iteration source augmentation + unpadding. These wrappers have
the same (src, dst[, T]) -> (d2, idx) contract as ``repro.core.nn_search``
so they can be dropped into ``core.icp`` via the ``nn_fn`` hook.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.nn_search import closer_of_two
from repro.kernels import ref
from repro.kernels.common import round_up as _round_up
from repro.kernels.nn_search import nn_search_kernel, target_block


@functools.partial(jax.jit, static_argnames=("bn", "bm", "interpret"))
def nn_search_pallas(src: jax.Array, dst: jax.Array,
                     T: jax.Array | None = None,
                     *, bn: int = 512, bm: int | None = None,
                     interpret: bool | None = None):
    """NN of each (optionally T-transformed) src point in dst via the kernel.

    src: (N,3), dst: (M,3); returns ((N,) fp32 d2, (N,) int32 idx).
    Shapes need not be tile-aligned; padding is handled here. Padded target
    slots carry a +1e30 bias so they never win; padded source rows are
    sliced off.
    """
    n, m = src.shape[0], dst.shape[0]
    bm = target_block(interpret) if bm is None else bm
    n_pad, m_pad = _round_up(n, bn), _round_up(m, bm)
    src_aug = ref.augment_source(src, T, pad_to=n_pad)
    dst_aug = ref.augment_target(dst, pad_to=m_pad)
    d2, idx, _, _ = nn_search_kernel(src_aug, dst_aug, bn=bn, bm=bm,
                               interpret=interpret)
    return jnp.maximum(d2[:n], 0.0), idx[:n]


def resident_nn_fn(dst: jax.Array, *, bn: int = 512,
                   bm: int | None = None, interpret: bool | None = None):
    """In-trace resident-target searcher for use *inside* a jitted program.

    Builds the (8, M') augmented target once at trace position — outside the
    ICP iteration scan/while body that the returned closure is called from —
    so the compiled program augments the target once per frame and only the
    small source cloud per iteration (the BRAM-resident analogue,
    DESIGN.md §2). The closure matches the ``core.icp`` ``nn_fn(src, dst)``
    contract but ignores its second argument in favour of the resident
    augmentation. Like ``core.nn_search`` it returns ``(d2, idx, points)``
    and picks between the two best candidates by the directly computed
    distance, which it also returns: the expansion's cancellation at scene
    scale would otherwise swap near-tied neighbours and move points across
    the correspondence gate.

    Padded/invalid target rows must already carry far-sentinel coordinates
    (as ``repro.data.collate`` produces) so they cannot win the argmin.
    """
    m = dst.shape[0]
    bm = target_block(interpret) if bm is None else bm
    dst_aug = ref.augment_target(dst, pad_to=_round_up(m, bm))

    def nn_fn(src: jax.Array, _target=None):
        n = src.shape[0]
        src_aug = ref.augment_source(src, pad_to=_round_up(n, bn))
        _, i1, s2, i2 = nn_search_kernel(src_aug, dst_aug, bn=bn, bm=bm,
                                         interpret=interpret)
        # A runner-up score of 1e30 or more is target padding.
        return closer_of_two(src, dst, i1[:n], i2[:n], s2[:n] < 1e29)

    return nn_fn


def make_frame_engine(dst: jax.Array, *, bn: int = 512,
                      bm: int | None = None, interpret: bool | None = None):
    """Pre-augment a target frame once; return nn_fn(src, T) for ICP loops.

    This is the intended production shape: the (8, M) augmented target is
    computed once per frame (the BRAM-resident analogue) and closed over by
    every ICP iteration.
    """
    m = dst.shape[0]
    bm = target_block(interpret) if bm is None else bm
    m_pad = _round_up(m, bm)
    dst_aug = ref.augment_target(dst, pad_to=m_pad)

    @functools.partial(jax.jit, static_argnames=())
    def nn_fn(src: jax.Array, T: jax.Array | None = None):
        n = src.shape[0]
        n_pad = _round_up(n, bn)
        src_aug = ref.augment_source(src, T, pad_to=n_pad)
        d2, idx, _, _ = nn_search_kernel(src_aug, dst_aug, bn=bn, bm=bm,
                                   interpret=interpret)
        return jnp.maximum(d2[:n], 0.0), idx[:n]

    return nn_fn
