"""Shared Pallas plumbing: interpret-mode resolution + compiler params.

Every kernel wrapper in ``repro.kernels`` takes an ``interpret`` knob with
the same tri-state meaning:

  * ``None`` (auto) — run compiled on TPU, interpret everywhere else, so
    the full kernel suite *executes* (instead of skipping) on CPU-only CI
    while TPU hosts get the real Mosaic lowering with zero configuration;
  * ``True`` / ``False`` — force one mode (tests pin ``True``; autotuning
    on hardware pins ``False``).

:func:`pallas_call_kwargs` is the single place the interpret default and
the TPU ``dimension_semantics`` compiler params live.

Layout shared by every kernel here: queries run along the 128 lanes and
candidates along the sublanes. Per-query values travel as ``(1, N)`` rows
in ``(1, bn)`` blocks, candidate planes as ``(CK, N)`` arrays in
``(bc, bn)`` blocks, so ``bn`` is a multiple of 128 and ``bc`` of 8. A
1-D ``(N,)`` operand would be refused by the chip's compiler (XLA tiles
it in 1024s) and cannot be batched under ``vmap`` (the batched block's
second-minor dimension would be 1).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def round_up(x: int, mult: int) -> int:
    """Smallest multiple of ``mult`` that is >= ``x`` (tile padding)."""
    return x + (-x) % mult


def default_interpret(interpret: bool | None = None) -> bool:
    """Resolve the tri-state ``interpret`` flag (see module docstring)."""
    if interpret is None:
        # tracecheck: ignore[PK001]  # this IS the single blessed home
        return jax.default_backend() != "tpu"
    return bool(interpret)


def pallas_call_kwargs(interpret: bool | None,
                       dimension_semantics: tuple[str, ...]) -> dict:
    """``pl.pallas_call`` kwargs: resolved interpret + TPU compiler params.

    ``dimension_semantics`` labels each grid axis "parallel" or
    "arbitrary" (axes carrying a running min / accumulator must be
    "arbitrary" so Mosaic keeps them sequential).
    """
    resolved = default_interpret(interpret)
    kwargs: dict = {"interpret": resolved}
    if not resolved:
        from jax.experimental.pallas import tpu as pltpu
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=dimension_semantics)
    return kwargs


def query_rows(q: jax.Array) -> tuple[jax.Array, ...]:
    """(N, 3) points -> three lane-dense (1, N) f32 coordinate rows."""
    qt = q.astype(jnp.float32).T
    return qt[0:1], qt[1:2], qt[2:3]


def candidate_planes(cand: jax.Array) -> tuple[jax.Array, ...]:
    """(N, CK, 3) per-query candidates -> three (CK, N) f32 planes."""
    ct = jnp.transpose(cand.astype(jnp.float32), (2, 1, 0))
    return ct[0], ct[1], ct[2]


def argmin_rows(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Column-wise ``(min, first argmin)`` of a ``(rows, lanes)`` tile,
    each as a lane-dense ``(1, lanes)`` row.

    The kernels lay queries along lanes and candidates along sublanes, so
    the per-query reduction runs over axis 0. The earliest row wins ties,
    matching ``jnp.argmin``.
    """
    lo = jnp.min(x, axis=0, keepdims=True)
    rows = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    arg = jnp.min(jnp.where(x == lo, rows, x.shape[0]), axis=0,
                  keepdims=True)
    return lo, arg
