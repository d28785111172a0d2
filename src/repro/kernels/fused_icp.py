"""Pallas TPU kernel: fused single-pass ICP iteration (DESIGN.md §11).

The unfused iteration is four separate XLA ops — grid-candidate sweep →
winner gather → distance gate + robust weight → Kabsch / Gauss-Newton
moment matmuls — and every stage round-trips its intermediates through
HBM. FPPS's whole thesis (§IV: 35x peak) is a streamed dataflow pipeline
where correspondence candidates never leave the chip between search and
accumulation; this kernel is that pipeline on the TPU:

  * grid = (N/bn, CK/bc): query blocks "parallel", the candidate axis
    innermost/"arbitrary". Per candidate tile the kernel computes the
    (bc, bn) distance plane in VMEM (queries along lanes, candidates along
    sublanes — ``kernels.common``), reduces it to a per-query running
    (min, winner-coordinates[, winner-normal]) carry — the winner's
    *values* are selected in-register via a one-hot reduction, so no
    index gather ever revisits earlier tiles.
  * on the **last** candidate tile the carried winner is gated
    (``d² ≤ gate²``, recomputed exact in fp32 from the carried
    coordinates), IRLS-weighted (huber/tukey, same formulas as
    ``core.point_to_plane.robust_weights``), and folded into per-query
    moment planes: the Kabsch sums (Σw, Σw·p, Σw·q, Σw·p⊗q, Σw·|p|²,
    Σw·|q|²) for point-to-point, or the 6x6 normal-equation blocks
    (Σw·a⊗a, Σw·r·a with a = [p×n; n]) for point-to-plane — the
    ten-plane running-sum + shared host epilogue template proven in
    ``kernels/normals.py``, widened to the ICP moment set.
  * the host-side epilogue (``core.transform.estimate_from_moments`` /
    ``core.point_to_plane.solve_normal_equations``) reduces the (N,)
    planes to scalars and performs the tiny 3x3-SVD / 6x6 solve — O(1)
    work per iteration, like the paper's result-accumulator stage.
  * masked candidate slots carry far-sentinel coordinates
    (``core.nn_search_grid``) and masked queries carry ``src_valid = 0``,
    so empty neighbourhoods and padded rows fall out of the gate with
    zero weight — no mask inputs, no NaN path, and the PR-5 zero-weight
    freeze triggers naturally when *every* row lands there.

Mixed-precision candidate prune (``prune=True``): a **bf16** distance
screen at a *widened* gate (``prune_margin`` ≥ any bf16 rounding of a
within-gate distance, so no true inlier is ever screened out) decides
which candidates get the exact fp32 update — and, via ``pl.when``, lets
the kernel skip the fp32 pass for whole tiles the screen rejects.
Selection among survivors runs on exact fp32 distances, so the moments
are *identical* to the unpruned pass: a screened candidate is provably
out-of-gate and would carry zero weight regardless of which of them
wins. The prune pays half-width math up front to skip full-width math on
cold tiles; whether that nets out is hardware-dependent, which is
exactly the knob the autotune sweep (``tools/autotune_fused.py``) flips.

Point-to-plane normals ride as candidate payload: three extra (bc, bn)
planes gathered through the same slot tables, selected by the same
one-hot carry — matching Sugiura & Matsutani's feature-payload streaming
(arXiv:2203.05763).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.nn_search_grid import _MASK_COORD, gather_candidate_planes
from repro.data.voxelize import VoxelGrid, build_voxel_grid
from repro.kernels.common import (argmin_rows, pallas_call_kwargs,
                                  query_rows, round_up)

# Default fused-kernel configuration. bn/bc/prune are the autotune axes
# (``python tools/autotune_fused.py``). The committed choice comes from an
# interpret-mode sweep on a CPU (BENCH_fused_autotune.json); it has not
# been tuned on a chip.
class FusedConfig(NamedTuple):
    bn: int = 512            # query tile (lanes per grid step)
    bc: int = 256            # candidate tile (sublanes per grid step)
    prune: bool = False      # bf16 coarse-distance screen (see module doc)
    prune_margin: float = 1.1  # gate widening for the bf16 screen


DEFAULT_CONFIG = FusedConfig()

# Finest-lattice default, matching ``core.pyramid.DEFAULT_GRID_DIMS``.
DEFAULT_GRID_DIMS: tuple[int, int, int] = (128, 128, 32)

# Moment-plane order (the kernel's output contract, after the carries).
# The "rmse block" is what the exact post-step RMSE needs: first moments
# of p and q, the raw cross moments Σw·p_i·q_j, and the squared norms.
_RMSE_BLOCK = (
    "px", "py", "pz", "qx", "qy", "qz",
    "pq00", "pq01", "pq02", "pq10", "pq11", "pq12",
    "pq20", "pq21", "pq22", "pp", "qq",
)
_AA = tuple(f"a{k}{li}" for k in range(6) for li in range(k, 6))  # 21
_RA = tuple(f"ra{k}" for k in range(6))
P2P_MOMENTS = ("w",) + _RMSE_BLOCK                         # 18 planes
P2PLANE_MOMENTS = ("w",) + _AA + _RA + _RMSE_BLOCK         # 45 planes


def moment_names(plane: bool) -> tuple[str, ...]:
    return P2PLANE_MOMENTS if plane else P2P_MOMENTS


def _carry_count(plane: bool) -> int:
    # running min + winner coordinates (+ winner normal for p2plane)
    return 7 if plane else 4


def _fused_kernel(*refs, bc: int, nc: int, gate2: float, prune_gate2: float,
                  robust: str, scale: float, plane: bool, prune: bool):
    n_in = 10 if plane else 7
    in_refs, out_refs = refs[:n_in], refs[n_in:]
    qx_ref, qy_ref, qz_ref, sv_ref = in_refs[:4]
    cx_ref, cy_ref, cz_ref = in_refs[4:7]
    ncarry = _carry_count(plane)
    carry_refs, mom_refs = out_refs[:ncarry], out_refs[ncarry:]
    best_ref, bqx_ref, bqy_ref, bqz_ref = carry_refs[:4]
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        best_ref[...] = jnp.full_like(best_ref, jnp.inf)
        for ref in (bqx_ref, bqy_ref, bqz_ref):
            ref[...] = jnp.full_like(ref, _MASK_COORD)
        if plane:
            for ref in carry_refs[4:]:
                ref[...] = jnp.zeros_like(ref)

    qx, qy, qz = qx_ref[...], qy_ref[...], qz_ref[...]
    cx, cy, cz = cx_ref[...], cy_ref[...], cz_ref[...]
    if prune:
        # bf16 coarse *screen* at a widened gate: half-width math decides
        # only which candidates (and, via pl.when, which whole tiles) get
        # the exact fp32 update. prune_margin exceeds bf16 rounding, so no
        # within-gate candidate is ever screened, and selection among the
        # survivors runs on exact fp32 distances — moments are identical
        # to the unpruned pass (screened rows are provably out-of-gate and
        # would carry weight 0 regardless of which of them wins).
        bf = jnp.bfloat16
        dxb = cx.astype(bf) - qx.astype(bf)
        dyb = cy.astype(bf) - qy.astype(bf)
        dzb = cz.astype(bf) - qz.astype(bf)
        d2b = (dxb * dxb + dyb * dyb + dzb * dzb).astype(jnp.float32)
        keep = d2b <= prune_gate2

    def _update():
        dx = cx - qx
        dy = cy - qy
        dz = cz - qz
        d2 = dx * dx + dy * dy + dz * dz
        if prune:
            d2 = jnp.where(keep, d2, jnp.inf)
        local_min, local_arg = argmin_rows(d2)
        # In-register winner-value selection: one-hot of the tile argmin,
        # so the carry holds coordinates/normals, never indices to
        # re-gather.
        onehot = jax.lax.broadcasted_iota(
            jnp.int32, d2.shape, 0) == local_arg

        def _sel(vals):
            return jnp.sum(jnp.where(onehot, vals, 0.0), axis=0,
                           keepdims=True)

        # Strict < keeps the earliest tile/slot on ties (first-match
        # semantics, same as the NN kernels).
        improved = local_min < best_ref[...]
        best_ref[...] = jnp.where(improved, local_min, best_ref[...])
        bqx_ref[...] = jnp.where(improved, _sel(cx), bqx_ref[...])
        bqy_ref[...] = jnp.where(improved, _sel(cy), bqy_ref[...])
        bqz_ref[...] = jnp.where(improved, _sel(cz), bqz_ref[...])
        if plane:
            bnx_ref, bny_ref, bnz_ref = carry_refs[4:]
            nx, ny, nz = in_refs[7][...], in_refs[8][...], in_refs[9][...]
            bnx_ref[...] = jnp.where(improved, _sel(nx), bnx_ref[...])
            bny_ref[...] = jnp.where(improved, _sel(ny), bny_ref[...])
            bnz_ref[...] = jnp.where(improved, _sel(nz), bnz_ref[...])

    if prune:
        # Whole-tile skip: when the bf16 screen rejects every candidate in
        # the (bc, bn) tile, the fp32 pass is provably a no-op (all-inf
        # local_min never improves the carry) — don't execute it.
        pl.when(jnp.any(keep))(_update)
    else:
        _update()

    @pl.when(j == nc - 1)
    def _epilogue():
        px, py, pz = qx, qy, qz
        wqx, wqy, wqz = bqx_ref[...], bqy_ref[...], bqz_ref[...]
        ex, ey, ez = px - wqx, py - wqy, pz - wqz
        d2x = ex * ex + ey * ey + ez * ez       # exact fp32, carried winner
        w = (d2x <= gate2).astype(jnp.float32) * sv_ref[...]
        if plane:
            nxv, nyv, nzv = (carry_refs[4][...], carry_refs[5][...],
                             carry_refs[6][...])
            r = nxv * ex + nyv * ey + nzv * ez  # n·(p − q)
        if robust != "none":
            resid = (jnp.abs(r) if plane
                     else jnp.sqrt(jnp.maximum(d2x, 0.0)))
            if robust == "huber":
                w = w * jnp.minimum(1.0, scale / jnp.maximum(resid, 1e-12))
            else:  # tukey
                u = resid / max(scale, 1e-12)
                w = w * jnp.where(u < 1.0, (1.0 - u * u) ** 2, 0.0)
        rmse_block = [w * px, w * py, w * pz, w * wqx, w * wqy, w * wqz]
        for pi in (px, py, pz):
            for qi in (wqx, wqy, wqz):
                rmse_block.append(w * pi * qi)
        rmse_block.append(w * (px * px + py * py + pz * pz))
        rmse_block.append(w * (wqx * wqx + wqy * wqy + wqz * wqz))
        planes_out = [w]
        if plane:
            a = (py * nzv - pz * nyv, pz * nxv - px * nzv,
                 px * nyv - py * nxv, nxv, nyv, nzv)   # [p×n; n]
            for k in range(6):
                for li in range(k, 6):
                    planes_out.append(w * a[k] * a[li])
            for k in range(6):
                planes_out.append(w * r * a[k])
        planes_out.extend(rmse_block)
        for ref, val in zip(mom_refs, planes_out):
            ref[...] = val


def fused_moment_sweep(q: jax.Array, cand: jax.Array,
                       src_valid: jax.Array | None = None,
                       cand_normals: jax.Array | None = None, *,
                       gate: float, robust_kernel: str = "none",
                       robust_scale: float = 0.5,
                       bn: int = 256, bc: int = 128,
                       prune: bool = False, prune_margin: float = 1.1,
                       interpret: bool | None = None) -> dict:
    """One fused candidate pass: NN min + gate + IRLS weight + moments.

    Args:
      q: (N, 3) transformed source points (the iteration's queries).
      cand: (N, CK, 3) candidate coordinates (masked slots = sentinel), or
        the same as three (N, CK) planes (``gather_candidate_planes``).
      src_valid: optional (N,) bool/float mask; invalid rows get weight 0.
      cand_normals: candidate normals, shaped like ``cand`` — required for
        (and selects) the point-to-plane moment set; invalid slots must
        be 0.
      gate / robust_kernel / robust_scale: the ``ICPParams`` weighting
        (static — they specialise the kernel).
      bn / bc / prune / prune_margin: kernel config (see ``FusedConfig``).

    Returns:
      dict mapping :func:`moment_names` to scalar fp32 sums over all N
      queries (padded rows contribute zero by construction).
    """
    plane = cand_normals is not None
    n = q.shape[0]
    ck = (cand[0] if isinstance(cand, tuple) else cand).shape[1]
    n_pad, ck_pad = round_up(n, bn), round_up(ck, bc)

    def kernel_planes(x, fill):
        # -> three padded (CK, N) f32 planes, candidates on sublanes
        planes = x if isinstance(x, tuple) else tuple(
            x[..., a] for a in range(3))
        return [jnp.pad(p.astype(jnp.float32),
                        ((0, n_pad - n), (0, ck_pad - ck)),
                        constant_values=fill).T for p in planes]

    qf = jnp.pad(q.astype(jnp.float32), ((0, n_pad - n), (0, 0)))
    sv = (jnp.ones((n,), jnp.float32) if src_valid is None
          else src_valid.astype(jnp.float32))
    sv = jnp.pad(sv, (0, n_pad - n))
    grid = (n_pad // bn, ck_pad // bc)
    inputs = [*query_rows(qf), sv[None, :],
              *kernel_planes(cand, _MASK_COORD)]
    if plane:
        inputs += kernel_planes(cand_normals, 0.0)
    names = moment_names(plane)
    n_out = _carry_count(plane) + len(names)
    kernel = functools.partial(
        _fused_kernel, bc=bc, nc=grid[1], gate2=float(gate) ** 2,
        prune_gate2=(float(gate) * float(prune_margin)) ** 2,
        robust=robust_kernel, scale=float(robust_scale),
        plane=plane, prune=prune)
    vspec = pl.BlockSpec((1, bn), lambda i, j: (0, i))
    cspec = pl.BlockSpec((bc, bn), lambda i, j: (j, i))
    in_specs = [vspec] * 4 + [cspec] * (6 if plane else 3)
    call = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=tuple(vspec for _ in range(n_out)),
        out_shape=tuple(jax.ShapeDtypeStruct((1, n_pad), jnp.float32)
                        for _ in range(n_out)),
        **pallas_call_kwargs(interpret, ("parallel", "arbitrary")),
    )
    outs = call(*inputs)
    mom_planes = outs[_carry_count(plane):]
    # Padded rows carry sv = 0 ⇒ zero moments, so the sum runs full-width
    # (one fused XLA reduction per plane — the host epilogue's only O(N)).
    return {name: jnp.sum(p) for name, p in zip(names, mom_planes)}


class PointMoments(NamedTuple):
    """Σ-moments of one point-to-point iteration (fused-kernel output)."""
    sw: jax.Array          # Σw
    sp: jax.Array          # (3,) Σw·p
    sq: jax.Array          # (3,) Σw·q
    spq: jax.Array         # (3,3) Σw·p⊗q (raw, uncentred)
    spp: jax.Array         # Σw·|p|²
    sqq: jax.Array         # Σw·|q|²


class PlaneMoments(NamedTuple):
    """Σ-moments of one point-to-plane iteration (fused-kernel output)."""
    sw: jax.Array
    A: jax.Array           # (6,6) Σw·a⊗a, a = [p×n; n]
    b: jax.Array           # (6,) −Σw·r·a (the GN right-hand side)
    sp: jax.Array
    sq: jax.Array
    spq: jax.Array
    spp: jax.Array
    sqq: jax.Array


def _rmse_moments(s: dict):
    sp = jnp.stack([s["px"], s["py"], s["pz"]])
    sq = jnp.stack([s["qx"], s["qy"], s["qz"]])
    spq = jnp.stack([
        jnp.stack([s[f"pq{i}{j}"] for j in range(3)]) for i in range(3)])
    return sp, sq, spq, s["pp"], s["qq"]


def _assemble(s: dict, plane: bool):
    sp, sq, spq, spp, sqq = _rmse_moments(s)
    if not plane:
        return PointMoments(sw=s["w"], sp=sp, sq=sq, spq=spq,
                            spp=spp, sqq=sqq)
    A = jnp.zeros((6, 6), jnp.float32)
    for k in range(6):
        for li in range(k, 6):
            A = A.at[k, li].set(s[f"a{k}{li}"])
            A = A.at[li, k].set(s[f"a{k}{li}"])
    b = -jnp.stack([s[f"ra{k}"] for k in range(6)])
    return PlaneMoments(sw=s["w"], A=A, b=b, sp=sp, sq=sq, spq=spq,
                        spp=spp, sqq=sqq)


def make_fused_fn(grid: VoxelGrid, params, target_normals=None, *,
                  max_per_cell: int = 32, rings: int = 1,
                  bn: int | None = None, bc: int | None = None,
                  prune: bool | None = None,
                  prune_margin: float | None = None,
                  interpret: bool | None = None):
    """Resident-grid fused iteration: ``fused_fn(src_t, src_valid)`` →
    :class:`PointMoments` / :class:`PlaneMoments`.

    Like ``grid_nn_fn``, the voxel grid (and the target normals, for the
    plane minimiser) live at trace scope; per iteration only the
    candidate gather + the single fused pass run. ``params`` is an
    ``core.icp.ICPParams`` (gate / minimizer / robust fields are baked
    into the kernel as static config).
    """
    cfg = DEFAULT_CONFIG
    bn = cfg.bn if bn is None else bn
    bc = cfg.bc if bc is None else bc
    prune = cfg.prune if prune is None else prune
    prune_margin = cfg.prune_margin if prune_margin is None else prune_margin
    plane = params.minimizer == "point_to_plane"
    if plane and target_normals is None:
        raise ValueError("minimizer='point_to_plane' needs target_normals "
                         "for the fused iteration (the kernel streams them "
                         "as candidate payload)")

    def fused_fn(src_t: jax.Array, src_valid: jax.Array | None = None):
        cand_pts, cand_idx, cand_valid = gather_candidate_planes(
            src_t, grid, max_per_cell, rings)
        cand_n = None
        if plane:
            cand_n = tuple(
                jnp.where(cand_valid, target_normals[:, a][cand_idx], 0.0)
                for a in range(3))
        sums = fused_moment_sweep(
            src_t, cand_pts, src_valid, cand_n,
            gate=params.max_correspondence_distance,
            robust_kernel=params.robust_kernel,
            robust_scale=params.robust_scale,
            bn=bn, bc=bc, prune=prune, prune_margin=prune_margin,
            interpret=interpret)
        return _assemble(sums, plane)

    return fused_fn


def default_fused_fn(target: jax.Array, params, *,
                     dst_valid: jax.Array | None = None,
                     target_normals: jax.Array | None = None,
                     grid_dims: tuple[int, int, int] = DEFAULT_GRID_DIMS,
                     grid_voxel: float | None = None,
                     max_per_cell: int = 32, rings: int = 1,
                     **kw):
    """Build the fused iteration for a raw target cloud: counting-sort
    grid at trace scope (voxel ≥ gate ⇒ every gate-passing correspondence
    is found, same exactness rule as the pyramid polish), then
    :func:`make_fused_fn`."""
    gv = (float(grid_voxel) if grid_voxel is not None
          else max(1.0, params.max_correspondence_distance))
    grid = build_voxel_grid(target.astype(jnp.float32), gv, grid_dims,
                            valid=dst_valid)
    return make_fused_fn(grid, params, target_normals,
                         max_per_cell=max_per_cell, rings=rings, **kw)


# -- static resource / roofline model (Table II analogue) -------------------

def fused_vmem_bytes(bn: int, bc: int, *, plane: bool = False,
                     prune: bool = False) -> dict:
    """Static VMEM budget of one fused grid step."""
    query = 4 * bn * 4                       # qx, qy, qz, sv
    cand = (6 if plane else 3) * bn * bc * 4  # coordinate (+normal) planes
    d2 = bn * bc * (2 if prune else 4)       # distance screen scratch
    carries = _carry_count(plane) * bn * 4
    moments = len(moment_names(plane)) * bn * 4
    total = query + cand + d2 + carries + moments
    return {
        "query_tile": query, "cand_tile": cand, "d2_scratch": d2,
        "carries": carries, "moment_planes": moments,
        "total_single": total,
        # in/out tiles double-buffered by the grid pipeline; d2 is scratch
        "total_double_buffered": 2 * (query + cand + carries + moments) + d2,
    }


def fused_cost_model(n: int, ck: int, *, plane: bool = False) -> dict:
    """FLOP / HBM-byte totals of one iteration: fused pass vs the
    separate-op chain (sweep kernel → winner gather → weight → moment
    matmuls). Both include the XLA-side candidate gather write+read; the
    chain additionally round-trips the winner/weight intermediates and
    re-reads the candidate matrix for the winner gather.
    """
    planes = 6 if plane else 3
    pmoms = len(moment_names(plane))
    dist_flops = 8 * n * ck                    # diff, square, add, min-tree
    select_flops = (2 + planes) * n * ck       # one-hot select reductions
    epilogue_flops = (160 if plane else 60) * n
    cand_bytes = planes * n * ck * 4
    fused = {
        "flops": dist_flops + select_flops + epilogue_flops,
        "hbm_bytes": (2 * cand_bytes            # gather write + kernel read
                      + 4 * n * 4               # queries + src_valid
                      + pmoms * n * 4),         # moment planes out
    }
    chain = {
        "flops": dist_flops + epilogue_flops + 2 * 3 * n * 3,  # + cov matmul
        "hbm_bytes": (2 * cand_bytes            # gather write + sweep read
                      + cand_bytes              # winner-gather re-read
                      + 3 * n * 4               # queries
                      + 2 * (n * 4 + n * 4)     # (d2, slot) out + re-read
                      + 6 * (3 * n * 4)),       # matched/weight/moment passes
    }
    for d in (fused, chain):
        d["flop_per_byte"] = d["flops"] / d["hbm_bytes"]
    return {"fused": fused, "chain": chain,
            "hbm_ratio": chain["hbm_bytes"] / fused["hbm_bytes"]}
