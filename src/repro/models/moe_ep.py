"""Explicit expert-parallel MoE: shard_map local-sort + all-to-all.

Why this exists (§Perf iteration B3): the single-program sort-based
dispatch in ``moe.py`` leaves the token shuffle to GSPMD, which resolves
the data-sharded-tokens -> expert-sharded-buffer scatter with replicated
all-reduces of the full pair-expanded activations (measured: 28 s
collective term on deepseek-moe-16b/train_4k; forcing buffer shardings
made it 123 s). The production pattern (GShard/DeepSpeed-MoE) is explicit:

  per device (tokens local over the data axes, experts local over model):
    1. route + sort my tokens into an (E, C_src, d) send buffer,
    2. all_to_all over the expert axis: send slab e to expert-owner(e),
       receive my experts' slabs from every token shard,
    3. dense local expert GEMMs on (E_loc, S_src*C_src, d),
    4. reverse all_to_all, weighted combine back to my tokens.

Collective volume per layer ≈ 2 x T_loc*k*cf*d — the all-to-all the
algorithm actually requires, nothing more. Differentiates cleanly
(shard_map transposes the collectives).

Capacity note: C_src is per (source shard, expert); overflow drops follow
the same semantics as the gspmd_sort path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models.moe import MoEConfig, capacity, route


def _local_dispatch(flat, weights, idx, e: int, c: int):
    """Sort local tokens into (E, C, d) slabs. Returns (buf, combine info)."""
    t, d = flat.shape
    k = idx.shape[1]
    pair_e = idx.reshape(t * k)
    pair_t = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    pair_w = weights.reshape(t * k)
    order = jnp.argsort(pair_e)
    se, st_tok, sw = pair_e[order], pair_t[order], pair_w[order]
    counts = jnp.bincount(pair_e, length=e)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(t * k, dtype=jnp.int32) - starts[se].astype(jnp.int32)
    keep = pos < c
    slot = jnp.where(keep, se * c + pos, e * c)
    buf = jnp.zeros((e * c + 1, d), flat.dtype).at[slot].set(flat[st_tok])
    return buf[:e * c].reshape(e, c, d), (se, st_tok, sw, pos, keep)


def _local_combine(h, info, t: int, c: int):
    se, st_tok, sw, pos, keep = info
    d = h.shape[-1]
    rows = h.reshape(-1, d)[jnp.where(keep, se * c + pos, 0)]
    rows = rows * (sw * keep).astype(rows.dtype)[:, None]
    return jnp.zeros((t, d), rows.dtype).at[st_tok].add(rows)


def moe_forward_ep(p, x: jax.Array, cfg: MoEConfig, mesh, rules):
    """shard_map expert-parallel forward. x: (B,S,D) -> (out, metrics)."""
    expert_axes = rules.get("expert") or ()
    expert_axes = ((expert_axes,) if isinstance(expert_axes, str)
                   else tuple(expert_axes))
    token_axes = tuple(rules.get("tokens") or ())
    assert len(expert_axes) == 1, "EP wants exactly one expert axis"
    ax = expert_axes[0]
    n_ep = mesh.shape[ax]
    fsdp_axes = rules.get("fsdp") or ()
    fsdp_axes = ((fsdp_axes,) if isinstance(fsdp_axes, str)
                 else tuple(fsdp_axes))
    fsdp = tuple(a for a in fsdp_axes if a in mesh.axis_names and a != ax)
    e = cfg.n_experts
    assert e % n_ep == 0, (e, n_ep)

    from jax.sharding import PartitionSpec as P

    def body(xb, router_k, wi, wg, wo):
        # xb: (B_loc, S_loc, d) — batch sharded over the token (data) axes,
        # sequence sharded over the expert axis (a free local slice: the
        # activations were replicated along it), so every device routes a
        # distinct token set.
        b, s, d = xb.shape
        t = b * s
        flat = xb.reshape(t, d)
        logits = flat.astype(jnp.float32) @ router_k.astype(jnp.float32)
        weights, idx, metrics = route(logits, cfg)
        c = capacity(t, cfg)
        buf, info = _local_dispatch(flat, weights, idx, e, c)   # (E,C,d)
        # all-to-all over the expert axis: dim0 E = n_ep * E_loc
        recv = jax.lax.all_to_all(
            buf.reshape(n_ep, e // n_ep, c, d), ax,
            split_axis=0, concat_axis=0, tiled=False)           # (n_ep,E/n_ep,C,d)
        mine = recv.transpose(1, 0, 2, 3).reshape(
            e // n_ep, n_ep * c, d)                             # (E_loc, n_ep*C, d)
        # FSDP gather of my experts' weights
        if fsdp:
            for a in fsdp:
                wi = jax.lax.all_gather(wi, a, axis=1, tiled=True)
                wg = jax.lax.all_gather(wg, a, axis=1, tiled=True)
                wo = jax.lax.all_gather(wo, a, axis=2, tiled=True)
        xb16 = mine.astype(jnp.bfloat16)
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xb16,
                                   wg.astype(jnp.bfloat16)))
        h = h * jnp.einsum("ecd,edf->ecf", xb16, wi.astype(jnp.bfloat16))
        y = jnp.einsum("ecf,efd->ecd", h, wo.astype(jnp.bfloat16))
        # reverse all-to-all
        back = jax.lax.all_to_all(
            y.reshape(e // n_ep, n_ep, c, d).transpose(1, 0, 2, 3), ax,
            split_axis=0, concat_axis=0, tiled=False)           # (n_ep,E/n_ep,C,d)
        y_local = back.reshape(e, c, d)
        out = _local_combine(y_local, info, t, c)
        # aux metrics: average over every token-holding axis
        for a in tok + (ax,):
            metrics = {k: jax.lax.pmean(v, a) for k, v in metrics.items()}
        return out.reshape(b, s, d), metrics

    tok = tuple(a for a in token_axes if a in mesh.axis_names and a != ax)
    in_specs = (P(tok if tok else None, ax, None),       # x: (batch, seq, d)
                P(None, None),                           # router (replicated)
                P(ax, fsdp if fsdp else None, None),     # wi (E, d, f)
                P(ax, fsdp if fsdp else None, None),     # wg
                P(ax, None, fsdp if fsdp else None))     # wo (E, f, d)
    metrics_spec = {k: P() for k in
                    ("load_balance_loss", "router_z_loss")}
    out_specs = (P(tok if tok else None, ax, None), metrics_spec)
    fn = jax.shard_map(body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                       check_vma=False)
    out, metrics = fn(x, p["router"]["kernel"], p["wi"], p["wg"], p["wo"])
    metrics["dropped_frac"] = jnp.zeros((), jnp.float32)  # tracked locally
    metrics["moe_aux_total"] = (cfg.aux_loss_coef * metrics["load_balance_loss"]
                                + cfg.z_loss_coef * metrics["router_z_loss"])
    if cfg.n_shared_experts:
        out = out + L.swiglu(p["shared"], x.reshape(-1, x.shape[-1])).reshape(
            x.shape)
    return out, metrics
