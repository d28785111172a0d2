"""Int8 error-feedback gradient compression for data-parallel all-reduce.

The distributed-optimization trick (1-bit-Adam / PowerSGD family, int8
variant): inside a shard_map data-parallel region, replace the fp32 ring
all-reduce of gradients with

    1. add the error-feedback residual from the previous step,
    2. quantize to int8 with a per-tensor scale,
    3. REDUCE via all_to_all: each device sums one 1/n chunk
       (wire: ~1 byte/elem instead of ~8),
    4. re-quantize the summed chunk, all_gather int8 chunks back
       (wire: ~1 byte/elem),
    5. dequantize; keep (local_grad - dequant(local_quant)) as the new
       error-feedback residual so quantization error accumulates into the
       next step instead of being lost.

Net wire bytes ≈ 2/8 = 4x less than fp32 ring all-reduce. Error feedback
makes the *accumulated* gradient unbiased — convergence matches fp32 within
noise (tests/test_grad_compression.py trains a model both ways).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_EPS = 1e-12


def _quantize(g: jax.Array):
    scale = jnp.maximum(jnp.max(jnp.abs(g)), _EPS) / 127.0
    q = jnp.clip(jnp.round(g / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def _dequantize(q: jax.Array, scale: jax.Array):
    return q.astype(jnp.float32) * scale


def compressed_psum_mean(g: jax.Array, axis: str, ef: jax.Array):
    """Mean-reduce ``g`` over mesh axis ``axis`` with int8 compression.

    g: fp32 array (any shape; padded internally to n_dev chunks);
    ef: error-feedback residual, same shape. Returns (g_mean, new_ef).
    Must run inside shard_map with ``axis`` manual."""
    n = jax.lax.axis_size(axis)
    shape = g.shape
    orig = 1
    for d in shape:
        orig *= d
    flat = (g + ef).reshape(-1)
    pad = (-flat.shape[0]) % n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    q, scale = _quantize(flat)
    new_ef = (flat - _dequantize(q, scale))[:orig].reshape(shape)
    # 3. all_to_all: device j receives everyone's chunk j -> sum locally
    chunks = q.reshape(n, -1)
    recv = jax.lax.all_to_all(chunks, axis, split_axis=0,
                              concat_axis=0)                     # (n, chunk)
    recv_scales = jax.lax.all_gather(scale, axis)                # (n,)
    summed = jnp.sum(recv.astype(jnp.float32)
                     * recv_scales[:, None], axis=0) / n         # mean chunk
    # 4. re-quantize my chunk, gather all chunks
    q2, s2 = _quantize(summed)
    all_q = jax.lax.all_gather(q2, axis)                         # (n, chunk)
    all_s = jax.lax.all_gather(s2, axis)                         # (n,)
    out = (all_q.astype(jnp.float32) * all_s[:, None]).reshape(-1)
    return out[:orig].reshape(shape), new_ef


def init_error_feedback(params):
    return jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params)


def compressed_grad_reduce(grads, axis: str, ef_state):
    """Tree-wise compressed mean-reduction. Returns (grads_mean, new_ef)."""
    flat_g, treedef = jax.tree_util.tree_flatten(grads)
    flat_e = jax.tree_util.tree_leaves(ef_state)
    outs, efs = [], []
    for g, e in zip(flat_g, flat_e):
        gm, ne = compressed_psum_mean(g.astype(jnp.float32), axis, e)
        outs.append(gm)
        efs.append(ne)
    return (jax.tree_util.tree_unflatten(treedef, outs),
            jax.tree_util.tree_unflatten(treedef, efs))
