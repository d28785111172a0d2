"""Subprocess worker: int8 error-feedback gradient compression on an
8-device data-parallel mesh. Checks (1) a single compressed reduction is
close to the exact mean and unbiased over steps thanks to error feedback,
(2) end-to-end DP training with compression tracks uncompressed training."""
import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")

import functools  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.compat import make_mesh  # noqa: E402
from repro.optim.compression import (compressed_grad_reduce,  # noqa: E402
                                     init_error_feedback)


def main():
    mesh = make_mesh((8,), ("data",))

    # --- 1. single reduction approximates the exact mean ------------------
    key = jax.random.PRNGKey(0)
    gs = jax.random.normal(key, (8, 1000))  # per-device gradients

    def reduce_once(g, ef):
        out, new_ef = compressed_grad_reduce({"g": g}, "data", {"g": ef})
        return out["g"], new_ef["g"]

    fn = jax.shard_map(reduce_once, mesh=mesh,
                       in_specs=(P("data"), P("data")), out_specs=(P("data"),
                                                               P("data")),
                       check_vma=False)
    g_in = gs.reshape(8000)
    out, ef = fn(g_in, jnp.zeros(8000))
    exact = jnp.mean(gs, axis=0)
    approx = out.reshape(8, 1000)[0]
    rel = float(jnp.linalg.norm(approx - exact) / jnp.linalg.norm(exact))
    print("single-step rel err:", rel)
    assert rel < 0.02, rel  # int8: ~1% quantization noise

    # --- 2. error feedback: accumulated mean over steps is ~unbiased ------
    accum_c = jnp.zeros(1000)
    accum_e = jnp.zeros(1000)
    ef = jnp.zeros(8000)
    for step in range(20):
        gstep = jax.random.normal(jax.random.PRNGKey(step), (8, 1000)) + 0.3
        out, ef = fn(gstep.reshape(8000), ef)
        accum_c = accum_c + out.reshape(8, 1000)[0]
        accum_e = accum_e + jnp.mean(gstep, axis=0)
    rel_acc = float(jnp.linalg.norm(accum_c - accum_e)
                    / jnp.linalg.norm(accum_e))
    print("20-step accumulated rel err:", rel_acc)
    assert rel_acc < 0.02, rel_acc

    # --- 3. end-to-end: compressed DP training tracks fp32 ----------------
    def loss_fn(w, x, y):
        pred = jnp.tanh(x @ w["a"]) @ w["b"]
        return jnp.mean((pred - y) ** 2)

    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(7), 3)
    w0 = {"a": 0.1 * jax.random.normal(k1, (16, 32)),
          "b": 0.1 * jax.random.normal(k2, (32, 4))}
    X = jax.random.normal(k3, (64, 16))
    Y = jnp.tanh(X[:, :4]) * 0.5

    def dp_step(w, ef, x, y, compress):
        g = jax.grad(loss_fn)(w, x, y)
        if compress:
            g, ef = compressed_grad_reduce(g, "data", ef)
        else:
            g = jax.tree_util.tree_map(
                lambda t: jax.lax.pmean(t, "data"), g)
        w = jax.tree_util.tree_map(lambda p, gg: p - 0.2 * gg, w, g)
        return w, ef

    def run(compress):
        step = jax.shard_map(
            functools.partial(dp_step, compress=compress), mesh=mesh,
            in_specs=(P(), {"a": P(), "b": P()}, P("data"), P("data")),
            out_specs=(P(), {"a": P(), "b": P()}), check_vma=False)
        w = jax.tree_util.tree_map(jnp.array, w0)
        ef = init_error_feedback(w)
        for _ in range(40):
            w, ef = step(w, ef, X, Y)
        return float(loss_fn(w, X, Y))

    l_fp32 = run(False)
    l_int8 = run(True)
    print(f"final loss fp32={l_fp32:.5f} int8={l_int8:.5f}")
    assert l_int8 < 1.5 * l_fp32 + 1e-3, (l_fp32, l_int8)
    print("GRAD-COMPRESSION-OK")


if __name__ == "__main__":
    main()
