"""Subprocess worker: validates shard_map ICP on an 8-device host mesh.

Run via tests/test_distributed.py — NOT imported by pytest directly (it must
set XLA_FLAGS before jax initialises, which would poison the main process).
Exits non-zero on any mismatch.
"""
import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.compat import make_mesh  # noqa: E402
from repro.core import ICPParams, icp_fixed_iterations  # noqa: E402
from repro.core.distributed import (batched_icp_sharded,  # noqa: E402
                                    distributed_nn_search, icp_sharded,
                                    shard_inputs)
from repro.core.nn_search import nn_search  # noqa: E402
from repro.core.transform import (random_rigid_transform,  # noqa: E402
                                  transform_points)


def main():
    assert jax.device_count() == 8, jax.devices()
    mesh = make_mesh((2, 4), ("data", "model"))

    key = jax.random.PRNGKey(0)

    # --- distributed NN == single-device NN -------------------------------
    k1, k2 = jax.random.split(key)
    src = jax.random.uniform(k1, (256, 3), minval=-20, maxval=20)
    dst = jax.random.uniform(k2, (4096, 3), minval=-20, maxval=20)
    d2_d, idx_d = distributed_nn_search(mesh, src, dst,
                                        target_axes=("data", "model"),
                                        chunk=256)
    d2_s, idx_s = nn_search(src, dst, chunk=256)
    np.testing.assert_allclose(np.asarray(d2_d), np.asarray(d2_s),
                               rtol=1e-4, atol=1e-4)
    mismatch = np.asarray(idx_d) != np.asarray(idx_s)
    if mismatch.any():  # fp ties only
        np.testing.assert_allclose(np.asarray(d2_d)[mismatch],
                                   np.asarray(d2_s)[mismatch], rtol=1e-4,
                                   atol=1e-4)
    print("distributed_nn_search OK")

    # --- giant-frame sharded ICP == single-device ICP ----------------------
    k1, k2, k3 = jax.random.split(key, 3)
    target = jax.random.uniform(k1, (2048, 3), minval=-10, maxval=10)
    T_gt = random_rigid_transform(k2, max_angle=0.1, max_translation=0.3)
    source = transform_points(jnp.linalg.inv(T_gt), target)
    source = source + 0.002 * jax.random.normal(k3, source.shape)
    params = ICPParams(max_iterations=20, chunk=256)
    res_d = icp_sharded(mesh, source, target, params,
                        target_axes=("data", "model"), fixed_iterations=True)
    res_s = icp_fixed_iterations(source, target, params)
    np.testing.assert_allclose(np.asarray(res_d.T), np.asarray(res_s.T),
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(res_d.T), np.asarray(T_gt),
                               atol=0.02)
    print("icp_sharded OK")

    # --- fleet mode: 4 frames over data axis, targets over model -----------
    keys = jax.random.split(jax.random.PRNGKey(42), 4)
    srcs, dsts, gts = [], [], []
    for k in keys:
        ka, kb, kc = jax.random.split(k, 3)
        tgt = jax.random.uniform(ka, (1024, 3), minval=-10, maxval=10)
        T = random_rigid_transform(kb, max_angle=0.1, max_translation=0.3)
        s = transform_points(jnp.linalg.inv(T), tgt)
        s = s + 0.002 * jax.random.normal(kc, s.shape)
        srcs.append(s)
        dsts.append(tgt)
        gts.append(T)
    src_b = jnp.stack(srcs)
    dst_b = jnp.stack(dsts)
    src_b, dst_b = shard_inputs(mesh, src_b, dst_b)
    res_b = batched_icp_sharded(mesh, src_b, dst_b, params,
                                frame_axes=("data",), target_axes=("model",))
    for i in range(4):
        ref = icp_fixed_iterations(srcs[i], dsts[i], params)
        np.testing.assert_allclose(np.asarray(res_b.T[i]), np.asarray(ref.T),
                                   atol=1e-4)
        np.testing.assert_allclose(np.asarray(res_b.T[i]), np.asarray(gts[i]),
                                   atol=0.02)
    print("batched_icp_sharded OK")


if __name__ == "__main__":
    main()
    print("ALL-DISTRIBUTED-OK")
