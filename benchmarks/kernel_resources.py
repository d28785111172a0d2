"""Paper Table II analogue: accelerator resource budget.

FPGA resources (LUT/FF/BRAM/DSP) map to the TPU kernel's static budget:
VMEM bytes per pipeline stage (BRAM analogue), the VPU operations a
candidate costs (DSP analogue), and the kernel's grid/pipelining
configuration. All numbers are
static properties of the BlockSpec tiling — the same table a kernel author
reads before committing a design.

Also times the kernel (interpret mode) against the ref oracle at paper
scale to document functional throughput parity on this container.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import emit, timeit
from repro.kernels.nn_search import AUG_ROWS, target_block, vmem_bytes
from repro.kernels.ops import nn_search_pallas
from repro.kernels.ref import nn_search_ref
from repro.roofline.report import V5E

VMEM_V5E = 128 * 2 ** 20  # ~128 MiB/core
SCORE_OPS = 6


def run():
    rows = []
    bn, bm = 512, target_block(False)
    b = vmem_bytes(bn, bm)
    for stage, size in b.items():
        rows.append((f"table2/vmem_{stage}", 0.0,
                     f"{size} B ({size / VMEM_V5E * 100:.2f}% of VMEM)"))
    # The DSP analogue: the score is elementwise float32 VPU work, three
    # multiplies and three adds a candidate; no MXU pass.
    rows.append(("table2/vpu_score_ops_per_candidate", 0.0,
                 f"{SCORE_OPS} (expansion less ||p'||^2, float32)"))
    rows.append(("table2/grid_tiles_per_130k_frame", 0.0,
                 f"{(4096 // bn) * (131072 // bm)} (bn={bn}, bm={bm})"))
    # arithmetic intensity of the kernel hot loop (per target element):
    ai = (SCORE_OPS * bn) / (AUG_ROWS * 4)  # flops per target byte
    rows.append(("table2/arithmetic_intensity", 0.0,
                 f"{ai:.0f} flop/byte vs v5e ridge "
                 f"{V5E['peak_flops_bf16'] / V5E['hbm_bw']:.0f}"))
    # Fused ICP-iteration kernel (DESIGN.md §11): VMEM footprint of the
    # tuned config and the FLOP/byte win over the separate-op chain — the
    # "why fusion is fast" numbers, not just the timings.
    from repro.kernels.fused_icp import (DEFAULT_CONFIG, fused_cost_model,
                                         fused_vmem_bytes)
    cfg = DEFAULT_CONFIG
    for plane, tag in ((False, "p2p"), (True, "p2plane")):
        fb = fused_vmem_bytes(cfg.bn, cfg.bc, plane=plane, prune=cfg.prune)
        rows.append((f"table2/fused_{tag}_vmem_double_buffered", 0.0,
                     f"{fb['total_double_buffered']} B "
                     f"({fb['total_double_buffered'] / VMEM_V5E * 100:.2f}% "
                     f"of VMEM; bn={cfg.bn},bc={cfg.bc})"))
    cost = fused_cost_model(4096, 27 * 32)  # 27-cell hood, max_per_cell=32
    rows.append(("table2/fused_flop_per_byte", 0.0,
                 f"{cost['fused']['flop_per_byte']:.2f} fused vs "
                 f"{cost['chain']['flop_per_byte']:.2f} chain "
                 f"(hbm_ratio={cost['hbm_ratio']:.2f}x)"))
    # functional check at paper scale (1 source point vs 130k candidates,
    # interpret mode on CPU — correctness, not speed)
    key = jax.random.PRNGKey(0)
    src = jax.random.uniform(key, (128, 3), minval=-50, maxval=50)
    dst = jax.random.uniform(jax.random.fold_in(key, 1), (131072, 3),
                             minval=-50, maxval=50)
    t = timeit(lambda: nn_search_pallas(src, dst, None, interpret=True),
               warmup=1, iters=1)
    d2k, idxk = nn_search_pallas(src, dst, None, interpret=True)
    d2r, idxr = nn_search_ref(src, dst)
    match = float(jnp.mean((idxk == idxr).astype(jnp.float32)))
    rows.append(("table2/kernel_interpret_128x131072", t * 1e6,
                 f"idx_match={match:.4f}"))
    return rows


if __name__ == "__main__":
    emit(run())
