"""The main-path Pallas kernels and the fleet round compile for a TPU v5e
at deployment widths, against a described ``v5e:2x2`` topology — the
chip's own compiler, with no chip attached.

Nothing runs, so these say nothing about results or time: they catch what
interpret mode cannot (block layouts, VMEM, device memory). The topology
is described inside a module fixture, never at import: only one process
at a time may load the TPU library, and every test worker imports every
test file. Keep these tests in this one file for the same reason.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import ICPParams
from repro.core.engine import PallasEngine, SlotEngine
from repro.core.odometry import OdometryConfig
from repro.kernels.fused_icp import fused_moment_sweep
from repro.kernels import nn_search
from repro.kernels.nn_search import KERNEL_NAME, target_block, vmem_bytes
from repro.kernels.nn_search_grid import candidate_sweep_kernel
from repro.kernels.normals import moment_sweep_kernel
from repro.kernels.ops import nn_search_pallas

V5E_HBM_BYTES = 16 * 10**9
# VMEM the chip's compiler keeps for itself in the NN kernel (80 KiB at
# every tile size tried), on top of what ``vmem_bytes`` counts.
COMPILER_VMEM = 128 * 2**10


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    # A compile for a described chip can be written to the persistent
    # cache but not read back without one: keep the cache out of it.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("TPU_LOG_DIR", "disabled")
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
    except Exception as e:  # any failure means the topology is unavailable
        jax.config.update("jax_enable_compilation_cache", enabled)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compile(fn, one_chip, *shapes):
    """Compile ``fn`` for the described chip; ``shapes`` are
    ``(shape, dtype)`` pairs or None."""
    args = [None if s is None else
            jax.ShapeDtypeStruct(s[0], s[1], sharding=one_chip)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile()


F32, BOOL = jnp.float32, jnp.bool_


def test_brute_nn_kernel_compiles(one_chip):
    c = _compile(lambda s, d: nn_search_pallas(s, d, interpret=False),
                 one_chip, ((4096, 3), F32), ((32768, 3), F32))
    assert "tpu_custom_call" in c.as_text()


def test_nn_kernel_vmem_budget_matches_its_layout(one_chip, monkeypatch):
    """``vmem_bytes`` counts what the NN kernel's layout takes at its
    default tiles: the double-buffered blocks and the scratch. Held to
    that count and the compiler's own room, the chip's compiler accepts
    the kernel at the pair cell's widths; held to the count alone, it
    refuses it."""
    from jax.experimental.pallas import tpu as pltpu
    budget = vmem_bytes(512, target_block(False))["total_double_buffered"]

    def compile_within(limit):
        def kwargs(interpret, semantics):
            return {"interpret": False,
                    "compiler_params": pltpu.CompilerParams(
                        dimension_semantics=semantics,
                        vmem_limit_bytes=limit)}
        monkeypatch.setattr(nn_search, "pallas_call_kwargs", kwargs)
        return _compile(lambda s, d: nn_search.nn_search_kernel(
            s, d, interpret=False), one_chip, ((8, 4096), F32),
            ((8, 262144), F32))

    assert "tpu_custom_call" in compile_within(
        budget + COMPILER_VMEM).as_text()
    with pytest.raises(Exception, match="memory space vmem"):
        compile_within(budget)


def test_grid_sweep_kernel_compiles(one_chip):
    c = _compile(lambda q, k: candidate_sweep_kernel(q, k, interpret=False),
                 one_chip, ((8192, 3), F32), ((8192, 256, 3), F32))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("plane", [False, True], ids=["p2p", "p2plane"])
def test_fused_kernel_compiles(one_chip, plane):
    def fn(q, k, n):
        return fused_moment_sweep(q, k, None, n, gate=1.0, interpret=False)

    c = _compile(fn, one_chip, ((8192, 3), F32), ((8192, 256, 3), F32),
                 ((8192, 256, 3), F32) if plane else None)
    assert "tpu_custom_call" in c.as_text()


def test_normals_kernel_compiles(one_chip):
    c = _compile(lambda q, k: moment_sweep_kernel(q, k, 1.0,
                                                  interpret=False),
                 one_chip, ((32768, 3), F32), ((32768, 128, 3), F32))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("fused", [False, True], ids=["brute", "fused"])
def test_pallas_engine_batch_compiles(one_chip, fused):
    """The batch executable vmaps the kernels over frame pairs, which adds
    a leading block dimension to every operand."""
    params = ICPParams(max_correspondence_distance=1.0, fused=fused)
    run = PallasEngine(interpret=False)._build_batch(params)
    c = _compile(run, one_chip, ((4, 4096, 3), F32), ((4, 32768, 3), F32),
                 None, ((4, 4096), BOOL), ((4, 32768), BOOL))
    assert "tpu_custom_call" in c.as_text()


def test_pairs_batch_at_the_pair_cells_widths_fits_v5e(one_chip):
    """The pallas engine's batch executable at the pair benchmark's
    widths (8 pairs of 4096 sampled points onto targets padded to
    262144 rows, its largest bucket, 50 iterations) fits one chip, and
    its NN kernel compiles at the default tiles into one custom call
    that carries the name a trace reads it by."""
    params = ICPParams(max_iterations=50, max_correspondence_distance=1.0)
    run = PallasEngine(interpret=False)._build_batch(params)
    n, m = 4096, 262144
    c = _compile(run, one_chip, ((8, n, 3), F32), ((8, m, 3), F32), None,
                 ((8, n), BOOL), ((8, m), BOOL))
    mem = c.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < V5E_HBM_BYTES, mem
    call = [line for line in c.as_text().splitlines()
            if f"%{KERNEL_NAME}." in line and "custom-call" in line]
    assert len(call) == 1, call
    # The chip's default target block divides the bucket, so the resident
    # target enters the kernel at its own width, 8 pairs of 8 rows.
    assert m % target_block(False) == 0
    assert f"f32[8,8,{m}]" in call[0], call[0][:400]


def test_slot_fleet_round_fits_v5e(one_chip):
    """The served 8-lane registration round at the ``OdometryConfig``
    defaults (8192 scan points x 24576 map rows) fits one chip, and so
    does the lattice search at the fleet benchmark's widths: 32768 scan
    points a lane against a 32768-row map on a (128, 128, 32) lattice,
    in under half the sweep's temporaries there (2.31 GB)."""
    odo = OdometryConfig()
    n, m, s = odo.scan_budget, odo.submap.capacity, 8
    run = SlotEngine(slots=s)._build_batch(odo.params)
    c = _compile(run, one_chip, ((s, n, 3), F32), ((s, m, 3), F32),
                 ((s, 4, 4), F32), ((s, n), BOOL), ((s, m), BOOL))
    mem = c.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < V5E_HBM_BYTES, mem
    n = m = 32768
    sub = odo.submap._replace(capacity=m, dims=(128, 128, 32))
    src, dst, T0, sv, dv, origin = (
        jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
        for shape, dtype in (((s, n, 3), F32), ((s, m, 3), F32),
                             ((s, 4, 4), F32), ((s, n), BOOL),
                             ((s, m), BOOL), ((s, 3), F32)))
    c = jax.jit(run).lower(src, sub.voxel_map(dst, origin), T0, sv,
                           dv).compile()
    mem = c.memory_analysis()
    assert mem.temp_size_in_bytes < 2.31e9 / 2, mem
