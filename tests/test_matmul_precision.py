"""Every matmul on the registration path asks for float32 precision.

On a TPU a float32 matmul at the default precision rounds its operands to
bfloat16: at 50 m from the sensor that is a 0.1 m error, and half the
nearest-neighbour ranks flip. The CPU computes f32 either way, so the
guard reads the precision each executable requests from its lowering.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.core import ICPParams
from repro.core.engine import PallasEngine, SlotEngine, XLAEngine, get_engine
from repro.core.odometry import _scan_plane_system
from repro.data.normals import NormalParams
from repro.data.submap import (SubmapParams, empty_state, fuse_lanes,
                               outside_lattice_fracs)

S, F, B = jax.ShapeDtypeStruct, jnp.float32, jnp.bool_
N, M, L = 256, 1024, 2
SUB = SubmapParams(capacity=M, dims=(32, 32, 8))


def _engines():
    return {
        "xla": XLAEngine(),
        "pallas": PallasEngine(interpret=True),
        "slots": SlotEngine(slots=L),
        "pyramid": get_engine("pyramid"),
    }


def _default_dots(lowered) -> list[str]:
    return [line.strip() for line in lowered.as_text().splitlines()
            if "dot_general" in line and "HIGHEST, HIGHEST" not in line]


@pytest.mark.parametrize("minimizer", ["point_to_point", "point_to_plane"])
@pytest.mark.parametrize("engine", ["xla", "pallas", "pallas-fused", "slots",
                                    "pyramid"])
def test_registration_executables_request_f32_matmuls(engine, minimizer):
    name, _, fused = engine.partition("-")
    params = ICPParams(max_iterations=2, minimizer=minimizer,
                       robust_kernel="huber", chunk=512, fused=bool(fused))
    run = _engines()[name]._build_batch(params)
    lowered = run.lower(S((L, N, 3), F), S((L, M, 3), F), S((L, 4, 4), F),
                        S((L, N), B), S((L, M), B))
    assert _default_dots(lowered) == []


def test_service_round_executables_request_f32_matmuls():
    state = tuple(S((L,) + leaf.shape, leaf.dtype)
                  for leaf in empty_state(SUB))
    lowered = [
        outside_lattice_fracs.lower(S((L, 4, 4), F), S((L, N, 3), F),
                                    S((L, N), B), S((L, 3), F), SUB),
        fuse_lanes.lower(state, S((L, N, 3), F), S((L, N), B),
                         S((L, 4, 4), F), S((L,), B), SUB),
        _scan_plane_system.lower(S((N, 3), F), S((N,), B),
                                 NormalParams(neighborhood="radius")),
    ]
    assert [d for low in lowered for d in _default_dots(low)] == []
