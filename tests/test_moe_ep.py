"""shard_map expert-parallel MoE == dense reference (subprocess: 8 devices)."""
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.mark.slow
def test_moe_ep_subprocess():
    proc = subprocess.run(
        [sys.executable, str(ROOT / "tests" / "moe_ep_worker.py")],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": str(ROOT / "src")},
    )
    assert proc.returncode == 0, f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    assert "MOE-EP-OK" in proc.stdout
