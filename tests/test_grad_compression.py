"""int8 error-feedback DP gradient compression (subprocess: 8 devices)."""
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.mark.slow
def test_grad_compression_subprocess():
    proc = subprocess.run(
        [sys.executable, str(ROOT / "tests" / "grad_compression_worker.py")],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": str(ROOT / "src")},
    )
    assert proc.returncode == 0, f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    assert "GRAD-COMPRESSION-OK" in proc.stdout
