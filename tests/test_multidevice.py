"""Device-sharded serving tests run in a subprocess so the 8-device
host-platform fleet never leaks into this interpreter (the tier-1 sharded
tests in test_service.py must see 1 device)."""
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.mark.slow
@pytest.mark.multidevice
def test_sharded_service_multidevice_subprocess():
    proc = subprocess.run(
        [sys.executable, str(ROOT / "tests" / "multidevice_worker.py")],
        capture_output=True, text=True, timeout=900,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": str(ROOT / "src")},
    )
    assert proc.returncode == 0, f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    assert "MULTIDEVICE-OK" in proc.stdout
