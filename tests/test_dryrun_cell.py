"""End-to-end dry-run regression: one real cell on the production 512-device
mesh, in a subprocess (slow; the full 84-cell sweep lives in results/)."""
import json
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.mark.slow
def test_dryrun_qwen2_decode_cell(tmp_path):
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "qwen2-0.5b",
         "--shape", "decode_32k", "--mesh", "single",
         "--out-dir", str(tmp_path)],
        capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": str(ROOT / "src")},
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    rec = json.loads((tmp_path / "qwen2-0.5b__decode_32k__single.json"
                      ).read_text())
    assert rec["status"] == "ok"
    assert rec["n_devices"] == 256
    assert rec["memory"]["fits_v5e_16g"]
    r = rec["roofline"]
    assert r["dominant"] == "memory"          # decode is memory-bound
    assert 0 < r["memory_s"] < 10
    assert rec["analyzed"]["unknown_trip_whiles"] == 0


@pytest.mark.slow
def test_dryrun_icp_cell_multi_pod(tmp_path):
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "fpps-icp",
         "--shape", "fleet_130k", "--mesh", "multi",
         "--out-dir", str(tmp_path)],
        capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": str(ROOT / "src")},
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    rec = json.loads((tmp_path / "fpps-icp__fleet_130k__multi.json"
                      ).read_text())
    assert rec["status"] == "ok"
    assert rec["n_devices"] == 512            # the pod axis shards
    assert rec["sharding"]["frame_axes"] == ["pod", "data"]
