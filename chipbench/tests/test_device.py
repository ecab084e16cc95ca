"""A run measures only on a TPU the peak table knows: anything else is an
error, never a fallback."""
import sys

import pytest

from chipbench import common
from conftest import ROOT


def test_unknown_device_kind_is_an_error():
    with pytest.raises(common.NoChip):
        common.peaks_for("TPU v9 imaginary")


def test_known_kind_has_its_peaks():
    p = common.peaks_for("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9


def test_cpu_is_not_a_chip():
    with pytest.raises(common.NoChip):
        common.chip_devices(1)


def test_run_on_cpu_exits_nonzero_without_a_result():
    import os
    import subprocess
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload",
                        "lr-eps.b64", "--seed", "1", "--seconds", "1"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_run_without_the_program_exits_nonzero(tmp_path):
    import shutil
    import subprocess
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench")
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload",
                        "lr-eps.b64", "--seed", "1", "--seconds", "1"],
                       cwd=tmp_path, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""
