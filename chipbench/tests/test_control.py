"""The control: the reference computed one precision below the
configuration's, put in the program's place, must fail at least one of
the cell's limits on every seed, while the program passes them all
(chipbench/calibrate.py, at a tiny size on the host CPU; on the chip it
runs at the cell's own size)."""
import json
import os
import subprocess
import sys

import pytest

from conftest import ROOT

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
ONE_CHIP = [w for w in BENCH["workloads"] if w["chips"] == 1]


@pytest.mark.parametrize("cell", ONE_CHIP, ids=lambda w: w["name"])
def test_control_fails_program_passes(tiny, cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "chipbench/calibrate.py", "--cpu",
                        "--workload", cell["name"], "--seeds", "3-4"],
                       cwd=tiny, env=env, capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    rows = [json.loads(x) for x in p.stdout.splitlines() if x.startswith("{")]
    limits = {k: v for k, v in json.loads(
        (tiny / "chipbench/limits" / f"{cell['name']}.json").read_text()
    )["limits"].items() if v is not None}
    assert limits
    assert {r["reading"] for r in rows} >= {"program", "control",
                                            "unchanged", "half_batch"}
    for r in rows:
        over = [k for k, lim in limits.items() if r[k] > lim]
        if r["reading"] == "program":
            assert not over, r
        elif not r["reading"].startswith("at_"):
            # the at_<precision> witnesses are read, not judged
            assert over, r
