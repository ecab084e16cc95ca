"""Helpers of the benchmark's own tests: a copy of the benchmark cut to a
size the host CPU runs in seconds, and a runner of one cell in it.

  python -m pytest chipbench/tests -q
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench.common import load_module  # noqa: E402

# the tiny sizes of a configuration and the limits of its check there
# come from its family module (chipbench/families/); those of traffic
# files, here
TINY_LM = dict(seq=16, table_rows=64, trace_seconds=0.5)


# cells whose traffic files are kept for a later PR that adds them as
# data (PERF.md, Open questions): rehearsed here so that the paths stay
# sound
DEFERRED = [{"name": "zoo-q05b.s512", "config": "qwen1.5-0.5b.zoo-q4",
             "traffic": "s512", "chips": 1,
             "why": "batch 8 x seq 512: the forwards take most of the round"},
            {"name": "zoo-q05b.s512.dp4", "config": "qwen1.5-0.5b.zoo-q4",
             "traffic": "s512.dp4", "chips": 4,
             "why": "data-parallel 4: the state replicated, the batch split"}]


def cells() -> list:
    """The cells of BENCHMARK.json and the deferred ones."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    return bench["workloads"] + DEFERRED


def driver_of(cell: dict) -> str:
    """The driver that the cell's configuration names."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    path = {c["name"]: c["file"] for c in bench["configs"]}[cell["config"]]
    return json.loads((ROOT / path).read_text())["driver"]


def shrink(root: Path) -> None:
    """Cut every configuration and traffic file under ``root`` to tiny
    sizes, in place, and add the deferred cells to its BENCHMARK.json.
    A configuration takes its family's ``TINY`` sizes into its ``model``
    block (its ``data`` block where it has no model) and its family's
    ``TINY_LIMITS`` as the limits of each of its cells."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"] += DEFERRED
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] == "round_p95_ms":
            m["workloads"] += [w["name"] for w in DEFERRED]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    limits = {}
    for c in bench["configs"]:
        p = root / c["file"]
        cfg = json.loads(p.read_text())
        fam = load_module("families", cfg["family"])
        cfg["model" if "model" in cfg else "data"].update(fam.TINY)
        limits[c["name"]] = fam.TINY_LIMITS
        p.write_text(json.dumps(cfg))
    for w in bench["workloads"]:
        p = root / "chipbench" / "limits" / f"{w['name']}.json"
        p.write_text(json.dumps({"limits": limits[w["config"]]}))
    for p in (root / "chipbench" / "traffic").glob("*.json"):
        t = json.loads(p.read_text())
        if "seq" in t:
            t.update(TINY_LM, batch=2 * t.get("mesh", 1))
        if "rounds_per_dispatch" in t:
            t.update(rounds_per_dispatch=50, trace_seconds=0.3)
        p.write_text(json.dumps(t))


def make_copy(dst: Path) -> Path:
    """The benchmark and BENCHMARK.json copied to ``dst``, the program's
    src linked beside them, cut to tiny sizes."""
    shutil.copytree(ROOT / "chipbench", dst / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", dst / "BENCHMARK.json")
    (dst / "src").symlink_to(ROOT / "src")
    shrink(dst)
    return dst


@pytest.fixture
def tiny(tmp_path):
    return make_copy(tmp_path / "checkout")


def run_cell(root: Path, workload: str, seed: int = 5, seconds: float = 1.0,
             trace: int = 0, fault: str | None = None,
             timeout: float = 300) -> tuple[dict, str]:
    """One rehearsed run of ``workload`` in ``root`` in a process of its
    own; returns (the result line, stderr)."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    chips = {w["name"]: w["chips"] for w in bench["workloads"]}[workload]
    script = ("chipbench/tests/fault_run.py" if fault
              else "chipbench/rehearse.py")
    cmd = [sys.executable, script, "--chips", str(chips)]
    if fault:
        cmd += ["--fault", fault]
    cmd += ["--workload", workload, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", str(trace)]
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONUNBUFFERED="1")
    # as on the chip: the harness finds the program's src itself
    env.pop("XLA_FLAGS", None)
    env.pop("PYTHONPATH", None)
    p = subprocess.run(cmd, cwd=root, env=env, capture_output=True,
                       text=True, timeout=timeout)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stderr
