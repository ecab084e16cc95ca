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

# the published widths cut to a few hundred thousand parameters
TINY_MODEL = dict(hidden_size=64, intermediate_size=128,
                  num_hidden_layers=2, num_attention_heads=4,
                  num_key_value_heads=4, vocab_size=512)
TINY_LM = dict(seq=16, table_rows=64, trace_seconds=0.5)
TINY_LR = dict(rows=4000, features=80, block_rows=1000)
# limits at the tiny sizes, set as the chip's are (PERF.md), from the
# host CPU's readings on seeds 1-12: program's largest below, control's
# and each fault's smallest above
TINY_CHECK = {
    # loss_gap: program <= 7.9e-5, control >= 1.7e-4, half batch
    # >= 1.3e-3; dir_gap: program about 1e-7, unchanged 1; w0_sign_gap:
    # program, control and half batch 0, unchanged and no server update 1
    "zoo_step": {"loss_gap": 1.2e-4, "dir_gap": 1e-3, "w0_sign_gap": 0.0},
    # program 0, 1.3e-4 and 0; the bf16 control >= 5.8e-5, 0.061 and
    # 7.5e-4; unchanged 1 for both of the last two
    "scan": {"loss_gap": 1e-5, "change_gap": 0.01, "dir_gap": 1e-4},
}


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
    sizes, in place, and add the deferred cells to its BENCHMARK.json."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"] += DEFERRED
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] == "round_p95_ms":
            m["workloads"] += [w["name"] for w in DEFERRED]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    drivers = {}
    for c in bench["configs"]:
        p = root / c["file"]
        cfg = json.loads(p.read_text())
        drivers[c["name"]] = cfg["driver"]
        if "model" in cfg:
            cfg["model"].update(TINY_MODEL)
        if "data" in cfg:
            cfg["data"].update(TINY_LR)
        p.write_text(json.dumps(cfg))
    for w in bench["workloads"]:
        p = root / "chipbench" / "limits" / f"{w['name']}.json"
        p.write_text(json.dumps(
            {"limits": TINY_CHECK[drivers[w["config"]]]}))
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
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(cmd, cwd=root, env=env, capture_output=True,
                       text=True, timeout=timeout)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stderr
