"""Later work comes as data: in a copy of the benchmark, one new
configuration file, one new traffic file, one new metric file and the
new cell's limits file, with entries added to BENCHMARK.json, make a new
cell that runs through the harness, while no file the benchmark already
has changes. A new model family comes the same way, with its adapter
(chipbench/families/) as one more new file."""
import hashlib
import json

from conftest import run_cell

METRIC = '''"""Host time per round in the traced window: the window less the
device's busy time, over the rounds (ms)."""


def read(rec):
    t = rec.get("trace")
    if not t or not rec.get("rounds"):
        return None
    return 1e3 * (t["window_s"] - t["busy_s"]) / rec["rounds"]
'''


def digest(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted((root / "chipbench").rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


def test_new_cell_from_files_only(tiny):
    before = digest(tiny)
    bench = json.loads((tiny / "BENCHMARK.json").read_text())
    lr = next(c for c in bench["configs"] if c["name"].startswith("paper-lr"))
    cfg = json.loads((tiny / lr["file"]).read_text())
    cfg["name"] = "paper-lr.extra"
    cfg["data"].update(rows=2000, features=40, block_rows=500)
    (tiny / "chipbench/configs/paper-lr.extra.json").write_text(
        json.dumps(cfg))
    (tiny / "chipbench/traffic/b32.json").write_text(json.dumps(
        {"batch": 32, "rounds_per_dispatch": 20, "mesh": 1,
         "check_rounds": 3, "trace_seconds": 0.3}))
    (tiny / "chipbench/metrics/host_ms_per_round.py").write_text(METRIC)
    (tiny / "chipbench/limits/lr-extra.b32.json").write_text(
        (tiny / "chipbench/limits/lr-eps.b64.json").read_text())
    bench["configs"].append(dict(lr, name="paper-lr.extra",
                                 file="chipbench/configs/paper-lr.extra.json"))
    bench["workloads"].append({"name": "lr-extra.b32",
                               "config": "paper-lr.extra", "traffic": "b32",
                               "chips": 1, "why": "added by files"})
    bench["per_layer"].append({"name": "host_ms_per_round", "unit": "ms",
                               "better": "lower", "source": "device_trace",
                               "layer": "host loop",
                               "moves": "rounds_per_s",
                               "workloads": ["lr-extra.b32"]})
    for m in bench["per_layer"] + bench["end_to_end"]:
        if "workloads" in m and m["name"] in ("mfu", "device_idle_share",
                                              "device_ms_per_round",
                                              "device_ops_per_round",
                                              "rounds_per_s", "peak_hbm_gb",
                                              "setup_s"):
            m["workloads"].append("lr-extra.b32")
    (tiny / "BENCHMARK.json").write_text(json.dumps(bench))
    res, err = run_cell(tiny, "lr-extra.b32", trace=1)
    assert res["correct"] is True, err[-3000:]
    assert res["metrics"]["host_ms_per_round"]["value"] > 0
    plain, _ = run_cell(tiny, "lr-extra.b32", trace=0)
    assert plain["metrics"]["rounds_per_s"]["value"] > 0
    after = digest(tiny)
    assert {k: v for k, v in after.items() if k in before} == before


FAMILY = '''"""A model family added by files: Qwen2's adapter, renamed."""
from chipbench.common import load_module

_qwen2 = load_module("families", "qwen2")
program_config, round_flops = _qwen2.program_config, _qwen2.round_flops
SERVER_LEAF, TINY, TINY_LIMITS = (_qwen2.SERVER_LEAF, _qwen2.TINY,
                                  _qwen2.TINY_LIMITS)
'''

SCOPE_METRIC = '''"""Device self time a round of the ops whose op_name
path holds server_forward, nested scopes included (ms)."""


def read(rec):
    t = rec.get("trace") or {}
    s = t.get("within_s", {}).get("server_forward")
    return None if s is None or not rec.get("rounds") else (
        1e3 * s / rec["rounds"])
'''


def test_new_family_from_files_only(tiny):
    before = digest(tiny)
    bench = json.loads((tiny / "BENCHMARK.json").read_text())
    lm = next(c for c in bench["configs"] if c["name"].startswith("qwen"))
    cfg = json.loads((tiny / lm["file"]).read_text())
    cfg.update(name="qwen2-copy.zoo-q4", family="qwen2_copy")
    (tiny / "chipbench/families/qwen2_copy.py").write_text(FAMILY)
    (tiny / "chipbench/configs/qwen2-copy.zoo-q4.json").write_text(
        json.dumps(cfg))
    (tiny / "chipbench/traffic/b2s16.json").write_text(json.dumps(
        {"batch": 2, "seq": 16, "table_rows": 32, "mesh": 1,
         "check_rounds": 3, "trace_seconds": 0.5}))
    (tiny / "chipbench/metrics/server_forward_within_ms.py").write_text(
        SCOPE_METRIC)
    (tiny / "chipbench/limits/copy.b2s16.json").write_text(
        (tiny / "chipbench/limits/zoo-q05b.b8s64.json").read_text())
    bench["configs"].append(dict(lm, name="qwen2-copy.zoo-q4",
                                 file="chipbench/configs/qwen2-copy.zoo-q4"
                                      ".json"))
    bench["workloads"].append({"name": "copy.b2s16",
                               "config": "qwen2-copy.zoo-q4",
                               "traffic": "b2s16", "chips": 1,
                               "why": "a family added by files"})
    bench["per_layer"].append({"name": "server_forward_within_ms",
                               "unit": "ms", "better": "lower",
                               "source": "device_trace",
                               "layer": "jitted round",
                               "moves": "rounds_per_s",
                               "workloads": ["copy.b2s16"]})
    (tiny / "BENCHMARK.json").write_text(json.dumps(bench))
    res, err = run_cell(tiny, "copy.b2s16", trace=1)
    assert res["correct"] is True, err[-3000:]
    within = res["metrics"]["server_forward_within_ms"]["value"]
    assert within >= res["metrics"]["server_forward_ms"]["value"] > 0
    after = digest(tiny)
    assert {k: v for k, v in after.items() if k in before} == before
