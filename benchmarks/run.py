"""Benchmark harness — one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run            # all
  PYTHONPATH=src python -m benchmarks.run fig3 table4

Prints ``name,us_per_call,derived`` CSV per row AND persists each suite's
rows as a ``BENCH_<artifact>.json`` file in the repo root (the machine-
readable bench trajectory: CI uploads these, and successive PRs diff
them). The roofline section (driven by results/dryrun artifacts, see
launch/dryrun.py) appends its own CSV block when artifacts exist.
"""
from __future__ import annotations

import json
import math
import os
import sys
import time
import traceback

SUITES = {
    "fig3": ("benchmarks.bench_convergence", "Fig 3: black-box convergence",
             "convergence"),
    "table3": ("benchmarks.bench_communication", "Table 3: PRCO ratios",
               "communication"),
    "table4": ("benchmarks.bench_losslessness", "Table 4: losslessness",
               "losslessness"),
    "fig4": ("benchmarks.bench_speedup", "Fig 4: q-party speedup",
             "speedup"),
    "thm1": ("benchmarks.bench_privacy", "Theorem 1: attack defense",
             "privacy"),
    "thm2": ("benchmarks.bench_rate", "Theorem 2: O(1/sqrt(T)) rate",
             "rate"),
    "kernels": ("benchmarks.bench_kernels", "Pallas kernel validation",
                "kernels"),
    "runtime": ("benchmarks.bench_runtime",
                "Multi-process TCP runtime vs in-memory executor",
                "runtime"),
    "dp": ("benchmarks.bench_dp",
           "DP defense: measured privacy/utility frontier vs epsilon",
           "dp"),
    "serving": ("benchmarks.bench_serving",
                "Federated inference serving: one wire crossing per party "
                "per step",
                "serving"),
    "obs": ("benchmarks.bench_obs",
            "Observability: --trace overhead on the fused round + merged "
            "trace chain reconstruction",
            "obs"),
}

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _parse_derived(derived: str) -> dict:
    """'a=1;b=x' -> {'a': 1.0, 'b': 'x'} (floats where they parse)."""
    out = {}
    for part in derived.split(";"):
        if "=" not in part:
            continue
        k, v = part.split("=", 1)
        try:
            f = float(v)
            # keep non-finite values as strings: bare NaN/Infinity in the
            # JSON artifact breaks strict parsers
            out[k] = f if math.isfinite(f) else v
        except ValueError:
            out[k] = v
    return out


def write_artifact(suite_key: str, rows, ok: bool, elapsed_s: float):
    """Persist one suite's rows as BENCH_<artifact>.json in the repo root."""
    import jax

    _, title, artifact = SUITES[suite_key]
    devices = jax.devices()
    payload = {
        "suite": suite_key,
        "title": title,
        "ok": ok,
        "elapsed_s": round(elapsed_s, 2),
        "generated_unix": time.time(),
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "rows": [{"name": name, "us_per_call": us, "derived": derived,
                  "metrics": _parse_derived(derived)}
                 for name, us, derived in rows],
    }
    path = os.path.join(_ROOT, f"BENCH_{artifact}.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
    print(f"# wrote {os.path.relpath(path, _ROOT)}", flush=True)


def main() -> None:
    wanted = sys.argv[1:] or list(SUITES)
    failures = 0
    for key in wanted:
        mod_name, title, _ = SUITES[key]
        print(f"# === {key}: {title} ===", flush=True)
        t0 = time.perf_counter()
        rows, ok = [], True
        try:
            mod = __import__(mod_name, fromlist=["run"])
            rows = list(mod.run())
            for name, us, derived in rows:
                print(f"{name},{us:.1f},{derived}", flush=True)
        except Exception:  # noqa: BLE001
            traceback.print_exc()
            ok = False
            failures += 1
        elapsed = time.perf_counter() - t0
        write_artifact(key, rows, ok, elapsed)
        print(f"# {key} done in {elapsed:.1f}s", flush=True)
    # roofline block (only if dry-run artifacts exist)
    try:
        from benchmarks import roofline
        recs = roofline.load_records()
        if recs:
            print("# === roofline (from dry-run artifacts) ===")
            rows = roofline.table(recs, multi_pod=False)
            rows += roofline.table(recs, multi_pod=False,
                                   mode_filter=("vfl_zoo",))
            for r in rows:
                t = r["roofline"]
                print(f"roofline_{r['arch']}_{r['shape']}_{r['mode']},0.0,"
                      f"compute={t['compute_s']:.4f};"
                      f"memory={t['memory_s']:.4f};"
                      f"collective={t['collective_s']:.4f};"
                      f"bottleneck={r['bottleneck']};"
                      f"useful={r['useful_flops_ratio']:.2f}")
    except Exception:  # noqa: BLE001
        traceback.print_exc()
        failures += 1
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
