"""Shared pieces of the chip benchmark: where things are, the device and
its peaks, compile accounting, seeds, and the result line.

Nothing here imports the program under test; ``src`` is put on the path
by ``run.py`` and by the drivers that need it.
"""
from __future__ import annotations

import functools
import json
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent          # <checkout>/chipbench
ROOT = HERE.parent                              # the checkout
CACHE_DIR = ROOT / ".jax_cache"                 # fixed: part of the key


def use_checkout_cache() -> None:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, whatever the environment says; every program is kept, so a
    second run of a cell compiles nothing. Call before importing jax.
    Also puts the program's ``src`` on the path."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    # libtpu logs under /tmp by default: outside the checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def cell_spec(workload: str, bench: dict | None = None) -> dict:
    """The workload entry, its configuration file, its traffic file and
    the limits of its output check (``limits/<workload>.json``), each
    found by the name ``BENCHMARK.json`` gives."""
    bench = bench or benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; have "
                         f"{sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = load_json(ROOT / configs[cell["config"]]["file"])
    traffic = load_json(HERE / "traffic" / f"{cell['traffic']}.json")
    limits = load_json(HERE / "limits" / f"{workload}.json")["limits"]
    return {"cell": cell, "config": cfg, "traffic": traffic,
            "limits": limits, "bench": bench}


@functools.lru_cache(maxsize=None)
def load_module(kind: str, name: str):
    """``chipbench/<kind>/<name>.py`` as a module, loaded once; names may
    hold dots."""
    import importlib.util
    path = HERE / kind / f"{name}.py"
    if not path.is_file():
        raise SystemExit(f"no {kind} file {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------------ device ----

class NoChip(SystemExit):
    """Raised where the run may not measure: it exits non-zero and
    prints no result."""


def peaks_for(device_kind: str) -> dict:
    table = load_json(HERE / "peaks.json")["devices"]
    if device_kind not in table:
        raise NoChip(f"device_kind {device_kind!r} is not in "
                     f"chipbench/peaks.json ({sorted(table)}); no default")
    return table[device_kind]


def chip_devices(chips: int):
    """The first ``chips`` TPU devices, or NoChip."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found "
                     f"{len(devs)}")
    peaks_for(devs[0].device_kind)
    return devs[:chips]


def device_report(devs, trace: dict | None = None) -> dict:
    """What JAX says of the devices. The memory peak is the fullest
    chip's ``peak_bytes_in_use`` plus its ``peak_bytes_reserved``: a TPU
    keeps a program's temporaries in a reserved region that the first
    leaves out (PERF.md, section 2)."""
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    out = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs), "memory_peak_bytes": peak}
    if trace is not None:
        out["busy_s"] = trace["busy_s"]
        out["window_s"] = trace["window_s"]
    return out


# ----------------------------------------------------------- compile ----

class CompileMeter:
    """JAX's own compile events: seconds in the backend compiler (a
    persistent-cache read included), backend compiles, and persistent
    cache hits and misses. Copied from the repo's chip_smoke.py, with a
    count of compiles so that one inside the window is seen."""

    def __init__(self):
        import jax
        self.compile_s, self.compiles, self.hits, self.misses = 0.0, 0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


# -------------------------------------------------------------- seeds ----

def seed_key(seed: int):
    """A PRNG key from any whole seed: jax.random.key keeps only 32 bits,
    so the rest is folded in."""
    import jax
    seed = int(seed)
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


# ------------------------------------------------------------- output ----

def emit(result: dict, compared: list) -> None:
    """The comparison on the last lines of stderr, then the result as the
    last line of stdout, its ``compared`` key last."""
    for name, value, limit in compared:
        print(f"compared {name}: {value!r} limit {limit!r}",
              file=sys.stderr, flush=True)
    result = dict(result)
    result["compared"] = {name: {"value": value, "limit": limit}
                          for name, value, limit in compared}
    print(json.dumps(result), flush=True)
