"""Run one cell of the chip benchmark once.

  python3 chipbench/run.py --workload NAME --seed N --seconds S --trace 0|1

The cell, its configuration file and its traffic file are found by name
through BENCHMARK.json; the configuration names its driver
(chipbench/drivers/), its model family (chipbench/families/) and its
reference (chipbench/reference/); each metric is read by
chipbench/metrics/<name>.py. With --trace 0 the run prints the cell's
end-to-end metrics, with --trace 1 its per-layer metrics from a profiler
trace of a shorter window, whose device time is also split by the
program's named scopes (chipbench/scopes.py).

The run exits non-zero, and prints no result, where JAX finds no TPU,
fewer chips than the cell asks for, or a device kind that
chipbench/peaks.json does not list. Otherwise its last stdout line is the
result, whose ``correct`` says whether the rounds it checked agree with
the plain reference (chipbench/check.py); the numbers compared, each
beside its limit, are the last lines of stderr and the result's last key.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

# the checkout, not this directory, leads the path: module names here
# must not shadow the standard library's
sys.path[0] = str(Path(__file__).resolve().parent.parent)

from chipbench import common  # noqa: E402


def parse(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=[0, 1], default=0)
    return p.parse_args(argv)


def reported(metrics: list, workload: str) -> list:
    return [m for m in metrics
            if "workloads" not in m or workload in m["workloads"]]


def main(argv=None, devices=None, t_start=None, seen=None) -> int:
    """``devices`` skips the look for a chip (the benchmark's own CPU
    tests pass host devices); a run from the command line never does.
    A ``seen`` dict receives the traced run's loaded trace (``record``),
    its reduction (``summary``) and the result (``result``)."""
    args = parse(argv)
    common.use_checkout_cache()
    spec = common.cell_spec(args.workload)
    cell_def, cfg, traffic = spec["cell"], spec["config"], spec["traffic"]
    import jax  # noqa: F401
    from chipbench import check as chk
    from chipbench import trace_reduce as tr

    if devices is None:
        try:
            devices = common.chip_devices(cell_def["chips"])
        except common.NoChip as e:
            print(f"chipbench: {e}", file=sys.stderr)
            return 2
        peak = common.peaks_for(devices[0].device_kind)
    else:
        peak = {"bf16_flops": 197e12}
    meter = common.CompileMeter()
    programs = {}
    if args.trace:
        # every program's op_names, looked up when the trace is read
        from chipbench import phases, scopes
        phases.record_programs(programs)
    driver = common.load_module("drivers", cfg["driver"])
    cell = driver.Cell(cfg, traffic, args.seed, devices)
    t_devices = time.perf_counter() - (t_start or T_START)
    cell.setup()
    # what set-up made stays: no collection pass walks it in the window
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - (t_start or T_START) - cell.snap_s
    print(f"chipbench: set-up {setup_s:.3f} s (check copies {cell.snap_s:.3f}"
          f" s left out); backend compiles {meter.compiles} taking "
          f"{meter.compile_s:.3f} s; compile cache hits {meter.hits} misses "
          f"{meter.misses} in {common.CACHE_DIR}", file=sys.stderr,
          flush=True)
    print(f"chipbench: set-up phases (s): start to devices {t_devices:.3f}, "
          + ", ".join(f"{k} {v:.3f}" for k, v in cell.phases.items()),
          file=sys.stderr, flush=True)
    compiles = meter.compiles
    summary = None
    if args.trace:
        logdir = common.ROOT / ".chipbench_trace" / args.workload
        seconds = min(args.seconds, traffic["trace_seconds"])
        win, path = tr.capture(lambda: cell.window(seconds, annotate=True),
                               str(logdir))
        record = scopes.load(path, tr.load(path), programs)
        summary = scopes.reduce(record)
        shutil.rmtree(logdir, ignore_errors=True)
        if seen is not None:
            seen.update(record=record, summary=summary)
    else:
        win = cell.window(args.seconds)
    in_window = meter.compiles - compiles
    if win.get("slowest"):
        print("chipbench: slowest rounds (index, draw, dispatch, read-back "
              f"ms): {win['slowest']}", file=sys.stderr)
    print(f"chipbench: memory stats {devices[0].memory_stats()}",
          file=sys.stderr)
    device = common.device_report(devices, summary)
    flops = cell.flops_per_round()
    cell.release()

    ref = cell.reference()
    readings = cell.readings(cell.program_side(), ref, cell.start_leaves())
    for k, v in readings.items():
        if k.startswith("_"):
            print(f"chipbench: {k[1:]} {v}", file=sys.stderr)
    numbers = {k: v for k, v in readings.items() if not k.startswith("_")}
    numbers["window_compiles"] = in_window
    limits = dict(spec["limits"], window_compiles=0)
    correct, compared = chk.judge(numbers, limits)

    rec = {"window": win, "setup_s": setup_s, "device": device,
           "trace": summary, "rounds": win["rounds"], "flops_per_round":
           flops, "chips": len(devices), "peak": peak}
    bench = spec["bench"]
    metrics = {}
    for m in reported(bench["per_layer" if args.trace else "end_to_end"],
                      args.workload):
        value = common.load_module("metrics", m["name"]).read(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {"correct": bool(correct), "attempted": win["rounds"],
              "failed": win.get("failed", 0), "metrics": metrics,
              "device": device}
    if summary is not None:
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    if seen is not None:
        seen["result"] = result
    common.emit(result, compared)
    return 0


if __name__ == "__main__":
    sys.exit(main())
