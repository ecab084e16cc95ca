"""Rehearse a cell on the host CPU: the same harness, without the look
for a chip, on ``--chips`` host devices.

  python chipbench/rehearse.py --chips 1 --workload NAME --seed N \
      --seconds S --trace 0|1

Meant for a copy of the benchmark whose configuration and traffic files
were cut to a tiny size (chipbench/tests does this); nothing it prints
is a device number. With --trace 1 the XLA CPU threads of the host plane
stand in for a device plane, so that the reduction has something to
read; each of their ops names its module and instruction, through which
its op_name is found in the recorded programs.
"""
from __future__ import annotations

import os
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
CPU = "/device:CPU:0"


def main(argv=None, before_run=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    chips = 1
    if "--chips" in argv:
        i = argv.index("--chips")
        chips = int(argv[i + 1])
        del argv[i:i + 2]
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               f" --xla_force_host_platform_device_count="
                               f"{chips}").strip()
    sys.path[0] = str(Path(__file__).resolve().parent.parent)
    import jax

    from chipbench import run, scopes, trace_reduce

    def load_cpu(path):
        pd = jax.profiler.ProfileData.from_file(path)
        ops, spans, hlo = [], [], []
        for plane in pd.planes:
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith(trace_reduce.SPAN_PREFIX):
                        spans.append((ev.name, float(ev.start_ns),
                                      float(ev.duration_ns)))
                    elif ln.name.startswith("tf_XLA"):
                        ops.append((ev.name, float(ev.start_ns),
                                    float(ev.duration_ns)))
                        stats = dict(ev.stats)
                        hlo.append((stats.get("hlo_module"),
                                    stats.get("hlo_op")))
        return {"devices": {CPU: ops}, "spans": spans, "lines": {},
                "hlo": hlo}

    def op_names_cpu(path, rec, programs):
        return dict(rec, host=[], op_names={CPU: [
            programs.get(module, {}).get(op, "")
            for module, op in rec["hlo"]]})

    trace_reduce.load, scopes.load = load_cpu, op_names_cpu
    if before_run is not None:
        before_run()
    return run.main(argv, devices=jax.devices()[:chips], t_start=T_START)


if __name__ == "__main__":
    sys.exit(main())
