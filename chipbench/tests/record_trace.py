"""Run one traced cell on the chip as run.py does, and also keep what the
trace reduction read: every plane's line names and, of each device op
line and the benchmark's spans, the events of the traced window's first
``--ms`` milliseconds. The result is a small recorded trace that
test_trace_reduce.py checks the reduction on.

  python3 chipbench/tests/record_trace.py --workload NAME --seed N \
      --out FILE [--ms 40]
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path[0] = str(Path(__file__).resolve().parents[2])


def trim(rec: dict, ms: float) -> dict:
    from chipbench import trace_reduce as tr
    w0 = min(s for n, s, _ in rec["spans"] if n == tr.WINDOW)
    end = w0 + ms * 1e6
    spans = [(n, s, min(d, end - s)) if n == tr.WINDOW else (n, s, d)
             for n, s, d in rec["spans"] if s < end]
    devices = {p: [e for e in evs if e[1] < end]
               for p, evs in rec["devices"].items()}
    return {"devices": devices, "spans": spans, "lines": rec["lines"]}


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", required=True)
    p.add_argument("--out", required=True)
    p.add_argument("--ms", type=float, default=40.0)
    args = p.parse_args(argv)
    from chipbench import run, trace_reduce

    load = trace_reduce.load

    def keep(path):
        rec = load(path)
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(trim(rec, args.ms), f)
        return rec

    trace_reduce.load = keep
    return run.main(["--workload", args.workload, "--seed", args.seed,
                     "--seconds", "10", "--trace", "1"])


if __name__ == "__main__":
    sys.exit(main())
