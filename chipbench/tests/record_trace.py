"""Run one traced cell on the chip as run.py does, and also keep what the
trace reduction read, as ``chipbench/phases.py --record`` keeps it
(``phases.trim``): every plane's line names and, of each device op line
and the benchmark's spans, the events of the traced window's first
``--ms`` milliseconds, each op with its phase, and the host events under
its idle gaps. The result is a small recorded trace that
test_trace_reduce.py and test_scopes.py check the reduction on.

  python3 chipbench/tests/record_trace.py --workload NAME --seed N \
      --out FILE [--ms 40]
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path[0] = str(Path(__file__).resolve().parents[2])


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", required=True)
    p.add_argument("--out", required=True)
    p.add_argument("--ms", type=float, default=40.0)
    args = p.parse_args(argv)
    from chipbench import phases, run

    got = {}
    rc = run.main(["--workload", args.workload, "--seed", args.seed,
                   "--seconds", "10", "--trace", "1"], seen=got)
    if rc == 0:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(phases.trim(got["record"],
                                                         args.ms)))
    return rc


if __name__ == "__main__":
    sys.exit(main())
