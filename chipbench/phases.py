"""Run one traced cell as ``run.py --trace 1`` does, and print what the
run read of the program's named phases (``chipbench/scopes.py``).

  python3 chipbench/phases.py --workload NAME --seed N \
      [--out FILE] [--record FILE]

run.py's own output comes first, unchanged, its result line last on
stdout. Then stderr gets each phase's device self time per round (ms),
``unscoped``, their sum beside the busy time per round, and the runtime
event under each of the longest idle gaps. ``--out`` writes the same as
JSON; ``--record`` keeps the first ``RECORD_MS`` milliseconds of the
window as a small recorded trace with each op's phase, for
``chipbench/tests/test_scopes.py``. The phases are those that run.py
computes for its per-layer metrics (``scope_s``); this script reads
them from the run and records nothing itself.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

if __name__ == "__main__":
    # the checkout, not this directory, leads the path; imported (by
    # run.py) the path is left as it is, the program's src with it
    sys.path[0] = str(Path(__file__).resolve().parent.parent)

from chipbench import scopes  # noqa: E402
from chipbench import trace_reduce as tr  # noqa: E402

RECORD_MS = 40.0


def trim(rec: dict, ms: float) -> dict:
    """The record's first ``ms`` milliseconds of the window; op names cut
    as ``trace_reduce`` cuts them, op_names cut to their phase, and only
    the host events that cover the middle of an idle gap, which are all
    that ``gap_events`` reads."""
    w0, _ = scopes._window(rec)
    end = w0 + ms * 1e6
    devices, op_names = {}, {}
    for plane, events in rec["devices"].items():
        ops = rec["op_names"].get(plane, [""] * len(events))
        keep = [(e, op) for e, op in zip(events, ops) if e[1] < end]
        devices[plane] = [(n[:tr.NAME_CHARS], s, d) for (n, s, d), _ in keep]
        op_names[plane] = [scopes.scope_of(op) for _, op in keep]
    spans = [(n, s, min(d, end - s)) if n == tr.WINDOW else (n, s, d)
             for n, s, d in rec["spans"] if s < end]
    out = {"devices": devices, "op_names": op_names, "spans": spans,
           "lines": rec["lines"]}
    mids = [(a + b) / 2 for a, b in scopes._gaps(out, *scopes._window(out))]
    out["host"] = [e for e in rec["host"]
                   if any(e[1] <= t <= e[1] + e[2] for t in mids)]
    return out


def per_round(summary: dict, rounds: int) -> dict:
    """Each phase's, and the busy time's, ms per round."""
    ms = {k: 1e3 * v / rounds for k, v in sorted(summary["scope_s"].items())}
    return {"phases_ms": ms, "sum_ms": sum(ms.values()),
            "busy_ms": 1e3 * summary["busy_s"] / rounds,
            "rounds": rounds, "gap_events": summary["gap_events"]}


def record_programs(programs: dict) -> None:
    """From now on, enter the HLO text of every program the process
    compiles or loads from the compile cache into ``programs``
    (``scopes.add_program``): what ``scopes.load`` looks op names up in.
    ``run.py`` calls it before a traced run's set-up.
    Call after ``common.use_checkout_cache``, which must precede the
    import of jax."""
    from jax._src import compiler
    compile_or_get_cached = compiler.compile_or_get_cached

    def recorded(*args, **kwargs):
        exe = compile_or_get_cached(*args, **kwargs)
        for module in exe.hlo_modules():
            scopes.add_program(programs, module.name, module.to_string())
        return exe

    compiler.compile_or_get_cached = recorded


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", required=True)
    p.add_argument("--out")
    p.add_argument("--record")
    args = p.parse_args(argv)
    from chipbench import run

    got = {}
    rc = run.main(["--workload", args.workload, "--seed", args.seed,
                   "--seconds", "10", "--trace", "1"], seen=got)
    if rc or "summary" not in got:
        return rc or 1
    if args.record:
        Path(args.record).parent.mkdir(parents=True, exist_ok=True)
        Path(args.record).write_text(json.dumps(trim(got["record"],
                                                     RECORD_MS)))
    out = per_round(got["summary"], got["result"]["attempted"])
    for k, v in out["phases_ms"].items():
        print(f"chipbench: phase {k} {v:.6f} ms a round", file=sys.stderr)
    print(f"chipbench: phases sum {out['sum_ms']:.6f} ms, busy "
          f"{out['busy_ms']:.6f} ms a round", file=sys.stderr)
    for label, s, event in out["gap_events"]:
        print(f"chipbench: idle gap {label} {1e3 * s:.3f} ms under {event}",
              file=sys.stderr)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
