"""Readings that set a cell's limits: the program against the reference
on many seeds, and beside it the control and each fault a cell can have,
read the same way.

  python3 chipbench/calibrate.py --workload NAME --seeds 101-112 \
      [--plant FAULT] [--out FILE]

For each seed one process builds the cell as a run does (set-up and its
first rounds), skips the window, frees the program's state and then
reads, each against the reference:

- ``program``: the program's first rounds;
- ``control``: the reference itself at the precision below the
  configuration's (chipbench/reference/<ref>.py ``CONTROL``);
- ``unchanged``: a step that returns its state unchanged;
- ``half_batch``: the reference with every loss over half the rows;
- ``one_shard`` (cells over several chips): the reference with every
  loss over one chip's rows, as a data-parallel step whose losses were
  never averaged;
- ``at_<operands>`` for each name in the reference module's ``ALSO``:
  the reference at other precisions, such as the configuration's own
  (the witness of how much of the program's gap is rounding).

With ``--plant FAULT`` the fault (chipbench/faults.py) is planted in the
program before its step is built, and only its reading is taken, as
``plant_<FAULT>``: the fault on the chip at the cell's own size.

One JSON line per seed and reading goes to stdout (and ``--out``). Runs
on the chip by default; ``--cpu`` (tests only) on the host.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path[0] = str(Path(__file__).resolve().parent.parent)


def seeds_of(text: str) -> list:
    out = []
    for part in text.split(","):
        a, _, b = part.partition("-")
        out += list(range(int(a), int(b) + 1)) if b else [int(a)]
    return out


def unchanged(side: dict, start: dict) -> dict:
    """The program's side as a step that returned its state unchanged
    would leave it."""
    out = dict(side)
    for k in ("first", "last"):
        if k in side:
            out[k] = {name: start[name] for name in side[k]}
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--out", default=None)
    p.add_argument("--cpu", action="store_true")
    p.add_argument("--plant", default=None)
    args = p.parse_args(argv)
    from chipbench import common
    common.use_checkout_cache()
    import jax

    if args.plant:
        from chipbench import faults
        faults.plant(args.plant)

    spec = common.cell_spec(args.workload)
    cfg, traffic, chips = spec["config"], spec["traffic"], \
        spec["cell"]["chips"]
    devices = (jax.devices()[:chips] if args.cpu
               else common.chip_devices(chips))
    driver = common.load_module("drivers", cfg["driver"])
    ref_mod = common.load_module("reference", cfg["reference"])
    out = open(args.out, "a") if args.out else None
    for seed in seeds_of(args.seeds):
        t0 = time.perf_counter()
        cell = driver.Cell(cfg, traffic, seed, devices)
        cell.setup()
        cell.release()
        start = cell.start_leaves()
        ref = cell.reference()
        prog = cell.program_side()
        if args.plant:
            rows, kinds = [(f"plant_{args.plant}",
                            cell.readings(prog, ref, start))], []
        else:
            rows = [("program", cell.readings(prog, ref, start)),
                    ("unchanged", cell.readings(unchanged(prog, start),
                                                ref, start))]
            kinds = [("control", dict(operands=ref_mod.CONTROL)),
                     ("half_batch", dict(fault="half_batch"))]
            if chips > 1:
                kinds.append(("one_shard", dict(fault="one_shard")))
            kinds += [(f"at_{name}", dict(operands=name))
                      for name in getattr(ref_mod, "ALSO", ())]
        for name, kw in kinds:
            other = cell.reference(**kw)
            rows.append((name, cell.readings(cell.side_of(other), ref,
                                             start)))
            del other
        for name, r in rows:
            line = json.dumps({"workload": args.workload, "seed": seed,
                               "reading": name, **r})
            print(line, flush=True)
            if out:
                out.write(line + "\n")
                out.flush()
        print(f"seed {seed}: {time.perf_counter() - t0:.1f} s",
              file=sys.stderr, flush=True)
        del cell, ref, prog, rows, start
    return 0


if __name__ == "__main__":
    sys.exit(main())
