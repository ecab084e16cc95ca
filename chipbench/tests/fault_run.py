"""A rehearsed run (chipbench/rehearse.py) with a fault planted in the
program under the timed path (chipbench/faults.py); its ``correct`` must
come out false.

  python chipbench/tests/fault_run.py --fault NAME --chips N <run args>
"""
from __future__ import annotations

import sys
from pathlib import Path

sys.path[0] = str(Path(__file__).resolve().parents[2])
sys.path.insert(1, str(Path(__file__).resolve().parents[2] / "src"))


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    i = argv.index("--fault")
    fault = argv[i + 1]
    del argv[i:i + 2]
    from chipbench import faults, rehearse
    # rehearse sets the platform and host devices before jax loads; the
    # program's modules import jax, so plant after that point
    return rehearse.main(argv, before_run=lambda: faults.plant(fault))


if __name__ == "__main__":
    sys.exit(main())
