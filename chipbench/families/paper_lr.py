"""Family ``paper_lr``: the paper's nonconvex LR (Eq. 22) through the
program's device scan trainer (``chipbench/drivers/scan.py``).

- ``program_model(cfg)``: the program's model for the configuration
  file ``cfg``;
- ``round_flops(data, vfl, batch)``: the matmul FLOPs of one round
  (``chipbench/flops.py``);
- ``TINY``, ``TINY_LIMITS``: the sizes that the benchmark's tests cut
  the ``data`` block to, and the check's limits at those sizes.
"""
from __future__ import annotations

from chipbench import flops

TINY = dict(rows=4000, features=80, block_rows=1000)
# set as the chip's are (PERF.md), from the host CPU's readings on seeds
# 1-12 at the tiny sizes: program 0, 1.3e-4 and 0; the bf16 control
# >= 5.8e-5, 0.061 and 7.5e-4; unchanged 1 for both of the last two
TINY_LIMITS = {"loss_gap": 1e-5, "change_gap": 0.01, "dir_gap": 1e-4}


def program_model(cfg: dict):
    from repro.configs import PaperLRConfig
    from repro.core.vfl import PaperLRModel
    return PaperLRModel(PaperLRConfig(
        num_features=cfg["data"]["features"],
        num_parties=cfg["vfl"]["num_parties"]))


def round_flops(data: dict, vfl: dict, batch: int) -> float:
    return flops.lr_round(data["features"], vfl["num_parties"], batch)
