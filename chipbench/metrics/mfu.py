"""Model FLOP/s utilization of the traced window (percent): the FLOPs
the algorithm needs per round (chipbench/flops.py) times the rounds
completed, over the window, the chips and each chip's bf16 peak."""


def read(rec: dict):
    t = rec.get("trace")
    if not t or not rec.get("rounds") or t["window_s"] <= 0:
        return None
    return (100.0 * rec["flops_per_round"] * rec["rounds"]
            / (t["window_s"] * rec["chips"] * rec["peak"]["bf16_flops"]))
