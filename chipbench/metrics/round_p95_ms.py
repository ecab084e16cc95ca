"""95th percentile of the host-clock time of every round in the window,
from the round's batch draw to its h read back (ms). Only drivers that
finish one round per call time rounds one by one."""
import numpy as np


def read(rec: dict):
    w = rec.get("window")
    if not w or not w.get("round_s"):
        return None
    return 1e3 * float(np.percentile(np.asarray(w["round_s"]), 95))
