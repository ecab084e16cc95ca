"""Device self time a round of the ops in the round's ``party_forward``
phase in the traced window (ms): every party tower's forward, the stale
c's and the activated party's c_hat. From the op_names of the compiled
programs (chipbench/scopes.py); None where the trace names no phase."""
from chipbench.scopes import phase_ms


def read(rec: dict):
    return phase_ms(rec, "party_forward")
