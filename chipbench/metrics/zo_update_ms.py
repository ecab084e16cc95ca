"""Device self time a round of the ops in the round's ``zo_update`` phase
in the traced window (ms): coeff*u and w - lr*g, the draws regenerated
there. From the op_names of the compiled programs
(chipbench/scopes.py); None where the trace names no phase."""
from chipbench.scopes import phase_ms


def read(rec: dict):
    return phase_ms(rec, "zo_update")
