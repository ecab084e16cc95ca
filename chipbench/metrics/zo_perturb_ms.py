"""Device self time a round of the ops in the round's ``zo_perturb`` phase
in the traced window (ms): the direction draws and w + mu*u, party and
server. From the op_names of the compiled programs
(chipbench/scopes.py); None where the trace names no phase."""
from chipbench.scopes import phase_ms


def read(rec: dict):
    return phase_ms(rec, "zo_perturb")
