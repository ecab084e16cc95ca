"""Device self time a round of the ops in the round's ``unscoped`` phase
in the traced window (ms): the ops under none of the named phases, and
a while op's own time. From the op_names of the compiled programs
(chipbench/scopes.py); None where the trace names no phase."""
from chipbench.scopes import phase_ms


def read(rec: dict):
    return phase_ms(rec, "unscoped")
