"""Device self time a round of the ops in the round's ``ring_buffer``
phase in the traced window (ms): the stale party reads out of the tau+1
ring buffer and its write. From the op_names of the compiled programs
(chipbench/scopes.py); None where the trace names no phase."""
from chipbench.scopes import phase_ms


def read(rec: dict):
    return phase_ms(rec, "ring_buffer")
