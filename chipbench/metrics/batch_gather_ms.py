"""Device self time a round of the ops in the round's ``batch_gather``
phase in the traced window (ms): the scan body's gather of its batch
rows from the data table. From the op_names of the compiled programs
(chipbench/scopes.py); None where the trace names no phase."""
from chipbench.scopes import phase_ms


def read(rec: dict):
    return phase_ms(rec, "batch_gather")
