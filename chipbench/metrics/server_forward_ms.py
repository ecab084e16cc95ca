"""Device self time a round of the ops in the round's ``server_forward``
phase in the traced window (ms): the server's h, h_bar and h_hat
forwards. From the op_names of the compiled programs
(chipbench/scopes.py); None where the trace names no phase."""
from chipbench.scopes import phase_ms


def read(rec: dict):
    return phase_ms(rec, "server_forward")
