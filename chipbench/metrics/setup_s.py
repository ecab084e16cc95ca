"""Seconds from the process's start to the window's start: imports,
data and weights, compiles (or cache reads), and the first rounds.
The host copies of the state that only the check needs are left out."""


def read(rec: dict):
    return rec.get("setup_s")
