"""Completed AsyREVEL rounds over the window's seconds (host clock, from
the window's start to the end of its last round)."""


def read(rec: dict):
    w = rec.get("window")
    if not w or w["elapsed_s"] <= 0:
        return None
    return w["rounds"] / w["elapsed_s"]
