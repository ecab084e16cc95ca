"""Device operations started per round in the traced window (a count,
averaged over the cell's chips)."""


def read(rec: dict):
    t = rec.get("trace")
    if not t or not rec.get("rounds"):
        return None
    return t["ops"] / rec["rounds"]
