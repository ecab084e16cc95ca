"""Device busy time per round in the traced window (ms), the busy time
averaged over the cell's chips."""


def read(rec: dict):
    t = rec.get("trace")
    if not t or not rec.get("rounds"):
        return None
    return 1e3 * t["busy_s"] / rec["rounds"]
