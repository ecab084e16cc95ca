"""Device self time a round of the ops whose op_name path holds the
program's ``mla`` scope, nested scopes included (ms): the latent
attention (MLA) of every layer: its projections, YaRN rope, scores and
values, in the round's three server forwards. From ``trace["within_s"]``
(chipbench/scopes.py); None where no op names it."""


def read(rec: dict):
    t = rec.get("trace") or {}
    s = t.get("within_s", {}).get("mla")
    if s is None or not rec.get("rounds"):
        return None
    return 1e3 * s / rec["rounds"]
