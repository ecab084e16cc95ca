"""Device self time a round of the ops whose op_name path holds the
program's ``moe_dispatch`` scope, nested scopes included (ms): the
expert layers' routing and dispatch: the f32 router, the top-k, the sort
by expert, each tile's gather of its tokens and the weighted add back,
in the round's three server forwards. From ``trace["within_s"]``
(chipbench/scopes.py); None where no op names it."""


def read(rec: dict):
    t = rec.get("trace") or {}
    s = t.get("within_s", {}).get("moe_dispatch")
    if s is None or not rec.get("rounds"):
        return None
    return 1e3 * s / rec["rounds"]
