"""Device self time a round of the ops whose op_name path holds the
program's ``moe_experts`` scope, nested scopes included (ms): the held
routed experts' SwiGLU matmuls over the tokens routed to them, in the
round's three server forwards. From ``trace["within_s"]``
(chipbench/scopes.py); None where no op names it."""


def read(rec: dict):
    t = rec.get("trace") or {}
    s = t.get("within_s", {}).get("moe_experts")
    if s is None or not rec.get("rounds"):
        return None
    return 1e3 * s / rec["rounds"]
