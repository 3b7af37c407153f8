"""Share of the (routed layer, held expert) cells that got at least one token
in a step, from the step's own counter: what of the experts' weights a step
has to read."""


def read(run):
    v = (run.get("server_stats") or {}).get("moe_experts_touched_share")
    return None if v is None else 100.0 * v
