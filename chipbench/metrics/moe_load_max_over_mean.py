"""Mean over routed layers and steps of the busiest held expert's tokens over
the mean: how uneven the routing leaves the grouped product."""


def read(run):
    return (run.get("server_stats") or {}).get("moe_load_max_over_mean")
