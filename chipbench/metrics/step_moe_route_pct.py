"""Share of the decode step's device time under ``mx.moe_route``: the
router's scores over all experts and the choice of the top 8."""
from chipbench import dots3_trace


def read(run):
    return dots3_trace.region_pct(run, "mx.moe_route")
