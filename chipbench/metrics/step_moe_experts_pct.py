"""Share of the decode step's device time under ``mx.moe_experts``: the
routed experts' grouped products, the sort and the weighted sum back."""
from chipbench import dots3_trace


def read(run):
    return dots3_trace.region_pct(run, "mx.moe_experts")
