"""Share of the decode step's device time under ``mx.attn``: the full
layer's page walk over every cached token of every slot."""
from chipbench import dots3_trace


def read(run):
    return dots3_trace.region_pct(run, "mx.attn")
