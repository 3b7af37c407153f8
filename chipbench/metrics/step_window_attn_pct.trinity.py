"""Share of the decode step's device time under ``mx.window_attn``: the four
sliding layers' walk of each slot's ring from its window's first page."""
from chipbench import dots3_trace


def read(run):
    return dots3_trace.region_pct(run, "mx.window_attn")
