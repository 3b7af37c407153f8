"""Share of the decode step's device time under ``mx.window_attn``: the
sliding layers' gather of their ring pages and the attention over them."""
from chipbench import dots3_trace


def read(run):
    return dots3_trace.region_pct(run, "mx.window_attn")
