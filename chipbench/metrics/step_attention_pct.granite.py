"""Share of the decode step's device time under ``mx.attn`` +
``mx.kv_write``: the four attention layers' page walk and row writes."""
from chipbench import dots3_trace


def read(run):
    return dots3_trace.region_pct(run, "mx.attn", "mx.kv_write")
