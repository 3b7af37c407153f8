"""Share of the train step's device time under ``mx.attn``: the attention
core, forward and backward."""
from chipbench import program_trace


def read(run):
    return program_trace.region_pct(run, "mx.attn")
