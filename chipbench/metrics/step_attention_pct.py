"""Share of the decode step's device time under ``mx.attn``: scores, mask,
softmax and the weighted sum."""
from chipbench import program_trace


def read(run):
    return program_trace.region_pct(run, "mx.attn")
