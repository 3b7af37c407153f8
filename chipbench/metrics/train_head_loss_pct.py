"""Share of the train step's device time under ``mx.head``: ``ln_f``, the head
logits and the loss, forward and backward."""
from chipbench import program_trace


def read(run):
    return program_trace.region_pct(run, "mx.head")
