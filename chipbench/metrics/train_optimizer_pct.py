"""Share of the train step's device time under ``mx.optimizer``: the per-leaf
update."""
from chipbench import program_trace


def read(run):
    return program_trace.region_pct(run, "mx.optimizer")
