"""Share of the train step's device time under no ``mx.*`` scope."""
from chipbench import program_trace


def read(run):
    return program_trace.region_pct(run, program_trace.UNSCOPED)
