"""Share of the dots3 decode step's device time under no ``mx.*`` scope."""
from chipbench import dots3_trace, program_trace


def read(run):
    return dots3_trace.region_pct(run, program_trace.UNSCOPED)
