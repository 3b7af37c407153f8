"""Share of the granite decode step's device time under no ``mx.*`` scope
(``while``, compiler-inserted copies, in-place scatter fusions)."""
from chipbench import dots3_trace, program_trace


def read(run):
    return dots3_trace.region_pct(run, program_trace.UNSCOPED)
