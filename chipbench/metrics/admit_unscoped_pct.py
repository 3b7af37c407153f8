"""Share of the admission executables' device time under no ``mx.*``
region: compiler copies, in-place scatters, fusions whose parts carry no
provenance."""
from chipbench import admit_trace


def read(run):
    return admit_trace.group_pct(run, "unscoped")
