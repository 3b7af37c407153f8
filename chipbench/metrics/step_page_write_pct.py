"""Share of the decode step's device time under ``mx.page_write``: the
scatter of the new columns into the page pool after the scan."""
from chipbench import program_trace


def read(run):
    return program_trace.region_pct(run, "mx.page_write")
