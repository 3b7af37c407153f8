"""Share of the decode step's device time under ``mx.kv_write``: the new K and
V column written into the per-layer view inside the scan body."""
from chipbench import program_trace


def read(run):
    return program_trace.region_pct(run, "mx.kv_write")
