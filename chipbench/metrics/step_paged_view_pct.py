"""Share of the decode step's device time under ``mx.paged_view``: the gather
through the page table into each slot's T-wide view (dequantisation,
``moveaxis`` and ``reshape`` with it)."""
from chipbench import program_trace


def read(run):
    return program_trace.region_pct(run, "mx.paged_view")
