"""Share of the decode step's device time under ``mx.index``: the indexer's
projections, its scores over the paged index keys and the exact top-k."""
from chipbench import dots3_trace


def read(run):
    return dots3_trace.region_pct(run, "mx.index")
