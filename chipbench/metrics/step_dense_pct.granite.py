"""Share of the decode step's device time under ``mx.dense`` + ``mx.head``:
projections, feed-forward, norms, the tied head and sampling."""
from chipbench import dots3_trace


def read(run):
    return dots3_trace.region_pct(run, "mx.dense", "mx.head")
