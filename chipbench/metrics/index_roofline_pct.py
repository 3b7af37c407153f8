"""The least time the indexer of one step needs (every live token's index
key read once) over the device time under ``mx.index``, which also holds the
exact top-k: a sort has no floor here, so this reads low."""
from chipbench import dots3_trace, shapes_dots3


def read(run):
    return dots3_trace.roofline_pct(
        run, lambda cfg, w: shapes_dots3.index_min(cfg, w["live_tokens"]),
        "mx.index")
