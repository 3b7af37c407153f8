"""The least time the sliding layers' attention of one step needs (each
slot's window of rows read once) over the device time under
``mx.window_attn``."""
from chipbench import dots3_trace, shapes_dots3


def read(run):
    return dots3_trace.roofline_pct(
        run, lambda cfg, w: shapes_dots3.window_attn_min(cfg, w["window_pairs"]),
        "mx.window_attn")
