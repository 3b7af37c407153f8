"""The least time the sliding layers' attention of one step needs (each
slot's window of K and V rows read once) over the device time under
``mx.window_attn``: the same kernel walking the ring from a start."""
from chipbench import shapes_trinity, trinity_trace


def read(run):
    return trinity_trace.roofline_pct(
        run, lambda cfg, w: shapes_trinity.window_attn_min(
            cfg, w["window_pairs"]), "mx.window_attn")
