"""The least time the full layer's attention of one step needs (the K and V
rows of every cached token in front of the step's queries read once) over the
device time under ``mx.attn``: the page-walk kernel from position 0."""
from chipbench import shapes_trinity, trinity_trace


def read(run):
    return trinity_trace.roofline_pct(
        run, lambda cfg, w: shapes_trinity.full_attn_min(
            cfg, w["live_tokens"]), "mx.attn")
