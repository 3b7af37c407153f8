"""The least time the sparse latent attention of one step needs (the
selected rows read once, scores and context in the absorbed form) over the
device time under ``mx.latent_gather`` and ``mx.latent_attn``."""
from chipbench import dots3_trace, shapes_dots3


def read(run):
    return dots3_trace.roofline_pct(
        run, lambda cfg, w: shapes_dots3.latent_attn_min(cfg, w["selected"]),
        "mx.latent_gather", "mx.latent_attn")
