"""Share of the decode step's device time under ``mx.latent_gather`` and
``mx.latent_attn``: the selected latent rows gathered through the page table
and the absorbed attention over them (full layers)."""
from chipbench import dots3_trace


def read(run):
    return dots3_trace.region_pct(run, "mx.latent_gather", "mx.latent_attn")
