"""Share of the decode step's device time under ``mx.latent_attn`` and
``mx.latent_write``: the new latent rows written through the page table and
the attention over every cached row (the page-walk kernel
``mx_latent_paged_attention``, the queries' absorption and the context's way
back through ``W_kvb``)."""
from chipbench import dots3_trace


def read(run):
    return dots3_trace.region_pct(run, "mx.latent_attn", "mx.latent_write")
