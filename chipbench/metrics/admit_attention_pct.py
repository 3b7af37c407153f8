"""Share of the admission executables' device time (every ``jit_admit*`` /
``jit_hit*`` / ``jit_chunk*`` run whole in the traced stretch) under the
regions that read or write cached context: ``mx.attn``, ``mx.window_attn``,
``mx.latent_attn``, ``mx.latent_gather``, ``mx.index``, ``mx.paged_view``,
``mx.kv_write``, ``mx.latent_write``, ``mx.page_write``."""
from chipbench import admit_trace


def read(run):
    return admit_trace.group_pct(run, "attention")
