"""Share of the decode step's device time under ``mx.dense`` + ``mx.head`` +
``mx.moe_shared`` + ``mx.qk_norm_rope`` + ``mx.kv_write``: the projections,
the norms, the dense and shared feed-forwards, the head, the q/k norms and
rotation, the row writes."""
from chipbench import dots3_trace


def read(run):
    return dots3_trace.region_pct(run, "mx.dense", "mx.head",
                                  "mx.moe_shared", "mx.qk_norm_rope",
                                  "mx.kv_write")
