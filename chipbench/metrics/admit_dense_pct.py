"""Share of the admission executables' device time under the dense
products and what rides them: ``mx.dense``, ``mx.head``, ``mx.moe_shared``,
``mx.qk_norm_rope``."""
from chipbench import admit_trace


def read(run):
    return admit_trace.group_pct(run, "dense")
