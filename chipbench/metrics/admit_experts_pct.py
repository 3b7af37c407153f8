"""Share of the admission executables' device time under the routed
experts: ``mx.moe_experts`` (the grouped products) and ``mx.moe_route``."""
from chipbench import admit_trace


def read(run):
    return admit_trace.group_pct(run, "experts")
