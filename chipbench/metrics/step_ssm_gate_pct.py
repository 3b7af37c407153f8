"""Share of the decode step's device time under ``mx.ssm_gate``: the skip,
the gate and the norm over the inner width."""
from chipbench import dots3_trace


def read(run):
    return dots3_trace.region_pct(run, "mx.ssm_gate")
