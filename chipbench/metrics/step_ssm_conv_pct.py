"""Share of the decode step's device time under ``mx.ssm_conv``: the tail's
read, the depthwise convolution and the tail's write."""
from chipbench import dots3_trace


def read(run):
    return dots3_trace.region_pct(run, "mx.ssm_conv")
