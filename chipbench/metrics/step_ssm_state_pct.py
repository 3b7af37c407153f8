"""Share of the decode step's device time under ``mx.ssm_state``: the
recurrent state's decay, rank-one update and readout (``ops/ssd.py``'s
``mx_ssm_update`` on the chip)."""
from chipbench import dots3_trace


def read(run):
    return dots3_trace.region_pct(run, "mx.ssm_state")
