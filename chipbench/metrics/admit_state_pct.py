"""Share of the admission executables' device time under the state-space
layers' own work: ``mx.ssm_scan``, ``mx.ssm_state``, ``mx.ssm_conv``,
``mx.ssm_gate``."""
from chipbench import admit_trace


def read(run):
    return admit_trace.group_pct(run, "state")
