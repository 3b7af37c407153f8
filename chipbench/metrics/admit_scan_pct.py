"""Share of the admission executables' device time (``jit_admit*``,
``jit_chunk*``) under ``mx.ssm_scan``: prefill's chunked scan."""
from chipbench import granite_trace


def read(run):
    return granite_trace.admit_region_pct(run, "mx.ssm_scan")
