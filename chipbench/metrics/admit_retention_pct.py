"""Share of the admission executables' device time (``jit_admit*``,
``jit_chunk*``) under prefill's chunked form of the retention state
(``mx.ssm_scan``: ``ops.power_retention.chunk_scan``)."""
from chipbench import brumby_trace


def read(run):
    return brumby_trace.admit_region_pct(run, brumby_trace.PREFILL_REGION)
