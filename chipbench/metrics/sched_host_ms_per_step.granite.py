"""Host milliseconds the scheduler spends a step dispatch (every
``mx:serve:*`` phase but the waits), with 64 slots to route and table."""
from chipbench import program_trace


def read(run):
    return program_trace.host_ms_per_dispatch(
        run, "mx:serve:", "mx:serve:step",
        leave_out=("mx:serve:drain_wait", "mx:serve:idle"))
