"""Host milliseconds the scheduler spends a step dispatch: every ``mx:serve:*``
phase span inside the window but the wait for the device
(``mx:serve:drain_wait``) and for work (``mx:serve:idle``), summed, over the
step dispatches."""
from chipbench import program_trace


def read(run):
    return program_trace.host_ms_per_dispatch(
        run, "mx:serve:", "mx:serve:step",
        leave_out=("mx:serve:drain_wait", "mx:serve:idle"))
