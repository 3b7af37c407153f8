"""Host milliseconds the scheduler spends a step dispatch (every
``mx:serve:*`` phase but the waits), with 24 slots, two tables and the
window pool's pages to take and let go.

``sched_host_ms_per_step.granite``'s reader under this cell's name."""
from chipbench import trinity_trace

read = trinity_trace.reader_of("sched_host_ms_per_step.granite")
