"""Live slots over slots, averaged over the window's step dispatches (the
server's own step and lane-step counters, read at the window's two ends).

``sched_occupancy_pct.granite``'s reader under this cell's name."""
from chipbench import trinity_trace

read = trinity_trace.reader_of("sched_occupancy_pct.granite")
