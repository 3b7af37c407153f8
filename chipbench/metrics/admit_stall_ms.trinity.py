"""How much later a step's tokens arrive when an admission (a prefix hit and
one 128-token chunk) ran on the device before it: median arrival-to-arrival
of consecutive step dispatches with another dispatch's ``seq`` between
theirs, less the median without (``program_trace.admit_stall_ms``).

``admit_stall_ms.granite``'s reader under this cell's name."""
from chipbench import trinity_trace

read = trinity_trace.reader_of("admit_stall_ms.granite")
