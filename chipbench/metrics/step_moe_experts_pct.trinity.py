"""Share of the decode step's device time under ``mx.moe_experts``: the
grouped products over the held experts that got a token.

``step_moe_experts_pct``'s reader under this cell's name."""
from chipbench import trinity_trace

read = trinity_trace.reader_of("step_moe_experts_pct")
