"""Share of the (routed layer, held expert) cells that got at least one token
in a step, from the step's own counter: what of the experts' weights a step
has to read.

``moe_experts_touched_pct``'s reader under this cell's name."""
from chipbench import trinity_trace

read = trinity_trace.reader_of("moe_experts_touched_pct")
