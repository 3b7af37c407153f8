"""Share of the decode step's device time under ``mx.moe_route``: the
router's scores over all 256 experts and the choice of the top 4.

``step_moe_route_pct``'s reader under this cell's name."""
from chipbench import trinity_trace

read = trinity_trace.reader_of("step_moe_route_pct")
