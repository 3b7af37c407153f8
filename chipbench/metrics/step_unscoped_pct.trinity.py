"""Share of the trinity decode step's device time under no ``mx.*`` scope
(``while``, compiler-inserted copies, in-place scatter fusions).

``step_unscoped_pct.dots3``'s reader under this cell's name."""
from chipbench import trinity_trace

read = trinity_trace.reader_of("step_unscoped_pct.dots3")
