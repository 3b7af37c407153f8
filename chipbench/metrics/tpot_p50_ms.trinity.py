"""Median, over every stream that ended in the window, of its time per output
token (the entry's own reading; a per-layer metric in this cell).

``tpot_p50_ms.dots3``'s reader under this cell's name."""
from chipbench import trinity_trace

read = trinity_trace.reader_of("tpot_p50_ms.dots3")
