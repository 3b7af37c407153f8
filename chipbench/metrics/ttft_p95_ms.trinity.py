"""95th percentile, over every request submitted in the window, of submit ->
first token at the client (the entry's own clock; a request with none counts
to the run's end).  Per-layer: a closed loop runs at capacity.

``ttft_p95_ms.granite``'s reader under this cell's name."""
from chipbench import trinity_trace

read = trinity_trace.reader_of("ttft_p95_ms.granite")
