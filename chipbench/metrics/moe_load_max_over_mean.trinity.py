"""Mean over routed layers and steps of the busiest held expert's tokens over
the mean: how uneven the routing leaves the grouped product.

``moe_load_max_over_mean``'s reader under this cell's name."""
from chipbench import trinity_trace

read = trinity_trace.reader_of("moe_load_max_over_mean")
