"""Prompt tokens the prefix cache served over prompt tokens admitted in the
window (``DecodeServer.stats()``).

``prefix_hit_token_pct``'s reader under this cell's name."""
from chipbench import trinity_trace

read = trinity_trace.reader_of("prefix_hit_token_pct")
