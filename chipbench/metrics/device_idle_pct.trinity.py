"""1 - (union of the device's operation intervals) / traced window.

``device_idle_pct.dots3``'s reader under this cell's name."""
from chipbench import trinity_trace

read = trinity_trace.reader_of("device_idle_pct.dots3")
