"""Device milliseconds of one run of the trinity step executable in the
traced window (``reduce.step_device_s``).

``step_device_ms.dots3``'s reader under this cell's name."""
from chipbench import trinity_trace

read = trinity_trace.reader_of("step_device_ms.dots3")
