"""Share of the device's busy time in the traced window that the admission
executables take (a prefix hit and one 128-token chunk a request).

``admit_device_pct.dots3``'s reader under this cell's name."""
from chipbench import trinity_trace

read = trinity_trace.reader_of("admit_device_pct.dots3")
