"""Admission's device microseconds per real prompt token: over the
admission executables, a whole run's mean device time in the traced stretch
times that executable's dispatches in the window, summed, over the window's
``admit_tokens``."""
from chipbench import admit_trace


def read(run):
    return admit_trace.device_us_per_token(run)
