"""Device milliseconds of one run of the brumby step executable in the
traced window (``reduce.step_device_s``)."""
from chipbench import reduce


def read(run):
    s = reduce.step_device_s(run)
    return None if s is None else s * 1e3
