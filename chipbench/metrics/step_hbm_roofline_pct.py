"""The least time one decode step could take — every weight and the K and
V of the tokens LIVE in the step read once at the HBM peak
(``shapes.decode_step_min_bytes``) — over the step executable's device
time.  Live tokens a step: over every token the step executable emitted in
the window (a stream's second token on), its context length, summed and
divided by the window's step dispatches."""
import numpy as np

from chipbench import reduce, shapes


def read(run):
    step_s, peaks = reduce.step_device_s(run), run.get("peaks")
    steps = run["counters"].get("steps")
    if step_s is None or not peaks or not steps:
        return None
    w = run["window"]
    live = 0
    for r in run["records"]:
        t = np.asarray(r["times"][1:])
        k = np.nonzero((t >= w["t_open"]) & (t < w["t_close"]))[0] + 1
        live += int(np.sum(r["prompt_len"] + k))
    itemsize = np.dtype("float32").itemsize if run["config"]["dtype"] == \
        "float32" else 2
    least_s = shapes.decode_step_min_bytes(
        run["geometry"], live / steps, itemsize) / peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / step_s
