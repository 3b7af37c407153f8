"""The least time the decode step's cache-to-output leg needs — the K and V
of the tokens LIVE in the step read once at the HBM peak
(``shapes.kv_bytes_per_token``) — over the step's device time under
``mx.paged_view`` + ``mx.kv_write`` + ``mx.attn``: gather, row write and
attention on the view path, the page walk where a kernel reads the pool in
place.  The same work whatever implements the leg; it cannot pass 100, since
what is read holds at least the live tokens.  Live tokens a step: as
``step_hbm_roofline_pct`` counts them."""
import numpy as np

from chipbench import dots3_trace, shapes

_LEG = ("mx.paged_view", "mx.kv_write", "mx.attn")


def read(run):
    leg_s, peaks = dots3_trace.region_seconds(run, *_LEG), run.get("peaks")
    steps = run["counters"].get("steps")
    if leg_s is None or not peaks or not steps:
        return None
    w = run["window"]
    live = 0
    for r in run["records"]:
        t = np.asarray(r["times"][1:])
        k = np.nonzero((t >= w["t_open"]) & (t < w["t_close"]))[0] + 1
        live += int(np.sum(r["prompt_len"] + k))
    itemsize = np.dtype("float32").itemsize if run["config"]["dtype"] == \
        "float32" else 2
    least_s = live / steps * shapes.kv_bytes_per_token(
        run["geometry"], itemsize) / peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / leg_s
