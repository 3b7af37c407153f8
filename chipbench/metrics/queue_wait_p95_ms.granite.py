"""95th percentile, over the requests submitted in the window, of submit ->
admit dispatch as the server's own ``serve_request`` event records it
(``queue_wait_s``): with 64 clients on 64 slots, the wait for the admissions
queued before one's own."""
import numpy as np


def read(run):
    w = run["window"]
    waits = [r["queue_wait_s"] for r in run["records"]
             if r.get("queue_wait_s") is not None
             and w["t_open"] <= r["submit"] < w["t_close"]]
    if not waits:
        return None
    return float(np.percentile(waits, 95)) * 1e3
