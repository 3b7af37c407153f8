"""95th percentile, over every request submitted in the window, of submit ->
first token at the client: queue, prefix hit, the question's chunk and one
step; a request that failed counts as the wait to the run's end."""
import numpy as np


def read(run):
    w = run["window"]
    waits = [((r["times"][0] if r["times"] and r["error"] is None
               else w["t_end"]) - r["submit"]) * 1e3
             for r in run["records"]
             if w["t_open"] <= r["submit"] < w["t_close"]]
    return float(np.percentile(waits, 95)) if waits else None
