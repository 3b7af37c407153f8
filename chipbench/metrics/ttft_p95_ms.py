"""95th percentile, over every request submitted in the window, of submit ->
first token at the client (the benchmark's own clock); a request that failed
counts as the wait to the run's end.  A per-layer metric here because a
closed loop with as many clients as slots runs at capacity, where a tail
over the window's few dozen admissions swings from run to run (PERF.md
section 2)."""
import numpy as np


def read(run):
    w = run["window"]
    waits = [((r["times"][0] if r["times"] and r["error"] is None
               else w["t_end"]) - r["submit"]) * 1e3
             for r in run["records"]
             if w["t_open"] <= r["submit"] < w["t_close"]]
    return float(np.percentile(waits, 95)) if waits else None
