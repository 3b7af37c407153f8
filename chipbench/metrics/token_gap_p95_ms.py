"""95th percentile over every gap between consecutive tokens of every
stream, at the client, for the gaps that close in the window.  A per-layer
metric: a step with an admission wave before it is a longer gap, the waves
are of a few sizes, and the 95th percentile sits among them, so it jumps
between their levels from run to run (PERF.md section 2)."""
import numpy as np


def read(run):
    w = run["window"]
    gaps = [(b - a) * 1e3 for r in run["records"]
            for a, b in zip(r["times"], r["times"][1:])
            if w["t_open"] <= b < w["t_close"]]
    return float(np.percentile(gaps, 95)) if gaps else None
