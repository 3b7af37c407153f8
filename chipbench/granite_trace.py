"""What the granite cell's per-layer metrics share: the step's work as the
window's clock readings give it, and an admission executable's device seconds
by region.  The step executable's own regions are ``dots3_trace``'s readers
(nothing in them is particular to a model).  Every function returns ``None``
where there is nothing to read (a CPU run, an untraced run, a program
without the region): the metric is then left out, never 0."""
import numpy as np

_ADMITS = ("jit_admit", "jit_chunk")


def step_work(run):
    """One mean step of the window: ``slots`` stepping and ``live_tokens``
    cached in front of their queries (over every token a step emitted in the
    window — a stream's second token on — its context length, summed and
    divided by the window's step dispatches)."""
    c, w = run["counters"], run["window"]
    steps = c.get("steps")
    if not steps:
        return None
    live = 0
    for r in run["records"]:
        t = np.asarray(r["times"][1:])
        k = np.nonzero((t >= w["t_open"]) & (t < w["t_close"]))[0] + 1
        live += int(np.sum(r["prompt_len"] + k))
    return {"slots": c["occupied_lane_steps"] / steps,
            "live_tokens": live / steps}


def admit_region_pct(run, *regions):
    """Share (%) of the admission executables' device time (every
    ``jit_admit*`` and ``jit_chunk*`` the traced stretch ran whole) under
    ``regions``."""
    try:
        from mxnet_tpu import profiler
        table = profiler.device_regions()
    except Exception:
        return None
    rows = [row for name, row in (table or {}).items()
            if name.startswith(_ADMITS) and row["runs"]]
    total = sum(sum(row["regions"].values()) for row in rows)
    part = sum(row["regions"].get(r, 0.0) for row in rows for r in regions)
    return 100.0 * part / total if total and part else None
