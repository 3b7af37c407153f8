"""What the brumby cell's per-layer metrics share: the live slots of a mean
step, and the admission executables' device seconds a run under the prefill
region against the prompt tokens a dispatch took into a state (the server's
counter ``admit_tokens``: no prefix hit skips any for a retention layer) and
those of them that read a carried state (``chunk_carried_tokens``).  The step executable's regions are
``dots3_trace``'s readers and the admission executables' ``admit_trace``'s
(nothing in them is particular to a model).  Every function returns ``None``
where there is nothing to read (a CPU run, an untraced run, a program
without the region or the counter): the metric is then left out, never 0."""
from chipbench import admit_trace

# the regions of a slot-table state: the step's in-place update, prefill's
# chunked form
STEP_REGION, PREFILL_REGION = "mx.ssm_state", "mx.ssm_scan"


def live_slots(run):
    """Slots stepping in a mean step of the window."""
    c = run["counters"]
    return c["occupied_lane_steps"] / c["steps"] if c.get("steps") else None


def admit_region_pct(run, *regions):
    """Share (%) of the admission executables' device time (every run of
    ``admit_trace``'s that lies whole in the traced stretch) under
    ``regions``."""
    rows = admit_trace._admission_rows() or {}
    total = sum(s for row in rows.values() for s in row["regions"].values())
    part = sum(row["regions"].get(r, 0.0) for row in rows.values()
               for r in regions)
    return 100.0 * part / total if total and part else None


def prefill_per_dispatch(run):
    """``(prompt tokens a state took, of them in chunks that continue a
    prompt, device seconds under the prefill region)`` of a mean admission
    dispatch: the counters over the window's admission and chunk dispatches
    (``chunk_carried_tokens`` 0 where the program has no such counter), the
    seconds over the admission runs whole in the traced stretch."""
    d, rows = admit_trace._dispatch(run), admit_trace._admission_rows()
    if not d or rows is None or not d.get("admit_tokens"):
        return None
    n = d.get("admit_dispatches", 0) + d.get("chunk_dispatches", 0)
    runs = sum(row["runs"] for row in rows.values())
    spent = sum(row["regions"].get(PREFILL_REGION, 0.0)
                for row in rows.values())
    if not n or not runs or not spent:
        return None
    return d["admit_tokens"] / n, d.get("chunk_carried_tokens", 0) / n, \
        spent / runs
