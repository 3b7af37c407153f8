"""What the per-layer metrics read from the program's own tracing, kept
in memory by the program while the profiler facade runs (the raw trace is
gone before any reader runs):

- ``mxnet_tpu.profiler.device_regions()``: per executable, the device
  self seconds under each ``mx.*`` named scope (``"unscoped"`` for the
  rest) over its runs that lie whole in the traced stretch;
- ``mxnet_tpu.telemetry.spans()``: the phase spans of the host loops,
  ``(name, t0, t1, seq, cause, fields)`` on ``run["window"]``'s clock.

A program that has neither (an older checkout), a CPU run and a stretch
with too little in it all read as ``None``: the metric is then left out.
"""
import statistics

UNSCOPED = "unscoped"


def step_regions(run):
    """``{region: seconds}`` of the configuration's step executable (the
    configuration file's ``executables.step``), or ``None``."""
    try:
        from mxnet_tpu import profiler
        table = profiler.device_regions()
    except Exception:       # no such reader in this program: nothing read
        return None
    row = (table or {}).get(
        run["config"].get("executables", {}).get("step"))
    if not row or not row["runs"] or not sum(row["regions"].values()):
        return None
    return row["regions"]


def region_pct(run, *regions):
    """Share (%) of the step executable's device time under ``regions``."""
    table = step_regions(run)
    if table is None:
        return None
    return 100.0 * sum(table.get(r, 0.0) for r in regions) \
        / sum(table.values())


def window_spans(run, prefix):
    """The program's spans called ``prefix...`` that lie whole inside the
    run's window, oldest first."""
    try:
        from mxnet_tpu import telemetry
        rows = telemetry.spans()
    except Exception:
        return []
    w = run["window"]
    return sorted((r for r in rows if r[0].startswith(prefix)
                   and r[1] >= w["t_open"] and r[2] <= w["t_close"]),
                  key=lambda r: r[1])


def host_ms_per_dispatch(run, prefix, dispatch, leave_out=()):
    """Host milliseconds in the ``prefix...`` spans (but ``leave_out``)
    for each ``dispatch`` span among them."""
    rows = window_spans(run, prefix)
    n = sum(1 for r in rows if r[0] == dispatch)
    if not n:
        return None
    return 1e3 * sum(r[2] - r[1] for r in rows
                     if r[0] not in leave_out) / n


def admit_stall_ms(run):
    """How much later a step's tokens reach the host when an admission
    wave ran on the device before it.  A step dispatch's ``seq`` is its
    place in the device's queue, and its ``mx:serve:route`` span (``cause``
    = that ``seq``) starts when its readback has arrived: over consecutive
    step dispatches, the median arrival-to-arrival with another dispatch's
    ``seq`` between theirs, less the median without.  (Start to start of
    the ``mx:serve:step`` spans themselves reads the host's few
    milliseconds: the scheduler dispatches one step ahead of the device.)"""
    rows = window_spans(run, "mx:serve:")
    steps = sorted(r[3] for r in rows if r[0] == "mx:serve:step")
    arrived = {r[4]: r[1] for r in rows if r[0] == "mx:serve:route"}
    gaps = {True: [], False: []}
    for a, b in zip(steps, steps[1:]):
        if a in arrived and b in arrived:
            gaps[b - a > 1].append(arrived[b] - arrived[a])
    if not gaps[True] or not gaps[False]:
        return None
    return 1e3 * (statistics.median(gaps[True])
                  - statistics.median(gaps[False]))
