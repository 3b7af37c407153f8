"""What the pangu cell's per-layer metrics share: the latent rows the decode
steps' page walks read, from the server's own counter differenced over the
window.  The step executable's regions are ``dots3_trace``'s readers and the
admission executables' ``admit_trace``'s (nothing in them is particular to a
model).  Every function returns ``None`` where there is nothing to read (a
CPU run, an untraced run, a program without the counter): the metric is then
left out, never 0."""


def rows_per_step(run):
    """Latent rows one step's walks read, summed over the live slots and the
    latent layers: the counter ``latent_rows_walked`` over the window's
    step dispatches."""
    d = (run.get("counters") or {}).get("dispatch") or {}
    steps, rows = d.get("step_dispatches"), d.get("latent_rows_walked")
    if not run.get("trace") or not steps or not rows:
        return None
    return rows / steps
