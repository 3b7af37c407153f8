"""The least time one decode step could take — the fixed weights, the
experts a step touched, the live tokens' index keys, the selected latent rows
and the sliding layers' windows, each read once at the HBM peak
(``shapes_dots3.decode_step_min_bytes``) — over the step executable's device
time: the same work whatever implements it."""
from chipbench import dots3_trace, reduce, shapes_dots3


def read(run):
    step_s, peaks = reduce.step_device_s(run), run.get("peaks")
    work = dots3_trace.step_work(run)
    if step_s is None or not peaks or work is None:
        return None
    least = shapes_dots3.decode_step_min_bytes(
        run["geometry"], work["touched"], work["live_tokens"],
        work["selected"], work["window_pairs"])
    return 100.0 * least / peaks["hbm_bytes_per_s"] / step_s
