"""The least time one decode step could take — every weight, each live
slot's recurrent state and tail read and written once, the live tokens' K
and V rows read once, at the HBM peak
(``shapes_granite.decode_step_min_bytes``) — over the step executable's
device time: the same work whatever implements it."""
from chipbench import granite_trace, reduce, shapes_granite


def read(run):
    step_s, peaks = reduce.step_device_s(run), run.get("peaks")
    work = granite_trace.step_work(run)
    if step_s is None or not peaks or work is None:
        return None
    least = shapes_granite.decode_step_min_bytes(
        run["geometry"], work["slots"], work["live_tokens"])
    return 100.0 * least / peaks["hbm_bytes_per_s"] / step_s
