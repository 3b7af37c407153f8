"""The least time one decode step could take — every fixed weight and every
touched expert read once, the live tokens' K and V rows of the full layer and
each slot's window of rows of the sliding layers read once, at the HBM peak
(``shapes_trinity.decode_step_min_bytes``) — over the step executable's
device time: the same work whatever implements it."""
from chipbench import reduce, shapes_trinity, trinity_trace


def read(run):
    step_s, peaks = reduce.step_device_s(run), run.get("peaks")
    work = trinity_trace.step_work(run)
    if step_s is None or not peaks or work is None:
        return None
    least = shapes_trinity.decode_step_min_bytes(
        run["geometry"], work["touched"], work["live_tokens"],
        work["window_pairs"])
    return 100.0 * least / peaks["hbm_bytes_per_s"] / step_s
