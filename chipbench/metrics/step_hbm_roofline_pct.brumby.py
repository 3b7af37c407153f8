"""The least time one decode step could take — the layers' weights and the
head read once, each live slot's retention state read and written once, at
the HBM peak (``shapes_brumby.decode_step_min_bytes``) — over the step
executable's device time: the same work whatever implements it."""
from chipbench import brumby_trace, reduce, shapes_brumby


def read(run):
    step_s, peaks = reduce.step_device_s(run), run.get("peaks")
    slots = brumby_trace.live_slots(run)
    if step_s is None or not peaks or not slots:
        return None
    least = shapes_brumby.decode_step_min_bytes(run["geometry"], slots)
    return 100.0 * least / peaks["hbm_bytes_per_s"] / step_s
