"""The least time the state's update of one step needs — each live slot's
``S`` and ``z`` over the 8 layers, at the ``d (d + 1) / 2`` distinct rows of
the expansion, read once and written once at the HBM peak
(``shapes_brumby.retention_state_min``) — over the step's device seconds
under the update's region: the same work whatever implements it.  It cannot
pass 100: what the region moves holds at least the live slots' states."""
from chipbench import brumby_trace, dots3_trace, shapes_brumby


def read(run):
    spent, peaks = dots3_trace.region_seconds(
        run, brumby_trace.STEP_REGION), run.get("peaks")
    slots = brumby_trace.live_slots(run)
    if spent is None or not peaks or not slots:
        return None
    floor = shapes_brumby.floor_seconds(
        shapes_brumby.retention_state_min(run["geometry"], slots), peaks)
    return 100.0 * floor / spent
