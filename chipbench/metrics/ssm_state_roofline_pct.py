"""The least time the state's update of one step needs — each live slot's
state over the 36 state-space layers read once and written once at the HBM
peak (``shapes_granite.ssm_state_min``) — over the step's device seconds
under ``mx.ssm_state``: the same work whatever implements the region.  It
cannot pass 100: what the region moves holds at least the live slots'
states."""
from chipbench import dots3_trace, granite_trace, shapes_granite


def read(run):
    spent, peaks = dots3_trace.region_seconds(run, "mx.ssm_state"), \
        run.get("peaks")
    work = granite_trace.step_work(run)
    if spent is None or not peaks or work is None:
        return None
    floor = shapes_granite.floor_seconds(
        shapes_granite.ssm_state_min(run["geometry"], work["slots"]), peaks)
    return 100.0 * floor / spent
