"""Share of the decode step's device time under the state's update region
(``mx.ssm_state``): the kernel ``mx_retention_update`` — each live slot's
retention state decayed, updated and read by its query heads in place — and
the expansions of ``k`` and ``q`` in front of it."""
from chipbench import brumby_trace, dots3_trace


def read(run):
    return dots3_trace.region_pct(run, brumby_trace.STEP_REGION)
