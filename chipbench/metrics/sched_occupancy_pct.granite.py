"""Live slots over slots, averaged over the window's step dispatches (the
server's own step and lane-step counters, read at the window's two ends)."""


def read(run):
    c = run.get("counters", {})
    if not c.get("steps"):
        return None
    return 100.0 * c["occupied_lane_steps"] / (c["steps"] * c["num_slots"])
