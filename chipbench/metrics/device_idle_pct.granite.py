"""1 - (union of the device's operation intervals) / traced window."""


def read(run):
    trace = run.get("trace")
    return None if not trace else trace["idle_pct"]
