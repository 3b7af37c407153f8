"""Share of the device's busy time in the traced window that the admission
executables take (``jit_admit``, ``jit_hit``, ``jit_chunk`` of the reduced
trace's ``modules``): prefill's share."""
ADMISSION = ("jit_admit", "jit_hit", "jit_chunk")


def read(run):
    trace = run.get("trace")
    if not trace or not trace["busy_s"]:
        return None
    return 100.0 * sum(row["seconds"] for name, row in
                       trace["modules"].items()
                       if name.startswith(ADMISSION)) / trace["busy_s"]
