"""Share of the device's busy time in the traced window that the admission
executables take (the prefill of one prompt of up to 768 tokens a
dispatch: ``admit_sizes`` [1])."""


def read(run):
    trace = run.get("trace")
    if not trace or not trace["busy_s"]:
        return None
    return 100.0 * sum(row["seconds"] for name, row in
                       trace["modules"].items()
                       if name.startswith(("jit_admit", "jit_hit",
                                           "jit_chunk"))) / trace["busy_s"]
