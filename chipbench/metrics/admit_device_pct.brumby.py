"""Share of the device's busy time in the traced window that the admission
executables take (``jit_admit``, ``jit_chunk``: one prompt of up to 1,024
tokens a dispatch, a longer one in chunks of 1,024): prefill's share beside
the decode steps."""


def read(run):
    trace = run.get("trace")
    if not trace or not trace["busy_s"]:
        return None
    return 100.0 * sum(row["seconds"] for name, row in
                       trace["modules"].items()
                       if name.startswith(("jit_admit", "jit_hit",
                                           "jit_chunk"))) / trace["busy_s"]
