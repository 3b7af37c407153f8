"""Median, over every stream that ended in the window, of its time per output
token (the entry's own reading; a per-layer metric in this cell): the decode
step's time as a stream sees it, admissions in between included."""


def read(run):
    return run["end_to_end"].get("tpot_p50_ms")
