"""95th percentile, over every request submitted in the window, of submit ->
first token at the client (the entry's own clock; a request with none counts
to the run's end).  Per-layer: a closed loop runs at capacity."""


def read(run):
    return run["end_to_end"].get("ttft_p95_ms")
