"""2 x parameters x (tokens served in the window) over the window and the
chip's bf16 peak.  Served tokens: every output token delivered in the
window, and the prompt of every request whose first token arrived in it
(whether or not a cache spared the prefill)."""
from chipbench import shapes


def read(run):
    peaks, w = run.get("peaks"), run["window"]
    if not peaks:
        return None
    tokens = 0
    for r in run["records"]:
        tokens += sum(1 for t in r["times"]
                      if w["t_open"] <= t < w["t_close"])
        if r["times"] and w["t_open"] <= r["times"][0] < w["t_close"]:
            tokens += r["prompt_len"]
    flops = shapes.served_flops(run["geometry"], tokens)
    return 100.0 * flops / (w["t_close"] - w["t_open"]) \
        / peaks["bf16_flops_per_s"]
