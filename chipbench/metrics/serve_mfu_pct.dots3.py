"""2 x the parameters active for a token here x the tokens the window
computed (every output token, and the prompt tokens no cache spared), with
one expert for every (token, held expert) pair the steps counted, over the
window and the chip's bf16 peak: the share of the whole step."""
from chipbench import dots3_trace, shapes_dots3


def read(run):
    peaks, w, c = run.get("peaks"), run["window"], run["counters"]
    work = dots3_trace.step_work(run)
    if not peaks or work is None:
        return None
    tokens = c["tokens_in_window"] + c["prompt_tokens"] \
        - c["prompt_tokens_cached"]
    flops = shapes_dots3.served_flops(
        run["geometry"], tokens, work["expert_tokens"] * c["steps"])
    return 100.0 * flops / (w["t_close"] - w["t_open"]) \
        / peaks["bf16_flops_per_s"]
