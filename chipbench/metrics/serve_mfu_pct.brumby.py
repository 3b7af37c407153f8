"""2 x the parameters a token multiplies by for every token the window
computed (every output token and every prompt token), with the retention
state's operations (a step's update and readouts an output token, prefill's
pairs, writes and carried reads for the prompt tokens), over the window and
the chip's bf16 peak: the share of the whole step."""
from chipbench import shapes_brumby


def read(run):
    peaks, w, c = run.get("peaks"), run["window"], run["counters"]
    if not peaks or c.get("prompt_tokens") is None:
        return None
    d = c.get("dispatch") or {}
    flops = shapes_brumby.served_flops(
        run["geometry"], c["tokens_in_window"], c["prompt_tokens"],
        d.get("admit_dispatches", 0) + d.get("chunk_dispatches", 0),
        d.get("chunk_carried_tokens", 0))
    return 100.0 * flops / (w["t_close"] - w["t_open"]) \
        / peaks["bf16_flops_per_s"]
