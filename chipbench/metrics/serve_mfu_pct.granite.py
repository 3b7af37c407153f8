"""2 x the parameters x the tokens the window computed (every output token
and every prompt token: no cache spares one here), over the window and the
chip's bf16 peak: the share of the whole step."""
from chipbench import shapes_granite


def read(run):
    peaks, w, c = run.get("peaks"), run["window"], run["counters"]
    if not peaks or c.get("prompt_tokens") is None:
        return None
    tokens = c["tokens_in_window"] + c["prompt_tokens"]
    return 100.0 * shapes_granite.served_flops(run["geometry"], tokens) \
        / (w["t_close"] - w["t_open"]) / peaks["bf16_flops_per_s"]
