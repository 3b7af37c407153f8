"""2 x the parameters active for the tokens the window computed (every output
token, every prompt token the prefix cache did not serve, every routed
(token, held expert) pair of a step) over the window and the chip's bf16
peak: the share of the whole step."""
from chipbench import shapes_trinity


def read(run):
    peaks, w, c = run.get("peaks"), run["window"], run["counters"]
    st = run.get("server_stats") or {}
    if not peaks or c.get("prompt_tokens") is None \
            or st.get("moe_tokens_per_expert_step") is None:
        return None
    cfg = run["geometry"]
    tokens = c["tokens_in_window"] + c["prompt_tokens"] \
        - c["prompt_tokens_cached"]
    routed = cfg["num_hidden_layers"] - cfg["num_dense_layers"]
    cells = routed * cfg["held_experts"][1]
    # the steps' routed pairs by the counter; a chunk's tokens are taken to
    # route as evenly: top_k choices over the router's width, the held share
    pairs = st["moe_tokens_per_expert_step"] * cells * c["steps"] \
        + (c["prompt_tokens"] - c["prompt_tokens_cached"]) * routed \
        * cfg["num_experts_per_tok"] * cfg["held_experts"][1] \
        / cfg["num_experts"]
    return 100.0 * shapes_trinity.served_flops(cfg, tokens, pairs) \
        / (w["t_close"] - w["t_open"]) / peaks["bf16_flops_per_s"]
