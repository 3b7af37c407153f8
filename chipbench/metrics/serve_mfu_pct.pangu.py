"""2 x the parameters active for the tokens the window computed (every output
token, every prompt token the prefix cache did not serve, every routed
(token, held expert) pair), with the operations of every latent row the
steps' walks read (the counter ``latent_rows_walked``), over the window and
the chip's bf16 peak: the share of the whole step."""
from chipbench import shapes_pangu


def read(run):
    peaks, w, c = run.get("peaks"), run["window"], run["counters"]
    st = run.get("server_stats") or {}
    rows = (c.get("dispatch") or {}).get("latent_rows_walked")
    if not peaks or not rows or c.get("prompt_tokens") is None \
            or st.get("moe_tokens_per_expert_step") is None:
        return None
    cfg = run["geometry"]
    tokens = c["tokens_in_window"] + c["prompt_tokens"] \
        - c["prompt_tokens_cached"]
    routed = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    # the steps' routed pairs by the counter; a chunk's tokens are taken to
    # route as evenly: top_k choices over the router's width, the held share
    pairs = st["moe_tokens_per_expert_step"] * routed \
        * cfg["held_experts"][1] * c["steps"] \
        + (c["prompt_tokens"] - c["prompt_tokens_cached"]) * routed \
        * cfg["num_experts_per_tok"] * cfg["held_experts"][1] \
        / cfg["n_routed_experts"]
    return 100.0 * shapes_pangu.served_flops(cfg, tokens, pairs, rows) \
        / (w["t_close"] - w["t_open"]) / peaks["bf16_flops_per_s"]
