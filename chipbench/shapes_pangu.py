"""Operations and bytes of the openPangu-Ultra-MoE-718B configuration from its
shapes alone: the yardstick behind ``serve_mfu_pct.pangu``,
``step_hbm_roofline_pct.pangu`` and ``latent_walk_roofline_pct.pangu``.
Nothing here knows of pages, padding to lane tiles or of how a page walk or
a grouped product is implemented — only what the algorithm needs: every weight a step uses read once, every cached
latent row a query attends to read once, two operations a multiply-add.
``cfg`` is ``pangu.reference_config``'s dict (published keys,
``held_experts``, the router's full width).
"""

BYTES = 2       # bfloat16 weights and cache rows


def _routed(cfg):
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def attention_params(cfg):
    """One layer's W_qa, W_qb, W_kva, W_kvb and W_o."""
    H, hh = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, r = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    return (H * rq + rq * hh * (dn + dr) + H * (r + dr) + r * hh * (dn + dv)
            + hh * dv * H)


def expert_params(cfg):
    """One expert (and the shared one): three H x width matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def fixed_params(cfg):
    """Parameters every token's step multiplies by, whatever the routing:
    the attention of every layer, the dense feed-forward, the shared
    experts, the routers and the head (the embedding is a row read, not a
    product)."""
    H = cfg["hidden_size"]
    return (cfg["num_hidden_layers"] * attention_params(cfg)
            + cfg["first_k_dense_replace"] * 3 * H * cfg["intermediate_size"]
            + _routed(cfg) * (cfg["n_shared_experts"] * expert_params(cfg)
                              + H * cfg["n_routed_experts"])
            + H * cfg["vocab_size"])


def total_params(cfg):
    """Everything resident: fixed + the held experts + the embedding."""
    return (fixed_params(cfg) + cfg["hidden_size"] * cfg["vocab_size"]
            + _routed(cfg) * cfg["held_experts"][1] * expert_params(cfg))


def served_flops(cfg, tokens, expert_tokens, rows):
    """2 x the parameters active for a token HERE: the fixed ones for each
    of ``tokens`` and one expert for each of ``expert_tokens`` (token, held
    expert) pairs the routed layers ran, and the latent attention's
    operations over ``rows`` (query, cached row) pairs the steps' walks
    read; the chunks' attention over the cache is left out, so the share can
    only read low."""
    return 2 * (fixed_params(cfg) * tokens
                + expert_params(cfg) * expert_tokens) \
        + latent_walk_min(cfg, rows)[1]


def moe_experts_min(cfg, touched_experts, expert_tokens):
    """``(bytes, flops)`` of the routed experts of ONE step over all routed
    layers: the weights of the ``touched_experts`` (layer, expert) cells
    read once, 2 x an expert's parameters a (token, expert) pair."""
    return (touched_experts * expert_params(cfg) * BYTES,
            2 * expert_params(cfg) * expert_tokens)


def latent_row_bytes(cfg):
    """One cached position's latent row of one layer: ``[c_kv | k_rope]``."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * BYTES


def latent_walk_min(cfg, rows):
    """``(bytes, flops)`` of the latent attention's walks of ONE step over
    ``rows`` (query, cached row) pairs summed over the slots and the layers:
    each row read once; every head's score against the whole row and its
    weight times the row's ``c_kv``, in the absorbed form."""
    r, dr = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    hh = cfg["num_attention_heads"]
    return (rows * latent_row_bytes(cfg), rows * 2 * hh * ((r + dr) + r))


def decode_step_min_bytes(cfg, touched_experts, rows):
    """Least HBM traffic of ONE decode step: every fixed weight once, the
    touched experts once, and the latent rows the walks must read."""
    return (fixed_params(cfg) * BYTES
            + moe_experts_min(cfg, touched_experts, 0)[0]
            + latent_walk_min(cfg, rows)[0])


def floor_seconds(bytes_flops, peaks):
    """The longer of reading the bytes and doing the operations at the
    chip's peaks."""
    b, f = bytes_flops
    return max(b / peaks["hbm_bytes_per_s"], f / peaks["bf16_flops_per_s"])
