"""Operations and bytes of the dots3_note configuration from its shapes
alone: the yardstick behind ``serve_mfu_pct.dots3``,
``step_hbm_roofline_pct.dots3`` and the ``*_roofline_pct`` of the decode
step's parts.  Nothing here knows of pages, padding to lane tiles, the ring
of the window pool or of how a grouped product is implemented — only what the
algorithm needs: every weight a step uses read once, every cached row a
query attends to read once, two operations a multiply-add.  ``cfg`` is
``dots3.reference_config``'s dict (published keys, ``held_experts``, the
router's full width).
"""

BYTES = 2       # bfloat16 weights and cache rows


def _layers(cfg):
    types = cfg["layer_types"][:cfg["num_hidden_layers"]]
    full = sum(1 for t in types if t == "full_attention")
    routed = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    return full, len(types) - full, routed


def _attention_params(cfg, full):
    p = "" if full else "swa_"
    H, hh = cfg["hidden_size"], cfg[p + "num_attention_heads"]
    rq, r = cfg[p + "q_lora_rank"], cfg[p + "kv_lora_rank"]
    dn, dr, dv = (cfg[p + "qk_nope_head_dim"], cfg[p + "qk_rope_head_dim"],
                  cfg[p + "v_head_dim"])
    n = (H * rq + rq * hh * (dn + dr) + H * (r + dr) + r * hh * (dn + dv)
         + hh * dv * H + H * hh)
    if full:
        n += (rq * cfg["index_n_heads"] * cfg["index_head_dim"]
              + H * cfg["index_head_dim"] + H * cfg["index_n_heads"])
    return n


def expert_params(cfg):
    """One expert (and the shared one): three H x width matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def fixed_params(cfg):
    """Parameters every token's step multiplies by, whatever the routing:
    the attention of every layer, the dense FFN, the shared experts, the
    routers and the head (the embedding is a row read, not a product)."""
    full, slide, routed = _layers(cfg)
    H = cfg["hidden_size"]
    return (full * _attention_params(cfg, True)
            + slide * _attention_params(cfg, False)
            + cfg["first_k_dense_replace"] * 3 * H * cfg["intermediate_size"]
            + routed * (cfg["n_shared_experts"] * expert_params(cfg)
                        + H * cfg["n_routed_experts"])
            + H * cfg["vocab_size"])


def total_params(cfg):
    """Everything resident: fixed + the held experts + the embedding."""
    _, _, routed = _layers(cfg)
    return (fixed_params(cfg) + cfg["hidden_size"] * cfg["vocab_size"]
            + routed * cfg["held_experts"][1] * expert_params(cfg))


def served_flops(cfg, tokens, expert_tokens):
    """2 x the parameters active for a token HERE: the fixed ones for each
    of ``tokens`` and one expert for each of ``expert_tokens`` (token,
    held expert) pairs the routed layers ran; attention over the cache is
    left out, so the share can only read low."""
    return 2 * (fixed_params(cfg) * tokens
                + expert_params(cfg) * expert_tokens)


def moe_experts_min(cfg, touched_experts, expert_tokens):
    """``(bytes, flops)`` of the routed experts of ONE step over all routed
    layers: the weights of the ``touched_experts`` (layer, expert) cells
    read once, 2 x an expert's parameters a (token, expert) pair."""
    return (touched_experts * expert_params(cfg) * BYTES,
            2 * expert_params(cfg) * expert_tokens)


def index_min(cfg, live_tokens):
    """``(bytes, flops)`` of the indexer of ONE step over the full layers:
    every live token's index key read once and its ``index_n_heads`` dot
    products with its slot's query (``live_tokens``: the cached tokens in
    front of the step's queries, summed over the slots)."""
    full, _, _ = _layers(cfg)
    d, j = cfg["index_head_dim"], cfg["index_n_heads"]
    return (full * live_tokens * d * BYTES, full * 2 * live_tokens * j * d)


def latent_attn_min(cfg, selected):
    """``(bytes, flops)`` of the sparse latent attention of ONE step over
    the full layers: the ``selected`` (query, key) pairs' latent rows read
    once; scores and context in the absorbed form."""
    full, _, _ = _layers(cfg)
    r, dr = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    hh = cfg["num_attention_heads"]
    return (full * selected * (r + dr) * BYTES,
            full * 2 * selected * hh * ((r + dr) + r))


def window_attn_min(cfg, pairs):
    """Likewise for the sliding layers over ``pairs`` (query, key in its
    window) pairs."""
    _, slide, _ = _layers(cfg)
    r, dr = cfg["swa_kv_lora_rank"], cfg["swa_qk_rope_head_dim"]
    hh = cfg["swa_num_attention_heads"]
    return (slide * pairs * (r + dr) * BYTES,
            slide * 2 * pairs * hh * ((r + dr) + r))


def decode_step_min_bytes(cfg, touched_experts, live_tokens, selected,
                          window_pairs):
    """Least HBM traffic of ONE decode step: every fixed weight once, the
    touched experts once, and what the three attentions read."""
    return (fixed_params(cfg) * BYTES
            + moe_experts_min(cfg, touched_experts, 0)[0]
            + index_min(cfg, live_tokens)[0]
            + latent_attn_min(cfg, selected)[0]
            + window_attn_min(cfg, window_pairs)[0])


def floor_seconds(bytes_flops, peaks):
    """The longer of reading the bytes and doing the operations at the
    chip's peaks."""
    b, f = bytes_flops
    return max(b / peaks["hbm_bytes_per_s"], f / peaks["bf16_flops_per_s"])
