"""Operations and bytes of the Trinity-Large-Preview configuration from its
shapes alone: the yardstick behind ``serve_mfu_pct.trinity``,
``step_hbm_roofline_pct.trinity`` and the ``*_roofline_pct.trinity`` of the
decode step's parts.  Nothing here knows of pages, rings, lane tiles or of
how a page walk or a grouped product is implemented — only what the
algorithm needs: every weight a step uses read once, every cached K and V
row a query attends to read once, two operations a multiply-add.  ``cfg`` is
``trinity.reference_config``'s dict (published keys, ``held_experts``, the
router's full width).
"""

BYTES = 2       # bfloat16 weights and cache rows


def _layers(cfg):
    """``(full layers, sliding layers, routed layers)``."""
    types = cfg["layer_types"][:cfg["num_hidden_layers"]]
    full = sum(1 for t in types if t == "full_attention")
    return full, len(types) - full, \
        cfg["num_hidden_layers"] - cfg["num_dense_layers"]


def attention_params(cfg):
    """One layer's q, k, v, gate and output projections."""
    H, D = cfg["hidden_size"], cfg["head_dim"]
    qw, kvw = cfg["num_attention_heads"] * D, cfg["num_key_value_heads"] * D
    return H * qw + 2 * H * kvw + H * qw + qw * H


def expert_params(cfg):
    """One expert (and the shared one): three H x width matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def fixed_params(cfg):
    """Parameters every token's step multiplies by, whatever the routing:
    the attention of every layer, the dense feed-forward, the shared
    experts, the routers and the head (the embedding is a row read, not a
    product)."""
    full, slide, routed = _layers(cfg)
    H = cfg["hidden_size"]
    return ((full + slide) * attention_params(cfg)
            + cfg["num_dense_layers"] * 3 * H * cfg["intermediate_size"]
            + routed * (cfg["num_shared_experts"] * expert_params(cfg)
                        + H * cfg["num_experts"])
            + H * cfg["vocab_size"])


def total_params(cfg):
    """Everything resident: fixed + the held experts + the embedding."""
    _, _, routed = _layers(cfg)
    return (fixed_params(cfg) + cfg["hidden_size"] * cfg["vocab_size"]
            + routed * cfg["held_experts"][1] * expert_params(cfg))


def served_flops(cfg, tokens, expert_tokens):
    """2 x the parameters active for a token HERE: the fixed ones for each
    of ``tokens`` and one expert for each of ``expert_tokens`` (token, held
    expert) pairs the routed layers ran; attention over the cache is left
    out, so the share can only read low."""
    return 2 * (fixed_params(cfg) * tokens
                + expert_params(cfg) * expert_tokens)


def moe_experts_min(cfg, touched_experts, expert_tokens):
    """``(bytes, flops)`` of the routed experts of ONE step over all routed
    layers: the weights of the ``touched_experts`` (layer, expert) cells
    read once, 2 x an expert's parameters a (token, expert) pair."""
    return (touched_experts * expert_params(cfg) * BYTES,
            2 * expert_params(cfg) * expert_tokens)


def kv_row_bytes(cfg):
    """One token's K row and V row of one layer."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * BYTES


def _attn_min(cfg, layers, pairs):
    """``(bytes, flops)`` of ``layers`` layers' single-query attention over
    ``pairs`` (query, cached key) pairs: each pair's K and V rows read
    once, every query head's score and context against them."""
    hq, D = cfg["num_attention_heads"], cfg["head_dim"]
    return (layers * pairs * kv_row_bytes(cfg),
            layers * 2 * pairs * hq * 2 * D)


def full_attn_min(cfg, live_tokens):
    """The full layers' walk of ONE step: every cached token in front of the
    step's queries (``live_tokens``, summed over the slots)."""
    return _attn_min(cfg, _layers(cfg)[0], live_tokens)


def window_attn_min(cfg, window_pairs):
    """The sliding layers' walk of ONE step: for every slot the cached
    tokens inside its window (``window_pairs``, summed over the slots)."""
    return _attn_min(cfg, _layers(cfg)[1], window_pairs)


def decode_step_min_bytes(cfg, touched_experts, live_tokens, window_pairs):
    """Least HBM traffic of ONE decode step: every fixed weight once, the
    touched experts once, and the rows the two walks must read."""
    return (fixed_params(cfg) * BYTES
            + moe_experts_min(cfg, touched_experts, 0)[0]
            + full_attn_min(cfg, live_tokens)[0]
            + window_attn_min(cfg, window_pairs)[0])


def floor_seconds(bytes_flops, peaks):
    """The longer of reading the bytes and doing the operations at the
    chip's peaks."""
    b, f = bytes_flops
    return max(b / peaks["hbm_bytes_per_s"], f / peaks["bf16_flops_per_s"])
