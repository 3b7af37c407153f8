"""Operations and bytes of the granite-4.0-h-micro configuration from its
shapes alone: the yardstick behind ``serve_mfu_pct.granite``,
``step_hbm_roofline_pct.granite`` and ``ssm_state_roofline_pct``.  Nothing
here knows of pages, slots' layout, lane tiles or of what implements the
state's update — only what the algorithm needs: every weight a step uses
read once, every live slot's state read once and written once, its tail
likewise, every cached K and V row a query attends to read once, two
operations a multiply-add.  ``cfg`` is ``granite.reference_config``'s dict
(the published keys).
"""

BYTES = 2           # bfloat16 weights, tails and K / V rows
STATE_BYTES = 4     # the recurrent state is float32 (the file's `assumed`)


def _counts(cfg):
    types = cfg["layer_types"][:cfg["num_hidden_layers"]]
    mamba = sum(1 for t in types if t == "mamba")
    return mamba, len(types) - mamba


def _inner(cfg):
    return cfg["mamba_n_heads"] * cfg["mamba_d_head"]


def conv_width(cfg):
    return _inner(cfg) + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]


def mlp_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["shared_intermediate_size"]


def mamba_params(cfg):
    """One state-space layer with its feed-forward."""
    H, inner, W = cfg["hidden_size"], _inner(cfg), conv_width(cfg)
    nh = cfg["mamba_n_heads"]
    return (H * (inner + W + nh) + (cfg["mamba_d_conv"] + 1) * W + 3 * nh
            + inner + inner * H + 2 * H + mlp_params(cfg))


def attention_params(cfg):
    """One attention layer with its feed-forward."""
    H = cfg["hidden_size"]
    kvw = cfg["num_key_value_heads"] * (H // cfg["num_attention_heads"])
    return 2 * H * H + 2 * H * kvw + 2 * H + mlp_params(cfg)


def total_params(cfg):
    """Everything resident; the embedding is also the head (tied)."""
    mamba, attn = _counts(cfg)
    return (mamba * mamba_params(cfg) + attn * attention_params(cfg)
            + cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"])


def served_flops(cfg, tokens):
    """2 x the parameters a token multiplies by (all of them: the tied
    embedding is the head's matrix), for each of ``tokens``; the recurrence
    and attention over the cache are left out, so the share reads low."""
    return 2 * total_params(cfg) * tokens


def state_bytes_per_slot(cfg):
    """One slot's recurrent state over every state-space layer."""
    mamba, _ = _counts(cfg)
    return mamba * _inner(cfg) * cfg["mamba_d_state"] * STATE_BYTES


def tail_bytes_per_slot(cfg):
    mamba, _ = _counts(cfg)
    return mamba * (cfg["mamba_d_conv"] - 1) * conv_width(cfg) * BYTES


def kv_bytes_per_token(cfg):
    _, attn = _counts(cfg)
    kvw = cfg["num_key_value_heads"] \
        * (cfg["hidden_size"] // cfg["num_attention_heads"])
    return attn * 2 * kvw * BYTES


def ssm_state_min(cfg, live_slots):
    """``(bytes, flops)`` of the state's update and readout of ONE step over
    every state-space layer: each live slot's state read once and written
    once; a decay, a rank-one update and a readout are 6 operations an
    element."""
    b = live_slots * state_bytes_per_slot(cfg)
    return 2 * b, 6 * b // STATE_BYTES


def decode_step_min_bytes(cfg, live_slots, live_tokens):
    """Least HBM traffic of ONE decode step: every weight once, each live
    slot's state and tail read and written once, the K and V rows of the
    ``live_tokens`` cached in front of the queries once."""
    return (total_params(cfg) * BYTES
            + 2 * live_slots * (state_bytes_per_slot(cfg)
                                + tail_bytes_per_slot(cfg))
            + live_tokens * kv_bytes_per_token(cfg))


def floor_seconds(bytes_flops, peaks):
    """The longer of reading the bytes and doing the operations at the
    chip's peaks."""
    b, f = bytes_flops
    return max(b / peaks["hbm_bytes_per_s"], f / peaks["bf16_flops_per_s"])
