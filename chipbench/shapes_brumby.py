"""Operations and bytes of the Brumby-14B-Base configuration from its shapes
alone: the yardstick behind ``step_hbm_roofline_pct.brumby``,
``serve_mfu_pct.brumby``, ``retention_state_roofline_pct`` and
``retention_chunk_mxu_roofline_pct``.  Nothing here knows of slots' layout,
lane tiles, the stored expansion's repeated rows or of what implements the
state's update — only what the algorithm needs: every weight a step uses
read once, every live slot's state over the ``d (d + 1) / 2`` distinct rows
of the degree-2 expansion read once and written once, two operations a
multiply-add.  ``cfg`` is ``brumby.reference_config``'s dict (the published
keys); the chunked form's count reads the chunk the program ran,
``retention_chunk``, which the entry adds to the run's ``geometry``.
"""

BYTES = 2           # bfloat16 weights
STATE_BYTES = 4     # the retention state is float32 (the file's `assumed`)


def expansion_rows(cfg):
    """``D``: the distinct rows of the degree-2 symmetric power of a head."""
    d = cfg["head_dim"]
    return d * (d + 1) // 2


def layer_params(cfg):
    """One retention layer with its feed-forward and norms."""
    H, F, d = cfg["hidden_size"], cfg["intermediate_size"], cfg["head_dim"]
    q = cfg["num_attention_heads"] * d
    kv = cfg["num_key_value_heads"] * d
    return (2 * H * q + 2 * H * kv + H * cfg["num_key_value_heads"]
            + 3 * H * F + 2 * H + 2 * d)


def head_params(cfg):
    return cfg["hidden_size"] * cfg["vocab_size"]


def total_params(cfg):
    """Everything resident: the layers, the embedding and the untied head."""
    return cfg["num_hidden_layers"] * layer_params(cfg) \
        + 2 * head_params(cfg) + cfg["hidden_size"]


def step_params(cfg):
    """What a step multiplies by: every layer and the head (the embedding
    gives one row a token)."""
    return cfg["num_hidden_layers"] * layer_params(cfg) + head_params(cfg) \
        + cfg["hidden_size"]


def state_bytes_per_slot_layer(cfg):
    """One slot's ``S`` and ``z`` of one layer: ``G x D x dv`` and ``G x D``
    float32 (34.08 MB at the published widths)."""
    G, D = cfg["num_key_value_heads"], expansion_rows(cfg)
    return G * D * (cfg["head_dim"] + 1) * STATE_BYTES


def state_bytes_per_slot(cfg):
    return cfg["num_hidden_layers"] * state_bytes_per_slot_layer(cfg)


def retention_step_flops(cfg):
    """A token's step of the state over every layer: a decay and a rank-one
    update of ``S`` and ``z`` (3 operations an element), and each query
    head's readout of ``S`` and ``z`` (2 an element)."""
    G, D, dv = cfg["num_key_value_heads"], expansion_rows(cfg), \
        cfg["head_dim"]
    hq = cfg["num_attention_heads"]
    return cfg["num_hidden_layers"] * (3 * G * D * (dv + 1)
                                       + 2 * hq * D * (dv + 1))


def retention_state_min(cfg, live_slots):
    """``(bytes, flops)`` of the state's update of ONE step over every layer:
    each live slot's state read once and written once."""
    return 2 * live_slots * state_bytes_per_slot(cfg), \
        live_slots * retention_step_flops(cfg)


def chunk_pairs(tokens, dispatches):
    """Causal (query, key) pairs within ``dispatches`` admission dispatches
    of ``tokens`` prompt tokens in all: ``m (m + 1) / 2`` a dispatch of
    ``m`` tokens, at least ``dispatches`` times that of their mean."""
    m = tokens / dispatches
    return dispatches * m * (m + 1) / 2


def retention_chunk_flops(cfg, tokens, dispatches, carried):
    """Prefill's operations over every layer for ``tokens`` prompt tokens in
    ``dispatches`` dispatches, ``carried`` of them in chunks that continue a
    prompt: the causal pairs' scores and weighted values within each
    dispatch (``4 d`` a pair and query head), each KV head's write of its
    tokens into the state (``2 dv D`` a token) and each query head's read of
    the carried state for the ``carried`` tokens (``2 dv D`` a token)."""
    d, D = cfg["head_dim"], expansion_rows(cfg)
    hq, G = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    pairs = chunk_pairs(tokens, dispatches)
    return cfg["num_hidden_layers"] * (pairs * hq * 4 * d
                                       + tokens * G * D * 2 * d
                                       + carried * hq * D * 2 * d)


def served_flops(cfg, step_tokens, prompt_tokens, dispatches, carried):
    """2 x the parameters a token multiplies by for each token the window
    computed, the state's step for each output token and prefill's
    operations (``retention_chunk_flops``) for the prompt tokens."""
    return 2 * step_params(cfg) * (step_tokens + prompt_tokens) \
        + retention_step_flops(cfg) * step_tokens \
        + (retention_chunk_flops(cfg, prompt_tokens, dispatches, carried)
           if dispatches else 0.0)


def decode_step_min_bytes(cfg, live_slots):
    """Least HBM traffic of ONE decode step: every weight of the layers and
    the head once, each live slot's state read and written once."""
    return step_params(cfg) * BYTES \
        + 2 * live_slots * state_bytes_per_slot(cfg)


def floor_seconds(bytes_flops, peaks):
    """The longer of reading the bytes and doing the operations at the
    chip's peaks."""
    b, f = bytes_flops
    return max(b / peaks["hbm_bytes_per_s"], f / peaks["bf16_flops_per_s"])
