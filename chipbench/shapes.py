"""Operations and bytes of GPT-2 from its shapes alone.

The yardstick behind ``serve_mfu_pct``, ``step_hbm_roofline_pct`` and
``train_mfu_pct``: nothing here knows of pages, buckets, the T-wide view of
the paged pool or of recomputation — only what the algorithm needs.
A GPT-2 geometry is a dict with ``num_layers``, ``units``, ``num_heads``,
``hidden_size``, ``vocab_size`` and ``max_length``.
"""
import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def gpt2_params(g):
    """Parameter count of a GPT-2 with a tied head (wte counted once)."""
    u, f = g["units"], g["hidden_size"]
    per_layer = (3 * u * u + 3 * u      # fused qkv
                 + u * u + u            # attention output projection
                 + u * f + f            # fc1
                 + f * u + u            # fc2
                 + 4 * u)               # two LayerNorms
    return (g["vocab_size"] * u + g["max_length"] * u
            + g["num_layers"] * per_layer + 2 * u)


def kv_bytes_per_token(g, itemsize):
    """Bytes of K and V one cached token holds over all layers."""
    return 2 * g["num_layers"] * g["units"] * itemsize


def decode_step_min_bytes(g, live_tokens, itemsize):
    """Least HBM traffic of ONE decode step: every weight read once and the
    K and V of the tokens live in the step read once."""
    return (gpt2_params(g) * itemsize
            + live_tokens * kv_bytes_per_token(g, itemsize))


def served_flops(g, tokens):
    """2 x parameters a token, prompt and output alike (the ``serve_mfu_pct``
    numerator; attention's part, small at these lengths, is left out so
    the share can only read low)."""
    return 2 * gpt2_params(g) * tokens


def train_flops_per_token(g, seq):
    """Forward + backward of one token at sequence length ``seq``:
    6 x parameters for the matrix products, and for attention QK^T and PV
    4 x seq x units a layer forward, times 3 with backward, HALVED because a
    causal model needs only the lower triangle: 6 x layers x units x seq.
    Recomputation is not counted."""
    return 6 * gpt2_params(g) + 6 * g["num_layers"] * g["units"] * seq


def peaks_for(device_kind, path=None):
    """The peaks row of ``device_kind``; an unknown device is an error."""
    with open(path or os.path.join(_HERE, "peaks.json")) as fh:
        table = json.load(fh)
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in peaks.json "
            f"(known: {sorted(table)}); add a row with its source")
    return table[device_kind]
