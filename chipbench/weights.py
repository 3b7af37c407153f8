"""Seeded GPT-2 weights, made on the device in one jitted call.

The benchmark owns the weights: the program gets them through
``Parameter.set_data`` and the plain reference gets the same values from a
second call with the same seed, so neither takes anything the other made.
Per-layer leaves come stacked on a leading layer axis.  Matrices are in the
dtype the configuration serves or trains in; LayerNorm rows are float32, as
the program keeps them.

Initialisation follows GPT-2 by default: N(0, 0.02) matrices and embeddings,
the two residual output projections scaled by 1/sqrt(2 x layers).  Biases and
the LayerNorm rows are perturbed off their 0 / 1 defaults (N(0, 0.02)) so
that a leaf left out of the forward or the update shows in the comparison.

A configuration may state other widths of the normal under ``init``
(``layer_std`` for the four layer matrices, ``qkv_std`` for the fused qkv
alone).  With GPT-2's own 0.02 a greedy stream of a random-weight model is
a few tokens repeated at a wide margin, so no served token tests the cache
or the precision; somewhat wider layers give streams that depend on their
context, with the top two logits as close as a trained model's; much wider
ones amplify bfloat16 rounding until nothing agrees with float32 (PERF.md
section 6, PR 24).
"""
import functools

import jax
import jax.numpy as jnp

LAYER_KINDS = ("ln1_g", "ln1_b", "qkv_w", "qkv_b", "proj_w", "proj_b",
               "ln2_g", "ln2_b", "fc1_w", "fc1_b", "fc2_w", "fc2_b")
TOP_KINDS = ("wte", "wpe", "lnf_g", "lnf_b")


def seed_key(seed):
    """A PRNG key from any non-negative seed, past 2**31 too."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31)),
                              seed // (2 ** 31))


def _shapes(g):
    nl, u, f = g["num_layers"], g["units"], g["hidden_size"]
    return {
        "wte": (g["vocab_size"], u), "wpe": (g["max_length"], u),
        "lnf_g": (u,), "lnf_b": (u,),
        "ln1_g": (nl, u), "ln1_b": (nl, u), "ln2_g": (nl, u),
        "ln2_b": (nl, u),
        "qkv_w": (nl, 3 * u, u), "qkv_b": (nl, 3 * u),
        "proj_w": (nl, u, u), "proj_b": (nl, u),
        "fc1_w": (nl, f, u), "fc1_b": (nl, f),
        "fc2_w": (nl, u, f), "fc2_b": (nl, u),
    }


@functools.partial(jax.jit, static_argnames=("geom", "dtype", "layer_std",
                                             "qkv_std"))
def _make(key, geom, dtype, layer_std, qkv_std):
    g = dict(geom)
    resid = 1.0 / (2.0 * g["num_layers"]) ** 0.5
    std = {"qkv_w": qkv_std, "fc1_w": layer_std,
           "proj_w": layer_std * resid, "fc2_w": layer_std * resid}
    out = {}
    for i, (name, shape) in enumerate(sorted(_shapes(g).items())):
        x = jax.random.normal(jax.random.fold_in(key, i), shape,
                              jnp.float32)
        if name.endswith("_g"):
            x = 1.0 + 0.02 * x
        else:
            x = std.get(name, 0.02) * x
        norm_row = name.startswith("ln")
        out[name] = x if norm_row else x.astype(dtype)
    return out


def make(geom, seed, dtype, init=None):
    """``{kind: array}`` for a GPT-2 of geometry ``geom`` (see
    ``shapes.py``) from ``seed``; the same arguments give the same bits.
    ``init`` is the configuration file's ``init`` group, if it has one."""
    keys = ("num_layers", "units", "num_heads", "hidden_size", "vocab_size",
            "max_length")
    init = init or {}
    layer_std = float(init.get("layer_std", 0.02))
    return _make(seed_key(seed), tuple((k, int(geom[k])) for k in keys),
                 jnp.dtype(dtype).name, layer_std,
                 float(init.get("qkv_std", layer_std)))
