"""Seeded dots3_note weights, made on the device one leaf a jitted call.

``shapes`` is ``{parameter name: (shape, dtype)}`` as the program declares
it (``mxnet_tpu.models.dots3.parameter_shapes``) and as the reference reads
it; matrices are stored ``(in, out)``.  A matrix is N(0, (gain /
sqrt(fan in))^2): every product sits behind a norm, so a unit gain keeps a
unit-variance input at unit variance.  The configuration's ``init`` group
sets the gains that shape the streams (its ``init_why`` says how): the
three projections that make attention scores (``qb``, ``kva``, ``kvb``) and
the routed experts' output.  Norm gains are 1 + N(0, 0.02), the index key's
LayerNorm shift N(0, 0.02), the router's selection bias N(0,
``router_bias_std``), so that a leaf left out of the forward shows in the
comparison.  Embeddings are N(0, 1).  The same seed gives the same bits.
"""
import functools

import jax
import jax.numpy as jnp

from chipbench.weights import seed_key


@functools.partial(jax.jit, static_argnames=("shape", "dtype", "kind"))
def _leaf(key, std, shape, dtype, kind):
    x = jax.random.normal(key, shape, jnp.float32)
    if kind == "gain":
        x = 1.0 + 0.02 * x
    else:
        x = std * x
    return x.astype(dtype)


def _gain(name, init):
    for suffix, key in (("qb_weight", "score_gain"),
                        ("kva_weight", "score_gain"),
                        ("kvb_weight", "score_gain"),
                        ("edown_weight", "expert_out_gain")):
        if name.endswith(suffix):
            return float(init.get(key, 1.0))
    return 1.0


def leaves(shapes, seed, init=None):
    """``(name, array)`` for every entry of ``shapes``, one at a time: a
    caller that hands each on as it comes never holds the model twice."""
    init, key = init or {}, seed_key(seed)
    for i, (name, (shape, dtype)) in enumerate(sorted(shapes.items())):
        if name.endswith("_gamma"):
            kind, std = "gain", 0.0
        elif name.endswith("_beta"):
            kind, std = "row", 0.02
        elif name.endswith("router_bias"):
            kind, std = "row", float(init.get("router_bias_std", 0.01))
        elif name == "wte_weight":
            kind, std = "row", 1.0
        else:
            kind, std = "row", _gain(name, init) / shape[-2] ** 0.5
        yield name, _leaf(jax.random.fold_in(key, i), std, tuple(shape),
                          jnp.dtype(dtype).name, kind)


def make(shapes, seed, init=None):
    """``{name: array}`` for every entry of ``shapes``."""
    return dict(leaves(shapes, seed, init))
