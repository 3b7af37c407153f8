"""Seeded Trinity-Large-Preview weights, made on the device one leaf a jitted
call.

``shapes`` is ``{parameter name: (shape, dtype)}`` as the program declares it
(``mxnet_tpu.models.trinity.parameter_shapes``: run ``r`` of like layers
stacked along a leading axis) and as the reference reads it; matrices are
stored ``(in, out)``.  A matrix is N(0, (gain / sqrt(fan in))^2): every
product sits behind a norm, so a unit gain keeps a unit-variance input at
unit variance.  The embedding is N(0, 1 / hidden): the model multiplies it by
``sqrt(hidden)`` (``mup_enabled``), so the stream starts at unit variance and
every sub-block, which joins it through a norm of its own, weighs in beside
it.  Norm gains are 1 + N(0, 0.02), so that a leaf left out of the forward
shows in the comparison; the q and k norms' gains are that times the
configuration's ``init.qk_gain`` (its ``init_why`` says what it sets), the
routed experts' output matrix takes ``init.expert_out_gain``, the router's
selection bias is N(0, ``init.router_bias_std``).  With ``init.router_pairs``
the router's experts come in opposed pairs: expert ``2k + 1``'s column and
bias are expert ``2k``'s with the sign turned, so what a seed's draw adds to
one's popularity it takes from the other's, and a rank that holds whole pairs
gets the same share of the choices whatever the seed.  The same seed gives
the same bits.
"""
import functools

import jax
import jax.numpy as jnp

from chipbench.weights import seed_key


@functools.partial(jax.jit, static_argnames=("shape", "dtype", "kind"))
def _leaf(key, std, shape, dtype, kind):
    x = jax.random.normal(key, shape, jnp.float32)
    x = std * (1.0 + 0.02 * x) if kind == "gain" else std * x
    if kind == "pairs":     # experts along the last axis
        x = x.at[..., 1::2].set(-x[..., 0::2])
    return x.astype(dtype)


def leaves(shapes, seed, init=None):
    """``(name, array)`` for every entry of ``shapes``, one at a time: a
    caller that hands each on as it comes never holds the model twice."""
    init, key = init or {}, seed_key(seed)
    for i, (name, (shape, dtype)) in enumerate(sorted(shapes.items())):
        kind, std = "row", 1.0 / shape[-2] ** 0.5 if len(shape) > 1 else 1.0
        if name.endswith(("qnorm_gamma", "knorm_gamma")):
            kind, std = "gain", float(init.get("qk_gain", 1.0))
        elif name.endswith("_gamma"):
            kind, std = "gain", 1.0
        elif name.endswith("router_bias"):
            std = float(init.get("router_bias_std", 0.01))
        elif name == "wte_weight":
            std = 1.0 / shape[-1] ** 0.5
        elif name.endswith("edown_weight"):
            std *= float(init.get("expert_out_gain", 1.0))
        if init.get("router_pairs") and name.endswith(
                ("router_weight", "router_bias")):
            kind = "pairs"
        yield name, _leaf(jax.random.fold_in(key, i), std, tuple(shape),
                          jnp.dtype(dtype).name, kind)


def make(shapes, seed, init=None):
    """``{name: array}`` for every entry of ``shapes``."""
    return dict(leaves(shapes, seed, init))
