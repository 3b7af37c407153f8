"""Seeded granite-4.0-h-micro weights, made on the device one leaf a jitted
call.

``shapes`` is ``{parameter name: (shape, dtype)}`` as the program declares it
(``mxnet_tpu.models.granite_hybrid.parameter_shapes``: run ``r`` of like
layers stacked along a leading axis) and as the reference reads it; matrices
are stored ``(in, out)``.  A matrix is N(0, (gain / sqrt(fan in))^2): every
product sits behind a norm, so a unit gain keeps a unit-variance input at
unit variance; the convolution's four taps likewise (fan in 4), its bias
N(0, 0.02).  The embedding, which is also the head, is a matrix of fan in
``hidden_size`` with the configuration's ``init.embed_gain``; norm gains are 1
+ N(0, 0.02), so that a leaf left out of the forward shows in the comparison,
the final norm's times ``init.final_norm_gain`` (the configuration's
``init_why`` says what the two set).

The three per-head rows of the recurrence follow Mamba-2's own
initialisation: ``A_log = log(U[1, 16])``, ``dt_bias = softplus^-1(exp(U[log
0.001, log 0.1]))``, ``D = 1`` — a head's decay a token is then between
``exp(-1.6)`` and ``exp(-0.001)``, so a state remembers over one to a
thousand tokens.  The same seed gives the same bits.
"""
import functools
import math

import jax
import jax.numpy as jnp

from chipbench.weights import seed_key


@functools.partial(jax.jit, static_argnames=("shape", "dtype", "kind"))
def _leaf(key, std, shape, dtype, kind):
    if kind == "a_log":
        x = jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    elif kind == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        math.log(0.001), math.log(0.1)))
        x = dt + jnp.log(-jnp.expm1(-dt))       # softplus^-1(dt)
    elif kind == "one":
        x = jnp.ones(shape, jnp.float32)
    else:
        x = jax.random.normal(key, shape, jnp.float32)
        x = std * (1.0 + 0.02 * x) if kind == "gain" else std * x
    return x.astype(dtype)


def leaves(shapes, seed, init=None):
    """``(name, array)`` for every entry of ``shapes``, one at a time: a
    caller that hands each on as it comes never holds the model twice."""
    init, key = init or {}, seed_key(seed)
    for i, (name, (shape, dtype)) in enumerate(sorted(shapes.items())):
        kind, std = "row", 0.02
        if name.endswith("_gamma"):
            kind, std = "gain", 1.0
            if name == "normf_gamma":
                std = float(init.get("final_norm_gain", 1.0))
        elif name.endswith("a_log"):
            kind = "a_log"
        elif name.endswith("dt_bias"):
            kind = "dt_bias"
        elif name.endswith("d_skip"):
            kind = "one"
        elif name == "wte_weight":
            std = float(init.get("embed_gain", 1.0)) / shape[-1] ** 0.5
        elif name.endswith("_weight"):
            std = 1.0 / shape[-2] ** 0.5
        yield name, _leaf(jax.random.fold_in(key, i), std, tuple(shape),
                          jnp.dtype(dtype).name, kind)


def make(shapes, seed, init=None):
    """``{name: array}`` for every entry of ``shapes``."""
    return dict(leaves(shapes, seed, init))
