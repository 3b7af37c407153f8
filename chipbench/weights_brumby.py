"""Seeded Brumby-14B-Base weights, made on the device one leaf a jitted call.

``shapes`` is ``{parameter name: (shape, dtype)}`` as the program declares it
(``mxnet_tpu.models.brumby.parameter_shapes``: the layers stacked along a
leading axis, ``r0_``) and as the reference reads it; matrices are stored
``(in, out)``.  A matrix is N(0, (1 / sqrt(fan in))^2): every product sits
behind a norm, so a unit-variance input stays at unit variance.  The
embedding is N(0, 1); norm gains are 1 + N(0, 0.02), so that a leaf left out
of the forward shows in the comparison.

The gate's construction (the configuration's ``init`` and ``init_why``): a
random ``W_a`` gives ``log a = logsigmoid(N(0, 1))``, a decay near 0.5, and a
state that forgets in two tokens.  So channel 0 of the residual stream is a
SINK: the embedding sets it to ``init.sink`` for every id, and no matrix
but ``W_a`` reads or writes it (its row of every other matrix that reads the
stream, and its column of every matrix that writes it, are 0).  After a
layer's RMSNorm it reads ``h_0 = sink / sqrt((sink^2 + (H - 1) s^2) / H)``,
``s^2`` the other channels' mean square, ``h_0(1)`` at ``s = 1``.  Row 0 of
``W_a`` is ``init.gate_logits[g] / h_0(1)`` for KV head ``g``, its other rows
N(0, (init.gate_noise / sqrt(H))^2): the decay of head ``g`` is then
``sigmoid(gate_logits[g])``, moved a little by the token and by how far the
stream has grown.  The same seed gives the same bits.
"""
import functools
import math

import jax
import jax.numpy as jnp

from chipbench.weights import seed_key

# matrices that read the residual stream (row 0 zeroed) and that write it
# (column 0 zeroed); the gate reads it through row 0 alone
_READS = ("q_weight", "k_weight", "v_weight", "gu_weight", "head_weight")
_WRITES = ("o_weight", "down_weight")


def sink_norm(sink, hidden):
    """``h_0(1)``: the sink channel after an RMSNorm of gain 1 when every
    other channel has mean square 1."""
    return sink / math.sqrt((sink * sink + hidden - 1) / hidden)


@functools.partial(jax.jit, static_argnames=("shape", "dtype", "kind",
                                             "sink", "noise"))
def _leaf(key, std, shape, dtype, kind, sink=0.0, noise=0.0, logits=None):
    x = jax.random.normal(key, shape, jnp.float32)
    if kind == "gain":
        x = std * (1.0 + 0.02 * x)
    elif kind == "embed":
        x = x.at[..., 0].set(sink)
    elif kind == "gate":
        # (layers, H, G): row 0 sets each head's decay, the rest is noise
        H = shape[-2]
        x = x * (noise / math.sqrt(H))
        x = x.at[..., 0, :].set(logits / sink_norm(sink, H))
    else:
        x = std * x
        if kind == "reads":
            x = x.at[..., 0, :].set(0.0)
        elif kind == "writes":
            x = x.at[..., 0].set(0.0)
    return x.astype(dtype)


def leaves(shapes, seed, init=None):
    """``(name, array)`` for every entry of ``shapes``, one at a time: a
    caller that hands each on as it comes never holds the model twice."""
    init, key = init or {}, seed_key(seed)
    sink = float(init.get("sink", 0.0))
    for i, (name, (shape, dtype)) in enumerate(sorted(shapes.items())):
        kind, std, extra = "row", 1.0, {}
        if name.endswith("_gamma"):
            kind = "gain"
        elif name == "wte_weight":
            kind, extra = "embed", {"sink": sink}
        elif name.endswith("gate_weight"):
            kind = "gate"
            extra = {"sink": sink, "noise": float(init.get("gate_noise",
                                                            1.0)),
                     "logits": jnp.asarray(init["gate_logits"],
                                           jnp.float32)}
        else:
            std = 1.0 / shape[-2] ** 0.5
            base = name[3:] if name.startswith("r0_") else name
            kind = "reads" if base in _READS else \
                "writes" if base in _WRITES else "row"
        yield name, _leaf(jax.random.fold_in(key, i), std, tuple(shape),
                          jnp.dtype(dtype).name, kind, **extra)


def make(shapes, seed, init=None):
    """``{name: array}`` for every entry of ``shapes``."""
    return dict(leaves(shapes, seed, init))
