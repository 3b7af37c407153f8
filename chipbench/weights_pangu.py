"""Seeded openPangu-Ultra-MoE-718B weights, made on the device one leaf a
jitted call.

``shapes`` is ``{parameter name: (shape, dtype)}`` as the program declares it
(``mxnet_tpu.models.pangu_moe.parameter_shapes``) and as the reference reads
it.  The leaves are ``weights_dots3``'s, by the same rules (a matrix N(0,
(gain / sqrt(fan in))^2), norm gains 1 + N(0, 0.02), the four norms of a
sandwich layer alike, embeddings N(0, 1), the configuration's ``init``
gains on the three projections that make attention scores and on the routed
experts' output, the router's selection bias N(0, ``router_bias_std``));
with ``init.router_pairs`` the router's experts come in opposed pairs, as
``weights_trinity`` has them: expert ``2k + 1``'s column and bias are expert
``2k``'s with the sign turned, so what a seed's draw adds to one's
popularity it takes from the other's, and a chip that holds whole pairs
gets the same share of the choices whatever the seed.  The same seed gives
the same bits.
"""
import jax

from chipbench import weights_dots3


@jax.jit
def _paired(x):
    """Experts along the last axis: every odd one the even one before it,
    with the sign turned."""
    return x.at[..., 1::2].set(-x[..., 0::2])


def leaves(shapes, seed, init=None):
    """``(name, array)`` for every entry of ``shapes``, one at a time: a
    caller that hands each on as it comes never holds the model twice."""
    pairs = bool((init or {}).get("router_pairs"))
    for name, leaf in weights_dots3.leaves(shapes, seed, init):
        if pairs and name.endswith(("router_weight", "router_bias")):
            leaf = _paired(leaf)
        yield name, leaf


def make(shapes, seed, init=None):
    """``{name: array}`` for every entry of ``shapes``."""
    return dict(leaves(shapes, seed, init))
