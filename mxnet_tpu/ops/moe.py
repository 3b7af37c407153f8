"""A routed feed-forward layer that is told which experts it holds.

``route`` scores every token against ALL ``E`` experts of the layer (the
router keeps its published width whatever is held here) and picks the
``top_k`` of largest ``sigmoid(score) + bias``; the bias is used for the
choice only, the weights are the chosen sigmoids normalised to sum 1
(``noaux_tc`` with ``norm_topk_prob``).  ``routed_experts`` computes, for
the tokens sent to the experts ``[lo, lo + n)`` this chip holds, those
experts' part of ``sum_e w_e E_e(x)`` with two grouped products (rows
sorted by expert, one group an expert) and drops no token: the row count is
the static worst case ``N * top_k``, rows routed elsewhere sort behind the
last group and are masked.  What the absent experts would add is left out;
no code stands in for other chips.

Which path runs the two products (``_products``):

- a TPU lowering: ONE Pallas kernel, ``mx_moe_gmm``
  (``ops/grouped_matmul.py``), whose grid visits only the row tiles that
  hold live rows and the groups that have rows there, reading each touched
  expert's weights once a visit — wherever ``grouped_matmul.plan`` finds a
  row tile for the shapes (``M`` a multiple of a whole sublane tile);
- every other platform, and shapes without a row tile: two
  ``jax.lax.ragged_dot`` calls, the XLA form, whose work follows the
  COMPILED rows (PERF.md, PR 39: 72-79% of the touched experts' bytes' time
  at 96 rows, 38% at 512 and 1,024, where the kernel reads 80-84%);
- ``MXNET_FLASH_INTERPRET=1``: the kernel interpreted, wherever it is (the
  tests' CPU numerics).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import grouped_matmul
from .attention import on_tpu

__all__ = ["route", "routed_experts", "swiglu"]


def swiglu(x, w_gu, w_down):
    """``(silu(x Wg) * (x Wu)) Wd`` with gate and up side by side in
    ``w_gu`` ``(H, 2I)``."""
    gu = jnp.dot(x, w_gu, preferred_element_type=jnp.float32)
    g, u = jnp.split(gu, 2, axis=-1)
    a = (jax.nn.silu(g) * u).astype(x.dtype)
    return jnp.dot(a, w_down,
                   preferred_element_type=jnp.float32).astype(x.dtype)


def route(x, w_router, bias, top_k, scale=1.0):
    """``(idx (N, top_k) int32, weights (N, top_k) float32)`` of ``x``
    ``(N, H)`` over all ``E = w_router.shape[1]`` experts."""
    s = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32),
                               w_router.astype(jnp.float32)))
    _, idx = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    w = chosen / jnp.sum(chosen, axis=-1, keepdims=True) * scale
    return idx.astype(jnp.int32), w


def routed_experts(x, idx, weights, w_gu, w_down, lo, layer=None):
    """The held experts' part of the routed sum for ``x`` ``(N, H)``:
    ``w_gu`` ``(n, H, 2I)`` and ``w_down`` ``(n, I, H)`` are experts ``lo
    .. lo + n - 1`` of the layer.  Returns ``(y (N, H), load (n,) int32)``,
    ``load`` the tokens each held expert got.

    With ``layer`` (a traced index) the two arrays hold a RUN of ``L``
    layers' experts stacked, ``(L, n, ...)``, as a scan over the run closes
    over them: the grouped products then see all ``L * n`` groups, every
    other layer's empty.  No layer's experts are sliced out of the run: the
    grouped product is a kernel of its own on the chip, takes no fused
    slice, and a slice of its operands was a copy of 1.8 GB a layer a step
    (tools/rehearse_serve.py, PERF.md PR 35)."""
    N, K = idx.shape
    n = w_gu.shape[-3]
    local = idx - lo
    mine = (local >= 0) & (local < n)
    group = jnp.where(mine, local, n).reshape(N * K)
    order = jnp.argsort(group, stable=True)
    tok = order // K
    load = jnp.zeros((n + 1,), jnp.int32).at[group].add(1)[:n]
    xs = x[tok]
    base = 0
    if layer is not None:
        base = layer * n
        groups = w_gu.shape[0] * n
        w_gu = w_gu.reshape((groups,) + w_gu.shape[2:])
        w_down = w_down.reshape((groups,) + w_down.shape[2:])
    ys = _products(xs, w_gu, w_down, load, base)
    w = jnp.where(mine, weights, 0.0).reshape(N * K)[order]
    # rows behind the last group belong to no expert here: whatever the
    # grouped product left there never reaches the sum
    live = jnp.arange(N * K) < jnp.sum(load)
    ys = jnp.where(live[:, None], ys * w[:, None], 0.0)
    y = jnp.zeros((N, x.shape[1]), jnp.float32).at[tok].add(ys)
    return y.astype(x.dtype), load


def _ragged(xs, w_gu, w_down, load, base):
    """The XLA form: two ``lax.ragged_dot`` calls over every group of the
    (stacked) weights, all but ``load``'s at ``base`` empty."""
    # the framework's default matmul precision is "highest" (base.py): a
    # no-op for a bfloat16 XLA dot, but the chip's grouped-product kernel
    # refuses bfloat16 operands under it, so they ask for what they are
    prec = None if xs.dtype == jnp.float32 else jax.lax.Precision.DEFAULT
    sizes = load
    if w_gu.shape[0] != load.shape[0]:
        sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((w_gu.shape[0],), jnp.int32), load, (base,))
    gu = jax.lax.ragged_dot(xs, w_gu, sizes, precision=prec,
                            preferred_element_type=jnp.float32)
    g, u = jnp.split(gu, 2, axis=-1)
    a = (jax.nn.silu(g) * u).astype(xs.dtype)
    return jax.lax.ragged_dot(a, w_down, sizes, precision=prec,
                              preferred_element_type=jnp.float32)


def _products(xs, w_gu, w_down, load, base):
    """``(M, H)`` float32: the live rows of ``xs`` through their group's
    expert (rows past ``sum(load)`` are masked by the caller).  The kernel
    on a TPU lowering where ``grouped_matmul.plan`` gives it a row tile,
    the XLA form everywhere else (module docstring)."""
    M, H = xs.shape
    if grouped_matmul.plan(M, H, w_down.shape[1], xs.dtype) is None:
        return _ragged(xs, w_gu, w_down, load, base)
    base = jnp.asarray(base, jnp.int32)
    return on_tpu(grouped_matmul.grouped_swiglu, _ragged, xs, w_gu, w_down,
                  load, base)
