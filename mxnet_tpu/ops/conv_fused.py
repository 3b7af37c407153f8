"""Fused backward for 1x1 convolutions: dgrad + wgrad in ONE HBM pass.

Reference anchor: the cuDNN autotuned conv backward paths behind
``Convolution`` (SURVEY.md §3.1 "cuDNN autotuned conv paths",
``MXNET_CUDNN_AUTOTUNE_DEFAULT``) — there the framework picks a cuDNN
algorithm per shape; here the TPU analog picks between XLA's conv
backward and this Pallas kernel per shape class.

Why this kernel exists (VERDICT r4 item 1):
ResNet-50's backward convs hold ~49 ms/step with the 1x1 bottleneck
convs HBM-bound (arithmetic intensity ~50 flops/byte vs the v5e ridge of
~240).  XLA lowers conv backward as TWO independent ops —

    dgrad:  dx = dy @ W        (reads dy, W;  writes dx)
    wgrad:  dW = dy^T @ x      (reads dy, x;  writes dW)

— so the large ``dy`` tensor (4x the size of ``x`` for the expand convs)
streams from HBM TWICE.  For HBM-bound shapes that's ~2x the minimum
traffic.  This kernel tiles ``dy`` through VMEM ONCE, computing the
``dx`` tile and accumulating the full ``dW`` in f32 VMEM as it goes:

    traffic:  read dy + read x + write dx   (vs  2*dy + x + dx)

A 1x1 stride-1 conv in NHWC is exactly a (P, Ci) x (Ci, Co) matmul over
the flattened batch*spatial axis P, so the whole backward is expressible
as two MXU contractions per tile with zero layout shuffling — C rides
the TPU lane dimension natively.  (NCHW would put spatial on lanes,
misaligned for every stage except 56x56 — measured in
benchmark/conv_shape_probe.py; the model zoo's ``layout="NHWC"`` mode is
the intended pairing.)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import _interpret, _pallas_backend_ok as _on_tpu

__all__ = ["conv1x1_nhwc", "fused_bwd_supported"]

_VMEM_BUDGET = 12 * 1024 * 1024


def _pick_tile(p: int, ci: int, co: int, itemsize: int = 2) -> int:
    """Largest P-tile that divides ``p`` and fits the VMEM budget:
    dy tile (Tp, Co) + x/dx tiles (Tp, Ci) double-buffered, plus the
    resident W (Co, Ci) and f32 dW accumulator.  ``itemsize`` is the
    operand dtype's byte width — f32 shapes cost twice the bf16 budget,
    so the same geometry may need a smaller tile (or none at all)."""
    fixed = co * ci * (itemsize + 4)
    for tp in (1024, 896, 784, 768, 640, 512, 448, 392, 256, 196, 128,
               112, 64, 56, 32, 16):
        if p % tp:
            continue
        tiled = 2 * (tp * co * itemsize) + 4 * (tp * ci * itemsize)
        if fixed + tiled <= _VMEM_BUDGET:
            return tp
    return 0


def fused_bwd_supported(shape_in, w_shape, stride, dilate, groups,
                        itemsize: int = 2) -> bool:
    """True when the fused Pallas backward serves this conv: NHWC 2-D,
    1x1 kernel, unit stride/dilation, ungrouped, and a tile exists."""
    import os
    # DEFAULT OFF — the r5 measured-negative: XLA's 1x1 backward pair
    # already runs at its two-read HBM roofline per shape (e.g. s1_1x1e
    # 1.21 ms vs 1.26 roof), this kernel's measured stream efficiency
    # (63-75% of ITS roofline) cancels the single-dy-read advantage
    # (1.20 ms — a tie), and in-step it FORCES the BN-backward
    # elementwise producer to materialize instead of fusing into the
    # conv ops (ResNet-50 NHWC: 153.8 ms/step fused vs 103.3 unfused).
    # Kept as an opt-in artifact + numerics-tested reference kernel.
    if os.environ.get("MXNET_FUSED_CONV_BWD", "0") != "1":
        return False
    if not _on_tpu():
        return False
    # GSPMD cannot auto-partition a pallas_call: on a multi-chip
    # mesh the conv stays on XLA's backward (a shard_map-wrapped
    # variant is the escalation path if multi-chip vision training
    # becomes the bottleneck)
    if jax.device_count() > 1 and not _interpret():
        return False
    if len(shape_in) != 4 or groups != 1:
        return False
    co, ci, kh, kw = w_shape
    if (kh, kw) != (1, 1) or tuple(stride) != (1, 1) or \
            tuple(dilate) != (1, 1):
        return False
    n, h, w_, c = shape_in
    if c != ci:
        return False
    p = n * h * w_
    return _pick_tile(p, ci, co, itemsize) > 0


def _bwd_pair_kernel(dy_ref, x_ref, w_ref, dx_ref, dw_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        dw_ref[:] = jnp.zeros_like(dw_ref)

    dy = dy_ref[:]
    # precision=DEFAULT explicitly: mxnet_tpu.base sets the ambient
    # jax_default_matmul_precision to "highest" (an f32 concern — bf16
    # MXU dots are bit-identical either way), and under "highest"
    # Mosaic rejects the transposed-lhs dot below with "Bad lhs type"
    # (bisected r5 against the identical kernel compiled without the
    # mxnet_tpu import).
    prec = lax.Precision.DEFAULT
    # dx tile: (Tp, Co) @ (Co, Ci) on the MXU, f32 accumulation
    dx_ref[:] = jnp.dot(dy, w_ref[:], precision=prec,
                        preferred_element_type=jnp.float32
                        ).astype(dx_ref.dtype)
    # dW: contract the two tiles over P.  Mosaic also rejects a
    # sublane-sublane contraction (dot_general ((0,),(0,))), so
    # transpose the dy tile IN VMEM (no HBM traffic — the whole point
    # of this kernel) and issue a standard (Co, Tp) x (Tp, Ci) matmul.
    dw_ref[:] += jnp.dot(dy.T, x_ref[:], precision=prec,
                         preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("tp",))
def _conv1x1_bwd_pair(dy2, x2, w2, tp):
    """dy2 (P, Co), x2 (P, Ci), w2 (Co, Ci) -> (dx (P, Ci) like x,
    dW (Co, Ci) f32).  One sequential grid over P tiles."""
    p, co = dy2.shape
    ci = x2.shape[1]
    grid = p // tp
    return pl.pallas_call(
        _bwd_pair_kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((tp, co), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tp, ci), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((co, ci), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((tp, ci), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((co, ci), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((p, ci), x2.dtype),
            jax.ShapeDtypeStruct((co, ci), jnp.float32),
        ],
        interpret=_interpret(),
    )(dy2, x2, w2)


@jax.custom_vjp
def conv1x1_nhwc(x, w):
    """1x1 stride-1 NHWC convolution with the fused Pallas backward.
    ``x`` (N, H, W, Ci), ``w`` (Co, Ci, 1, 1) OIHW (layout-invariant
    parameters, see ops/nn.py Convolution).  Forward is the same XLA
    conv the generic path emits; only the VJP differs."""
    return _conv1x1_fwd_math(x, w)


def _conv1x1_fwd_math(x, w):
    dn = lax.conv_dimension_numbers(x.shape, w.shape,
                                    ("NHWC", "OIHW", "NHWC"))
    return lax.conv_general_dilated(
        x, w, (1, 1), [(0, 0), (0, 0)], dimension_numbers=dn)


def _conv1x1_fwd(x, w):
    return _conv1x1_fwd_math(x, w), (x, w)


def _conv1x1_bwd(res, dy):
    x, w = res
    n, h, w_sp, ci = x.shape
    co = w.shape[0]
    p = n * h * w_sp
    tp = _pick_tile(p, ci, co, jnp.dtype(x.dtype).itemsize)
    if tp == 0:  # shape drifted past the gate: XLA fallback
        _, pullback = jax.vjp(_conv1x1_fwd_math, x, w)
        return pullback(dy)
    dx2, dw2 = _conv1x1_bwd_pair(
        dy.reshape(p, co), x.reshape(p, ci), w.reshape(co, ci), tp)
    return dx2.reshape(x.shape), dw2.astype(w.dtype).reshape(w.shape)


conv1x1_nhwc.defvjp(_conv1x1_fwd, _conv1x1_bwd)
