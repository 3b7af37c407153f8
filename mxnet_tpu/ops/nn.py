"""Neural-network operators.

Reference surface: ``src/operator/nn/**`` (SURVEY.md §3.1 "Operator corpus"
nn/ family: Convolution + cuDNN autotuned paths, FullyConnected, BatchNorm,
LayerNorm, Pooling, Activation, Softmax, Dropout, Embedding, ...).

TPU-native: every op lowers to XLA HLO that tiles onto the MXU
(``lax.conv_general_dilated``, ``jnp.matmul``) or fuses into neighbors
(norms, activations).  There is no autotune knob — XLA picks conv
algorithms — and no cuDNN analog to manage.  Layouts follow the reference
(NCHW default) but every conv/pool accepts ``layout=NHWC`` which is
preferred on TPU.
"""
from __future__ import annotations

import builtins
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..base import MXNetError
from .registry import op, alias


# ----------------------------------------------------------------------- #
# activations
# ----------------------------------------------------------------------- #

@op("Activation")
def Activation(data, *, act_type="relu"):
    fns = {
        "relu": jax.nn.relu,
        "sigmoid": jax.nn.sigmoid,
        "tanh": jnp.tanh,
        "softrelu": jax.nn.softplus,
        "softsign": jax.nn.soft_sign,
        "log_sigmoid": jax.nn.log_sigmoid,
        "mish": lambda x: x * jnp.tanh(jax.nn.softplus(x)),
        "gelu": jax.nn.gelu,
        "erf_gelu": lambda x: jax.nn.gelu(x, approximate=False),
        "swish": jax.nn.silu,
    }
    if act_type not in fns:
        raise MXNetError(f"unknown act_type {act_type}")
    return fns[act_type](data)


@op("LeakyReLU")
def LeakyReLU(data, gamma=None, *, act_type="leaky", slope=0.25,
              lower_bound=0.125, upper_bound=0.334):
    if act_type == "leaky":
        return jnp.where(data >= 0, data, slope * data)
    if act_type == "prelu":
        g = gamma
        if g.ndim < data.ndim and data.ndim > 1:
            g = g.reshape((1, -1) + (1,) * (data.ndim - 2))
        return jnp.where(data >= 0, data, g * data)
    if act_type == "elu":
        return jnp.where(data >= 0, data, slope * jnp.expm1(data))
    if act_type == "selu":
        alpha, scale = 1.6732632423543772, 1.0507009873554805
        return scale * jnp.where(data >= 0, data, alpha * jnp.expm1(data))
    if act_type == "gelu":
        return jax.nn.gelu(data, approximate=False)
    if act_type == "rrelu":  # eval mode: use mean slope
        s = (lower_bound + upper_bound) / 2.0
        return jnp.where(data >= 0, data, s * data)
    raise MXNetError(f"unknown LeakyReLU act_type {act_type}")


@op("softmax")
def softmax(data, length=None, *, axis=-1, temperature=None,
            use_length=False):
    x = data / temperature if temperature else data
    if use_length and length is not None:
        L = data.shape[axis]
        pos = jnp.arange(L)
        shape = [1] * data.ndim
        shape[axis] = L
        pos = pos.reshape(shape)
        ln = length.reshape(length.shape + (1,) * (data.ndim - length.ndim))
        ln = jnp.moveaxis(ln, -1, axis) if axis != -1 and axis != data.ndim - 1 else ln
        mask = pos < ln
        x = jnp.where(mask, x, -jnp.inf)
        out = jax.nn.softmax(x, axis=axis)
        return jnp.where(mask, out, 0.0)
    return jax.nn.softmax(x, axis=axis)


@op("log_softmax")
def log_softmax(data, *, axis=-1, temperature=None):
    x = data / temperature if temperature else data
    if x.dtype in (jnp.float16, jnp.bfloat16):
        # fp32 logits math, half-precision output (mixed-precision softmax)
        return jax.nn.log_softmax(x.astype(jnp.float32),
                                  axis=axis).astype(data.dtype)
    return jax.nn.log_softmax(x, axis=axis)


@op("_sparse_softmax_ce")
def _sparse_softmax_ce(pred, label, *, axis=-1):
    """Fused sparse-label softmax cross-entropy: per-element
    ``lse(pred) - pred[label]`` with keepdims on the class axis.

    The f32 math happens INSIDE the reductions (max + sum-of-exp chains
    XLA fuses into loop fusions), so no (N, V) f32 logits array is ever
    materialized — on the BERT MLM head that materialized convert alone
    was 1.5 ms/step (3% of the step).  The autodiff backward is
    ``softmax - onehot`` recomputed elementwise from the bf16 logits."""
    ax = axis % pred.ndim
    m = jnp.max(pred, axis=ax, keepdims=True)
    z = jnp.exp(pred.astype(jnp.float32) - m.astype(jnp.float32))
    lse = m.astype(jnp.float32) + jnp.log(
        jnp.sum(z, axis=ax, keepdims=True))
    lab = jnp.expand_dims(label.astype(jnp.int32), ax) \
        if label.ndim == pred.ndim - 1 else label.astype(jnp.int32)
    # clamp like the pick path (mxnet 'clip' mode): ignore/pad labels
    # outside [0, V) must not produce NaN/wrapped gathers
    lab = jnp.clip(lab, 0, pred.shape[ax] - 1)
    picked = jnp.take_along_axis(pred, lab, axis=ax).astype(jnp.float32)
    return (lse - picked).astype(pred.dtype)


@op("softmin")
def softmin(data, *, axis=-1):
    return jax.nn.softmax(-data, axis=axis)


@op("SoftmaxActivation")
def SoftmaxActivation(data, *, mode="instance"):
    if mode == "channel":
        return jax.nn.softmax(data, axis=1)
    return jax.nn.softmax(data.reshape(data.shape[0], -1), axis=-1).reshape(
        data.shape)


# ----------------------------------------------------------------------- #
# dense / conv / pooling
# ----------------------------------------------------------------------- #

@op("FullyConnected")
def FullyConnected(data, weight, bias=None, *, num_hidden=0, no_bias=False,
                   flatten=True):
    """Reference anchor ``FullyConnected``: y = x W^T + b.  The matmul is
    the MXU hot path; keep inputs bf16-friendly and batched."""
    x = data.reshape(data.shape[0], -1) if flatten else data
    y = jnp.matmul(x, weight.T)
    if not no_bias and bias is not None:
        y = y + bias
    return y


def _pair(v, n):
    if isinstance(v, int):
        return (v,) * n
    v = tuple(v)
    return v + (v[-1],) * (n - len(v)) if len(v) < n else v


@op("Convolution")
def Convolution(data, weight, bias=None, *, kernel=(), stride=(), dilate=(),
                pad=(), num_filter=0, num_group=1, no_bias=False,
                layout=None, cudnn_tune=None, cudnn_off=False,
                workspace=1024):
    """Reference anchor ``Convolution`` (+ ``nn/cudnn/`` autotuned paths).
    Lowers to one ``lax.conv_general_dilated`` — XLA chooses the algorithm
    (cudnn_tune/workspace accepted for API compat, ignored)."""
    ndim = len(kernel)
    stride = _pair(stride or 1, ndim)
    dilate = _pair(dilate or 1, ndim)
    pad = _pair(pad or 0, ndim)
    spatial = "DHW"[-ndim:]
    if layout is None or layout.startswith("NC"):
        dn_in = "NC" + spatial
        dn_ker = "OI" + spatial
        dn_out = "NC" + spatial
        feat_axis = 1
    else:  # NHWC-style (TPU-preferred)
        dn_in = "N" + spatial + "C"
        # weights stay OIHW in EVERY layout so parameters (and .params
        # checkpoints) are layout-invariant; XLA relayouts the small
        # kernel tensor internally
        dn_ker = "OI" + spatial
        dn_out = "N" + spatial + "C"
        feat_axis = data.ndim - 1
    dn = lax.conv_dimension_numbers(data.shape, weight.shape,
                                    (dn_in, dn_ker, dn_out))
    out = lax.conv_general_dilated(
        data, weight,
        window_strides=stride,
        padding=[(p, p) for p in pad],
        rhs_dilation=dilate,
        dimension_numbers=dn,
        feature_group_count=num_group,
        preferred_element_type=None)
    if not no_bias and bias is not None:
        bshape = [1] * out.ndim
        bshape[feat_axis] = bias.shape[0]
        out = out + bias.reshape(bshape)
    return out


@op("Deconvolution")
def Deconvolution(data, weight, bias=None, *, kernel=(), stride=(),
                  dilate=(), pad=(), adj=(), num_filter=0, num_group=1,
                  no_bias=True, layout=None, target_shape=None,
                  cudnn_tune=None, cudnn_off=False, workspace=512):
    ndim = len(kernel)
    stride = _pair(stride or 1, ndim)
    pad = _pair(pad or 0, ndim)
    dilate = _pair(dilate or 1, ndim)
    adj = _pair(adj or 0, ndim)
    spatial = "DHW"[-ndim:]
    dn = lax.conv_dimension_numbers(
        data.shape, weight.shape, ("NC" + spatial, "IO" + spatial,
                                   "NC" + spatial))
    pads = []
    for k, s, p, d, a in zip(kernel, stride, pad, dilate, adj):
        ke = (k - 1) * d + 1
        pads.append((ke - 1 - p, ke - 1 - p + a))
    out = lax.conv_general_dilated(
        data, weight, window_strides=(1,) * ndim, padding=pads,
        lhs_dilation=stride, rhs_dilation=dilate, dimension_numbers=dn,
        feature_group_count=num_group)
    if not no_bias and bias is not None:
        out = out + bias.reshape((1, -1) + (1,) * ndim)
    return out


@op("Pooling")
def Pooling(data, *, kernel=(), pool_type="max", stride=(), pad=(),
            global_pool=False, pooling_convention="valid",
            count_include_pad=True, layout=None, cudnn_off=False):
    ndim = len(kernel) if kernel else data.ndim - 2
    channels_last = layout is not None and layout[1] != "C"
    sp = tuple(range(2, 2 + ndim)) if not channels_last else \
        tuple(range(1, 1 + ndim))
    if global_pool:
        if pool_type == "max":
            return jnp.max(data, axis=sp, keepdims=True)
        return jnp.mean(data, axis=sp, keepdims=True)
    stride = _pair(stride or kernel, ndim)
    pad = _pair(pad or 0, ndim)
    if channels_last:
        window = (1,) + tuple(kernel) + (1,)
        strides = (1,) + tuple(stride) + (1,)
        pads = ((0, 0),) + tuple((p, p) for p in pad) + ((0, 0),)
    else:
        window = (1, 1) + tuple(kernel)
        strides = (1, 1) + tuple(stride)
        pads = ((0, 0), (0, 0)) + tuple((p, p) for p in pad)
    if pooling_convention == "full":
        # ceil-mode: pad extra on the high side so the last window fits
        newpads = list(pads)
        off = 2 if not channels_last else 1
        for i in range(ndim):
            size = data.shape[off + i] + 2 * pad[i]
            rem = (size - kernel[i]) % stride[i]
            extra = (stride[i] - rem) % stride[i] if rem else 0
            lo, hi = newpads[off + i]
            newpads[off + i] = (lo, hi + extra)
        pads = tuple(newpads)
    if pool_type == "max":
        init = -jnp.inf if jnp.issubdtype(data.dtype, jnp.floating) else \
            jnp.iinfo(data.dtype).min
        return lax.reduce_window(data, init, lax.max, window, strides, pads)
    if pool_type in ("avg", "sum"):
        s = lax.reduce_window(data, 0.0, lax.add, window, strides, pads)
        if pool_type == "sum":
            return s
        if count_include_pad:
            denom = 1
            for k in kernel:
                denom *= k
            return s / denom
        ones = jnp.ones_like(data)
        cnt = lax.reduce_window(ones, 0.0, lax.add, window, strides, pads)
        return s / cnt
    if pool_type == "lp":
        p = 2.0
        s = lax.reduce_window(jnp.abs(data) ** p, 0.0, lax.add, window,
                              strides, pads)
        return s ** (1.0 / p)
    raise MXNetError(f"unknown pool_type {pool_type}")


# ----------------------------------------------------------------------- #
# normalization — multi-output ops return (out, mean, var) so the Gluon
# layer can commit moving stats functionally (SURVEY.md §7: no aux-state
# mutation inside traced code)
# ----------------------------------------------------------------------- #

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _bn_stats_core(data, gamma, beta, moving_mean, moving_var, eps,
                   momentum, fix_gamma, use_global_stats, axis, training):
    return _bn_stats_fwd_math(data, gamma, beta, moving_mean, moving_var,
                              eps, momentum, fix_gamma, use_global_stats,
                              axis, training)


def _bn_stats_fwd(data, gamma, beta, moving_mean, moving_var, eps,
                  momentum, fix_gamma, use_global_stats, axis, training):
    outs = _bn_stats_fwd_math(data, gamma, beta, moving_mean, moving_var,
                              eps, momentum, fix_gamma, use_global_stats,
                              axis, training)
    # residuals: x, the (stop-gradient) batch stats, and the small param
    # vectors (their dtypes shape the cotangents — beta/moving stats may
    # differ from gamma's dtype under AMP)
    return outs, (data, gamma, beta, moving_mean, moving_var,
                  outs[3], outs[4])


def _bn_stats_bwd(eps, momentum, fix_gamma, use_global_stats, axis,
                  training, res, cts):
    """Hand-written BN backward (VERDICT r3 item 1 escalation): the
    autodiff of the shifted-stats forward materializes extra reduce +
    elementwise HBM passes; the closed form needs exactly TWO sibling
    reductions (Σdy, Σdy·x̂ — one fused pass over dy, x) plus one
    elementwise pass for dx:

        dβ = Σ dy;  dγ = Σ dy·x̂
        dx = (γ·inv)·(dy − (dβ + x̂·dγ)/n)      (batch stats)
        dx = (γ·inv)·dy                          (global stats)
    """
    data, gamma, beta, moving_mean, moving_var, mean, var = res
    g_out = cts[0]  # the other 4 outputs are stop_gradient'ed
    nd_ = data.ndim
    ax = axis % nd_
    red = tuple(i for i in range(nd_) if i != ax)
    bshape = [1] * nd_
    bshape[ax] = data.shape[ax]
    n = 1
    for i in red:
        n *= data.shape[i]
    x32 = data.astype(jnp.float32)
    g32 = g_out.astype(jnp.float32)
    inv = lax.rsqrt(var.astype(jnp.float32) + eps).reshape(bshape)
    xhat = (x32 - mean.astype(jnp.float32).reshape(bshape)) * inv
    dbeta = jnp.sum(g32, axis=red)
    dgamma = jnp.sum(g32 * xhat, axis=red)
    geff = 1.0 if fix_gamma else gamma.astype(jnp.float32).reshape(bshape)
    if training and not use_global_stats:
        dx = (geff * inv) * (
            g32 - (dbeta.reshape(bshape)
                   + xhat * dgamma.reshape(bshape)) / n)
    else:
        dx = (geff * inv) * g32
    return (dx.astype(data.dtype),
            jnp.zeros_like(gamma) if fix_gamma
            else dgamma.astype(gamma.dtype),
            dbeta.astype(beta.dtype),
            jnp.zeros_like(moving_mean), jnp.zeros_like(moving_var))


_bn_stats_core.defvjp(_bn_stats_fwd, _bn_stats_bwd)


@op("_BatchNormStats")
def _BatchNormStats(data, gamma, beta, moving_mean, moving_var, *, eps=1e-5,
                    momentum=0.9, fix_gamma=True, use_global_stats=False,
                    axis=1, training=True):
    """Internal: returns ``(out, new_moving_mean, new_moving_var, batch_mean,
    batch_var)``.  The Gluon layer commits the new moving stats functionally
    (no aux-state mutation inside traced code, SURVEY.md §7).  Backward is
    the hand-written two-pass closed form (``_bn_stats_bwd``), not
    autodiff of the shifted-stats forward."""
    return _bn_stats_core(data, gamma, beta, moving_mean, moving_var,
                          float(eps), float(momentum), bool(fix_gamma),
                          bool(use_global_stats), int(axis), bool(training))


def _bn_stats_fwd_math(data, gamma, beta, moving_mean, moving_var, eps,
                       momentum, fix_gamma, use_global_stats, axis,
                       training):
    red = tuple(i for i in range(data.ndim) if i != axis % data.ndim)
    bshape = [1] * data.ndim
    bshape[axis] = data.shape[axis]
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    if training and not use_global_stats:
        # ONE-PASS stats: E[x-s] and E[(x-s)²] are sibling reductions over
        # the same read, which XLA fuses into a single HBM pass (vs
        # mean-then-var = two full passes — measured 2x BN-stat traffic on
        # the ResNet-50 step).  The per-channel shift s = moving_mean is
        # the standard shifted-data guard against E[x²]-E[x]² catastrophic
        # cancellation: after warm-up s tracks the true mean, so the
        # squared terms stay O(var) instead of O(mean²).  f32 accumulation
        # for bf16 inputs.
        x32 = data.astype(jnp.float32) if data.dtype in (
            jnp.float16, jnp.bfloat16) else data
        n = 1
        for i in red:
            n *= data.shape[i]
        shift = lax.stop_gradient(moving_mean).astype(
            jnp.float32).reshape(bshape)
        d = x32 - shift
        s1 = jnp.sum(d, axis=red) / n
        s2 = jnp.sum(d * d, axis=red) / n
        mean = (shift.reshape(-1) + s1).astype(moving_mean.dtype)
        var = jnp.maximum(s2 - s1 * s1, 0.0).astype(moving_var.dtype)
        new_mm = moving_mean * momentum + mean * (1 - momentum)
        new_mv = moving_var * momentum + var * (1 - momentum)
    else:
        mean, var = moving_mean, moving_var
        new_mm, new_mv = moving_mean, moving_var
    inv = lax.rsqrt(var + eps)
    out = (data - mean.reshape(bshape)) * (inv * g).reshape(bshape) \
        + beta.reshape(bshape)
    return (out.astype(data.dtype),
            lax.stop_gradient(new_mm), lax.stop_gradient(new_mv),
            lax.stop_gradient(mean), lax.stop_gradient(var))


def BatchNorm(data, gamma, beta, moving_mean, moving_var, *, eps=1e-5,
              momentum=0.9, fix_gamma=True, use_global_stats=False,
              output_mean_var=False, axis=1, cudnn_off=False, **_ignored):
    """Reference anchor ``BatchNorm`` — public surface: one output by
    default, ``(out, batch_mean, batch_var)`` with ``output_mean_var``.
    Training behavior follows ``autograd.is_training()`` like the
    reference."""
    from .. import autograd
    outs = _BatchNormStats(
        data, gamma, beta, moving_mean, moving_var, eps=eps,
        momentum=momentum, fix_gamma=fix_gamma,
        use_global_stats=use_global_stats, axis=axis,
        training=autograd.is_training())
    out, _mm, _mv, mean, var = outs
    if output_mean_var:
        return out, mean, var
    return out


@op("LayerNorm")
def LayerNorm(data, gamma, beta, *, axis=-1, eps=1e-5, output_mean_var=False):
    """Reference anchor ``LayerNorm`` (fused CUDA kernel there; XLA fuses
    the reduction+scale chain here).  Statistics always accumulate in fp32
    — bf16 inputs keep bf16 storage but fp32 numerics (TPU mixed-precision
    convention)."""
    x = data.astype(jnp.float32) if data.dtype in (jnp.float16,
                                                   jnp.bfloat16) else data
    mean = jnp.mean(x, axis=axis, keepdims=True)
    var = jnp.var(x, axis=axis, keepdims=True)
    inv = lax.rsqrt(var + eps)
    shape = [1] * data.ndim
    shape[axis] = data.shape[axis]
    out = ((x - mean) * inv * gamma.astype(x.dtype).reshape(shape)
           + beta.astype(x.dtype).reshape(shape)).astype(data.dtype)
    if output_mean_var:
        return out, jnp.squeeze(mean, axis), jnp.squeeze(var, axis)
    return out


@op("InstanceNorm")
def InstanceNorm(data, gamma, beta, *, eps=1e-3):
    red = tuple(range(2, data.ndim))
    mean = jnp.mean(data, axis=red, keepdims=True)
    var = jnp.var(data, axis=red, keepdims=True)
    shape = (1, -1) + (1,) * (data.ndim - 2)
    return (data - mean) * lax.rsqrt(var + eps) * gamma.reshape(shape) + \
        beta.reshape(shape)


@op("GroupNorm")
def GroupNorm(data, gamma, beta, *, num_groups=1, eps=1e-5):
    n, c = data.shape[0], data.shape[1]
    x = data.reshape((n, num_groups, c // num_groups) + data.shape[2:])
    red = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=red, keepdims=True)
    var = jnp.var(x, axis=red, keepdims=True)
    x = (x - mean) * lax.rsqrt(var + eps)
    x = x.reshape(data.shape)
    shape = (1, -1) + (1,) * (data.ndim - 2)
    return x * gamma.reshape(shape) + beta.reshape(shape)


@op("RMSNorm")
def RMSNorm(data, gamma, *, axis=-1, eps=1e-6):
    """TPU-native addition (no reference analog; used by Llama-family
    models).  f32 statistics + f32 gamma application for half-precision
    inputs, single downcast at the end (same mixed-precision convention
    as LayerNorm)."""
    x = data.astype(jnp.float32) if data.dtype in (jnp.float16,
                                                   jnp.bfloat16) else data
    ms = jnp.mean(jnp.square(x), axis=axis, keepdims=True)
    gshape = [1] * data.ndim
    gshape[axis] = data.shape[axis]
    return (x * lax.rsqrt(ms + eps)
            * gamma.astype(x.dtype).reshape(gshape)).astype(data.dtype)


# ----------------------------------------------------------------------- #
# dropout / embedding
# ----------------------------------------------------------------------- #

@op("_DropoutImpl")
def _DropoutImpl(data, key, *, p=0.5, axes=()):
    """Pure dropout given an explicit uint32 PRNG key (randomness must be an
    input to stay pure under jit)."""
    shape = data.shape
    if axes:
        shape = tuple(1 if i in axes else s for i, s in enumerate(shape))
    keep = 1.0 - p
    mask = jax.random.bernoulli(key, keep, shape)
    return jnp.where(mask, data / keep, 0.0).astype(data.dtype)


def Dropout(data, key=None, *, p=0.5, mode="training", axes=(),
            cudnn_off=False, training=None):
    """Reference anchor ``Dropout`` (cudnn path there).  Applies in training
    mode (``autograd.is_training()``) or when ``mode='always'``; a fresh key
    is drawn from ``mxnet_tpu.random`` unless one is threaded explicitly
    (hybridize does that)."""
    from .. import autograd, random as mxrandom
    if training is None:
        training = autograd.is_training()
    if (not training and mode != "always") or p <= 0.0:
        return data
    if key is None:
        key = mxrandom.next_key()
    return _DropoutImpl(data, key, p=p, axes=tuple(axes))


@op("Embedding")
def Embedding(data, weight, *, input_dim=0, output_dim=0, dtype="float32",
              sparse_grad=False):
    """Reference anchor ``Embedding``: gather rows.  On TPU this is a
    ``take`` that XLA lowers to a dynamic-gather; sharded tables come from
    GSPMD annotations (SURVEY.md §3.3 sparse/EP row)."""
    return jnp.take(weight, data.astype(jnp.int32), axis=0)


# ----------------------------------------------------------------------- #
# losses shipped as ops in the reference
# ----------------------------------------------------------------------- #

@op("SoftmaxOutput")
def SoftmaxOutput(data, label, *, grad_scale=1.0, ignore_label=-1,
                  multi_output=False, use_ignore=False, preserve_shape=False,
                  normalization="null", out_grad=False, smooth_alpha=0.0):
    """Reference anchor ``SoftmaxOutput``: forward = softmax; BACKWARD is the
    cross-entropy gradient ``(p - onehot(label)) * grad_scale`` regardless of
    the incoming cotangent (unless ``out_grad``) — the semantics the legacy
    Module training loop relies on (backward with implicit ones).

    ``multi_output=True`` softmaxes over the channel axis (axis 1) of
    ``(n, c, d1...)`` inputs with ``(n, d1...)`` labels, matching the
    reference's NCHW segmentation-style usage."""
    axis = 1 if (multi_output and data.ndim > 2) else -1

    @jax.custom_vjp
    def f(d, l):
        return jax.nn.softmax(d, axis=axis)

    def fwd(d, l):
        return jax.nn.softmax(d, axis=axis), (d, l)

    def bwd(res, g):
        d, l = res
        dm = jnp.moveaxis(d, axis, -1) if axis != -1 else d
        p = jax.nn.softmax(dm, axis=-1)
        v = dm.shape[-1]
        if l.shape == d.shape:  # distribution labels
            lm = jnp.moveaxis(l, axis, -1) if axis != -1 else l
            onehot = lm.astype(d.dtype)
            l_is_dist = True
        else:
            onehot = jax.nn.one_hot(l.astype(jnp.int32), v, dtype=d.dtype)
            l_is_dist = False
        if smooth_alpha:
            onehot = onehot * (1.0 - smooth_alpha) + smooth_alpha / v
        grad = p - onehot
        scale = grad_scale
        if use_ignore and not l_is_dist:
            mask = (l.astype(jnp.int32) != int(ignore_label))
            grad = grad * mask[..., None].astype(d.dtype)
            if normalization == "valid":
                scale = scale / jnp.maximum(mask.sum(), 1).astype(d.dtype)
        if normalization == "batch":
            scale = scale / d.shape[0]
        grad = grad * scale
        if out_grad:
            gm = jnp.moveaxis(g, axis, -1) if axis != -1 else g
            grad = grad * gm
        if axis != -1:
            grad = jnp.moveaxis(grad, -1, axis)
        return grad.astype(d.dtype), jnp.zeros_like(l)

    f.defvjp(fwd, bwd)
    return f(data, label)


@op("CTCLoss")
def CTCLoss(data, label, data_lengths=None, label_lengths=None, *,
            use_data_lengths=False, use_label_lengths=False,
            blank_label="first"):
    """CTC via the standard alpha recursion in log space with lax.scan
    (reference: warp-ctc / native kernel).  data: (T, B, V) logits."""
    T, B, V = data.shape
    logp = jax.nn.log_softmax(data, axis=-1)
    blank = 0 if blank_label == "first" else V - 1
    lab = label.astype(jnp.int32)
    Lmax = lab.shape[1]
    if label_lengths is not None and use_label_lengths:
        lab_len = label_lengths.astype(jnp.int32)
    else:
        # count non-(-1|0) entries per reference convention (-1 padding)
        lab_len = jnp.sum((lab >= 0) & (lab != -1), axis=1).astype(jnp.int32)
        lab_len = jnp.where(lab_len == 0, Lmax, lab_len)
    if data_lengths is not None and use_data_lengths:
        t_len = data_lengths.astype(jnp.int32)
    else:
        t_len = jnp.full((B,), T, jnp.int32)

    S = 2 * Lmax + 1
    # extended label seq: blank, l1, blank, l2, ... blank
    ext = jnp.full((B, S), blank, jnp.int32)
    ext = ext.at[:, 1::2].set(jnp.where(lab == -1, blank, lab))
    neg_inf = -1e30

    alpha0 = jnp.full((B, S), neg_inf)
    alpha0 = alpha0.at[:, 0].set(logp[0, jnp.arange(B), blank])
    first_lab = ext[:, 1]
    alpha0 = alpha0.at[:, 1].set(logp[0, jnp.arange(B), first_lab])

    def lse(a, b):
        m = jnp.maximum(a, b)
        m = jnp.where(jnp.isfinite(m), m, 0.0)
        return jnp.where((a <= neg_inf) & (b <= neg_inf), neg_inf,
                         m + jnp.log(jnp.exp(a - m) + jnp.exp(b - m)))

    same = jnp.concatenate(
        [jnp.ones((B, 2), bool),
         ext[:, 2:] == ext[:, :-2]], axis=1)

    def step(alpha, t):
        shifted1 = jnp.concatenate([jnp.full((B, 1), neg_inf),
                                    alpha[:, :-1]], axis=1)
        shifted2 = jnp.concatenate([jnp.full((B, 2), neg_inf),
                                    alpha[:, :-2]], axis=1)
        a = lse(alpha, shifted1)
        a = jnp.where(same, a, lse(a, shifted2))
        emit = logp[t, jnp.arange(B)[:, None], ext]
        new = a + emit
        new = jnp.where((t < t_len)[:, None], new, alpha)
        return new, None

    alpha, _ = lax.scan(step, alpha0, jnp.arange(1, T))
    end1 = 2 * lab_len
    end2 = 2 * lab_len - 1
    br = jnp.arange(B)
    ll = lse(alpha[br, end1], alpha[br, jnp.maximum(end2, 0)])
    return -ll


@op("MakeLoss")
def MakeLoss(data, *, grad_scale=1.0, valid_thresh=0.0,
             normalization="null"):
    return data


alias("make_loss", "MakeLoss")


# ----------------------------------------------------------------------- #
# attention (reference: contrib interleaved matmul selfatt ops, BERT path)
# ----------------------------------------------------------------------- #

@op("_contrib_interleaved_matmul_selfatt_qk")
def interleaved_matmul_selfatt_qk(queries_keys_values, *, heads=1):
    """(L, B, 3*E) interleaved qkv -> (B*heads, L, L) scores (reference
    anchor ``_contrib_interleaved_matmul_selfatt_qk``)."""
    L, B, E3 = queries_keys_values.shape
    E = E3 // 3
    x = queries_keys_values.reshape(L, B, heads, 3 * (E // heads))
    hd = E // heads
    q = x[..., :hd]
    k = x[..., hd:2 * hd]
    q = jnp.transpose(q, (1, 2, 0, 3)).reshape(B * heads, L, hd)
    k = jnp.transpose(k, (1, 2, 0, 3)).reshape(B * heads, L, hd)
    return jnp.matmul(q, jnp.swapaxes(k, -1, -2)) / jnp.sqrt(
        jnp.asarray(hd, q.dtype))


@op("_contrib_interleaved_matmul_selfatt_valatt")
def interleaved_matmul_selfatt_valatt(queries_keys_values, attention, *,
                                      heads=1):
    L, B, E3 = queries_keys_values.shape
    E = E3 // 3
    hd = E // heads
    x = queries_keys_values.reshape(L, B, heads, 3 * hd)
    v = x[..., 2 * hd:]
    v = jnp.transpose(v, (1, 2, 0, 3)).reshape(B * heads, L, hd)
    out = jnp.matmul(attention, v)  # (B*heads, L, hd)
    out = out.reshape(B, heads, L, hd)
    return jnp.transpose(out, (2, 0, 1, 3)).reshape(L, B, E)


# ----------------------------------------------------------------------- #
# vision ops: upsampling / resize / ROI / NMS / spatial sampling
# (reference src/operator/{nn,contrib}/ — SURVEY.md §3.1 operator corpus)
# ----------------------------------------------------------------------- #

@op("UpSampling")
def UpSampling(data, *, scale=2, sample_type="nearest", num_args=1):
    """Reference anchor ``UpSampling`` (NCHW).  nearest: repeat; bilinear:
    resize (the reference's bilinear path uses a Deconvolution with a fixed
    kernel — same result)."""
    n, c, h, w = data.shape
    if sample_type == "nearest":
        out = jnp.repeat(jnp.repeat(data, scale, axis=2), scale, axis=3)
        return out
    return jax.image.resize(data, (n, c, h * scale, w * scale),
                            method="bilinear")


@op("_contrib_BilinearResize2D")
def BilinearResize2D(data, *, height=0, width=0, scale_height=None,
                     scale_width=None, mode="size",
                     align_corners=True):
    n, c, h, w = data.shape
    if scale_height is not None:
        height = int(round(h * scale_height))
        width = int(round(w * (scale_width or scale_height)))
    return jax.image.resize(data, (n, c, int(height), int(width)),
                            method="bilinear")


alias("BilinearResize2D", "_contrib_BilinearResize2D")


@op("_contrib_ROIAlign")
def ROIAlign(data, rois, *, pooled_size=(7, 7), spatial_scale=1.0,
             sample_ratio=2, position_sensitive=False, aligned=False):
    """Reference anchor ``_contrib_ROIAlign`` (RCNN head).  rois:
    (R, 5) [batch_idx, x1, y1, x2, y2] in image coords.  Bilinear sampling
    on a fixed grid — vectorized over ROIs/bins, MXU-free but fully fused
    by XLA."""
    n, c, h, w = data.shape
    ph, pw = pooled_size
    rois = rois.astype(jnp.float32)
    batch_idx = rois[:, 0].astype(jnp.int32)
    offset = 0.5 if aligned else 0.0
    x1 = rois[:, 1] * spatial_scale - offset
    y1 = rois[:, 2] * spatial_scale - offset
    x2 = rois[:, 3] * spatial_scale - offset
    y2 = rois[:, 4] * spatial_scale - offset
    roi_w = jnp.maximum(x2 - x1, 1.0 if not aligned else 1e-6)
    roi_h = jnp.maximum(y2 - y1, 1.0 if not aligned else 1e-6)
    bin_h = roi_h / ph                                   # (R,)
    bin_w = roi_w / pw
    s = max(int(sample_ratio), 1)
    # sample grid: (ph*s) x (pw*s) points per ROI
    iy = (jnp.arange(ph * s) + 0.5) / s                  # in bin units
    ix = (jnp.arange(pw * s) + 0.5) / s
    ys = y1[:, None] + bin_h[:, None] * iy[None, :]      # (R, ph*s)
    xs = x1[:, None] + bin_w[:, None] * ix[None, :]      # (R, pw*s)

    def bilinear(img, yy, xx):
        """img: (c,h,w); yy: (ph*s,); xx: (pw*s,) → (c, ph*s, pw*s)."""
        yy = jnp.clip(yy, 0, h - 1)
        xx = jnp.clip(xx, 0, w - 1)
        y0 = jnp.floor(yy).astype(jnp.int32)
        x0 = jnp.floor(xx).astype(jnp.int32)
        y1i = jnp.minimum(y0 + 1, h - 1)
        x1i = jnp.minimum(x0 + 1, w - 1)
        wy = (yy - y0)[:, None]
        wx = (xx - x0)[None, :]
        v00 = img[:, y0][:, :, x0]
        v01 = img[:, y0][:, :, x1i]
        v10 = img[:, y1i][:, :, x0]
        v11 = img[:, y1i][:, :, x1i]
        return (v00 * (1 - wy) * (1 - wx) + v01 * (1 - wy) * wx +
                v10 * wy * (1 - wx) + v11 * wy * wx)

    def per_roi(b, yy, xx):
        img = data[b]                                    # (c,h,w)
        sampled = bilinear(img, yy, xx)                  # (c, ph*s, pw*s)
        pooled = sampled.reshape(c, ph, s, pw, s).mean(axis=(2, 4))
        return pooled

    return jax.vmap(per_roi)(batch_idx, ys, xs)          # (R, c, ph, pw)


alias("ROIAlign", "_contrib_ROIAlign")


@op("ROIPooling")
def ROIPooling(data, rois, *, pooled_size=(7, 7), spatial_scale=1.0):
    """Reference anchor ``ROIPooling`` (max-pool variant, Fast-RCNN)."""
    n, c, h, w = data.shape
    ph, pw = pooled_size
    rois = rois.astype(jnp.float32)
    batch_idx = rois[:, 0].astype(jnp.int32)
    x1 = jnp.round(rois[:, 1] * spatial_scale).astype(jnp.int32)
    y1 = jnp.round(rois[:, 2] * spatial_scale).astype(jnp.int32)
    x2 = jnp.round(rois[:, 3] * spatial_scale).astype(jnp.int32)
    y2 = jnp.round(rois[:, 4] * spatial_scale).astype(jnp.int32)

    ys = jnp.arange(h)
    xs = jnp.arange(w)

    def per_roi(b, yy1, xx1, yy2, xx2):
        img = data[b]
        roi_h = jnp.maximum(yy2 - yy1 + 1, 1)
        roi_w = jnp.maximum(xx2 - xx1 + 1, 1)
        # bin index of every pixel, -1 outside the roi
        ybin = jnp.where((ys >= yy1) & (ys <= yy2),
                         ((ys - yy1) * ph) // roi_h, -1)
        xbin = jnp.where((xs >= xx1) & (xs <= xx2),
                         ((xs - xx1) * pw) // roi_w, -1)
        onehot_y = (ybin[None, :] == jnp.arange(ph)[:, None])  # (ph, h)
        onehot_x = (xbin[None, :] == jnp.arange(pw)[:, None])  # (pw, w)
        mask = onehot_y[:, None, :, None] & onehot_x[None, :, None, :]
        big = jnp.where(mask[None], img[:, None, None, :, :], -jnp.inf)
        out = big.max(axis=(3, 4))                        # (c, ph, pw)
        return jnp.where(jnp.isfinite(out), out, 0.0)

    return jax.vmap(per_roi)(batch_idx, y1, x1, y2, x2)


@op("_contrib_box_nms", differentiable=False)
def box_nms(data, *, overlap_thresh=0.5, valid_thresh=0.0, topk=-1,
            coord_start=2, score_index=1, id_index=-1, force_suppress=False,
            in_format="corner", out_format="corner"):
    """Reference anchor ``_contrib_box_nms`` (SSD/RCNN post-processing).
    data: (..., N, K) rows [id?, score, x1, y1, x2, y2, ...]; suppressed
    rows have score set to -1 (reference convention).  Static-shape NMS via
    a fori-loop over the score-sorted boxes."""
    shape = data.shape
    flat = data.reshape((-1,) + shape[-2:])

    def one(batch):
        scores = batch[:, score_index]
        boxes = lax.dynamic_slice_in_dim(batch, coord_start, 4, axis=1)
        ids = batch[:, id_index] if id_index >= 0 else None
        order = jnp.argsort(-scores)
        n = scores.shape[0]
        keep_lim = n if topk < 0 else builtins.min(topk, n)

        x1, y1, x2, y2 = (boxes[:, i] for i in range(4))
        area = jnp.maximum(x2 - x1, 0) * jnp.maximum(y2 - y1, 0)

        def iou(i, j):
            xx1 = jnp.maximum(x1[i], x1[j])
            yy1 = jnp.maximum(y1[i], y1[j])
            xx2 = jnp.minimum(x2[i], x2[j])
            yy2 = jnp.minimum(y2[i], y2[j])
            inter = jnp.maximum(xx2 - xx1, 0) * jnp.maximum(yy2 - yy1, 0)
            return inter / jnp.maximum(area[i] + area[j] - inter, 1e-12)

        def body(k, suppressed):
            i = order[k]
            valid_i = jnp.logical_and(~suppressed[i],
                                      scores[i] >= valid_thresh)
            valid_i = jnp.logical_and(valid_i, k < keep_lim)
            others = order
            ious = jax.vmap(lambda j: iou(i, j))(others)
            same_class = jnp.ones_like(ious, bool) if (
                force_suppress or ids is None) else (ids[others] == ids[i])
            kill = (ious > overlap_thresh) & same_class & \
                (jnp.arange(n) > k)
            kill_idx = jnp.where(kill, others, i)
            new_sup = suppressed.at[kill_idx].set(
                jnp.where(kill, valid_i | suppressed[kill_idx],
                          suppressed[kill_idx]))
            return new_sup

        suppressed = lax.fori_loop(0, n, body,
                                   jnp.zeros(n, bool))
        # reference discards all non-topk candidates outright (score -1),
        # not just excludes them as suppressors
        rank = jnp.zeros(n, jnp.int32).at[order].set(jnp.arange(n))
        new_scores = jnp.where(
            suppressed | (scores < valid_thresh) | (rank >= keep_lim),
            -1.0, scores)
        return batch.at[:, score_index].set(new_scores)

    return jax.vmap(one)(flat).reshape(shape)


alias("box_nms", "_contrib_box_nms")


@op("GridGenerator")
def GridGenerator(data, *, transform_type="affine", target_shape=(0, 0)):
    """Reference anchor ``GridGenerator``: affine (N,6) → sampling grid
    (N, 2, H, W) in [-1, 1] coords (pairs with BilinearSampler — the STN
    pipeline)."""
    h, w = target_shape
    theta = data.reshape(-1, 2, 3)
    ys = jnp.linspace(-1.0, 1.0, h)
    xs = jnp.linspace(-1.0, 1.0, w)
    gx, gy = jnp.meshgrid(xs, ys)                       # (h, w)
    ones = jnp.ones_like(gx)
    coords = jnp.stack([gx, gy, ones], axis=0).reshape(3, -1)  # (3, h*w)
    out = jnp.einsum("nij,jk->nik", theta.astype(jnp.float32), coords)
    return out.reshape(-1, 2, h, w)


@op("BilinearSampler")
def BilinearSampler(data, grid, *, cudnn_off=False):
    """Reference anchor ``BilinearSampler``: sample NCHW data at grid
    (N, 2, H', W') of [-1, 1] (x, y) coords."""
    n, c, h, w = data.shape
    gx = (grid[:, 0] + 1.0) * (w - 1) / 2.0             # (n, H', W')
    gy = (grid[:, 1] + 1.0) * (h - 1) / 2.0

    def sample(img, yy, xx):
        y0 = jnp.floor(yy).astype(jnp.int32)
        x0 = jnp.floor(xx).astype(jnp.int32)
        y1 = y0 + 1
        x1 = x0 + 1
        wy = yy - y0
        wx = xx - x0

        def at(yi, xi):
            inside = (yi >= 0) & (yi < h) & (xi >= 0) & (xi < w)
            yi = jnp.clip(yi, 0, h - 1)
            xi = jnp.clip(xi, 0, w - 1)
            v = img[:, yi, xi]                          # (c, H', W')
            return jnp.where(inside[None], v, 0.0)

        return (at(y0, x0) * (1 - wy) * (1 - wx) +
                at(y0, x1) * (1 - wy) * wx +
                at(y1, x0) * wy * (1 - wx) +
                at(y1, x1) * wy * wx)

    return jax.vmap(sample)(data, gy, gx)


# activation stragglers (reference mshadow_op corpus)
@op("log_sigmoid")
def log_sigmoid(data):
    return jax.nn.log_sigmoid(data)


@op("hard_sigmoid")
def hard_sigmoid(data, *, alpha=0.2, beta=0.5):
    return jnp.clip(alpha * data + beta, 0.0, 1.0)


@op("mish")
def mish(data):
    return data * jnp.tanh(jax.nn.softplus(data))


alias("SliceChannel", "split")


# ----------------------------------------------------------------------- #
# SSD MultiBox family (reference src/operator/contrib/multibox_*.cc —
# SURVEY.md §3.1 contrib: "MultiBox* [SSD]")
# ----------------------------------------------------------------------- #

@op("_contrib_MultiBoxPrior", differentiable=False)
def MultiBoxPrior(data, *, sizes=(1.0,), ratios=(1.0,), clip=False,
                  steps=(-1.0, -1.0), offsets=(0.5, 0.5)):
    """Anchor boxes for one feature map: data (N, C, H, W) →
    (1, H*W*(len(sizes)+len(ratios)-1), 4) corner-format boxes in [0,1]."""
    h, w = data.shape[2], data.shape[3]
    step_y = steps[0] if steps[0] > 0 else 1.0 / h
    step_x = steps[1] if steps[1] > 0 else 1.0 / w
    cy = (jnp.arange(h, dtype=jnp.float32) + offsets[0]) * step_y
    cx = (jnp.arange(w, dtype=jnp.float32) + offsets[1]) * step_x
    # anchor shapes: all sizes at ratio[0], plus size[0] at other ratios
    whs = [(s * (ratios[0] ** 0.5), s / (ratios[0] ** 0.5)) for s in sizes]
    whs += [(sizes[0] * (r ** 0.5), sizes[0] / (r ** 0.5))
            for r in ratios[1:]]
    whs = jnp.asarray(whs, jnp.float32)                # (A, 2) [w, h]
    gy, gx = jnp.meshgrid(cy, cx, indexing="ij")       # (H, W)
    centers = jnp.stack([gx, gy], axis=-1).reshape(-1, 1, 2)  # (HW, 1, 2)
    half = whs.reshape(1, -1, 2) / 2.0
    mins = centers - half
    maxs = centers + half
    boxes = jnp.concatenate([mins, maxs], axis=-1).reshape(1, -1, 4)
    if clip:
        boxes = jnp.clip(boxes, 0.0, 1.0)
    return boxes


alias("MultiBoxPrior", "_contrib_MultiBoxPrior")


def _iou_matrix(a, b):
    """a: (A, 4), b: (B, 4) corner boxes → (A, B) IoU."""
    ax1, ay1, ax2, ay2 = (a[:, i, None] for i in range(4))
    bx1, by1, bx2, by2 = (b[None, :, i] for i in range(4))
    iw = jnp.maximum(jnp.minimum(ax2, bx2) - jnp.maximum(ax1, bx1), 0.0)
    ih = jnp.maximum(jnp.minimum(ay2, by2) - jnp.maximum(ay1, by1), 0.0)
    inter = iw * ih
    area_a = jnp.maximum(ax2 - ax1, 0) * jnp.maximum(ay2 - ay1, 0)
    area_b = jnp.maximum(bx2 - bx1, 0) * jnp.maximum(by2 - by1, 0)
    return inter / jnp.maximum(area_a + area_b - inter, 1e-12)


@op("_contrib_MultiBoxTarget", differentiable=False)
def MultiBoxTarget(anchor, label, cls_pred, *, overlap_threshold=0.5,
                   ignore_label=-1.0, negative_mining_ratio=-1.0,
                   negative_mining_thresh=0.5, minimum_negative_samples=0,
                   variances=(0.1, 0.1, 0.2, 0.2)):
    """SSD training targets: anchors (1, A, 4), labels (N, O, 5)
    [cls, x1, y1, x2, y2] (−1 pad) → (loc_target (N, A*4),
    loc_mask (N, A*4), cls_target (N, A))."""
    A = anchor.shape[1]
    anc = anchor.reshape(A, 4)
    acx = (anc[:, 0] + anc[:, 2]) / 2
    acy = (anc[:, 1] + anc[:, 3]) / 2
    aw = jnp.maximum(anc[:, 2] - anc[:, 0], 1e-12)
    ah = jnp.maximum(anc[:, 3] - anc[:, 1], 1e-12)
    vx, vy, vw, vh = variances

    def one(lab):
        valid = lab[:, 0] >= 0                           # (O,)
        boxes = lab[:, 1:5]
        iou = _iou_matrix(anc, boxes)                    # (A, O)
        iou = jnp.where(valid[None, :], iou, -1.0)
        best_obj = jnp.argmax(iou, axis=1)               # (A,)
        best_iou = jnp.take_along_axis(iou, best_obj[:, None],
                                       axis=1)[:, 0]
        # every gt also claims its best anchor
        best_anchor = jnp.argmax(iou, axis=0)            # (O,)
        forced = jnp.zeros(A, bool).at[best_anchor].set(valid)
        pos = jnp.logical_or(best_iou >= overlap_threshold, forced)
        gt = boxes[best_obj]                             # (A, 4)
        gcx = (gt[:, 0] + gt[:, 2]) / 2
        gcy = (gt[:, 1] + gt[:, 3]) / 2
        gw = jnp.maximum(gt[:, 2] - gt[:, 0], 1e-12)
        gh = jnp.maximum(gt[:, 3] - gt[:, 1], 1e-12)
        loc = jnp.stack([(gcx - acx) / aw / vx,
                         (gcy - acy) / ah / vy,
                         jnp.log(gw / aw) / vw,
                         jnp.log(gh / ah) / vh], axis=-1)  # (A, 4)
        loc = jnp.where(pos[:, None], loc, 0.0).reshape(-1)
        mask = jnp.repeat(pos.astype(jnp.float32), 4)
        cls = jnp.where(pos, lab[best_obj, 0] + 1.0, 0.0)  # 0 = background
        return loc, mask, cls

    loc_t, loc_m, cls_t = jax.vmap(one)(label)
    return loc_t, loc_m, cls_t


alias("MultiBoxTarget", "_contrib_MultiBoxTarget")


@op("_contrib_MultiBoxDetection", differentiable=False)
def MultiBoxDetection(cls_prob, loc_pred, anchor, *, clip=True,
                      threshold=0.01, nms_threshold=0.5, force_suppress=False,
                      variances=(0.1, 0.1, 0.2, 0.2), nms_topk=-1):
    """SSD inference decode: class probs (N, C, A), loc offsets (N, A*4),
    anchors (1, A, 4) → (N, A, 6) rows [cls_id, score, x1, y1, x2, y2]
    (cls_id −1 = suppressed/background), NMS applied per class."""
    N, C, A = cls_prob.shape
    anc = anchor.reshape(A, 4)
    acx = (anc[:, 0] + anc[:, 2]) / 2
    acy = (anc[:, 1] + anc[:, 3]) / 2
    aw = jnp.maximum(anc[:, 2] - anc[:, 0], 1e-12)
    ah = jnp.maximum(anc[:, 3] - anc[:, 1], 1e-12)
    vx, vy, vw, vh = variances

    def one(probs, loc):
        loc = loc.reshape(A, 4)
        cx = loc[:, 0] * vx * aw + acx
        cy = loc[:, 1] * vy * ah + acy
        w = jnp.exp(loc[:, 2] * vw) * aw
        h = jnp.exp(loc[:, 3] * vh) * ah
        boxes = jnp.stack([cx - w / 2, cy - h / 2, cx + w / 2, cy + h / 2],
                          axis=-1)
        if clip:
            boxes = jnp.clip(boxes, 0.0, 1.0)
        # best non-background class per anchor (class 0 = background)
        fg = probs[1:]                                   # (C-1, A)
        best = jnp.argmax(fg, axis=0)                    # (A,)
        score = jnp.take_along_axis(fg, best[None], axis=0)[0]
        keep = score > threshold
        cls_id = jnp.where(keep, best.astype(jnp.float32), -1.0)
        score = jnp.where(keep, score, -1.0)
        return jnp.concatenate([cls_id[:, None], score[:, None], boxes],
                               axis=-1)

    rows = jax.vmap(one)(cls_prob, loc_pred)             # (N, A, 6)
    from .registry import get_op
    nms = get_op("_contrib_box_nms")
    return nms.fn(rows, overlap_thresh=nms_threshold, valid_thresh=0.0,
                  topk=nms_topk, coord_start=2, score_index=1, id_index=0,
                  force_suppress=force_suppress)


alias("MultiBoxDetection", "_contrib_MultiBoxDetection")


@op("fft", differentiable=False)
def fft(data, *, compute_size=128):
    """Reference anchor ``_contrib_fft``: real input → interleaved
    [real, imag] along the last axis (the reference's packed layout)."""
    out = jnp.fft.fft(data.astype(jnp.complex64), axis=-1)
    inter = jnp.stack([out.real, out.imag], axis=-1)
    return inter.reshape(data.shape[:-1] + (data.shape[-1] * 2,))


@op("ifft", differentiable=False)
def ifft(data, *, compute_size=128):
    """Inverse of :func:`fft` (interleaved [real, imag] input)."""
    n = data.shape[-1] // 2
    pairs = data.reshape(data.shape[:-1] + (n, 2))
    comp = pairs[..., 0] + 1j * pairs[..., 1]
    return jnp.fft.ifft(comp, axis=-1).real * n


alias("_contrib_fft", "fft")
alias("_contrib_ifft", "ifft")


@op("_contrib_Proposal", differentiable=False)
def Proposal(cls_prob, bbox_pred, im_info, *, rpn_pre_nms_top_n=6000,
             rpn_post_nms_top_n=300, threshold=0.7, rpn_min_size=16,
             scales=(4, 8, 16, 32), ratios=(0.5, 1, 2),
             feature_stride=16, output_score=False, iou_loss=False):
    """RCNN region-proposal op (reference anchor ``Proposal``,
    src/operator/contrib/proposal.cc): anchors over the feature grid →
    decode bbox deltas → clip → min-size filter → top-k by score → NMS →
    top post-NMS.  Static shapes throughout (argsort + box_nms), so the
    whole RPN head jits.

    cls_prob (N, 2A, H, W), bbox_pred (N, 4A, H, W), im_info (N, 3)
    [height, width, scale] → rois (N*post_nms, 5) [batch_idx, x1,y1,x2,y2]
    (+ scores (N*post_nms, 1) when ``output_score``)."""
    N, twoA, H, W = cls_prob.shape
    A = twoA // 2
    fs = float(feature_stride)
    # base anchors centered on (fs-1)/2 with area (fs*scale)^2 per ratio
    base = []
    for r in ratios:
        for s in scales:
            size = fs * fs * float(s) * float(s)
            w = jnp.sqrt(size / r)
            h = w * r
            cx = (fs - 1) / 2.0
            cy = (fs - 1) / 2.0
            base.append([cx - (w - 1) / 2, cy - (h - 1) / 2,
                         cx + (w - 1) / 2, cy + (h - 1) / 2])
    base = jnp.asarray(base, jnp.float32)                # (A, 4)
    sx = jnp.arange(W, dtype=jnp.float32) * fs
    sy = jnp.arange(H, dtype=jnp.float32) * fs
    gy, gx = jnp.meshgrid(sy, sx, indexing="ij")
    shifts = jnp.stack([gx, gy, gx, gy], axis=-1).reshape(-1, 1, 4)
    anchors = (shifts + base[None]).reshape(-1, 4)       # (H*W*A, 4)
    K = anchors.shape[0]

    def one(probs, deltas, info):
        # foreground scores: second half of the class channel
        score = probs[A:].transpose(1, 2, 0).reshape(-1)      # (H*W*A,)
        d = deltas.transpose(1, 2, 0).reshape(-1, A, 4) \
            .reshape(H * W, A, 4).reshape(-1, 4)
        aw = anchors[:, 2] - anchors[:, 0] + 1.0
        ah = anchors[:, 3] - anchors[:, 1] + 1.0
        acx = anchors[:, 0] + 0.5 * (aw - 1)
        acy = anchors[:, 1] + 0.5 * (ah - 1)
        cx = d[:, 0] * aw + acx
        cy = d[:, 1] * ah + acy
        w = jnp.exp(jnp.clip(d[:, 2], -10, 10)) * aw
        h = jnp.exp(jnp.clip(d[:, 3], -10, 10)) * ah
        x1 = jnp.clip(cx - 0.5 * (w - 1), 0, info[1] - 1)
        y1 = jnp.clip(cy - 0.5 * (h - 1), 0, info[0] - 1)
        x2 = jnp.clip(cx + 0.5 * (w - 1), 0, info[1] - 1)
        y2 = jnp.clip(cy + 0.5 * (h - 1), 0, info[0] - 1)
        min_sz = rpn_min_size * info[2]
        ok = ((x2 - x1 + 1) >= min_sz) & ((y2 - y1 + 1) >= min_sz)
        score = jnp.where(ok, score, -1.0)
        pre = builtins.min(rpn_pre_nms_top_n, K)
        order = jnp.argsort(-score)[:pre]
        rows = jnp.stack([jnp.zeros(pre), score[order], x1[order],
                          y1[order], x2[order], y2[order]], axis=-1)
        from .registry import get_op
        nms = get_op("_contrib_box_nms")
        kept = nms.fn(rows, overlap_thresh=threshold, valid_thresh=0.0,
                      topk=-1, coord_start=2, score_index=1, id_index=0,
                      force_suppress=True)
        post = builtins.min(rpn_post_nms_top_n, pre)
        order2 = jnp.argsort(-kept[:, 1])[:post]
        sel = kept[order2]
        return sel[:, 2:6], sel[:, 1:2]

    boxes, scores = jax.vmap(one)(cls_prob, bbox_pred, im_info)
    post = boxes.shape[1]
    batch_idx = jnp.repeat(jnp.arange(N, dtype=jnp.float32), post)
    rois = jnp.concatenate([batch_idx[:, None],
                            boxes.reshape(-1, 4)], axis=-1)
    if output_score:
        return rois, scores.reshape(-1, 1)
    return rois


alias("Proposal", "_contrib_Proposal")


@op("_contrib_DeformableConvolution")
def DeformableConvolution(data, offset, weight, bias=None, *, kernel=(),
                          stride=(), dilate=(), pad=(), num_filter=0,
                          num_group=1, num_deformable_group=1, no_bias=False,
                          layout="NCHW", workspace=1024):
    """Deformable conv v1 (reference anchor ``DeformableConvolution``,
    src/operator/contrib/deformable_convolution.cc).

    data (N, C, H, W); offset (N, 2*G*kh*kw, Ho, Wo) with (dy, dx) pairs per
    deformable group G and kernel tap.  TPU-native formulation: bilinear
    im2col gather at the offset sample points (vectorized — no scalar
    loops), then ONE big (N·Ho·Wo, C·kh·kw) × (C·kh·kw, F) MXU matmul."""
    kh, kw = kernel
    sh, sw = _pair(stride or 1, 2)
    dh, dw = _pair(dilate or 1, 2)
    ph, pw = _pair(pad or 0, 2)
    N, C, H, W = data.shape
    Ho = (H + 2 * ph - dh * (kh - 1) - 1) // sh + 1
    Wo = (W + 2 * pw - dw * (kw - 1) - 1) // sw + 1
    G = num_deformable_group
    K = kh * kw

    # base sampling grid per output position and tap (dilated kernel)
    oy = jnp.arange(Ho) * sh - ph                       # (Ho,)
    ox = jnp.arange(Wo) * sw - pw
    ky = jnp.arange(kh) * dh                            # (kh,)
    kx = jnp.arange(kw) * dw
    base_y = oy[:, None, None, None] + ky[None, None, :, None]  # (Ho,1,kh,1)
    base_x = ox[None, :, None, None] + kx[None, None, None, :]  # (1,Wo,1,kw)
    base_y = jnp.broadcast_to(base_y, (Ho, Wo, kh, kw)).reshape(Ho, Wo, K)
    base_x = jnp.broadcast_to(base_x, (Ho, Wo, kh, kw)).reshape(Ho, Wo, K)

    off = offset.reshape(N, G, K, 2, Ho, Wo)
    dy = jnp.moveaxis(off[:, :, :, 0], (1, 2), (3, 4))  # (N, Ho, Wo, G, K)
    dx = jnp.moveaxis(off[:, :, :, 1], (1, 2), (3, 4))
    sy = base_y[None, :, :, None, :] + dy               # (N, Ho, Wo, G, K)
    sx = base_x[None, :, :, None, :] + dx

    def sample_image(img, yy, xx):
        """img (C, H, W); yy/xx (Ho, Wo, G, K) → (C, Ho, Wo, G, K)."""
        y0 = jnp.floor(yy).astype(jnp.int32)
        x0 = jnp.floor(xx).astype(jnp.int32)
        wy = yy - y0
        wx = xx - x0

        def at(yi, xi):
            inside = (yi >= 0) & (yi < H) & (xi >= 0) & (xi < W)
            yi = jnp.clip(yi, 0, H - 1)
            xi = jnp.clip(xi, 0, W - 1)
            v = img[:, yi, xi]                          # (C, Ho, Wo, G, K)
            return jnp.where(inside[None], v, 0.0)

        return (at(y0, x0) * (1 - wy) * (1 - wx) +
                at(y0, x0 + 1) * (1 - wy) * wx +
                at(y0 + 1, x0) * wy * (1 - wx) +
                at(y0 + 1, x0 + 1) * wy * wx)

    cols = jax.vmap(sample_image)(data, sy, sx)         # (N,C,Ho,Wo,G,K)
    # deformable groups: channel block g samples with offset group g
    Cg = C // G
    cols = cols.reshape(N, G, Cg, Ho, Wo, G, K)
    cols = jnp.take_along_axis(
        cols, jnp.arange(G).reshape(1, G, 1, 1, 1, 1, 1), axis=5)[:, :, :, :, :, 0]
    cols = cols.reshape(N, C, Ho, Wo, K)
    # one MXU GEMM: (N*Ho*Wo, C*K) x (C*K, F)
    cols2 = jnp.moveaxis(cols, (2, 3), (1, 2)).reshape(N * Ho * Wo, C * K)
    wmat = weight.reshape(num_filter, C * K).T
    out = jnp.matmul(cols2, wmat).reshape(N, Ho, Wo, num_filter)
    out = jnp.moveaxis(out, 3, 1)
    if not no_bias and bias is not None:
        out = out + bias.reshape(1, -1, 1, 1)
    return out


alias("DeformableConvolution", "_contrib_DeformableConvolution")


@op("Correlation")
def Correlation(data1, data2, *, kernel_size=1, max_displacement=1,
                stride1=1, stride2=1, pad_size=0, is_multiply=True):
    """FlowNet correlation layer (reference anchor ``Correlation``,
    src/operator/correlation.cc): for every displacement (dy, dx) within
    ``max_displacement`` (step ``stride2``), the per-pixel patch
    correlation of data1 against shifted data2.

    Vectorized as one shifted multiply + box-sum per displacement (the
    displacement count is static, so the whole op jits to a fused loop).
    Output: (N, D*D, Ho, Wo) with D = 2*floor(max_displacement/stride2)+1
    and Ho = ceil((H + 2*pad - 2*border) / stride1) where
    border = max_displacement + (kernel_size-1)//2 — the reference crops
    that border from the padded grid before striding."""
    N, C, H, W = data1.shape
    p = pad_size
    a = jnp.pad(data1, ((0, 0), (0, 0), (p, p), (p, p)))
    b = jnp.pad(data2, ((0, 0), (0, 0), (p, p), (p, p)))
    Hp, Wp = H + 2 * p, W + 2 * p
    steps = max_displacement // stride2
    disps = [d * stride2 for d in range(-steps, steps + 1)]
    outs = []
    for dy in disps:
        for dx in disps:
            shifted = jnp.roll(b, shift=(-dy, -dx), axis=(2, 3))
            valid_y = jnp.zeros(Hp, bool).at[
                max(0, -dy):Hp - max(0, dy)].set(True)
            valid_x = jnp.zeros(Wp, bool).at[
                max(0, -dx):Wp - max(0, dx)].set(True)
            mask = valid_y[:, None] & valid_x[None, :]
            prod = (a * shifted if is_multiply
                    else jnp.abs(a - shifted))
            corr = prod.mean(axis=1) * mask[None]        # (N, Hp, Wp)
            if kernel_size > 1:
                corr = lax.reduce_window(
                    corr, 0.0, lax.add, (1, kernel_size, kernel_size),
                    (1, 1, 1), "SAME") / (kernel_size * kernel_size)
            outs.append(corr)
    out = jnp.stack(outs, axis=1)                        # (N, D*D, Hp, Wp)
    # crop the reference's border (max_displacement + kernel_radius) and
    # anchor stride1 sampling after it; within the crop every displaced
    # window stays in-bounds so the zero-masking above never bites
    border = max_displacement + (kernel_size - 1) // 2
    out = out[:, :, border:Hp - border:stride1, border:Wp - border:stride1]
    return out
