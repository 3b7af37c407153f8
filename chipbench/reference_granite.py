"""The granite-4.0-h-micro language model in plain ``jax.numpy``: the layer
equations of ISSUE 33 section 1 (ibm-granite/granite-4.0-h-micro
``config.json``, ``model_type`` ``granitemoehybrid``), float32, every product
at ``highest`` precision, the state-space layers by the TOKEN-BY-TOKEN
recurrence (a ``lax.scan`` over positions), no cache, no chunks, no kernels,
no batching.  It imports nothing of ``mxnet_tpu``; its weights are
``weights_granite.make``'s, a flat ``{parameter name: array}`` in which run
``r`` of like layers is stacked along a leading axis (``r3_in_weight[j]`` is
layer ``j`` of run 3), matrices stored ``(in, out)``.  A layer is read out of
its run, cast to float32 and dropped again, so the reference fits beside
bfloat16 weights of 6.4 GB.

``e = embedding_multiplier x wte[id]``.  Every layer, RMSNorm eps
``rms_norm_eps``, no biases but the convolution's: ``x <- x +
residual_multiplier x Mixer(RMSNorm_1(x))``; ``x <- x + residual_multiplier x
MLP(RMSNorm_2(x))``, ``MLP(h) = W_down (silu(a) * b)``, ``[a | b] = W_gu h``.
Logits ``= wte^T RMSNorm_f(x) / logits_scaling`` (tied).  No positions.

Attention layer: ``q, k, v = W_q h, W_k h, W_v h`` (``[k | v] = W_kv h``),
query head ``j`` reads K/V head ``j // (heads / kv heads)``, ``a =
softmax_{s <= t}(attention_multiplier x q . k)``, out ``W_o [sum a v]``.

Mamba-2 layer (one group): ``[z | u | dt] = W_in h``; ``u'_t = silu(b +
sum_j w_j u_{t-K+1+j})`` with zeros before the stream; ``[x | B | C] = u'``;
``dt = softplus(dt + dt_bias)``; ``A = -exp(A_log)``; ``S_t[h] =
exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] x_t[h] (outer) B_t``, ``S_0 = 0``;
``y_t[h] = S_t[h] C_t + D[h] x_t[h]``; ``g = RMSNorm(y * silu(z)) * gamma``
over the whole inner width; out ``W_out g``.

``leave_out`` names multipliers the TESTS drop from the reference, one at a
time, to see that the comparison fails without each.

The controls are the reference with one argument changed: ``"int8"`` rounds
every operand of every product, the K and V rows, the convolution's inputs
(what a slot's tail holds) and the STATE after every token to int8 steps,
the precision below bfloat16; ``"bf16_state"`` rounds only the state, after
every token, to bfloat16 — a lower precision than the configuration states
for it.
"""
import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
NEG = -1e30
MULTIPLIERS = ("embedding_multiplier", "residual_multiplier",
               "logits_scaling", "attention_multiplier", "d_skip")


def mm_f32(x, w):
    return jnp.einsum("...k,kn->...n", x.astype(jnp.float32),
                      w.astype(jnp.float32), precision=HIGHEST)


def _int8(a, axis):
    scale = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.clip(jnp.round(a / scale), -127, 127) * scale


def mm_int8(x, w):
    """Per-row activations and per-output-channel weights rounded to int8,
    accumulated exactly."""
    return mm_f32(_int8(x.astype(jnp.float32), -1),
                  _int8(w.astype(jnp.float32), 0))


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def _value(cfg, key, leave_out):
    return 1.0 if key in leave_out else float(cfg[key])


def runs(cfg):
    """``[(kind, layers)]``: the maximal runs of like ``layer_types``."""
    out = []
    for t in cfg["layer_types"][:cfg["num_hidden_layers"]]:
        if out and out[-1][0] == t:
            out[-1][1] += 1
        else:
            out.append([t, 1])
    return [tuple(r) for r in out]


def mamba_mixer(cfg, lw, h, mm, control, leave_out):
    """The state-space mixer's output for every row of ``h`` ``(T, H)``."""
    nh, P, N = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    K, inner = cfg["mamba_d_conv"], cfg["mamba_n_heads"] * cfg["mamba_d_head"]
    W = inner + 2 * cfg["mamba_n_groups"] * N
    T = h.shape[0]
    zxdt = mm(h, lw["in_weight"])
    z, u, dt = zxdt[:, :inner], zxdt[:, inner:inner + W], zxdt[:, inner + W:]
    if control == "int8":       # what a slot's tail holds of the inputs
        u = _int8(u, -1)
    up = jnp.concatenate([jnp.zeros((K - 1, W), jnp.float32), u], axis=0)
    cw = lw["conv_weight"].astype(jnp.float32)
    conv = lw["conv_bias"].astype(jnp.float32) + sum(
        cw[j] * up[j:j + T] for j in range(K))
    u = jax.nn.silu(conv)
    xs, bm, cm = u[:, :inner].reshape(T, nh, P), u[:, inner:inner + N], \
        u[:, inner + N:]
    dt = jax.nn.softplus(dt + lw["dt_bias"].astype(jnp.float32))
    a = -jnp.exp(lw["a_log"].astype(jnp.float32))

    def token(s, row):
        x_t, dt_t, b_t, c_t = row
        s = jnp.exp(dt_t * a)[:, None, None] * s \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        if control == "int8":
            s = _int8(s, (-2, -1))
        elif control == "bf16_state":
            # (not ``astype`` there and back: the chip's compiler, allowed
            # excess precision, drops that pair and the control reads 0.0)
            s = jax.lax.reduce_precision(s, exponent_bits=8,
                                         mantissa_bits=7)
        return s, jnp.einsum("hpn,n->hp", s, c_t, precision=HIGHEST)

    _, y = jax.lax.scan(token, jnp.zeros((nh, P, N), jnp.float32),
                        (xs, dt, bm, cm))
    if "d_skip" not in leave_out:
        y = y + lw["d_skip"].astype(jnp.float32)[:, None] * xs
    g = _rms(y.reshape(T, inner) * jax.nn.silu(z), lw["gnorm_gamma"],
             cfg["rms_norm_eps"])
    return mm(g, lw["out_weight"])


def attention_mixer(cfg, lw, h, mm, control, leave_out):
    """Grouped-query causal attention without positions over ``h`` ``(T,
    H)``."""
    hq, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    D, T = cfg["hidden_size"] // hq, h.shape[0]
    q = mm(h, lw["q_weight"]).reshape(T, kvh, hq // kvh, D)
    kv = mm(h, lw["kv_weight"])
    k, v = kv[:, :kvh * D].reshape(T, kvh, D), \
        kv[:, kvh * D:].reshape(T, kvh, D)
    if control == "int8":       # the cache rows and the queries
        q, k, v = _int8(q, -1), _int8(k, -1), _int8(v, -1)
    s = jnp.einsum("tkgd,skd->kgts", q, k, precision=HIGHEST) \
        * _value(cfg, "attention_multiplier", leave_out)
    if "attention_multiplier" in leave_out:
        s = s / D ** 0.5        # what a plain attention would scale by
    causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    p = jax.nn.softmax(jnp.where(causal[None, None], s, NEG), axis=-1)
    if control == "int8":
        p = _int8(p, -1)
    o = jnp.einsum("kgts,skd->tkgd", p, v, precision=HIGHEST)
    return mm(o.reshape(T, hq * D), lw["o_weight"])


@functools.partial(jax.jit, static_argnames=("cfg", "kind", "control",
                                             "leave_out"))
def _layer(lw, x, cfg, kind, control, leave_out):
    cfg = dict(cfg)
    mm = mm_int8 if control == "int8" else mm_f32
    res = _value(cfg, "residual_multiplier", leave_out)
    mixer = mamba_mixer if kind == "mamba" else attention_mixer
    h = _rms(x, lw["norm1_gamma"], cfg["rms_norm_eps"])
    x = x + res * mixer(cfg, lw, h, mm, control, leave_out)
    h = _rms(x, lw["norm2_gamma"], cfg["rms_norm_eps"])
    a, b = jnp.split(mm(h, lw["gu_weight"]), 2, axis=-1)
    return x + res * mm(jax.nn.silu(a) * b, lw["down_weight"])


@functools.partial(jax.jit, static_argnames=("cfg", "control", "leave_out"))
def _head(wte, normf, x, cfg, control, leave_out):
    cfg = dict(cfg)
    mm = mm_int8 if control == "int8" else mm_f32
    h = _rms(x, normf, cfg["rms_norm_eps"])
    return mm(h, wte.T) / _value(cfg, "logits_scaling", leave_out)


def freeze(cfg):
    """A hashable copy of a configuration dict (a jit static argument)."""
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in cfg.items()
                        if isinstance(v, (int, float, bool, str, list,
                                          tuple))))


def full_logits(w, cfg, tokens, control=None, leave_out=()):
    """Logits ``(T, vocabulary)`` at every position of ``tokens`` ``(T,)``."""
    fz, leave_out = freeze(cfg), tuple(leave_out)
    x = w["wte_weight"][tokens].astype(jnp.float32) \
        * _value(cfg, "embedding_multiplier", leave_out)
    for r, (kind, n) in enumerate(runs(cfg)):
        pre = f"r{r}_"
        for j in range(n):
            lw = {k[len(pre):]: v[j] for k, v in w.items()
                  if k.startswith(pre)}
            x = _layer(lw, x, fz, kind, control, leave_out)
    return _head(w["wte_weight"], w["normf_gamma"], x, fz, control,
                 leave_out)


def served_gaps(w, cfg, context, nxt, control=None):
    """For one request: ``context`` ``(T,)`` is prompt + served tokens,
    padded; ``nxt[t]`` the token that followed position ``t``.  Returns, at
    every position, the reference's best logit minus its logit of
    ``nxt[t]``; with ``control`` also the same gap for the token that
    control puts first there (a second pass of its own)."""
    z = full_logits(w, cfg, context)
    best = jnp.max(z, axis=-1)
    gap = best - jnp.take_along_axis(z, nxt[:, None], axis=-1)[:, 0]
    if not control:
        return gap, gap
    tq = jnp.argmax(full_logits(w, cfg, context, control), axis=-1)
    return gap, best - jnp.take_along_axis(z, tq[:, None], axis=-1)[:, 0]
