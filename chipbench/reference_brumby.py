"""The Brumby-14B-Base language model in plain ``jax.numpy``: the layer
equations below (manifestai/Brumby-14B-Base ``config.json``,
``model_type`` ``brumby``: Qwen3-14B's projections, QK-norm and rope with
every attention layer a degree-2 power-retention layer), float32, every
product at ``highest`` precision, in the ATTENTION FORM: no expansion, no
state, no chunks, no kernels, no batching.  It imports nothing of
``mxnet_tpu``; its weights are ``weights_brumby.make``'s, a flat ``{parameter
name: array}`` in which the layers are stacked along a leading axis
(``r0_q_weight[j]`` is layer ``j``), matrices stored ``(in, out)``.  A layer
is read out of the stack, cast to float32 and dropped again, so the
reference fits beside bfloat16 weights of 8.4 GB.

``x = wte[id]``.  Every layer, RMSNorm eps ``rms_norm_eps``, no biases: ``x
<- x + Retention(RMSNorm_1(x))``; ``x <- x + W_down (silu(a) * b)``, ``[a |
b] = W_gu RMSNorm_2(x)``.  Logits ``= RMSNorm_f(x) W_head`` (untied).

Retention: ``q_t[h] = rope(RMSNorm_q(W_q[h] h_t))``, ``k_t[g] =
rope(RMSNorm_k(W_k[g] h_t))``, ``v_t[g] = W_v[g] h_t``, rope over the halves
of a head at ``rope_theta``; ``log a_t[g] = logsigmoid(W_a[g] h_t)``, ``G_t =
sum_{r <= t} log a_r``; query head ``h`` of group ``g = h // (heads / kv
heads)``::

    y_t[h] = sum_{s <= t} exp(G_t - G_s) (q_t[h] . k_s[g])^2 v_s[g]
             / (sum_{s <= t} exp(G_t - G_s) (q_t[h] . k_s[g])^2 + eps)

Departures from a plain loop over the equations, none of them in the
arithmetic: the queries go in blocks of ``QUERY_BLOCK`` (the scores of one
block over every key are what fits), and ``exp(G_t - G_s)`` is taken after
the causal mask, so that no masked entry overflows.

``full_logits(..., probes=)`` reads the memory the program keeps in the SUM
form the equations define: for probe vectors ``p``, ``sum_s exp(G_T - G_s) (p .
k_s)^2 v_s`` and ``sum_s exp(G_T - G_s) (p . k_s)^2`` over every position of
a context — what ``phi(p)^T S_T`` and ``phi(p) . z_T`` of a state read.

The controls are the reference with one argument changed: ``"int8"`` rounds
every operand of every product, the queries, keys and values and the
attention weights to int8 steps, the precision below bfloat16;
``"bf16_state"`` runs the equations in their RECURRENT form (``S_t = a_t
S_{t-1} + phi(k_t) v_t^T``, ``z_t = a_t z_{t-1} + phi(k_t)``, ``phi`` the
degree-2 symmetric power over the ``d (d + 1) / 2`` distinct products) and
rounds ``S`` and ``z`` to bfloat16 after every token — a lower precision than
the configuration states for the state; ``"bf16_step"`` (a final state only,
``control_state``) keeps the recurrent form in float32 through the prompt and
rounds ``S`` and ``z`` only after each token past it: a state stored in
bfloat16 by the decode step alone.
"""
import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512


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


def _rope(x, theta):
    """Rotate the halves ``(j, j + d / 2)`` of every head of ``x`` ``(T,
    heads, d)`` by the angles of positions ``0 .. T - 1``."""
    T, _, d = x.shape
    inv = theta ** (-jnp.arange(d // 2, dtype=jnp.float32) * 2.0 / d)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _qkva(cfg, lw, h, mm, control):
    """Queries ``(T, G, heads a group, d)``, keys and values ``(T, G, d)``,
    ``log a`` ``(T, G)`` of one layer."""
    hq, G, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    T, eps = h.shape[0], cfg["rms_norm_eps"]
    q = _rms(mm(h, lw["q_weight"]).reshape(T, hq, d), lw["qnorm_gamma"], eps)
    k = _rms(mm(h, lw["k_weight"]).reshape(T, G, d), lw["knorm_gamma"], eps)
    v = mm(h, lw["v_weight"]).reshape(T, G, d)
    q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    if control == "int8":
        q, k, v = _int8(q, -1), _int8(k, -1), _int8(v, -1)
    log_a = jax.nn.log_sigmoid(mm(h, lw["gate_weight"]))
    return q.reshape(T, G, hq // G, d), k, v, log_a


def _attention_form(q, k, v, log_a, eps, control):
    """``y`` ``(T, G, heads a group, d)`` by the attention form, a block of
    queries at a time."""
    T = q.shape[0]
    cum = jnp.cumsum(log_a, axis=0)                           # (T, G)
    nb = -(-T // QUERY_BLOCK)
    pad = nb * QUERY_BLOCK - T
    qb = jnp.pad(q, [(0, pad)] + [(0, 0)] * 3).reshape(
        nb, QUERY_BLOCK, *q.shape[1:])
    cb = jnp.pad(cum, [(0, pad), (0, 0)]).reshape(nb, QUERY_BLOCK, -1)
    tb = jnp.arange(nb * QUERY_BLOCK).reshape(nb, QUERY_BLOCK)
    s_pos = jnp.arange(T)

    def block(xs):
        qq, cc, tt = xs
        dot = jnp.einsum("tgjd,sgd->gjts", qq, k, precision=HIGHEST)
        seen = s_pos[None, :] <= tt[:, None]                  # (Bq, T)
        decay = jnp.exp(jnp.where(seen[None], jnp.moveaxis(cc, 1, 0)[
            :, :, None] - jnp.moveaxis(cum, 1, 0)[:, None, :], -jnp.inf))
        w = dot * dot * decay[:, None]                        # (G, j, Bq, T)
        if control == "int8":
            w = _int8(w, -1)
        num = jnp.einsum("gjts,sgd->tgjd", w, v, precision=HIGHEST)
        den = jnp.moveaxis(jnp.sum(w, -1), 2, 0)              # (Bq, G, j)
        return num / (den + eps)[..., None]

    y = jax.lax.map(block, (qb, cb, tb))
    return y.reshape(nb * QUERY_BLOCK, *q.shape[1:])[:T]


def phi_exact(x):
    """The degree-2 symmetric power over the ``d (d + 1) / 2`` distinct
    products ``x_i x_j``, ``i <= j``, the cross ones times ``sqrt(2)``."""
    d = x.shape[-1]
    i, j = jnp.triu_indices(d)
    w = jnp.where(i == j, 1.0, math.sqrt(2.0)).astype(jnp.float32)
    return x[..., i] * x[..., j] * w


def _recurrent_form(q, k, v, log_a, eps, start=0):
    """``y`` by the recurrent form with ``S`` and ``z`` rounded to bfloat16
    after every token from position ``start`` on (the ``bf16_state`` control
    from 0, ``bf16_step`` from the prompt's end), and the final ``(S,
    z)``."""
    G, d = k.shape[1], k.shape[2]
    D = d * (d + 1) // 2
    # (not ``astype`` there and back: the chip's compiler, allowed excess
    # precision, drops that pair)
    rnd = lambda a, on: jnp.where(on, jax.lax.reduce_precision(
        a, exponent_bits=8, mantissa_bits=7), a)

    def token(carry, row):
        s, z = carry
        q_t, k_t, v_t, la, t = row
        a = jnp.exp(la)
        on = t >= start
        pk = phi_exact(k_t)                                   # (G, D)
        s = rnd(a[:, None, None] * s + pk[:, :, None] * v_t[:, None, :], on)
        z = rnd(a[:, None] * z + pk, on)
        pq = phi_exact(q_t)                                   # (G, j, D)
        num = jnp.einsum("gjD,gDv->gjv", pq, s, precision=HIGHEST)
        den = jnp.einsum("gjD,gD->gj", pq, z, precision=HIGHEST)
        return (s, z), num / (den + eps)[..., None]

    init = (jnp.zeros((G, D, d), jnp.float32), jnp.zeros((G, D), jnp.float32))
    final, y = jax.lax.scan(token, init, (q, k, v, log_a,
                                          jnp.arange(k.shape[0])))
    return y, final


@functools.partial(jax.jit, static_argnames=("cfg", "control"))
def _layer(lw, x, cfg, control):
    cfg = dict(cfg)
    mm = mm_int8 if control == "int8" else mm_f32
    eps = cfg["rms_norm_eps"]
    h = _rms(x, lw["norm1_gamma"], eps)
    q, k, v, log_a = _qkva(cfg, lw, h, mm, control)
    if control == "bf16_state":
        y, _ = _recurrent_form(q, k, v, log_a, cfg["retention_eps"])
    else:
        y = _attention_form(q, k, v, log_a, cfg["retention_eps"], control)
    x = x + mm(y.reshape(x.shape[0], -1), lw["o_weight"])
    h = _rms(x, lw["norm2_gamma"], eps)
    a, b = jnp.split(mm(h, lw["gu_weight"]), 2, axis=-1)
    return x + mm(jax.nn.silu(a) * b, lw["down_weight"]), (k, v, log_a)


@functools.partial(jax.jit, static_argnames=("cfg", "control"))
def _head(normf, head, x, cfg, control):
    cfg = dict(cfg)
    mm = mm_int8 if control == "int8" else mm_f32
    return mm(_rms(x, normf, cfg["rms_norm_eps"]), head)


@functools.partial(jax.jit, static_argnames=("eps",))
def _sums(k, v, log_a, probes, eps):
    """The sum form of a final state read by ``probes`` ``(G, n, d)``:
    ``(num (G, n, d), den (G, n))``."""
    cum = jnp.cumsum(log_a, axis=0)
    w = jnp.exp(cum[-1] - cum)                                # (T, G)
    dot = jnp.einsum("gnd,tgd->tgn", probes, k, precision=HIGHEST)
    p = dot * dot * w[..., None]
    return jnp.einsum("tgn,tgd->gnd", p, v, precision=HIGHEST), \
        jnp.sum(p, axis=0)


def freeze(cfg):
    """A hashable copy of a configuration dict (a jit static argument)."""
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in cfg.items()
                        if isinstance(v, (int, float, bool, str, list,
                                          tuple))))


def _layers(w, cfg):
    n = cfg["num_hidden_layers"]
    for j in range(n):
        yield {k[3:]: v[j] for k, v in w.items() if k.startswith("r0_")}


def full_logits(w, cfg, tokens, control=None, probes=None):
    """Logits ``(T, vocabulary)`` at every position of ``tokens`` ``(T,)``;
    with ``probes`` ``(layers, G, n, d)`` also, a layer, the sum form of the
    final state read by them (``_sums``): ``(logits, [(num, den)])``."""
    fz = freeze(cfg)
    x = w["wte_weight"][tokens].astype(jnp.float32)
    read = []
    for j, lw in enumerate(_layers(w, cfg)):
        x, (k, v, log_a) = _layer(lw, x, fz, control)
        if probes is not None:
            read.append(_sums(k, v, log_a, probes[j], cfg["retention_eps"]))
    logits = _head(w["normf_gamma"], w["head_weight"], x, fz, control)
    return logits if probes is None else (logits, read)


def decays(w, cfg, tokens):
    """``a = exp(log a)`` ``(layers, T, G)`` over ``tokens``: what the
    configuration's gate init is held to."""
    fz = freeze(cfg)
    x = w["wte_weight"][tokens].astype(jnp.float32)
    out = []
    for lw in _layers(w, cfg):
        x, (_, _, log_a) = _layer(lw, x, fz, None)
        out.append(jnp.exp(log_a))
    return jnp.stack(out)


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


def state_error(read, got):
    """The largest relative error, over layers and KV heads, of the probe
    readouts ``got`` (``[(num, den)]`` a layer, as ``read``) against the sum
    form's ``read``: ``|num - num_ref| / |num_ref|`` (Frobenius over the
    probes) and the same of ``den``, the larger."""
    worst = 0.0
    for (n0, d0), (n1, d1) in zip(read, got):
        for a, b, axes in ((n0, n1, (1, 2)), (d0, d1, (1,))):
            a, b = jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)
            err = jnp.sqrt(jnp.sum((b - a) ** 2, axes)) \
                / jnp.sqrt(jnp.sum(a * a, axes))
            worst = max(worst, float(jnp.max(err)))
    return worst


def control_state(w, cfg, tokens, probes, control, prompt_len=0):
    """The control's own final state, a layer, read by ``probes``: the sum
    form over its own keys (``"int8"``), or the recurrent form's rounded
    state (``"bf16_state"``; ``"bf16_step"`` rounds only past the first
    ``prompt_len`` tokens, each layer's own keys those of float32)."""
    fz = freeze(cfg)
    x = w["wte_weight"][tokens].astype(jnp.float32)
    read = []
    layer_control = None if control == "bf16_step" else control
    for j, lw in enumerate(_layers(w, cfg)):
        x, (k, v, log_a) = _layer(lw, x, fz, layer_control)
        if control == "bf16_state":
            read.append(_recurrent_reads(probes[j], k, v, log_a, 0))
        elif control == "bf16_step":
            read.append(_recurrent_reads(probes[j], k, v, log_a,
                                         prompt_len))
        else:
            read.append(_sums(k, v, log_a, probes[j], cfg["retention_eps"]))
    return read


@jax.jit
def _recurrent_reads(probes, k, v, log_a, start):
    """The recurrent state at the end of a context, rounded to bfloat16
    after every token from ``start`` on, read by ``probes``."""
    q = jnp.zeros((k.shape[0], k.shape[1], 1, k.shape[2]), jnp.float32)
    _, (s, z) = _recurrent_form(q, k, v, log_a, 1.0, start)
    pp = phi_exact(probes)                                    # (G, n, D)
    return jnp.einsum("gnD,gDv->gnv", pp, s, precision=HIGHEST), \
        jnp.einsum("gnD,gD->gn", pp, z, precision=HIGHEST)
