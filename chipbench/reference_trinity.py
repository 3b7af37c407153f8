"""The Trinity-Large-Preview language model in plain ``jax.numpy``: the layer
equations of ISSUE 35 section 1 (arcee-ai/Trinity-Large-Preview
``config.json``, ``model_type`` ``afmoe``, and its description), float32,
every product at ``highest`` precision, no cache, no kernels, no batching.
It imports nothing of ``mxnet_tpu``; its weights are ``weights_trinity.make``'s,
a flat ``{parameter name: array}`` in which run ``r`` of like layers is
stacked along a leading axis (``r2_q_weight[j]`` is layer ``j`` of run 2),
matrices stored ``(in, out)``.

``h = E[tok] sqrt(hidden)`` (``mup_enabled``).  Layer ``i``, sandwich norm,
RMSNorm eps ``rms_norm_eps``, no biases: ``h <- h + N_post_attn(Attn(N_in(
h)))``, then ``h <- h + N_post_mlp(F(N_pre_mlp(h)))``.  ``Attn(x)``: ``q = x
W_q`` (``num_attention_heads`` heads of ``head_dim``), ``[k | v] = x W_kv``
(``num_key_value_heads`` heads), ``g = x W_g``; ``q`` and ``k`` through an
RMSNorm over each head (one gain each, shared by the heads); on
``sliding_attention`` layers RoPE on q and k (the halves ``(j, j + head_dim /
2)`` rotated together, ``rope_theta``) and keys ``t - sliding_window < s <=
t``; on ``full_attention`` layers no rotation and every key ``s <= t``;
scores ``q . k / sqrt(head_dim)``, query head ``j`` reads K/V head ``j //
(heads / kv heads)``; out ``(o * sigmoid(g)) W_o``.  ``F``, layer ``i <
num_dense_layers``: SwiGLU at ``intermediate_size``; after them ``s =
sigmoid(x W_r)`` over all experts, the ``num_experts_per_tok`` largest of ``s
+ b`` chosen (``b`` for the choice only), weights ``s_chosen / (sum s_chosen +
1e-20) route_scale``, the HELD experts' part of the sum (every held expert
computed for every token and weighted, 0 where not chosen) plus the shared
expert.  What absent experts would add is left out, as in the program.
Logits ``= N_f(h) W_head`` (untied).

``tail_logits`` computes only what the last ``nq`` positions before ``end``
depend on: a layer under a full-attention layer over every position, a
sliding layer for ``sliding_window - 1`` positions more than the one above
it.  ``full_logits`` is the same with ``nq`` = the whole sequence.
Everything over positions runs in blocks, so 16k positions fit beside the
weights.

``leave_out`` names parts the TESTS change in the reference, one at a time,
to see that the comparison fails without each: ``gate``, ``qk_norm``,
``post_norms``, ``embedding_multiplier``, ``route_scale``, ``window`` (every
layer sees every key), ``rope_swap`` (the full layers rotated, the sliding
ones not), ``rope_pairs`` (consecutive pairs rotated, not halves).

``mm`` is the one matrix product every projection goes through; the control
is the reference with one argument changed: every operand of every product,
the K and V rows, the queries and the attention weights rounded to int8
steps, the precision below bfloat16.
"""
import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
NEG = -1e30
PARTS = ("gate", "qk_norm", "post_norms", "embedding_multiplier",
         "route_scale", "window", "rope_swap", "rope_pairs")


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


def _rope(x, pos, theta, pairs=False):
    """Rotate the halves ``(j, j + d / 2)`` of the last axis of ``x`` ``(n,
    heads, d)`` (``rotate_half``); consecutive pairs with ``pairs``."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(d // 2, dtype=jnp.float32) * 2.0 / d)
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if pairs:
        x1, x2 = x[..., 0::2], x[..., 1::2]
        return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                         axis=-1).reshape(x.shape)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1)


def _blocks(n, block):
    """``(block size, number of blocks)`` covering ``n`` rows."""
    b = min(block, n)
    return b, -(-n // b)


def _pad_rows(a, n):
    return jnp.pad(a, [(0, n - a.shape[0])] + [(0, 0)] * (a.ndim - 1))


def runs(cfg):
    """``[layers]`` of each maximal run of like layers: equal attention kind
    and equal feed-forward kind."""
    out, last = [], None
    for i, t in enumerate(cfg["layer_types"][:cfg["num_hidden_layers"]]):
        kind = (t, i < cfg["num_dense_layers"])
        if kind == last:
            out[-1] += 1
        else:
            out.append(1)
        last = kind
    return out


def attention(cfg, lw, sliding, x, pos, q_off, nq, mm, control, leave_out,
              q_block=64):
    """The attention sub-block's output (before its post norm) for the
    ``nq`` rows of ``x`` ``(n, H)`` from row ``q_off`` on (``pos`` are the
    rows' positions); keys come from every row."""
    n, H = x.shape
    hq, kvh, D = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    G, eps = hq // kvh, cfg["rms_norm_eps"]
    h = _rms(x, lw["norm1_gamma"], eps)
    kv = mm(h, lw["kv_weight"])
    k, v = kv[:, :kvh * D].reshape(n, kvh, D), \
        kv[:, kvh * D:].reshape(n, kvh, D)
    hq_rows = jax.lax.dynamic_slice_in_dim(h, q_off, nq)
    posq = jax.lax.dynamic_slice_in_dim(pos, q_off, nq)
    q = mm(hq_rows, lw["q_weight"]).reshape(nq, hq, D)
    if "qk_norm" not in leave_out:
        q, k = _rms(q, lw["qnorm_gamma"], eps), _rms(k, lw["knorm_gamma"],
                                                     eps)
    if sliding != ("rope_swap" in leave_out):
        pairs = "rope_pairs" in leave_out
        q = _rope(q, posq, float(cfg["rope_theta"]), pairs)
        k = _rope(k, pos, float(cfg["rope_theta"]), pairs)
    if control:         # the cache rows and the queries
        q, k, v = _int8(q, -1), _int8(k, -1), _int8(v, -1)
    qb, nb = _blocks(nq, q_block)
    npad = qb * nb
    posq_p = jnp.pad(posq, (0, npad - nq), constant_values=-1)
    window = sliding and "window" not in leave_out

    def attend(xs):
        q_b, pq = xs                                    # (qb, kvh, G, D)
        ok = pos[None, :] <= pq[:, None]
        if window:
            ok = ok & (pos[None, :] > pq[:, None] - cfg["sliding_window"])
        s = jnp.einsum("qkgd,skd->qkgs", q_b, k, precision=HIGHEST) \
            / D ** 0.5
        p = jax.nn.softmax(jnp.where(ok[:, None, None, :], s, NEG), axis=-1)
        if control:
            p = _int8(p, -1)
        return jnp.einsum("qkgs,skd->qkgd", p, v, precision=HIGHEST)

    o = jax.lax.map(attend, (
        _pad_rows(q, npad).reshape(nb, qb, kvh, G, D),
        posq_p.reshape(nb, qb))).reshape(npad, hq * D)[:nq]
    if "gate" not in leave_out:
        o = o * jax.nn.sigmoid(mm(hq_rows, lw["gate_weight"]))
    return mm(o, lw["o_weight"])


def _swiglu(x, w_gu, w_down, mm):
    g, u = jnp.split(mm(x, w_gu), 2, axis=-1)
    return mm(jax.nn.silu(g) * u, w_down)


def route(cfg, lw, h, leave_out=()):
    """``(n, experts)`` float32: a token's weight on every expert of the
    layer, 0 where the expert is not chosen."""
    s = jax.nn.sigmoid(jnp.einsum(
        "nk,ke->ne", h, lw["router_weight"].astype(jnp.float32),
        precision=HIGHEST))
    _, idx = jax.lax.top_k(s + lw["router_bias"].astype(jnp.float32),
                           cfg["num_experts_per_tok"])
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    scale = 1.0 if "route_scale" in leave_out else float(cfg["route_scale"])
    wts = chosen / (jnp.sum(chosen, -1, keepdims=True) + 1e-20) * scale
    return jnp.zeros_like(s).at[jnp.arange(s.shape[0])[:, None], idx].set(
        wts)


def ffn(cfg, lw, dense, x, mm, leave_out=(), row_block=2048):
    """The feed-forward sub-block's output (before its post norm) for every
    row of ``x``."""
    h = _rms(x, lw["norm2_gamma"], cfg["rms_norm_eps"])
    if dense:
        rb, nb = _blocks(h.shape[0], row_block)
        hp = _pad_rows(h, rb * nb).reshape(nb, rb, -1)
        return jax.lax.map(lambda hb: _swiglu(
            hb, lw["gu_weight"], lw["down_weight"], mm), hp).reshape(
                rb * nb, -1)[:h.shape[0]]
    dense_w = route(cfg, lw, h, leave_out)
    lo, held = cfg["held_experts"]

    def one(y, xs):
        gu, down, e = xs
        return y + dense_w[:, lo + e][:, None] * _swiglu(h, gu, down, mm), \
            None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h), (
        lw["egu_weight"], lw["edown_weight"], jnp.arange(held)))
    return y + _swiglu(h, lw["sgu_weight"], lw["sdown_weight"], mm)


def layer_weights(w, i, cfg):
    """Layer ``i``'s parameters out of its run."""
    first = 0
    for r, n in enumerate(runs(cfg)):
        if i < first + n:
            pre = f"r{r}_"
            return {k[len(pre):]: v[i - first] for k, v in w.items()
                    if k.startswith(pre)}
        first += n
    raise IndexError(i)


def tail_rows(cfg, T, nq, leave_out=()):
    """Rows each layer has to put out so that the last ``nq`` positions are
    right: a sliding layer above needs ``sliding_window - 1`` more of the
    layer below, a full layer above needs every position."""
    types = cfg["layer_types"][:cfg["num_hidden_layers"]]
    rows, need = [0] * len(types), nq
    for i in range(len(types) - 1, -1, -1):
        rows[i] = min(need, T)
        need = T if types[i] == "full_attention" or "window" in leave_out \
            else need + cfg["sliding_window"] - 1
    return rows


def tail_logits(w, cfg, tokens, end, nq, control=False, leave_out=()):
    """Logits ``(nq, held vocabulary)`` at positions ``[end - nq, end)`` of
    ``tokens`` ``(T,)`` (ids of the held slice; what lies at or behind
    ``end`` is padding).  ``end`` may be traced; the caller keeps it at or
    above every layer's row count below ``T`` (``tail_rows``)."""
    mm = mm_int8 if control else mm_f32
    T = tokens.shape[0]
    types = cfg["layer_types"][:cfg["num_hidden_layers"]]
    rows = tail_rows(cfg, T, nq, leave_out)
    eps = cfg["rms_norm_eps"]
    post = (lambda y, g: y) if "post_norms" in leave_out \
        else (lambda y, g: _rms(y, g, eps))
    x = w["wte_weight"].astype(jnp.float32)[tokens]
    if cfg.get("mup_enabled") and "embedding_multiplier" not in leave_out:
        x = x * float(cfg["hidden_size"]) ** 0.5
    start = jnp.int32(0)        # x[0] is position ``start``
    for i, kind in enumerate(types):
        lw = layer_weights(w, i, cfg)
        pos = start + jnp.arange(x.shape[0], dtype=jnp.int32)
        # this layer puts out the rows [lo, lo + rows[i]) in front of end
        lo = jnp.maximum(end - rows[i], 0)
        a = attention(cfg, lw, kind == "sliding_attention", x, pos,
                      lo - start, rows[i], mm, control, leave_out)
        x = jax.lax.dynamic_slice_in_dim(x, lo - start, rows[i]) \
            + post(a, lw["post1_gamma"])
        x = x + post(ffn(cfg, lw, i < cfg["num_dense_layers"], x, mm,
                         leave_out), lw["post2_gamma"])
        start = lo
    return mm(_rms(x, w["normf_gamma"], eps), w["head_weight"])


def full_logits(w, cfg, tokens, control=False, leave_out=()):
    T = tokens.shape[0]
    return tail_logits(w, cfg, tokens, T, T, control, tuple(leave_out))


@functools.partial(jax.jit, static_argnames=("cfg", "nq", "control"))
def _tail(w, cfg, context, end, nq, control):
    return tail_logits(w, dict(cfg), context, end, nq, control)


def freeze(cfg):
    """A hashable copy of a configuration dict (a jit static argument)."""
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in cfg.items()
                        if isinstance(v, (int, float, bool, str, list,
                                          tuple))))


def served_gaps(w, cfg, context, nxt_tail, end, nq, control=False):
    """For one request: ``context`` ``(T,)`` is prompt + served tokens,
    padded; ``nxt_tail[k]`` the token that followed position ``end - nq +
    k``.  Returns, for each of those ``nq`` positions, the reference's best
    logit minus its logit of ``nxt_tail[k]``; with ``control`` also the same
    gap for the token the int8 control puts first there (a second pass of
    its own)."""
    cfg = freeze(cfg)
    z = _tail(w, cfg, context, end, nq, False)
    best = jnp.max(z, axis=-1)
    gap = best - jnp.take_along_axis(z, nxt_tail[:, None], axis=-1)[:, 0]
    if not control:
        return gap, gap
    tq = jnp.argmax(_tail(w, cfg, context, end, nq, True), axis=-1)
    return gap, best - jnp.take_along_axis(z, tq[:, None], axis=-1)[:, 0]
