"""The dots3_note language model in plain ``jax.numpy``: the layer equations
of ISSUE 29 section 1 (dots-studio/dots3-note-prev ``config.json`` and its
description), float32, every product at ``highest`` precision, no cache, no
kernels, no batching.  It imports nothing of ``mxnet_tpu``; its weights are
``weights_dots3.make``'s, a flat ``{parameter name: array}``, matrices
stored ``(in, out)``.

Pre-norm residual blocks, RMSNorm eps ``rms_norm_eps``, no biases.  A
full-attention layer: query latent ``c_q = RMSNorm(W_qa x)``, per-head
``[q_nope | q_rope] = W_qb c_q``, ``[c_kv | k_rope] = W_kva x`` with ``c_kv``
normed, RoPE (consecutive pairs) on ``q_rope`` and the one shared ``k_rope``,
``[k_nope | v]_h = W_kvb c_kv``; the indexer ``I[t, s] = sum_j w[t, j]
relu(q_j[t] . k[s])`` and attention over the ``index_topk`` positions of
largest ``I[t, .]`` only, as DENSE scores masked to that set; a head-wise
sigmoid gate from the normed layer input.  A sliding layer: the same at the
``swa_*`` sizes under the mask ``t - window < s <= t``, no indexer.  FFN:
layer 0 SwiGLU; after it sigmoid routing over all experts with a bias used
for the choice only, weights normalised over the chosen, the HELD experts'
part of the sum (every held expert computed for every token and weighted,
0 where not chosen) plus the shared expert.  What absent experts would add
is left out, as in the program.

Departures and assumptions (the configuration file lists them): the two
latents are rescaled after their norms by ``sqrt(hidden / rank)``
(``apply_mla_qkv_lora_rescale``); the indexer rotates the first
``qk_rope_head_dim`` dims of its 128; its Hadamard rotation and FP8 are left
out (an orthogonal map of both sides leaves the products unchanged); the
index key's LayerNorm has eps 1e-6.

``tail_logits`` computes only what the last ``nq`` positions before ``end``
depend on: layer 0 (full attention) over every position, the second full
layer for the positions the three sliding layers above it can reach, each
sliding layer for ``window - 1`` positions fewer than the one below.
``full_logits`` is the same with ``nq`` = the whole sequence.  Everything
over positions runs in blocks, so 33k positions fit beside the weights.

``mm`` is the one matrix product every projection goes through; the control
(``mm_int8``, and ``attn_int8`` for the cache rows and attention operands)
is the reference with one argument changed: every operand of every product
rounded to int8, the precision below bfloat16.
"""
import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
NEG = -1e30


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


def _rms(x, g, eps, scale=1.0):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(jnp.float32) * scale


def _rope(x, pos, theta):
    """Rotate consecutive pairs of the last axis of ``x`` ``(n, [h,] d)``."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(d // 2, dtype=jnp.float32) * 2.0 / d)
    ang = pos.astype(jnp.float32)[:, None] * inv
    if x.ndim == 3:
        ang = ang[:, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


def _rope_head(x, pos, theta, dr):
    return jnp.concatenate([_rope(x[..., :dr], pos, theta), x[..., dr:]],
                           axis=-1)


def _blocks(n, block):
    """``(block size, number of blocks)`` covering ``n`` rows."""
    b = min(block, n)
    return b, -(-n // b)


def _pad_rows(a, n):
    return jnp.pad(a, [(0, n - a.shape[0])] + [(0, 0)] * (a.ndim - 1))


def _sizes(cfg, full):
    p = "" if full else "swa_"
    return {"heads": cfg[p + "num_attention_heads"],
            "rq": cfg[p + "q_lora_rank"], "r": cfg[p + "kv_lora_rank"],
            "dn": cfg[p + "qk_nope_head_dim"],
            "dr": cfg[p + "qk_rope_head_dim"], "dv": cfg[p + "v_head_dim"],
            "theta": float(cfg[p + "rope_theta"])}


def attention(cfg, lw, full, x, pos, q_off, nq, mm, attn_int8, q_block=128,
              head_group=32):
    """The attention sub-block's output for the ``nq`` rows of ``x`` ``(n,
    H)`` from row ``q_off`` on (``pos`` are the rows' positions); keys come
    from every row."""
    z = _sizes(cfg, full)
    n, H = x.shape
    hh, dn, dr, dv, r = z["heads"], z["dn"], z["dr"], z["dv"], z["r"]
    eps = cfg["rms_norm_eps"]
    rescale = cfg.get("apply_mla_qkv_lora_rescale", False)
    h = _rms(x, lw["norm1_gamma"], eps)
    kva = mm(h, lw["kva_weight"])
    ckv = _rms(kva[:, :r], lw["kvnorm_gamma"], eps,
               (H / r) ** 0.5 if rescale else 1.0)
    kr = _rope(kva[:, r:], pos, z["theta"])
    if attn_int8:       # the cache row, rounded as an int8 cache holds it
        ckv, kr = _int8(ckv, -1), _int8(kr, -1)
    hq = jax.lax.dynamic_slice_in_dim(h, q_off, nq)
    posq = jax.lax.dynamic_slice_in_dim(pos, q_off, nq)
    cq = _rms(mm(hq, lw["qa_weight"]), lw["qnorm_gamma"], eps,
              (H / z["rq"]) ** 0.5 if rescale else 1.0)
    qb, nb = _blocks(nq, q_block)
    npad = qb * nb
    posq_p = jnp.pad(posq, (0, npad - nq), constant_values=-1)
    causal = lambda pq: pos[None, :] <= pq[:, None]
    if full:
        J, dI = cfg["index_n_heads"], cfg["index_head_dim"]
        ik = mm(h, lw["ik_weight"])
        mu = jnp.mean(ik, -1, keepdims=True)
        var = jnp.mean((ik - mu) ** 2, -1, keepdims=True)
        ik = (ik - mu) * jax.lax.rsqrt(var + cfg.get("index_norm_eps", 1e-6)) \
            * lw["iknorm_gamma"] + lw["iknorm_beta"]
        ik = _rope_head(ik, pos, z["theta"], dr)
        iq = _rope_head(mm(cq, lw["iq_weight"]).reshape(nq, J, dI), posq,
                        z["theta"], dr)
        iw = mm(hq, lw["iw_weight"])
        if attn_int8:
            ik, iq = _int8(ik, -1), _int8(iq, -1)
        K = min(int(cfg["index_topk"]), n)

        def select(xs):
            iq_b, iw_b, pq = xs
            s = jnp.einsum("qjd,sd->qjs", iq_b, ik, precision=HIGHEST)
            score = jnp.einsum("qj,qjs->qs", iw_b, jax.nn.relu(s),
                               precision=HIGHEST)
            score = jnp.where(causal(pq), score, -jnp.inf)
            top, idx = jax.lax.top_k(score, K)
            return jnp.where(top > -jnp.inf, idx, n)    # n: dropped below

        sel = jax.lax.map(select, (
            _pad_rows(iq, npad).reshape(nb, qb, J, dI),
            _pad_rows(iw, npad).reshape(nb, qb, J),
            posq_p.reshape(nb, qb)))                    # (nb, qb, K)
    gate = jax.nn.sigmoid(mm(hq, lw["gate_weight"]))
    out = jnp.zeros((nq, H), jnp.float32)
    g = min(head_group, hh)
    for h0 in range(0, hh, g):      # a group of heads at a time
        cols = lambda width: (jnp.arange(h0, h0 + g)[:, None] * width
                              + jnp.arange(width)[None]).reshape(-1)
        q = mm(cq, lw["qb_weight"][:, cols(dn + dr)]).reshape(nq, g,
                                                               dn + dr)
        q = jnp.concatenate(
            [q[..., :dn], _rope(q[..., dn:], posq, z["theta"])], axis=-1)
        kvb = mm(ckv, lw["kvb_weight"][:, cols(dn + dv)]).reshape(
            n, g, dn + dv)
        k_nope, v = kvb[..., :dn], kvb[..., dn:]
        if attn_int8:
            v = _int8(v, -1)
        qg = _pad_rows(q, npad).reshape(nb, qb, g, dn + dr)

        def attend(xs):
            if full:
                q_b, pq, sel_b = xs
                ok = jnp.zeros((qb, n + 1), bool).at[
                    jnp.arange(qb)[:, None], sel_b].set(True)[:, :n]
            else:
                q_b, pq = xs
                ok = causal(pq) & (pos[None, :]
                                   > pq[:, None] - cfg["sliding_window_size"])
            if attn_int8:
                q_b = _int8(q_b, -1)
            s = (jnp.einsum("qhd,shd->qhs", q_b[..., :dn], k_nope,
                            precision=HIGHEST)
                 + jnp.einsum("qhd,sd->qhs", q_b[..., dn:], kr,
                              precision=HIGHEST)) / (dn + dr) ** 0.5
            p = jax.nn.softmax(jnp.where(ok[:, None, :], s, NEG), axis=-1)
            if attn_int8:
                p = _int8(p, -1)
            return jnp.einsum("qhs,shd->qhd", p, v, precision=HIGHEST)

        xs = (qg, posq_p.reshape(nb, qb)) + ((sel,) if full else ())
        o = jax.lax.map(attend, xs).reshape(npad, g, dv)[:nq]
        o = (o * gate[:, h0:h0 + g, None]).reshape(nq, g * dv)
        out = out + mm(o, lw["o_weight"][h0 * dv:(h0 + g) * dv])
    return out


def _swiglu(x, w_gu, w_down, mm):
    g, u = jnp.split(mm(x, w_gu), 2, axis=-1)
    return mm(jax.nn.silu(g) * u, w_down)


def ffn(cfg, lw, dense, x, mm, row_block=2048):
    """The feed-forward sub-block's output for every row of ``x``."""
    h = _rms(x, lw["norm2_gamma"], cfg["rms_norm_eps"])
    if dense:
        rb, nb = _blocks(h.shape[0], row_block)
        hp = _pad_rows(h, rb * nb).reshape(nb, rb, -1)
        return jax.lax.map(lambda hb: _swiglu(
            hb, lw["gu_weight"], lw["down_weight"], mm), hp).reshape(
                rb * nb, -1)[:h.shape[0]]
    s = jax.nn.sigmoid(jnp.einsum(
        "nk,ke->ne", h, lw["router_weight"].astype(jnp.float32),
        precision=HIGHEST))
    _, idx = jax.lax.top_k(s + lw["router_bias"], cfg["num_experts_per_tok"])
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    wts = chosen / jnp.sum(chosen, -1, keepdims=True) \
        * cfg.get("routed_scaling_factor", 1.0)
    dense_w = jnp.zeros_like(s).at[
        jnp.arange(s.shape[0])[:, None], idx].set(wts)
    lo, held = cfg["held_experts"]

    def one(y, xs):
        gu, down, e = xs
        return y + dense_w[:, lo + e][:, None] * _swiglu(h, gu, down, mm), \
            None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h), (
        lw["egu_weight"], lw["edown_weight"], jnp.arange(held)))
    return y + _swiglu(h, lw["sgu_weight"], lw["sdown_weight"], mm)


def layer_weights(w, i):
    pre = f"h{i}_"
    return {k[len(pre):]: v for k, v in w.items() if k.startswith(pre)}


def tail_logits(w, cfg, tokens, end, nq, control=False):
    """Logits ``(nq, held vocabulary)`` at positions ``[end - nq, end)`` of
    ``tokens`` ``(T,)`` (ids of the held slice; what lies at or behind
    ``end`` is padding).  ``end`` may be traced; the caller keeps it at or
    above every layer's row count below ``T`` (``tail_rows``)."""
    mm = mm_int8 if control else mm_f32
    T = tokens.shape[0]
    types = cfg["layer_types"][:cfg["num_hidden_layers"]]
    rows = tail_rows(cfg, T, nq)
    x = w["wte_weight"].astype(jnp.float32)[tokens]
    start = jnp.int32(0)        # x[0] is position ``start``
    for i, kind in enumerate(types):
        lw = layer_weights(w, i)
        pos = start + jnp.arange(x.shape[0], dtype=jnp.int32)
        # this layer puts out the rows [lo, lo + rows[i]) in front of end
        lo = jnp.maximum(end - rows[i], 0)
        a = attention(cfg, lw, kind == "full_attention", x, pos,
                      lo - start, rows[i], mm, control)
        x = jax.lax.dynamic_slice_in_dim(x, lo - start, rows[i]) + a
        x = x + ffn(cfg, lw, i < cfg["first_k_dense_replace"], x, mm)
        start = lo
    return mm(_rms(x, w["normf_gamma"], cfg["rms_norm_eps"]),
              w["head_weight"])


def tail_rows(cfg, T, nq):
    """Rows each layer has to put out so that the last ``nq`` positions are
    right: a sliding layer above needs ``window - 1`` more of the layer
    below, a full layer above needs every position."""
    types = cfg["layer_types"][:cfg["num_hidden_layers"]]
    rows, need = [0] * len(types), nq
    for i in range(len(types) - 1, -1, -1):
        rows[i] = min(need, T)
        need = T if types[i] == "full_attention" \
            else need + cfg["sliding_window_size"] - 1
    return rows


def full_logits(w, cfg, tokens, control=False):
    T = tokens.shape[0]
    return tail_logits(w, cfg, tokens, T, T, control)


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
    gap for the token the int8 control puts first there.  The control is a
    second pass of its own: the two together do not fit beside 8 GB of
    weights (19.4 GB of a chip's 15.75, my chip run, PR 29)."""
    cfg = freeze(cfg)
    z = _tail(w, cfg, context, end, nq, False)
    best = jnp.max(z, axis=-1)
    gap = best - jnp.take_along_axis(z, nxt_tail[:, None], axis=-1)[:, 0]
    if not control:
        return gap, gap
    tq = jnp.argmax(_tail(w, cfg, context, end, nq, True), axis=-1)
    return gap, best - jnp.take_along_axis(z, tq[:, None], axis=-1)[:, 0]
