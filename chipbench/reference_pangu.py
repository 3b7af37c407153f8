"""The openPangu-Ultra-MoE-718B language model in plain ``jax.numpy``: the
layer equations of the configuration (FreedomIntelligence/openPangu-Ultra-
MoE-718B ``config.json`` and the model's description), float32, every
product at ``highest`` precision, no cache, no kernels, no batching.  It
imports nothing of ``mxnet_tpu``; its weights are ``weights_pangu.make``'s, a
flat ``{parameter name: array}``, matrices stored ``(in, out)``.

Sandwich-norm residual blocks (``sandwich_norm``), RMSNorm eps
``rms_norm_eps``, no biases:

    x'  = x  + N_attn_post(MLA(N_in(x)))
    x'' = x' + N_ffn_post(FFN(N_ffn_pre(x')))

MLA, in the plain (not absorbed) form: query latent ``c_q = RMSNorm(x
W_qa)``, per head ``[q_nope | q_rope] = c_q W_qb``; ``[c_kv | k_r] = x
W_kva`` with ``c_kv`` normed, RoPE (consecutive pairs, ``rope_theta``) on
``q_rope`` and on ``k_r``, the one ``k_rope`` every head shares; per head
``[k_nope | v] = c_kv W_kvb``; scores ``(q_nope . k_nope + q_rope . k_rope)
/ sqrt(nope + rope)``, a softmax over EVERY position ``s <= t``; output
``concat_h(sum_s p v) W_o``.  FFN: the first ``first_k_dense_replace``
layers SwiGLU; after them sigmoid routing over all experts with a bias used
for the choice only, the ``num_experts_per_tok`` weights normalised over
the chosen (``norm_topk_prob``) and scaled by ``routed_scaling_factor``,
the HELD experts' part of the sum (every held expert computed for every
token and weighted, 0 where not chosen) plus the shared expert.  What absent
experts would add is left out, as in the program.  Head ``N_f(x) W_head``,
untied.

Assumptions (the configuration file's ``assumed``): the router's scoring
and its selection bias (``noaux_tc``, one group), consecutive RoPE pairs, no
rope scaling.

``tail_logits`` computes what the last ``nq`` positions before ``end``
depend on: every layer but the last over every position (each attends all
of them), the last over the ``nq`` rows alone.  ``full_logits`` is the same
with ``nq`` = the whole sequence.  Everything over positions runs in blocks
(queries by ``q_block`` against every key, a group of heads at a time; the
feed-forward by blocks of rows), so 33k positions fit beside the weights.

``mm`` is the one matrix product every projection goes through; the control
(``mm_int8``, and ``attn_int8`` for the cache rows and attention operands)
is the reference with one argument changed: every operand of every product
rounded to int8, the precision below bfloat16.
"""
import functools

import jax
import jax.numpy as jnp

from chipbench.reference_dots3 import (HIGHEST, NEG, _blocks, _int8,
                                       _pad_rows, _rms, _rope, _swiglu,
                                       freeze, layer_weights, mm_f32,
                                       mm_int8)


def attention(cfg, lw, x, pos, q_off, nq, mm, attn_int8, q_block=128,
              head_group=16):
    """The attention sub-block's output for the ``nq`` rows of ``x`` ``(n,
    H)`` from row ``q_off`` on (``pos`` are the rows' positions); keys come
    from every row."""
    n, H = x.shape
    hh = cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    r, theta, eps = cfg["kv_lora_rank"], float(cfg["rope_theta"]), \
        cfg["rms_norm_eps"]
    h = _rms(x, lw["norm1_gamma"], eps)
    kva = mm(h, lw["kva_weight"])
    ckv = _rms(kva[:, :r], lw["kvnorm_gamma"], eps)
    kr = _rope(kva[:, r:], pos, theta)
    if attn_int8:       # the cache row, rounded as an int8 cache holds it
        ckv, kr = _int8(ckv, -1), _int8(kr, -1)
    hq = jax.lax.dynamic_slice_in_dim(h, q_off, nq)
    posq = jax.lax.dynamic_slice_in_dim(pos, q_off, nq)
    cq = _rms(mm(hq, lw["qa_weight"]), lw["qnorm_gamma"], eps)
    qb, nb = _blocks(nq, q_block)
    npad = qb * nb
    posq_p = jnp.pad(posq, (0, npad - nq), constant_values=-1)
    out = jnp.zeros((nq, H), jnp.float32)
    g = min(head_group, hh)
    for h0 in range(0, hh, g):      # a group of heads at a time
        cols = lambda width: (jnp.arange(h0, h0 + g)[:, None] * width
                              + jnp.arange(width)[None]).reshape(-1)
        q = mm(cq, lw["qb_weight"][:, cols(dn + dr)]).reshape(nq, g,
                                                               dn + dr)
        q = jnp.concatenate(
            [q[..., :dn], _rope(q[..., dn:], posq, theta)], axis=-1)
        kvb = mm(ckv, lw["kvb_weight"][:, cols(dn + dv)]).reshape(
            n, g, dn + dv)
        k_nope, v = kvb[..., :dn], kvb[..., dn:]
        if attn_int8:
            v = _int8(v, -1)
        qg = _pad_rows(q, npad).reshape(nb, qb, g, dn + dr)

        def attend(xs):
            q_b, pq = xs
            if attn_int8:
                q_b = _int8(q_b, -1)
            s = (jnp.einsum("qhd,shd->qhs", q_b[..., :dn], k_nope,
                            precision=HIGHEST)
                 + jnp.einsum("qhd,sd->qhs", q_b[..., dn:], kr,
                              precision=HIGHEST)) / (dn + dr) ** 0.5
            ok = pos[None, :] <= pq[:, None]
            p = jax.nn.softmax(jnp.where(ok[:, None, :], s, NEG), axis=-1)
            if attn_int8:
                p = _int8(p, -1)
            return jnp.einsum("qhs,shd->qhd", p, v, precision=HIGHEST)

        o = jax.lax.map(attend, (qg, posq_p.reshape(nb, qb)))
        o = o.reshape(npad, g * dv)[:nq]
        out = out + mm(o, lw["o_weight"][h0 * dv:(h0 + g) * dv])
    return out


def ffn(cfg, lw, dense, x, mm, row_block=2048):
    """The feed-forward sub-block's output (before its post norm) for every
    row of ``x``, a block of rows at a time."""
    h = _rms(x, lw["norm2_gamma"], cfg["rms_norm_eps"])
    lo, held = cfg["held_experts"]

    def dense_block(hb):
        return _swiglu(hb, lw["gu_weight"], lw["down_weight"], mm)

    def routed_block(hb):
        s = jax.nn.sigmoid(jnp.einsum(
            "nk,ke->ne", hb, lw["router_weight"].astype(jnp.float32),
            precision=HIGHEST))
        _, idx = jax.lax.top_k(s + lw["router_bias"],
                               cfg["num_experts_per_tok"])
        chosen = jnp.take_along_axis(s, idx, axis=-1)
        wts = chosen / jnp.sum(chosen, -1, keepdims=True) \
            * cfg["routed_scaling_factor"]
        dense_w = jnp.zeros_like(s).at[
            jnp.arange(s.shape[0])[:, None], idx].set(wts)

        def one(y, xs):
            gu, down, e = xs
            return y + dense_w[:, lo + e][:, None] \
                * _swiglu(hb, gu, down, mm), None

        y, _ = jax.lax.scan(one, jnp.zeros_like(hb), (
            lw["egu_weight"], lw["edown_weight"], jnp.arange(held)))
        return y + _swiglu(hb, lw["sgu_weight"], lw["sdown_weight"], mm)

    rb, nb = _blocks(h.shape[0], row_block)
    hp = _pad_rows(h, rb * nb).reshape(nb, rb, -1)
    return jax.lax.map(dense_block if dense else routed_block, hp).reshape(
        rb * nb, -1)[:h.shape[0]]


def tail_rows(cfg, T, nq):
    """Rows each layer has to put out so that the last ``nq`` positions are
    right: every layer above attends every position, so every layer but the
    last puts out all ``T``."""
    L = cfg["num_hidden_layers"]
    return [T] * (L - 1) + [min(nq, T)]


# the sub-blocks' post norms, each of which a test may leave out of the
# reference (``leave_out``) to show that the comparison sees it
PARTS = ("post_attn", "post_ffn")


def tail_logits(w, cfg, tokens, end, nq, control=False, leave_out=()):
    """Logits ``(nq, held vocabulary)`` at positions ``[end - nq, end)`` of
    ``tokens`` ``(T,)`` (ids of the held slice; what lies at or behind
    ``end`` is padding).  ``end`` may be traced; the caller keeps it at or
    above ``nq``.  ``leave_out`` names ``PARTS`` to drop."""
    mm = mm_int8 if control else mm_f32
    T = tokens.shape[0]
    eps = cfg["rms_norm_eps"]
    post = lambda part, y, g: y if part in leave_out else _rms(y, g, eps)
    rows = tail_rows(cfg, T, nq)
    x = w["wte_weight"].astype(jnp.float32)[tokens]
    start = jnp.int32(0)        # x[0] is position ``start``
    for i in range(cfg["num_hidden_layers"]):
        lw = layer_weights(w, i)
        pos = start + jnp.arange(x.shape[0], dtype=jnp.int32)
        # this layer puts out the rows [lo, lo + rows[i]) in front of end
        lo = jnp.maximum(end - rows[i], 0)
        a = attention(cfg, lw, x, pos, lo - start, rows[i], mm, control)
        x = jax.lax.dynamic_slice_in_dim(x, lo - start, rows[i]) \
            + post("post_attn", a, lw["post1_gamma"])
        y = ffn(cfg, lw, i < cfg["first_k_dense_replace"], x, mm)
        x = x + post("post_ffn", y, lw["post2_gamma"])
        start = lo
    return mm(_rms(x, w["normf_gamma"], eps), w["head_weight"])


def full_logits(w, cfg, tokens, control=False, leave_out=()):
    T = tokens.shape[0]
    return tail_logits(w, cfg, tokens, T, T, control, leave_out)


@functools.partial(jax.jit, static_argnames=("cfg", "nq", "control"))
def _tail(w, cfg, context, end, nq, control):
    return tail_logits(w, dict(cfg), context, end, nq, control)


def served_gaps(w, cfg, context, nxt_tail, end, nq, control=False):
    """For one request: ``context`` ``(T,)`` is prompt + served tokens,
    padded; ``nxt_tail[k]`` the token that followed position ``end - nq +
    k``.  Returns, for each of those ``nq`` positions, the reference's best
    logit minus its logit of ``nxt_tail[k]``; with ``control`` also the same
    gap for the token the int8 control puts first there (a second pass of
    its own: the two together would not fit beside the weights)."""
    cfg = freeze(cfg)
    z = _tail(w, cfg, context, end, nq, False)
    best = jnp.max(z, axis=-1)
    gap = best - jnp.take_along_axis(z, nxt_tail[:, None], axis=-1)[:, 0]
    if not control:
        return gap, gap
    tq = jnp.argmax(_tail(w, cfg, context, end, nq, True), axis=-1)
    return gap, best - jnp.take_along_axis(z, tq[:, None], axis=-1)[:, 0]
