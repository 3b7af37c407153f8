"""Fused autoregressive decode step: ALL transformer layers in ONE
Pallas kernel per token.

Reference anchor: the decode/predict role of SURVEY.md §3.2 (the
reference serves decode through the same per-op executor as training —
hundreds of small kernel launches per token).  Measured in round 4: the
XLA scan-step decode is SEQUENCER-bound — ~230 device ops x ~2.5 us/op
= 0.58 ms of the 0.65 ms batch-1 token latency, vs a ~0.31 ms HBM
weight-streaming roofline.  VERDICT r4 item 2 asks for the op-count
collapse.

Design: a decode step at batch 1 is a chain of MATVECS — every matmul
touches each weight byte exactly once, so the step is one long weight
stream through VMEM.  The kernel packs every layer's projection weights
into ONE (n_chunks, U, CW) array and walks it with a sequential grid,
double-buffered; norm / attention / activation math happens in VMEM
between chunk matmuls.  Two families share the skeleton:

  GPT (LayerNorm, fused qkv, gelu FFN — models/transformer.py cell):
    qkv phase   xn = LN1(x);  qkv[:, c] = xn @ Wchunk + b
    attn+proj   k,v -> caches (VMEM copy + async HBM write-back at pos);
                softmax(q.K^T/sqrt(D)) V  (f32 scores, exact same math
                as models/decoding.py one_token);  x2 = x + o @ Wproj
    fc1 phase   h[:, c] = act(LN2(x2) @ Wchunk + b)
    fc2 phase   y += h[:, c] . Wchunk   (f32 accumulator)
                last chunk: x = x2 + (y + b2)

  Llama (RMSNorm, split q/k/v (GQA), RoPE, SwiGLU — models/llama.py):
    qkv phase   xn = RMS1(x); [q|k|v][:, c] = xn @ Wchunk
    attn+o      RoPE(q, k) at pos (interleaved-pair rotation via lane
                rolls, ops/attention.py rope math); grouped-query
                attention against the KV-head cache; x2 = x + o @ Wo
    gate phase  g[:, c] = RMS2(x2) @ Wchunk
    up phase    h[:, c] = silu(g[:, c]) * (RMS2(x2) @ Wchunk)
    down phase  y += h[:, c] . Wchunk;  last: x = x2 + y

K/V caches stay in HBM (pl.ANY, input-output aliased); each layer's
cache is DMA'd into a double-buffered VMEM slot one layer ahead, and the
new column is written back asynchronously — token t+1's loads see it
because pallas grid steps serialize.

``quant`` streams int8 codes with per-output-channel scales instead of
bf16 (half the HBM bytes — the q8_matvec discipline: codes convert to
bf16 in VMEM, f32 MXU accumulation, rescale in the epilogue).

The result is ONE kernel launch + ~8 XLA ops (embed, final norm, LM
head, sample) per token instead of ~230 ops.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import _interpret, _pallas_backend_ok as _on_tpu

__all__ = ["fused_decode_supported", "pack_gpt_weights",
           "pack_llama_weights", "decode_step",
           "stack_decode_weights", "stacked_decode_supported"]

_VMEM_BUDGET = 12 * 1024 * 1024


def _pick_cw(u: int, f: int, kvd: int | None = None) -> int:
    """Chunk width: must tile U (CW | U covers the 3U qkv span too), F,
    and — for GQA — the KV-projection width; bounded so the
    double-buffered (U, CW) stream block stays within 8 MB of VMEM
    (the ``2 * u * cw * 2 <= 8 MiB`` check below)."""
    for cw in (1536, 1280, 1024, 896, 768, 640, 512, 384, 256, 128, 64,
               32):
        if u % cw or f % cw:
            continue
        if kvd is not None and kvd % cw:
            continue
        if 2 * u * cw * 2 <= 8 * 1024 * 1024:
            return cw
    return 0


def _family_of(cfg):
    return "llama" if getattr(cfg, "num_kv_heads", None) is not None \
        and hasattr(cfg, "rope_base") else "gpt"


def fused_decode_supported(cfg, batch, total, dtype) -> bool:
    """Fused cached decode gate: small batch, bf16, chunk-tileable
    dims, and VMEM room for the double-buffered cache slots."""
    if not _on_tpu():
        return False
    u, f = cfg.units, cfg.hidden_size
    h = cfg.num_heads
    kv = getattr(cfg, "num_kv_heads", None) or h
    if batch > 4 or str(jnp.dtype(dtype)) != "bfloat16":
        return False
    if u % h or h % kv:
        return False
    d = u // h
    kvd = kv * d
    cw = _pick_cw(u, f, kvd if kv != h else None)
    if cw == 0:
        return False
    # two cache slots for K and V each, KV heads only (the GQA saving)
    cache_vmem = 4 * batch * kv * total * d * 2
    stream_vmem = 2 * u * cw * 2
    if cache_vmem + stream_vmem + 4 * u * max(f, 3 * u) > _VMEM_BUDGET:
        return False
    return True


def stack_decode_weights(blocks):
    """Stack every block's ``decode_layer_arrays`` export into one
    (NL, ...) array per slot — the operand set of the stacked-layer
    ``lax.scan`` decode path (``models/decoding.py``).

    This is the XLA-portable sibling of ``pack_gpt_weights`` /
    ``pack_llama_weights`` (same per-family weight enumeration, no chunk
    layout): each slot rides the scan's xs axis, so the compiled step
    contains ONE layer-body's worth of HLO instead of NL unrolled
    copies.  Callers cache the result pinned on the source arrays (the
    same invalidation discipline as the Pallas packers: a train step
    rebinds parameter arrays and triggers restacking)."""
    per = [blk.decode_layer_arrays() for blk in blocks]
    keys = list(per[0])
    if any(list(p) != keys for p in per[1:]):
        from ..base import MXNetError
        raise MXNetError("stack_decode_weights: blocks export different "
                         "decode slot sets — cannot stack")
    return {k: jnp.stack([p[k] for p in per]) for k in keys}


def stacked_decode_supported(model) -> bool:
    """Gate for the stacked-layer scan decode path (XLA, any backend).

    Requires: a block family that exports ``decode_layer_arrays`` (GPT
    ``_TransformerCell`` or ``LlamaCell``), uniform geometry / norm
    epsilons / FFN activation across layers (the scan compiles ONE body
    for all of them), and materialized parameters.  Anything else falls
    back to the per-layer unrolled path, which derives its math from the
    model's own sublayers and so covers arbitrary variants."""
    blocks = getattr(model, "blocks", None)
    if not blocks or not hasattr(model, "stacked_decode_weights"):
        return False
    if not all(hasattr(b, "decode_layer_arrays") for b in blocks):
        return False
    try:
        if hasattr(blocks[0], "rms1"):            # Llama family
            eps = {(float(b.rms1._eps), float(b.rms2._eps))
                   for b in blocks}
        else:                                     # GPT family
            eps = {(float(b.ln1._eps), float(b.ln2._eps))
                   for b in blocks}
            acts = {getattr(b.ffn.fc1.act, "_act_type", None)
                    if b.ffn.fc1.act is not None else None
                    for b in blocks}
            if len(acts) != 1:
                return False
        if len(eps) != 1:
            return False
        per0 = blocks[0].decode_layer_arrays()
        for b in blocks[1:]:
            p = b.decode_layer_arrays()
            if list(p) != list(per0) or any(
                    p[k].shape != per0[k].shape
                    or p[k].dtype != per0[k].dtype for k in per0):
                return False
    except (AttributeError, TypeError):
        # un-materialized params or a structurally different variant
        return False
    return True


def _schedule(cfg):
    """Chunk schedule: list of (phase_name, n_chunks) in grid order."""
    u, f = cfg.units, cfg.hidden_size
    h = cfg.num_heads
    kv = getattr(cfg, "num_kv_heads", None) or h
    d = u // h
    kvd = kv * d
    if _family_of(cfg) == "llama":
        cw = _pick_cw(u, f, kvd if kv != h else None)
        spans = [("qkv", (u + 2 * kvd) // cw), ("proj", u // cw),
                 ("gate", f // cw), ("up", f // cw), ("down", f // cw)]
    else:
        cw = _pick_cw(u, f)
        spans = [("qkv", 3 * u // cw), ("proj", u // cw),
                 ("fc1", f // cw), ("fc2", f // cw)]
    return cw, spans


def _quant_rows(w):
    """Per-output-channel symmetric int8 (models/decoding.py
    ``_quantize_rows`` convention): w (out, in) -> (int8 codes (out, in),
    f32 scales (out,))."""
    w32 = w.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(w32), axis=1) / 127.0, 1e-8)
    return jnp.round(w32 / s[:, None]).astype(jnp.int8), s


def _bias_of(lyr, n, dtype):
    if getattr(lyr, "bias", None) is not None:
        return lyr.bias.data()._data
    return jnp.zeros((n,), dtype)


def _pack(layer_mats, norm_rows, cw, dtype, quant):
    """Shared packer: ``layer_mats`` yields per layer a list of
    (W (out, in), bias (out,), mode) with mode ``"col"`` (stream W^T
    column chunks, per-chunk scales) or ``"row"`` (stream W column
    chunks contracted over lanes — the output-dim scales apply after
    the sum and are returned in ``s2``)."""
    w_chunks, b_chunks, s_chunks, norms, bias2, s2 = [], [], [], [], [], []
    for mats, nrm in zip(layer_mats, norm_rows):
        tail_bias = None
        tail_scale = None
        for (w, b, mode) in mats:
            if quant:
                wq, s = _quant_rows(w)
            else:
                wq, s = w, None
            n = wq.shape[0] if mode == "col" else wq.shape[1]
            if mode == "col":
                for c in range(wq.shape[0] // cw):
                    w_chunks.append(wq[c * cw:(c + 1) * cw, :].T)
                    b_chunks.append(b[c * cw:(c + 1) * cw])
                    if quant:
                        s_chunks.append(s[c * cw:(c + 1) * cw])
            else:
                for c in range(wq.shape[1] // cw):
                    w_chunks.append(wq[:, c * cw:(c + 1) * cw])
                    b_chunks.append(jnp.zeros((cw,), dtype))
                    if quant:
                        s_chunks.append(jnp.ones((cw,), jnp.float32))
                tail_bias = b
                tail_scale = s
        bias2.append((tail_bias if tail_bias is not None
                      else jnp.zeros((nrm.shape[1],), dtype)
                      ).astype(jnp.float32))
        s2.append(tail_scale if tail_scale is not None and quant
                  else jnp.ones((nrm.shape[1],), jnp.float32))
        norms.append(nrm)
    wstream = jnp.stack(w_chunks)
    if not quant:
        wstream = wstream.astype(dtype)
    bstream = jnp.stack(b_chunks)
    if quant:
        bstream = bstream.astype(jnp.float32)
    sstream = jnp.stack(s_chunks) if quant \
        else jnp.zeros((1, 1), jnp.float32)
    return (wstream, bstream, jnp.stack(norms), jnp.stack(bias2),
            sstream, jnp.stack(s2))


def pack_gpt_weights(blocks, dtype, quant=False):
    """Stack every GPT block's projections into the stream layout:
    Wqkv^T / Wproj^T / Wfc1^T column chunks + Wfc2 lane-contraction
    chunks, each (U, CW).  Returns the traceable 6-tuple
    ``(wstream, bstream, norms (NL,4,U) f32, bias2, sstream, s2)``."""
    cell0 = blocks[0]
    u = cell0.ln1.gamma.shape[0]
    f = cell0.ffn.fc1.weight.shape[0]
    cw = _pick_cw(u, f)

    def mats():
        for blk in blocks:
            yield [
                (blk.attn.qkv.weight.data()._data,
                 _bias_of(blk.attn.qkv, 3 * u, dtype), "col"),
                (blk.attn.proj.weight.data()._data,
                 _bias_of(blk.attn.proj, u, dtype), "col"),
                (blk.ffn.fc1.weight.data()._data,
                 _bias_of(blk.ffn.fc1, f, dtype), "col"),
                (blk.ffn.fc2.weight.data()._data,
                 _bias_of(blk.ffn.fc2, u, dtype), "row"),
            ]

    def nrms():
        for blk in blocks:
            yield jnp.stack([
                blk.ln1.gamma.data()._data.astype(jnp.float32),
                blk.ln1.beta.data()._data.astype(jnp.float32),
                blk.ln2.gamma.data()._data.astype(jnp.float32),
                blk.ln2.beta.data()._data.astype(jnp.float32)])

    return _pack(mats(), nrms(), cw, dtype, quant)


def pack_llama_weights(blocks, cfg, dtype, quant=False):
    """Llama stream: q/k/v/o^T + gate^T/up^T column chunks and down
    lane-contraction chunks.  norms rows: [rms1 gamma, 0, rms2 gamma,
    0] (RMSNorm has no beta)."""
    u, f = cfg.units, cfg.hidden_size
    d = u // cfg.num_heads
    kvd = cfg.num_kv_heads * d
    cw = _pick_cw(u, f, kvd if cfg.num_kv_heads != cfg.num_heads
                  else None)

    def mats():
        for blk in blocks:
            yield [
                (blk.attn.q_proj.weight.data()._data,
                 _bias_of(blk.attn.q_proj, u, dtype), "col"),
                (blk.attn.k_proj.weight.data()._data,
                 _bias_of(blk.attn.k_proj, kvd, dtype), "col"),
                (blk.attn.v_proj.weight.data()._data,
                 _bias_of(blk.attn.v_proj, kvd, dtype), "col"),
                (blk.attn.o_proj.weight.data()._data,
                 _bias_of(blk.attn.o_proj, u, dtype), "col"),
                (blk.mlp.gate.weight.data()._data,
                 _bias_of(blk.mlp.gate, f, dtype), "col"),
                (blk.mlp.up.weight.data()._data,
                 _bias_of(blk.mlp.up, f, dtype), "col"),
                (blk.mlp.down.weight.data()._data,
                 _bias_of(blk.mlp.down, u, dtype), "row"),
            ]

    def nrms():
        z = jnp.zeros((u,), jnp.float32)
        for blk in blocks:
            yield jnp.stack([
                blk.rms1.gamma.data()._data.astype(jnp.float32), z,
                blk.rms2.gamma.data()._data.astype(jnp.float32), z])

    return _pack(mats(), nrms(), cw, dtype, quant)


def _rope_lanewise(x32, pos, inv_lane):
    """ops/attention.py ``rope`` math on a (Rows, D) f32 value without
    strided lane access: interleaved (even, odd) pairs rotate by
    theta_i = pos * inv_freq[i]; expressed with lane rolls —
      out[even d] = x[d]*cos - x[d+1]*sin
      out[odd  d] = x[d-1]*sin + x[d]*cos
    ``inv_lane`` (1, D) carries inv_freq[d // 2] per lane."""
    rows, dd = x32.shape
    theta = pos.astype(jnp.float32) * inv_lane          # (1, D)
    c = jnp.cos(theta)
    s = jnp.sin(theta)
    d_idx = lax.broadcasted_iota(jnp.int32, (rows, dd), 1)
    even = (d_idx % 2) == 0
    xl = pltpu.roll(x32, dd - 1, axis=1)                # x[d+1]
    xr = pltpu.roll(x32, 1, axis=1)                     # x[d-1]
    return x32 * c + jnp.where(even, -xl * s, xr * s)


def _make_kernel(NL, NC, B, U, F, H, KV, D, T, CW, spans, family, act,
                 eps, quant):
    scale = 1.0 / (D ** 0.5)
    G = H // KV
    KVD = KV * D
    QS = 3 * U if family == "gpt" else U + 2 * KVD
    lo = {}
    off = 0
    for name, n in spans:
        lo[name] = (off, off + n)
        off += n
    qkv_hi = lo["qkv"][1]
    proj_lo, proj_hi = lo["proj"]
    llama = family == "llama"

    if act == "gelu":
        act_fn = jax.nn.gelu
    elif act == "relu":
        act_fn = jax.nn.relu
    elif act is None:
        act_fn = lambda z: z
    else:
        raise ValueError(f"fused decode: unsupported activation {act}")

    def kernel(pos_ref, x_ref, w_ref, b_ref, s_ref, norm_ref, b2_ref,
               s2_ref, rope_ref, kh_ref, vh_ref,
               xo_ref, kh_out, vh_out,
               xres, qkv_s, x2_s, xn_s, h_s, g_s, yacc, o_s,
               kslots, vslots, load_sem, store_sem):
        j = pl.program_id(0)
        layer = j // NC
        jj = j % NC
        pos = pos_ref[0]
        slot = lax.rem(layer, 2)

        def _chunk():
            w = w_ref[0]
            return w.astype(xres.dtype) if quant else w

        def _mm(lhs):
            """lhs @ chunk: f32 MXU accumulate; quant adds the
            per-output-channel rescale + f32 bias (q8_matvec path
            parity); native callers add the bf16 bias themselves."""
            part = jnp.dot(lhs, _chunk(),
                           preferred_element_type=jnp.float32)
            if quant:
                return part * s_ref[0][None, :] + b_ref[0][None, :]
            return part

        def _cast_add_bias(part, dst_dtype):
            if quant:
                return part.astype(dst_dtype)
            return part.astype(dst_dtype) + b_ref[0]

        def _norm(val32, grow, brow):
            g = norm_ref[layer, grow]
            if llama:  # RMSNorm (ops/nn.py): f32 ms + gamma, no beta
                ms = jnp.mean(val32 * val32, axis=-1, keepdims=True)
                return val32 * lax.rsqrt(ms + eps) * g[None, :]
            b = norm_ref[layer, brow]
            mean = jnp.mean(val32, axis=-1, keepdims=True)
            var = jnp.mean((val32 - mean) ** 2, axis=-1, keepdims=True)
            inv = lax.rsqrt(var + eps)
            return (val32 - mean) * inv * g[None, :] + b[None, :]

        def _load(lyr, slt):
            for i, (src, dst) in enumerate(((kh_ref, kslots),
                                            (vh_ref, vslots))):
                pltpu.make_async_copy(
                    src.at[lyr], dst.at[slt], load_sem.at[i, slt]).start()

        def _load_wait(slt):
            for i, (src, dst) in enumerate(((kh_ref, kslots),
                                            (vh_ref, vslots))):
                pltpu.make_async_copy(
                    src.at[0], dst.at[slt], load_sem.at[i, slt]).wait()

        @pl.when(j == 0)
        def _():
            xres[:] = x_ref[:]
            _load(0, 0)

        # ---- qkv phase: xn = norm1(x); qkv[:, c] = xn @ W (+ b) ------ #
        @pl.when(jj < qkv_hi)
        def _():
            @pl.when(jj == 0)
            def _():
                xn_s[:] = _norm(xres[:].astype(jnp.float32), 0, 1
                                ).astype(xn_s.dtype)
            part = _mm(xn_s[:])
            col = jj * CW
            qkv_s[:, pl.ds(col, CW)] = _cast_add_bias(part, qkv_s.dtype)

        # ---- attention (first proj chunk) ---------------------------- #
        @pl.when(jj == proj_lo)
        def _():
            _load_wait(slot)
            q = qkv_s[:, 0:U]
            k = qkv_s[:, U:U + KVD] if llama else qkv_s[:, U:2 * U]
            v = qkv_s[:, U + KVD:U + 2 * KVD] if llama \
                else qkv_s[:, 2 * U:3 * U]
            tids = lax.broadcasted_iota(jnp.int32, (1, T), 1)
            mask = tids <= pos
            pos_f = pos.astype(jnp.float32)
            outs = []
            for b_i in range(B):
                qh = q[b_i].reshape(H, D)
                kh_new = k[b_i].reshape(KV, D)
                vh_new = v[b_i].reshape(KV, D)
                if llama:  # RoPE on q and k (f32, cast back: op parity)
                    inv = rope_ref[0][None, :]
                    qh = _rope_lanewise(qh.astype(jnp.float32), pos_f,
                                        inv).astype(qh.dtype)
                    kh_new = _rope_lanewise(
                        kh_new.astype(jnp.float32), pos_f, inv
                    ).astype(kh_new.dtype)
                kslots[slot, b_i, :, pl.ds(pos, 1), :] = \
                    kh_new.reshape(KV, 1, D)
                vslots[slot, b_i, :, pl.ds(pos, 1), :] = \
                    vh_new.reshape(KV, 1, D)
                per_kv = []
                for kv_i in range(KV):
                    qg = qh[kv_i * G:(kv_i + 1) * G]       # (G, D)
                    s = lax.dot_general(
                        qg, kslots[slot, b_i, kv_i],
                        (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * scale
                    s = jnp.where(mask, s, -1e30)          # (G, T)
                    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
                    per_kv.append(jnp.dot(
                        p, vslots[slot, b_i, kv_i],
                        preferred_element_type=jnp.float32))  # (G, D)
                outs.append(jnp.concatenate(per_kv, axis=0)
                            .reshape(1, U))
            o_s[:] = jnp.concatenate(outs, axis=0).astype(o_s.dtype)
            kw = pltpu.make_async_copy(
                kslots.at[slot, :, :, pl.ds(pos, 1), :],
                kh_out.at[layer, :, :, pl.ds(pos, 1), :],
                store_sem.at[0])
            vw = pltpu.make_async_copy(
                vslots.at[slot, :, :, pl.ds(pos, 1), :],
                vh_out.at[layer, :, :, pl.ds(pos, 1), :],
                store_sem.at[1])
            kw.start()
            vw.start()
            kw.wait()
            vw.wait()

        # ---- proj phase: x2[:, c] = x[:, c] + o @ W (+ b) ------------ #
        @pl.when((jj >= proj_lo) & (jj < proj_hi))
        def _():
            c = (jj - proj_lo) * CW
            r = _mm(o_s[:])
            x2_s[:, pl.ds(c, CW)] = xres[:, pl.ds(c, CW)] + \
                _cast_add_bias(r, x2_s.dtype)

            @pl.when(jj == proj_hi - 1)
            def _():
                xn_s[:] = _norm(x2_s[:].astype(jnp.float32), 2, 3
                                ).astype(xn_s.dtype)

        if llama:
            gate_lo, gate_hi = lo["gate"]
            up_lo, up_hi = lo["up"]
            down_lo = lo["down"][0]

            # ---- gate phase: g[:, c] = xn2 @ Wgate ------------------- #
            @pl.when((jj >= gate_lo) & (jj < gate_hi))
            def _():
                @pl.when((jj == gate_lo) & (layer + 1 < NL))
                def _():
                    _load(layer + 1, 1 - slot)
                c = (jj - gate_lo) * CW
                g_s[:, pl.ds(c, CW)] = \
                    _cast_add_bias(_mm(xn_s[:]), g_s.dtype)

            # ---- up phase: h[:, c] = silu(g[:, c]) * (xn2 @ Wup) ----- #
            @pl.when((jj >= up_lo) & (jj < up_hi))
            def _():
                c = (jj - up_lo) * CW
                u_c = _cast_add_bias(_mm(xn_s[:]), h_s.dtype)
                g_c = g_s[:, pl.ds(c, CW)]
                # models/llama.py mlp: g * sigmoid(g) * u, in bf16
                h_s[:, pl.ds(c, CW)] = g_c * jax.nn.sigmoid(g_c) * u_c

            # ---- down phase: y += h[:, c] . W ------------------------ #
            @pl.when(jj >= down_lo)
            def _():
                @pl.when(jj == down_lo)
                def _():
                    yacc[:] = jnp.zeros_like(yacc)
                c = (jj - down_lo) * CW
                yacc[:] += lax.dot_general(
                    h_s[:, pl.ds(c, CW)], _chunk(),
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)

                @pl.when(jj == NC - 1)
                def _():
                    acc = yacc[:]
                    if quant:
                        acc = acc * s2_ref[layer][None, :]
                    y = (acc + b2_ref[layer][None, :]).astype(xres.dtype)
                    xres[:] = x2_s[:] + y

                    @pl.when(j == NL * NC - 1)
                    def _():
                        xo_ref[:] = xres[:]
        else:
            fc1_lo, fc1_hi = lo["fc1"]
            fc2_lo = lo["fc2"][0]

            # ---- fc1 phase ------------------------------------------- #
            @pl.when((jj >= fc1_lo) & (jj < fc1_hi))
            def _():
                @pl.when((jj == fc1_lo) & (layer + 1 < NL))
                def _():
                    _load(layer + 1, 1 - slot)
                c = (jj - fc1_lo) * CW
                # unfused parity: Dense casts the matmul to bf16, adds
                # the bf16 bias, then Activation runs on the bf16 value
                # (_dense_q8 likewise activates AFTER the cdtype cast)
                z = _cast_add_bias(_mm(xn_s[:]), h_s.dtype)
                h_s[:, pl.ds(c, CW)] = act_fn(z).astype(h_s.dtype)

            # ---- fc2 phase: y += h[:, c] . W  (contract lanes) ------- #
            @pl.when(jj >= fc2_lo)
            def _():
                @pl.when(jj == fc2_lo)
                def _():
                    yacc[:] = jnp.zeros_like(yacc)
                c = (jj - fc2_lo) * CW
                yacc[:] += lax.dot_general(
                    h_s[:, pl.ds(c, CW)], _chunk(),
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)

                @pl.when(jj == NC - 1)
                def _():
                    acc = yacc[:]
                    if quant:  # fc2 (U,)-scales apply after the F-sum
                        acc = acc * s2_ref[layer][None, :]
                    y = (acc + b2_ref[layer][None, :]).astype(xres.dtype)
                    xres[:] = x2_s[:] + y

                    @pl.when(j == NL * NC - 1)
                    def _():
                        xo_ref[:] = xres[:]

    return kernel


@functools.partial(
    jax.jit, static_argnames=("NL", "NC", "B", "U", "F", "H", "KV", "D",
                              "T", "CW", "spans", "family", "act",
                              "eps", "quant"))
def _decode_layers(pos, x, wstream, bstream, sstream, norms, bias2, s2,
                   rope_inv, kh, vh, *,
                   NL, NC, B, U, F, H, KV, D, T, CW, spans, family,
                   act, eps, quant):
    kernel = _make_kernel(NL, NC, B, U, F, H, KV, D, T, CW, spans,
                          family, act, eps, quant)
    dtype = x.dtype
    QS = 3 * U if family == "gpt" else U + 2 * KV * D
    s_spec = (pl.BlockSpec((1, CW), lambda j, pos: (j, 0),
                           memory_space=pltpu.VMEM) if quant
              else pl.BlockSpec(memory_space=pltpu.VMEM))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(NL * NC,),
        in_specs=[
            pl.BlockSpec((B, U), lambda j, pos: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, U, CW), lambda j, pos: (j, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, CW), lambda j, pos: (j, 0),
                         memory_space=pltpu.VMEM),
            s_spec,                                  # scales stream
            pl.BlockSpec(memory_space=pltpu.VMEM),   # norms (NL,4,U)
            pl.BlockSpec(memory_space=pltpu.VMEM),   # bias2 (NL,U)
            pl.BlockSpec(memory_space=pltpu.VMEM),   # s2 (NL,U)
            pl.BlockSpec(memory_space=pltpu.VMEM),   # rope inv (1,D)
            pl.BlockSpec(memory_space=pl.ANY),       # k cache
            pl.BlockSpec(memory_space=pl.ANY),       # v cache
        ],
        out_specs=[
            pl.BlockSpec((B, U), lambda j, pos: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        scratch_shapes=[
            pltpu.VMEM((B, U), dtype),               # xres
            pltpu.VMEM((B, QS), dtype),              # qkv
            pltpu.VMEM((B, U), dtype),               # x2
            pltpu.VMEM((B, U), dtype),               # xn
            pltpu.VMEM((B, F), dtype),               # h
            pltpu.VMEM((B, F if family == "llama" else 1), dtype),  # g
            pltpu.VMEM((B, U), jnp.float32),         # yacc
            pltpu.VMEM((B, U), dtype),               # o
            pltpu.VMEM((2, B, KV, T, D), dtype),     # k slots
            pltpu.VMEM((2, B, KV, T, D), dtype),     # v slots
            pltpu.SemaphoreType.DMA((2, 2)),         # load sems
            pltpu.SemaphoreType.DMA((2,)),           # store sems
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, U), dtype),
            jax.ShapeDtypeStruct(kh.shape, kh.dtype),
            jax.ShapeDtypeStruct(vh.shape, vh.dtype),
        ],
        input_output_aliases={9: 1, 10: 2},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=_interpret(),
    )(pos, x, wstream, bstream, sstream, norms, bias2, s2, rope_inv,
      kh, vh)


def decode_step(pos, x, packed, kh, vh, cfg, act, eps):
    """One fused decode step over every layer (both families).

    pos: () or (1,) int32 position; x: (B, U) hidden after embeddings;
    packed: the 6-tuple from the family packer (cw re-derived, int8
    inferred from the stream dtype); kh/vh: stacked (NL, B, KV, T, D)
    caches — returned updated (aliased in place)."""
    import numpy as onp

    wstream, bstream, norms, bias2, sstream, s2 = packed
    NL = norms.shape[0]
    B, U = x.shape
    F = cfg.hidden_size
    H = cfg.num_heads
    KV = getattr(cfg, "num_kv_heads", None) or H
    D = U // H
    T = kh.shape[3]
    family = _family_of(cfg)
    cw, spans = _schedule(cfg)
    NC = sum(n for _, n in spans)
    quant = wstream.dtype == jnp.int8
    if family == "llama":
        base = float(getattr(cfg, "rope_base", 10000.0))
        half = D // 2
        inv_freq = 1.0 / (base ** (
            onp.arange(0, half, dtype=onp.float32) * 2.0 / D))
        rope_inv = jnp.asarray(
            onp.repeat(inv_freq, 2)[None, :], jnp.float32)   # (1, D)
    else:
        rope_inv = jnp.zeros((1, D), jnp.float32)
    pos = jnp.asarray(pos, jnp.int32).reshape(1)
    return _decode_layers(
        pos, x, wstream, bstream, sstream, norms, bias2, s2, rope_inv,
        kh, vh,
        NL=NL, NC=NC, B=B, U=U, F=F, H=H, KV=KV, D=D, T=T, CW=cw,
        spans=tuple(spans), family=family, act=act, eps=float(eps),
        quant=quant)


# back-compat alias (r5 early integration name)
gpt_decode_step = decode_step
