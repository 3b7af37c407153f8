"""KV-cache incremental decoding for transformer-decoder models.

``GPT.generate`` recomputes the full prefix for every new token (O(L²) per
token, one jit program per prefix length — the BucketingModule analog).
``kv_generate`` is the TPU-native decoder: a fixed-shape per-layer K/V
cache updated with ``lax.dynamic_update_slice``, the WHOLE decode loop
(prefill + sampling) compiled as ONE ``lax.scan`` program — no per-token
dispatch, no retraces, O(L) work per token.

Two per-token step implementations share the program skeleton; the
MODEL'S OWN STRUCTURE picks one (``decode_mode``, through the gate
``stacked_decode_supported``) unless the caller's ``stacked=`` says
``"on"`` or ``"off"``:

- **stacked** (wherever the gate passes: a uniform GPT or Llama/GQA
  layer stack): every layer's weights are stacked into ``(NL, ...)``
  arrays (``stack_decode_weights``) and the per-token layer loop is ONE
  ``lax.scan`` over the layer axis — the compiled step contains one
  layer-body's worth of HLO instead of NL unrolled copies.  Portable
  XLA: it lands on CPU CI as well as the TPU.  Covers the
  ``weights="int8"`` stream too (stacked q8 codes ride the scan xs
  through ``q8_matvec``).
- **unrolled**: the per-layer math is DERIVED FROM THE MODEL'S OWN
  BLOCKS (``ln1``/``attn.qkv``/``ffn``/… invoked as Gluon layers on
  traced values via the same swap discipline as ``SPMDTrainer``), so a
  model variant that changes normalization, activation, or bias
  structure inside those sublayers decodes correctly with no decoder
  change.  Only the cache-attention core is decoder-specific math.  It
  is the only step that runs a non-uniform layer stack (and any block
  variant the gate rejects), in both weight modes, and
  ``stacked="off"`` is the reference arm the stacked step is tested
  against (``tests/test_stacked_decode.py``).

Which engine serves what (``decode_engine``, from the model's
``layer_description`` alone): a model whose layers all keep dense K/V
rows gets ``_DecodeEngine`` below; anything else (latent rows, windows,
routed experts) the kind-driven ``layered.LayeredEngine``.
``mxnet_tpu.serve`` runs ``_DecodeEngine``'s PAGED per-slot steps
(``pool_token_paged``, ``chunk_tokens``, ``pool_verify_paged``) over the
stacked weights; ``pool_token``, the same step on dense
``(NL, B, KV, T, D)`` caches, has no caller in the package: it is the
dense reference that ``tests/test_paged_parity.py`` and
``tests/test_paged_attention_kernel.py`` hold the paged steps to.

Decodable protocol — two block families are recognized:
- GPT/_TransformerCell: ``wte``+``wpe`` embeddings, blocks with ``ln1``,
  ``attn`` (fused ``qkv``+``proj``), ``ln2``, ``ffn``;
- Llama: ``wte`` only (RoPE applied per step via the ``rope`` op's
  ``position_offset``), blocks with ``rms1``, ``attn`` (separate
  ``q_proj``/``k_proj``/``v_proj``/``o_proj``, grouped-query kv heads),
  ``rms2``, ``mlp``.
Final norm is ``ln_f``; the head is a ``head``/``lm_head`` Block or the
tied ``wte`` weight.

Reference counterpart: none in-tree (GluonNLP-era beam/sampling ran the
full-prefix path); this is a NEW capability like flash/ring attention.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as onp
from jax import lax

__all__ = ["kv_generate", "decode_mode", "decode_step_program"]

# Trace-time cross-thread serialization — one lock for every
# ``params_swapped`` site (kv_generate, the serving loop, _CachedOp);
# defined next to the swap it guards.
from ..gluon.parameter import _TRACE_LOCK
from ..ops import page_walk
from ..ops import paged_attention as _paged


def _call(layer, *vals):
    """Invoke a Gluon (Hybrid)Block imperatively on traced jax values."""
    from ..gluon.block import _no_hybrid
    from ..ndarray.ndarray import NDArray
    from .. import autograd

    with autograd.pause(train_mode=False), _no_hybrid():
        out = layer(*[v if isinstance(v, NDArray) else NDArray(v)
                      for v in vals])
    return out._data if isinstance(out, NDArray) else out


def _quantize_rows(w):
    """Per-output-channel symmetric int8 quantization: w (out, in) →
    (int8 codes TRANSPOSED to (in, out) for the streaming kernel's
    canonical matmul layout, f32 scales (out,)).  bf16 exactly represents
    every int in [-127, 127], so the in-dot convert loses nothing;
    accumulation runs f32 via ``preferred_element_type``."""
    w32 = w.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(w32), axis=1) / 127.0, 1e-8)
    wq = jnp.round(w32 / s[:, None]).astype(jnp.int8)
    return wq.T.copy(), s


def _quantize_head(w, bias=None):
    """Head quantization with the vocab dim padded to the 128 lane tile:
    GPT-2's 50257 is not a lane multiple, and an unpadded head silently
    falls back to the dequantizing XLA einsum (measured 8x slower than
    bf16) for the LARGEST matmul of every decode step.  Pads codes with
    zeros and scales with 1.0 (padded logits come out 0 and are sliced
    off by the caller, which tracks the true vocab statically); returns
    (codes, scales, bias_or_None)."""
    wq, s = _quantize_rows(w)
    pad = (-wq.shape[1]) % 128
    if pad:
        wq = jnp.pad(wq, ((0, 0), (0, pad)))
        s = jnp.pad(s, (0, pad), constant_values=1.0)
        if bias is not None:
            bias = jnp.pad(bias.astype(jnp.float32), (0, pad))
    return wq, s, bias


# the int8 KV page pair's dtypes — ONE page is (codes, scale-per-
# (layer, head)).  These must agree with the serve operand schema's
# KV_PAGE_INT8 declaration (``mxnet_tpu/serve/schema.py``), which the
# page-pool pricing and ``telemetry_report --check-serve`` consume;
# tests/test_serve_schema.py pins the two equal (decoding cannot
# import serve without a cycle, so the contract is test-held).
_KV_CODE_DTYPE = jnp.int8
_KV_SCALE_DTYPE = jnp.float32


# -- the page pool's device layout --------------------------------------- #
# A K or V pool is ``(NL, NPAGES, page, KV·D)``: one layer's page is one
# contiguous ``(page, KV·D)`` block whose minor dimension fills whole
# 128-lane tiles for every KV·D that is a multiple of 128 (a minor
# dimension of D = 64 made the chip keep the pool NPAGES-minor and re-lay
# it out for every consumer, PERF.md PR 27).  An int8 pool's codes take the
# same layout and its scales are ``(NL, NPAGES, KV)``.  Two access idioms
# keep every program in place on the donated pools: READ pages with one
# gather at ``(layer, page id)`` out of the whole pool, WRITE with a scatter
# at explicit ``(layer, page id[, row])`` indices — the layer is always an
# index, never a window dimension or a scan-sliced axis.

def _layer_ids(arr, ids):
    """``arange(NL)`` shaped to broadcast against page ids ``ids``, whose
    own leading axis is the layer axis (``ids[None]`` or ``(NL, ...)``)."""
    return jnp.arange(arr.shape[0]).reshape((-1,) + (1,) * (ids.ndim - 1))


def _pages_get(arr, pg, layer=None):
    """Pages ``pg`` (any int shape) of a pool array out of the WHOLE
    array: ``(*pg.shape, *arr.shape[2:])`` for one ``layer`` (a traced
    scalar), ``(NL, *pg.shape, ...)`` for every layer.  The table
    sentinel ``NPAGES`` is CLAMPED onto the last page, in bounds, not
    filled with zeros: every reader masks by position (``idx <= pos``
    gives weight exactly 0 to what the sentinel stands for, as it does to
    the stale columns of a frontier page) or DROPS what it built from the
    gather on the way back, so the values behind a sentinel never reach a
    sum or the pool."""
    pg = jnp.minimum(pg, arr.shape[1] - 1)
    if layer is None:
        pg = pg[None]
        layer = _layer_ids(arr, pg)
    return arr.at[layer, pg].get(mode="promise_in_bounds")


def _pages_set(arr, pg, vals):
    """Whole pages ``pg`` of every layer := ``vals`` ``(NL, *pg.shape,
    ...)``; sentinel ids DROP (a retired slot cannot touch a freed
    page)."""
    pg = pg[None]
    return arr.at[_layer_ids(arr, pg), pg].set(vals, mode="drop")


def _rows_set(arr, pg, off, rows):
    """Token rows ``(pg, off)`` of every layer := ``rows`` ``(NL,
    *pg.shape, KV·D)``; sentinel ids DROP."""
    pg, off = pg[None], off[None]
    return arr.at[_layer_ids(arr, pg), pg, off].set(rows, mode="drop")


def _kv_lanes(scales, lanes):
    """Per-(page, head) scales ``(..., KV)`` -> ``(..., 1, KV·D)``: one
    per lane of the page's rows."""
    return jnp.repeat(scales, lanes // scales.shape[-1],
                      axis=-1)[..., None, :]


def _kv_dequant(codes, scales, dtype):
    """Int8 KV page codes ``(..., page, KV·D)`` -> ``dtype`` values:
    ``codes * scale`` with the per-page-per-head f32 scale ``(..., KV)``
    broadcast over the page's rows and the head's D lanes."""
    return (codes.astype(_KV_SCALE_DTYPE)
            * _kv_lanes(scales, codes.shape[-1])).astype(dtype)


def _kv_requant(vals, floor_scales, kv):
    """Symmetric int8 quantization of pages ``vals`` ``(..., page,
    KV·D)``, one scale per page and each of its ``kv`` K/V heads (the
    maximum over the page's rows and the head's D lanes), with the new
    scale FLOORED at the page's previous scale ``(..., KV)`` (pass
    ``0.0`` for fresh pages).  The floor
    is what keeps the read-modify-write page rewrites lossless for
    untouched columns: when a new column does not raise the page's
    dynamic range the scale is unchanged and every existing code
    round-trips to itself exactly (``round(c * s / s) == c``) — zero
    drift over the up-to-``page`` step rewrites a frontier page sees.
    When the range DOES grow, the whole page re-rounds at the coarser
    scale, exactly what a one-shot quantization of the final page
    contents would have produced.  Two preconditions keep the ratchet
    honest (both documented in PARITY.md):

    - a page must enter a slot's reservation with a ZERO scale — the
      serving admission/chunk executables scale-reset every freshly
      allocated page (a zero scale dequantizes a recycled page's stale
      codes to exact zeros), so the floor can never inherit a previous
      tenant's dynamic range;
    - speculative verify quantizes drafted columns BEFORE acceptance
      is known, so a rejected draft's magnitude can ratchet its page's
      scale (see ``_kv_verify_rmw``) — the one case where the final
      scale may be coarser than one-shot quantization of the surviving
      contents."""
    v32 = vals.astype(_KV_SCALE_DTYPE)
    heads = v32.reshape(v32.shape[:-1] + (kv, v32.shape[-1] // kv))
    amax = jnp.max(jnp.abs(heads), axis=(-3, -1))
    s = jnp.maximum(jnp.maximum(amax / 127.0, floor_scales), 1e-8)
    codes = jnp.round(v32 / _kv_lanes(s, v32.shape[-1])) \
        .astype(_KV_CODE_DTYPE)
    return codes, s


@jax.named_scope("mx.paged_view")
def _paged_rows(pool, pt, layer, dtype):
    """Layer ``layer``'s pages ``pt`` ``(..., MAXP)`` of a K or V pool as
    token rows ``(..., T, KV·D)``: ONE gather at ``(layer, page id)``
    straight out of the whole pool, whose ``(..., MAXP, page, KV·D)``
    result IS the row view (``t = j * page + o``) — no per-layer slice of
    the pool, no transpose.  An int8 pool, a ``(codes, scales)`` pair,
    dequantizes to ``dtype`` in the same gather."""
    if isinstance(pool, tuple):
        g = _kv_dequant(_pages_get(pool[0], pt, layer),
                        _pages_get(pool[1], pt, layer), dtype)
    else:
        g = _pages_get(pool, pt, layer)
    return g.reshape(pt.shape[:-1] + (-1, g.shape[-1]))


def _kv_step_rmw(pool, pg, offs, newrow):
    """Requantizing single-row page rewrite for the paged pool STEP:
    gather each slot's frontier page ``pg[b]`` of every layer (codes +
    scale), dequantize, land slot ``b``'s new K or V row at page offset
    ``offs[b]``, re-quantize with the old scale as floor, and scatter
    codes+scales back (``mode="drop"``: a retired lane's sentinel page
    id cannot touch a freed page).  ``newrow`` is ``(NL, B, KV·D)``.
    Write pages are exclusively owned (COW guarantees the shared prefix
    never holds a slot's write frontier), so the whole-page scatter
    never races another slot."""
    codes, scales = pool
    old_s = _pages_get(scales, pg)                       # (NL, B, KV)
    vals = _kv_dequant(_pages_get(codes, pg), old_s,
                       jnp.float32)                # (NL, B, page, KV·D)
    vals = vals.at[:, jnp.arange(pg.shape[0]), offs].set(
        newrow.astype(jnp.float32))
    q, s = _kv_requant(vals, old_s, scales.shape[-1])
    return _pages_set(codes, pg, q), _pages_set(scales, pg, s)


def _kv_chunk_rmw(pool, wpgs, loc, new_rows):
    """Requantizing page-WINDOW rewrite for ``chunk_tokens``: the
    chunk's ``C`` consecutive positions touch at most ``ntp``
    consecutive pages of one slot's row.  Gather the window,
    dequantize, land the chunk rows at their window-local offsets
    ``loc`` (out-of-window entries DROP — bucket-padded tails and
    positions past the cache horizon never land), re-quantize each
    window page with its old scale as floor, scatter back.  ``new_rows``
    is ``(NL, C, KV·D)``."""
    codes, scales = pool
    old_s = _pages_get(scales, wpgs)                     # (NL, NTP, KV)
    win = _kv_dequant(_pages_get(codes, wpgs), old_s,
                      jnp.float32)               # (NL, NTP, page, KV·D)
    flat = win.reshape(win.shape[0], -1, win.shape[-1])  # window rows
    flat = flat.at[:, loc].set(new_rows.astype(jnp.float32), mode="drop")
    q, s = _kv_requant(flat.reshape(win.shape), old_s, scales.shape[-1])
    return _pages_set(codes, wpgs, q), _pages_set(scales, wpgs, s)


def _kv_verify_rmw(pool, wpgs, loc, new_rows):
    """Requantizing per-slot page-window rewrite for
    ``pool_verify_paged``: like ``_kv_chunk_rmw`` batched over slots —
    slot ``b``'s block touches window pages ``wpgs[b]`` with
    window-local row offsets ``loc[b]``.  Slots' write windows are
    disjoint (every window page belongs to its slot's reserved,
    exclusively-owned range), so the batched whole-page scatter never
    collides.  ``new_rows`` is ``(NL, B, C, KV·D)``.

    Known deviation (documented in PARITY.md): all ``C`` drafted
    columns quantize here BEFORE acceptance is known.  Rejection rolls
    ``pos`` back — the garbage columns become unreachable and are
    overwritten by later writes at the same positions — but a rejected
    draft's magnitude has already ratcheted the page scale via the
    monotone floor, so subsequently accepted tokens on that page can
    quantize coarser than a one-shot quantization of the surviving
    contents.  Accepted-column error still respects the per-write
    ``scale/2`` code-step bound; the end-to-end effect is covered by
    the pinned greedy-agreement tolerance."""
    codes, scales = pool
    old_s = _pages_get(scales, wpgs)                  # (NL, B, NTP, KV)
    win = _kv_dequant(_pages_get(codes, wpgs), old_s,
                      jnp.float32)            # (NL, B, NTP, page, KV·D)
    NL, B = win.shape[:2]
    flat = win.reshape(NL, B, -1, win.shape[-1])         # window rows
    flat = flat.at[:, jnp.arange(B)[:, None], loc].set(
        new_rows.astype(jnp.float32), mode="drop")
    q, s = _kv_requant(flat.reshape(win.shape), old_s, scales.shape[-1])
    return _pages_set(codes, wpgs, q), _pages_set(scales, wpgs, s)


def _rope_rows(t, base, offset):
    """Rotary embedding of ``(B, C, heads, D)`` token rows at absolute
    positions ``offset + j`` (``offset`` a scalar or per-row ``(B,)``);
    the ``rope`` op itself takes ``(B, heads, L, D)``."""
    from ..ops.attention import rope

    return rope.__wrapped__(t.transpose(0, 2, 1, 3), base=base,
                            position_offset=offset).transpose(0, 2, 1, 3)


def _flat_attention(q, kc, vc, mask, scale, cdtype):
    """Attention of ``q`` ``(B, C, H, D)`` (C query positions a row)
    against K and V views in the pool's own row layout, ``(B, T, KV·D)``,
    without a transposed or head-split copy of either view: the heads of
    a row lie side by side in its lanes, so the queries are spread
    block-diagonally over them — ``Qb[b, k·D + d, (c, k', g)]`` is
    ``q[b, c, k'·G + g, d]`` where ``k' == k`` and 0 elsewhere — and one
    contraction over the whole row gives every head's scores at once
    (the same products as the per-head einsum plus exact zeros, float32
    accumulation).  ``p @ V`` over whole rows likewise gives ``(B, C·H,
    KV·D)``, of which each head keeps its own D lanes.  Both read their
    view once, as it is stored.  ``mask`` ``(B or 1, C, T)`` is true
    where a query may look; mask, scale, the float32 softmax and the
    cast of ``p`` to ``cdtype`` are the dense step's.  Returns ``(B, C,
    H·D)``."""
    B, C, H, D = q.shape
    T, F = kc.shape[1], kc.shape[2]
    KV = F // D
    G = H // KV
    own = jnp.eye(KV, dtype=jnp.bool_)                   # head k' == k
    qg = q.reshape(B, C, KV, G, D).transpose(0, 2, 4, 1, 3)
    qb = jnp.where(own[None, :, None, None, :, None],    # (B,KV,D,C,KV,G)
                   qg[:, :, :, :, None, :], 0).reshape(B, F, C * H)
    s = jnp.einsum("bfm,btf->bmt", qb, kc,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask[:, :, None, :], s.reshape(B, C, H, T), -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(cdtype)
    o = jnp.einsum("bmt,btf->bmf", p.reshape(B, C * H, T), vc)
    o = jnp.where(own[None, None, :, None, :, None],
                  o.reshape(B, C, KV, G, KV, D), 0).sum(axis=4)
    return o.reshape(B, C, H * D)


def _gpt_act_type(model):
    """fc1 activation of the first block (None for a linear fc1 — and
    for FFN variants without the fc1/act structure: the unrolled path
    calls the whole ffn Block and never needs the act type, so an
    unrecognized shape must not break the generality fallback)."""
    try:
        fc1 = model.blocks[0].ffn.fc1
        act = fc1.act
    except AttributeError:
        return None
    return getattr(act, "_act_type", None) if act is not None else None


def _check_args(prefill, weights, stacked):
    """Shared argument validation — runs even on the max_new_tokens<=0
    early return so a typo fails fast in 0-token smoke calls."""
    if prefill not in ("batched", "scan"):
        raise ValueError(f"prefill must be 'batched' or 'scan', "
                         f"got {prefill!r}")
    if weights not in ("native", "int8"):
        raise ValueError(f"weights must be 'native' or 'int8', "
                         f"got {weights!r}")
    if stacked not in ("auto", "on", "off"):
        raise ValueError(f"stacked must be 'auto', 'on' or 'off', "
                         f"got {stacked!r}")


def _family_tables(is_llama):
    """THE per-family slot maps — projection layers and stacked norm
    params, keyed by the slot names the scan body reads.  Every consumer
    (``_layer_weight_srcs`` cache pinning, ``_build_q8`` unrolled codes,
    ``_build_q8_stacked`` scan xs) derives from these two dicts, so a
    new projection or a third block family is a one-place edit."""
    if is_llama:
        proj = {"q": lambda blk: blk.attn.q_proj,
                "k": lambda blk: blk.attn.k_proj,
                "v": lambda blk: blk.attn.v_proj,
                "o": lambda blk: blk.attn.o_proj,
                "gate": lambda blk: blk.mlp.gate,
                "up": lambda blk: blk.mlp.up,
                "down": lambda blk: blk.mlp.down}
        norms = {"rms1_g": lambda blk: blk.rms1.gamma,
                 "rms2_g": lambda blk: blk.rms2.gamma}
    else:
        proj = {"qkv": lambda blk: blk.attn.qkv,
                "proj": lambda blk: blk.attn.proj,
                "fc1": lambda blk: blk.ffn.fc1,
                "fc2": lambda blk: blk.ffn.fc2}
        norms = {"ln1_g": lambda blk: blk.ln1.gamma,
                 "ln1_b": lambda blk: blk.ln1.beta,
                 "ln2_g": lambda blk: blk.ln2.gamma,
                 "ln2_b": lambda blk: blk.ln2.beta}
    return proj, norms


def _layer_weight_srcs(model, is_llama):
    """Pinned strong refs to every per-layer weight/bias/norm array —
    the cache-invalidation key of the stacked export: a train step
    rebinds parameter arrays, so comparing these by ``is`` detects
    staleness without hashing (and without the recycled-``id()`` hazard
    documented at the q8 cache)."""
    proj, norms = _family_tables(is_llama)
    srcs = []
    for blk in model.blocks:
        for get in proj.values():
            lyr = get(blk)
            srcs.append(lyr.weight.data()._data)
            if getattr(lyr, "bias", None) is not None:
                srcs.append(lyr.bias.data()._data)
        for get in norms.values():
            srcs.append(get(blk).data()._data)
    return srcs


def _pinned_cache(model, attr, srcs, build):
    """Source-pinned model cache: rebuild ``build()`` whenever any source
    array was rebound (compared by ``is`` against pinned strong refs)."""
    cache = model.__dict__.setdefault(attr, {})
    cached = cache.get("srcs")
    if cached is None or len(cached) != len(srcs) or \
            not all(a is b for a, b in zip(cached, srcs)):
        cache["srcs"] = srcs
        cache["val"] = build()
    return cache["val"]


def stack_decode_weights(blocks):
    """Stack every block's ``decode_layer_arrays`` export into one
    (NL, ...) array per slot — the operand set of the stacked-layer
    ``lax.scan`` step: each slot rides the scan's xs axis, so the
    compiled step contains ONE layer-body's worth of HLO instead of NL
    unrolled copies.  Callers cache the result pinned on the source
    arrays (``_pinned_cache``: a train step rebinds parameter arrays and
    triggers restacking)."""
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
    from ..base import MXNetError
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
    except (AttributeError, TypeError, MXNetError):
        # a structurally different variant, or un-materialized params
        # (``Parameter.data()`` raises MXNetError)
        return False
    return True


def decode_mode(model, weights="native", stacked="auto"):
    """Select the per-token step implementation ``kv_generate`` will run.

    Returns ``"stacked"`` | ``"unrolled"``.  ``stacked="auto"`` takes the
    stacked-layer scan whenever ``stacked_decode_supported(model)`` — for
    both ``weights`` modes (the int8 stream stacks its q8 codes);
    ``"on"`` requires it and raises ``MXNetError`` when the model is not
    stackable; ``"off"`` never takes it (the unrolled reference arm)."""
    from ..base import MXNetError

    _check_args("batched", weights, stacked)
    if stacked == "on":
        if not stacked_decode_supported(model):
            raise MXNetError(
                "stacked='on' but this model's layer stack cannot be "
                "stacked (non-uniform geometry/eps/activation or an "
                "unrecognized block family — see models/decoding.py "
                "stacked_decode_supported)")
        return "stacked"
    if stacked == "auto" and stacked_decode_supported(model):
        return "stacked"
    return "unrolled"


def layer_description(model):
    """The per-layer description the serving engine consumes: for each
    layer its attention kind, feed-forward kind and cache kind
    (``serve.schema.POOL_ROWS`` declares what a cache kind stores).  A
    model exports its own through ``decode_description()``; GPT and Llama
    are every layer alike, dense K/V attention under the main page table,
    and stay on the stacked-layer scan of ``_DecodeEngine``."""
    fn = getattr(model, "decode_description", None)
    if fn is not None:
        return fn()
    is_llama = hasattr(model.blocks[0], "rms1")
    return [{"attn": {"kind": "mha"},
             "ffn": {"kind": "swiglu" if is_llama else "mlp"},
             "cache": "kv"} for _ in model.blocks]


def decode_engine(model, B, P, total, temperature, top_k, prefill,
                  weights, stacked):
    """The engine that serves ``model``, chosen by its description alone:
    plain multi-head attention over the uniform K/V kind has the stacked
    scan, everything else the kind-driven ``layered.LayeredEngine``."""
    if all(d["cache"] == "kv" and d["attn"]["kind"] == "mha"
           for d in layer_description(model)):
        return _DecodeEngine(model, B, P, total, temperature, top_k,
                             prefill, weights, stacked)
    from .layered import LayeredEngine
    return LayeredEngine(model, B, P, total, temperature, top_k, prefill,
                         weights)


class _DecodeEngine:
    """Per-call decode program builder: family/geometry detection, weight
    preparation (q8 codes / stacked arrays — all cached on
    the model pinned to their source arrays, all riding as TRACED
    ARGUMENTS so weight updates never invalidate the compiled program),
    and the per-token step bodies the jitted ``run`` composes."""

    def __init__(self, model, B, P, total, temperature, top_k, prefill,
                 weights, stacked):
        with _TRACE_LOCK:
            self._init(model, B, P, total, temperature, top_k, prefill,
                       weights, stacked)

    def _init(self, model, B, P, total, temperature, top_k, prefill,
              weights, stacked):
        cfg = model._cfg
        self.model = model
        self.cfg = cfg
        self.B, self.P, self.total = B, P, total
        self.temperature, self.top_k = temperature, top_k
        self.prefill = prefill
        self.H = cfg.num_heads
        self.U = cfg.units
        self.D = self.U // self.H
        # family detection (see module docstring): Llama cells carry
        # separate projections + RoPE and may use fewer kv heads (GQA)
        self.is_llama = hasattr(model.blocks[0], "rms1")
        self.KV = getattr(cfg, "num_kv_heads", self.H) if self.is_llama \
            else self.H
        self.rope_base = float(getattr(cfg, "rope_base", 10000.0))
        _check_args(prefill, weights, stacked)
        self.use_int8 = weights == "int8"

        # weights ride as TRACED ARGUMENTS (swap discipline shared with
        # SPMDTrainer._forward_loss): updates to the model do NOT
        # invalidate the compiled decode program
        self.params = [p for p in model.collect_params().values()
                       if p._data is not None]
        self.param_vals = [p._data._data for p in self.params]
        self.NL = len(model.blocks)
        self.cdtype = model.wte.weight.data()._data.dtype
        self.scale = 1.0 / (self.D ** 0.5)
        self.head = getattr(model, "head", None) or \
            getattr(model, "lm_head", None)
        if self.is_llama:
            self.act_t = None
            self.norm_eps = (
                float(getattr(model.blocks[0].rms1, "_eps", 1e-6)),
                float(getattr(model.blocks[0].rms2, "_eps", 1e-6)))
        else:
            self.act_t = _gpt_act_type(model)
            self.norm_eps = (
                float(getattr(model.blocks[0].ln1, "_eps", 1e-5)),
                float(getattr(model.blocks[0].ln2, "_eps", 1e-5)))

        self.mode = decode_mode(model, weights, stacked)
        self.q8v = self.sw = None
        if self.mode == "stacked":
            if self.use_int8:
                # int8 stacked: the scan streams per-layer q8 codes as
                # xs; only the LM head rides through the q8v operand
                sq8 = self._build_q8_stacked()
                self.sw = {k: v for k, v in sq8.items() if k != "head"}
                self.q8v = {"head": sq8["head"]}
                self.head_vocab = self._head_vocab()
            else:
                self.sw = _pinned_cache(
                    model, "_stacked_decode_cache",
                    _layer_weight_srcs(model, self.is_llama),
                    model.stacked_decode_weights)
        if self.use_int8 and self.q8v is None:
            self.q8v = self._build_q8()

    # -- weight preparation -------------------------------------------- #
    def _head_arrays(self):
        """(head weight (V, U), head bias or None) — the tied ``wte``
        weight when the model has no separate head Block."""
        head = self.head
        head_w = (head.weight if head is not None
                  else self.model.wte.weight).data()._data
        head_b = None
        if head is not None and getattr(head, "bias", None) is not None:
            head_b = head.bias.data()._data
        return head_w, head_b

    def _head_vocab(self):
        return int(self._head_arrays()[0].shape[0])

    def _build_q8(self):
        """int8 weight streaming: quantize the decode matmul weights.
        Codes are cached keyed on the SOURCE ARRAYS THEMSELVES (weights
        AND biases), compared by ``is`` against pinned strong refs — a
        train step rebinds the arrays and triggers requantization, while
        repeated generate calls reuse the codes.  Pinning the sources
        (not id() snapshots) is load-bearing: freed buffer addresses get
        recycled by CPython, so an id()-keyed cache can silently serve
        stale codes after an update."""
        model = self.model
        head_w, head_b = self._head_arrays()
        self.head_vocab = int(head_w.shape[0])
        proj, _ = _family_tables(self.is_llama)
        lyr_tabs = [{k: get(blk) for k, get in proj.items()}
                    for blk in model.blocks]
        srcs = [l.weight.data()._data for t in lyr_tabs
                for l in t.values()]
        srcs += [l.bias.data()._data for t in lyr_tabs
                 for l in t.values()
                 if getattr(l, "bias", None) is not None]
        srcs.append(head_w)
        if head_b is not None:
            srcs.append(head_b)

        def _q(lyr):
            wq, s = _quantize_rows(lyr.weight.data()._data)
            b = None
            if getattr(lyr, "bias", None) is not None:
                b = lyr.bias.data()._data
            return (wq, s, b)

        return _pinned_cache(
            model, "_q8_weight_cache", srcs,
            lambda: {
                "blocks": [{k: _q(l) for k, l in t.items()}
                           for t in lyr_tabs],
                "head": _quantize_head(head_w, head_b),
            })

    def _build_q8_stacked(self):
        """int8 codes for the STACKED scan: every projection's per-layer
        (in, out) codes / (out,) scales / biases stacked to (NL, ...)
        arrays that ride the layer scan's xs, next to the stacked norm
        rows (same slot names as the native stack so the scan body
        shares its norm code).  Missing biases stack as zeros (adding
        f32 0 is exact, matching the unrolled path's no-bias add) unless
        the whole family is bias-free (Llama), where the slot is
        dropped.  Cached pinned on the layer+head source arrays — the
        same rebind-invalidation discipline as ``_build_q8``."""
        model = self.model
        head_w, head_b = self._head_arrays()
        srcs = _layer_weight_srcs(model, self.is_llama) + [head_w]
        if head_b is not None:
            srcs.append(head_b)

        def _build():
            kinds, norms = _family_tables(self.is_llama)
            out = {}
            for kind, get in kinds.items():
                qs, ss, bs = [], [], []
                any_bias = any(getattr(get(blk), "bias", None) is not None
                               for blk in model.blocks)
                for blk in model.blocks:
                    lyr = get(blk)
                    wq, s = _quantize_rows(lyr.weight.data()._data)
                    qs.append(wq)
                    ss.append(s)
                    if any_bias:
                        b = lyr.bias.data()._data \
                            if getattr(lyr, "bias", None) is not None \
                            else jnp.zeros((wq.shape[1],), self.cdtype)
                        bs.append(b)
                out[kind] = (jnp.stack(qs), jnp.stack(ss),
                             jnp.stack(bs) if any_bias else None)
            for name, get in norms.items():
                out[name] = jnp.stack(
                    [get(blk).data()._data for blk in model.blocks])
            out["head"] = _quantize_head(head_w, head_b)
            return out

        return _pinned_cache(model, "_q8_stacked_cache", srcs, _build)

    # -- step bodies ---------------------------------------------------- #
    def _dense_q8(self, x, ent, act_type=None):
        """Weight-only int8 matvec via the Pallas streaming kernel: int8
        codes convert to bf16 IN VMEM (exact for |code| ≤ 127), f32 MXU
        accumulation, per-channel rescale."""
        from ..ops.q8_matvec import q8_matvec
        from ..ops.registry import get_op
        wq, s, b = ent
        y = q8_matvec(x, wq, s, b).astype(self.cdtype)
        if act_type:
            y = get_op("Activation").fn(y, act_type=act_type)
        return y

    def _sample_logits(self, logits):
        """Shared temperature/top_k logits preparation — ``None`` means
        greedy (argmax).  The batch sampler and the serving per-slot
        sampler (``serve.engine.PoolPrograms._sample_slots``) both draw
        from THIS prep, so a sampler tweak (e.g. top_p) lands in the
        offline and served streams together — the parity contract."""
        temperature, top_k = self.temperature, self.top_k
        if temperature == 0.0:
            return None
        # temperature is a python-scalar closure capture, not an operand:
        # the cast folds at trace time (no suppression needed — the jit
        # seeds here close over the engine, so this is host-side prep)
        lg = logits / max(float(temperature), 1e-6)
        if top_k and top_k < lg.shape[-1]:
            kth = jax.lax.top_k(lg, top_k)[0][:, -1]
            lg = jnp.where(lg < kth[:, None], -jnp.inf, lg)
        return lg

    def _sample(self, logits, t, key0):
        lg = self._sample_logits(logits)
        if lg is None:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(
            jax.random.fold_in(key0, t), lg, axis=-1).astype(jnp.int32)

    def _head_logits(self, xl, q8):
        """ln_f output (B, U) → f32 logits (B, V); shared by every step
        body and the batched prefill tail."""
        model, head = self.model, self.head
        if q8 is not None:
            from ..ops.q8_matvec import q8_matvec
            hwq, hs, hb = q8["head"]
            # slice the 128-padded vocab back down; the true vocab is a
            # STATIC closure value (an int in the traced pytree would
            # arrive as a tracer and break the slice)
            return q8_matvec(xl, hwq, hs, hb)[:, :self.head_vocab]
        if head is not None:
            return _call(head, xl).astype(jnp.float32)
        w = model.wte.weight.data()._data                 # traced (swap)
        return (xl @ w.T).astype(jnp.float32)

    def _embed(self, x_tok, pos):
        x = _call(self.model.wte, x_tok)
        if not self.is_llama:
            x = x + _call(self.model.wpe,
                          jnp.broadcast_to(pos, (self.B,)))
        return x

    def one_token(self, x_tok, pos, ck, cv, q8=None):
        """x_tok (B,) int32 at position pos -> (logits (B,V), new caches).
        ck/cv: (NL, B, KV, maxT, D).  All layer math comes from the
        model's own sublayers; only the cached-attention core (and RoPE
        application for Llama) is inlined — the generality fallback (and
        the int8 path): decodes any block variant, at NL unrolled copies
        of the layer body in the compiled step."""
        from ..ops.attention import rope as _rope

        model = self.model
        B, U, H, KV, D = self.B, self.U, self.H, self.KV, self.D
        is_llama, cdtype = self.is_llama, self.cdtype

        x = self._embed(x_tok, pos)
        idx = lax.broadcasted_iota(jnp.int32, (1, 1, self.total), 2)
        for i, blk in enumerate(model.blocks):
            # one copy of the projection math for both weight modes
            def _lin(layer, kind, h):
                return self._dense_q8(h, q8["blocks"][i][kind]) \
                    if q8 is not None else _call(layer, h)

            if is_llama:
                h = _call(blk.rms1, x)
                q = _lin(blk.attn.q_proj, "q", h).reshape(B, H, 1, D)
                k = _lin(blk.attn.k_proj, "k", h).reshape(B, KV, 1, D)
                v = _lin(blk.attn.v_proj, "v", h).reshape(B, KV, 1, D)
                q = _rope.__wrapped__(q, base=self.rope_base,
                                      position_offset=pos)
                k = _rope.__wrapped__(k, base=self.rope_base,
                                      position_offset=pos)
            else:
                h = _call(blk.ln1, x)
                qkv = self._dense_q8(h, q8["blocks"][i]["qkv"]) \
                    if q8 is not None \
                    else _call(blk.attn.qkv, h)               # (B, 3U)
                q, k, v = (qkv[:, j * U:(j + 1) * U].reshape(B, H, 1, D)
                           for j in range(3))
            ck = lax.dynamic_update_slice(ck, k[None], (i, 0, 0, pos, 0))
            cv = lax.dynamic_update_slice(cv, v[None], (i, 0, 0, pos, 0))
            kc, vc = ck[i], cv[i]                             # (B,KV,T,D)
            # grouped einsums contract q's head groups directly against
            # the KV-head cache — no materialized H-head repeat (the GQA
            # memory-bandwidth benefit is the point of the small cache)
            qg = q.reshape(B, KV, H // KV, D)
            s = jnp.einsum("bkgd,bktd->bkgt", qg, kc,
                           preferred_element_type=jnp.float32) * self.scale
            s = jnp.where(idx[:, :, None] <= pos, s, -1e30)   # (B,KV,G,T)
            p = jax.nn.softmax(s, axis=-1).astype(cdtype)
            o = jnp.einsum("bkgt,bktd->bkgd", p, vc).reshape(B, U)
            if is_llama:
                x = x + _lin(blk.attn.o_proj, "o", o)
                h2 = _call(blk.rms2, x)
                if q8 is not None:
                    # SwiGLU decomposed: down(silu(gate)·up), matching
                    # models/llama.py (the native arm calls the whole
                    # mlp Block so model variants keep working)
                    g = _lin(blk.mlp.gate, "gate", h2)
                    u = _lin(blk.mlp.up, "up", h2)
                    x = x + _lin(blk.mlp.down, "down",
                                 g * jax.nn.sigmoid(g) * u)
                else:
                    x = x + _call(blk.mlp, h2)
            elif q8 is not None:
                x = x + self._dense_q8(o, q8["blocks"][i]["proj"])
                h2 = _call(blk.ln2, x)
                x = x + self._dense_q8(
                    self._dense_q8(h2, q8["blocks"][i]["fc1"],
                                   self.act_t),
                    q8["blocks"][i]["fc2"])
            else:
                x = x + _call(blk.attn.proj, o)
                x = x + _call(blk.ffn, _call(blk.ln2, x))
        xl = _call(model.ln_f, x)
        return self._head_logits(xl, q8), ck, cv

    def stacked_token(self, x_tok, pos, ck, cv, sw, q8=None):
        """one_token's stacked twin — THE op-count collapse: the layer
        loop is ONE ``lax.scan`` over the (NL, ...) stacked weights
        (``sw``), with the per-layer K/V cache slices riding the scan's
        xs and the two new cache columns coming back as ys (written into
        the carried caches with ONE dynamic_update_slice each).  The
        body dispatches the IDENTICAL op functions the model's sublayers
        dispatch (FullyConnected / LayerNorm / RMSNorm / Activation /
        rope, same arguments), so greedy and sampled token streams match
        the unrolled path.  With ``weights='int8'`` the xs carry stacked
        q8 codes/scales instead and every projection runs ``q8_matvec``
        (the same kernel and cast order as the unrolled q8 path, so int8
        stacked matches int8 unrolled token-for-token).  Compiled cost:
        one layer-body of HLO + the embed/head/sample tail, ~5x under
        the unrolled step's op count at GPT-2-small depth
        (benchmark/decode_bench.py ops/step)."""
        return self._scan_token(x_tok, pos, ck, cv, sw, q8,
                                per_slot=False)

    def pool_token(self, x_tok, pos, ck, cv, sw, q8=None):
        """stacked_token with PER-ROW positions on DENSE caches — the
        reference of the paged serving steps (no caller in the package;
        see the module docstring): every batch row is an independent
        sequence at its own depth ``pos[b]``, so the attention mask,
        rotary angles and cache-column writes are per-slot (the writes
        are scatters at ``(b, pos[b])`` instead of one
        dynamic_update_slice).  Retired slots keep computing (masked by
        the caller) — their cache writes land at their stale position
        and are overwritten on admission, so no branch, no retrace, no
        host sync."""
        return self._scan_token(x_tok, pos, ck, cv, sw, q8,
                                per_slot=True)

    def pool_token_paged(self, x_tok, pos, kp, vp, pt, page, sw, q8=None):
        """pool_token against a PAGED pool (``mxnet_tpu.serve``): the
        caches are page pools ``(NL, NPAGES, page, KV·D)`` (one layer's
        page a contiguous, lane-dense block of token rows; int8 pools a
        ``(codes, scales (NL, NPAGES, KV))`` pair) and each
        slot reads/writes them through its page-table row ``pt[b]``
        (``pt``: (B, MAXP) int32, a TRACED operand — allocation churn
        changes table VALUES, never shapes, so no retrace).  The layer
        scan runs over the layer NUMBER with the pools closed over.
        Where ``walks_pages`` holds (a native pool of whole tiles) and
        the step is lowered for a TPU, layer ``l`` WALKS each slot's
        pages at ``(l, page id)`` only as far as its length, inside
        ``ops/paged_attention.py``'s kernel, the new token's K and V
        beside them as operands.  Otherwise (an int8 pool, other
        shapes, another platform) layer ``l`` gathers its pages
        straight out of the whole pool into the ``(B, T, KV·D)`` row
        view, the new row lands at ``(b, pos[b])`` and attention
        contracts the stored rows (``_flat_attention``).  After the
        scan all layers' new rows scatter at ``(l, page, row)`` — in
        place on the donated pools.  Rows of retired/idle slots hold
        the one-past-the-end sentinel ``NPAGES``: their scatters DROP —
        a freed page can never be corrupted by a slot that no longer
        owns it — their walk is empty, and their gathers clamp onto the
        last page, read garbage and are masked by the caller, as a live
        slot's columns past ``pos`` (the stale tail of its frontier
        page, a sentinel entry) are by the position mask: weight
        exactly 0.  Token order is ``t = j * page + o``
        (page-major); the logits match ``pool_token``'s within float32
        summation order and greedy streams token for token
        (``tests/test_paged_parity.py``)."""
        return self._scan_token(x_tok, pos, kp, vp, sw, q8,
                                per_slot=True, pages=(pt, page))

    def walks_pages(self, page, quant=False):
        """Whether the single-query paged step WALKS each slot's pages as
        far as its length (``ops/paged_attention.py``, on a TPU) or builds
        the T-wide view: a static property of the pool — native dtype, rows
        and pages of whole tiles."""
        return not quant and _paged.supports(
            self.KV * self.D, self.cdtype, page, self.H, self.D)

    # what no inner scope names is ``mx.dense`` (an operation's region is
    # its INNERMOST ``mx.*`` scope): the embeddings here
    @jax.named_scope("mx.dense")
    def _scan_token(self, x_tok, pos, ck, cv, sw, q8, per_slot,
                    pages=None):
        from ..ops.attention import rope as _rope
        from ..ops.registry import get_op

        _fc = get_op("FullyConnected").fn
        _ln = get_op("LayerNorm").fn
        _rms = get_op("RMSNorm").fn
        _act = get_op("Activation").fn
        B, U, H, KV, D = self.B, self.U, self.H, self.KV, self.D
        llama, cdtype = self.is_llama, self.cdtype
        int8 = self.use_int8
        eps1, eps2 = self.norm_eps
        act_t, scale, rope_base = self.act_t, self.scale, self.rope_base
        # the unrolled q8 path's matvec+cast+activation body, verbatim —
        # stacked int8 matches unrolled int8 token-for-token through it
        _q8l = self._dense_q8

        def _ropeq(t):
            # pos is a traced scalar (stacked) or (B,) per-slot vector
            # (pool) — rope's position_offset handles both, so the pool
            # rows share the batch path's rotary math exactly
            return _rope.__wrapped__(t, base=rope_base,
                                     position_offset=pos)

        x = self._embed(x_tok, pos)
        idx = lax.broadcasted_iota(jnp.int32, (1, 1, self.total), 2)
        # (1,1,1,T) <= scalar pos, or <= (B,1,1,1) per-slot positions
        pos_b = pos[:, None, None, None] if per_slot else pos
        iB = jnp.arange(B)
        walk = False
        if pages is not None:
            pt, page = pages
            maxp = self.total // page
            # int8 pools ride as (codes, scales) tuples — a STATIC
            # python structure, so the branch is resolved at trace time
            # and costs the f32 path nothing
            quant = isinstance(ck, tuple)
            # a native pool of whole lane and sublane tiles is WALKED: the
            # kernel reads each slot's pages as far as its length, on a
            # TPU; everything else (an int8 pool, other shapes, another
            # platform) builds the T-wide view
            walk = self.walks_pages(page, quant)
            if walk:
                lengths = page_walk.walk_lengths(pt, pos, page, ck.shape[1])

            def _view_attention(q, k, v, l):
                """The view path: gather the slot's whole table row, land
                the new row in the view, contract all T columns."""
                kc = _paged_rows(ck, pt, l, cdtype)      # (B, T, KV·D)
                vc = _paged_rows(cv, pt, l, cdtype)
                with jax.named_scope("mx.kv_write"):
                    kc = kc.at[iB, pos].set(k)
                    vc = vc.at[iB, pos].set(v)
                with jax.named_scope("mx.attn"):
                    return _flat_attention(
                        q.reshape(B, 1, H, D), kc, vc,
                        idx <= pos[:, None, None], scale,
                        cdtype).reshape(B, U)

        @jax.named_scope("mx.dense")
        def body(x, xs):
            if pages is not None:
                # the donated pools are closed over, whole: a pool sliced
                # by the scan would be copied, a layer at a time
                w, l = xs
            else:
                w, kc, vc = xs                # per-layer slices
            if llama:
                h = _rms(x, w["rms1_g"], eps=eps1)
                if int8:
                    q = _q8l(h, w["q"]).reshape(B, H, 1, D)
                    k = _q8l(h, w["k"]).reshape(B, KV, 1, D)
                    v = _q8l(h, w["v"]).reshape(B, KV, 1, D)
                else:
                    q = _fc(h, w["q_w"], None, no_bias=True,
                            flatten=False).reshape(B, H, 1, D)
                    k = _fc(h, w["k_w"], None, no_bias=True,
                            flatten=False).reshape(B, KV, 1, D)
                    v = _fc(h, w["v_w"], None, no_bias=True,
                            flatten=False).reshape(B, KV, 1, D)
                q = _ropeq(q)
                k = _ropeq(k)
            else:
                h = _ln(x, w["ln1_g"], w["ln1_b"], eps=eps1)
                qkv = _q8l(h, w["qkv"]) if int8 else \
                    _fc(h, w["qkv_w"], w["qkv_b"], flatten=False)
                q, k, v = (qkv[:, j * U:(j + 1) * U].reshape(B, H, 1, D)
                           for j in range(3))
            if pages is not None:
                # the new token's K and V as rows of the pool's layout
                k, v = k.reshape(B, KV * D), v.reshape(B, KV * D)
                if walk:
                    with jax.named_scope("mx.attn"):
                        o = _paged.paged_attention(
                            q.reshape(B, H, D), k, v, ck, cv, l, pt,
                            lengths, scale,
                            lambda: _view_attention(q, k, v, l))
                else:
                    o = _view_attention(q, k, v, l)
            else:
                with jax.named_scope("mx.kv_write"):
                    if per_slot:
                        kc = kc.at[iB, :, pos, :].set(k[:, :, 0, :])
                        vc = vc.at[iB, :, pos, :].set(v[:, :, 0, :])
                    else:
                        kc = lax.dynamic_update_slice(kc, k,
                                                      (0, 0, pos, 0))
                        vc = lax.dynamic_update_slice(vc, v,
                                                      (0, 0, pos, 0))
                with jax.named_scope("mx.attn"):
                    qg = q.reshape(B, KV, H // KV, D)
                    s = jnp.einsum(
                        "bkgd,bktd->bkgt", qg, kc,
                        preferred_element_type=jnp.float32) * scale
                    s = jnp.where(idx[:, :, None] <= pos_b, s, -1e30)
                    p = jax.nn.softmax(s, axis=-1).astype(cdtype)
                    o = jnp.einsum("bkgt,bktd->bkgd", p,
                                   vc).reshape(B, U)
            if llama:
                x = x + (_q8l(o, w["o"]) if int8 else
                         _fc(o, w["o_w"], None, no_bias=True,
                             flatten=False))
                h2 = _rms(x, w["rms2_g"], eps=eps2)
                if int8:
                    g = _q8l(h2, w["gate"])
                    u = _q8l(h2, w["up"])
                    x = x + _q8l(g * jax.nn.sigmoid(g) * u, w["down"])
                else:
                    g = _fc(h2, w["gate_w"], None, no_bias=True,
                            flatten=False)
                    u = _fc(h2, w["up_w"], None, no_bias=True,
                            flatten=False)
                    x = x + _fc(g * jax.nn.sigmoid(g) * u, w["down_w"],
                                None, no_bias=True, flatten=False)
            elif int8:
                x = x + _q8l(o, w["proj"])
                h2 = _ln(x, w["ln2_g"], w["ln2_b"], eps=eps2)
                x = x + _q8l(_q8l(h2, w["fc1"], act_t), w["fc2"])
            else:
                x = x + _fc(o, w["proj_w"], w["proj_b"], flatten=False)
                h2 = _ln(x, w["ln2_g"], w["ln2_b"], eps=eps2)
                hh = _fc(h2, w["fc1_w"], w["fc1_b"], flatten=False)
                if act_t is not None:
                    hh = _act(hh, act_type=act_t)
                x = x + _fc(hh, w["fc2_w"], w["fc2_b"], flatten=False)
            return x, (k, v)

        # the dense caches ride the scan's xs, a layer a step; the paged
        # pools are closed over and only the layer's number rides.  The
        # scan is ``mx.paged_view`` (what it does to the caches is the
        # first leg of cache -> view) — but where the pages are walked,
        # which builds no view — and its body ``mx.dense`` but for the
        # regions named inside
        with jax.named_scope("mx.dense" if walk else "mx.paged_view"):
            x, (knew, vnew) = lax.scan(
                body, x, (sw, jnp.arange(self.NL)) if pages is not None
                else (sw, ck, cv))
        # knew/vnew: (NL, B, KV, 1, D), or (NL, B, KV·D) rows of the paged
        # pool — all layers' new columns land in the carried caches as
        # ONE update (slice, or per-slot scatter)
        with jax.named_scope("mx.page_write"):
            if pages is not None:
                # slot b's position pos[b] lives at (page
                # pt[b, pos//page], row pos % page).  Retired slots
                # carry the sentinel in their table rows so the scatter
                # DROPS their zombie writes; the clip keeps a stale
                # pos == T from indexing past the table (it would
                # otherwise clamp onto a live entry).
                pg = pt[iB, jnp.minimum(pos // page, maxp - 1)]
                if quant:
                    # requantizing page RMW: dequantize the frontier
                    # page, land the row, re-quantize (old scale as
                    # floor)
                    ck = _kv_step_rmw(ck, pg, pos % page, knew)
                    cv = _kv_step_rmw(cv, pg, pos % page, vnew)
                else:
                    ck = _rows_set(ck, pg, pos % page, knew)
                    cv = _rows_set(cv, pg, pos % page, vnew)
            elif per_slot:
                ck = ck.at[:, iB, :, pos, :].set(
                    jnp.moveaxis(knew[:, :, :, 0, :], 0, 1))
                cv = cv.at[:, iB, :, pos, :].set(
                    jnp.moveaxis(vnew[:, :, :, 0, :], 0, 1))
            else:
                ck = lax.dynamic_update_slice(ck, knew,
                                              (0, 0, 0, pos, 0))
                cv = lax.dynamic_update_slice(cv, vnew,
                                              (0, 0, 0, pos, 0))
        with jax.named_scope("mx.head"):
            xl = _call(self.model.ln_f, x)
            return self._head_logits(xl, q8), ck, cv

    @jax.named_scope("mx.dense")   # but for the regions named inside
    def chunk_tokens(self, toks, off, nlast, ptrow, page, kp, vp, sw,
                     q8=None):
        """ONE CHUNK of a single sequence's prefill against the PAGED
        pool (chunked prefill and prefix-cache suffix fill,
        ``mxnet_tpu.serve``): ``toks`` (C,) int32 occupy absolute
        positions ``off .. off+C-1`` of the slot whose page-table row
        is ``ptrow`` (MAXP,) int32.  The already-cached prefix is
        gathered through the row at ``(layer, page id)`` out of the
        whole ``(NL, NPAGES, page, KV·D)`` pool, the chunk's rows land
        in that ``(1, T, KV·D)`` view, the chunk attends causally over
        prefix + itself (scores masked at ``t <= off + j`` — the same
        mask/softmax/contraction as the decode step,
        ``_flat_attention``), chunk K/V scatters back through the row
        at ``(layer, page, row)`` (positions past the reserved
        pages resolve to the sentinel and DROP), and the logits at
        absolute position ``off + nlast`` come back for the final
        chunk's first-token sample.  ``off``/``nlast`` ride as TRACED
        scalars, so one compiled program per chunk length C serves
        every landing offset — chunked admission never retraces on
        prompt length."""
        from ..ops.registry import get_op

        _fc = get_op("FullyConnected").fn
        _ln = get_op("LayerNorm").fn
        _rms = get_op("RMSNorm").fn
        _act = get_op("Activation").fn
        U, H, KV, D = self.U, self.H, self.KV, self.D
        T = self.total
        llama, cdtype = self.is_llama, self.cdtype
        int8 = self.use_int8
        eps1, eps2 = self.norm_eps
        act_t, scale, rope_base = self.act_t, self.scale, self.rope_base
        _q8l = self._dense_q8
        C = toks.shape[0]
        maxp = T // page
        quant = isinstance(kp, tuple)      # int8 (codes, scales) pools
        npages = (kp[0] if quant else kp).shape[1]
        cpos = off + jnp.arange(C, dtype=jnp.int32)       # absolute

        x = _call(self.model.wte, toks)[None]             # (1, C, U)
        if not llama:
            x = x + _call(self.model.wpe, cpos)[None]
        # (C, T) causal mask over absolute positions: chunk row j sees
        # cached tokens 0..off+j (its own column included post-update)
        mask = jnp.arange(T, dtype=jnp.int32)[None, :] <= cpos[:, None]

        @jax.named_scope("mx.dense")
        def body(x, xs):
            w, l = xs               # the pools are closed over, whole
            # this slot's cached prefix as (1, T, KV·D) rows
            kc = _paged_rows(kp, ptrow, l, cdtype)[None]
            vc = _paged_rows(vp, ptrow, l, cdtype)[None]
            if llama:
                h = _rms(x, w["rms1_g"], eps=eps1)
                if int8:
                    # q8_matvec is strictly 2-D: project the (C, U) rows
                    q = _q8l(h[0], w["q"]).reshape(1, C, H, D)
                    k = _q8l(h[0], w["k"]).reshape(1, C, KV, D)
                    v = _q8l(h[0], w["v"]).reshape(1, C, KV, D)
                else:
                    q = _fc(h, w["q_w"], None, no_bias=True,
                            flatten=False).reshape(1, C, H, D)
                    k = _fc(h, w["k_w"], None, no_bias=True,
                            flatten=False).reshape(1, C, KV, D)
                    v = _fc(h, w["v_w"], None, no_bias=True,
                            flatten=False).reshape(1, C, KV, D)
                q = _rope_rows(q, rope_base, off)
                k = _rope_rows(k, rope_base, off)
            else:
                h = _ln(x, w["ln1_g"], w["ln1_b"], eps=eps1)
                qkv = _q8l(h[0], w["qkv"])[None] if int8 else \
                    _fc(h, w["qkv_w"], w["qkv_b"], flatten=False)
                q, k, v = (qkv[..., j * U:(j + 1) * U].reshape(1, C, H, D)
                           for j in range(3))
            # the chunk's K and V as (1, C, KV·D) rows of the view
            k = k.reshape(1, C, KV * D).astype(cdtype)
            v = v.reshape(1, C, KV * D).astype(cdtype)
            # chunk K/V lands in the view BEFORE attention, so one mask
            # covers prefix and intra-chunk causality together
            with jax.named_scope("mx.kv_write"):
                kc = lax.dynamic_update_slice(kc, k, (0, off, 0))
                vc = lax.dynamic_update_slice(vc, v, (0, off, 0))
            with jax.named_scope("mx.attn"):
                o = _flat_attention(q, kc, vc, mask[None], scale, cdtype)
            if llama:
                x = x + (_q8l(o[0], w["o"])[None] if int8 else
                         _fc(o, w["o_w"], None, no_bias=True,
                             flatten=False))
                h2 = _rms(x, w["rms2_g"], eps=eps2)
                if int8:
                    g = _q8l(h2[0], w["gate"])
                    u = _q8l(h2[0], w["up"])
                    x = x + _q8l(g * jax.nn.sigmoid(g) * u,
                                 w["down"])[None]
                else:
                    g = _fc(h2, w["gate_w"], None, no_bias=True,
                            flatten=False)
                    u = _fc(h2, w["up_w"], None, no_bias=True,
                            flatten=False)
                    x = x + _fc(g * jax.nn.sigmoid(g) * u, w["down_w"],
                                None, no_bias=True, flatten=False)
            elif int8:
                x = x + _q8l(o[0], w["proj"])[None]
                h2 = _ln(x, w["ln2_g"], w["ln2_b"], eps=eps2)
                x = x + _q8l(_q8l(h2[0], w["fc1"], act_t),
                             w["fc2"])[None]
            else:
                x = x + _fc(o, w["proj_w"], w["proj_b"], flatten=False)
                h2 = _ln(x, w["ln2_g"], w["ln2_b"], eps=eps2)
                hh = _fc(h2, w["fc1_w"], w["fc1_b"], flatten=False)
                if act_t is not None:
                    hh = _act(hh, act_type=act_t)
                x = x + _fc(hh, w["fc2_w"], w["fc2_b"], flatten=False)
            return x, (k, v)

        with jax.named_scope("mx.paged_view"):   # see _scan_token
            x, (knew, vnew) = lax.scan(body, x, (sw, jnp.arange(self.NL)))
        # knew/vnew: (NL, 1, C, KV·D) — scatter every chunk row through
        # the page-table row.  Positions past the reserved pages
        # (bucket-padded tails) resolve to the sentinel and DROP; the
        # explicit cpos < T guard covers tails that would otherwise CLIP
        # onto the row's own last page and corrupt earlier tokens.
        with jax.named_scope("mx.page_write"):
            if quant:
                # requantizing page-WINDOW RMW: the C consecutive rows
                # touch at most ntp consecutive pages of this row (static
                # in C and page, so the program shape is unchanged).  Pad
                # rows past ``nlast`` are masked OUT here — unlike the
                # f32 path's harmless garbage-but-unreachable writes, a pad
                # row would poison its page's shared SCALE.
                ntp = (C + page - 2) // page + 1
                p0 = off // page
                widx = p0 + jnp.arange(ntp, dtype=jnp.int32)
                wpgs = jnp.where(widx < maxp,
                                 ptrow[jnp.minimum(widx, maxp - 1)],
                                 npages)                       # (NTP,)
                keepc = (jnp.arange(C, dtype=jnp.int32) <= nlast) & \
                    (cpos < T)
                loc = jnp.where(keepc, cpos - p0 * page, ntp * page)
                kp = _kv_chunk_rmw(kp, wpgs, loc, knew[:, 0])
                vp = _kv_chunk_rmw(vp, wpgs, loc, vnew[:, 0])
            else:
                pgs = jnp.where(cpos < T,
                                ptrow[jnp.minimum(cpos // page, maxp - 1)],
                                npages)                        # (C,)
                kp = _rows_set(kp, pgs, cpos % page, knew[:, 0])
                vp = _rows_set(vp, pgs, cpos % page, vnew[:, 0])
        with jax.named_scope("mx.head"):
            x_last = lax.dynamic_slice(x, (0, nlast, 0),
                                       (1, 1, U))[:, 0]
            xl = _call(self.model.ln_f, x_last)
            # the chunk head is native, matching prefill_batch (q8
            # covers the per-token decode matvecs; each chunk runs once)
            return self._head_logits(xl, None), kp, vp

    @jax.named_scope("mx.dense")   # but for the regions named inside
    def pool_verify_paged(self, toks, pos, pt, page, kp, vp, sw,
                          q8=None):
        """Draft-and-verify scoring against the PAGED pool
        (``mxnet_tpu.serve`` speculative decoding): every slot ``b``
        carries a block ``toks[b]`` (C,) int32 whose column 0 is the
        slot's last emitted token (already sampled, not yet attended)
        and columns 1..C-1 are host-drafted candidates, occupying
        absolute positions ``pos[b] .. pos[b]+C-1``.  ONE dispatch
        computes the model's next-token logits at ALL C positions —
        ``out[b, j]`` is the token the plain step path would have
        produced after attending position ``pos[b]+j`` — so the caller
        accepts the longest prefix where ``out[:, :-1]`` matches the
        drafts.  The block's K/V columns scatter through the page
        table like ``chunk_tokens``; a rejected tail needs NO undo:
        its columns sit past the slot's advanced length, hidden by the
        causal mask and overwritten (write-before-attend) by the next
        dispatch that reaches those positions, and pages are reserved
        for the full ``prompt+max_new`` budget at admission, so
        rollback never moves a refcount.  Structurally this is
        ``chunk_tokens`` batched over slots — per-row positions ride
        as a traced (B,) operand (one compiled program per block
        width C, zero retraces under accept/reject churn), the same
        mask/softmax/einsum discipline, the same sentinel-row DROP
        semantics for retired lanes — crossed with ``_scan_token``'s
        per-slot paged views and q8 head (the parity contract: a
        verify column's logits come from the same projections and
        head as the plain step's)."""
        from ..ops.registry import get_op

        _fc = get_op("FullyConnected").fn
        _ln = get_op("LayerNorm").fn
        _rms = get_op("RMSNorm").fn
        _act = get_op("Activation").fn
        B, U, H, KV, D = self.B, self.U, self.H, self.KV, self.D
        T = self.total
        llama, cdtype = self.is_llama, self.cdtype
        int8 = self.use_int8
        eps1, eps2 = self.norm_eps
        act_t, scale, rope_base = self.act_t, self.scale, self.rope_base
        _q8l = self._dense_q8
        C = toks.shape[1]
        maxp = T // page
        quant = isinstance(kp, tuple)      # int8 (codes, scales) pools
        npages = (kp[0] if quant else kp).shape[1]
        iB = jnp.arange(B)
        cpos = pos[:, None] + jnp.arange(C, dtype=jnp.int32)   # (B, C)
        # dense-view write positions: a column past the cache horizon
        # (a near-budget slot co-resident with a deeper block, or a
        # zombie lane's stale pos) aims one-past-the-end and DROPS —
        # clamping instead would overwrite the slot's own live T-1
        # column before attention.  Such columns are never accepted
        # (the verify program caps advance at the slot's stop).
        wpos = jnp.where(cpos < T, cpos, T)

        x = _call(self.model.wte, toks)                    # (B, C, U)
        if not llama:
            x = x + _call(self.model.wpe, cpos)
        # (B, C, T) causal mask over absolute positions: block column j
        # of slot b sees cached tokens 0..pos[b]+j (itself included
        # post-update) — a rejected earlier burst's stale columns sit
        # PAST pos[b]+j and stay masked out
        mask = jnp.arange(T, dtype=jnp.int32)[None, None, :] <= \
            cpos[:, :, None]

        @jax.named_scope("mx.dense")
        def body(x, xs):
            w, l = xs               # the pools are closed over, whole
            kc = _paged_rows(kp, pt, l, cdtype)          # (B, T, KV·D)
            vc = _paged_rows(vp, pt, l, cdtype)
            if llama:
                h = _rms(x, w["rms1_g"], eps=eps1)
                if int8:
                    # q8_matvec is strictly 2-D: project (B*C, U) rows
                    h2d = h.reshape(B * C, U)
                    q = _q8l(h2d, w["q"]).reshape(B, C, H, D)
                    k = _q8l(h2d, w["k"]).reshape(B, C, KV, D)
                    v = _q8l(h2d, w["v"]).reshape(B, C, KV, D)
                else:
                    q = _fc(h, w["q_w"], None, no_bias=True,
                            flatten=False).reshape(B, C, H, D)
                    k = _fc(h, w["k_w"], None, no_bias=True,
                            flatten=False).reshape(B, C, KV, D)
                    v = _fc(h, w["v_w"], None, no_bias=True,
                            flatten=False).reshape(B, C, KV, D)
                # per-slot rotary phase: rope broadcasts a (B,) offset
                # to per-row absolute positions pos[b] + j
                q = _rope_rows(q, rope_base, pos)
                k = _rope_rows(k, rope_base, pos)
            else:
                h = _ln(x, w["ln1_g"], w["ln1_b"], eps=eps1)
                qkv = _q8l(h.reshape(B * C, U),
                           w["qkv"]).reshape(B, C, 3 * U) if int8 \
                    else _fc(h, w["qkv_w"], w["qkv_b"], flatten=False)
                q, k, v = (qkv[..., j * U:(j + 1) * U].reshape(B, C, H, D)
                           for j in range(3))
            # the block's K and V as (B, C, KV·D) rows of the views
            k = k.reshape(B, C, KV * D).astype(cdtype)
            v = v.reshape(B, C, KV * D).astype(cdtype)
            # block K/V lands in the views BEFORE attention (per-slot
            # scatter — offsets vary per row), so one mask covers cached
            # prefix and intra-block causality together
            with jax.named_scope("mx.kv_write"):
                kc = kc.at[iB[:, None], wpos].set(k, mode="drop")
                vc = vc.at[iB[:, None], wpos].set(v, mode="drop")
            with jax.named_scope("mx.attn"):
                o = _flat_attention(q, kc, vc, mask, scale, cdtype)
            if llama:
                x = x + (_q8l(o.reshape(B * C, U),
                              w["o"]).reshape(B, C, U) if int8 else
                         _fc(o, w["o_w"], None, no_bias=True,
                             flatten=False))
                h2 = _rms(x, w["rms2_g"], eps=eps2)
                if int8:
                    h2d = h2.reshape(B * C, U)
                    g = _q8l(h2d, w["gate"])
                    u = _q8l(h2d, w["up"])
                    x = x + _q8l(g * jax.nn.sigmoid(g) * u,
                                 w["down"]).reshape(B, C, U)
                else:
                    g = _fc(h2, w["gate_w"], None, no_bias=True,
                            flatten=False)
                    u = _fc(h2, w["up_w"], None, no_bias=True,
                            flatten=False)
                    x = x + _fc(g * jax.nn.sigmoid(g) * u, w["down_w"],
                                None, no_bias=True, flatten=False)
            elif int8:
                x = x + _q8l(o.reshape(B * C, U),
                             w["proj"]).reshape(B, C, U)
                h2 = _ln(x, w["ln2_g"], w["ln2_b"], eps=eps2)
                x = x + _q8l(_q8l(h2.reshape(B * C, U), w["fc1"],
                                  act_t), w["fc2"]).reshape(B, C, U)
            else:
                x = x + _fc(o, w["proj_w"], w["proj_b"], flatten=False)
                h2 = _ln(x, w["ln2_g"], w["ln2_b"], eps=eps2)
                hh = _fc(h2, w["fc1_w"], w["fc1_b"], flatten=False)
                if act_t is not None:
                    hh = _act(hh, act_type=act_t)
                x = x + _fc(hh, w["fc2_w"], w["fc2_b"], flatten=False)
            return x, (k, v)

        with jax.named_scope("mx.paged_view"):   # see _scan_token
            x, (knew, vnew) = lax.scan(body, x, (sw, jnp.arange(self.NL)))
        # knew/vnew: (NL, B, C, KV·D) — scatter every block row of every
        # slot through its page-table row.  Out-of-range rows (zombie
        # lanes past T) resolve to the sentinel and DROP; the cpos < T
        # guard keeps them from CLIPPING onto a live page.
        with jax.named_scope("mx.page_write"):
            if quant:
                # per-slot requantizing page-window RMW (the chunk write
                # batched over slots): slot b's C rows touch at most ntp
                # consecutive pages from its frontier page pos[b] // page
                ntp = (C + page - 2) // page + 1
                p0 = pos // page                               # (B,)
                widx = p0[:, None] + jnp.arange(ntp, dtype=jnp.int32)
                wpgs = jnp.where(widx < maxp,
                                 pt[iB[:, None],
                                    jnp.minimum(widx, maxp - 1)],
                                 npages)                       # (B, NTP)
                loc = jnp.where(cpos < T, cpos - p0[:, None] * page,
                                ntp * page)                    # (B, C)
                kp = _kv_verify_rmw(kp, wpgs, loc, knew)
                vp = _kv_verify_rmw(vp, wpgs, loc, vnew)
            else:
                pgs = jnp.where(cpos < T,
                                pt[iB[:, None], jnp.minimum(cpos // page,
                                                            maxp - 1)],
                                npages)                        # (B, C)
                kp = _rows_set(kp, pgs, cpos % page, knew)
                vp = _rows_set(vp, pgs, cpos % page, vnew)
        with jax.named_scope("mx.head"):
            xl = _call(self.model.ln_f, x)
            # same head as the plain step (q8 when int8) — the greedy
            # parity contract: out[b, 0]'s logits == the step path's
            logits = self._head_logits(xl.reshape(B * C, U), q8)
            return logits.reshape(B, C, -1), kp, vp

    def token_step(self, tok, t, ck, cv, q8, sw):
        """Dispatch one per-token step through the selected mode."""
        if self.mode == "stacked":
            return self.stacked_token(tok, t, ck, cv, sw, q8)
        return self.one_token(tok, t, ck, cv, q8)

    @jax.named_scope("mx.dense")   # but for the regions named inside
    def prefill_batch(self, prompt_dev, ck, cv, last_index=None):
        """One causal forward over the whole (B, P) prompt: fills cache
        positions [0, P) and returns the position-P-1 logits (or the
        position-``last_index`` logits when given — the serving
        admission path right-pads prompts to a compiled bucket length
        and reads the logits at the true last token; the padded tail's
        cache columns are overwritten by decode steps before any step
        attends to them).  ``last_index`` may be a scalar (every row
        ends at the same position) or a per-row ``(B,)`` vector — the
        RAGGED-ROW case batched admission dispatches: each row is an
        independent right-padded prompt with its own true length, and
        its logits are gathered at its own last real token.  Because
        every row starts at position 0, the rows share one causal mask
        and one rope phase (``position_offset=0``); a row's padding
        positions attend only backward into its own real tokens, and
        their outputs are never read — per-row raggedness surfaces
        only in the last-index gather here and in the caller's masked
        cache scatter.  Exact same math as the per-token path
        (einsum + f32 softmax), reshaped onto MXU-friendly (B·P, ·)
        GEMMs."""
        from ..ops.attention import rope as _rope

        from ..ops.registry import get_op
        _flash_fn = get_op("flash_attention").fn

        model = self.model
        B, P = self.B, self.P
        U, H, KV, D = self.U, self.H, self.KV, self.D
        is_llama, cdtype = self.is_llama, self.cdtype

        x = _call(model.wte, prompt_dev)                      # (B, P, U)
        if not is_llama:
            pos = jnp.arange(P, dtype=jnp.int32)
            x = x + _call(model.wpe, jnp.broadcast_to(pos[None], (B, P)))
        for i, blk in enumerate(model.blocks):
            if is_llama:
                h = _call(blk.rms1, x)
                q = _call(blk.attn.q_proj, h).reshape(
                    B, P, H, D).transpose(0, 2, 1, 3)
                k = _call(blk.attn.k_proj, h).reshape(
                    B, P, KV, D).transpose(0, 2, 1, 3)
                v = _call(blk.attn.v_proj, h).reshape(
                    B, P, KV, D).transpose(0, 2, 1, 3)
                q = _rope.__wrapped__(q, base=self.rope_base,
                                      position_offset=0)
                k = _rope.__wrapped__(k, base=self.rope_base,
                                      position_offset=0)
            else:
                h = _call(blk.ln1, x)
                qkv = _call(blk.attn.qkv, h)                  # (B, P, 3U)
                q, k, v = (qkv[..., j * U:(j + 1) * U]
                           .reshape(B, P, H, D).transpose(0, 2, 1, 3)
                           for j in range(3))
            with jax.named_scope("mx.kv_write"):
                ck = lax.dynamic_update_slice(
                    ck, k.astype(cdtype)[None], (i, 0, 0, 0, 0))
                cv = lax.dynamic_update_slice(
                    cv, v.astype(cdtype)[None], (i, 0, 0, 0, 0))
            # causal attention over the prompt via the flash kernel —
            # O(P) memory (no (P, P) score tensor), so long prompts
            # prefill without OOM; GQA repeats k/v across head groups
            kf, vf = k, v
            if KV != H:
                kf = jnp.repeat(k, H // KV, axis=1)
                vf = jnp.repeat(v, H // KV, axis=1)
            o = _flash_fn(q, kf, vf, None, scale=self.scale, causal=True)
            o = o.transpose(0, 2, 1, 3).reshape(B, P, U)
            if is_llama:
                x = x + _call(blk.attn.o_proj, o)
                x = x + _call(blk.mlp, _call(blk.rms2, x))
            else:
                x = x + _call(blk.attn.proj, o)
                x = x + _call(blk.ffn, _call(blk.ln2, x))
        if last_index is None:
            x_last = x[:, -1]
        else:
            li = jnp.asarray(last_index)
            if li.ndim == 0:
                x_last = lax.dynamic_index_in_dim(x, li, axis=1,
                                                  keepdims=False)
            else:
                # ragged rows: gather row b's hidden state at its own
                # last real token li[b]
                x_last = jnp.take_along_axis(
                    x, li.astype(jnp.int32)[:, None, None],
                    axis=1)[:, 0]
        with jax.named_scope("mx.head"):
            xl = _call(model.ln_f, x_last)
            # the prefill head is always native (q8 covers decode-step
            # matvecs; the prefill runs once)
            return self._head_logits(xl, None), ck, cv

    def zero_caches(self):
        shape = (self.NL, self.B, self.KV, self.total, self.D)
        return jnp.zeros(shape, self.cdtype), \
            jnp.zeros(shape, self.cdtype)

    def cache_bytes(self):
        """Device bytes of the K/V cache pair this engine's programs
        carry — the dominant in-executable allocation, reported as the
        ``cache_bytes`` field on the decode sites' compile events so a
        recording can split "KV cache" from "everything else" inside
        ``mem_temp_bytes`` without re-deriving the geometry."""
        return 2 * self.NL * self.B * self.KV * self.total * self.D \
            * jnp.dtype(self.cdtype).itemsize

    def take_operands(self):
        """Hand the weight operands (param values + prepared q8 /
        stacked arrays) to the caller and DROP the engine's own refs:
        the compiled program closure keeps the engine alive, and it must
        not pin the first call's arrays after a train-step rebind."""
        operands = (self.param_vals, self.q8v, self.sw)
        self.param_vals = self.q8v = self.sw = None
        return operands

    def build_run(self):
        """The whole-decode program (prefill + sampled scan) to be
        jitted: run(param_vals, q8, sw, prompt_dev, key0) →
        (N, B) new tokens."""
        from ..gluon.parameter import params_swapped

        eng = self
        P, total = self.P, self.total

        if self.prefill == "batched":
            def run(param_vals, q8, sw, prompt_dev, key0):
                with _TRACE_LOCK, params_swapped(eng.params, param_vals):
                    ck, cv = eng.zero_caches()
                    logits, ck, cv = eng.prefill_batch(prompt_dev, ck, cv)
                    first = eng._sample(logits, P - 1, key0)

                    def scan_body(carry, t):
                        tok, ck, cv = carry
                        logits, ck, cv = eng.token_step(
                            tok, t, ck, cv, q8, sw)
                        nxt = eng._sample(logits, t, key0)
                        return (nxt, ck, cv), nxt

                    (_, _, _), toks = lax.scan(
                        scan_body, (first, ck, cv),
                        jnp.arange(P, total - 1))
                    return jnp.concatenate([first[None], toks])  # (N, B)
        else:
            def run(param_vals, q8, sw, prompt_dev, key0):
                with _TRACE_LOCK, params_swapped(eng.params, param_vals):

                    def scan_body(carry, t):
                        tok, ck, cv = carry
                        # teacher-force while t is inside the prompt
                        cur = jnp.where(t < P,
                                        prompt_dev[:, jnp.minimum(t, P - 1)],
                                        tok)
                        logits, ck, cv = eng.token_step(
                            cur, t, ck, cv, q8, sw)
                        nxt = eng._sample(logits, t, key0)
                        return (nxt, ck, cv), nxt

                    ck, cv = eng.zero_caches()
                    tok0 = jnp.zeros((eng.B,), jnp.int32)
                    (_, _, _), toks = lax.scan(scan_body, (tok0, ck, cv),
                                               jnp.arange(total - 1))
                    # positions P-1 .. total-2 sampled the new tokens
                    return toks[P - 1:]                        # (N, B)

        return run


def kv_generate(model, prompt_tokens, max_new_tokens=32, temperature=1.0,
                top_k=0, seed=0, prefill="batched", weights="native",
                stacked="auto"):
    """Sample ``max_new_tokens`` continuations for a (B, P) prompt.

    Greedy when ``temperature == 0``; ``top_k > 0`` restricts the sample
    space (sampling uses ``jax.random.categorical`` with a per-step
    ``fold_in(key, t)`` key — deterministic given ``seed``).  Matches
    ``model.generate`` token-for-token in greedy mode (the KV-cached
    attention is mathematically identical to full recompute).  Returns
    the full (B, P + max_new_tokens) int32 array.

    ``prefill``: ``"batched"`` (default) runs the whole prompt through
    ONE causal forward that fills the K/V cache — P-1 sequential scan
    steps collapse into one MXU-shaped pass; ``"scan"`` keeps the
    token-at-a-time prefill (same token stream either way — the sampling
    key at position t is ``fold_in(key, t)`` in both modes).

    ``weights``: ``"int8"`` streams the decode-step matmul weights as
    per-channel-quantized int8 (half the HBM bytes of bf16),
    dequantizing inside the dot with f32 accumulation.  Both families
    (GPT fused-QKV and Llama split-projection/SwiGLU).  An approximate
    path — greedy tokens can differ from the exact native path (~0.4%
    weight error).  int8 runs the stacked-layer
    scan wherever the native path does (stacked q8 codes ride the scan
    xs; see PARITY.md decode support matrix), falling back to the
    per-layer unrolled step like native weights.

    ``stacked``: ``"auto"`` (default) runs the decode scan step as ONE
    ``lax.scan`` over stacked (NL, ...) layer weights whenever the model
    qualifies (``stacked_decode_supported``: a uniform GPT or Llama/GQA
    layer stack) — the compiled step carries one layer-body's worth of
    HLO instead of NL copies, on ANY backend; ``"on"`` requires it
    (raises if unsupported); ``"off"`` keeps the per-layer unrolled step.
    """
    _check_args(prefill, weights, stacked)
    prompt = onp.asarray(
        prompt_tokens.asnumpy() if hasattr(prompt_tokens, "asnumpy")
        else prompt_tokens, dtype=onp.int32)
    B, P = prompt.shape
    if max_new_tokens <= 0:
        return prompt.copy()
    total = P + max_new_tokens
    if total > model._cfg.max_length:
        raise ValueError(f"prompt+new = {total} exceeds max_length "
                         f"{model._cfg.max_length}")

    eng = _DecodeEngine(model, B, P, total, temperature, top_k, prefill,
                        weights, stacked)
    cache_key = (B, P, max_new_tokens, float(temperature), int(top_k),
                 str(eng.cdtype), prefill, weights, eng.mode)
    cache = model.__dict__.setdefault("_kv_decode_cache", {})
    if cache_key not in cache:
        from .. import telemetry
        cache[cache_key] = telemetry.instrument_jit(
            jax.jit(eng.build_run()), "models.kv_generate",
            key=cache_key, fields={"mode": eng.mode, "batch": B,
                                   "prompt_len": P,
                                   "new_tokens": max_new_tokens,
                                   "cache_bytes": eng.cache_bytes()})

    # the weight operands must not stay pinned on the engine: the cached
    # jitted run closes over it for the model's lifetime, and a train
    # step rebinds the parameter arrays — a retained first-call copy
    # would be a leaked full weight set per cache entry (the per-model
    # _pinned_cache entries are the intended reuse point; they are
    # REPLACED on rebind, freeing the old arrays)
    operands = eng.take_operands()
    new = onp.asarray(cache[cache_key](
        *operands, jnp.asarray(prompt), jax.random.PRNGKey(seed))).T
    return onp.concatenate([prompt, new], axis=1)


def decode_step_program(model, batch=1, total=32, temperature=0.0,
                        top_k=0, weights="native", stacked="auto",
                        seed=0):
    """ONE decode step as a ``(jitted_fn, example_args)`` pair — the unit
    ``profiler_xla.hlo_op_count`` measures and the op-count regression
    test / ``benchmark/decode_bench.py`` ops/step column assert on.

    ``fn(param_vals, q8, sw, tok, pos, ck, cv, key0)`` →
    ``(next_tok (B,), ck, cv)`` for a token at position ``pos`` against
    a ``total``-slot cache; the weight operands in ``example_args`` are
    the same traced-argument set the full ``kv_generate`` program uses,
    so the counted HLO is the per-step slice of the real decode scan."""
    eng = _DecodeEngine(model, batch, max(total - 1, 1), total,
                        temperature, top_k, "batched", weights, stacked)
    from ..gluon.parameter import params_swapped

    def step(param_vals, q8, sw, tok, pos, ck, cv, key0):
        with _TRACE_LOCK, params_swapped(eng.params, param_vals):
            logits, ck, cv = eng.token_step(tok, pos, ck, cv, q8, sw)
            nxt = eng._sample(logits, pos, key0)
        return nxt, ck, cv

    ck, cv = eng.zero_caches()
    # same closure-pinning discipline as kv_generate: the returned fn
    # closes over the engine, so the caller-owned args tuple holds the
    # only weight refs
    args = (*eng.take_operands(),
            jnp.zeros((batch,), jnp.int32),
            jnp.asarray(max(total - 2, 0), jnp.int32), ck, cv,
            jax.random.PRNGKey(seed))
    from .. import telemetry
    fn = telemetry.instrument_jit(
        jax.jit(step), "models.decode_step",
        key=(batch, total, weights, eng.mode),
        fields={"mode": eng.mode, "batch": batch,
                "cache_bytes": eng.cache_bytes()})
    return fn, args
