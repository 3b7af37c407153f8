"""Single-query attention straight out of the paged K/V pool (Pallas TPU).

The serving step's cache-to-output leg for ONE new token a slot
(``models/decoding.py::_scan_token``, ``pages=`` branch; ``models/layered.py::
_gqa_mixer``): slot ``b`` owns the pages its table row ``pt[b]`` names, of
which only the first ``ceil(pos[b] / page)`` hold tokens.  The view path
gathers all ``MAXP`` pages of every slot into a ``(B, T, KV·D)`` view and
contracts all ``T`` columns; this kernel walks each slot's row only as far
as its length and never builds the view.

A walk has an END and, for a layer that keeps a window, a START
(``walk_span``): the table row is then a RING (logical page ``lp`` lies at
entry ``lp % width``), the walk begins at the entry of the page that holds
the first visible position ``start[b]`` and runs in ring order to the page
of ``pos[b] - 1``, and the columns before ``start[b]`` in that first page
are masked.  A layer without a window passes no start: that is position 0,
entry 0, no wrap, and the kernel is what it was without one.

- The pools ``(NL, NPAGES, page, KV·D)`` stay in HBM, whole
  (``memory_space=pl.ANY``): no BlockSpec copy, no slice of the layer.
  ``layer``, the flattened table and each slot's walk end and start ride in
  SMEM (scalar prefetch).
- A page is one contiguous ``(page, KV·D)`` block; it is fetched by an
  async copy into VMEM, a GROUP of ``_ROWS // page`` pages (one compute
  block of ``_ROWS`` rows) at a time, K and V alike, double-buffered: while
  a group is contracted the next one — the same slot's, or the NEXT slot's
  first — is in flight, so a slot of two or three groups does not pay a
  fetch latency of its own.
- Scores for all heads of a row at once, as ``_flat_attention`` gets them:
  the queries arrive spread block-diagonally over the row's lanes
  (``_spread_queries``), one MXU contraction over the whole row, float32
  accumulation; an online softmax in float32 over the groups; ``p`` cast to
  the pool's dtype before ``p·V`` (as the view path does), accumulated in
  float32.  Columns at and past the walk's end, and before its start, are
  masked.
- The NEW token's own K and V are operands, not pool rows: they enter as the
  first key of the online softmax, so nothing is written to the pool here
  and the step's post-scan scatter stays as it is.
- No page id reaches a copy unchecked: ``walk_lengths`` / ``walk_span`` cut
  a slot's walk at the first sentinel entry on its way (a retired slot's row
  is all sentinel: it walks nothing) and the kernel clamps what it reads
  from the table — an out-of-range DMA takes the chip down, where a gather
  only clamps.

The grid runs the slots in turn (v5e has one TensorCore), so unequal
lengths cost no balance.

VMEM at GPT-2-large's shapes (page 16, KV·D = 1280, 20 heads, bfloat16;
a group is 16 pages = 256 rows): K and V group buffers 2 x 2 x (256, 1280) x
2 B = 2.62 MB, the spread queries' block 2 x (32, 1280) x 2 B = 0.16 MB, the
float32 accumulator (32, 1280) = 0.16 MB, running max and sum 32 KB, the new
rows and the output block a few KB: about 3.0 MB of the 16 MB a kernel may
take by default.  On the chip (PERF.md, PR 30) groups of 256 rows read 9%
faster than of 128 and 3% slower than of 512, which would contract twice the
masked columns of a short slot.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import _interpret

__all__ = ["paged_attention", "supports", "walk_lengths", "walk_span"]

_NEG_INF = -1e30
_ROWS = 256      # token rows a compute block (a group of pages) holds
# profiler_xla._KERNEL_REGIONS knows both: the walk from position 0, and the
# same kernel called with a start (a window layer's ring)
_NAME = "mx_paged_attention"
_NAME_WINDOW = "mx_paged_attention_window"


def supports(lanes, dtype, page, num_heads, head_dim):
    """Whether the kernel takes a pool of this static structure: rows of
    ``lanes`` = whole 128-lane tiles holding whole heads, pages of whole
    sublane tiles (16 rows of bfloat16, 8 of float32) that divide a compute
    block, heads that group evenly over the K/V heads."""
    dtype = jnp.dtype(dtype)
    if dtype not in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)):
        return False
    if lanes % 128 or lanes % head_dim or num_heads % (lanes // head_dim):
        return False
    sublanes = 8 * 4 // dtype.itemsize
    return page % sublanes == 0 and _ROWS % page == 0


def walk_lengths(pt, pos, page, num_pages):
    """Per slot, how many cached tokens the kernel reads: ``pos[b]`` (the
    tokens before the new one), cut at the row's first sentinel entry — a
    retired slot walks nothing, whatever its stale ``pos`` says — and so
    at the table's width.  ``(B,)`` int32; the same for every layer."""
    owned = jnp.cumprod((pt < num_pages).astype(jnp.int32), axis=1)
    return jnp.minimum(pos.astype(jnp.int32), owned.sum(axis=1) * page)


def walk_span(pt, pos, page, num_pages, window):
    """A window layer's walk over its RING ``pt`` ``(B, width)``: ``(ends,
    starts)``, both ``(B,)`` int32 positions.  The new token at ``pos[b]``
    sees the cached positions ``starts[b] = max(pos[b] - window + 1, 0)`` to
    ``pos[b] - 1``; the walk runs from the ring entry of the start's page in
    ring order and is cut at the first sentinel entry on its way, so a
    retired slot (all sentinel) has ``ends[b] <= starts[b]`` and walks
    nothing."""
    pos = pos.astype(jnp.int32)
    width = pt.shape[1]
    starts = jnp.maximum(pos - (window - 1), 0)
    first = starts // page
    entries = (first[:, None] + jnp.arange(width, dtype=jnp.int32)[None]) \
        % width
    owned = jnp.cumprod((jnp.take_along_axis(pt, entries, axis=1)
                         < num_pages).astype(jnp.int32), axis=1)
    return jnp.minimum(pos, (first + owned.sum(axis=1)) * page), starts


def _spread_queries(q, kv, kvp, rows, dtype):
    """``q`` ``(B, H, D)`` spread block-diagonally over a pool row's lanes:
    ``(B, rows, KV·D)`` whose row ``g * kvp + k`` holds head ``k * G + g``
    in lanes ``[k·D, (k+1)·D)`` and exact zeros elsewhere, so one
    contraction over the whole row gives every head's scores.  The ``G``
    heads that share a K/V head lie ``kvp`` rows apart (``kvp``: KV rounded
    up to whole sublane tiles); the padding rows are zero.  The heads, laid
    out by row, are repeated along the lanes by a product with a 0 / 1
    matrix (one term a sum: exact) and kept where the lane's head is the
    row's, in ONE pass over the result — 6% of the kernel's scan on the
    chip against a select, two pads and a reshape (PERF.md, PR 30)."""
    B, H, D = q.shape
    G, lanes = H // kv, kv * D
    qg = q.reshape(B, kv, G, D).transpose(0, 2, 1, 3)       # (B, G, KV, D)
    qg = jnp.pad(qg, ((0, 0), (0, 0), (0, kvp - kv), (0, 0)))
    qg = jnp.pad(qg.reshape(B, G * kvp, D),
                 ((0, 0), (0, rows - G * kvp), (0, 0)))      # (B, rows, D)
    repeat = (lax.broadcasted_iota(jnp.int32, (D, lanes), 1) % D
              == lax.broadcasted_iota(jnp.int32, (D, lanes), 0))
    spread = jnp.einsum("brd,df->brf", qg, repeat.astype(q.dtype),
                        precision=lax.Precision.HIGHEST,
                        preferred_element_type=jnp.float32)
    own = (lax.broadcasted_iota(jnp.int32, (rows, lanes), 1) // D
           == lax.broadcasted_iota(jnp.int32, (rows, lanes), 0) % kvp)
    return jnp.where(own[None], spread, 0).astype(dtype)


def _kernel(layer_ref, pt_ref, len_ref, start_ref,       # SMEM (prefetch)
            qb_ref, kn_ref, vn_ref, kp_ref, vp_ref,      # inputs
            out_ref,                                     # output
            kbuf, vbuf, acc, m_ref, l_ref, state, sems,  # scratch
            *, scale, page, maxp, num_pages, kv, kvp, groups, ring):
    b = pl.program_id(0)
    nslots = pl.num_programs(0)
    per = _ROWS // page                 # pages a group
    lanes = kbuf.shape[-1]
    D = lanes // kv
    layer = layer_ref[0]
    # ``ring`` is static: without it a walk starts at position 0, entry 0
    first_page = (lambda slot_b: start_ref[slot_b] // page) if ring \
        else (lambda slot_b: 0)
    length = len_ref[b]                 # the walk's end, a position
    origin = first_page(b) * page       # position of the walk's column 0
    ngroups = pl.cdiv(length - origin, _ROWS)
    prec = lax.Precision.HIGHEST if kbuf.dtype == jnp.float32 \
        else lax.Precision.DEFAULT

    def each_copy(slot_b, g, buf, act):
        """``act`` on the K and the V copy of every page of group ``g`` of
        slot ``slot_b`` into buffer ``buf`` (``start`` them, later ``wait``
        for the same ones)."""
        first = first_page(slot_b)
        count = jnp.minimum(
            pl.cdiv(len_ref[slot_b], page) - first - g * per, per)

        def body(j, carry):
            entry = first + g * per + j
            if ring:
                entry = lax.rem(entry, maxp)
            # checked against NPAGES though ``walk_lengths`` walks owned
            # pages only: an id out of range must never reach a DMA
            pid = jnp.clip(pt_ref[slot_b * maxp + entry], 0,
                           num_pages - 1)
            dst = pl.ds(pl.multiple_of(j * page, page), page)
            for i, (pool, rows) in enumerate(((kp_ref, kbuf),
                                              (vp_ref, vbuf))):
                act(pltpu.make_async_copy(pool.at[layer, pid],
                                          rows.at[buf, dst],
                                          sems.at[i, buf]))
            return carry
        lax.fori_loop(0, count, body, 0)

    def start(slot_b, g, buf):
        each_copy(slot_b, g, buf, lambda c: c.start())

    def wait(slot_b, g, buf):
        each_copy(slot_b, g, buf, lambda c: c.wait())

    @pl.when(b == 0)
    def _():
        # what a copy never fills is then zeros, not whatever VMEM held:
        # a masked column's V takes part in p·V with weight exactly 0
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)
        state[0] = 0        # groups walked so far: a group's buffer is
        state[1] = 0        # its number's parity.  1: this slot's first
                            # group was started by the slot before it
    first = state[0]

    @pl.when((ngroups > 0) & (state[1] == 0))
    def _():
        start(b, 0, first % 2)

    # the new token's own key opens the online softmax: its score is the
    # running max, its weight 1, its V row the accumulator
    qb = qb_ref[0]                                          # (rows, lanes)
    kn = kn_ref[0].astype(jnp.float32)                      # (1, lanes)
    m_ref[...] = jnp.sum(qb.astype(jnp.float32) * kn, axis=1,
                         keepdims=True) * scale
    l_ref[...] = jnp.ones_like(l_ref)
    acc[...] = jnp.broadcast_to(vn_ref[0].astype(jnp.float32), acc.shape)

    nxt = jnp.minimum(b + 1, nslots - 1)
    next_groups = jnp.where(
        b + 1 < nslots,
        pl.cdiv(len_ref[nxt] - first_page(nxt) * page, _ROWS), 0)

    def group(g, carry):
        buf = (first + g) % 2

        @pl.when(g + 1 < ngroups)
        def _():
            start(b, g + 1, 1 - buf)

        @pl.when((g + 1 == ngroups) & (next_groups > 0))
        def _():
            start(nxt, 0, 1 - buf)

        wait(b, g, buf)
        s = lax.dot_general(qb, kbuf[buf], (((1,), (1,)), ((), ())),
                            precision=prec,
                            preferred_element_type=jnp.float32) * scale
        col = origin + g * _ROWS + lax.broadcasted_iota(jnp.int32, s.shape,
                                                        1)
        seen = col < length
        if ring:
            seen = seen & (col >= start_ref[b])
        s = jnp.where(seen, s, _NEG_INF)
        m_old = m_ref[...]
        m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_old - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc[...] = alpha * acc[...] + lax.dot_general(
            p.astype(vbuf.dtype), vbuf[buf], (((1,), (0,)), ((), ())),
            precision=prec, preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        return carry

    lax.fori_loop(0, ngroups, group, 0)
    state[0] = first + ngroups
    state[1] = ((ngroups > 0) & (next_groups > 0)).astype(jnp.int32)

    # each head keeps its own D lanes of its row: the G heads of a K/V
    # head come out as G rows of the pool's lane layout
    o = acc[...] / l_ref[...]
    row = lax.broadcasted_iota(jnp.int32, (kvp, lanes), 0)
    lane = lax.broadcasted_iota(jnp.int32, (kvp, lanes), 1)
    own = (lane >= row * D) & (lane < (row + 1) * D)
    for g in range(groups):
        part = jnp.where(own, o[g * kvp:(g + 1) * kvp], 0.0)
        out_ref[0, g:g + 1, :] = jnp.sum(
            part, axis=0, keepdims=True).astype(out_ref.dtype)


def _kernel_call(q, k_new, v_new, kpool, vpool, layer, pt, lengths, scale,
                 interpret, starts=None):
    B, H, D = q.shape
    _, num_pages, page, lanes = kpool.shape
    kv = lanes // D
    G = H // kv
    maxp = pt.shape[1]
    kvp = -(-kv // 8) * 8
    rows = -(-G * kvp // 16) * 16
    dtype = kpool.dtype
    ring = starts is not None       # static: a walk with a start
    if starts is None:
        starts = jnp.zeros_like(lengths)
    qb = _spread_queries(q, kv, kvp, rows, dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, rows, lanes), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec((1, 1, lanes), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec((1, 1, lanes), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, G, lanes), lambda b, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, _ROWS, lanes), dtype),
            pltpu.VMEM((2, _ROWS, lanes), dtype),
            pltpu.VMEM((rows, lanes), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.SMEM((2,), jnp.int32),
            pltpu.SemaphoreType.DMA((2, 2)),
        ])
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, page=page, maxp=maxp,
                          num_pages=num_pages, kv=kv, kvp=kvp, groups=G,
                          ring=ring),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, G, lanes), dtype),
        # the slots run in turn: a slot starts the next one's first fetch
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name=_NAME_WINDOW if ring else _NAME,
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      pt.reshape(-1).astype(jnp.int32), lengths.astype(jnp.int32),
      starts.astype(jnp.int32), qb, k_new.astype(dtype)[:, None, :], v_new.astype(dtype)[:, None, :],
      kpool, vpool)
    # (B, G, KV·D) -> heads in order k * G + g
    return out.reshape(B, G, kv, D).transpose(0, 2, 1, 3).reshape(B, H * D)


def paged_attention(q, k_new, v_new, kpool, vpool, layer, pt, lengths,
                    scale, fallback, starts=None):
    """Attention of one new token a slot over its cached pages and itself.

    ``q`` ``(B, H, D)``; ``k_new`` / ``v_new`` ``(B, KV·D)``, the new
    token's rows; ``kpool`` / ``vpool`` the whole pools; ``layer`` a traced
    scalar; ``pt`` ``(B, MAXP)``; ``lengths`` ``walk_lengths(...)``.  With
    ``starts`` the table is a window layer's ring and ``(lengths, starts)``
    are ``walk_span(...)``'s; without, every walk starts at position 0.
    Returns ``(B, H·D)`` in the pool's dtype.

    The kernel is what a TPU lowering gets; every other platform lowers
    ``fallback()``, the view path, which is also the kernel's reference.
    ``MXNET_FLASH_INTERPRET=1`` runs the kernel interpreted wherever it is
    (CPU numerics)."""
    span = (lengths,) if starts is None else (lengths, starts)

    def kernel(q, k_new, v_new, kpool, vpool, layer, pt, lengths, *starts,
               interpret=False):
        return _kernel_call(q, k_new, v_new, kpool, vpool, layer, pt,
                            lengths, scale, interpret, *starts)

    if _interpret():
        return kernel(q, k_new, v_new, kpool, vpool, layer, pt, *span,
                      interpret=True)
    return lax.platform_dependent(
        q, k_new, v_new, kpool, vpool, layer, pt, *span,
        tpu=kernel, default=lambda *_: fallback())
