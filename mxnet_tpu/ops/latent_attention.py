"""Single-query LATENT attention straight out of the paged latent pool (Pallas
TPU).

The decode step of a ``latent`` layer (``models/layered.py``, ``C == 1``)
attends every cached position of every slot in the absorbed form:

    s[b, h, t] = qf[b, h, :] . row[b, t, :] * scale
    ctx[b, h]  = sum_t softmax_t(s[b, h, :]) * row[b, t, :rank]

``row[b, t]`` is the stored latent row ``[c_kv | k_rope | 0]`` of slot
``b``'s position ``t``, which lies in page ``pt[b, t // page]``; ``qf[b, h]``
the absorbed query ``[q_nope W_kvb,k | q_rope | 0]`` of head ``h``.  K and V
are the SAME row, so every head of a slot scores and sums one page read once.
The XLA form gathers all ``MAXP`` pages of every slot into a ``(B, T,
lanes)`` view and contracts it; this kernel walks each slot's table row over
the pool IN PLACE, only as far as the slot's length.

- The pool ``(NL, NPAGES, page, lanes)`` stays in HBM, whole
  (``memory_space=pl.ANY``).  ``layer``, the flattened table, each slot's
  walk end and the groups' run flags ride in SMEM (scalar prefetch).
- The walk's END is ``pos[b] + 1``: the new token's row is written before
  the walk and a query sees its own position (``page_walk.walk_lengths`` of
  ``pos + 1``); a slot that walks nothing reads 0.
- The walk is ``ops.page_walk``'s: groups of ``_ROWS // page`` pages,
  double-buffered across slots, fetched by ``run_copy`` with blocks of
  ``_BLOCK`` rows (copies of 20 KB pages started 43 ns apart would not bound
  the walk, but a document reserved whole lies in runs anyway).
- Per group two MXU products: the scores ``qf (H, lanes) x rows^T``, then
  ``p (H, rows) x rows[:, :rank]``, operands in the pool's dtype, float32
  accumulation; ``page_walk.online_softmax`` over the groups.  The rows of a
  slot's last group past its end are masked.
- Beside the context the kernel counts, per slot, the rows it walked and the
  copies it started: ``(B, 2)`` int32 out of SMEM.

Work at the ``pangu_ultra_moe_serve`` shapes (128 heads, rank 512 + rope 64
in 640 lanes, bfloat16): a row's 1,152 useful bytes carry 128 x (576 + 512)
x 2 = 278.5k operations, 242 a byte against the v5e's ridge of 240, so
neither the copies nor the MXU alone bound the walk.  VMEM (groups of 1,024
rows): two row buffers 2 x (1024, 640) x 2 B = 2.6 MB, the queries 2 x (128,
640) x 2 B = 0.33 MB, the output block 2 x (128, 512) x 2 B = 0.26 MB, the
float32 accumulator 0.26 MB, the running max and sum (padded to lane tiles)
0.13 MB, a group's scores and weights (128, 1024) float32 about 1.5 MB: about
5 MB of the 16 MB a kernel may take by default.

A CHUNK of ``C`` queries a slot (``latent_chunk_attention``) is the same
computation with ``C x H`` query rows, each with its own causal end, and a
kernel of its own (``mx_latent_paged_attention_chunk``): where the step is
balanced between bytes and operations with one ``(H, lanes)`` block a slot,
the chunk does ``C`` times the operations a row read and is bound by the
MXU.  Its grid is slots x TILES of ``_TILE`` query rows (whole queries: 16
of 128 heads); each tile walks the slot's table in groups of
``_CHUNK_ROWS`` rows, fetched as the step fetches its groups and
double-buffered across tiles and slots alike, only as far as the tile's
LAST query reaches — an early tile stops early.  Row ``r`` of a tile is
query ``r // Hp`` and sees the columns before that query's end; a group
that every row of the tile sees whole takes no mask.  VMEM at the
``pangu`` shapes: the queries 2 x (2048, 640) x 2 B = 5.2 MB, the output
block 2 x (2048, 512) x 2 B = 4.2 MB, the float32 accumulator 4.2 MB, the
running max and sum 2 MB, a group's scores and weights (2048, 512) float32
about 12 MB: past the 16 MB a kernel takes by default (``_CHUNK_VMEM``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import page_walk
from .attention import on_tpu

__all__ = ["latent_paged_attention", "latent_chunk_attention", "supports"]

_ROWS = 1024     # token rows a compute block (a group of pages) holds
_BLOCK = 256     # rows of a group's blocks, each fetched as a run or by page
# profiler_xla._KERNEL_REGIONS reads both kernels' device time under
# mx.latent_attn (the chunk's name holds the step's)
_NAME = "mx_latent_paged_attention"
_CHUNK_NAME = _NAME + "_chunk"
# query rows (queries x padded heads) a chunk tile holds, and token rows its
# compute block holds: on the chip 2,048 x 512 read 81-84% of the operations'
# floor at 16k-33k contexts, 1,024 x 1,024 80-83%, 2,048 x 1,024 53-54%
# (PERF.md, PR 41)
_TILE = 2048
_CHUNK_ROWS = 512
# what a chunk tile may hold in VMEM: its scores and weights (TILE x
# CHUNK_ROWS float32) are most of it, past the 16 MB a kernel gets by default
_CHUNK_VMEM = 96 << 20


def supports(lanes, rank, dtype, page, num_pages):
    """Whether the kernel takes a latent pool of this static structure: one
    ``page_walk.pool_fits`` whose first ``rank`` lanes (the context's) are
    whole tiles too and whose pages divide a fetch block, and at least a
    compute block of them (a run copy's stretch lies inside the pool)."""
    return page_walk.pool_fits(lanes, dtype, page) and rank % 128 == 0 \
        and rank <= lanes and _BLOCK % page == 0 and num_pages * page >= _ROWS


def _open(q_ref, m_ref, l_ref, acc):
    """A unit's queries, and its online softmax with nothing seen yet."""
    q = q_ref[0]
    m_ref[...] = jnp.full_like(m_ref, page_walk.NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc[...] = jnp.zeros_like(acc)
    return q


def _update(q, kbuf, buf, g, ends, m_ref, l_ref, acc, *, scale, rank):
    """One group of rows into the online softmax of the queries ``q``: the
    columns at and past ``ends`` (a position, or each query row's) masked,
    none where ``ends`` is None.  A partly held group's stale rows lie past
    the slot's end, a run's rows past the slot's length too."""
    rows, lanes = kbuf.shape[1] * kbuf.shape[2], kbuf.shape[3]
    keys = kbuf[buf].reshape(rows, lanes)
    s = lax.dot_general(q, keys, (((1,), (1,)), ((), ())),
                        precision=page_walk.precision(keys.dtype),
                        preferred_element_type=jnp.float32) * scale
    if ends is not None:
        col = g * rows + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(col < ends, s, page_walk.NEG_INF)
    page_walk.online_softmax(s, lambda: keys[:, :rank], keys.dtype, m_ref,
                             l_ref, acc)


def _readout(out_ref, acc, l_ref):
    # a unit that walked nothing (an idle or retired slot) reads 0
    l = l_ref[...]
    out_ref[0] = (acc[...] / jnp.where(l > 0, l, 1.0)).astype(out_ref.dtype)


def _kernel(layer_ref, pt_ref, end_ref, run_ref,        # SMEM (prefetch)
            q_ref, pool_ref,                            # inputs
            out_ref, cnt_ref,                           # outputs
            kbuf, acc, m_ref, l_ref, state, sems,       # scratch
            *, scale, rank, page, maxp, num_pages, per, sub, groups):
    b = pl.program_id(0)
    nslots = pl.num_programs(0)
    rows = per * page
    end = end_ref[b]                    # the walk's end, a position
    ngroups = pl.cdiv(end, rows)
    copy = page_walk.run_copy(
        pool_ref, kbuf, sems, pt_ref, end_ref, run_ref, layer_ref[0],
        page=page, maxp=maxp, num_pages=num_pages, per=per, sub=sub,
        groups=groups)

    def tally():
        cnt_ref[b, 0] = end

    def setup():
        q = _open(q_ref, m_ref, l_ref, acc)                 # (H, lanes)
        return *page_walk.next_slot(
            b, nslots, lambda s: pl.cdiv(end_ref[s], rows)), q

    def group(g, buf, q):
        _update(q, kbuf, buf, g, end, m_ref, l_ref, acc, scale=scale,
                rank=rank)

    page_walk.walk(state, copy, b, ngroups, setup, group, first_step=b == 0,
                   nslots=nslots, zero=(kbuf,), counts=cnt_ref, tally=tally)
    _readout(out_ref, acc, l_ref)


def _kernel_call(q, pool, layer, pt, ends, scale, rank, interpret=False,
                 rows=None, runs=True):
    """``(context (B, H, rank) in the pool's dtype, counts (B, 2) int32)``.
    ``rows`` and ``runs`` are the benchmark's and the tests'
    (``benchmark/latent_walk_bench.py``): another group size; every page a
    copy of its own."""
    B, H, _ = q.shape
    _, num_pages, page, lanes = pool.shape
    maxp = pt.shape[1]
    dtype = pool.dtype
    rows = _ROWS if rows is None else rows
    per = rows // page
    sub = min(_BLOCK, rows) // page
    G = -(-maxp // per)
    # whole sublane tiles of heads; a padding head reads 0 and is dropped
    Hp = -(-H // 16) * 16
    q = jnp.pad(q.astype(dtype), ((0, 0), (0, Hp - H),
                                  (0, lanes - q.shape[-1])))
    ends = ends.astype(jnp.int32)
    flags = page_walk.group_runs(pt, ends, page, per, sub, num_pages) if runs \
        else jnp.zeros((B, G), jnp.int32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, Hp, lanes), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((1, Hp, rank), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, per, page, lanes), dtype),
            pltpu.VMEM((Hp, rank), jnp.float32),
            pltpu.VMEM((Hp, 1), jnp.float32),
            pltpu.VMEM((Hp, 1), jnp.float32),
            pltpu.SMEM((2,), jnp.int32),
            pltpu.SemaphoreType.DMA((2,)),
        ])
    ctx, counts = pl.pallas_call(
        functools.partial(_kernel, scale=scale, rank=rank, page=page,
                          maxp=maxp, num_pages=num_pages, per=per, sub=sub,
                          groups=G),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, Hp, rank), dtype),
                   jax.ShapeDtypeStruct((B, 2), jnp.int32)],
        # the slots run in turn: a slot starts the next one's first fetch
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name=_NAME,
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      pt.reshape(-1).astype(jnp.int32), ends, flags.reshape(-1), q, pool)
    return ctx[:, :H], counts


def latent_paged_attention(q, pool, layer, pt, ends, scale, rank, fallback):
    """The latent context of one query a slot over its cached pages.

    ``q`` ``(B, H, lanes)``: the absorbed queries, padded to the pool's
    lanes; ``pool`` the whole latent pool; ``layer`` a traced scalar; ``pt``
    ``(B, MAXP)``, sentinels and all; ``ends`` ``walk_lengths(pt, pos + 1,
    ...)``; ``rank`` the lanes of a row that are the context's.  Returns
    ``(context (B, H, rank) in the pool's dtype, counts (B, 2) int32)``:
    ``counts[b]`` = rows walked, copies started.

    The kernel is what a TPU lowering gets; every other platform lowers
    ``fallback()`` (the gathered rows and ``_attend``'s contractions), which
    is also the kernel's reference, with counts of 0.
    ``MXNET_FLASH_INTERPRET=1`` runs the kernel interpreted wherever it is
    (CPU numerics)."""
    def view(q, *_):
        return fallback(), jnp.zeros((q.shape[0], 2), jnp.int32)

    return on_tpu(functools.partial(_kernel_call, scale=scale, rank=rank),
                  view, q, pool, layer, pt, ends)


def _chunk_kernel(layer_ref, pt_ref, end_ref, run_ref, qend_ref, span_ref,
                  q_ref, pool_ref,                            # inputs
                  out_ref, cnt_ref,                           # outputs
                  kbuf, acc, m_ref, l_ref, state, sems,       # scratch
                  *, scale, rank, page, maxp, num_pages, per, sub, groups,
                  heads, nq):
    b, t = pl.program_id(0), pl.program_id(1)
    nslots, ntiles = pl.num_programs(0), pl.num_programs(1)
    rows = per * page
    tq = acc.shape[0]
    tile = b * ntiles + t
    # the ends of the tile's first and last query: every row sees the
    # columns before ``lo``, none those from ``hi`` on
    lo, hi = span_ref[2 * tile], span_ref[2 * tile + 1]
    ngroups = pl.cdiv(hi, rows)
    copy = page_walk.run_copy(
        pool_ref, kbuf, sems, pt_ref, end_ref, run_ref, layer_ref[0],
        page=page, maxp=maxp, num_pages=num_pages, per=per, sub=sub,
        groups=groups)

    def tally():
        # the slot's walk, counted once however many tiles re-read it
        cnt_ref[b, 0] = end_ref[b]

    def setup():
        q = _open(q_ref, m_ref, l_ref, acc)                 # (TQ, lanes)
        # the tile after this one: the slot's next, or the next slot's first
        last = t + 1 == ntiles
        nb = jnp.minimum(jnp.where(last, b + 1, b), nslots - 1)
        nxt = nb * ntiles + jnp.where(last, 0, t + 1)
        next_groups = jnp.where(last & (b + 1 == nslots), 0,
                                pl.cdiv(span_ref[2 * nxt + 1], rows))
        # each row's end: row r is query r // heads of the tile
        r = lax.broadcasted_iota(jnp.int32, (tq, 1), 0)

        def put(j, ends):
            return jnp.where((r >= j * heads) & (r < (j + 1) * heads),
                             qend_ref[tile * nq + j], ends)
        rend = lax.fori_loop(0, nq, put, jnp.zeros((tq, 1), jnp.int32))
        return nb, next_groups, q, rend

    def group(g, buf, q, rend):
        update = functools.partial(_update, q, kbuf, buf, g, m_ref=m_ref,
                                   l_ref=l_ref, acc=acc, scale=scale,
                                   rank=rank)
        # a group that every row of the tile sees whole takes no mask
        whole = (g + 1) * rows <= lo
        pl.when(whole)(lambda: update(None))
        pl.when(jnp.logical_not(whole))(lambda: update(rend))

    page_walk.walk(state, copy, b, ngroups, setup, group,
                   first_step=(b == 0) & (t == 0), nslots=nslots,
                   zero=(kbuf,), counts=cnt_ref, tally=tally)
    _readout(out_ref, acc, l_ref)


def _chunk_call(q, pool, layer, pt, ends, scale, rank, interpret=False,
                tile=None, rows=None):
    """``(context (B, C, H, rank) in the pool's dtype, counts (B, 2)
    int32)``.  ``tile`` and ``rows`` are the benchmark's and the tests'
    (``benchmark/latent_walk_bench.py``): another tile (query rows), another
    group size."""
    B, C, H, _ = q.shape
    _, num_pages, page, lanes = pool.shape
    maxp = pt.shape[1]
    dtype = pool.dtype
    rows = _CHUNK_ROWS if rows is None else rows
    per = rows // page
    sub = min(_BLOCK, rows) // page
    G = -(-maxp // per)
    # whole sublane tiles of heads; a padding head, or a padding query of
    # the last tile, computes rows that are dropped
    Hp = -(-H // 16) * 16
    nq = max(1, min(C, (_TILE if tile is None else tile) // Hp))
    NT = -(-C // nq)
    Cp = NT * nq
    tq = nq * Hp
    q = jnp.pad(q.astype(dtype), ((0, 0), (0, Cp - C), (0, Hp - H),
                                  (0, lanes - q.shape[-1])))
    ends = ends.astype(jnp.int32)
    # a padding query repeats the slot's last one: it widens no tile's span
    qends = jnp.pad(ends, ((0, 0), (0, Cp - C)), mode="edge")
    by_tile = qends.reshape(B, NT, nq)
    span = jnp.stack([by_tile.min(-1), by_tile.max(-1)], axis=-1)
    slot_end = jnp.max(ends, axis=1)
    flags = page_walk.group_runs(pt, slot_end, page, per, sub, num_pages)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(B, NT),
        in_specs=[
            pl.BlockSpec((1, tq, lanes), lambda b, t, *_: (b, t, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((1, tq, rank), lambda b, t, *_: (b, t, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, per, page, lanes), dtype),
            pltpu.VMEM((tq, rank), jnp.float32),
            pltpu.VMEM((tq, 1), jnp.float32),
            pltpu.VMEM((tq, 1), jnp.float32),
            pltpu.SMEM((2,), jnp.int32),
            pltpu.SemaphoreType.DMA((2,)),
        ])
    ctx, counts = pl.pallas_call(
        functools.partial(_chunk_kernel, scale=scale, rank=rank, page=page,
                          maxp=maxp, num_pages=num_pages, per=per, sub=sub,
                          groups=G, heads=Hp, nq=nq),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, Cp * Hp, rank), dtype),
                   jax.ShapeDtypeStruct((B, 2), jnp.int32)],
        # the tiles run in turn: a tile starts the next one's first fetch
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_CHUNK_VMEM),
        name=_CHUNK_NAME,
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      pt.reshape(-1).astype(jnp.int32), slot_end, flags.reshape(-1),
      qends.reshape(-1), span.reshape(-1), q.reshape(B, Cp * Hp, lanes),
      pool)
    return ctx.reshape(B, Cp, Hp, rank)[:, :C, :H], counts


def latent_chunk_attention(q, pool, layer, pt, ends, scale, rank, fallback):
    """The latent context of ``C`` queries a slot over its cached pages.

    ``q`` ``(B, C, H, lanes)``: the absorbed queries, padded to the pool's
    lanes; ``ends`` ``(B, C)``: each query's walk end, its position + 1 cut
    at the table row's first sentinel (the chunk's own rows are in the pool
    already); the rest as ``latent_paged_attention``.  Returns ``(context
    (B, C, H, rank) in the pool's dtype, counts (B, 2) int32)``:
    ``counts[b]`` = rows the slot's walk reached (its last query's end, once
    however many tiles re-read them), copies started.

    A TPU lowering gets the kernel, every other platform ``fallback()`` with
    counts of 0; ``MXNET_FLASH_INTERPRET=1`` interprets the kernel."""
    def view(q, *_):
        return fallback(), jnp.zeros((q.shape[0], 2), jnp.int32)

    return on_tpu(functools.partial(_chunk_call, scale=scale, rank=rank),
                  view, q, pool, layer, pt, ends)
