"""The selecting attention's index scores straight out of the paged index-key
pool (Pallas TPU).

The decode step of a ``latent_sparse`` layer (``models/layered.py::_select``,
``C == 1``) scores every cached position of every slot before it selects:

    score[b, t] = sum_j w[b, j] * relu(q[b, j, :] . key[b, t, :])

over the ``J`` index heads, ``key[b, t]`` the index-key row of slot ``b``'s
position ``t``, which lies in page ``pt[b, t // page]`` of the pool.  The XLA
form gathers all ``MAXP`` pages of every slot into a ``(B, T, lanes)`` view,
contracts it into a ``(B, J, T)`` float32 block and reduces that over ``j``:
at 32 slots of 33,152 positions, 1.4 GB moved a layer for 0.2 GB of keys.
This kernel walks each slot's table row over the pool IN PLACE, only as far
as the slot's length, and writes ``(B, T)`` float32: no view, no block.

- The pool ``(NL, NPAGES, page, lanes)`` stays in HBM, whole
  (``memory_space=pl.ANY``).  ``layer``, the flattened table, each slot's
  walk end and the groups' run flags ride in SMEM (scalar prefetch).
- The walk's END is ``pos[b] + 1``: the new token's key is in the pool
  before the scores are taken and a query sees its own position
  (``page_walk.walk_lengths`` of ``pos + 1``).
- The walk is ``ops.page_walk``'s: a compute block is a GROUP of ``_ROWS //
  page`` pages, double-buffered across slots.
- An index-key page is 4 KB (16 rows of 256 B), a tenth of a GPT-2 K page:
  fetched page by page a walk of 25k tokens is 1,560 copies and the ISSUE
  RATE, not the bytes, sets the pace — 43 ns a copy, 2.1 ms a layer at the
  ``dots3`` cell's shapes, slower than the XLA form's 1.7 (PERF.md, PR 36).
  So the groups go by ``page_walk.run_copy``: a group whose pages have
  CONSECUTIVE ids is one contiguous ``(rows, lanes)`` stretch of the pool
  and goes as ONE copy; a group that is no run goes by BLOCKS of ``_BLOCK``
  rows, each one copy where its pages are consecutive and a copy a page
  where they are not, so what a broken run costs follows the pages out of
  order, not the group's size.  ``PagePool.alloc`` hands out ascending ids
  and a document is reserved whole, so cached contexts lie in runs.  Which
  stretches are runs is read off the table's entries
  (``page_walk.group_runs``, a few elementwise operations
  over the table outside the kernel): a choice on what the input shows, not
  an option.  A slot's last, partly held stretch is a run where its held
  pages are consecutive and the stretch ends inside the pool: the rows past
  the slot's length are other pages' and masked.
- Per group one MXU contraction ``q (J, lanes) x keys^T (lanes, rows)``,
  operands in the pool's dtype, float32 accumulation; ``relu``, times ``w``
  and the sum over the heads in float32 on the VPU; the ``(1, rows)`` result
  lands in the slot's row of the output.
- Columns at and past a slot's walk end are exactly 0.  The caller's
  ``top_mask`` keys every column that is not ``seen`` to 0 whatever its
  score, so they — or NaN in their place — cannot be chosen ahead of a
  seen one.
- Beside the scores the kernel counts, per slot, the pages it walked, the
  copies it started and the table's width: ``(B, 3)`` int32 out of SMEM.

VMEM at the ``dots3`` shapes (page 16, 128 lanes, 64 heads, bfloat16, table
2,072 pages, groups of 2,048 rows): two key buffers 2 x (2048, 128) x 2 B =
1.05 MB, the queries 2 x (64, 128) x 2 B = 32 KB, the weights 2 x (64, 1)
float32 (padded to lane tiles: 64 KB), one (64, 2048) float32 score block
and its weighted copy 1.05 MB, the output row 2 x (1, 34,816) float32 (8
sublanes a tile: 2.2 MB): under 5 MB of the 16 MB a kernel may take by
default.  SMEM: the table, 32 x 2,072 x 4 B = 265 KB.  On the chip (PERF.md,
PR 36; one layer of 32 slots over 792k live keys, every group a run) groups
of 256 / 512 / 1,024 / 2,048 / 4,096 rows took 1.15 / 0.73 / 0.50 / 0.40 /
0.38 ms — about 0.27 us a group whatever its size (the chain from the
copy's arrival through the MXU to the stored row, which the next group's
does not overlap) and 0.37 ns a row; 4,096 contracts twice the masked
columns of a slot's last group for 5%.
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

__all__ = ["index_scores", "supports"]

_ROWS = 2048     # token rows a compute block (a group of pages) holds
_BLOCK = 256     # rows of a group's blocks, each fetched as a run or by page
# profiler_xla._KERNEL_REGIONS reads the kernel's device time under mx.index
_NAME = "mx_index_scores"


def supports(lanes, dtype, page, num_pages):
    """Whether the kernel takes an index-key pool of this static structure:
    one ``page_walk.pool_fits`` whose pages divide a block, and at least a
    compute block of them (a run copy's stretch lies inside the pool)."""
    return page_walk.pool_fits(lanes, dtype, page) and _BLOCK % page == 0 \
        and num_pages * page >= _ROWS


def _kernel(layer_ref, pt_ref, end_ref, run_ref,        # SMEM (prefetch)
            q_ref, w_ref, pool_ref,                     # inputs
            out_ref, cnt_ref,                           # outputs
            kbuf, state, sems,                          # scratch
            *, page, maxp, num_pages, per, sub, groups):
    b = pl.program_id(0)
    nslots = pl.num_programs(0)
    rows = per * page
    lanes = kbuf.shape[-1]
    layer = layer_ref[0]
    end = end_ref[b]                    # the walk's end, a position
    ngroups = pl.cdiv(end, rows)
    prec = page_walk.precision(kbuf.dtype)
    copy = page_walk.run_copy(
        pool_ref, kbuf, sems, pt_ref, end_ref, run_ref, layer, page=page,
        maxp=maxp, num_pages=num_pages, per=per, sub=sub, groups=groups)

    def tally():
        cnt_ref[b, 0] = pl.cdiv(end, page)
        cnt_ref[b, 2] = maxp

    def setup():
        # what no group writes is 0, not whatever VMEM held
        out_ref[...] = jnp.zeros_like(out_ref)
        q = q_ref[0]                                        # (J, lanes)
        w = w_ref[0]                                        # (J, 1)
        return *page_walk.next_slot(
            b, nslots, lambda s: pl.cdiv(end_ref[s], rows)), q, w

    def group(g, buf, q, w):
        keys = kbuf[buf].reshape(rows, lanes)
        s = lax.dot_general(q, keys, (((1,), (1,)), ((), ())),
                            precision=prec,
                            preferred_element_type=jnp.float32)  # (J, rows)
        r = jnp.sum(jnp.maximum(s, 0.0) * w, axis=0, keepdims=True)
        col = g * rows + lax.broadcasted_iota(jnp.int32, r.shape, 1)
        # a partly held group's other rows are stale: masked, as are a
        # run's rows past the slot's length
        out_ref[0, :, pl.ds(pl.multiple_of(g * rows, rows), rows)] = \
            jnp.where(col < end, r, 0.0)

    page_walk.walk(state, copy, b, ngroups, setup, group, first_step=b == 0,
                   nslots=nslots, counts=cnt_ref, tally=tally)


def _kernel_call(q, w, pool, layer, pt, ends, interpret=False, rows=None,
                 sub=None, runs=True):
    """``(scores (B, T) float32, counts (B, 3) int32)``.  ``rows``, ``sub``
    and ``runs`` are the benchmark's and the tests' (``benchmark/
    index_scores_bench.py``): another group size, another block size (in
    rows); every page a copy of its own."""
    B, J, _ = q.shape
    _, num_pages, page, lanes = pool.shape
    maxp = pt.shape[1]
    dtype = pool.dtype
    rows = _ROWS if rows is None else rows
    per = rows // page
    sub = min(_BLOCK if sub is None else sub, rows) // page
    G = -(-maxp // per)
    # whole sublane tiles of heads; a padding head has weight 0
    Jp = -(-J // 16) * 16
    q = jnp.pad(q.astype(dtype), ((0, 0), (0, Jp - J),
                                  (0, lanes - q.shape[-1])))
    w = jnp.pad(w.astype(jnp.float32), ((0, 0), (0, Jp - J)))[..., None]
    ends = ends.astype(jnp.int32)
    flags = page_walk.group_runs(pt, ends, page, per, sub, num_pages) if runs \
        else jnp.zeros((B, G), jnp.int32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, Jp, lanes), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec((1, Jp, 1), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, G * rows), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, per, page, lanes), dtype),
            pltpu.SMEM((2,), jnp.int32),
            pltpu.SemaphoreType.DMA((2,)),
        ])
    scores, counts = pl.pallas_call(
        functools.partial(_kernel, page=page, maxp=maxp,
                          num_pages=num_pages, per=per, sub=sub, groups=G),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, 1, G * rows), jnp.float32),
                   jax.ShapeDtypeStruct((B, 3), jnp.int32)],
        # the slots run in turn: a slot starts the next one's first fetch
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name=_NAME,
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      pt.reshape(-1).astype(jnp.int32), ends, flags.reshape(-1),
      q, w, pool)
    return scores[:, 0, :maxp * page], counts


def index_scores(q, w, pool, layer, pt, ends, fallback):
    """The index scores of one query a slot over its cached pages.

    ``q`` ``(B, J, index_dim)``; ``w`` ``(B, J)`` float32; ``pool`` the
    whole index-key pool; ``layer`` a traced scalar; ``pt`` ``(B, MAXP)``,
    sentinels and all; ``ends`` ``walk_lengths(pt, pos + 1, ...)``.
    Returns ``(scores (B, MAXP·page) float32, counts (B, 3) int32)``:
    ``counts[b]`` = pages walked, copies started, table width.

    The kernel is what a TPU lowering gets; every other platform lowers
    ``fallback()``, the XLA form (a gather of the key view and two
    contractions), which is also the kernel's reference, with counts of 0.
    ``MXNET_FLASH_INTERPRET=1`` runs the kernel interpreted wherever it is
    (CPU numerics)."""
    def view(q, *_):
        return fallback(), jnp.zeros((q.shape[0], 3), jnp.int32)

    return on_tpu(_kernel_call, view, q, w, pool, layer, pt, ends)
