"""The routed experts' two grouped products as ONE Pallas TPU kernel that
visits only the row tiles holding live rows, and only the groups that have
rows there, reading each visited expert's weights once a visit.

``ops/moe.py::routed_experts`` sorts the ``M = N x top_k`` (token, expert)
rows by held expert; rows routed to experts this chip does not hold sort
behind the last group.  Per group ``g`` (an expert) of ``sizes[g]`` rows:

    ys[rows of g] = (silu(xs Wg_g) * (xs Wu_g)).astype(dtype) @ Wd_g

with ``W_gu`` ``(G, H, 2I)`` (gate and up side by side) and ``W_d`` ``(G, I,
H)``.  ``lax.ragged_dot`` (one call a product) does work in proportion to
the COMPILED rows: at the cells' shapes it falls from 86% of the touched
experts' bytes' time at 96 rows to about 40% at 1,024 (PERF.md, PR 39).

- The grid is ``(visits, blocks)``.  A VISIT is one (row tile, group) pair
  with rows of the group in the tile, laid out as ``megablox``'s
  ``make_group_metadata`` lays them (``visit_empty_groups=False``): groups
  in order, a group's tiles in order, so visits of one tile are
  consecutive.  The visit count is a traced scalar: empty groups, and the
  tiles that hold only rows bound for other chips' experts, are not in the
  grid at all — no copy, no product.
- A visit streams its group's weights in ``nk`` blocks of ``(tk, 2I)`` rows
  of ``W_gu`` (the gate and up products, accumulated in float32 over
  ``H``), then ``ni`` blocks of ``(ti, H)`` rows of ``W_d``.  Both are whole
  rows of the stacked array: contiguous copies of about 4 MB (``_BLOCK``),
  large enough to stream at the HBM peak (PR 36 met 43 ns a copy; a block
  of 32 KB would be issue-bound).  While one block is contracted the next
  is in flight; an index map that repeats the previous block's index skips
  the copy (``W_gu`` during the down blocks, ``W_d`` before them).
- ``silu(g) * u`` in float32, cast to the rows' dtype between the two
  products, as the XLA form casts it; float32 accumulation throughout.
- The row tile ``(tm, H)`` of ``xs`` is copied once a visit (its index does
  not change over the visit's blocks).  The output tile ``(tm, H)`` float32
  is zeroed at a tile's first visit and each visit writes its group's rows
  (a select on the row's place), so the rows of a tile's other groups stay.
  Rows of no group — the tail bound for other chips — are 0 in a visited
  tile and unwritten in a tile no visit reaches: the caller masks them.
- ``base``: the weights may be a stacked run, ``(L x n, H, 2I)``, of which
  the group sizes are one layer's ``n``; a visit of group ``g`` reads
  ``base + g`` in place (no slice, no copy of the run).

Tile sizes follow the static shapes only (``plan``): ``tm`` the largest of
128 / 64 / 32 / 16 / 8 rows that divides ``M`` (and holds whole sublane
tiles of the dtype).  A visit's product is ``tm`` rows by a whole expert:
at 128 rows the MXU needs about half of the time the expert's bytes take,
so the tile never grows past it, and a larger tile leaves fewer groups
straddling two tiles (each straddle reads that expert a second time).

The rule rests on the chip (``benchmark/moe_gmm_bench.py``, PERF.md PR 39;
three draws a shape, 32 held experts of 256): the kernel at ``plan``'s
tiles took 75.8-81.9% of the touched weights' bytes' time at 96 rows
(``lax.ragged_dot`` 71.8-79.0), 79.7-81.3% at 256 (57.8-58.4), 83.2-84.3%
at 512 (38.2-38.3) and 79.7-82.0% at 1,024 (38.1-38.2): faster at every
shape, so no shape keeps ``ragged_dot`` on a TPU.  Blocks of 2 MB read
within a point of 4 MB, 8 MB two to four points lower; tiles of 32 rows
were 2-4 points faster than 128 at 256 and 512 rows (a later issue's).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["plan", "grouped_swiglu", "metadata"]

# profiler_xla._KERNEL_REGIONS reads the kernel's device time under
# mx.moe_experts
_NAME = "mx_moe_gmm"
_BLOCK = 4 << 20        # bytes of one weight block: about 5 us at the peak
_ROW_TILES = (128, 64, 32, 16, 8)


def _lanes(width, other, itemsize, block=_BLOCK):
    """The rows of a ``(rows, other)`` weight block: the largest multiple of
    128 that divides ``width`` with the block under ``block`` bytes (at
    least 128), or ``width`` whole where it is no multiple of 128."""
    if width % 128:
        return width
    best = 128
    for t in range(128, width + 1, 128):
        if width % t == 0 and t * other * itemsize <= block:
            best = t
    return best


def plan(M, H, I, dtype):
    """``(tm, tk, ti)`` for ``M`` sorted rows of width ``H`` and experts of
    width ``I``, or ``None`` where no row tile fits: ``tm`` rows a tile,
    ``tk`` rows of ``W_gu`` and ``ti`` rows of ``W_d`` a block."""
    dtype = jnp.dtype(dtype)
    sub = 8 * 4 // dtype.itemsize
    tm = next((t for t in _ROW_TILES if t <= M and M % t == 0 and t % sub == 0),
              None)
    if tm is None:
        return None
    return (tm, _lanes(H, 2 * I, dtype.itemsize),
            _lanes(I, H, dtype.itemsize))


def metadata(sizes, M, tm):
    """``(group_ids, tile_ids, offsets, visits)`` of the group sizes
    ``sizes`` ``(G,)`` over ``M`` rows in tiles of ``tm``: visit ``v`` is
    group ``group_ids[v]``'s rows in tile ``tile_ids[v]``; ``offsets``
    ``(G + 1,)`` the groups' first rows; ``visits`` how many there are.  The
    arrays hold ``M // tm + G - 1`` entries, the most visits there can be."""
    G = sizes.shape[0]
    sizes = sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    tiles = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    vend = jnp.cumsum(tiles)
    V = M // tm + G - 1
    v = jnp.arange(V, dtype=jnp.int32)
    # the first group whose visits end past v (an empty group's end is its
    # predecessor's, so it is never chosen)
    gid = jnp.minimum(jnp.sum(vend[None, :] <= v[:, None], axis=1), G - 1)
    gid = gid.astype(jnp.int32)
    tid = first[gid] + v - (vend - tiles)[gid]
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return gid, tid.astype(jnp.int32), offsets, vend[-1]


def _kernel(gid_ref, tid_ref, off_ref, base_ref,           # SMEM (prefetch)
            x_ref, wgu_ref, wd_ref,                         # VMEM blocks
            out_ref,
            gu_acc, a_buf, y_acc,                           # scratch
            *, nk, ni, tk, ti, inner, prec):
    del base_ref
    v, s = pl.program_id(0), pl.program_id(1)
    tm = x_ref.shape[0]

    @pl.when(s == 0)
    def _():
        gu_acc[...] = jnp.zeros_like(gu_acc)

    @pl.when(s < nk)
    def _():
        k0 = pl.multiple_of(s * tk, tk)
        gu_acc[...] += jnp.dot(x_ref[:, pl.ds(k0, tk)], wgu_ref[...],
                               precision=prec,
                               preferred_element_type=jnp.float32)

    @pl.when(s == nk - 1)
    def _():
        g, u = gu_acc[:, :inner], gu_acc[:, inner:]
        a_buf[...] = (jax.nn.silu(g) * u).astype(a_buf.dtype)
        y_acc[...] = jnp.zeros_like(y_acc)

    @pl.when(s >= nk)
    def _():
        j0 = pl.multiple_of((s - nk) * ti, ti)
        y_acc[...] += jnp.dot(a_buf[:, pl.ds(j0, ti)], wd_ref[...],
                              precision=prec,
                              preferred_element_type=jnp.float32)

    @pl.when(s == nk + ni - 1)
    def _():
        tile, g = tid_ref[v], gid_ref[v]
        first = (v == 0) | (tid_ref[jnp.maximum(v - 1, 0)] != tile)
        row = tile * tm + lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
        mine = (row >= off_ref[g]) & (row < off_ref[g + 1])
        prev = jnp.where(first, 0.0, out_ref[...])
        out_ref[...] = jnp.where(mine, y_acc[...], prev)


def grouped_swiglu(xs, w_gu, w_down, sizes, base=0, interpret=False,
                   tiles=None):
    """``(M, H)`` float32: group ``g``'s rows of ``xs`` ``(M, H)`` (sorted,
    ``sizes`` ``(G,)`` int32 rows a group, the rest a dead tail) through
    ``silu(x Wg) * (x Wu)`` and ``W_d`` of expert ``base + g`` of ``w_gu``
    ``(G', H, 2I)`` / ``w_down`` ``(G', I, H)``.  Rows of no group are 0
    or unwritten.  ``tiles`` overrides ``plan``'s ``(tm, tk, ti)`` (the
    benchmark's)."""
    M, H = xs.shape
    I = w_down.shape[1]
    G = sizes.shape[0]
    tm, tk, ti = tiles or plan(M, H, I, xs.dtype)
    nk, ni = H // tk, I // ti
    gid, tid, offsets, visits = metadata(sizes, M, tm)
    base = jnp.reshape(jnp.asarray(base, jnp.int32), (1,))
    prec = lax.Precision.HIGHEST if xs.dtype == jnp.float32 \
        else lax.Precision.DEFAULT
    item = jnp.dtype(xs.dtype).itemsize
    # two buffers of each block, the output tile and the three scratches
    vmem = (2 * (tm * H * item + tk * 2 * I * item + ti * H * item
                 + tm * H * 4)
            + tm * 2 * I * 4 + tm * I * item + tm * H * 4)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(visits, nk + ni),
        in_specs=[
            pl.BlockSpec((tm, H), lambda v, s, gid, tid, *_: (tid[v], 0)),
            pl.BlockSpec((None, tk, 2 * I),
                         lambda v, s, gid, tid, off, b:
                         (b[0] + gid[v], jnp.minimum(s, nk - 1), 0)),
            pl.BlockSpec((None, ti, H),
                         lambda v, s, gid, tid, off, b:
                         (b[0] + gid[v], jnp.maximum(s - nk, 0), 0)),
        ],
        out_specs=pl.BlockSpec((tm, H),
                               lambda v, s, gid, tid, *_: (tid[v], 0)),
        scratch_shapes=[pltpu.VMEM((tm, 2 * I), jnp.float32),
                        pltpu.VMEM((tm, I), xs.dtype),
                        pltpu.VMEM((tm, H), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_kernel, nk=nk, ni=ni, tk=tk, ti=ti, inner=I,
                          prec=prec),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, H), jnp.float32),
        # a tile's visits run in turn and share its output block
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=max(32 << 20, vmem + vmem // 4)),
        name=_NAME,
        interpret=interpret,
    )(gid, tid, offsets, base, xs, w_gu, w_down)
