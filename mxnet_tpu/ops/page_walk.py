"""The page walk the paged kernels share (Pallas TPU): a slot's table row
walked over a pool held in HBM, one group of pages at a time.

``ops.paged_attention``, ``ops.index_scores`` and ``ops.latent_attention``
each keep only what their kernel computes of a group; how the group gets
into VMEM is decided here, once:

- What a walk reaches (``walk_lengths``, ``walk_span``): a slot's walk is
  cut at the first sentinel entry on its way through the table row, so a
  retired slot (a row all sentinel) walks nothing, whatever its stale
  position says.
- Every page id a copy reads from the table is clamped into the pool (a
  run's first id to the pool's size less the run's pages): an out-of-range
  DMA takes the chip down, where a gather only clamps.
- Two copy routines fill a group's buffer: ``page_copy``, a copy a page (K
  and V alike under one pair of semaphores), and ``run_copy``, one copy
  where the group's pages have consecutive ids, else a copy a block of
  ``sub`` pages that are, else a copy a page (``group_runs`` reads which
  are runs off the table, outside the kernel).
- The grid runs the slots (or a slot's query tiles) in turn; v5e has one
  TensorCore.  ``walk`` double-buffers the groups and keeps ONE fetch in
  flight across units: while a group is computed the next one, the same
  unit's or the next unit's first, is in flight, so a unit of two or three
  groups pays no fetch latency of its own and a unit that walks nothing
  passes the prefetch on.
- What a copy never fills is zeros, not whatever VMEM held, where a masked
  row takes part in a product with weight 0: a kernel names those buffers
  and ``walk`` clears them at the grid's first step.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["NEG_INF", "walk_lengths", "walk_span", "group_runs",
           "pool_fits", "precision", "page_copy", "run_copy", "walk",
           "next_slot", "online_softmax"]

NEG_INF = -1e30     # a masked score: exp(NEG_INF - max) is exactly 0


def walk_lengths(pt, pos, page, num_pages):
    """Per slot, how many cached positions a walk reads: ``pos[b]``, cut at
    the row's first sentinel entry and so at the table's width.  ``(B,)``
    int32; the same for every layer."""
    owned = jnp.cumprod((pt < num_pages).astype(jnp.int32), axis=1)
    return jnp.minimum(pos.astype(jnp.int32), owned.sum(axis=1) * page)


def walk_span(pt, pos, page, num_pages, window):
    """A window layer's walk over its RING ``pt`` ``(B, width)``: ``(ends,
    starts)``, both ``(B,)`` int32 positions.  The new token at ``pos[b]``
    sees the cached positions ``starts[b] = max(pos[b] - window + 1, 0)`` to
    ``pos[b] - 1``; the walk runs from the ring entry of the start's page in
    ring order and is cut at the first sentinel entry on its way, so a
    retired slot (all sentinel) has ``ends[b] <= starts[b]``."""
    pos = pos.astype(jnp.int32)
    width = pt.shape[1]
    starts = jnp.maximum(pos - (window - 1), 0)
    first = starts // page
    entries = (first[:, None] + jnp.arange(width, dtype=jnp.int32)[None]) \
        % width
    owned = jnp.cumprod((jnp.take_along_axis(pt, entries, axis=1)
                         < num_pages).astype(jnp.int32), axis=1)
    return jnp.minimum(pos, (first + owned.sum(axis=1)) * page), starts


def group_runs(pt, ends, page, per, sub, num_pages):
    """How ``run_copy`` fetches the groups of ``per`` table entries: ``(B,
    G)`` int32 codes.  A stretch of entries is a RUN where the pages a walk
    to ``ends[b]`` holds of it have consecutive ids and as many pages as the
    stretch has entries, from the first id on, lie inside the pool.
    ``1 << (per // sub)``: the whole group is a run, one copy.  Else bit
    ``k`` says whether its ``k``-th block of ``sub`` entries is one; the
    other blocks go page by page.  (A group the walk does not reach reads
    anything: it is not fetched.)"""
    B, maxp = pt.shape
    G = -(-maxp // per)
    ids = jnp.pad(pt.astype(jnp.int32), ((0, 0), (0, G * per - maxp)),
                  constant_values=num_pages)
    held = -(-ends.astype(jnp.int32) // page)[:, None]

    def runs(n):
        """``(B, G * per // n)``: the stretches of ``n`` entries."""
        block = ids.reshape(B, -1, n)
        j = jnp.arange(n, dtype=jnp.int32)
        first = block[..., :1]
        inside = (first[..., 0] >= 0) & (first[..., 0] + n <= num_pages)
        unheld = (jnp.arange(block.shape[1], dtype=jnp.int32) * n)[
            None, :, None] + j >= held[..., None]
        return jnp.all(unheld | (block == first + j), axis=-1) & inside

    nsub = per // sub
    bits = jnp.sum(runs(sub).reshape(B, G, nsub).astype(jnp.int32)
                   << jnp.arange(nsub, dtype=jnp.int32), axis=-1)
    return jnp.where(runs(per), 1 << nsub, bits)


def pool_fits(lanes, dtype, page):
    """Whether a pool's rows are whole 128-lane tiles of bfloat16 or float32
    and its pages whole sublane tiles (16 rows of bfloat16, 8 of float32):
    what every walk kernel asks of its pool."""
    dtype = jnp.dtype(dtype)
    if dtype not in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)):
        return False
    return lanes % 128 == 0 and page % (8 * 4 // dtype.itemsize) == 0


def precision(dtype):
    """A walk kernel's products: exact float32, or the MXU's native pass."""
    return lax.Precision.HIGHEST if dtype == jnp.float32 \
        else lax.Precision.DEFAULT


def page_copy(pairs, sems, pt_ref, end_ref, layer, *, page, maxp,
              num_pages, per, starts=None):
    """The copy a page: ``copy(slot, g, buf, act)`` calls ``act`` on the
    copy of every page group ``g`` of ``slot`` holds, for each ``(pool,
    rows)`` of ``pairs`` into ``rows[buf]`` (``(2, per * page, lanes)``)
    under semaphore ``sems[i, buf]``.  A slot's walk ends at ``end_ref[slot]``;
    with ``starts`` the row is a RING that the walk enters at the page of
    ``starts[slot]``, else it starts at entry 0."""
    def copy(slot, g, buf, act):
        first = starts[slot] // page if starts is not None else 0
        count = jnp.minimum(
            pl.cdiv(end_ref[slot], page) - first - g * per, per)

        def body(j, carry):
            entry = first + g * per + j
            if starts is not None:
                entry = lax.rem(entry, maxp)
            pid = jnp.clip(pt_ref[slot * maxp + entry], 0, num_pages - 1)
            dst = pl.ds(pl.multiple_of(j * page, page), page)
            for i, (pool, rows) in enumerate(pairs):
                act(pltpu.make_async_copy(pool.at[layer, pid],
                                          rows.at[buf, dst], sems.at[i, buf]))
            return carry
        lax.fori_loop(0, count, body, 0)
    return copy


def run_copy(pool_ref, kbuf, sems, pt_ref, end_ref, run_ref, layer, *, page,
             maxp, num_pages, per, sub, groups):
    """The copy by runs: ``copy(slot, g, buf, act, counted)`` calls ``act``
    on every copy of group ``g`` of ``slot`` into ``kbuf[buf]`` (``(2, per,
    page, lanes)``) under semaphore ``sems[buf]``: one copy where the whole
    group is a run (``run_ref``: ``group_runs``' codes, ``groups`` a slot);
    else, block by block of ``sub`` pages, one copy where the block is a run
    and one a held page where it is not.  ``counted(n)`` is told the copies
    as they are made."""
    nsub = per // sub

    def copy(slot, g, buf, act, counted=lambda n: None):
        entry = slot * maxp + g * per
        code = run_ref[slot * groups + g]
        whole = code == (1 << nsub)

        def stretch(first, n, dst):
            pid = jnp.clip(pt_ref[first], 0, num_pages - n)
            act(pltpu.make_async_copy(pool_ref.at[layer, pl.ds(pid, n)], dst,
                                      sems.at[buf]))

        @pl.when(whole)
        def _():
            stretch(entry, per, kbuf.at[buf])
            counted(1)

        @pl.when(jnp.logical_not(whole))
        def _():
            held = jnp.minimum(pl.cdiv(end_ref[slot], page) - g * per, per)
            for k in range(nsub):
                has = jnp.clip(held - k * sub, 0, sub)
                is_run = (code >> k) & 1 == 1

                @pl.when(is_run & (has > 0))
                def _():
                    stretch(entry + k * sub, sub,
                            kbuf.at[buf, pl.ds(k * sub, sub)])
                    counted(1)

                @pl.when(jnp.logical_not(is_run))
                def _():
                    def body(j, carry):
                        at = k * sub + j
                        pid = jnp.clip(pt_ref[entry + at], 0, num_pages - 1)
                        act(pltpu.make_async_copy(pool_ref.at[layer, pid],
                                                  kbuf.at[buf, at],
                                                  sems.at[buf]))
                        return carry
                    lax.fori_loop(0, has, body, 0)
                    counted(has)
    return copy


def walk(state, copy, unit, ngroups, setup, body, *, first_step, nslots,
         zero=(), counts=None, tally=None):
    """Walk ``ngroups`` groups of the grid step's ``unit`` (the slot whose
    table row ``copy`` reads), double-buffered, the next unit's first
    group in flight behind this unit's last.

    ``state`` ``(2,)`` int32 SMEM scratch: the groups walked so far (a
    group's buffer is its number's parity) and whether the previous unit
    started this one's first group.  ``copy`` is ``page_copy``'s or
    ``run_copy``'s.  At ``first_step`` the grid's first step clears
    ``state``, zeroes the VMEM buffers ``zero`` and column 1 of ``counts``
    ``(nslots, n)`` SMEM, where every copy started is counted for its slot;
    ``tally()`` then writes the kernel's own counts, before any fetch.
    ``setup()`` runs while the first group is in flight and returns the
    unit after this one (its slot), that unit's groups (0 where there is
    none), and what ``body(g, buf, ...)`` takes after the group and its
    buffer."""
    def start(slot, g, buf):
        if counts is None:
            return copy(slot, g, buf, lambda c: c.start())

        def counted(n):
            counts[slot, 1] = counts[slot, 1] + n
        copy(slot, g, buf, lambda c: c.start(), counted)

    @pl.when(first_step)
    def _():
        for ref in zero:
            ref[...] = jnp.zeros_like(ref)
        state[0] = 0
        state[1] = 0
        if counts is not None:
            def clear(s, carry):
                counts[s, 1] = 0
                return carry
            lax.fori_loop(0, nslots, clear, 0)
    first = state[0]
    if tally is not None:
        tally()

    @pl.when((ngroups > 0) & (state[1] == 0))
    def _():
        start(unit, 0, first % 2)

    nxt, next_groups, *held = setup()

    def group(g, carry):
        buf = (first + g) % 2

        @pl.when(g + 1 < ngroups)
        def _():
            start(unit, g + 1, 1 - buf)

        @pl.when((g + 1 == ngroups) & (next_groups > 0))
        def _():
            start(nxt, 0, 1 - buf)

        copy(unit, g, buf, lambda c: c.wait())
        body(g, buf, *held)
        return carry

    lax.fori_loop(0, ngroups, group, 0)
    state[0] = first + ngroups
    state[1] = ((ngroups > 0) & (next_groups > 0)).astype(jnp.int32)


def next_slot(b, nslots, groups_of):
    """For ``walk``'s ``setup`` where the units are the slots: the slot
    after ``b`` and its groups, ``groups_of(slot)``, 0 past the last."""
    nxt = jnp.minimum(b + 1, nslots - 1)
    return nxt, jnp.where(b + 1 < nslots, groups_of(nxt), 0)


def online_softmax(s, rows, dtype, m_ref, l_ref, acc):
    """One group's float32 online-softmax update: the masked scores ``s``
    ``(Q, R)`` against the running max ``m_ref`` and sum ``l_ref`` ``(Q,
    1)``; ``acc`` ``(Q, W)`` takes ``p`` cast to ``dtype`` times
    ``rows()``, the group's ``(R, W)`` rows (read where the product needs
    them)."""
    m_old = m_ref[...]
    m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_old - m_new)
    p = jnp.exp(s - m_new)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
    acc[...] = alpha * acc[...] + lax.dot_general(
        p.astype(dtype), rows(), (((1,), (0,)), ((), ())),
        precision=precision(dtype), preferred_element_type=jnp.float32)
    m_ref[...] = m_new
