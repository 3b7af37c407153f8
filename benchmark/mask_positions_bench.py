#!/usr/bin/env python3
"""``models.layered.mask_positions`` alone at the ``dots3`` cell's shapes: a
decode step's 32 rows and a question chunk's 128, 2,048 set of 33,152; and
beside it the selected positions' page ids, ``block_pages``, out of a table
of 2,072 pages a slot with ids up to 65,535.

    python benchmark/mask_positions_bench.py
    python benchmark/mask_positions_bench.py --rows 32,128 --positions 33152

Positions leg: the gather form ``mask_positions`` replaced, kept here as
the yardstick only (``gather_form``: the chosen blocks' mask rows, the
blocks' counts and ``seen`` at the positions by three ``take_along_axis``).

Page-id leg: up to ``--slots`` rows are that many slots of one query (the
step), more rows are one slot's queries (a chunk), which share its table
row.  Three forms, each with the positions it needs: ``take_along_axis``
over the table (the yardstick: what ``block_pages`` replaced),
``block_pages`` (a second product over ``block_positions``' one-hot) and
``joined`` (the page columns appended to the rank product's, a candidate
kept here only).

Every form runs inside one jit, the call repeated with its input hanging on
the carry, the whole ended by a readback.  One JSON line a point:
milliseconds a call, and whether the forms agree with each other and with
NumPy.  A chip's numbers only: off the TPU it times the CPU's lowering and
says so.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as onp


def gather_form(chosen, k, pos):
    """PR 29's ``mask_positions`` and its caller's ``ok``."""
    import jax.numpy as jnp
    N, T = chosen.shape
    W = 128
    nb = -(-T // W)
    blocks = jnp.pad(chosen, ((0, 0), (0, nb * W - T))).reshape(N, nb, W)
    count = jnp.sum(blocks, axis=-1, dtype=jnp.int32)
    before = jnp.cumsum(count, axis=-1) - count
    j = jnp.arange(k, dtype=jnp.int32)
    blk = jnp.sum(before[:, None, :] + count[:, None, :] <= j[None, :, None],
                  axis=-1, dtype=jnp.int32)
    blk = jnp.minimum(blk, nb - 1)
    nth = j[None] - jnp.take_along_axis(before, blk, axis=1)
    rows = jnp.take_along_axis(blocks, blk[..., None], axis=1)
    tri = jnp.triu(jnp.ones((W, W), jnp.bfloat16))
    running = jnp.einsum("nkw,wv->nkv", rows.astype(jnp.bfloat16), tri,
                         preferred_element_type=jnp.bfloat16)
    here = rows & (running == (nth + 1)[..., None].astype(jnp.bfloat16))
    sel = blk * W + jnp.argmax(here, axis=-1).astype(jnp.int32)
    seen = jnp.arange(T, dtype=jnp.int32)[None] <= pos[:, None]
    return sel, jnp.take_along_axis(seen, sel, axis=1)


def joined_form(chosen, k, table, page, npages):
    """``block_positions`` + ``block_pages`` with the page columns appended
    to the rank product's 128 (padded to 256 lanes) and broadcast to every
    row, in place of a second product over the one-hot."""
    import jax.numpy as jnp
    N, T = chosen.shape
    B, n = table.shape
    W = 128
    nb = -(-T // W)
    L = math.lcm(page, W)
    per = L // page
    parts = max(1, -(-(npages - 1).bit_length() // 8))
    spans = -(-nb * W // L)
    ids = jnp.pad(table, ((0, 0), (0, spans * per - n)))
    cols = jnp.stack([(ids >> (8 * i)) & 0xFF for i in range(parts)], -1)
    cols = jnp.repeat(cols.reshape(B, spans, per * parts), L // W,
                      axis=1)[:, :nb].astype(jnp.bfloat16)
    cols = jnp.repeat(cols, N // B, axis=0)                 # (N, nb, p)
    blocks = jnp.pad(chosen, ((0, 0), (0, nb * W - T))).reshape(N, nb, W)
    tri = jnp.triu(jnp.ones((W, W), jnp.bfloat16))
    rank = jnp.einsum("nbw,wv->nbv", blocks.astype(jnp.bfloat16), tri,
                      preferred_element_type=jnp.float32)
    count = rank[:, :, W - 1].astype(jnp.int32)
    rank = jnp.where(blocks, rank, 0).astype(jnp.bfloat16)
    upto = jnp.cumsum(count, axis=-1)[:, :nb - 1]
    j = jnp.arange(k, dtype=jnp.int32)
    passed = upto[:, None, :] <= j[None, :, None]
    blk = jnp.sum(passed, axis=-1, dtype=jnp.int32)
    nth = j[None] - jnp.sum(
        jnp.where(passed, count[:, None, :nb - 1], 0), axis=-1)
    onehot = jnp.arange(nb, dtype=jnp.int32) == blk[..., None]
    picked = jnp.einsum("nkb,nbw->nkw", onehot.astype(jnp.bfloat16),
                        jnp.concatenate([rank, cols], -1),
                        preferred_element_type=jnp.float32)
    here = picked[..., :W] == (nth + 1)[..., None].astype(jnp.float32)
    sel = blk * W + jnp.argmax(here, axis=-1).astype(jnp.int32)
    got = picked[..., W:].reshape(N, k, per, parts)
    inner = (sel % L) // page
    byte = jnp.sum(jnp.where(
        (inner[..., None] == jnp.arange(per, dtype=jnp.int32))[..., None],
        got, 0.0), axis=-2).astype(jnp.int32)
    return sel, sum(byte[..., i] << (8 * i) for i in range(parts))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", default="32,128")
    ap.add_argument("--positions", type=int, default=33152)
    ap.add_argument("--topk", type=int, default=2048)
    ap.add_argument("--lo", type=int, default=16400)
    ap.add_argument("--page", type=int, default=16)
    ap.add_argument("--npages", type=int, default=65536)
    ap.add_argument("--slots", type=int, default=32)
    ap.add_argument("--reps", type=int, default=20)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax import lax

    from mxnet_tpu.models import layered

    def tree_form(chosen, k, pos):
        sel = layered.mask_positions(chosen, k)
        return sel, sel <= pos[:, None]

    def looped(form, *rest):
        """``form(chosen, *rest)`` ``--reps`` times in one jit: each call's
        input hangs on the last one's result."""
        def run(chosen, *rest):
            def body(_, carry):
                flip, acc = carry
                out = [o.reshape(o.shape[0], -1)[:1, :1]
                       for o in form(chosen ^ flip, *rest)]
                # never true, and the compiler cannot know: the next call's
                # input hangs on this one's result
                return (out[0] < 0) & (out[1] < 0), acc + sum(
                    jnp.sum(o.astype(jnp.int32)) for o in out)
            return lax.fori_loop(
                0, args.reps, body,
                (jnp.zeros((1, 1), bool), jnp.int32(0)))[1]
        return jax.jit(run)

    def timed(run, *operands):
        run(*operands).block_until_ready()
        took = []
        for _ in range(5):
            t0 = time.perf_counter()
            run(*operands).block_until_ready()
            took.append((time.perf_counter() - t0) / args.reps * 1e3)
        return round(min(took), 4), round(sorted(took)[2], 4)

    dev = jax.devices()[0]
    T, k, page, npages = args.positions, args.topk, args.page, args.npages
    n = T // page
    rng = onp.random.RandomState(0)
    for N in (int(v) for v in args.rows.split(",")):
        head = {"device": dev.device_kind,
                "measured_on_chip": dev.platform == "tpu", "rows": N,
                "positions": T, "topk": k}
        pos = onp.linspace(min(args.lo, T - 1), T - 1, N).astype(onp.int32)
        pos = onp.maximum(pos, k - 1)
        chosen = onp.zeros((N, T), bool)
        for r in range(N):
            chosen[r, rng.choice(pos[r] + 1, k, replace=False)] = True
        c, p = jnp.asarray(chosen), jnp.asarray(pos)
        old = jax.jit(gather_form, static_argnums=1)(c, k, p)
        new = jax.jit(tree_form, static_argnums=1)(c, k, p)
        agree = bool(jnp.all(old[0] == new[0]) & jnp.all(old[1] == new[1]))
        exact = bool((onp.asarray(new[0]) == onp.stack(
            [onp.flatnonzero(r) for r in chosen])).all())
        for name, form in (("gather_form", gather_form),
                           ("mask_positions", tree_form)):
            best, median = timed(looped(lambda ch, pp, f=form: f(ch, k, pp)),
                                 c, p)
            print(json.dumps(dict(
                head, form=name, ms_a_call=best, ms_a_call_median=median,
                forms_agree=agree, equals_flatnonzero=exact)), flush=True)

        # the page-id leg: B slots of C queries, one table row a slot
        B, C = (N, 1) if N <= args.slots else (1, N)
        table = rng.randint(0, npages, (B, n)).astype(onp.int32)
        table[:, ::7] = npages - 1
        t = jnp.asarray(table)

        def take_along(chosen, table):
            sel = layered.mask_positions(chosen, k)
            return sel, jnp.take_along_axis(
                table[:, None, :], sel.reshape(B, C, k) // page, axis=2)

        def one_hot(chosen, table):
            sel, blocks = layered.block_positions(chosen, k)
            sel = sel.reshape(B, C, k)
            return sel, layered.block_pages(blocks.reshape(B, C, k, -1),
                                            sel, table, page, npages)

        def joined(chosen, table):
            return joined_form(chosen, k, table, page, npages)

        want = onp.take_along_axis(
            table[:, None, :],
            onp.asarray(new[0]).reshape(B, C, k) // page, axis=2)
        for name, form in (("take_along_axis", take_along),
                           ("block_pages", one_hot), ("joined", joined)):
            ids = onp.asarray(jax.jit(form)(c, t)[1]).reshape(B, C, k)
            best, median = timed(looped(form), c, t)
            print(json.dumps(dict(
                head, leg="page_ids", slots=B, queries_a_slot=C, pages=n,
                largest_id=npages - 1, form=name, ms_a_call=best,
                ms_a_call_median=median,
                ids_equal_take_along_axis=bool((ids == want).all()))),
                flush=True)


if __name__ == "__main__":
    main()
