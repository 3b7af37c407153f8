#!/usr/bin/env python3
"""``models.layered.mask_positions`` alone at the ``dots3`` cell's shapes: a
decode step's 32 rows and a question chunk's 128, 2,048 set of 33,152.

    python benchmark/mask_positions_bench.py
    python benchmark/mask_positions_bench.py --rows 32,128 --positions 33152

Beside it the form it replaced in PR 37, kept here as the yardstick only
(``gather_form``: the chosen blocks' mask rows, the blocks' counts and
``seen`` at the positions by three ``take_along_axis``).  Both run inside
one jit, the call repeated with its input hanging on the carry, the whole
ended by a readback.  One JSON line a point: milliseconds a call, and
whether the two forms and ``np.flatnonzero`` agree.  A chip's numbers
only: off the TPU it times the CPU's lowering and says so.
"""
from __future__ import annotations

import argparse
import json
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


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", default="32,128")
    ap.add_argument("--positions", type=int, default=33152)
    ap.add_argument("--topk", type=int, default=2048)
    ap.add_argument("--lo", type=int, default=16400)
    ap.add_argument("--reps", type=int, default=20)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax import lax

    from mxnet_tpu.models import layered

    def tree_form(chosen, k, pos):
        sel = layered.mask_positions(chosen, k)
        return sel, sel <= pos[:, None]

    def looped(form, k):
        def run(chosen, pos):
            def body(_, carry):
                flip, acc = carry
                sel, ok = form(chosen ^ flip, k, pos)
                # never true, and the compiler cannot know: the next call's
                # input hangs on this one's result
                return (sel[:1, :1] < 0) & ok[:1, :1], acc + jnp.sum(sel)
            return lax.fori_loop(
                0, args.reps, body,
                (jnp.zeros((1, 1), bool), jnp.int32(0)))[1]
        return jax.jit(run)

    dev = jax.devices()[0]
    T, k = args.positions, args.topk
    rng = onp.random.RandomState(0)
    for N in (int(n) for n in args.rows.split(",")):
        pos = onp.linspace(min(args.lo, T - 1), T - 1, N).astype(onp.int32)
        pos = onp.maximum(pos, k - 1)
        chosen = onp.zeros((N, T), bool)
        for n in range(N):
            chosen[n, rng.choice(pos[n] + 1, k, replace=False)] = True
        c, p = jnp.asarray(chosen), jnp.asarray(pos)
        old = jax.jit(gather_form, static_argnums=1)(c, k, p)
        new = jax.jit(tree_form, static_argnums=1)(c, k, p)
        agree = bool(jnp.all(old[0] == new[0]) & jnp.all(old[1] == new[1]))
        exact = bool((onp.asarray(new[0]) == onp.stack(
            [onp.flatnonzero(r) for r in chosen])).all())
        for name, form in (("gather_form", gather_form),
                           ("mask_positions", tree_form)):
            run = looped(form, k)
            run(c, p).block_until_ready()
            took = []
            for _ in range(5):
                t0 = time.perf_counter()
                run(c, p).block_until_ready()
                took.append((time.perf_counter() - t0) / args.reps * 1e3)
            print(json.dumps({
                "device": dev.device_kind, "measured_on_chip":
                dev.platform == "tpu", "rows": N, "positions": T, "topk": k,
                "form": name, "ms_a_call": round(min(took), 4),
                "ms_a_call_median": round(sorted(took)[2], 4),
                "forms_agree": agree, "equals_flatnonzero": exact}),
                flush=True)


if __name__ == "__main__":
    main()
