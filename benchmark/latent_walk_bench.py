#!/usr/bin/env python3
"""The latent attention's page walk alone at a decode step's shapes, against
the gathered form it replaces.

    python benchmark/latent_walk_bench.py
    python benchmark/latent_walk_bench.py --rows 512,1024,2048 --reps 10

One layer's context of ``--slots`` absorbed queries of ``--heads`` heads
over the latent rows (``--lanes`` stored, the first ``--rank`` the
context's) of contexts drawn as the ``pangu_ultra_serve_sessions24`` cell
draws them: the ``--slots`` stratified quantiles of documents of ``--lo`` to
``--hi`` tokens, each with a question of 64 tokens and half an answer of
128 behind it; a pool of ``--layers`` layers of ``--pages`` pages under a
table of ``--table`` entries, every slot's pages ascending (a document is
reserved whole).  Forms: ``gathered`` (the rows of every slot's whole table
gathered into a ``(B, T, lanes)`` view, scored and summed by two
contractions: what a step lowers to off the TPU), the kernel at each group
size of ``--rows``, and the kernel with every page a copy of its own
(``by_page``).  One JSON line a form: milliseconds a call (the call repeated
inside one jit, each call's queries hanging on the last one's context, the
whole ended by a readback), the step's five layers at that rate, the share
of ``shapes_pangu.latent_walk_min``'s floor (the longer of the rows' bytes
and the operations at the chip's peaks), and the largest difference from
the gathered form over the largest entry (bfloat16 rows and weights: the
two sum in another order and round ``p`` apart; the stated tolerance is
2e-2).  A chip's numbers only: off the TPU it times the interpreter and
says so.

    python benchmark/latent_walk_bench.py --chunk 128
    python benchmark/latent_walk_bench.py --chunk 128 --tiles 1024x1024,2048x512

The CHUNK arm: one slot's question chunk of ``--chunk`` queries whose last
query ends at each of ``--contexts`` (the chunk's own rows last in its
context), over the same pool and a table of ``--table`` entries, the
slot's pages ascending.  Forms: ``dense`` (the slot's whole table gathered
and every query scored against all of it in blocks of heads of at most
``layered._INDEX_BLOCK_BYTES`` of scores, masked past each query's
position: what a chunk lowered to before the chunk kernel, less the product
by ``W_kvb``'s value half, which both forms share) and the chunk kernel at
each ``--tiles`` pair of query rows a tile x rows a group.  One JSON line a
(context, form): milliseconds a layer, the share of the causal pairs'
operations floor (``shapes_pangu.latent_walk_min`` over every (query, row)
pair a query sees, at the chip's bf16 peak), the time over the dense
form's, and the largest difference from the dense form over its largest
entry.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as onp

TOL = 2e-2


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", default="512,1024,2048")
    ap.add_argument("--slots", type=int, default=24)
    ap.add_argument("--heads", type=int, default=128)
    ap.add_argument("--rank", type=int, default=512)
    ap.add_argument("--rope", type=int, default=64)
    ap.add_argument("--lanes", type=int, default=640)
    ap.add_argument("--lo", type=int, default=16384)
    ap.add_argument("--hi", type=int, default=32768)
    ap.add_argument("--layers", type=int, default=5)
    ap.add_argument("--pages", type=int, default=40960)
    ap.add_argument("--table", type=int, default=2072)
    ap.add_argument("--page", type=int, default=16)
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--chunk", type=int, default=0)
    ap.add_argument("--contexts", default="16384,24704,33152")
    ap.add_argument("--tiles", default="1024x1024,1024x512,2048x512")
    args = ap.parse_args()
    if args.chunk:
        return chunk_arm(args)

    import jax
    import jax.numpy as jnp

    from chipbench import harness, shapes, shapes_pangu
    from mxnet_tpu.ops import latent_attention as la

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    B, H, page, NP, maxp = args.slots, args.heads, args.page, args.pages, \
        args.table
    T, lanes, rank = maxp * page, args.lanes, args.rank
    docs = [round((args.lo + (j + 0.5) / B * (args.hi - args.lo)) / 16) * 16
            for j in range(B)]
    ends = onp.minimum(onp.asarray(docs) + 64 + 64, T).astype(onp.int32)
    held = -(-ends // page)
    pt = onp.full((B, maxp), NP, onp.int32)
    nxt = 0
    for b in range(B):
        pt[b, :held[b]] = onp.arange(nxt, nxt + held[b])
        nxt += held[b]
    assert nxt <= NP, "the pool holds fewer pages than the contexts"
    pt, endsj = jnp.asarray(pt), jnp.asarray(ends)
    pool = jax.random.normal(jax.random.PRNGKey(0),
                             (args.layers, NP, page, lanes), jnp.bfloat16)
    q = (jax.random.normal(jax.random.PRNGKey(1), (B, H, lanes),
                           jnp.float32) * 0.05).astype(jnp.bfloat16)
    scale = 1.0 / (128 + args.rope) ** 0.5
    rows_walked = int(ends.sum())
    cfg = {"kv_lora_rank": rank, "qk_rope_head_dim": args.rope,
           "num_attention_heads": H}
    peaks = shapes.peaks_for(dev.device_kind, os.path.join(
        os.path.dirname(harness.__file__), "peaks.json")) if on_tpu else None
    floor_ms = None if peaks is None else 1e3 * shapes_pangu.floor_seconds(
        shapes_pangu.latent_walk_min(cfg, rows_walked), peaks)

    def gathered(q, pool, pt, ends):
        rows = pool.at[2, jnp.minimum(pt, NP - 1)].get(
            mode="promise_in_bounds").reshape(B, T, lanes)
        s = jnp.einsum("bhf,btf->bht", q, rows,
                       preferred_element_type=jnp.float32) * scale
        ok = jnp.arange(T)[None, :] < ends[:, None]
        p = jax.nn.softmax(jnp.where(ok[:, None], s, -1e30),
                           axis=-1).astype(rows.dtype)
        return jnp.einsum("bht,btr->bhr", p, rows[..., :rank],
                          preferred_element_type=jnp.float32
                          ).astype(rows.dtype)

    want = None
    forms = [("gathered", None, None)] + [
        ("kernel", int(r), True) for r in args.rows.split(",")] + [
        ("by_page", la._ROWS, False)]
    for form, rows, runs in forms:
        if form == "gathered":
            fn = gathered
        else:
            fn = lambda q, pool, pt, ends, rows=rows, runs=runs: \
                la._kernel_call(q, pool, jnp.int32(2), pt, ends, scale,
                                rank, not on_tpu, rows=rows, runs=runs)[0]
        ms, ctx = _loop(fn, q, (pool, pt, endsj), args.reps)
        if want is None:
            want = ctx
        diff = float(onp.abs(ctx - want).max() / onp.abs(want).max())
        print(json.dumps({
            "device": dev.device_kind,
            "measures": "device" if on_tpu else "interpreter",
            "form": form, "rows": rows, "slots": B, "heads": H,
            "rows_walked": rows_walked, "ms": round(ms, 4),
            "step_ms": round(ms * args.layers, 3),
            "floor_pct": None if floor_ms is None
            else round(100.0 * floor_ms / ms, 2),
            "max_diff_rel": round(diff, 5), "agrees": diff <= TOL}),
            flush=True)


def _loop(fn, q, operands, reps):
    """``(ms a call, the first call's context)``: the call repeated inside
    one jit, each call's queries hanging on the last one's context."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    @jax.jit
    def looped(q, *operands):
        def body(_, carry):
            q, acc = carry
            ctx = fn(q, *operands)
            q = q + (1e-6 * ctx[..., :1]).astype(q.dtype)
            return q, acc + jnp.sum(ctx.astype(jnp.float32))
        return lax.fori_loop(0, reps, body, (q, jnp.float32(0)))

    first = jax.jit(fn)(q, *operands)
    jax.block_until_ready(looped(q, *operands))
    best = None
    for _ in range(3):
        t0 = time.perf_counter()
        float(looped(q, *operands)[1])
        dt = (time.perf_counter() - t0) / reps
        best = dt if best is None else min(best, dt)
    return best * 1e3, onp.asarray(first.astype(jnp.float32))


def chunk_arm(args):
    import jax
    import jax.numpy as jnp

    from chipbench import harness, shapes, shapes_pangu
    from mxnet_tpu.models import layered
    from mxnet_tpu.ops import latent_attention as la

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    C, H, page, NP, maxp = args.chunk, args.heads, args.page, args.pages, \
        args.table
    T, lanes, rank = maxp * page, args.lanes, args.rank
    scale = 1.0 / (128 + args.rope) ** 0.5
    pool = jax.random.normal(jax.random.PRNGKey(0),
                             (args.layers, NP, page, lanes), jnp.bfloat16)
    q = (jax.random.normal(jax.random.PRNGKey(1), (1, C, H, lanes),
                           jnp.float32) * 0.05).astype(jnp.bfloat16)
    cfg = {"kv_lora_rank": rank, "qk_rope_head_dim": args.rope,
           "num_attention_heads": H}
    peaks = shapes.peaks_for(dev.device_kind, os.path.join(
        os.path.dirname(harness.__file__), "peaks.json")) if on_tpu else None
    hb = max(1, min(H, layered._INDEX_BLOCK_BYTES // (C * T * 4)))
    while H % hb:
        hb -= 1

    def dense(q, pool, pt, ends):
        rows = pool.at[2, jnp.minimum(pt, NP - 1)].get(
            mode="promise_in_bounds").reshape(1, T, lanes)
        ok = jnp.arange(T)[None, None, :] < ends[..., None]     # (1, C, T)

        def block(qb):                                  # (1, C, hb, lanes)
            s = jnp.einsum("bchf,btf->bcht", qb, rows,
                           preferred_element_type=jnp.float32) * scale
            p = jax.nn.softmax(jnp.where(ok[:, :, None], s, -1e30),
                               axis=-1).astype(rows.dtype)
            return jnp.einsum("bcht,btr->bchr", p, rows[..., :rank],
                              preferred_element_type=jnp.float32
                              ).astype(rows.dtype)
        out = jax.lax.map(block, jnp.moveaxis(
            q.reshape(1, C, H // hb, hb, lanes), 2, 0))
        return jnp.moveaxis(out, 0, 2).reshape(1, C, H, rank)

    tiles = [tuple(int(v) for v in t.split("x"))
             for t in args.tiles.split(",")]
    for ctx_len in (int(c) for c in args.contexts.split(",")):
        held = -(-ctx_len // page)
        pt = onp.full((1, maxp), NP, onp.int32)
        pt[0, :held] = onp.arange(held)
        ends = onp.minimum(ctx_len - C + 1 + onp.arange(C), T)[None]
        pairs = int(ends.sum())
        floor_ms = None if peaks is None else 1e3 * shapes_pangu.floor_seconds(
            (0, shapes_pangu.latent_walk_min(cfg, pairs)[1]), peaks)
        operands = (pool, jnp.asarray(pt), jnp.asarray(ends, jnp.int32))
        want, dense_ms = None, None
        for form, tile, rows in [("dense", None, None)] + [
                ("kernel", t, r) for t, r in tiles]:
            fn = dense if form == "dense" else (
                lambda q, pool, pt, ends, tile=tile, rows=rows:
                la._chunk_call(q, pool, jnp.int32(2), pt, ends, scale, rank,
                               not on_tpu, tile=tile, rows=rows)[0])
            ms, ctx = _loop(fn, q, operands, args.reps)
            if want is None:
                want, dense_ms = ctx, ms
            diff = float(onp.abs(ctx - want).max() / onp.abs(want).max())
            print(json.dumps({
                "device": dev.device_kind,
                "measures": "device" if on_tpu else "interpreter",
                "arm": "chunk", "form": form, "tile": tile, "rows": rows,
                "queries": C, "heads": H, "context": ctx_len,
                "pairs": pairs, "ms": round(ms, 4),
                "floor_pct": None if floor_ms is None
                else round(100.0 * floor_ms / ms, 2),
                "of_dense": round(ms / dense_ms, 4),
                "max_diff_rel": round(diff, 5), "agrees": diff <= TOL}),
                flush=True)


if __name__ == "__main__":
    main()
