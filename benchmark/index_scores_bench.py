#!/usr/bin/env python3
"""The index-score kernel alone at a decode step's shapes: group sizes, the
page-by-page walk against run copies, and the XLA form it replaces.

    python benchmark/index_scores_bench.py
    python benchmark/index_scores_bench.py --rows 256,512,1024 --slots 32

One layer's scores of ``--slots`` queries of ``--heads`` index heads over
contexts spread evenly between ``--lo`` and ``--hi`` tokens, in a pool of
``--pages`` pages under a table of ``--table`` entries (the defaults are the
``dots3_note_serve`` cell's).  Four tables: ``runs`` (every slot's pages
ascending, as ``PagePool.alloc`` hands a reserved document out), ``shuffled``
(no two neighbours consecutive: every page a copy), ``mixed`` (the first 95%
of a context in runs, its tail shuffled) and ``tail`` (all but the last 20
pages in runs, those DESCENDING, as a LIFO free list hands a question's and
an answer's pages out after churn).  ``by_page`` switches the run copies off
over the ``runs`` table: the plain walk.  One JSON line a
point: milliseconds a call (the call repeated inside one jit, the whole ended
by a readback), the live keys' bytes over that time, copies a page walked.
A chip's numbers only: off the TPU it times the interpreter and says so.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as onp


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", default="256,512,1024")
    ap.add_argument("--slots", type=int, default=32)
    ap.add_argument("--heads", type=int, default=64)
    ap.add_argument("--lo", type=int, default=16400)
    ap.add_argument("--hi", type=int, default=33100)
    ap.add_argument("--pages", type=int, default=65536)
    ap.add_argument("--table", type=int, default=2072)
    ap.add_argument("--page", type=int, default=16)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--tables", default="",
                    help="comma-separated: by_page, runs, mixed, tail, "
                         "shuffled (default: all five)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax import lax

    from mxnet_tpu.ops import index_scores as ix
    from mxnet_tpu.ops import paged_attention as pa

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    B, J, page, NP, maxp = args.slots, args.heads, args.page, args.pages, \
        args.table
    T = maxp * page
    rng = onp.random.RandomState(0)
    pos = onp.linspace(args.lo, min(args.hi, T - 1), B).astype(onp.int32)
    held = -(-(pos + 1) // page)

    def table(kind):
        pt = onp.full((B, maxp), NP, onp.int32)
        nxt = 0
        for b in range(B):
            ids = onp.arange(nxt, nxt + held[b])
            nxt += held[b]
            if kind == "shuffled":
                ids = onp.concatenate([ids[1::2], ids[0::2]])
            elif kind == "mixed":
                cut = int(held[b] * 0.95)
                tail = ids[cut:]
                ids = onp.concatenate([ids[:cut], tail[1::2], tail[0::2]])
            elif kind == "tail":
                ids = onp.concatenate([ids[:-20], ids[-20:][::-1]])
            pt[b, :held[b]] = ids
        assert nxt <= NP
        return jnp.asarray(pt)

    pool = jax.random.normal(jax.random.PRNGKey(0), (2, NP, page, 128),
                             jnp.bfloat16)
    q = jnp.asarray(rng.randn(B, J, 128), jnp.bfloat16)
    w = jnp.asarray(rng.rand(B, J), jnp.float32)
    posj = jnp.asarray(pos)
    live_bytes = int((pos + 1).sum()) * 128 * 2

    def timed(fn, pt):
        """ms a call of ``fn(q, w, pool, pt, ends) -> (scores, counts)``."""
        ends = pa.walk_lengths(pt, posj + 1, page, NP)

        @jax.jit
        def looped(q, w, pool, pt, ends):
            def body(_, carry):
                w, pt, acc, _ = carry
                s, c = fn(q, w, pool, pt, ends)
                # the next call's weights and table hang on this call's
                # scores: nothing of a call can be hoisted out of the loop
                # (the table's entries never change: the sum is finite)
                total = jnp.sum(s)
                return (w + 1e-12 * s[:, :J],
                        pt + (total > 3e38).astype(jnp.int32), acc + total, c)
            return lax.fori_loop(
                0, args.reps, body,
                (w, pt, jnp.float32(0), jnp.zeros((B, 3), jnp.int32)))

        out = looped(q, w, pool, pt, ends)
        jax.block_until_ready(out)
        best = None
        for _ in range(3):
            t0 = time.perf_counter()
            out = looped(q, w, pool, pt, ends)
            float(out[2])
            dt = (time.perf_counter() - t0) / args.reps
            best = dt if best is None else min(best, dt)
        return best * 1e3, onp.asarray(out[3])

    def view(q, w, pool, pt, ends):
        keys = pool.at[1, jnp.minimum(pt, NP - 1)].get(
            mode="promise_in_bounds").reshape(B, T, -1)
        s = jnp.einsum("bjd,btd->bjt", q, keys,
                       preferred_element_type=jnp.float32)
        return jnp.einsum("bj,bjt->bt", w, jax.nn.relu(s)), \
            jnp.zeros((B, 3), jnp.int32)

    def say(**row):
        ms = row["ms"]
        print(json.dumps({
            "device": dev.device_kind, "measures": "device" if on_tpu
            else "interpreter", "slots": B, "heads": J,
            "live_tokens": int((pos + 1).sum()), **row,
            "live_gb_s": round(live_bytes / ms / 1e6, 1)}), flush=True)

    tables = {k: table(k) for k in ("runs", "shuffled", "mixed", "tail")}
    if on_tpu:
        ms, _ = timed(view, tables["runs"])
        say(form="xla_view", ms=round(ms, 4))
    for rows in (int(r) for r in args.rows.split(",")):
        for name, kind, runs in (("by_page", "runs", False),
                                 ("runs", "runs", True),
                                 ("mixed", "mixed", True),
                                 ("tail", "tail", True),
                                 ("shuffled", "shuffled", True)):
            if args.tables and name not in args.tables.split(","):
                continue
            fn = lambda q, w, pool, pt, ends: ix._kernel_call(
                q, w, pool, jnp.int32(1), pt, ends, not on_tpu, rows=rows,
                runs=runs)
            ms, counts = timed(fn, tables[kind])
            say(form="kernel", rows=rows, table=name, ms=round(ms, 4),
                copies_a_page=round(float(counts[:, 1].sum())
                                    / float(counts[:, 0].sum()), 4))


if __name__ == "__main__":
    main()
