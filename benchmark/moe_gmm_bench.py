#!/usr/bin/env python3
"""The routed experts' two grouped products alone, at the serve cells'
shapes: ``lax.ragged_dot`` (the XLA form ``ops/moe.py`` lowered before PR
39) against the ``mx_moe_gmm`` kernel (``ops/grouped_matmul.py``) at row
tiles around the one ``plan`` picks, and ``megablox.gmm`` where it compiles.

    python benchmark/moe_gmm_bench.py
    python benchmark/moe_gmm_bench.py --shapes dots3_chunk --draws 5

A shape is ``M = N x top_k`` compiled rows over 32 held experts of 256, the
group sizes drawn as the cells draw them: each of the ``N`` tokens picks
``top_k`` distinct experts uniformly, the held ones are 0-31, the rest sort
behind the last group.  One JSON line a (shape, draw, form): milliseconds a
call of both products (the call repeated inside one jit, every operand of
the form hanging on the carry, the whole ended by a readback), and beside it
the least time — the touched experts' weights at 819 GB/s — and the share
of it.  A chip's numbers only: off the TPU it times the interpreter and
says so.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as onp

# (tokens N, top_k, hidden H, expert width I): the step and the question
# chunk of each routed cell
SHAPES = {"trinity_step": (24, 4, 3072, 3072),
          "dots3_step": (32, 8, 5120, 1536),
          "trinity_chunk": (128, 4, 3072, 3072),
          "dots3_chunk": (128, 8, 5120, 1536)}
HELD, EXPERTS, HBM = 32, 256, 819e9


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--draws", type=int, default=3)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--tiles", default="",
                    help="row tiles of the kernel to time besides plan's")
    ap.add_argument("--blocks", default="",
                    help="weight block sizes (MiB) to time at plan's row "
                         "tile besides plan's own")
    ap.add_argument("--out", default="")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax import lax

    from mxnet_tpu.ops import grouped_matmul as gm

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    rng = onp.random.RandomState(0)
    out = open(args.out, "a") if args.out else None

    def say(**row):
        line = json.dumps({"device": dev.device_kind, "measures": "device"
                           if on_tpu else "interpreter", **row})
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    def xla(xs, wgu, wd, sizes):
        prec = lax.Precision.DEFAULT
        gu = lax.ragged_dot(xs, wgu, sizes, precision=prec,
                            preferred_element_type=jnp.float32)
        g, u = jnp.split(gu, 2, axis=-1)
        a = (jax.nn.silu(g) * u).astype(xs.dtype)
        return lax.ragged_dot(a, wd, sizes, precision=prec,
                              preferred_element_type=jnp.float32)

    def megablox(tm):
        from jax.experimental.pallas.ops.tpu.megablox import gmm

        def f(xs, wgu, wd, sizes):
            with jax.default_matmul_precision("bfloat16"):
                gu = gmm(xs, wgu, sizes, jnp.float32, (tm, 512, 1024),
                         None, None, False, not on_tpu)
                g, u = jnp.split(gu, 2, axis=-1)
                a = (jax.nn.silu(g) * u).astype(xs.dtype)
                return gmm(a, wd, sizes, jnp.float32, (tm, 512, 1024),
                           None, None, False, not on_tpu)
        return f

    compiled = {}

    def timed(form, fn, xs, wgu, wd, sizes):
        """``(ms a call, the call's output)``; one compile a (shape, form):
        the group sizes are an operand."""
        if form not in compiled:
            @jax.jit
            def looped(xs, wgu, wd, sizes):
                def body(_, carry):
                    xs, acc = carry
                    y = fn(xs, wgu, wd, sizes)
                    s = jnp.sum(y[:1])
                    # the next call's rows hang on this call's output
                    return xs + (s * 0).astype(xs.dtype), acc + s
                return lax.fori_loop(0, args.reps, body,
                                     (xs, jnp.float32(0)))
            compiled[form] = (looped, jax.jit(fn))
        looped, once = compiled[form]
        jax.block_until_ready(looped(xs, wgu, wd, sizes))
        best = None
        for _ in range(3):
            t0 = time.perf_counter()
            float(looped(xs, wgu, wd, sizes)[1])
            dt = (time.perf_counter() - t0) / args.reps
            best = dt if best is None else min(best, dt)
        return best * 1e3, onp.asarray(once(xs, wgu, wd, sizes))

    for name in args.shapes.split(","):
        N, K, H, I = SHAPES[name]
        M = N * K
        key = jax.random.PRNGKey(1)
        wgu = (jax.random.normal(key, (HELD, H, 2 * I), jnp.bfloat16)
               * (H ** -0.5)).astype(jnp.bfloat16)
        wd = (jax.random.normal(jax.random.fold_in(key, 1), (HELD, I, H),
                                jnp.bfloat16) * (I ** -0.5)).astype(
                                    jnp.bfloat16)
        xs = jax.random.normal(jax.random.fold_in(key, 2), (M, H),
                               jnp.bfloat16)
        expert_bytes = 3 * H * I * 2
        base = gm.plan(M, H, I, jnp.bfloat16)
        tms = sorted({base[0]} | {int(t) for t in args.tiles.split(",")
                                  if t and M % int(t) == 0})
        for draw in range(args.draws):
            picks = onp.stack([rng.choice(EXPERTS, K, replace=False)
                               for _ in range(N)])
            load = onp.bincount(picks.ravel(), minlength=EXPERTS)[:HELD]
            sizes = jnp.asarray(load, jnp.int32)
            touched = int((load > 0).sum())
            least = touched * expert_bytes / HBM * 1e3
            common = dict(shape=name, draw=draw, rows=M, live=int(load.sum()),
                          touched=touched,
                          load_max_over_mean=round(float(
                              load.max() / load[load > 0].mean()), 3),
                          least_ms=round(least, 4))
            forms = [("ragged_dot", xla)]
            forms += [(f"kernel_tm{tm}", lambda x, a, b, s, tm=tm:
                       gm.grouped_swiglu(x, a, b, s, interpret=not on_tpu,
                                         tiles=(tm,) + base[1:]))
                      for tm in tms]
            for mib in (float(b) for b in args.blocks.split(",") if b):
                blk = int(mib * (1 << 20))
                t = (base[0], gm._lanes(H, 2 * I, 2, blk),
                     gm._lanes(I, H, 2, blk))
                if t != base:
                    forms.append((f"kernel_tm{t[0]}_tk{t[1]}_ti{t[2]}",
                                  lambda x, a, b, s, t=t: gm.grouped_swiglu(
                                      x, a, b, s, interpret=not on_tpu,
                                      tiles=t)))
            forms.append((f"megablox_tm{base[0]}", megablox(base[0])))
            live = int(load.sum())
            want = None
            for form, fn in forms:
                try:
                    ms, got = timed((name, form), fn, xs, wgu, wd, sizes)
                    got = got[:live]
                except Exception as e:      # a form the chip refuses
                    say(**common, form=form, error=repr(e)[:300])
                    continue
                want = got if want is None else want
                # the live rows against the XLA form's, relative to its
                # largest entry
                diff = float(onp.abs(got - want).max()
                             / max(onp.abs(want).max(), 1e-30)) if live else 0.0
                say(**common, form=form, ms=round(ms, 4),
                    bytes_time_pct=round(100 * least / ms, 1),
                    max_rel_diff=diff)


if __name__ == "__main__":
    main()
