#!/usr/bin/env python3
"""Fused 1x1 conv-bwd Pallas kernel vs XLA's dgrad+wgrad pair, per
ResNet-50 1x1 shape — the VERDICT r4 item 1 kill measurement.

Harness notes (hard-won, r5):
- the slope method needs >= ~0.5 s of device work between the two trip
  counts or per-dispatch host jitter swamps the signal;
- XLA's algebraic simplifier defeats naive consumption: sum(dx) pushes
  THROUGH a matmul (sum(dy@w) = contract-then-tiny), and even
  sum((s*dy@w)^2) hoists the loop-invariant part via the scalar rule —
  the XLA arm varies the input by DYNAMIC SLICE (no algebraic escape);
- the Pallas arm scales dy INSIDE the kernel (opaque to XLA) so the
  variance costs no HBM traffic, and consumes one element per output
  (a pallas_call cannot be narrowed).

  python benchmark/conv_fused_bench.py [--bs 256] [--only s1]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

PEAK_TF = 197.0
HBM_GBS = 819.0
PREC = lax.Precision.DEFAULT


def shapes(bs):
    # (name, hw, ci, co) for every stride-1 1x1 of ResNet-50 v1
    return [("s1_1x1r", 56, 256, 64), ("s1_1x1e", 56, 64, 256),
            ("s2_1x1r", 28, 512, 128), ("s2_1x1e", 28, 128, 512),
            ("s3_1x1r", 14, 1024, 256), ("s3_1x1e", 14, 256, 1024),
            ("s4_1x1r", 7, 2048, 512), ("s4_1x1e", 7, 512, 2048)]


def slope(f, args, n1=5):
    """Pilot with an RTT-cancelling delta (T(5*n1)-T(n1)) — a plain
    T(n1)/n1 pilot is RTT-dominated for sub-ms ops and under-sizes n2
    (the r5 "0.000 ms" rows)."""
    float(f(n1, *args))
    t1 = time.time(); float(f(n1, *args)); t1 = time.time() - t1
    t5 = time.time(); float(f(5 * n1, *args)); t5 = time.time() - t5
    per_it = max((t5 - t1) / (4 * n1), 2e-5)
    n2 = n1 + max(500, min(20000, int(0.8 / per_it)))
    best = {}
    for n in (n1, n2):
        b = None
        for _ in range(3):
            t0 = time.time()
            float(f(n, *args))
            dt = time.time() - t0
            b = dt if b is None else min(b, dt)
        best[n] = b
    return max((best[n2] - best[n1]) / (n2 - n1), 1e-9)


def pallas_pair_call(p, ci, co, tp):
    grid = p // tp

    def kern(s_ref, dy_ref, x_ref, w_ref, dx_ref, dw_ref):
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _():
            dw_ref[:] = jnp.zeros_like(dw_ref)
        d = dy_ref[:] * s_ref[0, 0]
        dx_ref[:] = jnp.dot(d, w_ref[:], precision=PREC,
                            preferred_element_type=jnp.float32
                            ).astype(dx_ref.dtype)
        dw_ref[:] += jnp.dot(d.T, x_ref[:], precision=PREC,
                             preferred_element_type=jnp.float32)

    def call(s, dy, x, w):
        return pl.pallas_call(
            kern, grid=(grid,),
            in_specs=[
                pl.BlockSpec((1, 1), lambda i: (0, 0),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((tp, co), lambda i: (i, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((tp, ci), lambda i: (i, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((co, ci), lambda i: (0, 0),
                             memory_space=pltpu.VMEM)],
            out_specs=[
                pl.BlockSpec((tp, ci), lambda i: (i, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((co, ci), lambda i: (0, 0),
                             memory_space=pltpu.VMEM)],
            out_shape=[jax.ShapeDtypeStruct((p, ci), jnp.bfloat16),
                       jax.ShapeDtypeStruct((co, ci), jnp.float32)],
        )(s, dy, x, w)
    return call


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--bs", type=int, default=256)
    ap.add_argument("--only", default="")
    args = ap.parse_args()
    import numpy as onp

    from mxnet_tpu.ops.conv_fused import _pick_tile

    rng = onp.random.RandomState(0)
    rows = []
    print(f"{'shape':10s} | {'xla pair ms':>11s} | {'pallas ms':>9s} | "
          f"{'xla 2-read roof':>15s} | {'fused 1-read roof':>17s} | "
          f"{'tp':>5s}")
    for name, hw, ci, co in shapes(args.bs):
        if args.only and args.only not in name:
            continue
        p = args.bs * hw * hw
        dyb = jnp.asarray(rng.rand(p + 8, co) - 0.5, jnp.bfloat16)
        dy = dyb[:p]
        x = jnp.asarray(rng.rand(p, ci) - 0.5, jnp.bfloat16)
        w = jnp.asarray(rng.rand(co, ci) - 0.5, jnp.bfloat16)

        def xla_run(n, dyb_, x_, w_):
            def body(i, acc):
                d = lax.dynamic_slice(dyb_, (i % 8, 0), (p, co))
                dx = jnp.dot(d, w_, precision=PREC,
                             preferred_element_type=jnp.float32
                             ).astype(jnp.bfloat16)
                dw = lax.dot_general(
                    d, x_, (((0,), (0,)), ((), ())), precision=PREC,
                    preferred_element_type=jnp.float32)
                return acc + jnp.sum((dx * dx).astype(jnp.float32)) \
                    + jnp.sum(dw * dw)
            return lax.fori_loop(0, n, body, jnp.float32(0))

        tp = _pick_tile(p, ci, co)
        t_p = None
        if tp:
            call = pallas_pair_call(p, ci, co, tp)

            def pallas_run(n, ones, dy_, x_, w_):
                def body(i, acc):
                    s = ones[i % 8].reshape(1, 1)
                    dx, dw = call(s, dy_, x_, w_)
                    return acc + dx[0, 0].astype(jnp.float32) + dw[0, 0]
                return lax.fori_loop(0, n, body, jnp.float32(0))

            ones = jnp.ones((8,), jnp.bfloat16)
            # tracelint: disable=TL003 -- bench sweep: each loop iteration times a DIFFERENT shape config, one jit each is the point
            t_p = slope(jax.jit(pallas_run), (ones, dy, x, w))
        # tracelint: disable=TL003 -- bench sweep: each loop iteration times a DIFFERENT shape config, one jit each is the point
        t_x = slope(jax.jit(xla_run), (dyb, x, w))
        roof2 = (2 * p * co + 2 * p * ci) * 2 / HBM_GBS / 1e9
        roof1 = (p * co + 2 * p * ci) * 2 / HBM_GBS / 1e9
        row = {"name": name, "p": p, "ci": ci, "co": co, "tp": tp,
               "xla_ms": t_x * 1e3,
               "pallas_ms": t_p * 1e3 if t_p else None,
               "xla_roof_ms": roof2 * 1e3, "fused_roof_ms": roof1 * 1e3}
        rows.append(row)
        print(f"{name:10s} | {row['xla_ms']:11.3f} | "
              f"{(row['pallas_ms'] or -1):9.3f} | {roof2 * 1e3:15.3f} | "
              f"{roof1 * 1e3:17.3f} | {tp:5d}")
    with open("/tmp/conv_fused_bench.json", "w") as fh:
        json.dump(rows, fh, indent=1)
    print("wrote /tmp/conv_fused_bench.json")


if __name__ == "__main__":
    main()
