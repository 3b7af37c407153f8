#!/usr/bin/env python3
"""Per-shape conv fwd/dgrad/wgrad probe for ResNet-50 (BASELINE config 2).

Times every unique convolution of ResNet-50 v1 standalone — forward,
input-gradient (dgrad) and weight-gradient (wgrad) separately — with the
slope method (T(n2)-T(n1) over chained in-jit iterations, cancelling the
per-dispatch host cost exactly).  This
is the measurement VERDICT r4 item 1 asks for: where the 49 ms of
backward-conv time actually lives, per shape, against the 197 TF/s MXU
peak and ~819 GB/s HBM roofline of a v5e chip.

Reference counterpart: the reference autotunes per-shape cuDNN
algorithms (SURVEY.md §3.1 cuDNN autotuned conv paths,
``MXNET_CUDNN_AUTOTUNE_DEFAULT``); the TPU rebuild's analog is choosing
XLA vs a Pallas kernel per shape from measurements like these.

  python benchmark/conv_shape_probe.py [--bs 256] [--n1 10] [--n2 40]
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
from jax import lax

PEAK_TF = 197.0
HBM_GBS = 819.0


def resnet50_convs(bs):
    """(name, k, stride, cin, cout, hw_in, count) for every unique conv
    of ResNet-50 v1 at batch ``bs`` (v1: stride sits in the block's
    first 1x1 for stages 2-4; counts fold identical shapes)."""
    out = [("conv1_7x7s2", 7, 2, 3, 64, 224, 1)]
    # per stage: (hw of the 3x3 work, cin_block_in, bottleneck c, cout, blocks)
    stages = [(56, 64, 64, 256, 3), (28, 256, 128, 512, 4),
              (14, 512, 256, 1024, 6), (7, 1024, 512, 2048, 3)]
    for si, (hw, cin, cb, cout, nb) in enumerate(stages):
        s = 1 if si == 0 else 2
        hw_in = hw * s  # first block's input spatial
        # first block: 1x1 reduce (maybe strided), 3x3, 1x1 expand, downsample
        out.append((f"s{si+1}b1_1x1r", 1, s, cin, cb, hw_in, 1))
        out.append((f"s{si+1}_3x3", 3, 1, cb, cb, hw, nb))
        out.append((f"s{si+1}_1x1e", 1, 1, cb, cout, hw, nb))
        out.append((f"s{si+1}_ds", 1, s, cin, cout, hw_in, 1))
        if nb > 1:  # remaining blocks' 1x1 reduce (cout -> cb)
            out.append((f"s{si+1}_1x1r", 1, 1, cout, cb, hw, nb - 1))
    return out


def conv_fn(k, stride, layout="NCHW"):
    pad = [(k // 2, k // 2)] * 2
    dn = (layout, "OIHW", layout)

    def f(x, w):
        return lax.conv_general_dilated(
            x, w, (stride, stride), pad, dimension_numbers=dn)
    return f


def chained(op):
    """One jitted harness per op with a DYNAMIC trip count: iteration i
    scales the varying arg by a runtime ``ones`` vector (a traced input,
    so XLA cannot constant-fold it to 1.0 and hoist the op out of the
    loop) and accumulates the SUM of the whole output — consuming only
    one element lets XLA narrow the conv to computing that element
    (measured: "26 million TF/s"), the failure mode of the second
    version of this probe.  The sum fuses into the conv epilogue, so
    the extra cost is far below the conv itself."""
    def run(n, ones, *args):
        def body(i, acc):
            a0 = args[0] * ones[i % ones.shape[0]]
            y = op(a0, *args[1:])
            return acc + jnp.sum(y.astype(jnp.float32))
        return lax.fori_loop(0, n, body, jnp.float32(0))
    return jax.jit(run)


def slope_time(f, args, n1, n2, reps=3):
    """T(n2)-T(n1) over (n2-n1): cancels the dispatch/readback cost.

    The iteration-count DELTA must put >= ~0.5 s of device work between
    the two measurements or the slope is noise (the r5 first-probe
    failure mode: 30 ms of signal under 100 ms of jitter produced
    0.000-ms ops and "26 million TF/s").  A pilot run sizes n2
    adaptively."""
    ones = jnp.ones((8,), args[0].dtype)
    float(f(n1, ones, *args))  # one compile serves all counts
    # pilot with an RTT-cancelling delta: a plain T(n1)/n1 estimate is
    # RTT-dominated for sub-ms ops and under-sizes n2 (the "0.000 ms
    # op" failure mode)
    t1 = time.time(); float(f(n1, ones, *args)); t1 = time.time() - t1
    t5 = time.time(); float(f(5 * n1, ones, *args)); t5 = time.time() - t5
    per_it = max((t5 - t1) / (4 * n1), 2e-5)
    n2 = max(n2, n1 + max(500, int(0.8 / per_it)))
    n2 = min(n2, n1 + 20000)
    ts = []
    for n in (n1, n2):
        best = None
        for _ in range(reps):
            t0 = time.time()
            float(f(n, ones, *args))
            dt = time.time() - t0
            best = dt if best is None else min(best, dt)
        ts.append(best)
    return max((ts[1] - ts[0]) / (n2 - n1), 1e-9)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--bs", type=int, default=256)
    ap.add_argument("--n1", type=int, default=10)
    ap.add_argument("--n2", type=int, default=40)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--only", default="",
                    help="comma-separated substring filter on shape names")
    ap.add_argument("--layout", default="NCHW", choices=["NCHW", "NHWC"])
    args = ap.parse_args()
    only = [s for s in args.only.split(",") if s]
    dt_ = jnp.dtype(args.dtype)
    bs = args.bs

    import numpy as onp
    rng = onp.random.RandomState(0)
    rows = []
    tot = {"fwd": 0.0, "dgrad": 0.0, "wgrad": 0.0}
    print(f"{'shape':16s} {'cnt':>3s} | {'fwd ms':>8s} {'TF/s':>6s} | "
          f"{'dgrad ms':>8s} {'TF/s':>6s} | {'wgrad ms':>8s} {'TF/s':>6s} | "
          f"{'GB(min)':>7s} {'AI':>5s}")
    for name, k, s, cin, cout, hw, cnt in resnet50_convs(bs):
        if only and not any(p in name for p in only):
            continue
        f = conv_fn(k, s, args.layout)
        hw_out = hw // s
        if args.layout == "NHWC":
            x = jnp.asarray(rng.rand(bs, hw, hw, cin) - 0.5, dt_)
            y = jnp.asarray(rng.rand(bs, hw_out, hw_out, cout) - 0.5,
                            dt_)
        else:
            x = jnp.asarray(rng.rand(bs, cin, hw, hw) - 0.5, dt_)
            y = jnp.asarray(rng.rand(bs, cout, hw_out, hw_out) - 0.5,
                            dt_)
        w = jnp.asarray(rng.rand(cout, cin, k, k) - 0.5, dt_)
        flops = 2 * bs * hw_out * hw_out * cin * cout * k * k

        def dgrad(dy, ww):
            _, pb = jax.vjp(lambda xx: f(xx, ww), x)
            return pb(dy)[0]

        def wgrad(dy, xx):
            _, pb = jax.vjp(lambda ww: f(xx, ww), w)
            return pb(dy)[0]

        t_f = slope_time(chained(f), (x, w), args.n1, args.n2)
        t_d = slope_time(chained(dgrad), (y, w), args.n1, args.n2)
        t_w = slope_time(chained(wgrad), (y, x), args.n1, args.n2)
        # minimal one-pass traffic for ONE of the three passes (read two
        # operands, write one), bf16:
        nbytes = dt_.itemsize
        gb = (x.size + w.size + y.size) * nbytes / 1e9
        ai = flops / (gb * 1e9)
        row = {"name": name, "count": cnt, "k": k, "stride": s,
               "cin": cin, "cout": cout, "hw": hw,
               "fwd_ms": t_f * 1e3, "dgrad_ms": t_d * 1e3,
               "wgrad_ms": t_w * 1e3, "tf_fwd": flops / t_f / 1e12,
               "tf_dgrad": flops / t_d / 1e12,
               "tf_wgrad": flops / t_w / 1e12,
               "min_gb": gb, "ai": ai}
        rows.append(row)
        tot["fwd"] += cnt * t_f * 1e3
        tot["dgrad"] += cnt * t_d * 1e3
        tot["wgrad"] += cnt * t_w * 1e3
        print(f"{name:16s} x{cnt:2d} | {t_f*1e3:8.3f} {row['tf_fwd']:6.1f} | "
              f"{t_d*1e3:8.3f} {row['tf_dgrad']:6.1f} | "
              f"{t_w*1e3:8.3f} {row['tf_wgrad']:6.1f} | "
              f"{gb:7.3f} {ai:5.0f}")
    print(f"\ncount-weighted totals (ms/step): fwd {tot['fwd']:.1f}  "
          f"dgrad {tot['dgrad']:.1f}  wgrad {tot['wgrad']:.1f}  "
          f"bwd {tot['dgrad']+tot['wgrad']:.1f}")
    with open("/tmp/conv_shape_probe.json", "w") as fh:
        json.dump({"bs": bs, "rows": rows, "totals": tot}, fh, indent=1)
    print("wrote /tmp/conv_shape_probe.json")


if __name__ == "__main__":
    main()
