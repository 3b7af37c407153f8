#!/usr/bin/env python3
"""ResNet-50 ImageNet-shape training throughput (BASELINE config 2:
images/sec/chip, synthetic device-resident data — the reference's
``train_imagenet.py --benchmark 1`` dummy-data mode).

Prints one JSON line.  ResNet-50 fwd ≈ 4.1 GFLOP/img at 224²; train ≈ 3×.
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as onp

PEAK_TFLOPS = 197.0
# ResNet-50 fwd ~= 4.1 GMACs = 8.2 GFLOP/img at 224^2 (2 flops per
# multiply-add; cross-checked against XLA's own model_flops in the step
# trace: 7.4 GFLOP/img conv-only fwd, 22.2 train).  Train ~= 3x fwd.
# The r1/r2 bench used 4.1 GFLOP here — counting MACs as FLOPs — which
# UNDERSTATED MFU by 2x (the r2 "12.7% MFU" was really ~25%).
GFLOP_PER_IMG_TRAIN = 8.2 * 3


def main():
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel
    from mxnet_tpu.gluon.model_zoo.vision import get_resnet

    platform = jax.devices()[0].platform
    on_tpu = platform == "tpu"
    bs = int(os.environ.get("RESNET_BS", "128")) if on_tpu else 4
    hw = 224 if on_tpu else 32
    mx.random.seed(0)

    # NCHW default: measured FASTER end-to-end than NHWC on this chip
    # (r5: 99.7 vs 103.3 ms/step — XLA's internal conv relayout beats
    # the whole-stack channels-last graph); NHWC selectable for A/B
    layout = os.environ.get("RESNET_LAYOUT", "NCHW")
    net = get_resnet(1, 50, classes=1000, layout=layout)
    net.initialize(mx.init.Xavier())
    if on_tpu:
        net.cast("bfloat16")
    trainer = parallel.SPMDTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1, "momentum": 0.9, "wd": 1e-4},
        mesh=parallel.make_mesh({"dp": len(jax.devices())}))

    rng = onp.random.RandomState(0)
    x = rng.rand(bs, 3, hw, hw).astype(
        "bfloat16" if on_tpu else "float32")
    y = rng.randint(0, 1000, bs).astype(onp.float32)
    # 30 steps per dispatch keep the fixed per-dispatch host cost out of
    # the per-step time
    n_steps = 30 if on_tpu else 2
    # transfer ONE batch, broadcast device-side: 30 host copies would
    # ship ~1 GB of identical data
    import jax.numpy as jnp
    sd = mx.nd.from_jax(jnp.broadcast_to(jnp.asarray(x), (n_steps,) + x.shape))
    sl = mx.nd.from_jax(jnp.broadcast_to(jnp.asarray(y), (n_steps,) + y.shape))
    # compile + warmup, then best-of-3 fused multi-step scans
    float(onp.asarray(trainer.run_steps(sd, sl).asnumpy()).reshape(-1)[0])
    best = None
    for _ in range(3 if on_tpu else 1):
        t0 = time.perf_counter()
        float(onp.asarray(trainer.run_steps(sd, sl).asnumpy())
              .reshape(-1)[-1])
        dt = (time.perf_counter() - t0) / n_steps
        best = dt if best is None else min(best, dt)

    imgs = bs / best / max(1, len(jax.devices()))
    rec = {"bench": "resnet50_train", "imgs_per_sec_per_chip":
           round(imgs, 1), "step_ms": round(best * 1e3, 2),
           "batch": bs, "hw": hw, "layout": layout,
           "platform": platform}
    if on_tpu:
        rec["mfu_pct"] = round(
            100 * imgs * GFLOP_PER_IMG_TRAIN / 1e3 / PEAK_TFLOPS, 1)
    print(json.dumps(rec))
    return 0


if __name__ == "__main__":
    sys.exit(main())
