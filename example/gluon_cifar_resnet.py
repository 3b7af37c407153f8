#!/usr/bin/env python3
"""Train ResNet-18 (thumbnail) on CIFAR-10 with the Gluon API
(reference ``example/image-classification`` workflow).

Uses real CIFAR-10 from ``--data-dir`` when present, else deterministic
synthetic data (the reference's ``--benchmark 1`` dummy-data mode).

    python example/gluon_cifar_resnet.py --epochs 2 --batch-size 64
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as onp


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--epochs", type=int, default=2)
    p.add_argument("--batch-size", type=int, default=64)
    p.add_argument("--lr", type=float, default=0.05)
    p.add_argument("--data-dir", default=os.path.join("~", ".mxnet",
                                                      "datasets", "cifar10"))
    p.add_argument("--synthetic", type=int, default=0,
                   help="use N synthetic samples instead of real CIFAR")
    p.add_argument("--hybridize", type=int, default=1)
    p.add_argument("--eval", type=int, default=1,
                   help="evaluate test-split accuracy each epoch")
    p.add_argument("--lr-decay-epochs", type=str, default="",
                   help="comma-separated epochs at which lr *= 0.1")
    args = p.parse_args(argv)

    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon
    from mxnet_tpu.gluon.data.vision import CIFAR10
    from mxnet_tpu.gluon.model_zoo.vision import get_resnet

    ctx = mx.tpu() if mx.context.num_tpus() else mx.cpu()
    # per-BATCH device-side normalization: per-sample nd transforms would
    # dispatch one device op per image (the reference normalizes on the
    # CPU side of the pipeline)
    mean = mx.nd.array(onp.array([0.4914, 0.4822, 0.4465],
                                 onp.float32).reshape(1, 3, 1, 1))
    std = mx.nd.array(onp.array([0.2470, 0.2435, 0.2616],
                                onp.float32).reshape(1, 3, 1, 1))

    mean = mean.as_in_context(ctx)
    std = std.as_in_context(ctx)

    def prep(x):
        # x: uint8 NHWC batch -> normalized float NCHW on device
        x = x.astype("float32").as_in_context(ctx)
        x = x.transpose((0, 3, 1, 2)) / 255.0
        return (x - mean) / std

    try:
        train = CIFAR10(root=args.data_dir, train=True,
                        synthetic=args.synthetic)
    except Exception:
        print("CIFAR-10 not found; falling back to synthetic data")
        train = CIFAR10(train=True, synthetic=args.synthetic or 512)
    test = None
    if args.eval:
        try:
            test = CIFAR10(root=args.data_dir, train=False,
                           synthetic=args.synthetic and
                           max(1000, args.synthetic // 5))
        except Exception:
            test = CIFAR10(train=False, synthetic=1000)

    # numpy-level batching: ONE host->device transfer per batch (a
    # per-sample DataLoader would pay one transfer per image)
    def batches(ds, bs, shuffle, rng, drop_last=True):
        data, labels = ds._data, ds._label
        order = rng.permutation(len(labels)) if shuffle else \
            onp.arange(len(labels))
        stop = len(order) - bs + 1 if drop_last else len(order)
        for lo in range(0, max(stop, 0 if drop_last else 1), bs):
            idx = order[lo:lo + bs]
            if len(idx) == 0:
                return
            yield mx.nd.array(data[idx]), mx.nd.array(
                labels[idx].astype(onp.float32))

    net = get_resnet(1, 18, thumbnail=True, classes=10)
    net.initialize(mx.init.Xavier(), ctx=ctx)
    if args.hybridize:
        net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": args.lr, "momentum": 0.9,
                             "wd": 1e-4})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    metric = mx.metric.Accuracy()

    decay_epochs = {int(e) for e in args.lr_decay_epochs.split(",") if e}
    for epoch in range(args.epochs):
        if epoch in decay_epochs:
            trainer.set_learning_rate(trainer.learning_rate * 0.1)
        metric.reset()
        tic = time.time()
        n = 0
        rng = onp.random.RandomState(epoch)
        for x, y in batches(train, args.batch_size, True, rng):
            x = prep(x)
            y = y.as_in_context(ctx)
            with autograd.record():
                out = net(x)
                loss = loss_fn(out, y)
            loss.backward()
            trainer.step(x.shape[0])
            metric.update(y, out)
            n += x.shape[0]
        name, acc = metric.get()
        dt = time.time() - tic
        line = f"epoch {epoch}: {name}={acc:.4f} ({n / dt:.0f} samples/s)"
        if test is not None:
            vmetric = mx.metric.Accuracy()
            for x, y in batches(test, args.batch_size, False,
                                onp.random.RandomState(0),
                                drop_last=False):
                x = prep(x)
                y = y.as_in_context(ctx)
                vmetric.update(y, net(x))
            line += f" val-acc={vmetric.get()[1]:.4f}"
        print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
