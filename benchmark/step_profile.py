#!/usr/bin/env python3
"""Per-XLA-op profile of a full fused train step (ResNet-50 or BERT).

Uses ``mxnet_tpu.profiler_xla`` (the trace-parsing device profiler,
SURVEY.md §5.1 parity) to attribute every microsecond of the compiled
SPMD step to an HLO op / source jaxpr op — the tool the reference gets
from engine hooks, recovered here from the ``jax.profiler`` device trace.

  python benchmark/step_profile.py resnet  [--bs 256] [--by tf_op]
  python benchmark/step_profile.py bert    [--bs 64]  [--by category]
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as onp

from benchmark import mem_fields

PEAK_TFLOPS = 197.0


def emit_row(row):
    """Measured row into the telemetry event stream (kind ``bench``) —
    a ``MXNET_TELEMETRY_JSONL`` recording carries the phase rows next
    to the compile events in one schema (``tools/telemetry_report.py``
    renders both; the printed human tables stay as-is)."""
    from mxnet_tpu import telemetry
    telemetry.emit("bench", **row)


def build_resnet(bs):
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel
    from mxnet_tpu.gluon.model_zoo.vision import get_resnet

    on_tpu = jax.devices()[0].platform == "tpu"
    hw = 224 if on_tpu else 32
    mx.random.seed(0)
    net = get_resnet(1, 50, classes=1000)
    net.initialize(mx.init.Xavier())
    if on_tpu:
        net.cast("bfloat16")
    trainer = parallel.SPMDTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1, "momentum": 0.9, "wd": 1e-4},
        mesh=parallel.make_mesh({"dp": len(jax.devices())}))
    rng = onp.random.RandomState(0)
    x = mx.nd.array(rng.rand(bs, 3, hw, hw).astype(
        "bfloat16" if on_tpu else "float32"))
    y = mx.nd.array(rng.randint(0, 1000, bs).astype(onp.float32))
    return trainer, x, y


def build_bert(bs):
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel
    from mxnet_tpu.models import BERTConfig, BERTModel

    on_tpu = jax.devices()[0].platform == "tpu"
    seq = 128
    mx.random.seed(0)
    cfg = BERTConfig(vocab_size=30528, max_length=seq, num_layers=12,
                     units=768, num_heads=12, hidden_size=3072,
                     dtype="bfloat16" if on_tpu else "float32")
    bert = BERTModel(cfg, use_pooler=False, use_mlm=True)

    class _MLMHeadOnly(gluon.Block):
        def __init__(self):
            super().__init__()
            self.bert = bert

        def forward(self, tokens):
            return self.bert(tokens)[-1]

    net = _MLMHeadOnly()
    net.initialize(mx.init.Normal(0.02))
    trainer = parallel.SPMDTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "adamw",
        {"learning_rate": 1e-4},
        mesh=parallel.make_mesh({"dp": len(jax.devices())}))
    rng = onp.random.RandomState(0)
    x = mx.nd.array(rng.randint(0, cfg.vocab_size, (bs, seq)))
    y = mx.nd.array(rng.randint(0, cfg.vocab_size, (bs, seq)))
    return trainer, x, y


def build_gpt(bs):
    """BASELINE config 5: GPT-2 774M (36L/1280U/20H/5120FF, seq 512) —
    same geometry as benchmark/transformer_bench.py."""
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel
    from mxnet_tpu.models import GPT, GPTConfig

    on_tpu = jax.devices()[0].platform == "tpu"
    mx.random.seed(0)
    cfg = GPTConfig(vocab_size=50304, max_length=512, num_layers=36,
                    units=1280, num_heads=20, hidden_size=5120,
                    dtype="bfloat16") if on_tpu else \
        GPTConfig(vocab_size=512, max_length=64, num_layers=2, units=64,
                  num_heads=4, hidden_size=128)
    gpt = GPT(cfg)
    gpt.initialize(mx.init.Normal(0.02))
    trainer = parallel.SPMDTrainer(
        gpt, gluon.loss.SoftmaxCrossEntropyLoss(), "adamw",
        {"learning_rate": 1e-4},
        mesh=parallel.make_mesh({"dp": len(jax.devices())}))
    rng = onp.random.RandomState(0)
    L = 512 if on_tpu else 16
    toks = rng.randint(0, cfg.vocab_size, (bs, L + 1))
    return trainer, mx.nd.array(toks[:, :-1]), mx.nd.array(toks[:, 1:])


def build_transformer(bs):
    """BASELINE config 4: Transformer-big seq2seq (1024U/4096FF/16H,
    6+6 layers, seq 256)."""
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel
    from mxnet_tpu.models import TransformerSeq2Seq as Transformer

    on_tpu = jax.devices()[0].platform == "tpu"
    V, L = (32768, 256) if on_tpu else (512, 16)
    mx.random.seed(0)
    net = Transformer(V, units=1024 if on_tpu else 64,
                      hidden_size=4096 if on_tpu else 128,
                      num_heads=16 if on_tpu else 4,
                      num_enc_layers=6 if on_tpu else 2,
                      num_dec_layers=6 if on_tpu else 2,
                      max_length=L, dropout=0.0,
                      dtype="bfloat16" if on_tpu else "float32")
    net.initialize(mx.init.Xavier())

    class _Wrap(gluon.Block):
        def __init__(self):
            super().__init__()
            self.net = net

        def forward(self, both):
            return self.net(both[:, 0], both[:, 1])

    wrap = _Wrap()
    rng = onp.random.RandomState(0)
    src = rng.randint(0, V, (bs, L))
    tgt = rng.randint(0, V, (bs, L))
    both = onp.stack([src, tgt], axis=1)
    trainer = parallel.SPMDTrainer(
        wrap, gluon.loss.SoftmaxCrossEntropyLoss(), "adam",
        {"learning_rate": 1e-4},
        mesh=parallel.make_mesh({"dp": len(jax.devices())}))
    return trainer, mx.nd.array(both), mx.nd.array(tgt)


def measure_optimizer_apply(params, opt_name, reps=10):
    """Fused-vs-legacy optimizer-apply phase over a ParameterDict (the
    imperative ``gluon.Trainer`` path): synthesizes grads, times ``reps``
    steady-state steps per mode, and counts optimizer-apply dispatches.
    Returns ``(n_params, [(mode, dispatches_per_step, ms_per_step)])``.
    One implementation shared by step_profile and step_breakdown so the
    two benchmarks can't drift on methodology."""
    import time

    import jax.numpy as jnp

    from mxnet_tpu import gluon
    from mxnet_tpu.ndarray.ndarray import waitall
    from mxnet_tpu.optimizer import optimizer as opt_impl

    live = [p for p in params.values() if p.grad_req != "null"]
    rng = onp.random.RandomState(0)
    for p in live:
        p.grad()._rebind(jnp.asarray(rng.randn(*p.shape) * 1e-3,
                                     p.data()._data.dtype))
    prev = os.environ.get("MXNET_FUSED_OPTIMIZER")
    rows = []
    try:
        for mode, env in (("fused", "1"), ("legacy", "0")):
            os.environ["MXNET_FUSED_OPTIMIZER"] = env
            tr = gluon.Trainer(params, opt_name,
                               {"learning_rate": 1e-4}, kvstore=None)
            tr.step(1)          # compile + state creation
            waitall()
            opt_impl.reset_apply_counters()
            t0 = time.perf_counter()
            for _ in range(reps):
                tr.step(1)
            waitall()
            dt = (time.perf_counter() - t0) / reps * 1e3
            c = opt_impl.apply_counters
            disp = (c["fused_calls"] + c["fallback_params"]) / reps
            rows.append((mode, disp, dt))
    finally:
        if prev is None:
            os.environ.pop("MXNET_FUSED_OPTIMIZER", None)
        else:
            os.environ["MXNET_FUSED_OPTIMIZER"] = prev
    return len(live), rows


def measure_fused_step(n_layers=200, units=64, bs=32, reps=10,
                       intervals=(1, 4), opt_name="adamw", warm=2):
    """Fused-step phase: the whole train step (forward + loss + backward
    + optimizer apply) as ONE donated-buffer XLA executable
    (``Trainer.fused_step``) vs today's phase-by-phase chain (jitted
    CachedOp forward → tape backward → fused ``multi_update`` apply) on
    the BASELINE 200-param workload (``n_layers`` chained bias-free
    Dense(units) layers = n_layers (units,units) f32 params).  Sweeps the
    gradient-accumulation window (``Trainer(update_interval=N)``): the N
    amortizes the optimizer apply + its host bookkeeping over the window.
    Returns ``(n_params, [(mode, host_dispatches_per_step, ms_per_step)])``
    — one implementation shared by step_profile and step_breakdown.
    ``host_dispatches_per_step`` counts registry invokes + jitted apply
    calls on the phase path, and fused-step executable invocations on the
    fused path (exactly 1)."""
    import time

    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.gluon.fused_step import (step_counters,
                                            reset_step_counters)
    from mxnet_tpu.ndarray.ndarray import waitall
    from mxnet_tpu.ops import registry as reg
    from mxnet_tpu.optimizer import optimizer as opt_impl

    rng = onp.random.RandomState(0)
    X = rng.randn(bs, units).astype(onp.float32)
    Y = rng.randn(bs, 1).astype(onp.float32)
    loss_l = gluon.loss.L2Loss()

    def build():
        mx.random.seed(0)
        net = nn.HybridSequential()
        with net.name_scope():
            for _ in range(n_layers - 1):
                net.add(nn.Dense(units, use_bias=False, in_units=units))
            net.add(nn.Dense(1, use_bias=False, in_units=units))
        net.initialize(mx.init.Xavier())
        net.hybridize()
        return net

    rows = []

    # -- phase-by-phase (today's path) --------------------------------- #
    net = build()
    trainer = gluon.Trainer(net.collect_params(), opt_name,
                            {"learning_rate": 1e-4}, kvstore=None)
    x, y = mx.nd.array(X), mx.nd.array(Y)

    def phase_step():
        with mx.autograd.record():
            loss = loss_l(net(x), y)
        loss.backward()
        trainer.step(bs)
        return loss

    for _ in range(warm):
        phase_step()
    waitall()
    invokes = [0]
    orig_invoke = reg.invoke

    def counting_invoke(*a, **k):
        invokes[0] += 1
        return orig_invoke(*a, **k)

    reg.invoke = counting_invoke
    opt_impl.reset_apply_counters()
    try:
        t0 = time.perf_counter()
        for _ in range(reps):
            phase_step()
        waitall()
        dt = (time.perf_counter() - t0) / reps * 1e3
    finally:
        reg.invoke = orig_invoke
    disp = (invokes[0] + opt_impl.apply_counters["fused_calls"]
            + opt_impl.apply_counters["fallback_params"]) / reps
    rows.append(("phase-by-phase", disp, dt))

    # -- fused step, accumulate window sweep --------------------------- #
    for N in intervals:
        net = build()
        trainer = gluon.Trainer(net.collect_params(), opt_name,
                                {"learning_rate": 1e-4}, kvstore=None,
                                update_interval=N)

        def loss_fn(xx, yy):
            return loss_l(net(xx), yy)

        # two full windows of warmup: the second window re-executes both
        # executables on buffers PRODUCED by them (donation steady state)
        warm_n = max(warm, 2 * N) + (-max(warm, 2 * N)) % N
        for _ in range(warm_n):  # compile micro + apply executables
            trainer.fused_step(loss_fn, x, y)
        waitall()
        reset_step_counters()
        reps_n = max(N, reps - reps % N)  # whole windows only
        t0 = time.perf_counter()
        for _ in range(reps_n):
            trainer.fused_step(loss_fn, x, y)
        waitall()
        dt = (time.perf_counter() - t0) / reps_n * 1e3
        assert step_counters["compiles"] == 0, "retraced in steady state"
        disp = step_counters["dispatches"] / reps_n
        rows.append((f"fused step, N={N}", disp, dt))

    n_params = len([p for p in net.collect_params().values()
                    if p.grad_req != "null"])
    return n_params, rows


def train_step_op_count_smoke():
    """Tiny-BERT SPMD train-step HLO op count (the tier-1 gate for the
    static sequencer-overhead metric): builds a 2-layer BERT trainer and
    prints ``SPMDTrainer.step_hlo_op_count`` — the same counter the full
    ``bert`` run reports, whose round-3 anatomy is ~5,300
    ops x ~1 us of fixed per-op cost (the wall-vs-device MFU gap)."""
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel
    from mxnet_tpu.models import BERTConfig, BERTModel

    mx.random.seed(0)
    cfg = BERTConfig(vocab_size=512, max_length=32, num_layers=2,
                     units=64, num_heads=4, hidden_size=128)
    bert = BERTModel(cfg, use_pooler=False, use_mlm=True)

    class _MLMHeadOnly(gluon.Block):
        def __init__(self):
            super().__init__()
            self.bert = bert

        def forward(self, tokens):
            return self.bert(tokens)[-1]

    net = _MLMHeadOnly()
    net.initialize(mx.init.Normal(0.02))
    trainer = parallel.SPMDTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "adamw",
        {"learning_rate": 1e-4},
        mesh=parallel.make_mesh({"dp": len(jax.devices())}))
    rng = onp.random.RandomState(0)
    bs = max(8, len(jax.devices()))
    x = mx.nd.array(rng.randint(0, cfg.vocab_size, (bs, 16)))
    y = mx.nd.array(rng.randint(0, cfg.vocab_size, (bs, 16)))
    n = trainer.step_hlo_op_count(x, y)
    print(f"\ntrain-step HLO op count (tiny BERT, 2L): {n}")
    emit_row({"bench": "step_profile", "mode": "train_step_op_count",
              "model": "tiny-bert-2l", "hlo_ops": n})
    return n


def profile_fused_step(smoke=False):
    """Fused-step phase rows (imperative Trainer path): ms/step and
    host-dispatch count, phase-by-phase vs one-executable, with the
    gradient-accumulation window sweep."""
    kw = dict(n_layers=8, units=8, bs=4, reps=3, intervals=(1, 2),
              warm=2) if smoke else {}
    n, rows = measure_fused_step(**kw)
    mem = mem_fields("gluon.fused_step")
    print(f"\nfused-step phase (imperative Trainer, {n}-param chain, "
          f"{'smoke' if smoke else 'baseline'} workload):")
    if mem:
        print(f"  executable memory (CPU-profile buffer sizes): "
              f"temp {mem['mem_temp_mb']} MB, "
              f"peak {mem['mem_peak_mb']} MB")
    for mode, disp, dt in rows:
        print(f"  {mode:18s}: {disp:6.0f} host dispatches/step   "
              f"{dt:8.2f} ms/step")
        emit_row({"bench": "step_profile", "mode": "fused_step_phase",
                  "arm": mode, "n_params": n,
                  "workload": "smoke" if smoke else "baseline",
                  "dispatches_per_step": round(disp, 2),
                  "ms_per_step": round(dt, 3), **mem})
    return rows


def profile_checkpoint(smoke=False):
    """Checkpoint-stall phase rows (ISSUE 15 acceptance): what one
    ``mx.checkpoint`` save costs the training loop, per mode.  The sync
    arm pays snapshot + atomic write inline; the async arm pays ONLY
    the device→host snapshot (``save()`` returns once the values are
    host-resident — the donation-safety contract — and the fsync+rename
    commit happens on the writer thread).  The stall is the measured
    ``save()`` wall time; steady-state step time with a save every
    step quantifies the residual overlap cost."""
    import shutil
    import tempfile
    import time

    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.ndarray.ndarray import waitall

    n_layers, units, bs = (8, 8, 4) if smoke else (50, 64, 32)
    reps = 3 if smoke else 10
    rng = onp.random.RandomState(0)
    x = mx.nd.array(rng.randn(bs, units).astype(onp.float32))
    y = mx.nd.array(rng.randn(bs, 1).astype(onp.float32))
    loss_l = gluon.loss.L2Loss()

    mx.random.seed(0)
    net = nn.HybridSequential()
    with net.name_scope():
        for _ in range(n_layers - 1):
            net.add(nn.Dense(units, use_bias=False, in_units=units))
        net.add(nn.Dense(1, use_bias=False, in_units=units))
    net.initialize(mx.init.Xavier())
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "adamw",
                            {"learning_rate": 1e-4}, kvstore=None)

    def loss_fn(bx, by):
        return loss_l(net(bx), by)

    for _ in range(2):
        trainer.fused_step(loss_fn, x, y)
    waitall()

    def run(mode):
        tmp = tempfile.mkdtemp(prefix="mxnet_ckpt_bench_")
        mgr = None
        if mode != "no-save":
            mgr = mx.checkpoint.CheckpointManager(
                tmp, max_to_keep=2, async_save=(mode == "async-save"))
        stalls = []
        t0 = time.perf_counter()
        for k in range(reps):
            trainer.fused_step(loss_fn, x, y)
            if mgr is not None:
                s0 = time.perf_counter()
                mgr.save(k + 1, net, trainer)
                stalls.append(time.perf_counter() - s0)
        if mgr is not None:
            mgr.wait_until_finished()
        waitall()
        step_ms = (time.perf_counter() - t0) / reps * 1e3
        if mgr is not None:
            mgr.close()
        shutil.rmtree(tmp, ignore_errors=True)
        stall_ms = (sum(stalls) / len(stalls) * 1e3) if stalls else 0.0
        return step_ms, stall_ms

    print(f"\ncheckpoint phase ({n_layers}-layer chain, save every "
          f"step, {'smoke' if smoke else 'baseline'} workload):")
    rows = []
    for mode in ("no-save", "sync-save", "async-save"):
        step_ms, stall_ms = run(mode)
        rows.append((mode, step_ms, stall_ms))
        print(f"  {mode:10s}: {step_ms:8.2f} ms/step   "
              f"save stall {stall_ms:8.2f} ms")
        emit_row({"bench": "step_profile", "mode": "checkpoint_phase",
                  "arm": mode, "n_layers": n_layers,
                  "workload": "smoke" if smoke else "baseline",
                  "ms_per_step": round(step_ms, 3),
                  "save_stall_ms": round(stall_ms, 3)})
    return rows


def profile_optimizer_apply(trainer, iters=10):
    """Optimizer-apply phase row for the IMPERATIVE Trainer path (the
    API-parity path the SPMD profile above doesn't cover): the fused
    multi-tensor apply collapses the per-step host->device dispatch count
    from O(#params) to O(#groups) — this prints both counts and ms/step
    so the collapse is measurable per model."""
    n, rows = measure_optimizer_apply(
        trainer._block.collect_params(),
        type(trainer.optimizer).__name__.lower(), reps=iters)
    print(f"\noptimizer-apply phase (imperative Trainer, {n} params):")
    for mode, disp, dt in rows:
        print(f"  {mode:7s}: {disp:6.0f} optimizer-apply dispatches/step   "
              f"{dt:8.2f} ms/step")
        emit_row({"bench": "step_profile",
                  "mode": "optimizer_apply_phase", "arm": mode,
                  "n_params": n,
                  "dispatches_per_step": round(disp, 2),
                  "ms_per_step": round(dt, 3)})


def profile_input_overlap(trainer, x, y, steps=8, depth=2):
    """Input-pipeline / H2D overlap phase rows: feeds the compiled step
    from a host batch source (synthetic decode+augment work per batch)
    synchronously — input + H2D serialized into the step latency, the
    pre-PR DataLoader reality — vs through the depth-``depth``
    ``DevicePrefetchIter`` ring placed PRE-SHARDED with the trainer's own
    batch-axis ``NamedSharding`` (the ``DataLoader(device=sharding)``
    path).  With the ring, steady-state ms/step ≈ max(input, compute)."""
    import time

    from jax.sharding import NamedSharding, PartitionSpec

    from mxnet_tpu import nd
    from mxnet_tpu.gluon.data.dataloader import DevicePrefetchIter

    hx, hy = x.asnumpy(), y.asnumpy()
    sharding = NamedSharding(trainer._mesh, PartitionSpec(trainer._dp_axis))

    def host_batch():
        # stand-in for decode + augment: one smoothing pass over the batch
        out = hx
        for ax in range(max(1, hx.ndim - 1), hx.ndim):
            out = (onp.roll(out, 1, ax) + out + onp.roll(out, -1, ax)) / 3
        return out.astype(hx.dtype)

    def batches(n):
        for _ in range(n):
            yield (host_batch(), hy)

    t0 = time.perf_counter()
    for _ in batches(steps):
        pass
    input_ms = (time.perf_counter() - t0) / steps * 1e3

    def run(ring_depth):
        it = DevicePrefetchIter(batches(steps + 2), sharding,
                                depth=ring_depth,
                                background=ring_depth > 0)
        bx, by = next(it)  # warm ring + placement-signature executable
        trainer.step(bx, by).wait_to_read()
        t0 = time.perf_counter()
        n = 0
        for bx, by in it:
            trainer.step(bx, by).wait_to_read()
            n += 1
            if n == steps:
                break
        dt = (time.perf_counter() - t0) / n * 1e3
        it.close()
        return dt

    prev = os.environ.get("MXNET_DEVICE_PREFETCH")
    try:
        os.environ["MXNET_DEVICE_PREFETCH"] = "0"
        sync_ms = run(0)
        os.environ["MXNET_DEVICE_PREFETCH"] = str(depth)
        overlap_ms = run(depth)
    finally:
        if prev is None:
            os.environ.pop("MXNET_DEVICE_PREFETCH", None)
        else:
            os.environ["MXNET_DEVICE_PREFETCH"] = prev

    print(f"\ninput-pipeline overlap phase (depth-{depth} device ring, "
          f"pre-sharded placement):")
    print(f"  host input            : {input_ms:8.2f} ms/batch")
    print(f"  step, synchronous feed: {sync_ms:8.2f} ms/step  "
          f"(input + H2D + compute serialized)")
    print(f"  step, device prefetch : {overlap_ms:8.2f} ms/step  "
          f"({sync_ms / overlap_ms:.2f}x; ideal = max(input, compute) = "
          f"{max(input_ms, sync_ms - input_ms):.2f})")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("model", nargs="?",
                    choices=["resnet", "bert", "gpt", "transformer"])
    ap.add_argument("--bs", type=int, default=0)
    ap.add_argument("--by", default="tf_op",
                    choices=["tf_op", "name", "category", "source"])
    ap.add_argument("--limit", type=int, default=40)
    ap.add_argument("--iters", type=int, default=2)
    ap.add_argument("--no-opt-phase", action="store_true",
                    help="skip the imperative optimizer-apply phase row")
    ap.add_argument("--no-input-phase", action="store_true",
                    help="skip the input-pipeline / H2D overlap phase rows")
    ap.add_argument("--no-fused-step-phase", action="store_true",
                    help="skip the fused-step phase rows")
    ap.add_argument("--no-checkpoint-phase", action="store_true",
                    help="skip the checkpoint save-stall phase rows")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny fused-step phase rows only (tier-1 gate: "
                         "no model build, no trace, runs on CPU in "
                         "seconds)")
    args = ap.parse_args()

    # memory columns for the phase rows: every compile event this run
    # triggers carries memory_analysis fields (one extra AOT compile
    # per program, warm-up only — cheap at the smoke's toy sizes too)
    os.environ.setdefault("MXNET_TELEMETRY_MEM", "1")

    if args.smoke:
        rows = profile_fused_step(smoke=True)
        # the smoke gate checks the mechanism, not the speedup (CPU
        # timing at toy sizes is noise): every fused row must be exactly
        # one executable dispatch per step
        assert all(d == 1 for m, d, _ in rows if m.startswith("fused"))
        ck = profile_checkpoint(smoke=True)
        # async stall must be measured and strictly the snapshot side:
        # the async arm's save() wall is bounded by the sync arm's
        # (snapshot + atomic write) on any platform
        ck = {m: (step, stall) for m, step, stall in ck}
        assert ck["async-save"][1] > 0.0
        assert ck["async-save"][1] <= ck["sync-save"][1] * 1.5 + 5.0, ck
        assert train_step_op_count_smoke() > 0
        return 0
    if args.model is None:
        ap.error("model is required unless --smoke")

    import jax

    from mxnet_tpu import profiler_xla

    bs = args.bs or {"resnet": 256, "bert": 64, "gpt": 4,
                     "transformer": 32}[args.model]
    trainer, x, y = {"resnet": build_resnet, "bert": build_bert,
                     "gpt": build_gpt,
                     "transformer": build_transformer}[args.model](bs)

    def run():
        return trainer.step(x, y)

    # compile + warmup
    loss = run()
    print("warmup loss:", float(onp.asarray(loss.asnumpy()).reshape(-1)[0]))
    run()

    # static sequencer-overhead metric beside the measured trace: the
    # compiled step's HLO instruction count (round-3 anatomy
    # — the BERT step's wall-vs-device MFU gap is ~5,300 ops x ~1 us of
    # fixed per-op cost; the stacked-scan decode attacks the same class
    # of overhead on the decode side)
    print(f"train-step HLO op count: {trainer.step_hlo_op_count(x, y)}")

    import tempfile
    td = tempfile.mkdtemp(prefix="mxtpu_step_prof_")
    jax.profiler.start_trace(td)
    out = None
    for _ in range(args.iters):
        out = run()
    onp.asarray(out.asnumpy())  # the traced region ends in a readback
    jax.profiler.stop_trace()

    records = (profiler_xla.parse_xplane(td) or {"ops": []})["ops"]
    for r in records:
        r["dur_us"] /= args.iters
        r["self_us"] /= args.iters
        r["flops"] //= args.iters
        r["bytes"] //= args.iters
    rows = profiler_xla.aggregate(records, by=args.by)
    tot_us = sum(r["dur_us"] for r in rows)
    tot_fl = sum(r["flops"] for r in rows)
    if tot_us > 0:
        print(f"\ndevice step time: {tot_us / 1e3:.2f} ms   "
              f"model TFLOP: {tot_fl / 1e12:.3f}   "
              f"achieved {tot_fl / tot_us / 1e6:.1f} TFLOP/s "
              f"({100 * tot_fl / tot_us / 1e6 / PEAK_TFLOPS:.1f}% MFU)\n")
        print(profiler_xla.format_table(rows, peak_tflops=PEAK_TFLOPS,
                                        limit=args.limit))
    else:
        print("\n(no device trace records — per-op table skipped; "
              "phase rows below still measured)")
    if not args.no_input_phase:
        profile_input_overlap(trainer, x, y)
    if not args.no_opt_phase:
        profile_optimizer_apply(trainer)
    if not args.no_fused_step_phase:
        profile_fused_step()
    if not args.no_checkpoint_phase:
        profile_checkpoint()
    return 0


if __name__ == "__main__":
    sys.exit(main())
