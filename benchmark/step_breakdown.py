#!/usr/bin/env python3
"""BERT train-step component breakdown on TPU (VERDICT r1 item 2: publish a
per-component breakdown and close the MFU gap).

Times each component with the chained-scan methodology (outputs feed the
next iteration so XLA cannot hoist; in-dispatch reps sized so the
per-dispatch host cost is noise).  Prints one JSON line per component.
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as onp

PEAK_TFLOPS = 197.0  # v5e bf16


def emit_fused_step_rows(platform, smoke=False):
    """Section 8: the whole train step as ONE donated-buffer executable
    (``Trainer.fused_step``) vs the phase-by-phase chain, with the
    gradient-accumulation window sweep — methodology shared with
    step_profile (``measure_fused_step``)."""
    from benchmark.step_profile import measure_fused_step
    kw = dict(n_layers=8, units=8, bs=4, reps=3, intervals=(1, 2),
              warm=2) if smoke else {}
    n, rows = measure_fused_step(**kw)
    for mode, disp, dt in rows:
        name = "train_step_phase" if mode.startswith("phase") else \
            "train_step_fused_" + mode.split("N=")[-1].strip()
        print(json.dumps({
            "bench": "step_breakdown",
            "component": name,
            "ms": round(dt, 3),
            "params": n,
            "host_dispatches_per_step": round(disp),
            "platform": platform}))
        sys.stdout.flush()


def main():
    import argparse

    import jax
    import jax.numpy as jnp
    from jax import lax

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="fused-step section only, tiny sizes (tier-1 "
                         "gate)")
    args = ap.parse_args()
    platform = jax.devices()[0].platform
    if args.smoke:
        emit_fused_step_rows(platform, smoke=True)
        return 0
    B, L, U, H, FF, V = 64, 128, 768, 12, 3072, 30528
    NL = 12
    dtype = jnp.bfloat16 if platform == "tpu" else jnp.float32
    tokens = B * L

    def bench(fn, *args, feed_index=0):
        """ms/op via chained scan with adaptive rep count."""
        def make(inner):
            @jax.jit
            def looped(x0, *rest):
                def body(c, _):
                    out = fn(c, *rest)
                    nxt = out[feed_index] if isinstance(out, tuple) else out
                    return nxt.astype(x0.dtype) if nxt.shape == x0.shape \
                        else x0 + 0 * jnp.sum(nxt).astype(x0.dtype), None
                c, _ = lax.scan(body, x0, None, length=inner)
                return jnp.sum(c.astype(jnp.float32))
            return looped

        cal = make(8)
        float(cal(*args))
        t0 = time.perf_counter()
        float(cal(*args))
        est = (time.perf_counter() - t0) / 8
        inner = max(8, min(2048, int(2.0 / max(est, 1e-5))))
        run = make(inner)
        float(run(*args))
        times = []
        for _ in range(2):
            t0 = time.perf_counter()
            float(run(*args))
            times.append(time.perf_counter() - t0)
        return min(times) / inner * 1e3

    def emit(name, ms, gflop=None):
        rec = {"bench": "step_breakdown", "component": name,
               "ms": round(ms, 3), "platform": platform}
        if gflop:
            rec["tflops"] = round(gflop / ms, 2)
            rec["mfu_pct"] = round(100 * gflop / ms / PEAK_TFLOPS, 1)
        print(json.dumps(rec))
        sys.stdout.flush()

    rng = onp.random.RandomState(0)
    x = jnp.asarray(rng.randn(tokens, U), dtype)

    # 1. FFN chain fwd: NL x (U->FF gelu FF->U)
    w1 = jnp.asarray(rng.randn(U, FF) * 0.02, dtype)
    w2 = jnp.asarray(rng.randn(FF, U) * 0.02, dtype)

    def ffn_fwd(h):
        for _ in range(NL):
            h = jax.nn.gelu(h @ w1) @ w2
        return h

    g_ffn = 2 * tokens * U * FF * 2 * NL / 1e9
    emit("ffn_chain_fwd(12x)", bench(ffn_fwd, x), g_ffn)

    # 2. FFN chain fwd+bwd
    def ffn_loss(h):
        return jnp.sum(ffn_fwd(h).astype(jnp.float32))
    emit("ffn_chain_fwd+bwd(12x)", bench(jax.grad(ffn_loss), x),
         g_ffn * 3)

    # 3. attention fwd+bwd at seq 128 (plain path, as the bench model uses)
    from mxnet_tpu.ops import attention as attn
    qh = jnp.asarray(rng.randn(B, H, L, U // H), dtype)

    def attn_all(q):
        out = q
        for _ in range(NL):
            out = attn._plain_attn(out, out, out, None, 0.125, False)
        return out
    g_attn = 4 * B * H * L * L * (U // H) * NL / 1e9
    emit("attention_fwd(12x,seq128)", bench(attn_all, qh), g_attn)

    def attn_loss(q):
        return jnp.sum(attn_all(q).astype(jnp.float32))
    emit("attention_fwd+bwd(12x)", bench(jax.grad(attn_loss), qh),
         g_attn * 3.5)

    # 4. MLM head: logits matmul + softmax-CE fwd+bwd
    wv = jnp.asarray(rng.randn(U, V) * 0.02, dtype)
    labels = jnp.asarray(rng.randint(0, V, (tokens,)), jnp.int32)

    def head_loss(h):
        logits = (h @ wv).astype(jnp.float32)
        lp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(lp, labels[:, None], 1))
    g_head = 2 * tokens * U * V * 3 / 1e9
    emit("mlm_head_fwd+bwd", bench(jax.grad(head_loss), x), g_head)

    # 5. AdamW update on a BERT-sized param set (~110M fp32 master+states)
    nparams = 110_000_000
    w = jnp.zeros((nparams // 64, 64), dtype)
    m = jnp.zeros(w.shape, jnp.float32)
    v = jnp.zeros(w.shape, jnp.float32)
    master = jnp.zeros(w.shape, jnp.float32)
    gbuf = jnp.asarray(rng.randn(*w.shape) * 1e-3, dtype)

    def adamw(g, m, v, master):
        g32 = g.astype(jnp.float32)
        m2 = 0.9 * m + 0.1 * g32
        v2 = 0.999 * v + 0.001 * g32 * g32
        mast2 = master - 1e-4 * (m2 / (jnp.sqrt(v2) + 1e-8) + 0.01 * master)
        return g, m2, v2, mast2
    emit("adamw_update(110M,mp)", bench(adamw, gbuf, m, v, master))

    # 6. full train step via SPMDTrainer (the bench.py path), per-step
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel
    from mxnet_tpu.models import BERTModel, BERTConfig
    mx.random.seed(0)
    cfg = BERTConfig(vocab_size=V, max_length=L, num_layers=NL, units=U,
                     num_heads=H, hidden_size=FF,
                     dtype="bfloat16" if platform == "tpu" else "float32")
    bert = BERTModel(cfg, use_pooler=False, use_mlm=True)

    class _Head(gluon.Block):
        def __init__(self):
            super().__init__()
            self.bert = bert

        def forward(self, tokens):
            return self.bert(tokens)[-1]

    net = _Head()
    net.initialize(mx.init.Normal(0.02))
    trainer = parallel.SPMDTrainer(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                                   "adamw", {"learning_rate": 1e-4},
                                   mesh=parallel.make_mesh(
                                       {"dp": len(jax.devices())}))
    toks = rng.randint(0, V, (B, L))
    labs = rng.randint(0, V, (B, L))

    # 6a. model-only ablation: loss fwd and fwd+bwd through the full BERT
    # (no optimizer, no scan) — isolates where the step's non-matmul time
    # lives
    g_step = (g_ffn + g_attn + 2 * tokens * U * V / 1e9 +
              2 * tokens * 4 * U * U * NL / 1e9) * 3
    trainer._ensure_built(mx.nd.array(toks), mx.nd.array(labs))
    tv = tuple(trainer._train_vals)
    fv = list(trainer._frozen_vals)
    d32 = jnp.asarray(toks)
    l32 = jnp.asarray(labs)
    key0 = jax.random.PRNGKey(0)

    def loss_only(tv_q, d, l):
        box = []
        return trainer._forward_loss(key0, tv_q, fv, d, l, box)

    @jax.jit
    def fwd_rep(d, l):
        def body(c, _):
            return c + loss_only(tv, d, l), None
        c, _ = jax.lax.scan(body, jnp.float32(0), None, length=8)
        return c

    @jax.jit
    def fwdbwd_rep(d, l):
        # grads must feed the next iteration's params or XLA DCEs the
        # whole backward — a 1e-12-lr SGD keeps it alive at ~zero cost
        def body(c_tv, _):
            lv, gr = jax.value_and_grad(
                lambda t: loss_only(t, d, l))(c_tv)
            new_tv = tuple(v - g.astype(v.dtype) * 1e-12
                           for v, g in zip(c_tv, gr))
            return new_tv, lv
        tv_out, losses = jax.lax.scan(body, tv, None, length=8)
        return losses[-1] + jnp.sum(tv_out[0].astype(jnp.float32)) * 0 + \
            sum(jnp.sum(t.astype(jnp.float32)) for t in tv_out) * 1e-12

    for nm, f, mult in (("model_fwd_only", fwd_rep, 1),
                        ("model_fwd+bwd_sgd1e-12", fwdbwd_rep, 3)):
        float(f(d32, l32))
        ts = []
        for _ in range(2):
            t0 = time.perf_counter()
            float(f(d32, l32))
            ts.append(time.perf_counter() - t0)
        emit(nm, min(ts) / 8 * 1e3, g_step / 3 * mult)

    n_steps = 20
    sd = mx.nd.array(onp.broadcast_to(toks, (n_steps,) + toks.shape))
    sl = mx.nd.array(onp.broadcast_to(labs, (n_steps,) + labs.shape))
    float(onp.asarray(trainer.run_steps(sd, sl).asnumpy()).reshape(-1)[0])
    best = None
    for _ in range(3):
        t0 = time.perf_counter()
        float(onp.asarray(trainer.run_steps(sd, sl).asnumpy())
              .reshape(-1)[-1])
        dt = (time.perf_counter() - t0) / n_steps
        best = dt if best is None else min(best, dt)
    emit("full_train_step", best * 1e3, g_step)
    print(json.dumps({"bench": "step_breakdown",
                      "component": "throughput",
                      "tokens_per_sec": round(tokens / best, 1),
                      "platform": platform}))

    # 7. optimizer-apply phase on the IMPERATIVE Trainer path: the fused
    # multi-tensor apply issues O(#groups) jitted dispatches per step vs
    # the legacy O(#params) loop — both timed on the same BERT param set
    # with synthetic grads (the phase under test is the apply itself;
    # measurement methodology shared with step_profile)
    from benchmark.step_profile import measure_optimizer_apply
    n, rows = measure_optimizer_apply(net.collect_params(), "adamw")
    for mode, disp, dt in rows:
        print(json.dumps({
            "bench": "step_breakdown",
            "component": f"optimizer_apply_{mode}",
            "ms": round(dt, 3),
            "params": n,
            "apply_dispatches_per_step": round(disp),
            "platform": platform}))
        sys.stdout.flush()

    # 8. fused train step: fwd+bwd+apply as ONE executable, accumulate
    # window sweep
    emit_fused_step_rows(platform)
    return 0


if __name__ == "__main__":
    sys.exit(main())
