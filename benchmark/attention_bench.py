#!/usr/bin/env python3
"""Flash-attention length sweep: every path, fwd AND fwd+bwd.

VERDICT r2 item 4: the op's dispatch must follow the measurements — this
sweep measures all three implementations (plain materialized, XLA
blockwise, Pallas kernel) at seq 512/1024/2048/4096/8192, forward and
train (fwd+bwd), and prints one JSON line per point.  The crossover
constants in ``ops/attention.py`` (``_PATH_TABLE``) are derived from this
table; ``tests/test_attention.py`` asserts the dispatch matches it.

    python benchmark/attention_bench.py            # full sweep
    python benchmark/attention_bench.py --seqs 512,2048
    python benchmark/attention_bench.py --batch 8 --heads 16 --seqs 1024
    python benchmark/attention_bench.py --seqs 1024 --blocks 128,256,512
    python benchmark/attention_bench.py --batch 8 --heads 16 --seqs 1024 --packed

``--batch`` / ``--heads`` set the shape (the table's is B4 H8, the train
cell's B8 H16); ``--blocks`` times each Pallas kernel alone (forward, dq,
dk/dv) over every pair of the listed block sizes instead — the sweep behind
``_block_for`` / ``_block_dkv_for``.  ``--packed`` starts from the fused
``(B, L, 3U)`` projection instead and times the kernels that read it where
it lies (``flash_attention_qkv``'s packed path) against what a model paid
before them: the split into ``(B, H, L, D)`` heads, the same kernels on
those, and the transpose back.

Every timed region ends in a device->host readback (as in bench.py), and
dispatch is amortized by looping the op inside one jit via lax.scan.
"""
from __future__ import annotations

import argparse
import functools
import itertools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as onp


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seqs", default="512,1024,2048,4096,8192")
    ap.add_argument("--budget", type=float, default=1.5,
                    help="target device-seconds per timed dispatch")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--blocks", default="",
                    help="comma-separated block sizes: time each Pallas "
                         "kernel alone over every (resident, streamed) pair")
    ap.add_argument("--packed", action="store_true",
                    help="time the kernels over the packed (B, L, 3U) "
                         "projection against split + kernels + transpose")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax import lax

    from mxnet_tpu.ops import attention as attn

    platform = jax.devices()[0].platform
    B, H, D = args.batch, args.heads, 64
    dtype = jnp.bfloat16 if platform == "tpu" else jnp.float32

    def bench(fn, *args_):
        """Adaptive timing: calibrate with a short run, then size the
        in-dispatch rep count so device work dwarfs the per-dispatch host
        cost.  Each iteration feeds its first
        outputs back as the first input (same (B,H,L,D) shape) so XLA
        cannot hoist the loop-invariant op out of the scan."""
        def make(inner):
            @jax.jit
            def looped(q0, *rest):
                def body(c, _):
                    out = fn(c, *rest)
                    # every q-shaped output feeds the next iteration: one
                    # left unused (dk, dv) is dead code, and XLA drops
                    # the kernel that made it
                    outs = out if isinstance(out, tuple) else (out,)
                    same = [o.astype(jnp.float32) for o in outs
                            if o is not None and o.shape == q0.shape]
                    if same:
                        return sum(same).astype(q0.dtype), None
                    # --packed, forward: (B, L, U) out of (B, L, 3U) goes
                    # back into the carry's leading lanes, in place
                    return lax.dynamic_update_slice(
                        c, outs[0].astype(q0.dtype), (0,) * c.ndim), None
                c, _ = lax.scan(body, q0, None, length=inner)
                return jnp.sum(c.astype(jnp.float32))
            return looped

        cal = make(8)
        float(cal(*args_))  # compile + warmup
        t0 = time.perf_counter()
        float(cal(*args_))
        est = (time.perf_counter() - t0) / 8
        inner = max(8, min(4096, int(args.budget / max(est, 1e-5))))
        run = make(inner)
        float(run(*args_))  # compile
        times = []
        for _ in range(2):
            t0 = time.perf_counter()
            float(run(*args_))  # readback syncs
            times.append(time.perf_counter() - t0)
        return min(times) / inner * 1e3

    results = {}

    def emit(seq, impl, pas, timed):
        try:
            ms = timed()
        except Exception as e:      # a point too large for the chip's HBM
            print(json.dumps({"bench": "flash_attention", "batch": B,
                              "heads": H, "seq": seq, "impl": impl,
                              "pass": pas, "error": str(e)[:200]}))
            return
        # fwd: 2 matmuls (QK^T, PV) = 4*B*H*L^2*D flops; bwd ~2.5x fwd.
        # The causal count is the half a causal model needs
        # (chipbench/shapes.train_flops_per_token counts that one).
        flops = 4 * B * H * seq * seq * D * (1 if pas == "fwd" else 3.5)
        results[(seq, impl, pas)] = ms
        print(json.dumps({
            "bench": "flash_attention", "batch": B, "heads": H, "seq": seq,
            "impl": impl, "pass": pas, "ms": round(ms, 3),
            "tflops": round(flops / ms / 1e9, 2),
            "causal_gflop": round(flops / 2 / 1e9, 2),
            "causal_tflops": round(flops / 2 / ms / 1e9, 2),
            "platform": platform}))
        sys.stdout.flush()

    def force_pallas(on):
        """Monkeypatch the trace-time path predicate (dispatch happens at
        trace time, so this reliably selects the implementation)."""
        attn._use_pallas_saved = getattr(attn, "_use_pallas_saved",
                                         attn._use_pallas)
        attn._use_pallas = (attn._use_pallas_saved if on
                            else (lambda: False))

    scale = 1.0 / D ** 0.5
    blocks = [int(b) for b in args.blocks.split(",") if b]
    for seq in [int(s) for s in args.seqs.split(",")]:
        rng = onp.random.RandomState(0)
        q, k, v = (jnp.asarray(rng.randn(B, H, seq, D), dtype)
                   for _ in range(3))

        if args.packed:
            # one (B, L, 3U) array in, (B, L, U) out, a (B, L, 3U)
            # gradient back: what MultiHeadAttention holds between its two
            # projections.  Both arms run the Pallas kernels; "apart" pays
            # the copies around them
            force_pallas(True)
            qkv = jnp.asarray(rng.randn(B, seq, 3 * H * D), dtype)
            seed = jnp.uint32(0)

            def packed(qkv):
                return attn._flash_qkv(qkv, None, seed, H, scale, True, 0.0)

            def apart(qkv):
                out = attn._flash(*attn._split_heads(qkv, H), None, seed,
                                  scale, True, 0.0, "pallas")
                return out.transpose(0, 2, 1, 3).reshape(B, seq, H * D)

            for impl, fn in (("packed", packed), ("apart", apart)):
                emit(seq, impl, "fwd", lambda: bench(fn, qkv))
                emit(seq, impl, "fwd+bwd", lambda: bench(jax.grad(
                    lambda x: jnp.sum(fn(x).astype(jnp.float32))), qkv))
            continue

        if blocks:
            # each kernel alone; "resident" is the block that stays while
            # the innermost grid axis walks the "streamed" one
            force_pallas(True)
            out, lse = attn._pallas_fwd(q, k, v, scale, True)
            lse = lse.reshape(B * H, 1, seq)
            delta = jnp.sum(out.astype(jnp.float32) ** 2,
                            axis=-1).reshape(B * H, 1, seq)
            for res, stream in itertools.product(blocks, blocks):
                if seq % res or seq % stream:
                    continue
                kernels = {
                    "fwd": (functools.partial(
                        attn._pallas_fwd, scale=scale, causal=True,
                        block_q=res, block_k=stream), (q, k, v)),
                    "bwd_dq": (functools.partial(
                        attn._pallas_bwd_dq, scale=scale, causal=True,
                        block_q=res, block_k=stream),
                        (q, k, v, out, lse, delta)),
                    "bwd_dkv": (functools.partial(
                        attn._pallas_bwd_dkv, scale=scale, causal=True,
                        block_k=res, block_q=stream),
                        (q, k, v, out, lse, delta)),
                }
                for name, (fn, operands) in kernels.items():
                    print(json.dumps({
                        "bench": "flash_blocks", "batch": B, "heads": H,
                        "seq": seq, "kernel": name, "resident": res,
                        "streamed": stream,
                        "ms": round(bench(fn, *operands), 4),
                        "platform": platform}))
                    sys.stdout.flush()
            continue

        # ---------------- forward ----------------
        if platform == "tpu":
            force_pallas(True)
            emit(seq, "pallas", "fwd", lambda: bench(functools.partial(
                attn._pallas_fwd, scale=scale, causal=True), q, k, v))
        emit(seq, "xla_blockwise", "fwd", lambda: bench(
            lambda q, k, v: attn._blockwise_attn(
                q, k, v, None, jnp.uint32(0), scale, True, 0.0, 128),
            q, k, v))
        if seq <= 4096:  # plain materializes O(L^2); OOM-prone past 4k
            emit(seq, "plain", "fwd", lambda: bench(functools.partial(
                attn._plain_attn, bias=None, scale=scale, causal=True),
                q, k, v))

        # ---------------- fwd+bwd ----------------
        def flash_loss(q, k, v):
            return jnp.sum(
                attn._flash(q, k, v, None, jnp.uint32(0), scale, True)
                .astype(jnp.float32))

        def plain_loss(q, k, v):
            return jnp.sum(
                attn._plain_attn(q, k, v, None, scale, True)
                .astype(jnp.float32))

        if platform == "tpu":
            force_pallas(True)
            emit(seq, "pallas", "fwd+bwd", lambda: bench(
                jax.grad(flash_loss, argnums=(0, 1, 2)), q, k, v))
        force_pallas(False)
        emit(seq, "xla_blockwise", "fwd+bwd", lambda: bench(
            jax.grad(flash_loss, argnums=(0, 1, 2)), q, k, v))
        force_pallas(True)
        if seq <= 4096:
            emit(seq, "plain", "fwd+bwd", lambda: bench(
                jax.grad(plain_loss, argnums=(0, 1, 2)), q, k, v))

    # summary: fastest impl per (seq, pass)
    best = {}
    for (seq, impl, pas), ms in results.items():
        k_ = (seq, pas)
        if k_ not in best or ms < best[k_][1]:
            best[k_] = (impl, ms)
    print(json.dumps({"bench": "flash_attention_best",
                      "best": {f"{s}/{p}": i for (s, p), (i, _)
                               in sorted(best.items())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
