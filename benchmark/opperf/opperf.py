#!/usr/bin/env python3
"""opperf — per-operator timing harness over the full registry.

Reference surface: ``benchmark/opperf/`` (SURVEY.md §6 "benchmark
machinery": per-operator timing harness over the full registry).

Times each registered op's eager dispatch and, separately, its jitted
steady-state (the compiled-kernel cost, what actually matters on TPU).
Every timed region ends in a device→host readback.

Usage::

    python benchmark/opperf/opperf.py                # all default-profiled ops
    python benchmark/opperf/opperf.py --ops dot relu softmax
    python benchmark/opperf/opperf.py --json out.json
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as onp

# runnable from any cwd: the repo root is two levels up
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


# shapes per op family; (args builder) -> list of jax arrays
def _default_inputs(name, rng, large):
    import jax.numpy as jnp
    n = 1024 if large else 128
    sq = (n, n)
    vec = (n * n,)
    mk = lambda shape: jnp.asarray(rng.rand(*shape).astype(onp.float32))
    specials = {
        "dot": lambda: [mk(sq), mk(sq)],
        "matmul": lambda: [mk(sq), mk(sq)],
        "batch_dot": lambda: [mk((8,) + sq), mk((8,) + sq)],
        "linalg_gemm2": lambda: [mk(sq), mk(sq)],
        "FullyConnected": lambda: ([mk(sq), mk(sq)],
                                   {"num_hidden": n, "no_bias": True}),
        "Convolution": lambda: ([mk((8, 16, 32, 32)),
                                 mk((32, 16, 3, 3))],
                                {"kernel": (3, 3), "num_filter": 32,
                                 "no_bias": True}),
        "Pooling": lambda: ([mk((8, 16, 32, 32))],
                            {"kernel": (2, 2), "pool_type": "max"}),
        "concat": lambda: [mk(sq), mk(sq)],
        "take": lambda: [mk(sq), jnp.asarray(
            rng.randint(0, n, 64).astype(onp.int32))],
        "one_hot": lambda: ([jnp.asarray(rng.randint(0, n, vec[0] // n)
                                         .astype(onp.int32))],
                            {"depth": n}),
        "Embedding": lambda: ([jnp.asarray(rng.randint(0, n, (64,))
                                           .astype(onp.int32)), mk(sq)],
                              {"input_dim": n, "output_dim": n}),
        "LayerNorm": lambda: [mk(sq), mk((n,)), mk((n,))],
        "RMSNorm": lambda: [mk(sq), mk((n,))],
        "softmax": lambda: [mk(sq)],
        "topk": lambda: ([mk(sq)], {"k": 8}),
        "sort": lambda: [mk(sq)],
        "argsort": lambda: [mk(sq)],
        "flash_attention": lambda: [mk((4, 8, 256, 64)), mk((4, 8, 256, 64)),
                                    mk((4, 8, 256, 64))],
        "flash_attention_qkv": lambda: ([mk((4, 256, 3 * 8 * 64))],
                                        {"num_heads": 8}),
    }
    if name in specials:
        out = specials[name]()
        return out if isinstance(out, tuple) else (out, {})
    return [mk(sq)], {}


_SKIP = {
    # need structured inputs not worth synthesizing here
    "fused_rnn", "CTCLoss", "ring_attention", "sequence_last",
    "sequence_mask", "sequence_reverse", "boolean_mask", "gather_nd",
    "scatter_nd", "where", "pick", "_DropoutImpl", "_BatchNormStats",
    "broadcast_like", "slice", "slice_axis", "slice_like", "split",
    "_contrib_interleaved_matmul_selfatt_qk",
    "_contrib_interleaved_matmul_selfatt_valatt",
    "_contrib_dequantize", "_contrib_requantize", "quantized_matmul_int8",
    "repeat", "tile", "pad", "expand_dims", "reshape", "diag",
    "SoftmaxOutput", "MakeLoss", "InstanceNorm", "GroupNorm", "Deconvolution",
    "L2Normalization", "LeakyReLU", "Activation", "SoftmaxActivation",
    "amp_multicast", "multi_all_finite", "add_n", "stack",
    "broadcast_axis", "broadcast_to", "full_like", "one_hot", "cast",
    "arctan2", "broadcast_hypot",
}


def run_op_benchmark(names=None, warmup=2, runs=10, large=False):
    import jax

    from mxnet_tpu.ops import registry
    import mxnet_tpu.ndarray  # noqa: F401 — populate registry

    rng = onp.random.RandomState(7)
    results = []
    all_names = names or [n for n in registry.list_ops() if n not in _SKIP]
    for name in all_names:
        opref = registry.get_op(name)
        try:
            arrays, kwargs = _default_inputs(name, rng, large)
            fn = lambda *xs: opref.fn(*xs, **kwargs)
            # tracelint: disable=TL003 -- opperf times one fresh executable per op by design; fn differs every iteration
            jitted = jax.jit(fn)
            # correctness/compile check
            out = jitted(*arrays)
            onp.asarray(jax.device_get(
                out[0] if isinstance(out, (tuple, list)) else out)).ravel()[:1]
        except Exception as e:  # pragma: no cover - skip unsupported combos
            results.append({"op": name, "error": str(e)[:120]})
            continue

        def sync(r):
            onp.asarray(jax.device_get(
                r[0] if isinstance(r, (tuple, list)) else r)).ravel()[:1]

        for _ in range(warmup):
            sync(jitted(*arrays))
        t0 = time.perf_counter()
        for _ in range(runs):
            r = jitted(*arrays)
        sync(r)
        jit_ms = (time.perf_counter() - t0) / runs * 1e3

        t0 = time.perf_counter()
        for _ in range(runs):
            r = fn(*arrays)
        sync(r)
        eager_ms = (time.perf_counter() - t0) / runs * 1e3
        results.append({"op": name, "jit_ms": round(jit_ms, 4),
                        "eager_ms": round(eager_ms, 4)})
    return results


def main(argv=None):
    p = argparse.ArgumentParser(description="per-op timing harness")
    p.add_argument("--ops", nargs="*", default=None)
    p.add_argument("--runs", type=int, default=10)
    p.add_argument("--large", action="store_true",
                   help="1024^2 operands instead of 128^2")
    p.add_argument("--json", default=None, help="write results to file")
    args = p.parse_args(argv)
    results = run_op_benchmark(args.ops, runs=args.runs, large=args.large)
    ok = [r for r in results if "jit_ms" in r]
    bad = [r for r in results if "error" in r]
    print(f"{'Op':<36}{'jit(ms)':>10}{'eager(ms)':>11}")
    print("-" * 57)
    for r in sorted(ok, key=lambda r: -r["jit_ms"]):
        print(f"{r['op']:<36}{r['jit_ms']:>10.3f}{r['eager_ms']:>11.3f}")
    if bad:
        print(f"\n{len(bad)} ops skipped with errors:")
        for r in bad:
            print(f"  {r['op']}: {r['error']}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(results, f, indent=2)
    return 0


if __name__ == "__main__":
    sys.exit(main())
