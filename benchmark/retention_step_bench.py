#!/usr/bin/env python3
"""The power-retention decode step alone at a serving step's shapes: every
layer's ``ops.power_retention.state_update`` over the whole stored state, as
the ``brumby14b_serve_long24`` cell's step calls it.

    python benchmark/retention_step_bench.py
    python benchmark/retention_step_bench.py --live 12

The layers, heads, KV heads and head width are those of
``chipbench/configs/brumby_14b_serve.json``, the slots the largest of its
``server.pool_sizes``; the state ``(layers, slots, groups, d, E)`` and ``z``
float32 as stored, donated and rewritten in place; bfloat16 ``q``, ``k``,
``v`` as the model's projections give them; the first ``--live`` slots
live.  One step is the layers one after another inside one jit, each
layer's update under the region ``mx.ssm_state`` as in the model.  Prints
one JSON line: host milliseconds a step (10 steps, ended by a readback),
and from a profiler trace of 3 steps the device milliseconds a step, split
into the kernel ``mx_retention_update`` and the operations around it (the
largest listed by name), and the kernel's share of the floor that
``retention_state_roofline_pct`` reads
(``shapes_brumby.retention_state_min``).
A chip's numbers only: off the TPU it times the interpreter and says so.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
REPS, TRACED = 10, 3


def main():
    from chipbench import brumby, harness, shapes, shapes_brumby

    config = harness.read_json(os.path.join(
        ROOT, "chipbench", "configs", "brumby_14b_serve.json"))
    cfg = brumby.reference_config(config)
    slots = max(config["server"]["pool_sizes"])
    ap = argparse.ArgumentParser()
    ap.add_argument("--live", type=int, default=slots)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from mxnet_tpu import profiler_xla
    from mxnet_tpu.ops import power_retention as pr

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    L, S, G, d = cfg["num_hidden_layers"], slots, \
        cfg["num_key_value_heads"], cfg["head_dim"]
    H = cfg["num_attention_heads"]
    E = pr.expanded_rows(d)
    f32, bf16 = jnp.float32, jnp.bfloat16
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(keys[0], (L, S, H, d), f32).astype(bf16)
    k = (jax.random.normal(keys[1], (L, S, G, d), f32) * 0.1).astype(bf16)
    v = jax.random.normal(keys[2], (L, S, G, d), f32).astype(bf16)
    la = -jax.random.uniform(keys[3], (L, S, G), f32) * 0.05
    live = jnp.arange(S) < args.live
    state = jnp.zeros((L, S, G, d, E), f32)
    z = jnp.zeros((L, S, G, E), f32)

    def step(state, z, q, k, v, la, live):
        acc = jnp.float32(0)
        for li in range(L):
            with jax.named_scope("mx.ssm_state"):
                y, state, z = pr.state_update(
                    state, z, jnp.int32(li), q[li], k[li], v[li], la[li],
                    live, cfg["retention_eps"])
            acc = acc + jnp.sum(y)
        return state, z, acc

    step = jax.jit(step, donate_argnums=(0, 1))
    t_compile = time.perf_counter()
    state, z, acc = step(state, z, q, k, v, la, live)
    float(acc)
    compile_s = time.perf_counter() - t_compile
    t0 = time.perf_counter()
    for _ in range(REPS):
        state, z, acc = step(state, z, q, k, v, la, live)
    float(acc)
    host_ms = (time.perf_counter() - t0) / REPS * 1e3

    row = {"device": dev.device_kind,
           "measures": "device" if on_tpu else "interpreter",
           "slots": S, "live": args.live, "layers": L, "groups": G,
           "heads": H, "d": d, "compile_s": round(compile_s, 2),
           "host_ms_step": round(host_ms, 4)}
    with tempfile.TemporaryDirectory() as trace_dir:
        jax.profiler.start_trace(trace_dir)
        try:
            for _ in range(TRACED):
                state, z, acc = step(state, z, q, k, v, la, live)
            float(acc)
        finally:
            jax.profiler.stop_trace()
        parsed = profiler_xla.parse_xplane(trace_dir)
    if parsed is not None:
        runs = [r for r in parsed["runs"] if r["module"] == "jit_step"]
        n = len(runs) or 1
        by_op = {}
        for op in parsed["ops"]:
            if op["module"] == "jit_step":
                by_op[op["name"]] = by_op.get(op["name"], 0.0) \
                    + op["self_us"]
        kernel = sum(t for name, t in by_op.items() if pr._NAME in name)
        total = sum(r["dur_us"] for r in runs)
        peaks = shapes.peaks_for(dev.device_kind, os.path.join(
            os.path.dirname(harness.__file__), "peaks.json"))
        floor_s = shapes_brumby.floor_seconds(
            shapes_brumby.retention_state_min(cfg, args.live), peaks)
        kernel_ms = kernel / n / 1e3
        row.update({
            "device_ms_step": round(total / n / 1e3, 4),
            "kernel_ms_step": round(kernel_ms, 4),
            "outside_kernel_ms_step": round((total - kernel) / n / 1e3, 4),
            "kernel_state_roofline_pct": round(
                100.0 * floor_s / (kernel_ms / 1e3), 2) if kernel else None,
            "ops": [[name, round(t / n / 1e3, 4)] for name, t in sorted(
                by_op.items(), key=lambda kv: -kv[1])[:10]]})
    print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
