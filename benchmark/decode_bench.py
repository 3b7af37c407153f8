#!/usr/bin/env python3
"""Autoregressive decode throughput: KV-cache (one compiled scan) vs the
full-recompute ``GPT.generate`` loop.  Prints one JSON line per mode.

Batch-1 arms sweep the per-token step implementation (unrolled per-layer
/ stacked-layer scan) and report, next to the timings, the **ops/step
column**: the optimized-HLO instruction count of ONE compiled decode step
(``models.decode_step_program`` + ``profiler_xla.hlo_op_count``) — what
the stacked-scan path collapses, measurable on any backend.

The full run also carries the **ragged-arrival arm** (shared with
``serve_bench.py``): one ragged workload served as static padded
batches vs slot-pool continuous batching (``mxnet_tpu/serve/``) at
25/50/100% padded-batch occupancy — the serving-shaped comparison the
static arms can't express.

Every arm reports **tokens_per_dispatch** (ISSUE 17): useful tokens
emitted per executable dispatch.  The scan/loop arms are exactly 1.0 by
construction (one decode dispatch per token per lane); the
**speculative arm** (``spec_selfdraft``) decodes a repetitive-suffix
prompt on a ONE-slot pump-driven server with draft-and-verify on, and
its strict global ratio — tokens / (admit + step + verify dispatches)
— must clear > 1.5 (the n-gram self-drafts verify at high acceptance,
so each verify dispatch advances several positions).

``--smoke``: tiny geometry, no TPU — exercises the unrolled and stacked
arms plus the op-count column and asserts greedy parity between them;
gated in tier-1 like ``step_profile.py --smoke``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as onp


def _step_ops(net, total, weights, stacked):
    """ops/step for one compiled batch-1 decode step of this arm."""
    from mxnet_tpu import profiler_xla
    from mxnet_tpu.models import decode_step_program

    fn, args = decode_step_program(net, batch=1, total=total,
                                   weights=weights, stacked=stacked)
    return profiler_xla.hlo_op_count(fn, *args)


def run_spec_single(net, cfg, P, N):
    """ISSUE 17 speculative arm: one slot, repetitive-suffix prompt.

    A single request decodes on a pump-driven one-slot server with
    draft-and-verify ON; the prompt's repeated suffix gives the n-gram
    drafter material from the first step, so verifies advance several
    positions each.  Returns ``(prompt, toks, tokens_per_dispatch,
    accept_rate, dispatch_deltas, wall)`` where tokens_per_dispatch is
    the STRICT global ratio tokens / (admit + step + verify
    dispatches) — every dispatch the request cost, nothing amortised
    away."""
    from mxnet_tpu.serve import DecodeServer

    prompt = onp.tile(onp.arange(1, 5), -(-P // 4))[:P]
    srv = DecodeServer(net, max_total_len=P + N, pool_sizes=(1,),
                       spec=True, prefix_cache=False, autostart=False)
    warm = srv.submit(prompt, max_new_tokens=N)   # compile everything
    while srv.pump():
        pass
    warm.tokens(1)
    base = dict(srv.counters)
    t0 = time.perf_counter()
    stream = srv.submit(prompt, max_new_tokens=N)
    while srv.pump():
        pass
    wall = time.perf_counter() - t0
    toks = stream.tokens(1)
    d = {k: v - base[k] for k, v in dict(srv.counters).items()}
    disp = (d["admit_dispatches"] + d["step_dispatches"]
            + d["verify_dispatches"])
    tpd = len(toks) / max(disp, 1)
    acc = d["draft_accepted"] / max(d["draft_accepted"]
                                    + d["draft_rejected"], 1)
    srv.close()
    return prompt, toks, tpd, acc, d, wall


def smoke():
    """Tiny-geometry unrolled-vs-stacked decode: parity + op-count
    collapse, CPU-friendly (the tier-1 gate)."""
    import mxnet_tpu as mx
    from mxnet_tpu.models import GPT, GPTConfig, kv_generate

    mx.random.seed(0)
    cfg = GPTConfig(vocab_size=512, max_length=128, num_layers=2,
                    units=64, num_heads=4, hidden_size=128)
    net = GPT(cfg)
    net.initialize(mx.init.Normal(0.02))
    B, P, N = 2, 8, 16
    prompt = onp.random.RandomState(0).randint(0, cfg.vocab_size, (B, P))
    outs, rows = {}, []
    for arm, skw, wmode in (("unrolled", "off", "native"),
                            ("stacked", "on", "native"),
                            ("int8_unrolled", "off", "int8"),
                            ("int8_stacked", "on", "int8")):
        kv_generate(net, prompt, max_new_tokens=N, temperature=0.0,
                    stacked=skw, weights=wmode)  # compile
        t0 = time.perf_counter()
        outs[arm] = kv_generate(net, prompt, max_new_tokens=N,
                                temperature=0.0, stacked=skw,
                                weights=wmode)
        dt = time.perf_counter() - t0
        ops = _step_ops(net, P + N, wmode, skw)
        rows.append((arm, ops))
        print(json.dumps({"bench": "decode_smoke", "mode": arm,
                          "ops_per_step": ops,
                          "ms_per_token": round(dt / N * 1e3, 3),
                          "tokens_per_dispatch": 1.0,  # 1 token/step scan
                          "batch": B, "new_tokens": N}))
    onp.testing.assert_array_equal(outs["stacked"], outs["unrolled"])
    onp.testing.assert_array_equal(outs["int8_stacked"],
                                   outs["int8_unrolled"])
    ops = dict(rows)
    assert ops["stacked"] < ops["unrolled"], rows
    assert ops["int8_stacked"] < ops["int8_unrolled"], rows
    print(f"# parity OK; ops/step {ops['unrolled']} -> {ops['stacked']}"
          f" (int8 {ops['int8_unrolled']} -> {ops['int8_stacked']})")

    # speculative arm (ISSUE 17): strict tokens/(admit+step+verify)
    # on a repetitive-suffix prompt must clear the > 1.5 acceptance
    # bar, and the served stream must match the offline greedy decode
    import jax
    platform = jax.devices()[0].platform
    Ns = 48
    sp_prompt, sp_toks, tpd, acc, d, wall = run_spec_single(
        net, cfg, P, Ns)
    print(json.dumps({"bench": "decode_smoke", "mode": "spec_selfdraft",
                      "tokens_per_dispatch": round(tpd, 3),
                      "accept_rate": round(acc, 3),
                      "admit_dispatches": d["admit_dispatches"],
                      "step_dispatches": d["step_dispatches"],
                      "verify_dispatches": d["verify_dispatches"],
                      "ms_per_token": round(wall / Ns * 1e3, 3),
                      "new_tokens": Ns, "platform": platform}))
    assert tpd > 1.5, f"spec tokens/dispatch {tpd:.2f} <= 1.5"
    ref = list(kv_generate(net, sp_prompt[None], max_new_tokens=Ns,
                           temperature=0.0)[0, sp_prompt.size:])
    assert sp_toks == ref, "spec stream != kv_generate"
    print(f"# spec OK: {tpd:.2f} tokens/dispatch at "
          f"{acc:.2f} accept, parity exact")
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny unrolled-vs-stacked arms + op-count "
                         "column only (tier-1 gate, runs on CPU in "
                         "seconds)")
    args = ap.parse_args()
    if args.smoke:
        return smoke()

    import jax

    import mxnet_tpu as mx
    from mxnet_tpu.models import GPT, GPTConfig, decode_mode, kv_generate

    platform = jax.devices()[0].platform
    on_tpu = platform == "tpu"
    mx.random.seed(0)
    cfg = GPTConfig(vocab_size=32768, max_length=1024, num_layers=12,
                    units=768, num_heads=12, hidden_size=3072,
                    dtype="bfloat16" if on_tpu else "float32") \
        if on_tpu else GPTConfig(vocab_size=512, max_length=128,
                                 num_layers=2, units=64, num_heads=4,
                                 hidden_size=128)
    net = GPT(cfg)
    net.initialize(mx.init.Normal(0.02))
    B, P, N = (8, 32, 256) if on_tpu else (2, 8, 16)
    prompt = onp.random.RandomState(0).randint(0, cfg.vocab_size, (B, P))

    # KV-cache path: one compiled scan (time incl. sampling), default
    # step mode (stacked where supported)
    kv_generate(net, prompt, max_new_tokens=N, temperature=0.0)  # compile
    t0 = time.perf_counter()
    kv_generate(net, prompt, max_new_tokens=N, temperature=0.0)
    dt = time.perf_counter() - t0
    print(json.dumps({"bench": "decode", "mode": "kv_cache",
                      "step": decode_mode(net),
                      "tokens_per_sec": round(B * N / dt, 1),
                      "tokens_per_dispatch": 1.0,  # 1 token/step scan
                      "batch": B, "new_tokens": N,
                      "platform": platform}))
    sys.stdout.flush()

    # batch-1 latency (interactive serving).  prefill='batched' runs the
    # prompt as ONE causal forward, then N-1 scan decode steps; the timed
    # wall covers prefill + decode, so ms_per_token = wall / N is the
    # honest serving latency per emitted token.  Arms: per-layer
    # unrolled vs stacked-layer scan, each with the int8 weight stream.
    p1 = prompt[:1]
    arms = [("native", "off", "kv_cache_batch1"),
            ("native", "on", "kv_cache_batch1_stacked"),
            ("int8", "off", "kv_cache_batch1_int8"),
            ("int8", "on", "kv_cache_batch1_int8_stacked")]
    for wmode, smode, tag in arms:
        kw = dict(max_new_tokens=N, temperature=0.0, weights=wmode,
                  stacked=smode)
        kv_generate(net, p1, **kw)  # compile
        t0 = time.perf_counter()
        kv_generate(net, p1, **kw)
        dt = time.perf_counter() - t0
        ops = _step_ops(net, P + N, wmode, smode)
        print(json.dumps({"bench": "decode", "mode": tag,
                          "new_tokens_per_sec": round(N / dt, 1),
                          "ms_per_token": round(dt / N * 1e3, 3),
                          "ops_per_step": ops,
                          "tokens_per_dispatch": 1.0,  # 1 token/step
                          "batch": 1, "new_tokens": N, "prompt": P,
                          "platform": platform}))
        sys.stdout.flush()

    # speculative-decoding arm (ISSUE 17): one slot, repetitive-suffix
    # prompt, draft-and-verify on — strict global tokens per dispatch
    sp_prompt, sp_toks, tpd, acc, d, wall = run_spec_single(
        net, cfg, P, N)
    print(json.dumps({"bench": "decode", "mode": "spec_selfdraft",
                      "new_tokens_per_sec": round(len(sp_toks) / wall, 1),
                      "tokens_per_dispatch": round(tpd, 3),
                      "accept_rate": round(acc, 3),
                      "admit_dispatches": d["admit_dispatches"],
                      "step_dispatches": d["step_dispatches"],
                      "verify_dispatches": d["verify_dispatches"],
                      "batch": 1, "new_tokens": N, "prompt": P,
                      "platform": platform}))
    sys.stdout.flush()
    assert tpd > 1.5, f"spec tokens/dispatch {tpd:.2f} <= 1.5"

    # ragged-arrival arm: the same ragged workload (per 8-request wave
    # one long request + seven short) served as static padded batches
    # (every lane decodes to the wave max) vs slot-pool continuous
    # batching (mxnet_tpu/serve/ — retired slots back-fill mid-flight).
    # Useful-token throughput at 25/50/100% padded-batch occupancy;
    # continuous wins at sparse occupancy wherever decode compute
    # dominates dispatch (TPU, or serve_bench.py --cpu-full on CPU).
    from benchmark.serve_bench import run_ragged
    S_r, N_r = 8, N
    for frac in (0.25, 0.5, 1.0):
        st, ct, occ, _ttfts = run_ragged(net, cfg, S_r, P, N_r, frac,
                                         2 * S_r)
        print(json.dumps({"bench": "decode",
                          "mode": f"ragged_occ={frac}",
                          "static_padded_tok_s": round(st, 1),
                          "continuous_tok_s": round(ct, 1),
                          "continuous_vs_static": round(ct / st, 3),
                          "tokens_per_dispatch": 1.0,  # spec=False
                          "occupancy": round(occ, 3),
                          "num_slots": S_r, "new_tokens": N_r,
                          "platform": platform}))
        sys.stdout.flush()

    # full-recompute path (the reference-style loop); fewer tokens — it
    # retraces per length and does O(L^2) work
    n2 = min(N, 4)
    net.generate(prompt, max_new_tokens=2, temperature=0.0)  # warm traces
    t0 = time.perf_counter()
    net.generate(prompt, max_new_tokens=n2, temperature=0.0)
    dt = time.perf_counter() - t0
    print(json.dumps({"bench": "decode", "mode": "full_recompute",
                      "tokens_per_sec": round(B * n2 / dt, 1),
                      "tokens_per_dispatch": 1.0,  # 1 forward/token
                      "batch": B, "new_tokens": n2,
                      "platform": platform}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
