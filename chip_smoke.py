"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python3 chip_smoke.py          # on a TPU host, from the repo root

One process drives the two paths every benchmark cell stands on, through the
entry points a user calls, at published width with seeded random weights:

- **train**  BERT-base (the geometry of ``bench.py``) on
  ``parallel.SPMDTrainer`` over a dp mesh of every local chip;
- **serve**  GPT-2-small behind ``serve.DecodeServer`` with every default on
  (paged pool, prefix cache, speculation, the real scheduler thread), once in
  bfloat16 and once in float32, each stream checked against
  ``kv_generate(..., temperature=0.0)``;
- **kernel** the default-on Pallas flash-attention forward + backward,
  compiled by Mosaic, against the plain O(L^2) attention.

It exits non-zero on the first failed check and on any platform other than
``tpu`` (before printing any result).  The last stdout line is
``{"ok": true, "device": {"platform", "kind", "count"}}``.  No throughput is
printed: this is a bring-up check, not a benchmark.

Serving places its pool on ``jax.devices()[0]`` by design (one replica per
chip; a router over four replicas is future work), so on a four-chip host
only the train phase uses all four.

``tests/test_chip_smoke.py`` runs the same three phases on the CPU at a tiny
geometry with the flash kernels in interpret mode.
"""
import json
import math
import os
import sys
import threading
import time

import numpy as onp

HERE = os.path.dirname(os.path.abspath(__file__))

# dp=N against dp=1 first-step loss: same weights, same batch, only the
# reduction order of the batch mean and the gradient psum differ.  The
# step returns the loss in the model dtype, so the two may sit one bf16
# spacing apart (2**-7 relative at most).
DP_LOSS_RTOL = 2.0 ** -7


class SmokeFailure(AssertionError):
    """A smoke check failed (a plain ``assert`` would vanish under -O)."""


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def _compile_events(since=0):
    from mxnet_tpu import telemetry
    return telemetry.events("compile")[since:]


def _report(phase, t0, compile_s, **fields):
    wall = time.perf_counter() - t0
    row = {"phase": phase, "ok": True, "wall_s": round(wall, 2),
           "compile_s": round(compile_s, 2),
           "run_s": round(wall - compile_s, 2)}
    row.update(fields)
    print(json.dumps(row), flush=True)
    return row


def _on_platform(arr, platform):
    """The distinct devices holding ``arr``'s shards, all on ``platform``."""
    devs = {s.device for s in arr.addressable_shards}
    check(all(d.platform == platform for d in devs),
          f"array lives on {sorted(str(d) for d in devs)}, "
          f"expected platform {platform!r}")
    return devs


# --------------------------------------------------------------------------- #
# train
# --------------------------------------------------------------------------- #

def train_phase(platform, geom=None, batch=64, steps=4, scan_steps=4):
    import jax
    import jax.numpy as jnp

    import bench
    import mxnet_tpu as mx
    from mxnet_tpu import parallel

    geom = {**bench.BERT_BASE, **(geom or {})}
    t0 = time.perf_counter()
    ev0 = len(_compile_events())
    n_dev = len(jax.devices())
    check(batch % n_dev == 0, f"batch {batch} does not split over "
                              f"{n_dev} devices")
    rng = onp.random.RandomState(0)
    toks = rng.randint(0, geom["vocab_size"], (batch, geom["seq"]))
    labels = rng.randint(0, geom["vocab_size"], (batch, geom["seq"]))

    def first_losses(devices, n):
        mx.random.seed(0)
        net, trainer, mesh = bench.build_bert_trainer(devices=devices,
                                                      **geom)
        # plain mx.nd.array: lands on device 0, the trainer reshards it
        data, label = mx.nd.array(toks), mx.nd.array(labels)
        return net, trainer, mesh, [
            float(trainer.step(data, label).asnumpy().reshape(()))
            for _ in range(n)]

    net, trainer, mesh, losses = first_losses(None, steps)
    scan = trainer.run_steps(bench.repeat_batch(toks, scan_steps),
                             bench.repeat_batch(labels, scan_steps))
    losses += [float(x) for x in scan.asnumpy().reshape(-1)]
    check(len(losses) == steps + scan_steps, "run_steps loss count")
    check(all(math.isfinite(x) for x in losses),
          f"non-finite loss in {losses}")
    check(losses[-1] < losses[0],
          f"loss did not fall on one fixed batch: {losses}")
    # seeded N(0, 0.02) weights predict near-uniformly
    check(abs(losses[0] - math.log(geom["vocab_size"])) < 0.5,
          f"first loss {losses[0]} far from ln(vocab)")

    # the batch pre-placed with the trainer's own dp sharding goes through
    # the SAME executables (no retrace below) and sits on every device
    sharded = jax.device_put(jnp.asarray(toks), parallel.data_sharding(mesh))
    batch_devs = _on_platform(sharded, platform)
    check(len(batch_devs) == n_dev and all(
        s.data.shape == (batch // n_dev, geom["seq"])
        for s in sharded.addressable_shards),
        f"batch shards on {len(batch_devs)} devices, expected {n_dev}")
    trainer.step(mx.nd.from_jax(sharded), mx.nd.array(labels))

    for name, p in net.collect_params().items():
        devs = _on_platform(p.data().asjax(), platform)
        check(len(devs) == n_dev,
              f"{name} replicated on {len(devs)} devices, expected {n_dev}")

    events = _compile_events(ev0)
    sites = sorted(e["site"] for e in events)
    check(sites == ["spmd.run_steps", "spmd.step"],
          f"expected one compile per program, got {sites}")
    check(not any(e.get("retrace") for e in events), "train step retraced")

    dp1 = None
    if n_dev > 1:
        # the same seed, weights and batch on ONE chip
        _, _, _, (dp1,) = first_losses(jax.devices()[:1], 1)
        check(abs(dp1 - losses[0]) <= DP_LOSS_RTOL * abs(dp1),
              f"dp={n_dev} first loss {losses[0]} != dp=1 loss {dp1} "
              f"(rtol {DP_LOSS_RTOL})")
    return _report("train", t0,
                   sum(e["wall_s"] for e in _compile_events(ev0)),
                   devices=n_dev,
                   per_device_batch=batch // n_dev,
                   losses=[round(x, 4) for x in losses],
                   dp1_first_loss=dp1)


# --------------------------------------------------------------------------- #
# serve
# --------------------------------------------------------------------------- #

def _gpt2_small(dtype):
    from mxnet_tpu import models
    return models.gpt2_small(vocab_size=50257, max_length=1024, dtype=dtype)


def _prompts(vocab, lens, seed=0):
    """Ragged seeded prompts; the second is self-similar (a tiled 6-gram)
    so the n-gram drafter has something to propose."""
    rng = onp.random.RandomState(seed)
    out = [rng.randint(1, vocab, (n,)).astype(onp.int32) for n in lens]
    out[1] = onp.tile(rng.randint(1, vocab, (6,)), lens[1] // 6 + 1)[
        :lens[1]].astype(onp.int32)
    return out


def serve_phase(platform, dtype, make_net=_gpt2_small, max_total_len=1024,
                prompt_lens=(5, 48, 150, 700), max_new=12, recording=None):
    """Correctness rule against ``kv_generate(..., temperature=0.0)``.

    The repo's contract is token identity, and it was pinned on CPU, where
    the admit, step and verify executables happen to round alike.

    - float32: identity, every stream, every position.  ``base.py`` pins
      f32 matmuls to ``highest``, so executables differ by f32 rounding
      only and a top-2 tie at that resolution does not occur in practice.
    - bfloat16: identity up to the first differing position; there the
      model's own plain forward (``net(tokens)``, a third executable) must
      put the served and the reference token within two bf16 spacings of
      each other and of the top logit.  bf16 logits in [2**k, 2**(k+1))
      lie 2**(k-7) apart, so among 50257 of them exact ties at the top are
      common; executables that batch and tile differently each round a
      logit by up to one spacing and break such a tie differently.  Nothing
      after that position is comparable, because the contexts differ.
      (First chip run of this rule: 3 of 5 streams identical, 2 parted at
      position 0 on an exact tie, gap 0.0.)
    """
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import models, serve, telemetry
    from mxnet_tpu.telemetry.memory import ACCOUNTANT
    from tools import telemetry_report

    t0 = time.perf_counter()
    ev0 = len(_compile_events())
    mx.random.seed(0)
    net, cfg = make_net(dtype)
    net.initialize(mx.init.Normal(0.02))
    vocab = cfg.vocab_size
    prompts = _prompts(vocab, prompt_lens)
    refs = [models.kv_generate(net, p[None], max_new_tokens=max_new,
                               temperature=0.0)[0, p.size:]
            for p in prompts]
    # the third prompt comes back after its first run retired: a full
    # prefix-cache hit
    repeat = 2
    requests = prompts + [prompts[repeat]]
    expected = refs + [refs[repeat]]

    if recording is None:
        recording = os.path.join(HERE, "chiprun_out", "chip_smoke",
                                 f"serve_{dtype}.jsonl")
    os.makedirs(os.path.dirname(recording), exist_ok=True)
    if os.path.exists(recording):
        os.remove(recording)
    sink = telemetry.add_jsonl_sink(recording)
    served, errors = [], []
    try:
        srv = serve.DecodeServer(net, max_total_len=max_total_len)
        check(srv.sync_mode is False,
              f"server fell back to sync mode: {srv.sync_reason}")

        def client():
            try:
                streams = [srv.submit(p, max_new_tokens=max_new)
                           for p in prompts]
                served.extend(s.tokens(timeout=900) for s in streams)
                served.append(srv.submit(
                    prompts[repeat],
                    max_new_tokens=max_new).tokens(timeout=900))
            except Exception as e:   # surfaced on the main thread below
                errors.append(e)

        th = threading.Thread(target=client, name="smoke-client")
        th.start()
        th.join(1000)
        check(not th.is_alive(), "serve client did not finish in 1000 s")
        if errors:
            raise errors[0]
        stats = srv.stats()
        pool = ACCOUNTANT.snapshot().get("serve.kv_pool", {})
        srv.close()
    finally:
        telemetry.remove_sink(sink)

    ties = []
    for i, (prompt, got, ref) in enumerate(zip(requests, served, expected)):
        got = onp.asarray(got)
        check(got.shape == (max_new,) and (got >= 0).all()
              and (got < vocab).all(),
              f"stream {i}: {got.tolist()} is not {max_new} "
              "in-vocabulary tokens")
        diff = onp.nonzero(got != ref)[0]
        if diff.size == 0:
            continue
        t = int(diff[0])
        check(dtype != "float32",
              f"stream {i} (float32) left the reference at position {t}: "
              f"served {got.tolist()} vs {ref.tolist()}")
        ctx = onp.concatenate([prompt, ref[:t]])[None]
        z = net(mx.nd.array(ctx, dtype="int32")).asnumpy()[0, -1].astype(
            onp.float32)
        top = float(z.max())
        spacing = 2.0 ** (math.floor(math.log2(abs(top))) - 7)
        gap = float(abs(z[ref[t]] - z[got[t]]))
        below_top = top - float(min(z[ref[t]], z[got[t]]))
        ties.append({"stream": i, "pos": t, "top": top, "gap": gap,
                     "below_top": below_top, "bf16_spacing": spacing})
        check(gap <= 2 * spacing and below_top <= 2 * spacing,
              f"stream {i} left the reference at position {t} where the "
              f"logits are no near-tie (top {top}, gap {gap}, below top "
              f"{below_top}, bf16 spacing {spacing}): served "
              f"{got.tolist()} vs {ref.tolist()}")

    c = stats["counters"]
    check(c["step_dispatches"] == stats["steps"],
          f"{c['step_dispatches']} step dispatches for "
          f"{stats['steps']} steps")
    check(c["prefix_hits"] >= 1, "the repeated prompt took no prefix hit")
    check(c["verify_dispatches"] >= 1, "the drafter never proposed")
    check(c["sync_requests"] == 0, "a request was served synchronously")
    check(pool and all(d.startswith(platform + ":") for d in pool)
          and sum(pool.values()) == stats["pool_bytes"],
          f"pool state accounted on {pool}, expected {platform}")
    events = _compile_events(ev0)
    check(not any(e.get("retrace") for e in events),
          "a serve executable retraced: "
          f"{[e['site'] for e in events if e.get('retrace')]}")
    check(telemetry_report.main([recording, "--check-serve"]) == 0,
          "telemetry_report --check-serve failed on the run's recording")
    return _report(
        f"serve_{dtype}", t0, sum(e["wall_s"] for e in events),
        device=str(jax.devices()[0]), requests=len(served),
        compiles=len(events), steps=stats["steps"],
        num_slots=stats["num_slots"], prefix_hits=c["prefix_hits"],
        verify_dispatches=c["verify_dispatches"],
        draft_accepted=c["draft_accepted"],
        draft_rejected=c["draft_rejected"],
        identical_streams=len(served) - len(ties), near_ties=ties)


# --------------------------------------------------------------------------- #
# kernel
# --------------------------------------------------------------------------- #

def kernel_phase(platform, shape=(2, 12, 2048, 64), dtype="bfloat16"):
    """Flash attention fwd + bwd through the public op at a shape the
    measured dispatch table routes to the Pallas kernels, against
    ``_plain_attn``.  Tolerance: outputs are O(1) and gradients are
    compared relative to the reference's largest entry, both within 4
    bf16 ulps (2**-6) — the two paths accumulate in f32 and differ in
    where they round to bf16."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu import nd
    from mxnet_tpu.ops.attention import _choose_path, _plain_attn

    t0 = time.perf_counter()
    B, H, L, D = shape
    interpret = os.environ.get("MXNET_FLASH_INTERPRET") == "1"
    check(not (interpret and platform == "tpu"),
          "MXNET_FLASH_INTERPRET must be unset on the chip (it is for the "
          "CPU rehearsal)")
    check(_choose_path(L, L, None, True) == "pallas",
          f"L={L} training is not routed to the Pallas kernels")
    scale = 1.0 / D ** 0.5
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v, do = (jax.random.normal(kk, shape, jnp.float32).astype(dtype)
                   for kk in ks)

    def fwd_bwd(attn):
        def run(q, k, v, do):
            out, vjp = jax.vjp(attn, q, k, v)
            return (out,) + vjp(do)
        return jax.jit(run)

    flash = fwd_bwd(lambda q, k, v: nd.flash_attention(
        q, k, v, causal=True, training=True).asjax())
    plain = fwd_bwd(lambda q, k, v: _plain_attn(q, k, v, None, scale, True))

    tc = time.perf_counter()
    lowered = flash.lower(q, k, v, do)
    n_calls = lowered.as_text().count("tpu_custom_call")
    if not interpret:
        # forward + dq + dk/dv; a quiet drop to the XLA path has none
        check(n_calls >= 3, f"{n_calls} Mosaic custom calls in the "
                            "lowered fwd+bwd program, expected >= 3")
    flash_c = lowered.compile()
    plain_c = plain.lower(q, k, v, do).compile()
    compile_s = time.perf_counter() - tc

    got = jax.block_until_ready(flash_c(q, k, v, do))
    ref = jax.block_until_ready(plain_c(q, k, v, do))
    errs = {}
    for name, g, r in zip(("out", "dq", "dk", "dv"), got, ref):
        _on_platform(g, platform)
        g = onp.asarray(g.astype(jnp.float32))
        r = onp.asarray(r.astype(jnp.float32))
        check(g.shape == shape and onp.isfinite(g).all(),
              f"flash {name}: bad shape or non-finite values")
        errs[name] = float(onp.abs(g - r).max() / onp.abs(r).max())
        check(errs[name] < 2.0 ** -6,
              f"flash {name} off the plain path by {errs[name]} of its "
              "largest entry (bound 2**-6)")
    return _report("kernel", t0, compile_s, shape=list(shape), dtype=dtype,
                   mosaic_calls=n_calls,
                   max_rel_err={n: float(f"{e:.3g}")
                                for n, e in errs.items()})


# --------------------------------------------------------------------------- #

def main():
    import importlib.metadata as md

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: jax found platform {dev.platform!r}, not a TPU; "
              "nothing to prove here", file=sys.stderr)
        return 1
    n_dev = len(jax.devices())

    import mxnet_tpu  # noqa: F401  (configures the compile cache)

    cache_dir = jax.config.jax_compilation_cache_dir
    warm = os.path.isdir(cache_dir) and len(os.listdir(cache_dir))
    print(f"chip_smoke: jax {jax.__version__} jaxlib "
          f"{md.version('jaxlib')} libtpu {md.version('libtpu')} "
          f"python {sys.version.split()[0]}")
    print(f"chip_smoke: platform={dev.platform} "
          f"device_kind={dev.device_kind!r} devices={n_dev}")
    print(f"chip_smoke: compile cache {cache_dir} "
          f"({'warm, %d entries' % warm if warm else 'cold'})")
    if n_dev > 1:
        print(f"chip_smoke: train runs dp={n_dev}; serve and kernel run on "
              f"{dev} only (one serve replica per chip by design)")

    t0 = time.perf_counter()
    rows = [train_phase("tpu"),
            serve_phase("tpu", "bfloat16"),
            serve_phase("tpu", "float32"),
            kernel_phase("tpu")]
    print(f"chip_smoke: all phases passed in "
          f"{time.perf_counter() - t0:.1f} s, "
          f"{sum(r['compile_s'] for r in rows):.1f} s of it compile "
          f"(trace + compile + first dispatch)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": n_dev}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
