"""The serving entry of the brumby_14b configuration: the model behind
``serve.DecodeServer`` on the scheduler's own thread, driven by a closed loop
of long base-model completions (``generators/closed_loop.py``).  The driver,
the warm-up of the pinned ladder, the clock readings and the tracer are
``entries/serve.py``'s, by import.

Set-up builds the model (``mxnet_tpu.models.brumby``) with the benchmark's
seeded weights, handed over leaf by leaf so that the model is on the device
once, warms every admit executable, every chunk executable (a prompt of the
widest bucket and one more bucket's worth of tokens, for each bucket) and the
step, starts the scheduler thread and fills the pool; the window opens when
every client has had its first token.

``correct``: once the window has closed, one PROBE request of the
configuration's ``check`` lengths (seeded ids, past the widest bucket, so it
is chunked) is served alone, and its slot's final retention state is read
back (``DecodeServer.slot_state``).  After the server is gone, the plain
reference (``reference_brumby.py``: float32, the attention form) re-reads

- a seeded sample of the finished requests, the longest among them, at every
  served position: the mean gap by which a served token's logit lies below
  the reference's best (``entries/serve.py``'s measure);
- the probe's final state, by probe vectors against the SUM form of the
  equations: the largest relative error over layers and KV heads
  (``state_rel_err``), which is what a state kept at a lower precision moves.

Limits from chip readings of the program and of the controls (PERF.md
section 2).  With ``ctx.control`` (``readings.py``) the same two numbers are
read for the int8 control and for the reference with its state rounded to
bfloat16 after every token (its recurrent form), and ``state_rel_err`` for
the reference with its state rounded only after each token past the probe's
prompt (``bf16_step``: prefill in float32, every decode step's write in
bfloat16).
"""
import gc
import time

import numpy as np

from chipbench import brumby, harness, reference_brumby
from chipbench.entries import serve as serve_entry

IDLE_WAIT_S = 120.0


def _warm_chunks(srv, config, vocab, seed):
    """Every chunk executable a prompt past the widest bucket can reach: a
    prompt of the widest bucket plus each bucket, two tokens each."""
    buckets = config["server"]["prefill_buckets"]
    rng = np.random.default_rng([int(seed), 0x78])
    for b in buckets:
        s = srv.submit(rng.integers(0, vocab, buckets[-1] + b,
                                    dtype=np.int32), max_new_tokens=2)
        for _ in range(64):
            if s.done:
                break
            srv.pump()
        s.tokens(timeout=0)


def _probe(srv, config, vocab, seed):
    """Serve the probe request alone and read its slot's final state:
    ``(prompt, tokens, (state, z))`` or ``None`` where it did not finish."""
    chk = config["check"]
    prompt = np.random.default_rng([int(seed), 0x91]).integers(
        0, vocab, int(chk["probe_prompt"]), dtype=np.int32)
    stream = srv.submit(prompt, max_new_tokens=int(chk["probe_new"]))
    toks = stream.tokens(timeout=IDLE_WAIT_S)
    deadline = time.monotonic() + IDLE_WAIT_S
    while True:
        try:
            held = [srv.slot_state(s) for s in range(srv.stats()[
                "num_slots"])]
            break
        except Exception:       # not idle yet: the last readbacks
            if time.monotonic() > deadline:
                return None
            time.sleep(0.05)
    mine = [entries for rid, entries in held if rid == stream.request_id]
    if len(toks) != int(chk["probe_new"]) or not mine:
        return None
    return prompt, np.asarray(toks, np.int32), mine[0]


def _probe_vectors(cfg, n, seed):
    import jax.numpy as jnp
    rng = np.random.default_rng([int(seed), 0x92])
    return jnp.asarray(rng.normal(size=(
        cfg["num_hidden_layers"], cfg["num_key_value_heads"], n,
        cfg["head_dim"])), jnp.float32)


def _state_errors(ctx, cfg, w, probe, controls):
    """``state_rel_err`` of the program's probe state and of each control's
    (``bf16_step`` rounds from the probe's prompt's end on); the decays'
    median over the probe's context."""
    import jax.numpy as jnp
    from mxnet_tpu.ops import power_retention as pr

    out = {k: None for k in ("program",) + controls}
    if probe is None:
        return out, None
    prompt, toks, (state, z) = probe
    context = jnp.asarray(np.concatenate([prompt, toks[:-1]]))
    vecs = _probe_vectors(cfg, int(ctx.config["check"]["probes"]), ctx.seed)
    _, read = reference_brumby.full_logits(w, cfg, context, probes=vecs)
    mine = [(jnp.einsum("gnE,gvE->gnv", pr.expand(vecs[j]),
                        jnp.asarray(state[j])),
             jnp.einsum("gnE,gE->gn", pr.expand(vecs[j]), jnp.asarray(z[j])))
            for j in range(cfg["num_hidden_layers"])]
    out["program"] = reference_brumby.state_error(read, mine)
    for c in controls:
        out[c] = reference_brumby.state_error(
            read, reference_brumby.control_state(w, cfg, context, vecs, c,
                                                 prompt_len=prompt.size))
    decay = float(np.median(np.asarray(
        reference_brumby.decays(w, cfg, context))))
    return out, decay


def _check(ctx, cfg, model_shapes, finished, probe):
    """Over a seeded sample of ``finished`` with the longest in it: per
    reading (the program's served tokens; with ``ctx.control`` also the int8
    control's and the bfloat16-state control's) the mean and the widest gap
    to the reference's best logit; the probe's state errors (with
    ``ctx.control`` also the ``bf16_step`` control's); the malformed
    streams; the tokens read."""
    import jax.numpy as jnp

    V, T = cfg["vocab_size"], int(ctx.config["server"]["max_total_len"])
    bad = sum(1 for r in finished
              if len(r["tokens"]) != r["max_new"]
              or min(r["tokens"]) < 0 or max(r["tokens"]) >= V)
    controls = ("int8", "bf16_state") if ctx.control else ()
    w = brumby.seeded_weights(ctx.config, model_shapes, ctx.seed)
    state_err, decay = _state_errors(
        ctx, cfg, w, probe, controls + ("bf16_step",) * bool(ctx.control))
    none = {"mean": None, "widest": None}
    if not finished:
        return {k: none for k in ("program",) + controls}, state_err, \
            decay, bad, 0
    rng = np.random.default_rng([int(ctx.seed), 0x5A])
    longest = max(finished, key=lambda r: r["prompt_len"] + r["max_new"])
    rest = [r for r in finished if r is not longest]
    picks = [longest] + [rest[i] for i in rng.permutation(len(rest))[
        :int(ctx.config["check"]["sample"]) - 1]]
    gaps = {k: [] for k in ("program",) + controls}
    for r in picks:
        prompt = ctx.requests[r["i"]]["prompt"]
        toks = np.asarray(r["tokens"], dtype=np.int32)
        P, n = prompt.size, toks.size
        context = np.zeros(T, np.int32)
        context[:P] = prompt
        context[P:P + n - 1] = toks[:-1]
        nxt = np.zeros(T, np.int32)
        nxt[P - 1:P - 1 + n] = toks
        args = (w, cfg, jnp.asarray(context), jnp.asarray(nxt))
        gap, _ = reference_brumby.served_gaps(*args)
        gaps["program"].append(np.asarray(gap)[P - 1:P - 1 + n])
        for c in controls:
            _, gap_c = reference_brumby.served_gaps(*args, control=c)
            gaps[c].append(np.asarray(gap_c)[P - 1:P - 1 + n])

    def summary(parts):
        g = np.concatenate(parts).astype(np.float64)
        return {"mean": float(g.mean()), "widest": float(g.max())}

    return {k: summary(v) for k, v in gaps.items()}, state_err, decay, bad, \
        int(sum(g.size for g in gaps["program"]))


def run(ctx):
    import mxnet_tpu as mx
    from mxnet_tpu import serve, telemetry

    config = ctx.config
    cfg = brumby.reference_config(config)
    traffic = ctx.generator().make(ctx.traffic, ctx.seed, cfg["vocab_size"])
    ctx.requests = traffic["requests"]

    t_entry = time.time() - ctx.t_start
    t_load = time.perf_counter()
    net, model_cfg = brumby.build(config)
    model_shapes = brumby.shapes(model_cfg)
    # an inference deployment attaches no gradient buffers
    net.collect_params().setattr("grad_req", "null")
    net.initialize(mx.init.Zero())
    brumby.load_seeded(net, config, model_shapes, ctx.seed)
    server_args = {k: tuple(v) if isinstance(v, list) else v
                   for k, v in config["server"].items()}
    srv = serve.DecodeServer(net, autostart=False, **server_args)
    if srv.sync_mode:
        raise harness.BenchError("the server fell back to sync mode: "
                                 + str(srv.sync_reason))
    try:
        t0 = time.perf_counter()
        load_s = t0 - t_load
        serve_entry._warm(srv, config, cfg["vocab_size"], ctx.seed)
        _warm_chunks(srv, config, cfg["vocab_size"], ctx.seed)
        warm_s = time.perf_counter() - t0
        compiles_warm = len(telemetry.events("compile"))
        srv.start()
        driver = serve_entry._Driver(srv, traffic, ctx.seconds)
        tracer = None
        if ctx.trace:
            tracer = harness.Tracer(float(ctx.traffic["trace_delay_s"]),
                                    min(float(ctx.traffic["trace_seconds"]),
                                        ctx.seconds))
            tracer.start()
        t_open, t_close, t_end = driver.run()
        stats0, stats1 = driver.stats_open, srv.stats()
        compiles_window = len(telemetry.events("compile")) - compiles_warm
        request_events = {e["request_id"]: e
                          for e in telemetry.events("serve_request")}
        trace = tracer.finish() if tracer is not None else None
        memory_peak = harness.memory_peak_bytes()
        probe = _probe(srv, config, cfg["vocab_size"], ctx.seed)
    finally:
        srv.close(drain=False, timeout=30.0)
    setup_s = (time.time() - ctx.t_start) - (time.perf_counter() - t_open)

    records = driver.records
    for r in records:
        r.pop("stream", None)
        ev = request_events.get(r.get("request_id"))
        r["queue_wait_s"] = None if ev is None else ev.get("queue_wait_s")
    in_window = [r for r in records if t_open <= r["submit"] < t_close]
    failed = [r for r in in_window if r["error"] is not None]
    tokens_in = sum(1 for r in records for t in r["times"]
                    if t_open <= t < t_close)
    finished = [r for r in records if r["error"] is None and r["times"]
                and len(r["times"]) == r["max_new"]
                and t_open <= r["times"][-1] < t_close]
    tpot = [(r["times"][-1] - r["times"][0]) * 1e3 / (len(r["times"]) - 1)
            for r in finished]
    ttft = [((r["times"][0] if r["times"] else t_end) - r["submit"]) * 1e3
            for r in in_window]

    del driver, srv, net
    gc.collect()
    gaps, state_err, decay, bad, checked = _check(ctx, cfg, model_shapes,
                                                  finished, probe)
    limits = config["limits"]
    compared = {
        "served_gap_mean": {"value": gaps["program"]["mean"],
                            "limit": limits["served_gap_mean"]},
        "state_rel_err": {"value": state_err["program"],
                          "limit": limits["state_rel_err"]},
        "malformed_streams": {"value": bad + len(failed), "limit": 0},
        "compiles_in_window": {"value": compiles_window, "limit": 0},
    }
    diff = lambda k: (stats1.get(k) or 0) - (stats0.get(k) or 0)
    steps = stats1["steps"] - stats0["steps"]
    lane_steps = (stats1["occupancy"] * stats1["steps"]
                  - stats0["occupancy"] * stats0["steps"]) \
        * stats1["num_slots"]
    control = lambda c: {
        "served_gap_mean": gaps.get(c, {}).get("mean"),
        "served_gap_widest": gaps.get(c, {}).get("widest"),
        "state_rel_err": state_err.get(c)}
    return {
        "end_to_end": {
            "serve_tok_s": tokens_in / ctx.seconds,
            "tpot_p50_ms": harness.percentile(tpot, 50) if tpot else None,
            "ttft_p95_ms": harness.percentile(ttft, 95) if ttft else None,
            "setup_s": setup_s,
        },
        "attempted": len(in_window), "failed": len(failed),
        "compared": compared, "memory_peak_bytes": memory_peak,
        "trace": trace, "records": records, "geometry": cfg,
        "control": {"int8": control("int8"),
                    "bf16_state": control("bf16_state"),
                    "bf16_step": control("bf16_step"),
                    # as the other entries name it: the int8 control's gap
                    "served_gap_mean": gaps.get("int8", {}).get("mean")},
        "numbers": {"served_gap_widest": gaps["program"]["widest"],
                    "decay_median": decay,
                    "reach_s": t_entry, "load_s": load_s,
                    "warm_s": warm_s,
                    "fill_s": t_open - t0 - warm_s,
                    # a stream of a few tokens repeated tests no state
                    "distinct_token_share": float(np.mean(
                        [len(set(r["tokens"])) / len(r["tokens"])
                         for r in finished])) if finished else None},
        "window": {"t_open": t_open, "t_close": t_close, "t_end": t_end},
        "server_stats": {k: stats1.get(k) for k in (
            "state_bytes_per_slot", "state_resets", "slot_kinds",
            "prefix_cache", "pages_in_use", "pages_total")},
        "counters": {
            "steps": steps, "occupied_lane_steps": lane_steps,
            "num_slots": stats1["num_slots"],
            "pool_bytes": stats1["pool_bytes"],
            "prompt_tokens": diff("prompt_tokens"),
            "state_resets": diff("state_resets"),
            "tokens_in_window": tokens_in,
            "dispatch": {k: stats1["counters"][k] - stats0["counters"][k]
                         for k in stats1["counters"]},
            "checked_tokens": checked,
            "longest_token_gap_ms": max(
                ((b - a) * 1e3 for r in records
                 for a, b in zip(r["times"], r["times"][1:])
                 if t_open <= b < t_close), default=None),
        },
    }
