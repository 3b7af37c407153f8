"""The serving entry of the granite4h_micro configuration: the model behind
``serve.DecodeServer`` on the scheduler's own thread, driven by a closed loop
of short chats (``generators/closed_loop.py``).  The driver, the warm-up of
the pinned ladder, the clock readings and the tracer are ``entries/serve.py``'s,
by import.

Set-up builds the model (``mxnet_tpu.models.granite_hybrid``) with the
benchmark's seeded weights, handed over leaf by leaf so that the model is on
the device once, warms every (wave, bucket) admit executable and the step,
starts the scheduler thread and fills the pool; the window opens when every
client has had its first token.

``correct``: once the window has closed and the server is gone, the plain
reference (``reference_granite.py``: float32, token-by-token recurrence)
re-reads a seeded sample of the finished requests, the longest among them,
at every served position, and the mean gap by which a served token's logit
lies below the reference's best is compared (``entries/serve.py``'s
measure; limit from chip readings of the program and of the int8 control,
PERF.md section 2).  With ``ctx.control`` (``readings.py``) the same gap is
read for the int8 control's tokens and for those of the reference with a
bfloat16 STATE.
"""
import gc
import time

import numpy as np

from chipbench import granite, harness, reference_granite
from chipbench.entries import serve as serve_entry


def _check(ctx, cfg, model_shapes, finished):
    """Over a seeded sample of ``finished`` with the longest in it: per
    reading (the program's served tokens; with ``ctx.control`` also the int8
    control's and the bfloat16-state reference's own) the mean and the widest
    gap to the reference's best logit; the malformed streams; the tokens
    read."""
    import jax.numpy as jnp

    V, T = cfg["vocab_size"], int(ctx.config["server"]["max_total_len"])
    bad = sum(1 for r in finished
              if len(r["tokens"]) != r["max_new"]
              or min(r["tokens"]) < 0 or max(r["tokens"]) >= V)
    controls = ("int8", "bf16_state") if ctx.control else ()
    none = {"mean": None, "widest": None}
    if not finished:
        return {k: none for k in ("program",) + controls}, bad, 0
    rng = np.random.default_rng([int(ctx.seed), 0x5A])
    longest = max(finished, key=lambda r: r["prompt_len"] + r["max_new"])
    rest = [r for r in finished if r is not longest]
    picks = [longest] + [rest[i] for i in rng.permutation(len(rest))[
        :int(ctx.config["check"]["sample"]) - 1]]
    w = granite.seeded_weights(ctx.config, model_shapes, ctx.seed)
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
        gap, _ = reference_granite.served_gaps(*args)
        gaps["program"].append(np.asarray(gap)[P - 1:P - 1 + n])
        for c in controls:
            _, gap_c = reference_granite.served_gaps(*args, control=c)
            gaps[c].append(np.asarray(gap_c)[P - 1:P - 1 + n])

    def summary(parts):
        g = np.concatenate(parts).astype(np.float64)
        return {"mean": float(g.mean()), "widest": float(g.max())}

    return {k: summary(v) for k, v in gaps.items()}, bad, \
        int(sum(g.size for g in gaps["program"]))


def run(ctx):
    import mxnet_tpu as mx
    from mxnet_tpu import serve, telemetry

    config = ctx.config
    cfg = granite.reference_config(config)
    traffic = ctx.generator().make(ctx.traffic, ctx.seed, cfg["vocab_size"])
    ctx.requests = traffic["requests"]

    # set-up's parts, printed beside the result (``numbers``): reaching the
    # device and making the traffic; the model with its seeded weights; the
    # warm-up; the pool's fill until every client has had its first token
    t_entry = time.time() - ctx.t_start
    t_load = time.perf_counter()
    net, model_cfg = granite.build(config)
    model_shapes = granite.shapes(model_cfg)
    # an inference deployment attaches no gradient buffers
    net.collect_params().setattr("grad_req", "null")
    net.initialize(mx.init.Zero())
    granite.load_seeded(net, config, model_shapes, ctx.seed)
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
    gaps, bad, checked = _check(ctx, cfg, model_shapes, finished)
    compared = {
        "served_gap_mean": {"value": gaps["program"]["mean"],
                            "limit": config["limits"]["served_gap_mean"]},
        "malformed_streams": {"value": bad + len(failed), "limit": 0},
        "compiles_in_window": {"value": compiles_window, "limit": 0},
    }
    diff = lambda k: (stats1.get(k) or 0) - (stats0.get(k) or 0)
    steps = stats1["steps"] - stats0["steps"]
    lane_steps = (stats1["occupancy"] * stats1["steps"]
                  - stats0["occupancy"] * stats0["steps"]) \
        * stats1["num_slots"]
    control = gaps.get("int8", {"mean": None, "widest": None})
    return {
        "end_to_end": {
            "serve_tok_s": tokens_in / ctx.seconds,
            "tpot_p50_ms": harness.percentile(tpot, 50) if tpot else None,
            "ttft_p95_ms": harness.percentile(ttft, 95) if ttft else None,
            "setup_s": setup_s,
        },
        "attempted": len(in_window), "failed": len(failed),
        "compared": compared, "memory_peak_bytes": memory_peak,
        "trace": trace, "geometry": cfg, "records": records,
        "control": {
            "served_gap_mean": control["mean"],
            "served_gap_widest": control["widest"],
            "bf16_state_gap_mean": gaps.get("bf16_state", {}).get("mean")},
        "numbers": {"served_gap_widest": gaps["program"]["widest"],
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
            "prefix_cache", "pages_in_use", "pages_total",
            "step_pages_walked", "step_pages_table")},
        "counters": {
            "steps": steps, "occupied_lane_steps": lane_steps,
            "num_slots": stats1["num_slots"],
            "pool_bytes": stats1["pool_bytes"],
            "prompt_tokens": diff("prompt_tokens"),
            "state_resets": diff("state_resets"),
            "tokens_in_window": tokens_in,
            "context_tokens_mean": float(np.mean(
                [r["prompt_len"] + r["max_new"] / 2 for r in in_window]))
            if in_window else None,
            "dispatch": {k: stats1["counters"][k] - stats0["counters"][k]
                         for k in stats1["counters"]},
            "checked_tokens": checked,
            "longest_token_gap_ms": max(
                ((b - a) * 1e3 for r in records
                 for a, b in zip(r["times"], r["times"][1:])
                 if t_open <= b < t_close), default=None),
        },
    }
