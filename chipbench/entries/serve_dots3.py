"""The serving entry of the dots3_note configuration: the model behind
``serve.DecodeServer`` on the scheduler's own thread, driven by long-document
sessions (``generators/doc_sessions.py``).  The driver, the clock readings
and the tracer are ``entries/serve.py``'s, by import.

Set-up builds the model (``mxnet_tpu.models.dots3``) with the benchmark's
seeded weights, submits every client's document bare with one new token —
the server's chunked prefill builds its cache and the prefix index registers
its end — and runs a few whole sessions so that every executable the window
can reach (the step, the question chunk, the prefix-hit planning) has run.
All of it is set-up: the window opens when every client has had the first
token of its first answer, and holds no prefill longer than one question
chunk an admission.

``correct``: once the window has closed and the server is gone, the plain
reference (``reference_dots3.py``) re-reads a seeded sample of the finished
requests, the longest among them, at every served position, and the mean
gap by which a served token's logit lies below the reference's best is
compared (``entries/serve.py``'s measure; limit from chip readings of the
program and of the int8 control, PERF.md section 2).
"""
import gc
import time

import numpy as np

from chipbench import dots3, harness, reference_dots3
from chipbench.entries import serve as serve_entry


class _SessionDriver(serve_entry._Driver):
    """``serve._Driver`` whose next request is put together for the client
    that asks it: that client's document + the next question."""

    def __init__(self, srv, traffic, seconds):
        super().__init__(srv, traffic, seconds)
        self.documents = traffic["documents"]

    def submit(self, client):
        if self.next < len(self.requests):
            req = self.requests[self.next]
            req["client"] = client
            req["prompt"] = np.concatenate([self.documents[client],
                                            req["question"]])
        super().submit(client)
        if self.next <= len(self.requests):
            self.requests[self.next - 1].pop("prompt", None)


def _pump_until(srv, streams, what, limit_s=1200.0):
    t0 = time.perf_counter()
    while not all(s.done for s in streams):
        srv.pump()
        if time.perf_counter() - t0 > limit_s:
            raise harness.BenchError(f"{what} did not finish in {limit_s} s")
    return [s.tokens(timeout=0) for s in streams]


def _prepare(srv, traffic, spec, vocab, seed):
    """Cache every document, then run a few whole sessions (a prefix hit,
    a question chunk, a few steps) on questions of their own."""
    docs = traffic["documents"]
    _pump_until(srv, [srv.submit(d, max_new_tokens=1) for d in docs],
                "caching the documents")
    rng = np.random.default_rng([int(seed), 0x77])
    q = spec["question_len"]
    warm = [srv.submit(np.concatenate([docs[c], rng.integers(
        0, vocab, int(n), dtype=np.int32)]), max_new_tokens=3)
        for c, n in zip(range(int(spec.get("warm_requests", 2))),
                        (q["min"], q["max"]))]
    _pump_until(srv, warm, "the warm-up sessions")


def _check(ctx, cfg, model_shapes, traffic, finished):
    """Over a seeded sample of ``finished`` with the longest in it: the mean
    and the widest gap by which a served token's logit lies below the
    reference's best (with ``ctx.control`` the same for the token the int8
    control puts first), the malformed streams, and the tokens read."""
    import jax.numpy as jnp

    V, T = cfg["vocab_size"], int(ctx.config["server"]["max_total_len"])
    nq = int(ctx.config["check"]["rows"])
    bad = sum(1 for r in finished
              if len(r["tokens"]) != r["max_new"]
              or min(r["tokens"]) < 0 or max(r["tokens"]) >= V)
    none = {"mean": None, "widest": None}
    if not finished:
        return none, none, bad, 0
    rng = np.random.default_rng([int(ctx.seed), 0x5A])
    longest = max(finished, key=lambda r: r["prompt_len"] + r["max_new"])
    rest = [r for r in finished if r is not longest]
    picks = [longest] + [rest[i] for i in rng.permutation(len(rest))[
        :int(ctx.config["check"]["sample"]) - 1]]
    w = dots3.seeded_weights(ctx.config, model_shapes, ctx.seed)
    gaps, gaps_control = [], []
    for r in picks:
        req = traffic["requests"][r["i"]]
        prompt = np.concatenate([traffic["documents"][req["client"]],
                                 req["question"]])
        toks = np.asarray(r["tokens"], dtype=np.int32)
        P, n = prompt.size, toks.size
        context = np.zeros(T, np.int32)
        context[:P] = prompt
        context[P:P + n - 1] = toks[:-1]
        end = P - 1 + n     # the served tokens follow positions P-1..end-1
        nxt = np.zeros(nq, np.int32)
        nxt[nq - n:] = toks
        gap, gap_c = reference_dots3.served_gaps(
            w, cfg, jnp.asarray(context), jnp.asarray(nxt), end, nq,
            control=ctx.control)
        gaps.append(np.asarray(gap)[nq - n:])
        gaps_control.append(np.asarray(gap_c)[nq - n:])

    def summary(parts):
        g = np.concatenate(parts).astype(np.float64)
        return {"mean": float(g.mean()), "widest": float(g.max())}

    return summary(gaps), summary(gaps_control), bad, \
        int(sum(g.size for g in gaps))


def run(ctx):
    import mxnet_tpu as mx
    from mxnet_tpu import serve, telemetry

    config = ctx.config
    cfg = dots3.reference_config(config)
    traffic = ctx.generator().make(ctx.traffic, ctx.seed, cfg["vocab_size"])
    ctx.requests = traffic["requests"]

    net, model_cfg = dots3.build(config)
    model_shapes = dots3.shapes(model_cfg)
    # an inference deployment attaches no gradient buffers
    net.collect_params().setattr("grad_req", "null")
    net.initialize(mx.init.Zero())
    dots3.load_seeded(net, config, model_shapes, ctx.seed)
    server_args = {k: tuple(v) if isinstance(v, list) else v
                   for k, v in config["server"].items()}
    srv = serve.DecodeServer(net, autostart=False, **server_args)
    if srv.sync_mode:
        raise harness.BenchError("the server fell back to sync mode: "
                                 + str(srv.sync_reason))
    try:
        t0 = time.perf_counter()
        _prepare(srv, traffic, ctx.traffic, cfg["vocab_size"], ctx.seed)
        prepare_s = time.perf_counter() - t0
        compiles_warm = len(telemetry.events("compile"))
        srv.start()
        driver = _SessionDriver(srv, traffic, ctx.seconds)
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
        chunk_events = telemetry.events("serve_chunk")
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
    # the window may hold no prefill longer than one question chunk an
    # admission: the widest chunk bucket dispatched for a request of it
    window_ids = {r.get("request_id") for r in in_window}
    widest_chunk = max((e["c_bucket"] for e in chunk_events
                        if e["request_id"] in window_ids), default=0)

    del driver, srv, net
    gc.collect()
    gap, gap_control, bad, checked = _check(ctx, cfg, model_shapes, traffic,
                                            finished)
    limits = config["limits"]
    compared = {
        "served_gap_mean": {"value": gap["mean"],
                            "limit": limits["served_gap_mean"]},
        "malformed_streams": {"value": bad + len(failed), "limit": 0},
        "compiles_in_window": {"value": compiles_window, "limit": 0},
        "window_chunk_tokens": {
            "value": widest_chunk,
            "limit": min(config["server"]["prefill_buckets"])},
    }
    diff = lambda k: (stats1.get(k) or 0) - (stats0.get(k) or 0)
    steps = stats1["steps"] - stats0["steps"]
    lane_steps = (stats1["occupancy"] * stats1["steps"]
                  - stats0["occupancy"] * stats0["steps"]) \
        * stats1["num_slots"]
    return {
        "end_to_end": {
            "serve_tok_s": tokens_in / ctx.seconds,
            "tpot_p50_ms": harness.percentile(tpot, 50) if tpot else None,
            "setup_s": setup_s,
        },
        "attempted": len(in_window), "failed": len(failed),
        "compared": compared, "memory_peak_bytes": memory_peak,
        "trace": trace, "geometry": cfg, "records": records,
        "control": {"served_gap_mean": gap_control["mean"],
                    "served_gap_widest": gap_control["widest"]},
        "numbers": {"served_gap_widest": gap["widest"],
                    "prepare_s": prepare_s,
                    # a stream of a few tokens repeated tests no cache
                    "distinct_token_share": float(np.mean(
                        [len(set(r["tokens"])) / len(r["tokens"])
                         for r in finished])) if finished else None},
        "window": {"t_open": t_open, "t_close": t_close, "t_end": t_end},
        "server_stats": {k: stats1.get(k) for k in (
            "moe_tokens_per_expert_step", "moe_experts_touched_share",
            "moe_load_max_over_mean", "selected_keys_per_query",
            "window_pages_slot_max", "window_pages_slot_bound",
            "window_pages_in_use", "window_pages_total", "pages_in_use",
            "pages_total", "prefix_nodes", "prefix_tails")},
        "counters": {
            "steps": steps, "occupied_lane_steps": lane_steps,
            "num_slots": stats1["num_slots"],
            "pool_bytes": stats1["pool_bytes"],
            "prompt_tokens": diff("prompt_tokens"),
            "prompt_tokens_cached": diff("prompt_tokens_cached"),
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
