"""The serving entry: a GPT-2 behind ``serve.DecodeServer`` on the
scheduler's own thread, driven by a generator's requests.

Set-up builds the model with the benchmark's seeded weights, warms exactly
the ladder the configuration pins (every (wave, bucket) admit executable and
the step), starts the scheduler thread and fills the pool; the window opens
when every client has had its first token.  The clock readings are the
benchmark's own: ``time.perf_counter()`` just before ``submit`` and inside
the per-token callback, which the scheduler thread calls as it routes a
token to its stream.

``correct``: once the window has closed, the peak has been read and the
server is gone, the plain reference runs once over a seeded sample of the
requests the window finished (the longest among them) and reads, at every
served position, how far the served token's logit lies below the
reference's best; the mean of that gap is what is compared (the widest gap,
an extreme of a few hundred readings, does not tell the program from the
int8 control: PERF.md section 2).
"""
import gc
import queue
import threading
import time

import numpy as np

from chipbench import gpt, harness, reference

SAMPLE = 32           # requests the reference re-reads, the longest included
STALL_S = 120.0       # no stream finishes for this long: the run has failed
FIRST_TOKEN_WAIT_S = 60.0   # how long past the close a first token may come


class _Driver:
    """Submits, stamps and collects; one instance a run."""

    def __init__(self, srv, traffic, seconds):
        self.srv, self.seconds = srv, seconds
        self.requests = traffic["requests"]
        self.clients = traffic["clients"]
        self.records, self.next = [], 0
        self.done_q = queue.SimpleQueue()
        self.first_seen, self.t_open, self.stats_open = 0, None, None
        self.opened = threading.Event()

    def _on_token(self, rec, tok):          # scheduler thread
        t = time.perf_counter()
        rec["times"].append(t)
        rec["tokens"].append(tok)
        if len(rec["times"]) == 1 and rec["i"] < self.clients:
            self.first_seen += 1
            if self.first_seen == self.clients:
                self.t_open, self.stats_open = t, self.srv.stats()
                self.opened.set()
        if len(rec["times"]) == rec["max_new"]:
            self.done_q.put(rec)

    def submit(self, client):
        if self.next >= len(self.requests):
            raise harness.BenchError(
                f"the traffic file's {len(self.requests)} requests ran out; "
                "raise `requests`")
        req = self.requests[self.next]
        rec = {"i": self.next, "client": client,
               "prompt_len": int(req["prompt"].size),
               "max_new": int(req["max_new"]), "times": [], "tokens": [],
               "error": None}
        self.next += 1
        self.records.append(rec)
        rec["submit"] = time.perf_counter()
        try:
            rec["stream"] = self.srv.submit(
                req["prompt"], max_new_tokens=rec["max_new"],
                on_token=lambda rid, tok, rec=rec: self._on_token(rec, tok))
            rec["request_id"] = rec["stream"].request_id
        except Exception as e:      # a refused request is a failed one
            rec["error"], rec["stream"] = repr(e), None
            self.done_q.put(rec)

    def _sweep(self):
        """Mark the streams that ended before their last token (a server
        error): each counts as failed and frees its client."""
        for r in self.records:
            s = r["stream"]
            if r["error"] is None and s is not None and s.done \
                    and not s.cancelled and len(r["times"]) < r["max_new"]:
                r["error"] = "stream ended early"
                self.done_q.put(r)

    def run(self):
        """Closed loop until the window closes; then wait only until every
        request submitted in the window has had its first token (a late
        first token is late, not lost), and cancel what is still decoding.
        Returns (t_open, t_close, t_end)."""
        for c in range(self.clients):
            self.submit(c)
        t_close, last = None, time.perf_counter()
        while t_close is None or time.perf_counter() < t_close:
            if t_close is None and self.opened.is_set():
                t_close = self.t_open + self.seconds
            try:
                rec = self.done_q.get(timeout=0.02)
            except queue.Empty:
                self._sweep()
                if time.perf_counter() - last > STALL_S:
                    break
                continue
            last = time.perf_counter()
            if t_close is None or last < t_close:
                self.submit(rec["client"])
        if t_close is None:
            raise harness.BenchError("the window never opened: " + str(
                [r["error"] for r in self.records if r["error"]][:3]))
        deadline = t_close + FIRST_TOKEN_WAIT_S
        while time.perf_counter() < deadline:
            self._sweep()
            if all(r["times"] or r["error"] is not None
                   for r in self.records):
                break
            time.sleep(0.005)
        t_end = time.perf_counter()
        for r in self.records:
            if r["error"] is None and not r["times"]:
                r["error"] = "no first token " \
                             f"{FIRST_TOKEN_WAIT_S} s past the close"
            if r["stream"] is not None and not r["stream"].done:
                r["stream"].cancel()
        return self.t_open, t_close, t_end


def _warm(srv, cfg, vocab, seed):
    """Compile and run every executable the pinned ladder can reach: one
    wave of A prompts of length P for each (A, P), two tokens each so the
    step runs too.  Prompts are random, so no prefix ever hits."""
    rng = np.random.default_rng([int(seed), 0x77])
    for p in cfg["server"]["prefill_buckets"]:
        for a in cfg["server"]["admit_sizes"]:
            streams = [srv.submit(rng.integers(0, vocab, int(p),
                                               dtype=np.int32),
                                  max_new_tokens=2) for _ in range(a)]
            for _ in range(64):
                if all(s.done for s in streams):
                    break
                srv.pump()
            for s in streams:
                s.tokens(timeout=0)


def _check(ctx, geom, finished):
    """Over a seeded sample of ``finished`` with the longest in it: the mean
    and the widest gap by which a served token's logit lies below the
    reference's best (with ``ctx.control`` the same two for the token the
    int8 control puts first at each position), how many streams were not
    ``max_new`` in-vocabulary tokens, and the tokens read."""
    import jax.numpy as jnp

    T, V = geom["max_length"], geom["vocab_size"]
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
        :SAMPLE - 1]]
    w = gpt.seeded_weights(ctx.config, ctx.seed)
    gaps, gaps_control = [], []
    for r in picks:
        prompt = ctx.requests[r["i"]]["prompt"]
        toks = np.asarray(r["tokens"], dtype=np.int32)
        P, n = prompt.size, toks.size
        context = np.zeros(T, np.int32)
        context[:P] = prompt
        context[P:P + n - 1] = toks[:-1]
        nxt = np.zeros(T, np.int32)
        nxt[P - 1:P - 1 + n] = toks
        gap, gap_c = reference.served_gaps(
            w, jnp.asarray(context), jnp.asarray(nxt), geom["num_heads"],
            control=ctx.control)
        gaps.append(np.asarray(gap)[P - 1:P - 1 + n])
        gaps_control.append(np.asarray(gap_c)[P - 1:P - 1 + n])

    def summary(parts):
        g = np.concatenate(parts).astype(np.float64)
        return {"mean": float(g.mean()), "widest": float(g.max())}

    return summary(gaps), summary(gaps_control), bad, \
        int(sum(g.size for g in gaps))


def run(ctx):
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import models, serve, telemetry

    cfg, geom = ctx.config, gpt.geometry(ctx.config)
    traffic = ctx.generator().make(ctx.traffic, ctx.seed, geom["vocab_size"])
    ctx.requests = traffic["requests"]

    net, _ = getattr(models, cfg["preset"])(dtype=cfg["dtype"], **geom)
    # an inference deployment attaches no gradient buffers (with them the
    # parameters take three times their bytes)
    net.collect_params().setattr("grad_req", "null")
    net.initialize(mx.init.Zero())
    w = gpt.seeded_weights(cfg, ctx.seed)
    gpt.load_into(net, geom, w)
    del w
    server_args = {k: tuple(v) if isinstance(v, list) else v
                   for k, v in cfg["server"].items()}
    srv = serve.DecodeServer(net, autostart=False, **server_args)
    if srv.sync_mode:
        raise harness.BenchError("the server fell back to sync mode: "
                                 + str(srv.sync_reason))
    try:
        _warm(srv, cfg, geom["vocab_size"], ctx.seed)
        compiles_warm = len(telemetry.events("compile"))
        srv.start()
        driver = _Driver(srv, traffic, ctx.seconds)
        tracer = None
        if ctx.trace:
            # the ramp fills the pool in about a second; the trace starts
            # a little into the window and the reduction keeps to its own
            # bracket, so the start-up of the profiler is outside it
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
    # time per output token of every stream that ended in the window: its
    # mean gap, so tokens handed over in bursts cannot flatter it
    tpot = [(r["times"][-1] - r["times"][0]) * 1e3 / (len(r["times"]) - 1)
            for r in finished]

    del driver, srv, net
    gc.collect()
    gap, gap_control, bad, checked = _check(ctx, geom, finished)
    limits = cfg["limits"]
    compared = {
        "served_gap_mean": {"value": gap["mean"],
                            "limit": limits["served_gap_mean"]},
        "malformed_streams": {"value": bad + len(failed), "limit": 0},
        "compiles_in_window": {"value": compiles_window, "limit": 0},
    }
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
        "trace": trace, "geometry": geom, "records": records,
        "control": {"served_gap_mean": gap_control["mean"],
                    "served_gap_widest": gap_control["widest"]},
        "numbers": {"served_gap_widest": gap["widest"]},
        "window": {"t_open": t_open, "t_close": t_close, "t_end": t_end},
        "counters": {
            "steps": steps, "occupied_lane_steps": lane_steps,
            "num_slots": stats1["num_slots"],
            "pool_bytes": stats1["pool_bytes"],
            "dispatch": {k: stats1["counters"][k] - stats0["counters"][k]
                         for k in stats1["counters"]},
            "checked_tokens": checked,
            # a stall of the host or the device shows here first
            "longest_token_gap_ms": max(
                ((b - a) * 1e3 for r in records
                 for a, b in zip(r["times"], r["times"][1:])
                 if t_open <= b < t_close), default=None),
        },
    }
