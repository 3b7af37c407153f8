"""Unified runtime telemetry (ISSUE 9): metrics registry, event log,
compile watch, exporters, profiler-facade delegation, and the
telemetry_report invariant checker.

Conventions: the registry and event ring are process-global, so tests
use test-unique metric names / event sites and measure deltas instead
of absolute values."""
import json
import os
import subprocess
import sys
import threading

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.base import MXNetError


# --------------------------------------------------------------------- #
# registry
# --------------------------------------------------------------------- #

class TestRegistry:
    def test_counter_gauge_roundtrip(self):
        c = telemetry.counter("t_reg_counter", case="a")
        c.inc()
        c.inc(4)
        assert c.value == 5
        # same (name, labels) -> same instrument; different labels don't
        assert telemetry.counter("t_reg_counter", case="a") is c
        assert telemetry.counter("t_reg_counter", case="b") is not c
        g = telemetry.gauge("t_reg_gauge")
        g.set(2.5)
        g.add(0.5)
        assert g.value == 3.0

    def test_kind_collision_raises(self):
        telemetry.counter("t_reg_kind")
        # one exposition series per (name, labels): re-requesting it as
        # another instrument kind is a caller error, not a second metric
        with pytest.raises(TypeError, match="registered as a counter"):
            telemetry.gauge("t_reg_kind")
        telemetry.gauge("t_reg_kind", other="label")  # distinct labels ok

    def test_histogram_buckets_and_summary(self):
        h = telemetry.histogram("t_reg_hist")
        for v in (0.001, 0.003, 0.02, 0.4):
            h.observe(v)
        s = h.summary()
        assert s["count"] == 4
        assert s["min"] == pytest.approx(0.001)
        assert s["max"] == pytest.approx(0.4)
        assert 0.001 <= s["p50"] <= 0.4
        assert s["p99"] <= 0.4    # clamped to observed max
        assert h.quantile(0.0) == pytest.approx(0.001)

    def test_histogram_empty_summary(self):
        h = telemetry.histogram("t_reg_hist_empty")
        s = h.summary()
        assert s["count"] == 0 and s["p50"] is None and s["mean"] is None

    def test_concurrent_counter_increments_not_lost(self):
        """The registry's core contract: concurrent inc() from N
        threads loses nothing (the serve scheduler + consumer threads
        both hit these)."""
        c = telemetry.counter("t_reg_concurrent")
        N, per = 8, 5000

        def work():
            for _ in range(per):
                c.inc()

        ts = [threading.Thread(target=work) for _ in range(N)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert c.value == N * per

    def test_prometheus_render(self):
        c = telemetry.counter("t_prom_counter", arm="x")
        c.inc(3)
        h = telemetry.histogram("t_prom_hist")
        h.observe(0.002)
        text = telemetry.render_prometheus()
        assert "# TYPE t_prom_counter counter" in text
        assert 't_prom_counter{arm="x"} 3' in text
        assert 't_prom_hist_bucket{le="+Inf"} 1' in text
        assert "t_prom_hist_count 1" in text

    def test_prometheus_hostile_label_values_escaped(self):
        """Satellite (ISSUE 10): label VALUES are escaped per the text
        exposition format — a backslash-laden path, an embedded quote,
        or a newline in a label (error strings end up in labels) must
        not break the scrape line."""
        c = telemetry.counter("t_prom_escape", path="a\\b",
                              msg='say "hi"\nline2')
        c.inc()
        text = telemetry.render_prometheus()
        line = next(l for l in text.splitlines()
                    if l.startswith("t_prom_escape{"))
        # labels sort by key: msg before path
        assert line == ('t_prom_escape{msg="say \\"hi\\"\\nline2",'
                        'path="a\\\\b"} 1')
        # every sample stays one line: the newline was escaped
        assert "\nline2" not in line

    def test_snapshot_and_reset(self):
        c = telemetry.counter("t_snap_counter")
        c.inc(7)
        rows = telemetry.snapshot()["t_snap_counter"]
        assert rows[0]["value"] == 7 and rows[0]["kind"] == "counter"
        telemetry.reset_metrics()
        assert c.value == 0   # cached references stay valid


# --------------------------------------------------------------------- #
# event log
# --------------------------------------------------------------------- #

class TestEvents:
    def test_emit_ring_and_filter(self):
        telemetry.emit("t_ev_kind", n=1)
        telemetry.emit("t_ev_kind", n=2)
        telemetry.emit("t_ev_other")
        evs = telemetry.events("t_ev_kind")
        assert [e["n"] for e in evs[-2:]] == [1, 2]
        assert all(e["kind"] == "t_ev_kind" for e in evs)
        assert all("ts" in e for e in telemetry.events())

    def test_disabled_by_env(self, monkeypatch):
        monkeypatch.setenv("MXNET_TELEMETRY", "0")
        assert telemetry.emit("t_ev_disabled") is None
        assert telemetry.events("t_ev_disabled") == []

    def test_jsonl_sink_writes_lines(self, tmp_path):
        path = str(tmp_path / "ev.jsonl")
        sink = telemetry.add_jsonl_sink(path)
        try:
            telemetry.emit("t_ev_sink", value=onp.int32(3))
        finally:
            telemetry.remove_sink(sink)
        telemetry.emit("t_ev_sink", value=4)  # after detach: not written
        with open(path) as fh:
            rows = [json.loads(ln) for ln in fh]
        assert len(rows) == 1
        assert rows[0]["kind"] == "t_ev_sink"
        assert rows[0]["value"] == 3          # numpy scalar serialized

    def test_broken_sink_is_dropped_not_fatal(self):
        def bad(_ev):
            raise RuntimeError("boom")

        telemetry.add_sink(bad)
        with pytest.warns(UserWarning, match="sink"):
            telemetry.emit("t_ev_broken")
        telemetry.emit("t_ev_broken")   # sink gone, no warning needed
        assert len(telemetry.events("t_ev_broken")) >= 2


# --------------------------------------------------------------------- #
# compile watch
# --------------------------------------------------------------------- #

class TestCompileWatch:
    def test_compile_event_once_then_retrace_on_new_signature(self):
        import jax
        import jax.numpy as jnp

        fn = telemetry.instrument_jit(
            jax.jit(lambda x: x * 2), "t.compile", key="k",
            fields={"extra": "f"})
        before = len(telemetry.events("compile"))
        fn(jnp.ones(3))
        fn(jnp.ones(3))   # cache hit: no new event
        evs = [e for e in telemetry.events("compile")
               if e.get("site") == "t.compile"]
        assert len(telemetry.events("compile")) == before + 1
        assert evs[-1]["key"] == "k" and evs[-1]["extra"] == "f"
        assert evs[-1]["cache_size"] == 1
        assert "retrace" not in evs[-1]
        assert evs[-1]["wall_s"] > 0
        # a NEW signature is a retrace: second event, flagged
        fn(jnp.ones(5))
        evs = [e for e in telemetry.events("compile")
               if e.get("site") == "t.compile"]
        assert len(evs) == 2 and evs[-1]["retrace"] is True
        assert telemetry.counter("retraces_total",
                                 site="t.compile").value >= 1

    def test_disabled_returns_fn_unwrapped(self, monkeypatch):
        import jax

        jitted = jax.jit(lambda x: x + 1)
        monkeypatch.setenv("MXNET_TELEMETRY", "0")
        assert telemetry.instrument_jit(jitted, "t.off") is jitted
        monkeypatch.setenv("MXNET_TELEMETRY", "1")
        assert telemetry.instrument_jit(jitted, "t.on") is not jitted
        # non-jit callables pass through untouched
        plain = lambda x: x  # noqa: E731
        assert telemetry.instrument_jit(plain, "t.plain") is plain

    def test_wrapper_delegates_jit_surface(self):
        import jax
        import jax.numpy as jnp

        fn = telemetry.instrument_jit(jax.jit(lambda x: x - 1),
                                      "t.delegate")
        fn(jnp.ones(2))
        assert fn._cache_size() == 1      # the retrace-pin API
        lowered = fn.lower(jnp.ones(2))   # the AOT API
        assert lowered is not None

    def test_hlo_ops_recorded_under_env(self, monkeypatch):
        import jax
        import jax.numpy as jnp

        monkeypatch.setenv("MXNET_TELEMETRY_HLO", "1")
        fn = telemetry.instrument_jit(
            jax.jit(lambda x: jnp.tanh(x) @ x), "t.hlo")
        fn(jnp.ones((4, 4)))
        ev = [e for e in telemetry.events("compile")
              if e.get("site") == "t.hlo"][-1]
        assert ev["hlo_ops"] > 0

    def test_donated_buffers_survive_hlo_count(self, monkeypatch):
        """MXNET_TELEMETRY_HLO recomputes HLO from shape structs —
        it must not dereference the just-donated input buffer."""
        import jax
        import jax.numpy as jnp

        monkeypatch.setenv("MXNET_TELEMETRY_HLO", "1")
        fn = telemetry.instrument_jit(
            jax.jit(lambda x: x * 3, donate_argnums=(0,)), "t.donate")
        out = fn(jnp.ones(8))
        ev = [e for e in telemetry.events("compile")
              if e.get("site") == "t.donate"][-1]
        assert ev["hlo_ops"] > 0
        assert float(out[0]) == 3.0


# --------------------------------------------------------------------- #
# span / annotation bridging
# --------------------------------------------------------------------- #

class TestSpan:
    def test_span_observes_histogram(self):
        with telemetry.span("t_span_phase", hist="t_span_phase_seconds"):
            pass
        assert telemetry.histogram("t_span_phase_seconds").count == 1

    def test_span_is_noop_without_profiler(self):
        before = len(telemetry.spans())
        with telemetry.span("t_ann", seq=1) as sp:
            pass   # the shared nullcontext: no annotation, no record
        assert sp is None and len(telemetry.spans()) == before


# --------------------------------------------------------------------- #
# profiler facade (satellites)
# --------------------------------------------------------------------- #

class TestProfilerFacade:
    def test_set_config_unknown_key_raises(self):
        with pytest.raises(MXNetError, match="profile_imperativ"):
            mx.profiler.set_config(profile_imperativ=True)
        # known keys still work
        mx.profiler.set_config(aggregate_stats=True)

    def test_counter_delegates_to_registry(self):
        c = mx.profiler.Counter(name="t_prof_counter", value=3)
        c += 2
        c.decrement(1)
        assert c.value == 4
        g = telemetry.gauge("profiler_counter",
                            counter="t_prof_counter")
        assert g.value == 4

    def test_marker_emits_event(self):
        before = len(telemetry.events("marker"))
        mx.profiler.Marker(name="t_prof_marker").mark()
        evs = telemetry.events("marker")
        assert len(evs) == before + 1
        assert evs[-1]["name"] == "t_prof_marker"

    def test_dumps_reset_concurrent_no_lost_rows(self):
        """Satellite: ``dumps(reset=True)`` swaps the aggregate while
        dispatch threads record — every recorded row must appear in
        exactly one returned table (none lost to the swap, none
        duplicated across tables)."""
        from mxnet_tpu import profiler

        with profiler._lock:
            profiler._state["op_stats"] = profiler._OpStats()
        N_THREADS, PER = 4, 3000
        done = threading.Event()
        tables = []

        def record(tid):
            for i in range(PER):
                profiler._hook(f"op{tid}", 1e-6)

        def reaper():
            while not done.is_set():
                tables.append(profiler.dumps(reset=True))
            tables.append(profiler.dumps(reset=True))

        reap = threading.Thread(target=reaper)
        reap.start()
        ts = [threading.Thread(target=record, args=(i,))
              for i in range(N_THREADS)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        done.set()
        reap.join()

        total = 0
        for table in tables:
            for line in table.splitlines():
                if line.startswith("op"):
                    total += int(line.split()[1])
        assert total == N_THREADS * PER
        with profiler._lock:
            profiler._state["op_stats"] = None

    def test_dumps_reset_still_works_single_threaded(self):
        from mxnet_tpu import profiler

        with profiler._lock:
            profiler._state["op_stats"] = profiler._OpStats()
        profiler._hook("single_op", 0.001)
        table = profiler.dumps(reset=True)
        assert "single_op" in table
        assert "single_op" not in profiler.dumps()
        with profiler._lock:
            profiler._state["op_stats"] = None


# --------------------------------------------------------------------- #
# subsystem wiring
# --------------------------------------------------------------------- #

class TestFusedStepTelemetry:
    def test_fused_step_emits_compile_events_and_metrics(self):
        from mxnet_tpu import gluon
        from mxnet_tpu.gluon import nn

        mx.random.seed(0)
        net = nn.Dense(4, in_units=6)
        net.initialize(mx.init.Xavier())
        trainer = gluon.Trainer(net.collect_params(), "sgd",
                                {"learning_rate": 0.1}, kvstore=None)
        loss_l = gluon.loss.L2Loss()

        def loss_fn(xx, yy):
            return loss_l(net(xx), yy)

        rng = onp.random.RandomState(0)
        x = mx.nd.array(rng.rand(2, 6).astype("float32"))
        y = mx.nd.array(rng.rand(2, 4).astype("float32"))
        d = telemetry.counter("fused_step_dispatches_total",
                              phase="apply")
        lat = telemetry.histogram("fused_step_seconds", phase="apply")
        before_d, before_n = d.value, lat.count
        before_c = len([e for e in telemetry.events("compile")
                        if e.get("site") == "gluon.fused_step"])
        trainer.fused_step(loss_fn, x, y)
        trainer.fused_step(loss_fn, x, y)
        comp = [e for e in telemetry.events("compile")
                if e.get("site") == "gluon.fused_step"]
        assert len(comp) == before_c + 1     # one trace, no retrace
        assert comp[-1]["phase"] == "apply"
        assert d.value == before_d + 2
        assert lat.count == before_n + 2

    def test_cached_op_compile_event(self):
        from mxnet_tpu.gluon import nn

        mx.random.seed(0)
        net = nn.Dense(3, in_units=5)
        net.initialize(mx.init.Xavier())
        net.hybridize()
        x = mx.nd.array(onp.random.RandomState(1)
                        .rand(2, 5).astype("float32"))
        before = len([e for e in telemetry.events("compile")
                      if e.get("site") == "gluon.cached_op"])
        net(x)
        net(x)
        comp = [e for e in telemetry.events("compile")
                if e.get("site") == "gluon.cached_op"]
        assert len(comp) == before + 1
        assert comp[-1]["training"] is False

    def test_kv_generate_compile_event(self):
        from mxnet_tpu.models import GPT, GPTConfig, kv_generate

        mx.random.seed(0)
        net = GPT(GPTConfig(vocab_size=61, max_length=32, num_layers=2,
                            units=16, num_heads=2, hidden_size=32))
        net.initialize(mx.init.Normal(0.02))
        prompt = onp.random.RandomState(0).randint(0, 61, (1, 4))
        before = len([e for e in telemetry.events("compile")
                      if e.get("site") == "models.kv_generate"])
        kv_generate(net, prompt, max_new_tokens=3)
        kv_generate(net, prompt, max_new_tokens=3)   # cached: no event
        comp = [e for e in telemetry.events("compile")
                if e.get("site") == "models.kv_generate"]
        assert len(comp) == before + 1
        assert comp[-1]["mode"] == "stacked"


class TestPrefetchTelemetry:
    def test_device_ring_stall_and_depth_metrics(self):
        from mxnet_tpu.gluon.data.dataloader import DevicePrefetchIter

        stalls = telemetry.counter("data_prefetch_stalls_total")
        before = stalls.value
        it = DevicePrefetchIter(iter([1, 2, 3]), None, depth=2,
                                background=True)
        out = list(it)
        assert out == [1, 2, 3]
        # the first get had nothing ready — at least one stall counted
        assert stalls.value >= before + 1
        it.close()


class TestServeCounterView:
    def test_view_is_dict_api_over_registry(self):
        from mxnet_tpu.serve.server import _CounterView

        v = _CounterView("t_view_srv")
        assert set(v) == {"step_dispatches", "admit_dispatches",
                          "sync_requests", "pool_grows", "prefix_hits",
                          "cow_copies", "chunk_dispatches",
                          "verify_dispatches", "draft_proposed",
                          "draft_accepted", "draft_rejected",
                          "hit_dispatches", "admit_rows", "admit_tokens",
                          "compiles", "compile_ms", "chunk_expert_tokens",
                          "chunk_experts_touched", "latent_rows_walked",
                          "chunk_latent_rows_walked", "chunk_carried_tokens"}
        v.inc("step_dispatches")
        v["step_dispatches"] += 2        # MutableMapping read-modify
        assert v["step_dispatches"] == 3
        assert telemetry.counter("serve_step_dispatches_total",
                                 server="t_view_srv").value == 3
        for k in v:
            v[k] = 0                     # the reset_counters idiom
        assert dict(v) == {k: 0 for k in v}
        with pytest.raises(MXNetError):
            del v["step_dispatches"]

    def test_module_aggregate_reset_is_locked(self):
        """Satellite: reset_serve_counters racing _bump loses no
        increments — every bump lands either before a reset (erased
        with the whole aggregate) or after (kept)."""
        from mxnet_tpu.serve import server as srv_mod

        srv_mod.reset_serve_counters()
        STOP = threading.Event()

        def resetter():
            while not STOP.is_set():
                srv_mod.reset_serve_counters()

        t = threading.Thread(target=resetter)
        t.start()
        try:
            for _ in range(20000):
                srv_mod._bump("step_dispatches")
        finally:
            STOP.set()
            t.join()
        srv_mod.reset_serve_counters()
        # the real assertion is the lock discipline (tracelint TL004
        # enforces it statically); dynamically: counts stay consistent
        assert srv_mod.serve_counters["step_dispatches"] == 0


# --------------------------------------------------------------------- #
# telemetry_report
# --------------------------------------------------------------------- #

def _write_jsonl(path, events):
    with open(path, "w") as fh:
        for e in events:
            fh.write(json.dumps(e) + "\n")


def _serve_stream(step_dispatches=10, steps=10, retrace=False):
    evs = [
        {"ts": 1.0, "kind": "serve_config", "server": "s0",
         "pool_sizes": [2], "admit_sizes": [1, 2],
         "prefill_buckets": [8, 16], "max_total_len": 32,
         "sync_mode": False},
        {"ts": 1.1, "kind": "compile", "site": "serve.step",
         "server": "s0", "pool": 2, "wall_s": 0.5, "cache_size": 1},
        {"ts": 1.2, "kind": "compile", "site": "serve.admit",
         "server": "s0", "pool": 2, "a_bucket": 1, "p_bucket": 8,
         "wall_s": 0.4, "cache_size": 1},
        {"ts": 1.4, "kind": "serve_request", "server": "s0",
         "request_id": 0, "reason": "max_len", "tokens": 5,
         "ttft_s": 0.01, "queue_wait_s": 0.001, "wave": 1,
         "a_bucket": 1, "p_bucket": 8, "occupancy_at_admit": 0.5},
        {"ts": 2.0, "kind": "serve_stats", "server": "s0",
         "steps": steps, "occupancy": 0.8,
         "counters": {"step_dispatches": step_dispatches,
                      "admit_dispatches": 1, "sync_requests": 0,
                      "pool_grows": 0}},
        {"ts": 2.1, "kind": "bench", "bench": "serve",
         "mode": "saturated", "tokens_per_sec": 100.0},
    ]
    if retrace:
        evs.insert(3, {"ts": 1.25, "kind": "compile",
                       "site": "serve.admit", "server": "s0",
                       "pool": 2, "a_bucket": 1, "p_bucket": 8,
                       "wall_s": 0.4, "cache_size": 2, "retrace": True})
    return evs


class TestTelemetryReport:
    def test_summary_and_check_pass(self, tmp_path):
        sys.path.insert(0, "/root/repo")
        from tools import telemetry_report

        path = str(tmp_path / "ok.jsonl")
        _write_jsonl(path, _serve_stream())
        events = telemetry_report.load(path)
        assert telemetry_report.check_serve(events) == []
        text = telemetry_report.render(events)
        assert "serve.admit" in text and "serve requests" in text
        assert "bench rows" in text

    def test_check_flags_dispatch_mismatch(self, tmp_path):
        from tools import telemetry_report

        path = str(tmp_path / "bad.jsonl")
        _write_jsonl(path, _serve_stream(step_dispatches=12, steps=10))
        fails = telemetry_report.check_serve(telemetry_report.load(path))
        assert any("12 step dispatches" in f for f in fails)

    def test_check_flags_retrace(self, tmp_path):
        from tools import telemetry_report

        path = str(tmp_path / "retrace.jsonl")
        _write_jsonl(path, _serve_stream(retrace=True))
        fails = telemetry_report.check_serve(telemetry_report.load(path))
        assert any("retrace" in f for f in fails)

    def test_check_flags_ladder_overflow(self, tmp_path):
        from tools import telemetry_report

        evs = _serve_stream()
        for i in range(8):   # 9 admit compiles > 1*2*2 ladder product
            evs.append({"ts": 3.0 + i, "kind": "compile",
                        "site": "serve.admit", "server": "s0",
                        "pool": 2, "a_bucket": 2, "p_bucket": 16 + i,
                        "wall_s": 0.1, "cache_size": 1})
        path = str(tmp_path / "ladder.jsonl")
        _write_jsonl(path, evs)
        fails = telemetry_report.check_serve(telemetry_report.load(path))
        assert any("ladder" in f for f in fails)

    def test_cli_roundtrip(self, tmp_path):
        path = str(tmp_path / "cli.jsonl")
        _write_jsonl(path, _serve_stream())
        r = subprocess.run(
            [sys.executable, "tools/telemetry_report.py", path,
             "--check-serve"],
            capture_output=True, text=True, cwd="/root/repo",
            timeout=60)
        assert r.returncode == 0, r.stderr
        assert "serve checks OK" in r.stdout
        r2 = subprocess.run(
            [sys.executable, "tools/telemetry_report.py", path,
             "--json"],
            capture_output=True, text=True, cwd="/root/repo",
            timeout=60)
        assert r2.returncode == 0
        parsed = json.loads(r2.stdout)
        assert parsed["events"] == len(_serve_stream())
