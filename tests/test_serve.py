"""Continuous-batching decode server (mxnet_tpu/serve/).

Parity: a served request must reproduce ``kv_generate(model,
prompt[None], ...)`` token-for-token — greedy AND sampled (the per-slot
sampler folds the request key at the absolute position, the exact
batch-1 stream), across mid-scan admissions, slot reuse and pool
growth.  Scheduler edge cases: EOS / max-length retirement on device,
pool-full backpressure, empty-queue idle (no dispatch), and the
dispatch-count regression — ONE step-executable dispatch per decode
step at steady state (ISSUE 7 acceptance).
"""
import os
import subprocess
import sys

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError


def _gpt(layers=2, units=32, heads=4, hidden=64, vocab=97,
         max_length=64):
    from mxnet_tpu.models import GPT, GPTConfig
    mx.random.seed(0)
    net = GPT(GPTConfig(vocab_size=vocab, max_length=max_length,
                        num_layers=layers, units=units, num_heads=heads,
                        hidden_size=hidden))
    net.initialize(mx.init.Normal(0.02))
    return net


def _prompt(seed, n, vocab=97):
    return onp.random.RandomState(seed).randint(0, vocab, (n,))


def _drain(server):
    while server.pump():
        pass


def _ref(net, prompt, n, **kw):
    from mxnet_tpu.models import kv_generate
    kw.setdefault("temperature", 0.0)
    return list(kv_generate(net, prompt[None], max_new_tokens=n,
                            **kw)[0, prompt.size:])


@pytest.fixture(scope="module")
def net():
    return _gpt()


@pytest.fixture(scope="module")
def server(net):
    """Shared greedy 2-slot pool, pump-driven (compiles once for the
    whole module); every test drains it back to idle.  spec=False: this
    module pins the PLAIN one-dispatch-per-step accounting (speculative
    draft-and-verify has its own suite, test_serve_spec.py)."""
    from mxnet_tpu.serve import DecodeServer
    srv = DecodeServer(net, max_total_len=64, pool_sizes=(2,),
                       spec=False, autostart=False)
    yield srv
    srv.close(drain=False)


class TestServeParity:
    def test_two_ragged_requests_match_kv_generate(self, net, server):
        p1, p2 = _prompt(0, 5), _prompt(1, 3)
        s1 = server.submit(p1, max_new_tokens=8)
        s2 = server.submit(p2, max_new_tokens=4)
        _drain(server)
        assert s1.tokens(5) == _ref(net, p1, 8)
        assert s2.tokens(5) == _ref(net, p2, 4)

    def test_mid_scan_admission(self, net, server):
        """A request submitted while another is mid-decode joins at a
        step boundary; both streams stay exact."""
        p1, p2 = _prompt(2, 4), _prompt(3, 6)
        s1 = server.submit(p1, max_new_tokens=10)
        for _ in range(4):          # run a few steps of s1 alone
            server.pump()
        assert not s1.done
        s2 = server.submit(p2, max_new_tokens=5)
        _drain(server)
        assert s1.tokens(5) == _ref(net, p1, 10)
        assert s2.tokens(5) == _ref(net, p2, 5)

    def test_slot_reuse_after_retirement(self, net, server):
        """More requests than slots: retired slots re-admit from the
        queue and the recycled cache columns never leak into the new
        sequence."""
        prompts = [_prompt(10 + i, 3 + i % 3) for i in range(5)]
        streams = [server.submit(p, max_new_tokens=4 + i % 2)
                   for i, p in enumerate(prompts)]
        _drain(server)
        for i, (p, s) in enumerate(zip(prompts, streams)):
            assert s.tokens(5) == _ref(net, p, 4 + i % 2), f"req {i}"

    def test_sampled_stream_matches_batch1_seed(self, net):
        """temperature/top_k sampling: slot i draws with
        fold_in(PRNGKey(seed_i), pos) — the same stream kv_generate
        emits for that seed at batch 1."""
        from mxnet_tpu.serve import DecodeServer
        srv = DecodeServer(net, max_total_len=64, pool_sizes=(2,),
                           temperature=0.8, top_k=5, autostart=False)
        p1, p2 = _prompt(4, 5), _prompt(5, 3)
        s1 = srv.submit(p1, max_new_tokens=6, seed=11)
        s2 = srv.submit(p2, max_new_tokens=6, seed=42)
        _drain(srv)
        kw = dict(temperature=0.8, top_k=5)
        assert s1.tokens(5) == _ref(net, p1, 6, seed=11, **kw)
        assert s2.tokens(5) == _ref(net, p2, 6, seed=42, **kw)
        srv.close()

    def test_int8_pool_serving(self, net):
        """The q8 weight stream serves through the same slot pool (the
        int8 stacked scan from this PR's satellite)."""
        from mxnet_tpu.serve import DecodeServer
        srv = DecodeServer(net, max_total_len=64, pool_sizes=(2,),
                           weights="int8", autostart=False)
        p = _prompt(6, 4)
        s = srv.submit(p, max_new_tokens=5)
        _drain(srv)
        assert s.tokens(5) == _ref(net, p, 5, weights="int8")
        srv.close()


class TestRetirement:
    def test_eos_retires_early(self, net):
        from mxnet_tpu.serve import DecodeServer
        # pick the token the greedy stream actually emits as "EOS"
        p = _prompt(0, 5)
        full = _ref(net, p, 8)
        eos = full[1]
        srv = DecodeServer(net, max_total_len=64, pool_sizes=(2,),
                           eos_id=eos, autostart=False)
        s = srv.submit(p, max_new_tokens=8)
        _drain(srv)
        toks = s.tokens(5)
        assert toks[-1] == eos
        assert len(toks) == full.index(eos) + 1
        assert srv.stats()["in_flight"] == 0
        srv.close()

    def test_max_length_retires(self, net, server):
        p = _prompt(7, 4)
        s = server.submit(p, max_new_tokens=6)
        _drain(server)
        assert len(s.tokens(5)) == 6

    def test_single_token_budget_retires_at_admission(self, net,
                                                      server):
        """max_new_tokens=1 finishes inside the admission executable and
        never occupies a step lane."""
        p = _prompt(8, 4)
        server.reset_counters()
        s = server.submit(p, max_new_tokens=1)
        _drain(server)
        assert s.tokens(5) == _ref(net, p, 1)
        assert server.counters["admit_dispatches"] == 1
        assert server.counters["step_dispatches"] == 0

    def test_request_longer_than_cache_rejected(self, server):
        with pytest.raises(MXNetError, match="exceeds"):
            server.submit(_prompt(9, 10), max_new_tokens=60)

    def test_oversized_seed_rejected_at_submit(self, net, server):
        """A seed outside int32 must be a caller error at submit() —
        not an OverflowError on the scheduler thread that fails every
        other client's stream (post-review regression)."""
        with pytest.raises(MXNetError, match="int32"):
            server.submit(_prompt(9, 4), max_new_tokens=2, seed=2 ** 31)
        p = _prompt(9, 4)                    # the server still serves
        s = server.submit(p, max_new_tokens=2, seed=2 ** 31 - 1)
        _drain(server)
        assert s.tokens(5) == _ref(net, p, 2, seed=2 ** 31 - 1)


class TestScheduler:
    def test_empty_queue_idle_no_dispatch(self, server):
        """An idle server must not burn dispatches: pump() on an empty
        queue reports no work and launches nothing."""
        _drain(server)
        server.reset_counters()
        for _ in range(3):
            assert server.pump() is False
        assert server.counters["step_dispatches"] == 0
        assert server.counters["admit_dispatches"] == 0

    def test_pool_full_backpressure(self, net):
        from mxnet_tpu.serve import DecodeServer
        srv = DecodeServer(net, max_total_len=64, pool_sizes=(1,),
                           max_pending=2, autostart=False)
        p = _prompt(12, 4)
        streams = [srv.submit(p, max_new_tokens=4) for _ in range(2)]
        with pytest.raises(MXNetError, match="backpressure"):
            srv.submit(p, max_new_tokens=4, nowait=True)
        _drain(srv)
        for s in streams:
            assert len(s.tokens(5)) == 4
        # queue drained — submission admits again
        s = srv.submit(p, max_new_tokens=2, nowait=True)
        _drain(srv)
        assert len(s.tokens(5)) == 2
        srv.close()

    def test_pump_mode_blocking_submit_raises(self, net):
        """With autostart=False there is no scheduler thread to drain
        the queue, so a blocking submit() at max_pending would deadlock
        the pump-driving thread — it must raise instead (post-review
        regression)."""
        from mxnet_tpu.serve import DecodeServer
        srv = DecodeServer(net, max_total_len=64, pool_sizes=(1,),
                           max_pending=2, autostart=False)
        p = _prompt(29, 4)
        streams = [srv.submit(p, max_new_tokens=3) for _ in range(2)]
        with pytest.raises(MXNetError, match="pump"):
            srv.submit(p, max_new_tokens=3)    # nowait=False
        _drain(srv)
        for s in streams:
            assert s.tokens(5) == _ref(net, p, 3)
        srv.close()

    def test_counters_are_per_instance(self, net, server):
        """Dispatch accounting must not cross-talk between servers in
        one process (the module-level serve_counters is only a
        process-wide aggregate; post-review regression)."""
        from mxnet_tpu.serve import DecodeServer
        _drain(server)
        server.reset_counters()
        other = DecodeServer(net, max_total_len=64, pool_sizes=(2,),
                             autostart=False)
        p = _prompt(31, 4)
        s = other.submit(p, max_new_tokens=3)
        _drain(other)
        assert s.tokens(5) == _ref(net, p, 3)
        assert other.counters["admit_dispatches"] == 1
        assert server.counters["admit_dispatches"] == 0
        assert server.counters["step_dispatches"] == 0
        other.close()

    def test_bad_on_token_callback_fails_only_its_stream(self, net,
                                                         server):
        """A raising per-request on_token callback fails THAT stream
        with the callback's error; the scheduler and every concurrent
        request keep serving (post-review regression)."""
        _drain(server)

        def bad(req_id, tok):
            raise RuntimeError("callback boom")

        p1, p2 = _prompt(32, 4), _prompt(33, 3)
        s1 = server.submit(p1, max_new_tokens=4, on_token=bad)
        s2 = server.submit(p2, max_new_tokens=4)
        _drain(server)
        with pytest.raises(RuntimeError, match="callback boom"):
            s1.tokens(5)
        assert s2.tokens(5) == _ref(net, p2, 4)
        p3 = _prompt(34, 3)                 # the server survives
        s3 = server.submit(p3, max_new_tokens=2)
        _drain(server)
        assert s3.tokens(5) == _ref(net, p3, 2)

    def test_close_timeout_leaves_scheduler_state_alone(self, net):
        """close() must not tear down scheduler-owned state while the
        scheduler thread is still inside pump() (a long dispatch or
        growth retrace): it raises after the join timeout, and a later
        close() finishes teardown (post-review regression)."""
        import threading
        from mxnet_tpu.serve import DecodeServer
        srv = DecodeServer(net, max_total_len=64, pool_sizes=(1,))
        entered, release = threading.Event(), threading.Event()
        real_pump = srv.pump

        def slow_pump():
            entered.set()
            release.wait(30)
            return real_pump()

        srv.pump = slow_pump
        assert entered.wait(5)
        s = srv.submit(_prompt(41, 3), max_new_tokens=6)
        with pytest.raises(MXNetError, match="timed out"):
            srv.close(drain=False, timeout=0.3)
        release.set()
        # the scheduler exits at its next _stopping check with the
        # request still outstanding; the advertised recovery — "call
        # close() again" — must DETECT the dead thread and self-pump
        # the drain instead of sleeping out the full timeout
        srv.close(drain=True, timeout=10.0)
        assert not srv._thread.is_alive()
        assert s.tokens(1) == _ref(net, _prompt(41, 3), 6)

    def test_close_drain_serves_request_mid_admission(self, net):
        """A request popped from the queue but still inside its
        admission dispatch must stay visible to close(drain=True): it
        finishes instead of failing with 'server closed' (post-review
        regression — pop + slot-record are atomic)."""
        import threading
        import time as _time
        from mxnet_tpu.serve import DecodeServer
        srv = DecodeServer(net, max_total_len=64, pool_sizes=(1,))
        real = srv._dispatch_admit
        started = threading.Event()

        def slow_admit(wave):
            started.set()
            _time.sleep(0.5)
            return real(wave)

        srv._dispatch_admit = slow_admit
        p = _prompt(35, 4)
        s = srv.submit(p, max_new_tokens=3)
        assert started.wait(10)
        srv.close(drain=True)
        assert s.tokens(5) == _ref(net, p, 3)

    def test_pool_grows_to_pinned_size(self, net):
        """Backlog beyond the current slot count grows the pool to the
        next pinned size at a step boundary; in-flight sequences carry
        their cache/position state across the growth."""
        from mxnet_tpu.serve import DecodeServer
        srv = DecodeServer(net, max_total_len=64, pool_sizes=(2, 4),
                           autostart=False)
        p0 = _prompt(13, 4)
        s0 = srv.submit(p0, max_new_tokens=8)
        srv.pump()                       # admit s0, step once
        prompts = [_prompt(14 + i, 3) for i in range(3)]
        streams = [srv.submit(p, max_new_tokens=4) for p in prompts]
        _drain(srv)
        assert srv.counters["pool_grows"] == 1
        assert srv.stats()["num_slots"] == 4
        assert s0.tokens(5) == _ref(net, p0, 8)
        for p, s in zip(prompts, streams):
            assert s.tokens(5) == _ref(net, p, 4)
        srv.close()

    def test_background_thread_and_close_drain(self, net):
        from mxnet_tpu.serve import DecodeServer
        srv = DecodeServer(net, max_total_len=64, pool_sizes=(2,))
        p = _prompt(20, 4)
        s = srv.submit(p, max_new_tokens=6)
        assert s.tokens(30) == _ref(net, p, 6)
        srv.close()
        with pytest.raises(MXNetError, match="closed"):
            srv.submit(p, max_new_tokens=2)


class TestDispatchCount:
    def test_one_executable_dispatch_per_decode_step(self, net, server):
        """THE acceptance regression: at steady state (full pool, no
        admissions) every decode step is exactly ONE executable
        dispatch.  N-token requests cost 1 admit + (N-1) decode steps;
        the only extra dispatch is the single trailing step in flight
        when the retirement flags reach the host."""
        _drain(server)
        N = 9
        p1, p2 = _prompt(21, 4), _prompt(22, 4)
        server.reset_counters()
        s1 = server.submit(p1, max_new_tokens=N)
        s2 = server.submit(p2, max_new_tokens=N)
        _drain(server)
        assert s1.tokens(5) == _ref(net, p1, N)
        assert s2.tokens(5) == _ref(net, p2, N)
        # both requests were pending at one step boundary: ONE batched
        # admission dispatch admits the whole wave
        assert server.counters["admit_dispatches"] == 1
        assert server.counters["step_dispatches"] == (N - 1) + 1
        # the step executable itself never retraced
        assert server._progs.step_fn()._cache_size() == 1

    def test_step_program_reused_across_waves(self, net, server):
        """A second wave of requests reuses the SAME compiled step and
        admission executables — slot admit/retire is a device-side
        masked update, not a recompile."""
        _drain(server)
        step = server._progs.step_fn()
        before = step._cache_size()
        admits = {b: f._cache_size()
                  for b, f in server._progs._admits.items()}
        p = _prompt(23, 4)
        s = server.submit(p, max_new_tokens=5)
        _drain(server)
        assert s.tokens(5) == _ref(net, p, 5)
        assert server._progs.step_fn() is step
        assert step._cache_size() == before
        for b, f in server._progs._admits.items():
            if b in admits:
                assert f._cache_size() == admits[b]


class TestCommittedState:
    def test_admit_and_step_compile_exactly_once(self, net):
        """Committed-placement regression: jit keys its executable
        cache on each argument's committed device, so the FIRST
        admission (running on the freshly initialized pool state) and
        every steady-state admission (running on jit-output state)
        must hit the SAME compiled signature.  Before
        ``pool_state_init`` committed the state with ``device_put``,
        the second admission silently recompiled (~seconds) INSIDE the
        serving loop — this pins one compile per program, ever."""
        from mxnet_tpu.serve import DecodeServer
        srv = DecodeServer(net, max_total_len=64, pool_sizes=(2,),
                           autostart=False)
        for wave in range(3):
            p = _prompt(40 + wave, 4)
            s = srv.submit(p, max_new_tokens=4)
            _drain(srv)
            assert s.tokens(5) == _ref(net, p, 4)
        assert srv._progs.step_fn()._cache_size() == 1
        assert srv._progs._admits, "no admission program compiled"
        for bucket, fn in srv._progs._admits.items():
            assert fn._cache_size() == 1, f"bucket {bucket} retraced"
        srv.close()


class TestBatchedAdmission:
    """ISSUE 8 tentpole: one bucketed ``(A, P)`` dispatch admits a
    whole wave of pending prompts.  ``admit_sizes=(1,)`` reproduces
    the per-request admission path (every wave capped at one row), so
    batched-vs-sequential parity is a ladder choice, not a second code
    path."""

    def test_wave_of_4_costs_one_admit_dispatch(self, net):
        """THE acceptance regression: k >= 4 pending prompts at one
        step boundary cost exactly 1 admit dispatch, not k."""
        from mxnet_tpu.serve import DecodeServer
        srv = DecodeServer(net, max_total_len=64, pool_sizes=(4,),
                           autostart=False)
        prompts = [_prompt(50 + i, 3 + i) for i in range(4)]
        streams = [srv.submit(p, max_new_tokens=5) for p in prompts]
        _drain(srv)
        assert srv.counters["admit_dispatches"] == 1
        for p, s in zip(prompts, streams):
            assert s.tokens(5) == _ref(net, p, 5)
        srv.close()

    def test_batched_matches_sequential_greedy(self, net):
        """Mixed prompt lengths ACROSS prefill buckets in one wave:
        the batched streams are token-identical to the per-request
        ladder (and to kv_generate)."""
        from mxnet_tpu.serve import DecodeServer
        prompts = [_prompt(55, 3), _prompt(56, 10), _prompt(57, 5),
                   _prompt(58, 18)]           # buckets 8, 16 and 32
        budgets = [6, 4, 5, 3]
        outs = {}
        for name, ladder in (("batched", None), ("sequential", (1,))):
            srv = DecodeServer(net, max_total_len=64, pool_sizes=(4,),
                               admit_sizes=ladder, autostart=False)
            streams = [srv.submit(p, max_new_tokens=n)
                       for p, n in zip(prompts, budgets)]
            _drain(srv)
            outs[name] = [s.tokens(5) for s in streams]
            expect = 1 if name == "batched" else len(prompts)
            assert srv.counters["admit_dispatches"] == expect, name
            srv.close()
        assert outs["batched"] == outs["sequential"]
        for p, n, got in zip(prompts, budgets, outs["batched"]):
            assert got == _ref(net, p, n)

    def test_batched_matches_sequential_sampled(self, net):
        """Sampled decoding: every wave row folds ITS request key at
        its own position — the batched wave reproduces the per-request
        (and offline batch-1) streams exactly."""
        from mxnet_tpu.serve import DecodeServer
        prompts = [_prompt(60 + i, 3 + 2 * i) for i in range(3)]
        outs = {}
        for name, ladder in (("batched", None), ("sequential", (1,))):
            srv = DecodeServer(net, max_total_len=64, pool_sizes=(4,),
                               temperature=0.7, top_k=7,
                               admit_sizes=ladder, autostart=False)
            streams = [srv.submit(p, max_new_tokens=5, seed=90 + i)
                       for i, p in enumerate(prompts)]
            _drain(srv)
            outs[name] = [s.tokens(5) for s in streams]
            srv.close()
        assert outs["batched"] == outs["sequential"]
        kw = dict(temperature=0.7, top_k=7)
        for i, (p, got) in enumerate(zip(prompts, outs["batched"])):
            assert got == _ref(net, p, 5, seed=90 + i, **kw)

    def test_wave_of_one(self, net, server):
        """A single pending request admits through the same batched
        program path (smallest A bucket; idle rows are masked)."""
        _drain(server)
        server.reset_counters()
        p = _prompt(65, 4)
        s = server.submit(p, max_new_tokens=4)
        _drain(server)
        assert s.tokens(5) == _ref(net, p, 4)
        assert server.counters["admit_dispatches"] == 1

    def test_wave_larger_than_free_slots(self, net):
        """5 pending, 2 slots: the first wave admits 2, the rest
        re-admit in waves as slots retire — parity holds and the
        dispatch count is the wave count, not the request count."""
        from mxnet_tpu.serve import DecodeServer
        srv = DecodeServer(net, max_total_len=64, pool_sizes=(2,),
                           autostart=False)
        prompts = [_prompt(70 + i, 3 + i % 3) for i in range(5)]
        streams = [srv.submit(p, max_new_tokens=4) for p in prompts]
        _drain(srv)
        for p, s in zip(prompts, streams):
            assert s.tokens(5) == _ref(net, p, 4)
        # 5 equal-budget requests through a 2-slot pool retire in
        # lockstep: ceil(5/2) = 3 waves
        assert srv.counters["admit_dispatches"] == 3
        srv.close()

    def test_wave_spills_past_largest_admit_bucket(self, net):
        """A backlog larger than the biggest pinned A bucket spills to
        a second dispatch in the SAME pump."""
        from mxnet_tpu.serve import DecodeServer
        srv = DecodeServer(net, max_total_len=64, pool_sizes=(4,),
                           admit_sizes=(2,), autostart=False)
        prompts = [_prompt(80 + i, 4) for i in range(4)]
        streams = [srv.submit(p, max_new_tokens=3) for p in prompts]
        srv.pump()
        assert srv.counters["admit_dispatches"] == 2
        _drain(srv)
        for p, s in zip(prompts, streams):
            assert s.tokens(5) == _ref(net, p, 3)
        srv.close()

    def test_compile_count_bounded_by_ladder_product(self, net):
        """Executable count stays <= len(admit_sizes) *
        len(prefill_buckets) whatever the traffic mix, and no program
        ever retraces."""
        from mxnet_tpu.serve import DecodeServer
        srv = DecodeServer(net, max_total_len=64, pool_sizes=(4,),
                           autostart=False)
        for wave in ([3], [1, 9], [17, 2, 4], [30], [1, 1, 1, 1]):
            streams = [srv.submit(_prompt(100 + n, n),
                                  max_new_tokens=2) for n in wave]
            _drain(srv)
            for s in streams:
                assert len(s.tokens(5)) == 2
        bound = len(srv.admit_sizes) * len(srv.prefill_buckets)
        assert len(srv._progs._admits) <= bound
        for fn in srv._progs._admits.values():
            assert fn._cache_size() == 1
        srv.close()

    def test_prompt_longer_than_largest_bucket_chunks_in(self, net):
        """Satellite (ISSUE 16): a prompt past the largest pinned
        prefill bucket is NOT rejected any more — chunked prefill
        streams it in over several dispatches, token-exact."""
        from mxnet_tpu.serve import DecodeServer
        srv = DecodeServer(net, max_total_len=64, pool_sizes=(2,),
                           prefill_buckets=(8,), prefix_cache=False,
                           autostart=False)
        p = _prompt(85, 12)              # 12 > bucket 8: two chunks
        s = srv.submit(p, max_new_tokens=4)
        _drain(srv)
        assert s.tokens(5) == _ref(net, p, 4)
        assert srv.counters["chunk_dispatches"] == 2
        assert srv.counters["admit_dispatches"] == 0
        p2 = _prompt(86, 6)              # short prompts still admit
        s2 = srv.submit(p2, max_new_tokens=3)
        _drain(srv)
        assert s2.tokens(5) == _ref(net, p2, 3)
        assert srv.counters["admit_dispatches"] == 1
        srv.close()

    def test_prompt_longer_than_cache_names_limit(self, server):
        """The only hard length limit left is the pool cache length."""
        with pytest.raises(MXNetError, match="pool cache length"):
            server.submit(_prompt(87, 70), max_new_tokens=1)

    def test_ttft_recorded_separately(self, net, server):
        """Satellite: TokenStream.ttft = first-token arrival minus
        submit, kept separately from the per-token times list."""
        _drain(server)
        p = _prompt(88, 4)
        s = server.submit(p, max_new_tokens=3)
        assert s.ttft is None            # nothing arrived yet
        _drain(server)
        assert s.tokens(5) == _ref(net, p, 3)
        assert s.ttft is not None and s.ttft > 0
        assert abs(s.ttft - (s.times[0] - s.submit_time)) < 1e-9
        assert len(s.times) == 3

    def test_env_ladders(self, net, monkeypatch):
        """MXNET_SERVE_ADMIT_SIZES / MXNET_SERVE_PREFILL_BUCKETS pin
        the ladders (prefill buckets clamp to the cache length);
        malformed values are a caller error at construction."""
        from mxnet_tpu.serve import DecodeServer
        monkeypatch.setenv("MXNET_SERVE_ADMIT_SIZES", "1,3")
        monkeypatch.setenv("MXNET_SERVE_PREFILL_BUCKETS", "4,16,999")
        srv = DecodeServer(net, max_total_len=64, pool_sizes=(4,),
                           autostart=False)
        assert srv.admit_sizes == (1, 3)
        assert srv.prefill_buckets == (4, 16, 64)    # clamped to T
        p = _prompt(89, 6)
        s = srv.submit(p, max_new_tokens=3)
        _drain(srv)
        assert s.tokens(5) == _ref(net, p, 3)
        assert (1, 16) in srv._progs._admits
        srv.close()
        monkeypatch.setenv("MXNET_SERVE_ADMIT_SIZES", "zero")
        with pytest.raises(MXNetError, match="ADMIT_SIZES"):
            DecodeServer(net, max_total_len=64, pool_sizes=(2,),
                         autostart=False)


class TestPagedKV:
    """ISSUE 16 tentpole: the paged KV pool, COW shared-prefix caching
    and chunked prefill.  T=64 with the default 16-token pages gives 4
    pages per sequence; prompts of 32/33 tokens pin the two full-hit
    boundary cases (prompt ending ON a page boundary needs one COW
    copy; one past it shares every matched page outright)."""

    def test_full_prefix_hit_zero_prefill_dispatches(self, net):
        """THE acceptance pin: an identical prompt re-submitted after
        its producer retired admits with ZERO prefill dispatches (no
        admit, no chunk) and stays token-exact — including the eager
        COW copy of the boundary page the first step re-writes."""
        from mxnet_tpu.serve import DecodeServer
        srv = DecodeServer(net, max_total_len=64, pool_sizes=(2,),
                           autostart=False)
        p = _prompt(200, 32)             # exactly 2 full pages
        s1 = srv.submit(p, max_new_tokens=5)
        _drain(srv)
        assert s1.tokens(5) == _ref(net, p, 5)
        srv.reset_counters()
        s2 = srv.submit(p, max_new_tokens=5)
        _drain(srv)
        assert s2.tokens(5) == _ref(net, p, 5)
        assert srv.counters["prefix_hits"] == 1
        assert srv.counters["cow_copies"] == 1
        assert srv.counters["admit_dispatches"] == 0
        assert srv.counters["chunk_dispatches"] == 0
        srv.close()

    def test_prefix_hit_off_boundary_no_copy(self, net):
        """A prompt ending one past a page boundary shares every
        matched page read-only — no COW copy at all (the first owned
        page takes the recompute write)."""
        from mxnet_tpu.serve import DecodeServer
        srv = DecodeServer(net, max_total_len=64, pool_sizes=(2,),
                           autostart=False)
        p = _prompt(201, 33)             # 2 full pages + 1 token
        s1 = srv.submit(p, max_new_tokens=4)
        _drain(srv)
        assert s1.tokens(5) == _ref(net, p, 4)
        srv.reset_counters()
        s2 = srv.submit(p, max_new_tokens=4)
        _drain(srv)
        assert s2.tokens(5) == _ref(net, p, 4)
        assert srv.counters["prefix_hits"] == 1
        assert srv.counters["cow_copies"] == 0
        assert srv.counters["admit_dispatches"] == 0
        srv.close()

    def test_prefix_hit_sampled_parity(self, net):
        """A hit's first token comes from the step's recompute of the
        last prompt position with fold_in(key, L-1) — the batched
        admission's exact sampling key, so hit and miss streams match
        the offline batch-1 stream seed-for-seed."""
        from mxnet_tpu.serve import DecodeServer
        srv = DecodeServer(net, max_total_len=64, pool_sizes=(2,),
                           temperature=0.8, top_k=5, autostart=False)
        p = _prompt(202, 32)
        kw = dict(temperature=0.8, top_k=5)
        s1 = srv.submit(p, max_new_tokens=5, seed=7)
        _drain(srv)
        assert s1.tokens(5) == _ref(net, p, 5, seed=7, **kw)
        srv.reset_counters()
        s2 = srv.submit(p, max_new_tokens=5, seed=99)   # new key
        _drain(srv)
        assert s2.tokens(5) == _ref(net, p, 5, seed=99, **kw)
        assert srv.counters["prefix_hits"] == 1
        assert srv.counters["admit_dispatches"] == 0
        srv.close()

    def test_cow_fork_divergence(self, net):
        """Two prompts sharing a one-page prefix fork correctly after
        the first non-shared token: the second maps the shared page and
        streams only its divergent suffix (a partial hit), and neither
        stream perturbs the other."""
        from mxnet_tpu.serve import DecodeServer
        srv = DecodeServer(net, max_total_len=64, pool_sizes=(2,),
                           autostart=False)
        prefix = _prompt(210, 16)        # exactly one full page
        p1 = onp.concatenate([prefix, _prompt(211, 4)])
        p2 = onp.concatenate([prefix, _prompt(212, 4)])
        s1 = srv.submit(p1, max_new_tokens=5)
        _drain(srv)
        srv.reset_counters()
        s2 = srv.submit(p2, max_new_tokens=5)
        _drain(srv)
        assert s1.tokens(5) == _ref(net, p1, 5)
        assert s2.tokens(5) == _ref(net, p2, 5)
        assert srv.counters["prefix_hits"] == 1    # partial hit
        assert srv.counters["admit_dispatches"] == 0
        assert srv.counters["chunk_dispatches"] == 1   # 4-token suffix
        srv.close()

    def test_hit_first_token_costs_one_step(self, net):
        """Acceptance: prefix-hit TTFT is ONE decode step — the hit
        admission dispatches nothing, and the first pump's single step
        dispatch produces the first token."""
        from mxnet_tpu.serve import DecodeServer
        srv = DecodeServer(net, max_total_len=64, pool_sizes=(2,),
                           autostart=False)
        p = _prompt(203, 32)
        s1 = srv.submit(p, max_new_tokens=4)
        _drain(srv)
        srv.reset_counters()
        s2 = srv.submit(p, max_new_tokens=4)
        srv.pump()                       # hit admission + 1 step
        assert srv.counters["admit_dispatches"] == 0
        assert srv.counters["chunk_dispatches"] == 0
        assert srv.counters["step_dispatches"] == 1
        srv.pump()                       # drains the step's readback
        assert len(s2.times) >= 1        # first token arrived
        _drain(srv)
        assert s2.tokens(5) == _ref(net, p, 4)
        srv.close()

    def test_refcounted_pages_freed_on_retire(self, net):
        """Retirement decrefs the slot's page row back to the free
        list; the resident pool's accountant-metered bytes never move
        (pages are recycled, not reallocated)."""
        from mxnet_tpu.serve import DecodeServer
        from mxnet_tpu.telemetry.memory import ACCOUNTANT
        srv = DecodeServer(net, max_total_len=64, pool_sizes=(2,),
                           prefix_cache=False, autostart=False)
        label = srv.telemetry_label
        bytes0 = ACCOUNTANT.bytes(subsystem="serve.kv_pool", key=label)
        assert bytes0 == srv.stats()["pool_bytes"] > 0
        p = _prompt(204, 20)             # pages_for(20 + 4) = 2
        s = srv.submit(p, max_new_tokens=4)
        srv.pump()
        assert srv.stats()["pages_in_use"] == 2
        _drain(srv)
        assert s.tokens(5) == _ref(net, p, 4)
        assert srv.stats()["pages_in_use"] == 0      # refs released
        assert ACCOUNTANT.bytes(subsystem="serve.kv_pool",
                                key=label) == bytes0  # no delta
        srv.close()
        assert srv.stats()["pages_in_use"] == 0

    def test_prefix_cache_retains_only_full_pages(self, net):
        """With the cache ON, retirement keeps exactly the registered
        FULL prompt pages resident (index-owned) for future hits."""
        from mxnet_tpu.serve import DecodeServer
        srv = DecodeServer(net, max_total_len=64, pool_sizes=(2,),
                           autostart=False)
        p = _prompt(205, 20)             # one full page registered
        s = srv.submit(p, max_new_tokens=4)
        _drain(srv)
        assert s.tokens(5) == _ref(net, p, 4)
        st = srv.stats()
        assert st["pages_in_use"] == 1 and st["prefix_nodes"] == 1
        srv.close()

    def test_env_prefix_cache_off(self, net, monkeypatch):
        """MXNET_SERVE_PREFIX_CACHE=0 disables the index: identical
        prompts re-prefill (no hits), parity unchanged."""
        from mxnet_tpu.serve import DecodeServer
        monkeypatch.setenv("MXNET_SERVE_PREFIX_CACHE", "0")
        srv = DecodeServer(net, max_total_len=64, pool_sizes=(2,),
                           autostart=False)
        p = _prompt(206, 32)
        for _ in range(2):
            s = srv.submit(p, max_new_tokens=3)
            _drain(srv)
            assert s.tokens(5) == _ref(net, p, 3)
        assert srv.counters["prefix_hits"] == 0
        assert srv.counters["admit_dispatches"] == 2
        srv.close()

    def test_env_page_size(self, net, monkeypatch):
        """MXNET_SERVE_PAGE_SIZE pins the page granule; malformed
        values are a constructor error."""
        from mxnet_tpu.serve import DecodeServer
        monkeypatch.setenv("MXNET_SERVE_PAGE_SIZE", "8")
        srv = DecodeServer(net, max_total_len=64, pool_sizes=(2,),
                           autostart=False)
        assert srv._progs.page == 8 and srv._progs.maxp == 8
        p = _prompt(207, 12)
        s = srv.submit(p, max_new_tokens=4)
        _drain(srv)
        assert s.tokens(5) == _ref(net, p, 4)
        srv.close()
        monkeypatch.setenv("MXNET_SERVE_PAGE_SIZE", "none")
        with pytest.raises(MXNetError, match="PAGE_SIZE"):
            DecodeServer(net, max_total_len=64, autostart=False)

    def test_page_churn_never_retraces(self, net):
        """Steady-state discipline through the page-table operand:
        admit / hit / chunk / retire churn changes table VALUES only —
        the step executable compiles once, ever."""
        from mxnet_tpu.serve import DecodeServer
        srv = DecodeServer(net, max_total_len=64, pool_sizes=(2,),
                           prefill_buckets=(8, 16),
                           autostart=False)
        p_long = _prompt(208, 24)        # chunks (24 > bucket 16)
        p_short = _prompt(209, 6)
        for p, n in ((p_short, 4), (p_long, 4), (p_short, 3),
                     (p_long, 3)):
            s = srv.submit(p, max_new_tokens=n)
            _drain(srv)
            assert s.tokens(5) == _ref(net, p, n)
        assert srv.counters["prefix_hits"] >= 1
        assert srv.counters["chunk_dispatches"] >= 1
        assert srv._progs.step_fn()._cache_size() == 1
        for fn in srv._progs._admits.values():
            assert fn._cache_size() == 1
        for fn in srv._progs._chunks.values():
            assert fn._cache_size() == 1
        for fn in srv._progs._hits.values():
            assert fn._cache_size() == 1
        srv.close()


class TestKVQuantPages:
    """ISSUE 18: int8 page storage — representation-error pins (the
    PARITY.md tolerance), the env knob, and the one-executable
    steady-state discipline on a quantized pool."""

    def test_requant_roundtrip_bound_and_drift_free(self):
        """The PARITY.md representation pins: dequantized values sit
        within page_absmax/254 (half a code step) of the written
        values, and floor-scale requantization is drift-free — codes
        re-quantized at their own scale round-trip EXACTLY, so a
        frontier page's RMW never re-rounds already-written columns."""
        import jax.numpy as jnp

        from mxnet_tpu.models.decoding import _kv_dequant, _kv_requant

        rng = onp.random.RandomState(0)
        # two pages in the pool's row layout: (page 16, KV 4 x D 8)
        vals = jnp.asarray(rng.randn(2, 16, 4 * 8).astype("float32"))
        codes, scales = _kv_requant(vals, 0.0, 4)
        assert codes.dtype == jnp.int8 and scales.dtype == jnp.float32
        assert codes.shape == (2, 16, 32) and scales.shape == (2, 4)
        deq = _kv_dequant(codes, scales, jnp.float32)
        # one scale per page and K/V head: over its rows and its D lanes
        heads = lambda a: onp.asarray(a).reshape(2, 16, 4, 8)
        amax = onp.max(onp.abs(heads(vals)), axis=(1, 3))
        err = onp.max(onp.abs(heads(deq - vals)), axis=(1, 3))
        assert onp.all(err <= amax / 254.0 * (1 + 1e-5))
        # drift-free: requantizing the dequantized page at its own
        # floor scale reproduces codes and scales bit-for-bit
        codes2, scales2 = _kv_requant(deq, scales, 4)
        assert onp.array_equal(onp.asarray(codes), onp.asarray(codes2))
        assert onp.array_equal(onp.asarray(scales),
                               onp.asarray(scales2))
        # scales only ratchet: a larger floor wins, a smaller one is
        # ignored
        _, s_up = _kv_requant(deq, scales * 2, 4)
        assert onp.allclose(onp.asarray(s_up),
                            onp.asarray(scales) * 2)

    def test_kv_dtype_env_knob_and_validation(self, net, monkeypatch):
        """MXNET_SERVE_KV_DTYPE selects the pool storage dtype; the
        explicit constructor argument wins; malformed values are a
        constructor error naming the variable."""
        from mxnet_tpu.serve import DecodeServer
        monkeypatch.setenv("MXNET_SERVE_KV_DTYPE", "int8")
        srv = DecodeServer(net, max_total_len=64, pool_sizes=(2,),
                           autostart=False)
        assert srv.kv_dtype == "int8"
        assert srv.stats()["kv_dtype"] == "int8"
        pb_i8 = srv.stats()["page_bytes"]
        p = _prompt(220, 6)
        s = srv.submit(p, max_new_tokens=8)
        _drain(srv)
        ref = _ref(net, p, 8)
        agree = sum(int(a == b)
                    for a, b in zip(s.tokens(5), ref)) / len(ref)
        assert agree >= 0.9, (s.tokens(5), ref)
        srv.close()
        # explicit argument beats the env
        srv = DecodeServer(net, max_total_len=64, pool_sizes=(2,),
                           kv_dtype="f32", autostart=False)
        assert srv.kv_dtype == "native"
        assert srv.stats()["page_bytes"] > 2 * pb_i8
        srv.close()
        monkeypatch.setenv("MXNET_SERVE_KV_DTYPE", "int4")
        with pytest.raises(MXNetError, match="KV_DTYPE"):
            DecodeServer(net, max_total_len=64, autostart=False)

    def test_int8_churn_never_retraces(self, net):
        """The tentpole's compile discipline on the QUANTIZED pool:
        admit / hit / chunk / retire churn against int8 pages keeps
        every executable at one signature — quantization lives inside
        the same programs, not beside them."""
        from mxnet_tpu.serve import DecodeServer
        srv = DecodeServer(net, max_total_len=64, pool_sizes=(2,),
                           prefill_buckets=(8, 16), kv_dtype="int8",
                           autostart=False)
        p_long = _prompt(221, 24)        # chunks (24 > bucket 16)
        p_short = _prompt(222, 6)
        for p, n in ((p_short, 4), (p_long, 4), (p_short, 3),
                     (p_long, 3)):
            s = srv.submit(p, max_new_tokens=n)
            _drain(srv)
            got, ref = s.tokens(5), _ref(net, p, n)
            agree = sum(int(a == b) for a, b in zip(got, ref)) / n
            assert agree >= 0.9, (got, ref)
        assert srv.counters["prefix_hits"] >= 1
        assert srv.counters["chunk_dispatches"] >= 1
        assert srv._progs.step_fn()._cache_size() == 1
        for fns in (srv._progs._admits, srv._progs._chunks,
                    srv._progs._hits):
            for fn in fns.values():
                assert fn._cache_size() == 1
        srv.close()

    # recycled-page scale reset (post-review regression): the pool
    # free list is host-only bookkeeping, so a reallocated page still
    # holds its previous tenant's codes AND per-page scale on device.
    # The requantizing RMWs floor each write at the page's resident
    # scale (monotone ratchet), so WITHOUT a reset the first touch of
    # a recycled page pins its scale to the OLD tenant's dynamic range
    # — breaking the PARITY.md absmax/254 bound exactly under churn.
    # Each admission path (admit / prefix-hit / chunk) must zero the
    # scales of every freshly allocated page inside its own dispatch.

    @staticmethod
    def _scales(srv):
        (_, ks), (_, vs) = srv._state[0], srv._state[1]
        return onp.asarray(ks), onp.asarray(vs)

    def test_recycled_pages_reset_on_admit(self, net):
        from mxnet_tpu.serve import DecodeServer
        srv = DecodeServer(net, max_total_len=64, pool_sizes=(1,),
                           prefill_buckets=(8, 16), kv_dtype="int8",
                           spec=False, autostart=False)
        # tenant A dirties pages with real (nonzero) scales, then
        # retires — its pages return to the free list un-zeroed
        pa = _prompt(230, 14)
        sa = srv.submit(pa, max_new_tokens=18)     # 2 pages, both hit
        _drain(srv)
        assert sa.tokens(5) is not None
        ks0, _ = self._scales(srv)
        dirty = {p for p in range(4) if onp.any(ks0[:, p] != 0)}
        assert dirty and dirty <= set(srv._pages._free)
        # tenant B reserves the WHOLE pool: prompt page + 3 decode-
        # frontier pages, at least one of which A dirtied
        pb = _prompt(231, 6)
        sb = srv.submit(pb, max_new_tokens=58)
        assert srv.pump()
        row = srv._slot_pages[0]
        assert len(row) == 4
        assert set(row[1:]) & dirty, (row, dirty)  # churn precondition
        ks, vs = self._scales(srv)
        # admit wrote the prompt page; the reserved-but-unwritten
        # frontier pages must carry ZERO scales (reset happened) so
        # their first RMW floors at 0, not at A's range
        assert onp.all(ks[:, row[1:]] == 0), ks[:, row[1:]]
        assert onp.all(vs[:, row[1:]] == 0), vs[:, row[1:]]
        assert onp.all(ks[:, row[0]] > 0)          # prompt page landed
        _drain(srv)
        ref = _ref(net, pb, 58)
        got = sb.tokens(5)
        agree = sum(int(a == b) for a, b in zip(got, ref)) / len(ref)
        assert agree >= 0.9, (got, ref)
        srv.close()

    def test_recycled_pages_reset_on_prefix_hit(self, net):
        from mxnet_tpu.serve import DecodeServer
        srv = DecodeServer(net, max_total_len=64, pool_sizes=(1,),
                           prefill_buckets=(8, 16), kv_dtype="int8",
                           spec=False, autostart=False)
        p = _prompt(232, 16)                       # one full page
        sa = srv.submit(p, max_new_tokens=32)      # 3 pages dirtied
        _drain(srv)
        assert sa.tokens(5) is not None
        ks0, _ = self._scales(srv)
        dirty = {p for p in range(4) if onp.any(ks0[:, p] != 0)}
        # resubmit: full prefix hit with one COW copy (prompt ends on
        # the shared page boundary); the fresh pages are recycled
        sb = srv.submit(p, max_new_tokens=16)
        assert srv.pump()
        assert srv.counters["prefix_hits"] >= 1
        assert srv.counters["cow_copies"] >= 1
        row = srv._slot_pages[0]
        assert len(row) == 2
        assert set(row[1:]) & dirty, (row, dirty)  # churn precondition
        ks, vs = self._scales(srv)
        # row[0] is the COW dst: zeroed, then the copied scale landed
        assert onp.all(ks[:, row[0]] > 0)
        # row[1] is a recycled decode-frontier page: must be reset
        assert onp.all(ks[:, row[1]] == 0), ks[:, row[1]]
        assert onp.all(vs[:, row[1]] == 0), vs[:, row[1]]
        _drain(srv)
        ref = _ref(net, p, 16)
        got = sb.tokens(5)
        agree = sum(int(a == b) for a, b in zip(got, ref)) / len(ref)
        assert agree >= 0.9, (got, ref)
        srv.close()

    def test_recycled_pages_reset_on_chunked_prefill(self, net):
        from mxnet_tpu.serve import DecodeServer
        srv = DecodeServer(net, max_total_len=64, pool_sizes=(1,),
                           prefill_buckets=(8, 16), kv_dtype="int8",
                           spec=False, autostart=False)
        pa = _prompt(233, 30)                      # > bucket: chunks
        srv.submit(pa, max_new_tokens=18)          # 3 pages dirtied
        _drain(srv)
        ks0, _ = self._scales(srv)
        dirty = {p for p in range(4) if onp.any(ks0[:, p] != 0)}
        pb = _prompt(234, 24)
        pb[0] = (pa[0] + 1) % 97                   # no prefix match
        sb = srv.submit(pb, max_new_tokens=40)     # needs all 4 pages
        assert srv.pump()                          # FIRST chunk only
        row = srv._slot_pages[0]
        assert len(row) == 4
        # chunk 1 (16 tokens) writes window pages row[0:2]; the pages
        # beyond it were only scale-reset by the dispatch's zrow
        assert set(row[2:]) & dirty, (row, dirty)  # churn precondition
        ks, vs = self._scales(srv)
        assert onp.all(ks[:, row[2:]] == 0), ks[:, row[2:]]
        assert onp.all(vs[:, row[2:]] == 0), vs[:, row[2:]]
        assert onp.all(ks[:, row[0]] > 0)          # chunk 1 landed
        _drain(srv)
        assert srv.counters["chunk_dispatches"] >= 2
        ref = _ref(net, pb, 40)
        got = sb.tokens(5)
        agree = sum(int(a == b) for a, b in zip(got, ref)) / len(ref)
        assert agree >= 0.9, (got, ref)
        srv.close()


class TestSyncFallback:
    def test_env_hatch_serves_synchronously(self, net, monkeypatch):
        from mxnet_tpu.serve import DecodeServer
        monkeypatch.setenv("MXNET_SERVE_SYNC", "1")
        srv = DecodeServer(net, max_total_len=64, autostart=False)
        assert srv.sync_mode and "MXNET_SERVE_SYNC" in srv.sync_reason
        p = _prompt(24, 5)
        s = srv.submit(p, max_new_tokens=6)
        _drain(srv)
        assert s.tokens(5) == _ref(net, p, 6)
        assert srv.counters["sync_requests"] == 1
        assert srv.counters["step_dispatches"] == 0
        srv.close()

    def test_unstackable_model_falls_back(self, monkeypatch):
        """A model the slot-pool gate rejects (non-uniform layer stack)
        still serves — through the kv_generate fallback, with the
        reason recorded."""
        from mxnet_tpu.serve import DecodeServer
        net = _gpt()
        net.blocks[1].ln1._eps = 1e-3
        srv = DecodeServer(net, max_total_len=64, autostart=False)
        assert srv.sync_mode
        assert "stacked" in srv.sync_reason
        p = _prompt(25, 4)
        s = srv.submit(p, max_new_tokens=4)
        _drain(srv)
        assert s.tokens(5) == _ref(net, p, 4)
        srv.close()


class TestTokenStream:
    def test_streaming_iteration_and_detok(self, net, server):
        seen = []
        p = _prompt(26, 4)
        s = server.submit(p, max_new_tokens=4,
                          on_token=lambda rid, t: seen.append(t))
        _drain(server)
        assert list(s) == _ref(net, p, 4)      # iterator replay
        assert seen == _ref(net, p, 4)

    def test_finished_stream_reiterates(self, net, server):
        """Iterating a TokenStream is replayable: a second pass (or a
        second consumer) sees the full stream again instead of hanging
        on a consumed end-sentinel (post-review regression)."""
        import threading
        p = _prompt(28, 4)
        s = server.submit(p, max_new_tokens=4)
        _drain(server)
        ref = _ref(net, p, 4)
        assert list(s) == ref
        assert list(s) == ref                  # second pass replays
        got = []
        th = threading.Thread(target=lambda: got.append(list(s)))
        th.start()
        th.join(5.0)
        assert not th.is_alive() and got == [ref]

    def test_text_iter_detokenizes(self, net):
        from mxnet_tpu.serve import DecodeServer
        srv = DecodeServer(net, max_total_len=64, pool_sizes=(1,),
                           detokenize=lambda t: f"<{t}>",
                           autostart=False)
        p = _prompt(27, 4)
        s = srv.submit(p, max_new_tokens=3)
        _drain(srv)
        ref = _ref(net, p, 3)
        assert s.text(5) == "".join(f"<{t}>" for t in ref)
        srv.close()


class TestServeBenchSmoke:
    def test_ragged_lengths_single_slot_pool(self):
        """A 1-slot pool (the default MXNET_SERVE_POOL_SIZES starts at
        1) has no short lanes — ragged_lengths must degenerate to
        all-full-length instead of dividing by S - 1 = 0."""
        from benchmark.serve_bench import ragged_lengths
        assert ragged_lengths(1, 8, 0.25, 5) == [8] * 5
        lens = ragged_lengths(4, 8, 0.25, 8)
        assert len(lens) == 8 and max(lens) == 8 and min(lens) >= 1

    def test_serve_bench_smoke(self, tmp_path):
        """benchmark/serve_bench.py --smoke: saturated slot-pool serving
        on a tiny geometry — parity with kv_generate, dispatch
        accounting and a throughput floor asserted inside, plus the
        ragged-arrival continuous-vs-static rows printed (the tier-1
        gate; the 0.8x/ragged-win acceptance bars are asserted by the
        compute-bound --cpu-full profile, recorded in BASELINE.md).

        The run records its telemetry stream to a JSONL
        (``MXNET_TELEMETRY_JSONL``), and ``tools/telemetry_report.py
        --check-serve`` must then reproduce the pinned serving
        invariants — ladder-bounded compile count, zero steady-state
        retraces, one step dispatch per decode step — from the
        recorded file ALONE (ISSUE 9 acceptance)."""
        jsonl = str(tmp_path / "serve_telemetry.jsonl")
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   MXNET_TELEMETRY_JSONL=jsonl)
        r = subprocess.run(
            [sys.executable, "benchmark/serve_bench.py", "--smoke"],
            capture_output=True, text=True, cwd="/root/repo", env=env,
            timeout=570)
        assert r.returncode == 0, r.stderr[-2000:]
        assert '"bench": "serve_smoke"' in r.stdout
        assert "serve OK" in r.stdout
        assert "telemetry OK" in r.stdout

        assert os.path.exists(jsonl), "JSONL sink never attached"
        rep = subprocess.run(
            [sys.executable, "tools/telemetry_report.py", jsonl,
             "--check-serve"],
            capture_output=True, text=True, cwd="/root/repo",
            timeout=120)
        assert rep.returncode == 0, \
            rep.stdout[-2000:] + rep.stderr[-2000:]
        assert "serve checks OK" in rep.stdout
        assert "compile events" in rep.stdout
        assert "serve requests" in rep.stdout
        assert "bench rows" in rep.stdout
