"""Memory observability (ISSUE 10): per-executable memory analysis on
compile events (``MXNET_TELEMETRY_MEM``), the live HBM accountant and
its ``jax.live_arrays()`` reconciliation, budget-aware serving
(``MXNET_SERVE_HBM_BUDGET`` / ``DecodeServer(hbm_budget=)``), and the
offline ``tools/memory_report.py``.

Conventions follow tests/test_telemetry.py: the registry / event ring /
accountant are process-global, so tests use unique subsystem names and
measure deltas instead of absolute values."""
import json
import os
import subprocess
import sys

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.telemetry import memory as tmem


@pytest.fixture(scope="module")
def tiny_gpt():
    from mxnet_tpu.models import GPT, GPTConfig

    mx.random.seed(0)
    net = GPT(GPTConfig(vocab_size=64, max_length=24, num_layers=2,
                        units=16, num_heads=2, hidden_size=32))
    net.initialize(mx.init.Normal(0.02))
    return net


def _pool1_bytes(net):
    """Exact device bytes of a 1-slot pool for ``net`` at T=24 — the
    unit the budget tests price against."""
    from mxnet_tpu.serve import DecodeServer

    srv = DecodeServer(net, max_total_len=24, pool_sizes=(1,),
                       autostart=False)
    try:
        return srv.stats()["pool_bytes"]
    finally:
        srv.close()


# --------------------------------------------------------------------- #
# byte helpers
# --------------------------------------------------------------------- #

class TestByteHelpers:
    def test_parse_bytes(self):
        assert tmem.parse_bytes(1024) == 1024
        assert tmem.parse_bytes("1024") == 1024
        assert tmem.parse_bytes("4k") == 4 << 10
        assert tmem.parse_bytes("2M") == 2 << 20
        assert tmem.parse_bytes("1.5G") == 3 << 29
        with pytest.raises(MXNetError, match="t_budget"):
            tmem.parse_bytes("lots", "t_budget")
        with pytest.raises(MXNetError, match=">= 0"):
            tmem.parse_bytes(-1)
        # overflow/inf degrade to the same clean error, not a raw
        # OverflowError out of int()
        with pytest.raises(MXNetError, match="expected bytes"):
            tmem.parse_bytes("1e999")
        with pytest.raises(MXNetError, match="expected bytes"):
            tmem.parse_bytes(float("inf"))
        with pytest.raises(MXNetError, match="expected bytes"):
            tmem.parse_bytes(True)

    def test_format_bytes(self):
        assert tmem.format_bytes(512) == "512 B"
        assert tmem.format_bytes(3 << 29) == "1.50 GiB"
        assert "MiB" in tmem.format_bytes(5 << 20)

    def test_nbytes_of(self):
        import jax.numpy as jnp

        assert tmem.nbytes_of(None) == 0
        assert tmem.nbytes_of(onp.zeros((4, 4), onp.float32)) == 64
        assert tmem.nbytes_of(jnp.zeros((8,), jnp.int32)) == 32
        nd = mx.nd.array(onp.zeros((2, 2), onp.float32))
        assert tmem.nbytes_of(nd) == 16
        tree = {"a": [onp.zeros(2, onp.float64), None],
                "b": (jnp.zeros(3, jnp.float32),)}
        assert tmem.nbytes_of(tree) == 16 + 12
        assert tmem.nbytes_of("not an array") == 0

    def test_per_device_bytes(self):
        import jax.numpy as jnp

        pd = tmem.per_device_bytes(jnp.zeros((4,), jnp.float32))
        assert sum(pd.values()) == 16
        assert all(":" in k for k in pd)
        # host numpy is charged to the host bucket, not a device
        assert tmem.per_device_bytes(onp.zeros(4, onp.int8)) == \
            {"host:0": 4}


# --------------------------------------------------------------------- #
# per-executable analysis on compile events
# --------------------------------------------------------------------- #

class TestCompileMemoryFields:
    def test_mem_fields_under_env(self, monkeypatch):
        import jax
        import jax.numpy as jnp

        monkeypatch.setenv("MXNET_TELEMETRY_MEM", "1")
        fn = telemetry.instrument_jit(
            jax.jit(lambda x: jnp.tanh(x) @ x, donate_argnums=(0,)),
            "t.mem_on")
        out = fn(jnp.ones((16, 16)))
        ev = [e for e in telemetry.events("compile")
              if e.get("site") == "t.mem_on"][-1]
        assert ev["mem_arg_bytes"] == 16 * 16 * 4
        assert ev["mem_out_bytes"] == 16 * 16 * 4
        assert ev["mem_temp_bytes"] >= 0
        # peak is the documented arithmetic over the parts
        assert ev["mem_peak_bytes"] == (
            ev["mem_arg_bytes"] + ev["mem_out_bytes"]
            + ev["mem_temp_bytes"] + ev.get("mem_code_bytes", 0)
            - ev.get("mem_alias_bytes", 0))
        # the analysis recompiles from shape structs: the just-donated
        # input buffer was never dereferenced, the output is live
        assert float(out[0, 0]) != 0.0

    def test_mem_off_by_default(self):
        import jax
        import jax.numpy as jnp

        fn = telemetry.instrument_jit(jax.jit(lambda x: x + 1),
                                      "t.mem_off")
        fn(jnp.ones(4))
        ev = [e for e in telemetry.events("compile")
              if e.get("site") == "t.mem_off"][-1]
        assert not any(k.startswith("mem_") for k in ev)

    def test_memory_analysis_helper(self):
        import jax
        import jax.numpy as jnp

        compiled = jax.jit(lambda x: x * 2).lower(
            jax.ShapeDtypeStruct((8,), jnp.float32)).compile()
        ma = telemetry.memory_analysis(compiled)
        assert ma["arg_bytes"] == 32 and ma["out_bytes"] == 32
        assert ma["peak_bytes"] >= 64
        # objects without the stats surface degrade to None, not a crash
        assert telemetry.memory_analysis(object()) is None


# --------------------------------------------------------------------- #
# the accountant
# --------------------------------------------------------------------- #

class TestAccountant:
    def test_set_drop_gauge_and_events(self):
        import jax.numpy as jnp

        def my_events():
            # scoped to THIS test's subsystem: under the full suite,
            # other tests' gc'd trainers/rings drain deferred drops
            # (their own device_memory events) inside our set() calls
            return [e for e in telemetry.events("device_memory")
                    if e.get("subsystem") == "t.acct"]

        A = telemetry.ACCOUNTANT
        arr = jnp.zeros((8, 8), jnp.float32)
        before = len(my_events())
        A.set("t.acct", "k1", arr)
        assert A.bytes(subsystem="t.acct") == 256
        dev = next(iter(tmem.per_device_bytes(arr)))
        g = telemetry.gauge("device_bytes", subsystem="t.acct",
                            device=dev)
        assert g.value == 256
        # unchanged re-registration is free: no second event
        A.set("t.acct", "k1", arr)
        assert len(my_events()) == before + 1
        # a second key accumulates into the subsystem gauge
        A.set("t.acct", "k2", jnp.zeros((4,), jnp.float32))
        assert A.bytes(subsystem="t.acct") == 256 + 16
        assert g.value == 272
        assert A.snapshot()["t.acct"][dev] == 272
        A.drop("t.acct", "k1")
        A.drop("t.acct", "k2")
        A.drop("t.acct", "k2")          # idempotent
        assert A.bytes(subsystem="t.acct") == 0
        assert g.value == 0
        last = my_events()[-1]
        assert last["subsystem"] == "t.acct" and last["bytes"] == 0

    def test_deferred_drop_lock_free_and_drained_on_query(self):
        """``drop_deferred`` (the ``__del__``-safe path) takes no lock
        at enqueue time; the entry is fully retired — ledger, gauge,
        event — by the next normal-thread query."""
        A = telemetry.ACCOUNTANT
        A.set("t.acct_def", "k", per_device={"cpu:0": 64})
        A.drop_deferred("t.acct_def", "k")
        A.drop_deferred("t.acct_def", "never-registered")   # harmless
        # the query drains the queue before reading
        assert A.bytes(subsystem="t.acct_def") == 0
        g = telemetry.gauge("device_bytes", subsystem="t.acct_def",
                            device="cpu:0")
        assert g.value == 0
        assert "t.acct_def" not in A.snapshot()

    def test_explicit_per_device_mapping(self):
        A = telemetry.ACCOUNTANT
        A.set("t.acct_pd", "ring", per_device={"cpu:0": 100,
                                               "cpu:1": 50})
        assert A.bytes(subsystem="t.acct_pd") == 150
        assert A.bytes(subsystem="t.acct_pd", device="cpu:1") == 50
        A.drop("t.acct_pd", "ring")

    def test_reconcile_against_live_arrays(self):
        import jax.numpy as jnp

        A = telemetry.ACCOUNTANT
        arr = jnp.ones((32, 32), jnp.float32)   # keep a live ref
        A.set("t.acct_rec", "arr", arr)
        try:
            rec = telemetry.reconcile()
            dev = next(iter(tmem.per_device_bytes(arr)))
            assert dev in rec
            # live_arrays sees this registered array plus everything the
            # ledger was never told about — the accounted bytes for a
            # LIVE allocation can never exceed the live total
            assert rec[dev]["live"] >= 32 * 32 * 4
            assert rec[dev]["accounted"] >= 32 * 32 * 4
            assert 0 < rec[dev]["coverage"] <= 1 or \
                rec[dev]["delta"] < 0   # stale entries from other tests
        finally:
            A.drop("t.acct_rec", "arr")


# --------------------------------------------------------------------- #
# acceptance: mem fields from >= 4 distinct compile sites + reconcile
# --------------------------------------------------------------------- #

class TestSiteCoverage:
    def test_four_sites_carry_memory_analysis(self, monkeypatch,
                                              tiny_gpt):
        """With ``MXNET_TELEMETRY_MEM=1``, compile events from the
        fused train step, the CachedOp, offline decode, and the serve
        step/admit programs all carry ``mem_*`` fields — and the live
        accountant reconciles against ``jax.live_arrays()`` while the
        pool is resident (the documented tolerance: live >= accounted
        for live allocations; live also holds unregistered buffers)."""
        from mxnet_tpu import gluon
        from mxnet_tpu.gluon import nn
        from mxnet_tpu.models import kv_generate
        from mxnet_tpu.serve import DecodeServer

        monkeypatch.setenv("MXNET_TELEMETRY_MEM", "1")
        before = len(telemetry.events("compile"))

        # 1. fused train step
        mx.random.seed(0)
        net = nn.Dense(4, in_units=6)
        net.initialize(mx.init.Xavier())
        trainer = gluon.Trainer(net.collect_params(), "sgd",
                                {"learning_rate": 0.1}, kvstore=None)
        loss_l = gluon.loss.L2Loss()

        def loss_fn(xx, yy):
            return loss_l(net(xx), yy)

        rng = onp.random.RandomState(0)
        trainer.fused_step(loss_fn,
                           mx.nd.array(rng.rand(2, 6).astype("f")),
                           mx.nd.array(rng.rand(2, 4).astype("f")))
        # ledger: this trainer's params are exactly accounted
        assert telemetry.ACCOUNTANT.bytes(
            subsystem="train.params", key=trainer._mem_label) == \
            sum(tmem.nbytes_of(p.data())
                for p in net.collect_params().values())

        # 2. CachedOp (hybridized inference)
        hnet = nn.Dense(3, in_units=5)
        hnet.initialize(mx.init.Xavier())
        hnet.hybridize()
        hnet(mx.nd.array(rng.rand(2, 5).astype("f")))

        # 3. offline decode (kv_generate)
        kv_generate(tiny_gpt, rng.randint(0, 64, (1, 3)),
                    max_new_tokens=5, temperature=0.0)

        # 4. serve step + admit
        srv = DecodeServer(tiny_gpt, max_total_len=24, pool_sizes=(1,),
                           autostart=False)
        s = srv.submit(rng.randint(0, 64, (3,)), max_new_tokens=3)
        while srv.pump():
            pass
        s.tokens(30)
        pool_bytes = srv.stats()["pool_bytes"]
        assert pool_bytes > 0
        assert telemetry.ACCOUNTANT.bytes(
            subsystem="serve.kv_pool",
            key=srv.telemetry_label) == pool_bytes
        rec = telemetry.reconcile()
        # reconcile on the device the pool actually lives on (under the
        # suite's 8-device virtual mesh other devices hold other tests'
        # entries) — live >= this live allocation's accounted bytes
        pool_devs = telemetry.ACCOUNTANT.snapshot()["serve.kv_pool"]
        dev = max(pool_devs, key=pool_devs.get)
        assert rec[dev]["live"] >= pool_bytes
        srv.close()

        sites = {e.get("site") for e in
                 telemetry.events("compile")[before:]
                 if "mem_peak_bytes" in e}
        assert {"gluon.fused_step", "gluon.cached_op",
                "models.kv_generate", "serve.step",
                "serve.admit"} <= sites, sites


# --------------------------------------------------------------------- #
# paged-pool pricing (ISSUE 16)
# --------------------------------------------------------------------- #

class TestPagedPricing:
    """ISSUE 16 satellite: ``pool_state_bytes`` pages pricing equals
    the allocator-reported device bytes of the paged state at init and
    after growth, and ``stats()['pool_bytes']`` stays truthful while
    pages are recycled.  ISSUE 18 re-pins every identity for BOTH pool
    dtypes — an int8 pool's (codes, scales) pages must price exactly
    like they allocate."""

    @pytest.mark.parametrize("kv_dtype", ["native", "int8"])
    def test_pool_state_bytes_matches_device_state(self, tiny_gpt,
                                                   kv_dtype):
        from mxnet_tpu.serve import engine as seng

        progs = seng.PoolPrograms(tiny_gpt, num_slots=2, max_total=24,
                                  kv_dtype=kv_dtype)
        state = seng.pool_state_init(progs)
        assert sum(tmem.nbytes_of(x) for x in state) == \
            seng.pool_state_bytes(progs)

    @pytest.mark.parametrize("kv_dtype", ["native", "int8"])
    def test_pool_state_grow_matches_pricing(self, tiny_gpt, kv_dtype):
        """Growth adds slots AND pages; the priced bytes track the
        grown state exactly (no drift between pricer and allocator)."""
        from mxnet_tpu.serve import engine as seng

        progs = seng.PoolPrograms(tiny_gpt, num_slots=1, max_total=24,
                                  kv_dtype=kv_dtype)
        state = seng.pool_state_init(progs)
        new_pages = 3 * progs.maxp
        grown = seng.pool_state_grow(state, 3, new_pages=new_pages)
        assert sum(tmem.nbytes_of(x) for x in grown) == \
            seng.pool_state_bytes(progs, 3, num_pages=new_pages)

    def test_int8_pool_shrinks_pages_about_4x(self, tiny_gpt):
        """The capacity claim at the pricing layer: an int8 page costs
        codes + per-page scales, ~4x under the f32 page (>= 2x is the
        budget-doubling bar; the exact ratio depends on page geometry
        via the scale overhead)."""
        from mxnet_tpu.serve import engine as seng

        f32 = seng.PoolPrograms(tiny_gpt, num_slots=2, max_total=24)
        i8 = seng.PoolPrograms(tiny_gpt, num_slots=2, max_total=24,
                               kv_dtype="int8")
        assert i8.page_bytes() * 2 < f32.page_bytes()
        assert seng.pool_state_bytes(i8) * 2 < \
            seng.pool_state_bytes(f32)

    @pytest.mark.parametrize("kv_dtype", ["native", "int8"])
    def test_pool_bytes_truthful_under_page_reuse(self, tiny_gpt,
                                                  kv_dtype):
        """Admit/retire churn recycles pages in place: the resident
        pool's reported and accountant-metered bytes never move (and
        under int8 they agree with the allocator's view of the
        (codes, scales) state)."""
        from mxnet_tpu.serve import DecodeServer
        from mxnet_tpu.serve.engine import pool_state_bytes

        srv = DecodeServer(tiny_gpt, max_total_len=24, pool_sizes=(1,),
                           prefix_cache=False, autostart=False,
                           kv_dtype=kv_dtype)
        try:
            b0 = srv.stats()["pool_bytes"]
            assert b0 > 0
            assert b0 == pool_state_bytes(srv._progs)
            for seed in range(3):
                rng = onp.random.RandomState(seed)
                s = srv.submit(rng.randint(0, 64, (5,)),
                               max_new_tokens=4)
                while srv.pump():
                    pass
                s.tokens(10)
                st = srv.stats()
                assert st["pool_bytes"] == b0
                assert st["pages_in_use"] == 0
                assert telemetry.ACCOUNTANT.bytes(
                    subsystem="serve.kv_pool",
                    key=srv.telemetry_label) == b0
        finally:
            srv.close()


# --------------------------------------------------------------------- #
# budget-aware serving
# --------------------------------------------------------------------- #

class TestServeBudget:
    def test_growth_over_budget_raises(self, tiny_gpt):
        """The acceptance pin: an over-budget pool growth is a clean
        ``MXNetError`` naming requested vs available bytes — never an
        allocator OOM."""
        from mxnet_tpu.serve import DecodeServer

        pool1 = _pool1_bytes(tiny_gpt)
        # 2.5x: fits the minimum usable config (pool + A=1 scratch =
        # 2x) and steady serving at 1 slot, refuses the growth's
        # transient old+new peak (3x)
        srv = DecodeServer(tiny_gpt, max_total_len=24,
                           pool_sizes=(1, 2),
                           hbm_budget=int(pool1 * 2.5),
                           autostart=False)
        try:
            srv.submit(onp.array([1, 2, 3]), max_new_tokens=6)
            srv.submit(onp.array([4, 5, 6]), max_new_tokens=6)
            with pytest.raises(MXNetError,
                               match=r"pool growth 1 -> 2") as ei:
                while srv.pump():
                    pass
            msg = str(ei.value)
            # requested vs available, in bytes, plus the remedy
            assert "requests" in msg and "remains" in msg
            assert "KiB" in msg or " B" in msg
            assert "MXNET_SERVE_HBM_BUDGET" in msg
        finally:
            srv.close(drain=False)

    def test_growth_priced_at_transient_peak(self, tiny_gpt):
        """pool_state_grow holds old AND new pools until the copy
        completes — a budget the settled 2-slot pool fits (2x) but the
        transient old+new peak (3x) does not is refused at the peak."""
        from mxnet_tpu.serve import DecodeServer

        pool1 = _pool1_bytes(tiny_gpt)
        srv = DecodeServer(tiny_gpt, max_total_len=24,
                           pool_sizes=(1, 2),
                           hbm_budget=int(pool1 * 2.2),
                           autostart=False)
        try:
            srv.submit(onp.array([1, 2], onp.int32), max_new_tokens=6)
            srv.submit(onp.array([3, 4], onp.int32), max_new_tokens=6)
            with pytest.raises(MXNetError, match="pool growth"):
                while srv.pump():
                    pass
        finally:
            srv.close(drain=False)

    def test_grad_accum_ledger_per_fused_step(self):
        """Two FusedSteps on one trainer own two accumulator rings —
        two ledger entries, not one overwriting the other — and
        release_accounting (the eviction hook) retires an entry."""
        from mxnet_tpu import gluon
        from mxnet_tpu.gluon import nn

        mx.random.seed(0)
        net = nn.Dense(4, in_units=6)
        net.initialize(mx.init.Xavier())
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.1}, kvstore=None,
                           update_interval=2)
        loss_l = gluon.loss.L2Loss()

        def loss_a(xx, yy):
            return loss_l(net(xx), yy)

        def loss_b(xx, yy):
            return loss_l(net(xx), yy) * 2

        rng = onp.random.RandomState(0)
        x = mx.nd.array(rng.rand(2, 6).astype("f"))
        y = mx.nd.array(rng.rand(2, 4).astype("f"))
        ring = sum(tmem.nbytes_of(p.data())
                   for p in net.collect_params().values())
        before = telemetry.ACCOUNTANT.bytes(
            subsystem="train.grad_accum")
        tr.fused_step(loss_a, x, y)
        tr.fused_step(loss_b, x, y)
        after = telemetry.ACCOUNTANT.bytes(subsystem="train.grad_accum")
        assert after - before == 2 * ring, (after, before, ring)
        for fs in list(tr._fused_steps.values()):
            fs.release_accounting()
            fs.release_accounting()    # idempotent
        assert telemetry.ACCOUNTANT.bytes(
            subsystem="train.grad_accum") == before
        # the trainer-level release retires params/opt-state entries
        # too (the __del__ path for discarded trainers)
        assert telemetry.ACCOUNTANT.bytes(
            subsystem="train.params", key=tr._mem_label) > 0
        tr.release_accounting()
        assert telemetry.ACCOUNTANT.bytes(
            subsystem="train.params", key=tr._mem_label) == 0
        assert telemetry.ACCOUNTANT.bytes(
            subsystem="train.opt_states", key=tr._mem_label) == 0

    def test_initial_pool_over_budget_raises(self, tiny_gpt):
        from mxnet_tpu.serve import DecodeServer

        with pytest.raises(MXNetError, match="initial pool"):
            DecodeServer(tiny_gpt, max_total_len=24, pool_sizes=(1,),
                         hbm_budget=16, autostart=False)

    def test_admission_wave_clamped_to_budget(self, tiny_gpt):
        """A burst whose big (A=2) wave bucket's prefill scratch would
        overflow the budget is not refused — it admits in smaller
        waves the budget CAN hold (2 dispatches at A=1) and every
        request still serves."""
        from mxnet_tpu.serve import DecodeServer

        pool1 = _pool1_bytes(tiny_gpt)
        # pool(2 slots)=2x + A=1 scratch=1x fits; the A=2 bucket's 2x
        # scratch (total 4x) does not — so the wave must clamp to 1
        srv = DecodeServer(tiny_gpt, max_total_len=24, pool_sizes=(2,),
                           hbm_budget=int(pool1 * 3) + 100,
                           autostart=False)
        try:
            a = srv.submit(onp.array([1, 2, 3]), max_new_tokens=4)
            b = srv.submit(onp.array([4, 5, 6]), max_new_tokens=4)
            while srv.pump():
                pass
            assert len(a.tokens(30)) == 4 and len(b.tokens(30)) == 4
            assert srv.counters["admit_dispatches"] == 2
        finally:
            srv.close(drain=False)

    def test_admission_unserveable_after_growth_raises(self, tiny_gpt):
        """When even the SMALLEST wave bucket's scratch no longer fits
        next to the (grown) pool, admission refuses cleanly — before
        the wave touches the slot table, so nothing is stranded."""
        from mxnet_tpu.serve import DecodeServer

        pool1 = _pool1_bytes(tiny_gpt)
        # min bucket A=2: constructor check pool(1)+scratch(2)=3x fits
        # the 3.5x budget and growth's transient peak (3x) fits — but
        # the grown pool(2)+scratch(2)=4x does not
        srv = DecodeServer(tiny_gpt, max_total_len=24,
                           pool_sizes=(1, 2), admit_sizes=(2,),
                           hbm_budget=int(pool1 * 3.5),
                           autostart=False)
        try:
            srv.submit(onp.array([1, 2, 3]), max_new_tokens=4)
            srv.submit(onp.array([4, 5, 6]), max_new_tokens=4)
            with pytest.raises(MXNetError, match="admission wave"):
                while srv.pump():
                    pass
            st = srv.stats()
            assert st["in_flight"] == 0 and st["pending"] == 2, st
        finally:
            srv.close(drain=False)

    def test_budget_below_minimum_usable_fails_at_construction(
            self, tiny_gpt):
        """A budget the resident pool fits but the smallest admission
        scratch does not would fail EVERY request — refused at
        construction, naming the scratch."""
        from mxnet_tpu.serve import DecodeServer

        pool1 = _pool1_bytes(tiny_gpt)
        with pytest.raises(MXNetError,
                           match=r"smallest admission wave"):
            DecodeServer(tiny_gpt, max_total_len=24, pool_sizes=(1,),
                         hbm_budget=pool1 + 100, autostart=False)

    def test_env_budget_parsed(self, monkeypatch, tiny_gpt):
        from mxnet_tpu.serve import DecodeServer

        monkeypatch.setenv("MXNET_SERVE_HBM_BUDGET", "64M")
        srv = DecodeServer(tiny_gpt, max_total_len=24, pool_sizes=(1,),
                           autostart=False)
        assert srv.hbm_budget == 64 << 20
        srv.close()
        monkeypatch.setenv("MXNET_SERVE_HBM_BUDGET", "plenty")
        with pytest.raises(MXNetError, match="MXNET_SERVE_HBM_BUDGET"):
            DecodeServer(tiny_gpt, max_total_len=24, pool_sizes=(1,),
                         autostart=False)

    def test_kwarg_budget_accepts_suffix(self, tiny_gpt):
        from mxnet_tpu.serve import DecodeServer

        srv = DecodeServer(tiny_gpt, max_total_len=24, pool_sizes=(1,),
                           hbm_budget="1G", autostart=False)
        assert srv.hbm_budget == 1 << 30
        # within budget: serving works end to end
        s = srv.submit(onp.array([5, 6]), max_new_tokens=3)
        while srv.pump():
            pass
        assert len(s.tokens(30)) == 3
        srv.close()
        assert telemetry.ACCOUNTANT.bytes(
            subsystem="serve.kv_pool", key=srv.telemetry_label) == 0


# --------------------------------------------------------------------- #
# satellite: stats()/histogram behavior on fresh & empty state
# --------------------------------------------------------------------- #

class TestStatsAudit:
    def test_fresh_server_stats_sensible_zeros(self, tiny_gpt):
        from mxnet_tpu.serve import DecodeServer

        srv = DecodeServer(tiny_gpt, max_total_len=24, pool_sizes=(1,),
                           autostart=False)
        st = srv.stats()
        assert st["steps"] == 0 and st["occupancy"] == 0.0
        assert st["pending"] == 0 and st["in_flight"] == 0
        assert st["pool_bytes"] > 0 and st["hbm_budget"] is None
        for hist in ("ttft", "token_gap", "queue_wait"):
            assert st[hist]["count"] == 0
            assert st[hist]["p50"] is None
            assert st[hist]["mean"] is None
        assert all(v == 0 for v in st["counters"].values())
        srv.close()
        # stats after close: no crash, pool actually RELEASED (state
        # refs dropped, so the allocator agrees with the zeroed gauge)
        st2 = srv.stats()
        assert st2["in_flight"] == 0 and st2["pool_bytes"] == 0
        assert srv._state is None

    def test_sync_mode_pool_bytes_zero(self, monkeypatch, tiny_gpt):
        from mxnet_tpu.serve import DecodeServer

        monkeypatch.setenv("MXNET_SERVE_SYNC", "1")
        srv = DecodeServer(tiny_gpt, max_total_len=24, autostart=False)
        st = srv.stats()
        assert st["sync_mode"] and st["pool_bytes"] == 0
        s = srv.submit(onp.array([1, 2]), max_new_tokens=2)
        srv.pump()
        assert len(s.tokens(30)) == 2
        srv.close()

    def test_sync_mode_budget_warns_inert(self, monkeypatch, tiny_gpt):
        """A configured hbm_budget has nothing to meter on the
        kv_generate fallback — the constructor says so instead of
        silently carrying an unenforced limit."""
        from mxnet_tpu.serve import DecodeServer

        monkeypatch.setenv("MXNET_SERVE_SYNC", "1")
        with pytest.warns(UserWarning,
                          match="NOT enforced in sync mode"):
            srv = DecodeServer(tiny_gpt, max_total_len=24,
                               hbm_budget="1G", autostart=False)
        srv.close()

    def test_empty_histogram_full_surface(self):
        h = telemetry.histogram("t_mem_empty_hist")
        assert h.quantile(0.9) is None
        s = h.summary()
        assert s["count"] == 0 and s["sum"] == 0.0
        assert s["min"] is None and s["max"] is None
        assert s["p50"] is None and s["p99"] is None
        # an empty histogram renders (all-zero buckets), no crash
        text = telemetry.render_prometheus()
        assert "t_mem_empty_hist_count 0" in text


# --------------------------------------------------------------------- #
# satellite: the MXNET_TELEMETRY / MXNET_TELEMETRY_MEM hatches
# --------------------------------------------------------------------- #

class TestHatches:
    def test_telemetry_off_serve_uninstrumented(self, monkeypatch,
                                                tiny_gpt):
        """``MXNET_TELEMETRY=0``: the serve programs are plain jitted
        fns (no compile-watch wrapper), no events are emitted, and the
        served stream still reproduces ``kv_generate`` — the
        uninstrumented path is dispatch-identical."""
        from mxnet_tpu.models import kv_generate
        from mxnet_tpu.serve import DecodeServer
        from mxnet_tpu.telemetry.compile import _CompileWatch

        ref = list(kv_generate(tiny_gpt, onp.array([[7, 8, 9]]),
                               max_new_tokens=4,
                               temperature=0.0)[0, 3:])
        monkeypatch.setenv("MXNET_TELEMETRY", "0")
        before = len(telemetry.events())
        srv = DecodeServer(tiny_gpt, max_total_len=24, pool_sizes=(1,),
                           autostart=False)
        s = srv.submit(onp.array([7, 8, 9]), max_new_tokens=4)
        while srv.pump():
            pass
        assert s.tokens(30) == ref
        assert not isinstance(srv._progs.step_fn(), _CompileWatch)
        assert srv.counters["step_dispatches"] >= 1
        assert len(telemetry.events()) == before    # nothing emitted
        srv.close()

    def test_mem_off_serve_no_fields(self, monkeypatch, tiny_gpt):
        """``MXNET_TELEMETRY_MEM=0`` (the default): serve compile
        events carry no ``mem_*`` fields and no extra AOT compile
        happens — the PR-9 event schema is unchanged."""
        from mxnet_tpu.serve import DecodeServer

        monkeypatch.setenv("MXNET_TELEMETRY_MEM", "0")
        srv = DecodeServer(tiny_gpt, max_total_len=24, pool_sizes=(1,),
                           autostart=False)
        s = srv.submit(onp.array([3, 4]), max_new_tokens=3)
        while srv.pump():
            pass
        s.tokens(30)
        evs = [e for e in telemetry.events("compile")
               if e.get("server") == srv.telemetry_label]
        assert evs, "serve compile events missing"
        assert not any(k.startswith("mem_") for e in evs for k in e)
        srv.close()


# --------------------------------------------------------------------- #
# tools/memory_report.py
# --------------------------------------------------------------------- #

def _mem_stream(pool_bytes=4096, budget=None):
    cfg = {"ts": 1.0, "kind": "serve_config", "server": "m0",
           "pool_sizes": [2], "admit_sizes": [1, 2],
           "prefill_buckets": [8], "max_total_len": 32,
           "sync_mode": False, "hbm_budget": budget,
           "pool_bytes": pool_bytes}
    return [
        cfg,
        {"ts": 1.1, "kind": "compile", "site": "serve.step",
         "server": "m0", "pool": 2, "wall_s": 0.5, "cache_size": 1,
         "mem_arg_bytes": 1000, "mem_out_bytes": 500,
         "mem_temp_bytes": 2048, "mem_code_bytes": 0,
         "mem_alias_bytes": 0, "mem_peak_bytes": 3548},
        {"ts": 1.2, "kind": "device_memory", "subsystem":
         "serve.kv_pool", "key": "m0", "device": "cpu:0",
         "bytes": pool_bytes, "subsystem_bytes": pool_bytes},
        {"ts": 1.3, "kind": "device_memory", "subsystem":
         "train.params", "key": "trainer0", "device": "cpu:0",
         "bytes": 800, "subsystem_bytes": 800},
        {"ts": 2.0, "kind": "serve_stats", "server": "m0", "steps": 4,
         "occupancy": 0.5, "pool_bytes": pool_bytes,
         "counters": {"step_dispatches": 4, "admit_dispatches": 1,
                      "sync_requests": 0, "pool_grows": 0}},
    ]


class TestMemoryReport:
    def test_budget_table_and_fit(self):
        sys.path.insert(0, "/root/repo")
        from tools import memory_report

        events = _mem_stream()
        comp = memory_report.compile_memory(events)
        assert comp[0]["site"] == "serve.step"
        assert comp[0]["temp_bytes"] == 2048
        subs = memory_report.subsystem_memory(events)
        assert subs["serve.kv_pool"]["cpu:0"] == 4096
        table = memory_report.budget_table(events)
        total = table[-1]
        assert total["kind"] == "total"
        assert total["bytes"] == 4096 + 800 + 2048
        good = memory_report.fit_verdict(events, 1 << 20)
        assert good["fits"] and good["measured"]
        assert good["headroom_bytes"] > 0
        bad = memory_report.fit_verdict(events, 1024)
        assert not bad["fits"] and bad["headroom_bytes"] < 0
        # an UNMEASURED recording must never pass a fit gate: 0 bytes
        # of telemetry is "don't know", not "fits"
        empty = memory_report.fit_verdict(
            [{"ts": 1.0, "kind": "bench"}], 1 << 30)
        assert not empty["measured"] and not empty["fits"]
        # accountant-only streams (recorded without MXNET_TELEMETRY_
        # MEM=1) are ALSO unmeasured: resident rows without any
        # per-executable scratch cannot answer "does a step fit"
        acct_only = memory_report.fit_verdict(
            [e for e in events if e["kind"] != "compile"], 1 << 30)
        assert not acct_only["measured"] and not acct_only["fits"]
        # the fit math uses PEAK bytes: a pool dropped to 0 at close
        # still counts (it had to fit while live); the last-known
        # display view reports the 0
        dropped = events + [
            {"ts": 3.0, "kind": "device_memory",
             "subsystem": "serve.kv_pool", "key": "m0",
             "device": "cpu:0", "bytes": 0, "subsystem_bytes": 0}]
        t2 = memory_report.budget_table(dropped)
        assert t2[-1]["bytes"] == 4096 + 800 + 2048, t2
        assert memory_report.subsystem_memory(
            dropped)["serve.kv_pool"]["cpu:0"] == 0
        text = memory_report.render(events)
        assert "serve.kv_pool" in text and "TOTAL" in text

    def test_cli_fit_exit_codes(self, tmp_path):
        path = str(tmp_path / "mem.jsonl")
        with open(path, "w") as fh:
            for e in _mem_stream():
                fh.write(json.dumps(e) + "\n")
        ok = subprocess.run(
            [sys.executable, "tools/memory_report.py", path,
             "--hbm", "16G"],
            capture_output=True, text=True, cwd="/root/repo",
            timeout=60)
        assert ok.returncode == 0, ok.stderr
        assert "FITS" in ok.stdout
        over = subprocess.run(
            [sys.executable, "tools/memory_report.py", path,
             "--hbm", "1k"],
            capture_output=True, text=True, cwd="/root/repo",
            timeout=60)
        assert over.returncode == 1
        assert "DOES NOT FIT" in over.stdout
        js = subprocess.run(
            [sys.executable, "tools/memory_report.py", path, "--json"],
            capture_output=True, text=True, cwd="/root/repo",
            timeout=60)
        assert js.returncode == 0
        parsed = json.loads(js.stdout)
        assert parsed["budget"][-1]["kind"] == "total"
        # malformed --hbm is a clean argparse error, not a traceback
        bad = subprocess.run(
            [sys.executable, "tools/memory_report.py", path,
             "--hbm", "16GB"],
            capture_output=True, text=True, cwd="/root/repo",
            timeout=60)
        assert bad.returncode == 2
        assert "--hbm" in bad.stderr and "Traceback" not in bad.stderr
        # a recording with no memory telemetry fails the gate
        empty_path = str(tmp_path / "empty.jsonl")
        with open(empty_path, "w") as fh:
            fh.write(json.dumps({"ts": 1.0, "kind": "bench"}) + "\n")
        unmeasured = subprocess.run(
            [sys.executable, "tools/memory_report.py", empty_path,
             "--hbm", "16G"],
            capture_output=True, text=True, cwd="/root/repo",
            timeout=60)
        assert unmeasured.returncode == 1
        assert "NO MEMORY TELEMETRY" in unmeasured.stdout

    def test_memory_report_smoke(self, tmp_path):
        """``tools/memory_report.py --smoke`` records a tiny train +
        serve workload under ``MXNET_TELEMETRY_MEM=1`` and asserts the
        whole pipeline (the ISSUE 10 tier-1 gate)."""
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("MXNET_TELEMETRY_JSONL", None)
        r = subprocess.run(
            [sys.executable, "tools/memory_report.py", "--smoke"],
            capture_output=True, text=True, cwd="/root/repo", env=env,
            timeout=540)
        assert r.returncode == 0, r.stderr[-2000:]
        assert "memory report smoke OK" in r.stdout
        assert "gluon.fused_step" in r.stdout
        assert "serve.step" in r.stdout


class TestCheckServeBudget:
    """telemetry_report --check-serve: pool bytes vs configured
    budget, from the recording alone."""

    def test_within_budget_passes(self):
        from tools import telemetry_report

        events = _mem_stream(pool_bytes=4096, budget=8192)
        assert telemetry_report.check_serve(events) == []

    def test_over_budget_flagged(self):
        from tools import telemetry_report

        events = _mem_stream(pool_bytes=4096, budget=1000)
        fails = telemetry_report.check_serve(events)
        assert any("hbm_budget" in f for f in fails)

    def test_no_budget_not_checked(self):
        from tools import telemetry_report

        events = _mem_stream(pool_bytes=4096, budget=None)
        assert telemetry_report.check_serve(events) == []

    def test_pages_over_capacity_flagged(self):
        """ISSUE 16: serve_stats carrying the paged-pool fields must
        report pages_in_use <= pages_total; pre-paging streams lack
        the fields and skip the check (the no-budget stream above)."""
        from tools import telemetry_report

        events = _mem_stream(pool_bytes=4096)
        stats = next(e for e in events if e["kind"] == "serve_stats")
        stats["pages_total"] = 8
        stats["pages_in_use"] = 3
        assert telemetry_report.check_serve(events) == []
        stats["pages_in_use"] = 9
        fails = telemetry_report.check_serve(events)
        assert any("pool capacity" in f for f in fails), fails

    @pytest.mark.parametrize("kv_dtype,page_bytes",
                             [("native", 512), ("int8", 132)])
    def test_pool_bytes_vs_priced_pages(self, kv_dtype, page_bytes):
        """ISSUE 18: serve_stats carrying the dtype-priced page fields
        must satisfy ``pages_total * page_bytes <= pool_bytes`` within
        the slot-state slack — the identity that catches a pricer that
        forgot an int8 pool's scales (or priced codes at f32).
        Recordings from before the fields existed skip the check."""
        from tools import telemetry_report

        total = 8
        events = _mem_stream(pool_bytes=total * page_bytes + 58)
        stats = next(e for e in events if e["kind"] == "serve_stats")
        stats.update(pages_total=total, pages_in_use=0, num_slots=2,
                     kv_dtype=kv_dtype, page_bytes=page_bytes)
        assert telemetry_report.check_serve(events) == []
        # a pool priced at the WRONG dtype (4x codes) is flagged
        stats["pool_bytes"] = total * page_bytes * 4
        events[0]["pool_bytes"] = stats["pool_bytes"]
        fails = telemetry_report.check_serve(events)
        assert any("priced page bytes" in f and kv_dtype in f
                   for f in fails), fails
        # a torn-down pool (pool_bytes 0) has nothing resident: skip
        stats["pool_bytes"] = 0
        events[0]["pool_bytes"] = 0
        assert telemetry_report.check_serve(events) == []

    def test_pool_bytes_count_the_slot_table(self):
        """ISSUE 33: a model with state under the slot table reports
        ``state_bytes_per_slot``; the identity counts it a slot, and a
        pool that forgot it (or counted it twice) is flagged."""
        from tools import telemetry_report

        total, page_bytes, per_slot = 8, 512, 17920
        events = _mem_stream(
            pool_bytes=total * page_bytes + 2 * (29 + per_slot))
        stats = next(e for e in events if e["kind"] == "serve_stats")
        stats.update(pages_total=total, pages_in_use=0, num_slots=2,
                     kv_dtype="native", page_bytes=page_bytes,
                     state_bytes_per_slot=per_slot)
        assert telemetry_report.check_serve(events) == []
        stats["state_bytes_per_slot"] = 0
        assert any("priced page bytes" in f
                   for f in telemetry_report.check_serve(events))
