"""Stacked-layer scan decode (models/decoding.py stacked_token +
stack_decode_weights): ONE lax.scan over the layer axis must reproduce
the per-layer unrolled step token-for-token (greedy AND sampled, GPT and
Llama/GQA), collapse the compiled step's HLO op count under the ROADMAP
ceiling, and keep the whole token loop on one executable; the choice
between the two steps (``stacked_decode_supported``, ``decode_mode``) is
held here too."""
import os
import subprocess
import sys
from types import SimpleNamespace

import numpy as onp
import pytest

import mxnet_tpu as mx


def _gpt(layers=2, units=32, heads=4, hidden=64, vocab=97, init=0.02,
         max_length=64):
    from mxnet_tpu.models import GPT, GPTConfig
    mx.random.seed(0)
    net = GPT(GPTConfig(vocab_size=vocab, max_length=max_length,
                        num_layers=layers, units=units, num_heads=heads,
                        hidden_size=hidden))
    net.initialize(mx.init.Normal(init))
    return net


def _llama():
    from mxnet_tpu.models import llama_tiny
    mx.random.seed(0)
    net, cfg = llama_tiny()
    net.initialize(mx.init.Normal(0.02))
    return net, cfg


class TestStackedParity:
    def test_gpt_greedy_matches_unrolled_and_full_recompute(self):
        from mxnet_tpu.models import kv_generate
        net = _gpt()
        prompt = onp.random.RandomState(0).randint(0, 97, (2, 5))
        full = net.generate(prompt, max_new_tokens=12, temperature=0.0)
        st = kv_generate(net, prompt, max_new_tokens=12, temperature=0.0,
                         stacked="on")
        un = kv_generate(net, prompt, max_new_tokens=12, temperature=0.0,
                         stacked="off")
        onp.testing.assert_array_equal(st, un)
        onp.testing.assert_array_equal(st, full)

    def test_gpt_sampled_parity(self):
        """Sampled decode draws through the identical fold_in/categorical
        keys, so stacked and unrolled must emit the same stream."""
        from mxnet_tpu.models import kv_generate
        net = _gpt()
        prompt = onp.random.RandomState(1).randint(0, 97, (2, 4))
        kw = dict(max_new_tokens=8, temperature=0.7, top_k=5, seed=3)
        onp.testing.assert_array_equal(
            kv_generate(net, prompt, stacked="on", **kw),
            kv_generate(net, prompt, stacked="off", **kw))

    def test_gpt_scan_prefill_parity(self):
        from mxnet_tpu.models import kv_generate
        net = _gpt()
        prompt = onp.random.RandomState(2).randint(0, 97, (1, 6))
        for kw in (dict(temperature=0.0),
                   dict(temperature=0.8, top_k=4, seed=7)):
            onp.testing.assert_array_equal(
                kv_generate(net, prompt, max_new_tokens=7,
                            prefill="scan", stacked="on", **kw),
                kv_generate(net, prompt, max_new_tokens=7,
                            prefill="scan", stacked="off", **kw))

    def test_llama_gqa_greedy_and_sampled_parity(self):
        """Llama family through the stack: RMSNorm, per-step RoPE,
        grouped-query KV cache (llama_tiny is GQA: KV < H), SwiGLU."""
        from mxnet_tpu.models import kv_generate
        net, cfg = _llama()
        assert cfg.num_kv_heads < cfg.num_heads
        prompt = onp.random.RandomState(6).randint(0, cfg.vocab_size,
                                                   (2, 4))
        full = net.generate(prompt, max_new_tokens=10, temperature=0.0)
        st = kv_generate(net, prompt, max_new_tokens=10, temperature=0.0,
                         stacked="on")
        un = kv_generate(net, prompt, max_new_tokens=10, temperature=0.0,
                         stacked="off")
        onp.testing.assert_array_equal(st, un)
        onp.testing.assert_array_equal(st, full)
        kw = dict(max_new_tokens=6, temperature=0.9, top_k=7, seed=11)
        onp.testing.assert_array_equal(
            kv_generate(net, prompt, stacked="on", **kw),
            kv_generate(net, prompt, stacked="off", **kw))

    def test_weight_update_invalidates_stack(self):
        """The stacked arrays must restack after a weight rebind (the
        pinned-source discipline shared with the q8 caches) — and the
        already-compiled program must pick up the new values through
        its traced weight operands."""
        from mxnet_tpu.models import kv_generate
        net = _gpt(init=0.15)
        prompt = onp.random.RandomState(3).randint(0, 97, (1, 4))
        out1 = kv_generate(net, prompt, max_new_tokens=4,
                           temperature=0.0, stacked="on")
        w = net.blocks[0].attn.qkv.weight
        w.set_data(mx.nd.from_jax(-w.data()._data))
        out2 = kv_generate(net, prompt, max_new_tokens=4,
                           temperature=0.0, stacked="on")
        ref2 = kv_generate(net, prompt, max_new_tokens=4,
                           temperature=0.0, stacked="off")
        onp.testing.assert_array_equal(out2, ref2)
        assert (out1 != out2).any()


class TestStackedGating:
    def test_default_mode_is_stacked(self):
        from mxnet_tpu.models import decode_mode
        net = _gpt()
        assert decode_mode(net) == "stacked"
        lnet, _ = _llama()
        assert decode_mode(lnet) == "stacked"

    def test_int8_runs_stacked_where_supported(self):
        """The q8 stream rides the stacked scan by default (ROADMAP PR 5
        remainder); the unrolled fallback still covers it when the stack
        gate rejects the model."""
        from mxnet_tpu.models import decode_mode
        net = _gpt()
        assert decode_mode(net, weights="int8") == "stacked"
        assert decode_mode(net, weights="int8", stacked="off") \
            == "unrolled"
        net.blocks[1].ln1._eps = 1e-3          # non-uniform stack
        assert decode_mode(net, weights="int8") == "unrolled"

    def test_invalid_args_raise_even_with_zero_new_tokens(self):
        """Argument validation runs ahead of the max_new_tokens<=0 early
        return (post-review regression: a typo must fail fast in 0-token
        smoke calls, as it did before the engine refactor)."""
        from mxnet_tpu.models import kv_generate
        net = _gpt()
        prompt = onp.zeros((1, 4), onp.int32)
        for bad in (dict(weights="int4"), dict(prefill="batch"),
                    dict(stacked="sideways")):
            with pytest.raises(ValueError):
                kv_generate(net, prompt, max_new_tokens=0, **bad)

    def test_nonstandard_ffn_variant_decodes_unrolled(self):
        """A GPT-family variant whose FFN lacks the fc1/act structure
        must keep decoding through the unrolled generality fallback
        (post-review regression: the engine's act-type probe must not
        crash on it — one_token calls the whole ffn Block and never
        needs fc1)."""
        from mxnet_tpu.gluon.block import HybridBlock
        from mxnet_tpu.gluon.nn.basic_layers import Dense
        from mxnet_tpu.models import decode_mode, kv_generate

        class _WeirdFFN(HybridBlock):
            def __init__(self, units, hidden, **kw):
                super().__init__(**kw)
                with self.name_scope():
                    self.a = Dense(hidden, flatten=False, in_units=units,
                                   activation="tanh", prefix="a_")
                    self.b = Dense(units, flatten=False, in_units=hidden,
                                   prefix="b_")

            def hybrid_forward(self, F, x):
                return self.b(self.a(x))

        net = _gpt()
        for i, blk in enumerate(net.blocks):
            blk.ffn = _WeirdFFN(32, 64, prefix=f"wf{i}_")
        net.initialize(mx.init.Normal(0.02))
        assert decode_mode(net) == "unrolled"
        prompt = onp.random.RandomState(8).randint(0, 97, (1, 4))
        out = kv_generate(net, prompt, max_new_tokens=5, temperature=0.0)
        ref = net.generate(prompt, max_new_tokens=5, temperature=0.0)
        onp.testing.assert_array_equal(out, ref)

    def test_non_uniform_stack_falls_back(self):
        """A layer stack with differing norm eps cannot share one scan
        body — the gate must reject it and kv_generate must fall back to
        the unrolled path (which derives math from the model's own
        sublayers) with correct output."""
        from mxnet_tpu.models import decode_mode, kv_generate
        from mxnet_tpu.models.decoding import stacked_decode_supported
        net = _gpt()
        net.blocks[1].ln1._eps = 1e-3
        assert not stacked_decode_supported(net)
        assert decode_mode(net) == "unrolled"
        prompt = onp.random.RandomState(5).randint(0, 97, (1, 4))
        out = kv_generate(net, prompt, max_new_tokens=4, temperature=0.0)
        ref = net.generate(prompt, max_new_tokens=4, temperature=0.0)
        onp.testing.assert_array_equal(out, ref)

    def test_stack_export_shapes(self):
        """stacked_decode_weights: every slot is (NL, ...) with the
        per-layer array's shape behind it; GQA k/v rows are KV*D wide."""
        net, cfg = _llama()
        sw = net.stacked_decode_weights()
        NL = cfg.num_layers
        d = cfg.units // cfg.num_heads
        assert sw["q_w"].shape == (NL, cfg.units, cfg.units)
        assert sw["k_w"].shape == (NL, cfg.num_kv_heads * d, cfg.units)
        assert sw["rms1_g"].shape == (NL, cfg.units)
        gnet = _gpt(layers=3, units=32, hidden=64)
        gsw = gnet.stacked_decode_weights()
        assert gsw["qkv_w"].shape == (3, 96, 32)
        assert gsw["fc1_b"].shape == (3, 64)


def _differing_eps():
    net = _gpt()
    net.blocks[1].ln1._eps = 1e-3
    return net


def _differing_activation():
    net = _gpt()
    net.blocks[1].ffn.fc1.act._act_type = "relu"
    return net


def _block_without_export():
    net = _gpt()
    return SimpleNamespace(blocks=[net.blocks[0], object()],
                           stacked_decode_weights=net.stacked_decode_weights)


def _unmaterialised():
    from mxnet_tpu.models import GPT, GPTConfig
    return GPT(GPTConfig(vocab_size=97, max_length=64, num_layers=2,
                         units=32, num_heads=4, hidden_size=64))


def _differing_slot_shapes():
    net = _gpt()
    return SimpleNamespace(
        blocks=[net.blocks[0], _gpt(hidden=128).blocks[0]],
        stacked_decode_weights=net.stacked_decode_weights)


class TestStepChoice:
    """The one fork that remains: which of the two steps a model gets.
    Pure Python over block attributes — no decode is compiled here."""

    @pytest.mark.parametrize("build,expect", [
        (_gpt, True),
        (lambda: _llama()[0], True),
        (_differing_eps, False),
        (_differing_activation, False),
        (_block_without_export, False),
        (_unmaterialised, False),
        (_differing_slot_shapes, False),
    ], ids=["uniform_gpt", "uniform_llama_gqa", "differing_norm_eps",
            "differing_fc1_activation", "block_without_export",
            "unmaterialised_parameters", "differing_slot_shapes"])
    def test_stacked_decode_supported(self, build, expect):
        from mxnet_tpu.models.decoding import stacked_decode_supported
        assert stacked_decode_supported(build()) is expect

    @pytest.mark.parametrize("stacked,expect", [
        ("auto", "stacked"), ("on", "stacked"), ("off", "unrolled")])
    @pytest.mark.parametrize("family", ["gpt", "llama"])
    def test_decode_mode(self, family, stacked, expect):
        from mxnet_tpu.models import decode_mode
        net = _gpt() if family == "gpt" else _llama()[0]
        for weights in ("native", "int8"):
            assert decode_mode(net, weights=weights,
                               stacked=stacked) == expect

    def test_stacked_on_raises_on_a_non_uniform_stack(self):
        from mxnet_tpu.base import MXNetError
        from mxnet_tpu.models import decode_mode
        net = _differing_eps()
        assert decode_mode(net) == "unrolled"
        with pytest.raises(MXNetError, match="stacked_decode_supported"):
            decode_mode(net, stacked="on")
        with pytest.raises(ValueError, match="stacked"):
            decode_mode(net, stacked="sideways")

    def test_take_operands_hands_over_three_and_keeps_none(self):
        """(parameter values, q8, stacked weights): the engine's cached
        program closes over it, so it must not hold what it handed on."""
        from mxnet_tpu.models.decoding import _DecodeEngine
        eng = _DecodeEngine(_gpt(), 1, 1, 8, 0.0, 0, "batched", "int8",
                            "auto")
        param_vals, q8, sw = eng.take_operands()
        assert len(param_vals) == len(eng.params) > 0
        assert set(q8) == {"head"} and "qkv" in sw
        assert eng.param_vals is None and eng.q8v is None \
            and eng.sw is None


class TestInt8StackedParity:
    """The q8 weight stream through the stacked scan (stacked codes ride
    the xs through q8_matvec) must match the per-layer unrolled q8 path
    token-for-token — same codes, same kernel, same cast order."""

    def test_gpt_int8_stacked_matches_unrolled(self):
        from mxnet_tpu.models import kv_generate
        net = _gpt()
        prompt = onp.random.RandomState(10).randint(0, 97, (2, 5))
        kw = dict(max_new_tokens=10, temperature=0.0, weights="int8")
        onp.testing.assert_array_equal(
            kv_generate(net, prompt, stacked="on", **kw),
            kv_generate(net, prompt, stacked="off", **kw))
        kw = dict(max_new_tokens=6, temperature=0.8, top_k=5, seed=13,
                  weights="int8")
        onp.testing.assert_array_equal(
            kv_generate(net, prompt, stacked="on", **kw),
            kv_generate(net, prompt, stacked="off", **kw))

    def test_llama_gqa_int8_stacked_matches_unrolled(self):
        from mxnet_tpu.models import kv_generate
        net, cfg = _llama()
        assert cfg.num_kv_heads < cfg.num_heads
        prompt = onp.random.RandomState(11).randint(0, cfg.vocab_size,
                                                    (2, 4))
        kw = dict(max_new_tokens=8, temperature=0.0, weights="int8")
        onp.testing.assert_array_equal(
            kv_generate(net, prompt, stacked="on", **kw),
            kv_generate(net, prompt, stacked="off", **kw))

    def test_int8_stack_requantizes_on_rebind(self):
        """A weight rebind must invalidate the stacked q8 codes (the
        pinned-source discipline shared with the per-layer q8 cache)."""
        from mxnet_tpu.models import kv_generate
        net = _gpt(init=0.15)
        prompt = onp.random.RandomState(12).randint(0, 97, (1, 4))
        kw = dict(max_new_tokens=4, temperature=0.0, weights="int8")
        out1 = kv_generate(net, prompt, stacked="on", **kw)
        w = net.blocks[0].attn.qkv.weight
        w.set_data(mx.nd.from_jax(-w.data()._data))
        out2 = kv_generate(net, prompt, stacked="on", **kw)
        ref2 = kv_generate(net, prompt, stacked="off", **kw)
        onp.testing.assert_array_equal(out2, ref2)
        assert (out1 != out2).any()

    def test_int8_op_count_collapse_and_layer_invariance(self):
        """The int8 stacked step carries one layer-body of HLO too:
        deepening the stack must not grow the op count, and the stacked
        count stays under the unrolled one."""
        from mxnet_tpu import profiler_xla
        from mxnet_tpu.models import decode_step_program
        counts = {}
        for layers in (2, 4):
            net = _gpt(layers=layers)
            for smode in ("on", "off"):
                fn, args = decode_step_program(net, batch=1, total=16,
                                               weights="int8",
                                               stacked=smode)
                counts[(smode, layers)] = profiler_xla.hlo_op_count(
                    fn, *args)
        assert counts[("on", 4)] == counts[("on", 2)]
        assert counts[("off", 4)] > counts[("off", 2)]
        assert counts[("on", 2)] < counts[("off", 2)]


class TestOpCountCeiling:
    def test_tiny_geometry_collapse(self):
        """Stacked step carries ~one layer-body of HLO: deepening the
        stack must NOT grow the op count (the unrolled step grows
        linearly)."""
        from mxnet_tpu import profiler_xla
        from mxnet_tpu.models import decode_step_program
        counts = {}
        for layers in (2, 4):
            net = _gpt(layers=layers)
            fn, args = decode_step_program(net, batch=1, total=16)
            counts[("stacked", layers)] = profiler_xla.hlo_op_count(
                fn, *args)
            fn, args = decode_step_program(net, batch=1, total=16,
                                           stacked="off")
            counts[("unrolled", layers)] = profiler_xla.hlo_op_count(
                fn, *args)
        assert counts[("stacked", 4)] == counts[("stacked", 2)]
        assert counts[("unrolled", 4)] > counts[("unrolled", 2)]
        assert counts[("stacked", 2)] < counts[("unrolled", 2)]

    def test_gpt2_small_geometry_under_ceiling(self):
        """The acceptance bar: GPT-2-small geometry (12L/768U/12H/3072F)
        compiled stacked decode step stays ≤ 60 HLO ops on CPU (vs ~230
        executed device ops measured for the unrolled scan step in the
        r4 TPU profile; the unrolled step lowers to ~450 static ops on
        CPU), with greedy outputs token-identical to the unrolled
        path."""
        from mxnet_tpu import profiler_xla
        from mxnet_tpu.models import decode_step_program, kv_generate
        net = _gpt(layers=12, units=768, heads=12, hidden=3072,
                   vocab=2048, init=0.05)
        fn, args = decode_step_program(net, batch=1, total=48)
        n = profiler_xla.hlo_op_count(fn, *args)
        assert n <= 60, f"stacked decode step op count {n} > ceiling 60"
        prompt = onp.random.RandomState(0).randint(0, 2048, (1, 4))
        st = kv_generate(net, prompt, max_new_tokens=6, temperature=0.0,
                         stacked="on")
        un = kv_generate(net, prompt, max_new_tokens=6, temperature=0.0,
                         stacked="off")
        onp.testing.assert_array_equal(st, un)


class TestRetraceGuard:
    def test_one_executable_across_token_loop(self):
        """The whole decode (prefill + every token) is ONE jit program:
        repeated calls with the same signature reuse one cache entry and
        one compiled executable — no per-token dispatch, no retrace."""
        from mxnet_tpu.models import kv_generate
        net = _gpt()
        prompt = onp.random.RandomState(7).randint(0, 97, (1, 5))
        kv_generate(net, prompt, max_new_tokens=8, temperature=0.0)
        kv_generate(net, prompt, max_new_tokens=8, temperature=0.0)
        cache = net._kv_decode_cache
        assert len(cache) == 1
        (jitted,) = cache.values()
        assert jitted._cache_size() == 1
        # a weight edit must NOT retrace (weights ride as traced args)
        w = net.blocks[0].attn.qkv.weight
        w.set_data(mx.nd.from_jax(-w.data()._data))
        kv_generate(net, prompt, max_new_tokens=8, temperature=0.0)
        assert len(cache) == 1 and jitted._cache_size() == 1


class TestNoWeightPinning:
    def test_rebound_weights_are_freed(self):
        """Train/serve interleave must not leak weight copies: the
        cached decode program's closure (which outlives each call) must
        not pin the first call's weight arrays after a rebind
        (post-review regression — the engine now hands its operand refs
        to the caller and drops them)."""
        import gc
        import weakref

        from mxnet_tpu.models import kv_generate
        net = _gpt()
        prompt = onp.random.RandomState(9).randint(0, 97, (1, 4))
        kv_generate(net, prompt, max_new_tokens=3, temperature=0.0)
        old = net.blocks[0].attn.qkv.weight.data()._data
        ref = weakref.ref(old)
        w = net.blocks[0].attn.qkv.weight
        w.set_data(mx.nd.from_jax(-old))
        del old
        kv_generate(net, prompt, max_new_tokens=3, temperature=0.0)
        gc.collect()
        assert ref() is None, \
            "first-call weight array still pinned after rebind"


class TestStepOpCountSideEffects:
    def test_step_hlo_op_count_does_not_advance_global_rng(self):
        """step_hlo_op_count is a compile-only diagnostic: inserting it
        between training steps must not change the global PRNG stream
        (post-review regression — it previously consumed
        random.next_key())."""
        from mxnet_tpu import gluon, parallel
        from mxnet_tpu import random as mxr
        from mxnet_tpu.gluon import nn
        import jax

        mx.random.seed(0)
        net = nn.Dense(4, in_units=4, flatten=False)
        net.initialize(mx.init.Xavier())
        tr = parallel.SPMDTrainer(
            net, gluon.loss.L2Loss(), "sgd", {"learning_rate": 0.1},
            mesh=parallel.make_mesh({"dp": len(jax.devices())}))
        x = mx.nd.array(onp.random.RandomState(0).rand(8, 4)
                        .astype("float32"))
        y = mx.nd.array(onp.random.RandomState(1).rand(8, 4)
                        .astype("float32"))
        mx.random.seed(7)
        ref = onp.asarray(mxr.next_key())
        mx.random.seed(7)
        assert tr.step_hlo_op_count(x, y) > 0
        got = onp.asarray(mxr.next_key())
        onp.testing.assert_array_equal(got, ref)


class TestDecodeBenchSmoke:
    def test_decode_bench_smoke(self):
        """benchmark/decode_bench.py --smoke: unrolled vs stacked arms +
        ops/step column on a tiny geometry (the tier-1 gate — asserts
        parity and the op-count collapse internally)."""
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        r = subprocess.run(
            [sys.executable, "benchmark/decode_bench.py", "--smoke"],
            capture_output=True, text=True, cwd="/root/repo", env=env,
            timeout=570)
        assert r.returncode == 0, r.stderr[-2000:]
        assert '"mode": "stacked"' in r.stdout
        assert '"ops_per_step"' in r.stdout
        assert "parity OK" in r.stdout
