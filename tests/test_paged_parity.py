"""The paged decode step against the dense one (ISSUE 27): the pool is
``(NL, NPAGES, page, KV·D)``, the step gathers its pages at ``(layer, page
id)`` with the table sentinel CLAMPED in bounds (no zero fill), and the
attention contracts whole ``KV·D`` rows against block-diagonal queries.
What the mask gives weight 0 — a retired lane's sentinel rows, the tail of
a frontier page, a recycled page that still holds its previous tenant's
large values — must not reach a logit: ``pool_token_paged`` is held to
``pool_token`` on dense ``(NL, B, KV, T, D)`` caches with zeros there.
"""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu.gluon.parameter import params_swapped
from mxnet_tpu.models.decoding import (_DecodeEngine, _TRACE_LOCK,
                                       _kv_dequant, _kv_requant)

B, T, PAGE = 3, 32, 4
MAXP = T // PAGE
NPAGES = B * MAXP + 2
STEPS = 6
STALE = 1.0e4       # a previous tenant's values: no live value is near


def _gpt():
    from mxnet_tpu.models import GPT, GPTConfig
    mx.random.seed(0)
    net = GPT(GPTConfig(vocab_size=97, max_length=T, num_layers=2,
                        units=32, num_heads=4, hidden_size=64))
    net.initialize(mx.init.Normal(0.2))
    return net


def _llama():
    from mxnet_tpu.models import llama_tiny
    mx.random.seed(0)
    net, cfg = llama_tiny()
    assert cfg.num_kv_heads < cfg.num_heads        # grouped queries
    net.initialize(mx.init.Normal(0.2))
    return net


def _pages_of(dense, table, fill):
    """Pool array ``(NL, NPAGES, page, KV·D)`` holding the dense cache
    ``(NL, B, KV, T, D)`` through ``table`` ``(B, MAXP)``; every row no
    table entry maps reads ``fill``."""
    NL, _, KV, _, D = dense.shape
    pool = onp.full((NL, NPAGES, PAGE, KV * D), fill, onp.float32)
    rows = dense.transpose(0, 1, 3, 2, 4).reshape(NL, B, MAXP, PAGE, KV * D)
    for b in range(B):
        for j in range(MAXP):
            if table[b, j] < NPAGES:
                pool[:, table[b, j]] = rows[:, b, j]
    return pool


@pytest.mark.parametrize("scenario", ["live", "retired", "recycled"])
@pytest.mark.parametrize("pool_dtype", ["float32", "int8"])
@pytest.mark.parametrize("family", ["gpt_mha", "llama_gqa"])
def test_paged_step_matches_dense_step(family, pool_dtype, scenario):
    net = _gpt() if family == "gpt_mha" else _llama()
    eng = _DecodeEngine(net, B, 1, T, 0.0, 0, "batched", "native", "auto")
    assert eng.mode == "stacked"
    param_vals, q8, sw = eng.take_operands()
    NL, KV, D = eng.NL, eng.KV, eng.D
    rng = onp.random.RandomState(7)
    quant = pool_dtype == "int8"

    # slots at ragged depths; slot 1 is the one that retires
    pos = onp.array([5, 17, 24], onp.int32)
    live = onp.array([True, scenario != "retired", True])
    stale = STALE if scenario == "recycled" else 0.0
    # tables: a slot owns the pages its tokens and its next STEPS tokens
    # need, in shuffled order, the last page of the pool among them (the
    # clamped sentinel reads THAT page); the rest is the sentinel
    order = [NPAGES - 1] + list(rng.permutation(NPAGES - 1))
    table = onp.full((B, MAXP), NPAGES, onp.int32)
    for b in range(B):
        if live[b]:
            for j in range(-(-(pos[b] + STEPS) // PAGE)):
                table[b, j] = order.pop(0)

    # cached tokens: values before pos[b]; beyond it the dense reference
    # holds zeros and the pool holds the previous tenant's values
    kd = rng.randn(NL, B, KV, T, D).astype("float32")
    vd = rng.randn(NL, B, KV, T, D).astype("float32")
    written = (onp.arange(T)[None, :] < pos[:, None]) & live[:, None]
    mask = written[None, :, None, :, None]
    kd, vd = onp.where(mask, kd, 0.0), onp.where(mask, vd, 0.0)
    kp = _pages_of(onp.where(mask, kd, stale), table, stale)
    vp = _pages_of(onp.where(mask, vd, stale), table, stale)
    kp0 = kp
    if quant:
        # the pool holds codes and scales; the dense reference holds what
        # they dequantize to wherever a token was written
        kp = _kv_requant(jnp.asarray(kp), 0.0, KV)
        vp = _kv_requant(jnp.asarray(vp), 0.0, KV)

        def dense_of(pool):
            rows = onp.asarray(_kv_dequant(*pool, jnp.float32))
            out = onp.zeros((NL, B, KV, T, D), onp.float32)
            for b in range(B):
                for j in range(MAXP):
                    if table[b, j] < NPAGES:
                        out[:, b, :, j * PAGE:(j + 1) * PAGE] = \
                            rows[:, table[b, j]].reshape(
                                NL, PAGE, KV, D).transpose(0, 2, 1, 3)
            return onp.where(mask, out, 0.0)

        kd, vd = dense_of(kp), dense_of(vp)
    else:
        kp, vp = jnp.asarray(kp), jnp.asarray(vp)

    def dense_step(tok, pos, ck, cv):
        with _TRACE_LOCK, params_swapped(eng.params, param_vals):
            return eng.pool_token(tok, pos, ck, cv, sw, q8)

    def paged_step(tok, pos, kp, vp, pt):
        with _TRACE_LOCK, params_swapped(eng.params, param_vals):
            return eng.pool_token_paged(tok, pos, kp, vp, pt, PAGE, sw, q8)

    dense_step, paged_step = jax.jit(dense_step), jax.jit(paged_step)
    tok_d = tok_p = jnp.asarray(rng.randint(0, 97, B), jnp.int32)
    ck, cv, pt = jnp.asarray(kd), jnp.asarray(vd), jnp.asarray(table)
    # float32 pools run a greedy stream; an int8 pool rounds each new row
    # as it lands, which the dense reference does not: one step there
    for step in range(1 if quant else STEPS):
        p = jnp.asarray(pos + step)
        lg_d, ck, cv = dense_step(tok_d, p, ck, cv)
        lg_p, kp, vp = paged_step(tok_p, p, kp, vp, pt)
        assert onp.isfinite(onp.asarray(lg_p)).all()
        onp.testing.assert_allclose(onp.asarray(lg_p)[live],
                                    onp.asarray(lg_d)[live],
                                    rtol=2e-4, atol=2e-4)
        tok_d = jnp.argmax(lg_d, axis=-1).astype(jnp.int32)
        tok_p = jnp.argmax(lg_p, axis=-1).astype(jnp.int32)
        onp.testing.assert_array_equal(onp.asarray(tok_p)[live],
                                       onp.asarray(tok_d)[live])

    if not quant:
        # the writes landed at (page, row) of the owner and nowhere else:
        # a retired lane's sentinel rows DROP, so every row no live token
        # maps still holds what it held
        kp1 = onp.asarray(kp)
        touched = onp.zeros((NPAGES, PAGE), bool)
        for b in range(B):
            for t in range(pos[b], pos[b] + STEPS):
                if live[b]:
                    touched[table[b, t // PAGE], t % PAGE] = True
        assert onp.array_equal(kp1[:, ~touched], kp0[:, ~touched])
        assert not onp.array_equal(kp1[:, touched], kp0[:, touched])
