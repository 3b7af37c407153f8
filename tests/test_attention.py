"""Flash / ring attention numerics vs the naive O(L²) softmax reference
(the reference framework's vanilla attention path, SURVEY.md §5.7)."""
import os

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd


def _naive(q, k, v, causal=False, scale=None):
    import jax.numpy as jnp
    scale = scale or 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        Lq, Lk = s.shape[-2], s.shape[-1]
        mask = onp.tril(onp.ones((Lq, Lk), bool))
        s = jnp.where(jnp.asarray(mask), s, -1e30)
    p = jnp.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))


def _rand(*shape):
    return onp.random.RandomState(0).randn(*shape).astype("float32")


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_naive(causal):
    q, k, v = (_rand(2, 3, 64, 16) for _ in range(3))
    out = mx.nd.flash_attention(mx.nd.array(q), mx.nd.array(k),
                                mx.nd.array(v), causal=causal)
    ref = _naive(q, k, v, causal=causal)
    onp.testing.assert_allclose(out.asnumpy(), onp.asarray(ref),
                                rtol=2e-5, atol=2e-5)


def test_flash_long_seq_blocks():
    # seq > q_block so the scan path actually tiles
    q, k, v = (_rand(1, 2, 300, 8) for _ in range(3))
    out = mx.nd.flash_attention(mx.nd.array(q), mx.nd.array(k),
                                mx.nd.array(v), causal=True)
    ref = _naive(q, k, v, causal=True)
    onp.testing.assert_allclose(out.asnumpy(), onp.asarray(ref),
                                rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_grads_match_naive(causal):
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.attention import _flash

    q, k, v = (_rand(1, 2, 48, 8) for _ in range(3))

    def f_flash(q, k, v):
        return jnp.sum(_flash(q, k, v, None, jnp.uint32(0), 0.125, causal) ** 2)

    def f_naive(q, k, v):
        return jnp.sum(_naive(q, k, v, causal=causal, scale=0.125) ** 2)

    g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_naive, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b),
                                    rtol=1e-4, atol=1e-4)


def test_flash_autograd_through_tape():
    q = mx.nd.array(_rand(1, 2, 32, 8))
    k = mx.nd.array(_rand(1, 2, 32, 8))
    v = mx.nd.array(_rand(1, 2, 32, 8))
    for x in (q, k, v):
        x.attach_grad()
    with autograd.record():
        out = mx.nd.flash_attention(q, k, v, causal=True)
        loss = (out * out).sum()
    loss.backward()
    assert q.grad is not None and onp.isfinite(q.grad.asnumpy()).all()
    assert onp.abs(v.grad.asnumpy()).sum() > 0


def test_pallas_kernel_interpret_mode():
    """Run the actual Pallas kernel through the interpreter on CPU and
    check numerics (128-aligned shapes as on real TPU)."""
    from mxnet_tpu.ops import attention as attn

    q, k, v = (_rand(1, 1, 128, 128) for _ in range(3))
    os.environ["MXNET_FLASH_INTERPRET"] = "1"
    try:
        out, lse = attn._pallas_fwd(q, k, v, 0.08838834765, True)
    finally:
        del os.environ["MXNET_FLASH_INTERPRET"]
    ref = _naive(q, k, v, causal=True, scale=0.08838834765)
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref),
                                rtol=2e-5, atol=2e-5)
    assert onp.isfinite(onp.asarray(lse)).all()


def test_flash_padding_mask_bias():
    import jax.numpy as jnp
    q, k, v = (_rand(2, 2, 32, 8) for _ in range(3))
    valid = 20  # keys >= valid are masked out
    bias = onp.zeros((2, 1, 32, 32), "float32")
    bias[:, :, :, valid:] = -1e30
    out = mx.nd.flash_attention(mx.nd.array(q), mx.nd.array(k),
                                mx.nd.array(v), mx.nd.array(bias))
    ref = _naive(q, k[:, :, :valid], v[:, :, :valid])
    onp.testing.assert_allclose(out.asnumpy(), onp.asarray(ref),
                                rtol=2e-5, atol=2e-5)


def test_flash_bias_grad_matches_naive():
    """A learned (e.g. ALiBi-style) bias must receive real gradients."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.attention import _flash

    q, k, v = (_rand(2, 2, 24, 8) for _ in range(3))
    bias = (_rand(2, 1, 24, 24) * 0.1).astype("float32")

    def f_flash(bias):
        return jnp.sum(_flash(q, k, v, bias, jnp.uint32(0), 0.3, False) ** 2)

    def f_naive(bias):
        import jax.numpy as jnp
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * 0.3 + bias
        p = jax.nn.softmax(s, axis=-1)
        return jnp.sum(jnp.einsum("bhqk,bhkd->bhqd", p, v) ** 2)

    g1 = jax.grad(f_flash)(bias)
    g2 = jax.grad(f_naive)(bias)
    onp.testing.assert_allclose(onp.asarray(g1), onp.asarray(g2),
                                rtol=1e-4, atol=1e-5)


def test_pallas_kernel_interpret_head_dim_64():
    """head_dim 64 (every shipped model) must reach the kernel, in blocks
    of its own width (no padding to 128 lanes since PR 32)."""
    from mxnet_tpu.ops import attention as attn

    q, k, v = (_rand(1, 2, 256, 64) for _ in range(3))
    os.environ["MXNET_FLASH_INTERPRET"] = "1"
    try:
        out, lse = attn._pallas_fwd(q, k, v, 0.125, True)
    finally:
        del os.environ["MXNET_FLASH_INTERPRET"]
    assert out.shape == (1, 2, 256, 64)
    ref = _naive(q, k, v, causal=True, scale=0.125)
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref),
                                rtol=2e-5, atol=2e-5)


def test_bf16_transformer_forward():
    from mxnet_tpu.models import GPT, GPTConfig
    mx.random.seed(0)
    net = GPT(GPTConfig(vocab_size=97, max_length=32, num_layers=1,
                        units=32, num_heads=4, hidden_size=64,
                        dtype="bfloat16"))
    net.initialize()
    # every Dense/Embedding param must actually be bf16
    import jax.numpy as jnp
    dts = {n: p.data().dtype for n, p in net.collect_params().items()}
    assert all(onp.dtype(dt) == onp.dtype(jnp.bfloat16) for n, dt in
               dts.items() if "weight" in n or "bias" in n), dts
    toks = onp.random.RandomState(0).randint(0, 97, size=(2, 16))
    out = net(mx.nd.array(toks))
    assert onp.isfinite(out.asnumpy().astype("float32")).all()


def test_ring_attention_matches_full():
    from mxnet_tpu import parallel

    mesh = parallel.make_mesh({"sp": 8})
    q, k, v = (_rand(1, 2, 64, 8) for _ in range(3))
    for causal in (False, True):
        out = mx.nd.ring_attention(mx.nd.array(q), mx.nd.array(k),
                                   mx.nd.array(v), causal=causal,
                                   axis="sp", mesh=mesh)
        ref = _naive(q, k, v, causal=causal)
        onp.testing.assert_allclose(out.asnumpy(), onp.asarray(ref),
                                    rtol=2e-5, atol=2e-5)


def test_flash_path_beyond_plain_threshold():
    """L=640 exceeds the plain-attention score cap — the op must route to
    the blockwise kernel and still match naive attention."""
    q, k, v = (_rand(1, 1, 640, 8) for _ in range(3))
    out = mx.nd.flash_attention(mx.nd.array(q), mx.nd.array(k),
                                mx.nd.array(v), causal=True)
    ref = _naive(q, k, v, causal=True)
    onp.testing.assert_allclose(out.asnumpy(), onp.asarray(ref),
                                rtol=3e-5, atol=3e-5)


def test_plain_and_blockwise_paths_agree():
    """Same inputs through both implementations (the op picks by length;
    here both are invoked explicitly) must agree."""
    from mxnet_tpu.ops.attention import _flash, _plain_attn
    import jax.numpy as jnp
    q, k, v = (jnp.asarray(_rand(1, 2, 96, 8)) for _ in range(3))
    a = _plain_attn(q, k, v, None, 0.125, True)
    b = _flash(q, k, v, None, jnp.uint32(0), 0.125, True)
    onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b),
                                rtol=2e-5, atol=2e-5)


# --------------------------------------------------------------------------- #
# round 2: Pallas backward kernels, in-kernel padding mask, dropout
# --------------------------------------------------------------------------- #

def _naive_dropout(q, k, v, bias, scale, causal, rate, seed):
    """Naive attention using the SAME position-hash keep mask as the
    kernels — exact reference for dropout numerics on every path."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from mxnet_tpu.ops.attention import _keep
    B, H, Lq, _ = q.shape
    Lk = k.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    if causal:
        qp = lax.broadcasted_iota(jnp.int32, (Lq, Lk), 0)
        kp = lax.broadcasted_iota(jnp.int32, (Lq, Lk), 1)
        s = jnp.where(qp >= kp, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    if rate > 0:
        bh = (lax.broadcasted_iota(jnp.int32, (B, H), 0) * H +
              lax.broadcasted_iota(jnp.int32, (B, H), 1))[..., None, None]
        qp = lax.broadcasted_iota(jnp.int32, (1, 1, Lq, 1), 2)
        kp = lax.broadcasted_iota(jnp.int32, (1, 1, 1, Lk), 3)
        p = jnp.where(_keep(seed, bh, qp, kp, rate), p, 0.0) / (1 - rate)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))


def test_pallas_bwd_kernels_match_naive_grads():
    """Interpret-mode Pallas dq + dkdv kernels vs jax.grad of naive."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.attention import _flash

    q, k, v = (_rand(1, 2, 128, 64) for _ in range(3))
    for causal in (False, True):
        os.environ["MXNET_FLASH_INTERPRET"] = "1"
        try:
            g1 = jax.grad(lambda *a: jnp.sum(
                _flash(*a, None, jnp.uint32(0), 0.125, causal) ** 2),
                argnums=(0, 1, 2))(q, k, v)
        finally:
            del os.environ["MXNET_FLASH_INTERPRET"]
        g2 = jax.grad(lambda *a: jnp.sum(
            _naive(*a, causal=causal, scale=0.125) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b),
                                        rtol=2e-4, atol=2e-4)


def test_pallas_kmask_in_kernel_fwd_bwd():
    """Key-padding-mask bias stays ON the Pallas path (fwd + both bwd
    kernels, incl. dbias) and matches masked naive attention."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.attention import _flash, _pallas_eligible

    q, k, v = (_rand(2, 2, 128, 64) for _ in range(3))
    bias = onp.zeros((2, 1, 1, 128), "float32")
    bias[:, :, :, 100:] = -1e30
    bias = jnp.asarray(bias)
    os.environ["MXNET_FLASH_INTERPRET"] = "1"
    try:
        assert _pallas_eligible(jnp.asarray(q), jnp.asarray(k), bias)
        out = _flash(q, k, v, bias, jnp.uint32(0), 0.125, False)
        g1 = jax.grad(lambda qq, kk, vv, bb: jnp.sum(
            _flash(qq, kk, vv, bb, jnp.uint32(0), 0.125, False) ** 2),
            argnums=(0, 1, 2, 3))(q, k, v, bias)
    finally:
        del os.environ["MXNET_FLASH_INTERPRET"]
    ref = _naive(q, k[:, :, :100], v[:, :, :100], scale=0.125)
    onp.testing.assert_allclose(onp.asarray(out[:, :, :, :]),
                                onp.asarray(ref), rtol=2e-5, atol=2e-5)

    def f_naive(qq, kk, vv, bb):
        s = jnp.einsum("bhqd,bhkd->bhqk", qq, kk) * 0.125 + bb
        p = jax.nn.softmax(s, axis=-1)
        return jnp.sum(jnp.einsum("bhqk,bhkd->bhqd", p, vv) ** 2)

    g2 = jax.grad(f_naive, argnums=(0, 1, 2, 3))(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), bias)
    for a, b in zip(g1, g2):
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b),
                                    rtol=2e-4, atol=2e-4)


def test_dropout_fwd_stats_and_determinism():
    """Dropout keeps ~(1-rate) mass, is deterministic per seed, differs
    across seeds, and is off in inference mode."""
    import jax.numpy as jnp
    from mxnet_tpu.ops.attention import _plain_attn

    q, k, v = (jnp.asarray(_rand(2, 4, 64, 16)) for _ in range(3))
    a1 = _plain_attn(q, k, v, None, 0.25, False, dropout=0.5,
                     seed=jnp.uint32(7))
    a2 = _plain_attn(q, k, v, None, 0.25, False, dropout=0.5,
                     seed=jnp.uint32(7))
    a3 = _plain_attn(q, k, v, None, 0.25, False, dropout=0.5,
                     seed=jnp.uint32(8))
    onp.testing.assert_array_equal(onp.asarray(a1), onp.asarray(a2))
    assert onp.abs(onp.asarray(a1) - onp.asarray(a3)).max() > 1e-4

    # E[dropped p row-sum] == 1; check the keep fraction is ~50%
    from mxnet_tpu.ops.attention import _keep
    import jax.lax as lax
    bits = _keep(jnp.uint32(7), jnp.int32(0),
                 lax.broadcasted_iota(jnp.int32, (256, 1), 0),
                 lax.broadcasted_iota(jnp.int32, (1, 256), 1), 0.5)
    frac = onp.asarray(bits).mean()
    assert 0.47 < frac < 0.53, frac


def test_dropout_grads_consistent_across_paths():
    """XLA blockwise fwd+bwd with dropout == grads of the hash-identical
    naive implementation (the mask regenerates identically)."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.attention import _flash

    q, k, v = (_rand(1, 2, 96, 8) for _ in range(3))
    seed = jnp.uint32(42)
    g1 = jax.grad(lambda *a: jnp.sum(
        _flash(*a, None, seed, 0.125, False, 0.3) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda *a: jnp.sum(
        _naive_dropout(*a, None, 0.125, False, 0.3, seed) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b),
                                    rtol=2e-4, atol=2e-4)


def test_dropout_pallas_kernels_match_naive():
    """Pallas fwd + bwd with in-kernel dropout == hash-identical naive."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.attention import _flash

    q, k, v = (_rand(1, 2, 128, 64) for _ in range(3))
    seed = jnp.uint32(5)
    os.environ["MXNET_FLASH_INTERPRET"] = "1"
    try:
        out = _flash(q, k, v, None, seed, 0.125, False, 0.2)
        g1 = jax.grad(lambda *a: jnp.sum(
            _flash(*a, None, seed, 0.125, False, 0.2) ** 2),
            argnums=(0, 1, 2))(q, k, v)
    finally:
        del os.environ["MXNET_FLASH_INTERPRET"]
    ref = _naive_dropout(q, k, v, None, 0.125, False, 0.2, seed)
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref),
                                rtol=2e-5, atol=2e-5)
    g2 = jax.grad(lambda *a: jnp.sum(
        _naive_dropout(*a, None, 0.125, False, 0.2, seed) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b),
                                    rtol=2e-4, atol=2e-4)


def test_flash_attention_op_dropout_training_flag():
    """The public op applies dropout only in training mode."""
    q, k, v = (mx.nd.array(_rand(1, 2, 32, 8)) for _ in range(3))
    mx.random.seed(0)
    out_infer = mx.nd.flash_attention(q, k, v, dropout=0.5)
    with autograd.record(train_mode=True):
        out_train = mx.nd.flash_attention(q, k, v, dropout=0.5)
    ref = _naive(q.asnumpy(), k.asnumpy(), v.asnumpy())
    onp.testing.assert_allclose(out_infer.asnumpy(), onp.asarray(ref),
                                rtol=2e-5, atol=2e-5)
    assert onp.abs(out_train.asnumpy() - out_infer.asnumpy()).max() > 1e-4


def test_ring_attention_grads_match_full():
    """Ring attention must be differentiable through the ppermute ring
    (long-context training, not just inference)."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import parallel
    from mxnet_tpu.ops.attention import _ring_attn_local
    from mxnet_tpu.parallel.mesh import P
    import functools

    mesh = parallel.make_mesh({"sp": 8})
    q, k, v = (_rand(1, 2, 64, 8) for _ in range(3))

    fn = jax.shard_map(
        functools.partial(_ring_attn_local, scale=0.125, causal=True,
                          axis="sp", n_shards=8),
        mesh=mesh, in_specs=(P(None, None, "sp", None),) * 3,
        out_specs=P(None, None, "sp", None), check_vma=False)

    def ring_loss(q, k, v):
        return jnp.sum(fn(q, k, v).astype(jnp.float32) ** 2)

    def full_loss(q, k, v):
        return jnp.sum(jnp.asarray(
            _naive(q, k, v, causal=True, scale=0.125)) ** 2)

    g1 = jax.grad(ring_loss, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(full_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b),
                                    rtol=2e-4, atol=2e-4)


# --------------------------------------------------------------------------- #
# PR 32: the kernels at the train cell's dtype, at any block size, and the
# operands of the XLA path
# --------------------------------------------------------------------------- #

def _fwd_and_grads(attn_fn, q, k, v, g):
    import jax
    out, vjp = jax.vjp(attn_fn, q, k, v)
    return (out,) + vjp(g)


@pytest.mark.parametrize("L", [256, 1024])
@pytest.mark.parametrize("D", [64, 128])
@pytest.mark.parametrize("causal", [False, True])
def test_pallas_kernels_bfloat16_match_naive(monkeypatch, causal, D, L):
    """bfloat16 q, k, v through the three kernels (interpret mode): the
    products take bfloat16 operands, p and ds are rounded to bfloat16 once
    each, everything else stays float32 — within 4 bfloat16 ulps (2**-6)
    of the float32 naive reference's largest entry, as chip_smoke holds
    the chip to."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.attention import _flash
    monkeypatch.setenv("MXNET_FLASH_INTERPRET", "1")
    scale = 1.0 / D ** 0.5
    ks = jax.random.split(jax.random.PRNGKey(L + D), 4)
    q, k, v, g = (jax.random.normal(kk, (1, 2, L, D), jnp.float32)
                  .astype(jnp.bfloat16) for kk in ks)
    got = _fwd_and_grads(
        lambda q, k, v: _flash(q, k, v, None, jnp.uint32(0), scale, causal,
                               0.0, "pallas"), q, k, v, g)
    ref = _fwd_and_grads(
        lambda q, k, v: _naive(q, k, v, causal=causal, scale=scale),
        q, k, v, g.astype(jnp.float32))
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, ref):
        assert a.dtype == jnp.bfloat16, (name, a.dtype)
        a = onp.asarray(a.astype(jnp.float32))
        b = onp.asarray(b.astype(jnp.float32))
        err = onp.abs(a - b).max() / onp.abs(b).max()
        assert err < 2.0 ** -6, (name, err)


@pytest.mark.parametrize("Lq, Lk", [(512, 512), (256, 512), (512, 256)])
@pytest.mark.parametrize("block_q, block_k",
                         [(128, 128), (128, 256), (256, 128), (256, 256)])
def test_pallas_kernels_any_block_sizes(monkeypatch, block_q, block_k,
                                        Lq, Lk):
    """Every pair of block sizes gives the same causal result (float32,
    the tolerance of the other interpret-mode tests): the diagonal skip
    and the clamped index maps, which hand a skipped step the block that
    is already resident, agree with the mask for square and oblong blocks
    and for Lq != Lk."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import attention as attn
    monkeypatch.setenv("MXNET_FLASH_INTERPRET", "1")
    q, g = (jnp.asarray(_rand(1, 2, Lq, 64)) + i for i in range(2))
    k, v = (jnp.asarray(_rand(1, 2, Lk, 64)) * (i + 1) for i in range(2))
    out, lse = attn._pallas_fwd(q, k, v, 0.125, True, block_q=block_q,
                                block_k=block_k)
    delta = jnp.sum(out * g, axis=-1).reshape(2, 1, Lq)
    dq = attn._pallas_bwd_dq(q, k, v, g, lse.reshape(2, 1, Lq), delta,
                             0.125, True, block_q=block_q, block_k=block_k)
    dk, dv, _ = attn._pallas_bwd_dkv(
        q, k, v, g, lse.reshape(2, 1, Lq), delta, 0.125, True,
        block_q=block_q, block_k=block_k)
    ref = _fwd_and_grads(
        lambda q, k, v: _naive(q, k, v, causal=True, scale=0.125),
        q, k, v, g)
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref[0]),
                                rtol=3e-5, atol=3e-5)
    for a, b in zip((dq, dk, dv), ref[1:]):
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b),
                                    rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("L", [128, 256, 512])
def test_pallas_kernels_causal_with_key_mask_and_dropout(monkeypatch, L):
    """Causal + key-padding mask + dropout together through the three
    kernels, dbias included, against the hash-identical naive reference:
    at 128 a diagonal block is one masked tile, from 256 on the backward
    kernels cut it in two and skip its upper-right quarter (dq by q rows,
    dk/dv by k rows) — the mask's slice and the dropout positions must
    follow the tile."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.attention import _flash
    monkeypatch.setenv("MXNET_FLASH_INTERPRET", "1")
    q, k, v = (jnp.asarray(_rand(2, 2, L, 64)) * (i + 1) for i in range(3))
    bias = onp.zeros((2, 1, 1, L), "float32")
    bias[0, :, :, L - 40:] = -1e30
    bias = jnp.asarray(bias)
    seed = jnp.uint32(11)

    def loss(fn):
        return lambda q, k, v, b: jnp.sum(fn(q, k, v, b) ** 2)

    flash = lambda q, k, v, b: _flash(q, k, v, b, seed, 0.125, True, 0.2,
                                      "pallas")
    naive = lambda q, k, v, b: _naive_dropout(q, k, v, b, 0.125, True, 0.2,
                                              seed)
    onp.testing.assert_allclose(
        onp.asarray(flash(q, k, v, bias)), onp.asarray(naive(q, k, v, bias)),
        rtol=3e-5, atol=3e-5)
    g1 = jax.grad(loss(flash), argnums=(0, 1, 2, 3))(q, k, v, bias)
    g2 = jax.grad(loss(naive), argnums=(0, 1, 2, 3))(q, k, v, bias)
    for a, b in zip(g1, g2):        # relative to each gradient's largest
        top = float(jnp.abs(b).max())
        onp.testing.assert_allclose(onp.asarray(a) / top,
                                    onp.asarray(b) / top, atol=2e-5)


def _dot_operand_dtypes(jaxpr):
    """The operand dtypes of every ``dot_general`` in ``jaxpr`` and the
    jaxprs nested in it (scan bodies, custom-vjp calls)."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            found.append(tuple(v.aval.dtype for v in eqn.invars))
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)        # ClosedJaxpr
                if hasattr(sub, "eqns"):
                    found += _dot_operand_dtypes(sub)
    return found


@pytest.mark.parametrize("causal", [False, True])
def test_xla_path_feeds_products_the_input_dtype(causal):
    """On bfloat16 inputs no product of the blockwise XLA path, forward or
    backward, takes a float32 operand (a float32 product runs in several
    MXU passes); two forward and five backward products are there."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.attention import _flash
    q, k, v = (jnp.asarray(_rand(1, 2, 640, 16), jnp.bfloat16)
               for _ in range(3))

    def loss(q, k, v):
        return jnp.sum(_flash(q, k, v, None, jnp.uint32(0), 0.25, causal,
                              0.0, "xla").astype(jnp.float32))

    dots = _dot_operand_dtypes(
        jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v).jaxpr)
    assert len(dots) == 7, dots
    assert all(d == jnp.bfloat16 for pair in dots for d in pair), dots


# --------------------------------------------------------------------------- #
# measured dispatch table (VERDICT r2 item 4)
# --------------------------------------------------------------------------- #

class TestDispatch:
    def _choose(self, Lq, Lk=None, bias=None, training=True, pallas_ok=True):
        import jax.numpy as jnp
        from mxnet_tpu.ops import attention as attn
        Lk = Lk or Lq
        q = jnp.zeros((1, 1, Lq, 8))
        saved = attn._use_pallas
        attn._use_pallas = lambda: pallas_ok
        try:
            return attn._choose_path(Lq, Lk, bias, training)
        finally:
            attn._use_pallas = saved

    def test_short_is_plain(self):
        assert self._choose(128) == "plain"
        assert self._choose(512) == "plain"

    def test_mid_range_follows_table(self):
        from mxnet_tpu.ops.attention import _PATH_TABLE
        # both columns: the table rows must be respected exactly
        for column, training in (("train", True), ("fwd", False)):
            for bound, impl in _PATH_TABLE[column]:
                if bound is None or bound <= 512:
                    continue
                assert self._choose(bound, training=training) == impl
        # the train cell's length, and the first length past the plain path
        assert self._choose(1024, training=True) == "pallas"
        assert self._choose(640, training=True) == "pallas"
        assert self._choose(640, training=False) == "xla"

    def test_long_is_pallas(self):
        assert self._choose(8192, training=True) == "pallas"
        assert self._choose(8192, training=False) == "pallas"

    def test_unaligned_long_still_pallas(self):
        # 128-unaligned lengths are padded inside the op, not demoted
        assert self._choose(8000, training=True) == "pallas"

    def test_dense_bias_never_pallas(self):
        import jax.numpy as jnp
        dense_bias = jnp.zeros((1, 1, 8192, 8192))
        assert self._choose(8192, bias=dense_bias) == "xla"

    def test_no_pallas_backend_degrades_to_xla(self):
        assert self._choose(8192, pallas_ok=False) == "xla"


    def test_dispatch_matches_measured_best(self):
        """Frozen copy of the v5e sweep (benchmark/attention_bench.py,
        2026-10-03, B4 H8 and B8 H16 agreeing above 512): chosen path ==
        fastest measured path at every measured (seq, pass) point
        (VERDICT r2 item 4 done-criterion).  512 is the plain path's by
        ``_PLAIN_ATTN_MAX_SCORES``, which the table does not override."""
        measured_best = {
            (512, False): "plain", (512, True): "plain",
            (768, False): "xla", (768, True): "pallas",
            (1024, False): "pallas", (1024, True): "pallas",
            (2048, False): "pallas", (2048, True): "pallas",
            (4096, False): "pallas", (4096, True): "pallas",
            (8192, False): "pallas", (8192, True): "pallas",
        }
        for (seq, training), want in measured_best.items():
            got = self._choose(seq, training=training)
            assert got == want, (seq, training, got, want)


class TestPadding:
    def test_pad_to_block_shapes_and_mask(self):
        import jax.numpy as jnp
        from mxnet_tpu.ops.attention import _pad_to_block, _NEG_INF
        q = jnp.ones((2, 3, 200, 16))
        k = jnp.ones((2, 3, 250, 16))
        v = jnp.ones((2, 3, 250, 16))
        q2, k2, v2, bias2, Lq = _pad_to_block(q, k, v, None)
        assert Lq == 200
        assert q2.shape[2] == 256 and k2.shape[2] == 256
        assert v2.shape == k2.shape
        # synthesized key mask: 0 for real keys, -inf for pad keys
        assert bias2.shape == (1, 1, 1, 256)
        assert float(bias2[0, 0, 0, 249]) == 0.0
        assert float(bias2[0, 0, 0, 250]) <= _NEG_INF / 2

    def test_pad_preserves_existing_kmask(self):
        import jax.numpy as jnp
        from mxnet_tpu.ops.attention import _pad_to_block, _NEG_INF
        q = jnp.ones((2, 1, 128, 8))
        k = jnp.ones((2, 1, 130, 8))
        bias = jnp.zeros((2, 1, 1, 130)).at[0, 0, 0, 5].set(_NEG_INF)
        q2, k2, v2, bias2, _ = _pad_to_block(q, k, jnp.ones_like(k), bias)
        assert bias2.shape == (2, 1, 1, 256)
        assert float(bias2[0, 0, 0, 5]) <= _NEG_INF / 2   # user mask kept
        assert float(bias2[1, 0, 0, 129]) == 0.0          # real key open
        assert float(bias2[1, 0, 0, 130]) <= _NEG_INF / 2  # pad key masked

    def test_padded_pallas_matches_naive(self, monkeypatch):
        """Unaligned seq through the actual Pallas kernel (interpret mode)
        must equal the naive reference after the in-op pad+slice."""
        import jax.numpy as jnp
        from mxnet_tpu.ops import attention as attn
        monkeypatch.setenv("MXNET_FLASH_INTERPRET", "1")
        q, k, v = (jnp.asarray(_rand(1, 2, 200, 16)) for _ in range(3))
        q2, k2, v2, bias2, Lq = attn._pad_to_block(q, k, v, None)
        out = attn._flash(q2, k2, v2, bias2, jnp.uint32(0), 0.25, False,
                          0.0, "pallas")[:, :, :Lq]
        ref = _naive(q, k, v, causal=False, scale=0.25)
        onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref),
                                    rtol=3e-5, atol=3e-5)

    def test_broadcast_kmask_bias_not_pallas(self):
        """A (B,1,1,1) broadcast bias cannot become a padded kernel mask —
        dispatch must route it to the XLA path, and the op must compute
        correctly (review regression)."""
        import jax.numpy as jnp
        from mxnet_tpu.ops import attention as attn
        bias = jnp.zeros((1, 1, 1, 1))
        assert attn._choose_path(8000, 8000, bias, False) == "xla"
        q, k, v = (jnp.asarray(_rand(1, 1, 600, 8)) for _ in range(3))
        out = mx.nd.flash_attention(mx.nd.array(onp.asarray(q)),
                                    mx.nd.array(onp.asarray(k)),
                                    mx.nd.array(onp.asarray(v)),
                                    bias=mx.nd.array(onp.zeros(
                                        (1, 1, 1, 1), "float32")))
        ref = _naive(q, k, v, causal=False)
        onp.testing.assert_allclose(out.asnumpy(), onp.asarray(ref),
                                    rtol=3e-5, atol=3e-5)


class TestQ8MatvecTiling:
    """ADVICE r4 (medium): large-K layers must tile K within the VMEM
    budget instead of streaming the whole (K, bo) block, and unaligned
    vocabs must not silently fall off the kernel path."""

    def test_pick_tiles_bounds_bytes(self):
        from mxnet_tpu.ops import q8_matvec as q8
        # Llama-7B down-proj: K=11008, O=4096 — must find a tiling whose
        # working set fits the budget (pre-fix this streamed ~86 MB f32)
        bk, bo = q8._pick_tiles(1, 11008, 4096)
        assert bk and bo and bk % 32 == 0 and bo % 128 == 0
        assert 11008 % bk == 0 and 4096 % bo == 0
        assert q8._tile_bytes(1, bk, bo) <= q8._VMEM_BUDGET
        # huge-K pathological shape still admits the minimum lane tile
        bk2, bo2 = q8._pick_tiles(1, 32768, 128)
        assert bk2 and bo2 == 128
        assert q8._tile_bytes(1, bk2, bo2) <= q8._VMEM_BUDGET

    def test_k_tiled_kernel_matches_einsum(self, monkeypatch):
        import jax.numpy as jnp
        from mxnet_tpu.ops.q8_matvec import q8_matvec, _pick_tiles
        monkeypatch.setenv("MXNET_FLASH_INTERPRET", "1")
        # shrink the budget so K genuinely tiles even at this test size
        monkeypatch.setattr("mxnet_tpu.ops.q8_matvec._VMEM_BUDGET",
                            256 * 1024)
        B, K, O = 2, 512, 384
        bk, bo = _pick_tiles(B, K, O)
        assert bk < K  # the accumulation path is actually exercised
        x = jnp.asarray(onp.random.RandomState(0).randn(B, K), "float32")
        wq = jnp.asarray(
            onp.random.RandomState(1).randint(-127, 128, (K, O)), "int8")
        s = jnp.asarray(onp.random.RandomState(2).rand(O) + 0.5, "float32")
        b = jnp.asarray(onp.random.RandomState(3).randn(O), "float32")
        got = q8_matvec(x, wq, s, b)
        ref = (x @ wq.astype(jnp.float32)) * s + b
        onp.testing.assert_allclose(onp.asarray(got), onp.asarray(ref),
                                    rtol=2e-4, atol=2e-3)

    def test_misaligned_O_falls_back(self):
        """bo must stay a 128 lane multiple — O=1000 has no admissible
        tile and must route to the einsum fallback (review regression)."""
        from mxnet_tpu.ops import q8_matvec as q8
        assert q8._pick_tiles(1, 256, 1000) == (0, 0)
        assert q8._pick_tiles(1, 64, 192) == (0, 0)


# --------------------------------------------------------------------------- #
# the kernels over the packed (B, L, 3U) projection (PR 34)
# --------------------------------------------------------------------------- #

def _mha_apart(qkv, bias, heads, **kw):
    """What ``MultiHeadAttention`` did between its projections before the
    packed op: reshape / transpose / slice, ``flash_attention``, transpose
    back."""
    from mxnet_tpu.ops.registry import get_op
    B, L, U3 = qkv.shape
    x = qkv.reshape(B, L, 3, heads, U3 // 3 // heads).transpose(2, 0, 3, 1, 4)
    out = get_op("flash_attention").fn(x[0], x[1], x[2], bias, **kw)
    return out.transpose(0, 2, 1, 3).reshape(B, L, U3 // 3)


def _packed_inputs(B, H, D, L, dtype, key_mask=False):
    import jax
    import jax.numpy as jnp
    ks = jax.random.split(jax.random.PRNGKey(H * D + L), 2)
    qkv = jax.random.normal(ks[0], (B, L, 3 * H * D), jnp.float32)
    g = jax.random.normal(ks[1], (B, L, H * D), jnp.float32)
    bias = None
    if key_mask:
        bias = onp.zeros((B, 1, 1, L), "float32")
        bias[0, :, :, L - 40:] = -1e30
        bias = jnp.asarray(bias)
    return qkv.astype(dtype), g.astype(dtype), bias


def _assert_same(got, want, what):
    """Bit for bit: a head of a pair is the same sums in the same order as
    the head apart (the lanes masked out add exact zeros)."""
    for name, a, b in zip(("out", "dqkv", "dbias"), got, want):
        if b is None:
            assert a is None, (what, name)
            continue
        assert a.dtype == b.dtype and a.shape == b.shape, (what, name)
        onp.testing.assert_array_equal(
            onp.asarray(a.astype("float32")), onp.asarray(b.astype("float32")),
            err_msg=f"{what}: {name}")


def _packed_and_apart(qkv, g, bias, H, causal, rate):
    """(out, dqkv, dbias) of the packed kernels and of the same kernels on
    heads split apart, one dropout seed."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import attention as attn
    B, L, U = g.shape
    scale = 1.0 / (U // H) ** 0.5
    seed = jnp.uint32(5)

    def packed(qkv, bias):
        return attn._flash_qkv(qkv, bias, seed, H, scale, causal, rate)

    def apart(qkv, bias):
        out = attn._flash(*attn._split_heads(qkv, H), bias, seed, scale,
                          causal, rate, "pallas")
        return out.transpose(0, 2, 1, 3).reshape(B, L, U)

    res = []
    for fn in (packed, apart):
        out, vjp = jax.vjp(fn, qkv, bias)
        res.append((out,) + vjp(g))
    return res


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("L", [256, 1024])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("D", [64, 128])
def test_packed_kernels_match_kernels_apart(monkeypatch, D, causal, L, dtype):
    """Forward and the one (B, L, 3U) gradient of the three kernels reading
    the projection where it lies, against the same kernels on (B, H, L, D)
    copies: heads in pairs at D 64 (two pairs), one a block at D 128."""
    monkeypatch.setenv("MXNET_FLASH_INTERPRET", "1")
    H = 4 if D == 64 else 2
    qkv, g, _ = _packed_inputs(1, H, D, L, dtype)
    got, want = _packed_and_apart(qkv, g, None, H, causal, 0.0)
    assert got[0].shape == (1, L, H * D) and got[1].shape == qkv.shape
    _assert_same(got, want, f"D{D} causal={causal} L{L} {dtype}")


@pytest.mark.parametrize("rate", [0.0, 0.2], ids=["keep_all", "dropout"])
@pytest.mark.parametrize("key_mask", [False, True], ids=["open", "key_mask"])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("D", [64, 128])
def test_packed_kernels_key_mask_and_dropout(monkeypatch, D, causal,
                                             key_mask, rate):
    """A key mask (its gradient too) and dropout under one seed: the hash
    is keyed on batch * H + head in both layouts, so a packed call and a
    call apart drop the same positions — two batch rows, so the head's
    index is not the grid's."""
    monkeypatch.setenv("MXNET_FLASH_INTERPRET", "1")
    H = 4 if D == 64 else 2
    qkv, g, bias = _packed_inputs(2, H, D, 256, "float32", key_mask)
    got, want = _packed_and_apart(qkv, g, bias, H, causal, rate)
    if rate:
        # some probability dropped, not all: the outputs differ from the
        # call that keeps every position
        kept, _ = _packed_and_apart(qkv, g, bias, H, causal, 0.0)
        assert not onp.allclose(onp.asarray(got[0]), onp.asarray(kept[0]))
    _assert_same(got, want, f"D{D} causal={causal} mask={key_mask} p={rate}")


def _paths(fn, *args):
    """(result, jaxpr text, ``path`` fields of the op's telemetry events)
    of one traced call."""
    import jax
    from mxnet_tpu import telemetry
    telemetry.clear_events()
    jaxpr = str(jax.make_jaxpr(fn)(*args))
    paths = [e["path"] for e in telemetry.events("attention_path")]
    return fn(*args), jaxpr, paths


FALLBACKS = {
    # name: (H, D, L, bias, path on a TPU)
    "L128_plain": (4, 64, 128, None, "plain"),
    "dense_bias": (2, 64, 1024, "dense", "xla"),
    "D80": (2, 80, 1024, None, "pallas"),
    "H_odd_D64": (3, 64, 1024, None, "pallas"),
    # four heads a lane block: their temporaries do not fit the chip's VMEM
    "D32": (4, 32, 1024, None, "pallas"),
}


@pytest.mark.parametrize("case", list(FALLBACKS))
def test_packed_op_falls_back_to_the_split(monkeypatch, case):
    """Every caller that does not reach the packed kernels gets what
    ``MultiHeadAttention`` computed before: the split, ``flash_attention``,
    the transpose back — no ``pallas_call`` off the TPU, and on one (the
    interpreter stands in) only the kernels over heads apart."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.registry import get_op
    op_fn = get_op("flash_attention_qkv").fn
    H, D, L, bias, tpu_path = FALLBACKS[case]
    qkv, g, _ = _packed_inputs(1, H, D, L, "float32")
    if bias == "dense":
        bias = jnp.asarray(_rand(1, 1, L, L))
    kw = dict(causal=True, dropout=0.0, training=True)

    def loss(fn):
        return lambda qkv: jnp.sum(fn(qkv) * g)

    new = lambda qkv: op_fn(qkv, bias, num_heads=H, **kw)
    old = lambda qkv: _mha_apart(qkv, bias, H, **kw)
    out, jaxpr, paths = _paths(new, qkv)
    assert "pallas_call" not in jaxpr
    assert paths == ["plain" if tpu_path == "plain" else "xla"]
    onp.testing.assert_array_equal(onp.asarray(out), onp.asarray(old(qkv)))
    onp.testing.assert_array_equal(
        onp.asarray(jax.grad(loss(new))(qkv)),
        onp.asarray(jax.grad(loss(old))(qkv)))
    assert "pallas_call" not in str(jax.make_jaxpr(jax.grad(loss(new)))(qkv))

    monkeypatch.setenv("MXNET_FLASH_INTERPRET", "1")
    _, jaxpr, paths = _paths(jax.grad(loss(new)), qkv)
    assert paths == [tpu_path]
    assert "_qkv" not in jaxpr
    assert ("mx_flash_bwd_dkv" in jaxpr) == (tpu_path == "pallas")


@pytest.mark.parametrize("D, H", [(64, 4), (128, 2)])
def test_packed_op_takes_the_packed_kernels(monkeypatch, D, H):
    """Training at L 1,024 on a TPU (the interpreter stands in): the op's
    three kernels are the packed ones, by name, no transpose of a
    (B, H, L, D) array is left in the program, and the result is the
    split's."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.registry import get_op
    monkeypatch.setenv("MXNET_FLASH_INTERPRET", "1")
    op_fn = get_op("flash_attention_qkv").fn
    qkv, g, _ = _packed_inputs(1, H, D, 1024, "float32")
    kw = dict(causal=True, dropout=0.0, training=True)
    new = lambda qkv: jnp.sum(op_fn(qkv, num_heads=H, **kw) * g)
    old = lambda qkv: jnp.sum(_mha_apart(qkv, None, H, **kw) * g)
    grad, jaxpr, paths = _paths(jax.grad(new), qkv)
    assert paths == ["packed"]
    for kernel in ("mx_flash_fwd_qkv", "mx_flash_bwd_dq_qkv",
                   "mx_flash_bwd_dkv_qkv"):
        assert kernel in jaxpr, kernel
    assert "transpose[" not in jaxpr.split("pallas_call")[0]
    onp.testing.assert_array_equal(onp.asarray(grad),
                                   onp.asarray(jax.grad(old)(qkv)))
    # inference at this length stays with the measured table's XLA row
    _, _, paths = _paths(
        lambda qkv: op_fn(qkv, num_heads=H, causal=True, training=False),
        qkv[:, :768])
    assert paths == ["xla"]
