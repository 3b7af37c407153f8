"""Op-level golden tests vs NumPy + finite-difference gradient checks.

Mirrors the reference test strategy (SURVEY.md §7):
``tests/python/unittest/test_operator.py`` — golden vs numpy,
``check_numeric_gradient``."""
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.test_utils import (assert_almost_equal,
                                  check_numeric_gradient, rand_ndarray)


class TestElemwise:
    def test_unary_golden(self):
        x = onp.random.uniform(0.1, 2.0, (3, 4)).astype("float32")
        a = nd.array(x)
        for name, ref in [("exp", onp.exp), ("log", onp.log),
                          ("sqrt", onp.sqrt), ("square", onp.square),
                          ("abs", onp.abs), ("sign", onp.sign),
                          ("floor", onp.floor), ("ceil", onp.ceil),
                          ("sin", onp.sin), ("cos", onp.cos),
                          ("tanh", onp.tanh)]:
            out = getattr(nd, name)(a)
            assert_almost_equal(out, ref(x), rtol=1e-4, atol=1e-4,
                                names=(name, "numpy"))

    def test_binary_broadcast(self):
        x = onp.random.randn(3, 1, 4).astype("float32")
        y = onp.random.randn(1, 5, 4).astype("float32")
        a, b = nd.array(x), nd.array(y)
        assert_almost_equal(nd.broadcast_add(a, b), x + y)
        assert_almost_equal(nd.broadcast_mul(a, b), x * y)
        assert_almost_equal(nd.broadcast_maximum(a, b), onp.maximum(x, y))
        assert_almost_equal(a * 2 + 1 - b / 2, x * 2 + 1 - y / 2)

    def test_comparison_dtype(self):
        a = nd.array([1.0, 2.0, 3.0])
        b = nd.array([2.0, 2.0, 2.0])
        out = a > b
        assert out.dtype == onp.float32
        assert_almost_equal(out, [0.0, 0.0, 1.0])

    def test_scalar_ops(self):
        a = nd.array([1.0, -2.0])
        assert_almost_equal(2.0 - a, [1.0, 4.0])
        assert_almost_equal(1.0 / a, [1.0, -0.5])
        assert_almost_equal(a ** 2, [1.0, 4.0])

    def test_clip_where(self):
        x = onp.random.randn(4, 4).astype("float32")
        assert_almost_equal(nd.clip(nd.array(x), a_min=-0.5, a_max=0.5),
                            onp.clip(x, -0.5, 0.5))
        c = (x > 0).astype("float32")
        assert_almost_equal(
            nd.where(nd.array(c), nd.array(x), nd.array(-x)), onp.abs(x))


class TestReduce:
    def test_reductions(self):
        x = onp.random.randn(2, 3, 4).astype("float32")
        a = nd.array(x)
        assert_almost_equal(nd.sum(a), x.sum())
        assert_almost_equal(nd.sum(a, axis=1), x.sum(1))
        assert_almost_equal(nd.sum(a, axis=(0, 2), keepdims=True),
                            x.sum((0, 2), keepdims=True))
        assert_almost_equal(nd.mean(a, axis=-1), x.mean(-1))
        assert_almost_equal(nd.max(a, axis=0), x.max(0))
        assert_almost_equal(nd.min(a), x.min())
        assert_almost_equal(nd.prod(a, axis=2), x.prod(2))
        assert_almost_equal(nd.norm(a), onp.sqrt((x ** 2).sum()),
                            rtol=1e-4, atol=1e-4)

    def test_sum_exclude(self):
        x = onp.random.randn(2, 3, 4).astype("float32")
        out = nd.sum(nd.array(x), axis=1, exclude=True)
        assert_almost_equal(out, x.sum((0, 2)))

    def test_argmax_argmin(self):
        x = onp.random.randn(3, 5).astype("float32")
        assert_almost_equal(nd.argmax(nd.array(x), axis=1),
                            onp.argmax(x, 1).astype("float32"))
        assert_almost_equal(nd.argmin(nd.array(x), axis=0),
                            onp.argmin(x, 0).astype("float32"))


class TestOrdering:
    def test_topk(self):
        x = onp.random.randn(4, 10).astype("float32")
        v = nd.topk(nd.array(x), k=3, ret_typ="value")
        ref = -onp.sort(-x, axis=-1)[:, :3]
        assert_almost_equal(v, ref)

    def test_sort_argsort(self):
        x = onp.random.randn(5, 6).astype("float32")
        assert_almost_equal(nd.sort(nd.array(x)), onp.sort(x))
        assert_almost_equal(nd.sort(nd.array(x), is_ascend=False),
                            -onp.sort(-x))
        assert_almost_equal(nd.argsort(nd.array(x)),
                            onp.argsort(x).astype("float32"))


class TestLinalg:
    def test_dot(self):
        a = onp.random.randn(3, 4).astype("float32")
        b = onp.random.randn(4, 5).astype("float32")
        assert_almost_equal(nd.dot(nd.array(a), nd.array(b)), a @ b,
                            rtol=1e-4, atol=1e-5)
        assert_almost_equal(
            nd.dot(nd.array(a.T), nd.array(b), transpose_a=True), a @ b,
            rtol=1e-4, atol=1e-5)
        assert_almost_equal(
            nd.dot(nd.array(a), nd.array(b.T), transpose_b=True), a @ b,
            rtol=1e-4, atol=1e-5)

    def test_dot_nd(self):
        a = onp.random.randn(2, 3, 4).astype("float32")
        b = onp.random.randn(4, 5).astype("float32")
        assert_almost_equal(nd.dot(nd.array(a), nd.array(b)),
                            onp.tensordot(a, b, axes=([-1], [0])),
                            rtol=1e-4, atol=1e-5)

    def test_batch_dot(self):
        a = onp.random.randn(6, 3, 4).astype("float32")
        b = onp.random.randn(6, 4, 5).astype("float32")
        assert_almost_equal(nd.batch_dot(nd.array(a), nd.array(b)), a @ b,
                            rtol=1e-4, atol=1e-5)
        assert_almost_equal(
            nd.batch_dot(nd.array(a), nd.array(b.transpose(0, 2, 1)),
                         transpose_b=True), a @ b, rtol=1e-4, atol=1e-5)


class TestShape:
    def test_reshape_codes(self):
        x = nd.zeros((2, 3, 4))
        assert nd.reshape(x, shape=(6, 4)).shape == (6, 4)
        assert nd.reshape(x, shape=(0, -1)).shape == (2, 12)
        assert nd.reshape(x, shape=(-2,)).shape == (2, 3, 4)
        assert nd.reshape(x, shape=(-3, 4)).shape == (6, 4)
        assert nd.reshape(x, shape=(-4, 1, 2, 0, 0)).shape == (1, 2, 3, 4)

    def test_transpose_etc(self):
        x = onp.random.randn(2, 3, 4).astype("float32")
        a = nd.array(x)
        assert_almost_equal(a.T, x.transpose())
        assert_almost_equal(nd.transpose(a, axes=(1, 0, 2)),
                            x.transpose(1, 0, 2))
        assert_almost_equal(nd.swapaxes(a, dim1=0, dim2=2), x.swapaxes(0, 2))
        assert_almost_equal(nd.expand_dims(a, axis=1),
                            onp.expand_dims(x, 1))
        assert_almost_equal(nd.flip(a, axis=2), onp.flip(x, 2))

    def test_concat_stack_split(self):
        x = onp.random.randn(2, 3).astype("float32")
        y = onp.random.randn(2, 3).astype("float32")
        assert_almost_equal(nd.concat(nd.array(x), nd.array(y), dim=1),
                            onp.concatenate([x, y], 1))
        assert_almost_equal(nd.stack(nd.array(x), nd.array(y), axis=0),
                            onp.stack([x, y]))
        parts = nd.split(nd.array(x), num_outputs=3, axis=1)
        assert len(parts) == 3 and parts[0].shape == (2, 1)

    def test_slice_ops(self):
        x = onp.arange(24).reshape(2, 3, 4).astype("float32")
        a = nd.array(x)
        assert_almost_equal(nd.slice(a, begin=(0, 1), end=(2, 3)),
                            x[0:2, 1:3])
        assert_almost_equal(nd.slice_axis(a, axis=2, begin=1, end=3),
                            x[:, :, 1:3])
        assert_almost_equal(a[1], x[1])
        assert_almost_equal(a[:, 1:2], x[:, 1:2])

    def test_tile_repeat_pad(self):
        x = onp.arange(6).reshape(2, 3).astype("float32")
        a = nd.array(x)
        assert_almost_equal(nd.tile(a, reps=(2, 2)), onp.tile(x, (2, 2)))
        assert_almost_equal(nd.repeat(a, repeats=2, axis=1),
                            onp.repeat(x, 2, 1))
        assert_almost_equal(
            nd.pad(a.reshape(1, 1, 2, 3), mode="constant",
                   pad_width=(0, 0, 0, 0, 1, 1, 2, 2)),
            onp.pad(x.reshape(1, 1, 2, 3), ((0, 0), (0, 0), (1, 1), (2, 2))))


class TestIndexing:
    def test_take_pick(self):
        x = onp.random.randn(5, 4).astype("float32")
        idx = onp.array([0, 2, 4])
        assert_almost_equal(nd.take(nd.array(x), nd.array(idx)), x[idx])
        pidx = onp.array([0, 1, 2, 3, 0])
        assert_almost_equal(
            nd.pick(nd.array(x), nd.array(pidx.astype("float32")), axis=1),
            x[onp.arange(5), pidx])

    def test_one_hot(self):
        out = nd.one_hot(nd.array([0.0, 2.0]), depth=3)
        assert_almost_equal(out, [[1, 0, 0], [0, 0, 1]])

    def test_gather_scatter(self):
        x = onp.random.randn(3, 4).astype("float32")
        ind = onp.array([[0, 2], [1, 3]])
        out = nd.gather_nd(nd.array(x), nd.array(ind))
        assert_almost_equal(out, x[ind[0], ind[1]])

    def test_advanced_index_grad(self):
        x = nd.array(onp.arange(6, dtype="float32"))
        x.attach_grad()
        with mx.autograd.record():
            y = (x[nd.array([1, 3])] * 2).sum()
        y.backward()
        assert_almost_equal(x.grad, [0, 2, 0, 2, 0, 0])


class TestSequence:
    def test_sequence_mask(self):
        x = onp.ones((4, 2, 3), "float32")
        out = nd.SequenceMask(nd.array(x), nd.array([2.0, 3.0]),
                              use_sequence_length=True, value=-1.0)
        ref = x.copy()
        ref[2:, 0] = -1
        ref[3:, 1] = -1
        assert_almost_equal(out, ref)

    def test_sequence_last(self):
        x = onp.random.randn(4, 2, 3).astype("float32")
        out = nd.SequenceLast(nd.array(x), nd.array([2.0, 4.0]),
                              use_sequence_length=True)
        assert_almost_equal(out, onp.stack([x[1, 0], x[3, 1]]))

    def test_sequence_reverse(self):
        x = onp.arange(8).reshape(4, 2, 1).astype("float32")
        out = nd.SequenceReverse(nd.array(x), nd.array([2.0, 4.0]),
                                 use_sequence_length=True)
        assert_almost_equal(out[:, 0, 0], [2, 0, 4, 6])
        assert_almost_equal(out[:, 1, 0], [7, 5, 3, 1])


class TestGradients:
    def test_numeric_gradients(self):
        a = onp.random.uniform(0.5, 1.5, (3, 4))
        b = onp.random.uniform(0.5, 1.5, (3, 4))
        check_numeric_gradient(lambda x: (x * x).sum(), [a])
        check_numeric_gradient(lambda x: nd.exp(x).sum(), [a])
        check_numeric_gradient(lambda x, y: (x * y + x / y).sum(), [a, b])
        check_numeric_gradient(
            lambda x: nd.sum(nd.sigmoid(x) * nd.tanh(x)), [a])

    def test_dot_grad(self):
        a = onp.random.randn(3, 4) * 0.5
        b = onp.random.randn(4, 2) * 0.5
        check_numeric_gradient(lambda x, y: nd.dot(x, y).sum(), [a, b])

    def test_softmax_grad(self):
        a = onp.random.randn(2, 5)
        check_numeric_gradient(
            lambda x: (nd.softmax(x) * nd.softmax(x)).sum(), [a])

    def test_concat_split_grad(self):
        a = onp.random.randn(2, 3)
        b = onp.random.randn(2, 3)
        def f(x, y):
            c = nd.concat(x, y, dim=1)
            parts = nd.split(c, num_outputs=2, axis=1)
            return (parts[0] * parts[1]).sum()
        check_numeric_gradient(f, [a, b])

    def test_blockgrad(self):
        x = nd.array([1.0, 2.0])
        x.attach_grad()
        with mx.autograd.record():
            y = (nd.BlockGrad(x * 2) * x).sum()
        y.backward()
        assert_almost_equal(x.grad, [2.0, 4.0])


class TestCreation:
    def test_creation(self):
        assert_almost_equal(nd.zeros((2, 2)), onp.zeros((2, 2)))
        assert_almost_equal(nd.ones((2, 2)), onp.ones((2, 2)))
        assert_almost_equal(nd.full((2,), 3.0), [3.0, 3.0])
        assert_almost_equal(nd.arange(0, 5), onp.arange(5, dtype="float32"))
        assert nd.eye(3).shape == (3, 3)
        x = nd.array([[1, 2]], dtype="int32")
        assert x.dtype == onp.int32
        assert_almost_equal(nd.ones_like(x), [[1, 1]])

    def test_float64_input_becomes_f32(self):
        x = nd.array(onp.zeros((2,), onp.float64))
        assert x.dtype == onp.float32


class TestBNHandWrittenBackward:
    """r4: _BatchNormStats backward is the hand-written two-pass closed
    form — it must match autodiff of the forward math exactly (both
    training and global-stats modes, fix_gamma on/off)."""

    @pytest.mark.parametrize("training,fix_gamma", [
        (True, True), (True, False), (False, True), (False, False)])
    def test_grad_matches_autodiff(self, training, fix_gamma):
        import jax
        import jax.numpy as jnp
        from mxnet_tpu.ops.nn import _bn_stats_core, _bn_stats_fwd_math
        rng = onp.random.RandomState(0)
        x = jnp.asarray(rng.randn(4, 3, 5, 5), jnp.float32)
        gamma = jnp.asarray(rng.rand(3) + 0.5, jnp.float32)
        beta = jnp.asarray(rng.randn(3), jnp.float32)
        mm = jnp.asarray(rng.randn(3) * 0.1, jnp.float32)
        mv = jnp.asarray(rng.rand(3) + 0.5, jnp.float32)
        args = (1e-5, 0.9, fix_gamma, False, 1, training)

        def loss_custom(x, g, b):
            out = _bn_stats_core(x, g, b, mm, mv, *args)[0]
            return jnp.sum(out * out)

        def loss_auto(x, g, b):
            out = _bn_stats_fwd_math(x, g, b, mm, mv, *args)[0]
            return jnp.sum(out * out)

        gc = jax.grad(loss_custom, argnums=(0, 1, 2))(x, gamma, beta)
        ga = jax.grad(loss_auto, argnums=(0, 1, 2))(x, gamma, beta)
        for c, a, nm in zip(gc, ga, ("dx", "dgamma", "dbeta")):
            onp.testing.assert_allclose(onp.asarray(c), onp.asarray(a),
                                        rtol=2e-4, atol=2e-5, err_msg=nm)


class TestConvolutionNHWC1x1:
    """NHWC 1x1 stride-1 ``Convolution`` (every bottleneck conv of an NHWC
    ResNet) lowers to ``lax.conv_general_dilated`` like every other conv:
    output and both gradients must equal the channel einsum's."""

    @pytest.mark.parametrize("shape", [(2, 8, 8, 64, 256),
                                       (1, 4, 4, 128, 32),
                                       (4, 8, 8, 256, 64)])
    def test_matches_channel_einsum(self, shape):
        import jax
        import jax.numpy as jnp
        from mxnet_tpu.ops import nn as nn_ops
        n, h, w_, ci, co = shape
        rng = onp.random.RandomState(0)
        x = jnp.asarray(rng.randn(n, h, w_, ci), jnp.float32)
        w = jnp.asarray(rng.randn(co, ci, 1, 1) * 0.05, jnp.float32)

        def conv(x, w):
            return nn_ops.Convolution.__wrapped__(
                x, w, kernel=(1, 1), num_filter=co, no_bias=True,
                layout="NHWC")

        def ref(x, w):
            return jnp.einsum("bhwc,oc->bhwo", x, w[:, :, 0, 0])

        y, vjp = jax.vjp(conv, x, w)
        y_ref, vjp_ref = jax.vjp(ref, x, w)
        onp.testing.assert_allclose(y, y_ref, rtol=1e-5, atol=1e-5)
        dy = jnp.asarray(rng.randn(*y.shape), jnp.float32)
        (dx, dw), (dx_ref, dw_ref) = vjp(dy), vjp_ref(dy)
        onp.testing.assert_allclose(dx, dx_ref, rtol=2e-4, atol=1e-4)
        onp.testing.assert_allclose(dw, dw_ref, rtol=2e-4, atol=1e-3)
        assert dw.dtype == w.dtype and dw.shape == w.shape
