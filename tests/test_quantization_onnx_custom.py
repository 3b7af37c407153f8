"""Quantization + ONNX export + custom-op tests (reference
tests/python/quantization/, tests/python-pytest/onnx/,
tests/python/unittest/test_operator.py::test_custom_op coverage)."""
import json
import os

import numpy as onp
import pytest

import mxnet_tpu as mx
import mxnet_tpu.operator as mop
from mxnet_tpu import autograd, gluon
from mxnet_tpu.base import MXNetError
from mxnet_tpu.contrib.quantization import quantize_net, QuantizedDense
from mxnet_tpu.ops.quantization import optimal_threshold_kl


class TestQuantizeOps:
    def test_quantize_dequantize_roundtrip(self):
        x = mx.nd.array(onp.linspace(-2, 2, 16).astype(onp.float32))
        q, mn, mxr = mx.nd._contrib_quantize_v2(x)
        assert str(q.dtype) == "int8"
        deq = mx.nd._contrib_dequantize(q, mn, mxr)
        onp.testing.assert_allclose(deq.asnumpy(), x.asnumpy(), atol=0.02)

    def test_calibrated_range_clips(self):
        x = mx.nd.array(onp.array([0.1, 5.0], onp.float32))
        q, mn, mxr = mx.nd._contrib_quantize_v2(x, min_calib_range=-1.0,
                                                max_calib_range=1.0)
        assert int(q.asnumpy()[1]) == 127  # clipped at the calib range

    def test_int8_matmul_matches_fp32(self):
        rng = onp.random.RandomState(0)
        a = rng.rand(8, 16).astype(onp.float32) - 0.5
        b = rng.rand(4, 16).astype(onp.float32) - 0.5
        qa, _, amax_a = mx.nd._contrib_quantize_v2(mx.nd.array(a))
        qb, _, amax_b = mx.nd._contrib_quantize_v2(mx.nd.array(b))
        acc = mx.nd.quantized_matmul_int8(qa, qb, transpose_b=True)
        scale = (float(amax_a.asnumpy()[0]) * float(amax_b.asnumpy()[0])
                 / (127.0 * 127.0))
        out = acc.asnumpy().astype(onp.float32) * scale
        onp.testing.assert_allclose(out, a @ b.T, atol=0.05)

    def test_kl_threshold_reasonable(self):
        rng = onp.random.RandomState(0)
        data = rng.normal(0, 1, 100000)
        hist, edges = onp.histogram(data, bins=1001, range=(-8, 8))
        t = optimal_threshold_kl(hist, edges)
        # optimal clip for a unit gaussian is far below the 8-sigma tail
        assert 1.0 < t < 8.0


class TestQuantizeNet:
    def test_mlp_accuracy_preserved(self):
        # pin the init stream: the 0.9 argmax-agreement bound on 64
        # samples is draw-sensitive, and an unseeded root key makes the
        # test's pass/fail depend on suite composition
        mx.random.seed(0)
        rng = onp.random.RandomState(0)
        net = gluon.nn.HybridSequential()
        net.add(gluon.nn.Dense(32, activation="relu"), gluon.nn.Dense(10))
        net.initialize(mx.init.Xavier())
        X = mx.nd.array(rng.rand(64, 20).astype(onp.float32))
        ref = net(X).asnumpy()
        qnet = quantize_net(net, calib_data=[X], calib_mode="naive")
        assert any(isinstance(c, QuantizedDense)
                   for c in qnet._children.values())
        out = qnet(X).asnumpy()
        rel = onp.abs(out - ref).max() / onp.abs(ref).max()
        assert rel < 0.05
        assert (out.argmax(1) == ref.argmax(1)).mean() > 0.9

    def test_entropy_mode_runs(self):
        net = gluon.nn.HybridSequential()
        net.add(gluon.nn.Dense(8))
        net.initialize(mx.init.Xavier())
        X = mx.nd.array(onp.random.rand(32, 6).astype(onp.float32))
        qnet = quantize_net(net, calib_data=[X], calib_mode="entropy")
        assert qnet(X).shape == (32, 8)

    def test_requires_calib_data(self):
        net = gluon.nn.Dense(4)
        with pytest.raises(MXNetError):
            quantize_net(net)


class TestONNXExport:
    def test_export_conv_net(self, tmp_path):
        net = gluon.nn.HybridSequential()
        net.add(gluon.nn.Conv2D(4, 3, padding=1), gluon.nn.BatchNorm(),
                gluon.nn.Activation("relu"), gluon.nn.MaxPool2D(2),
                gluon.nn.Flatten(), gluon.nn.Dense(10))
        net.initialize(mx.init.Xavier())
        x = mx.nd.array(onp.random.rand(1, 3, 8, 8).astype(onp.float32))
        net(x)
        prefix = str(tmp_path / "m")
        net.export(prefix)
        out = mx.onnx.export_model(
            prefix + "-symbol.json", prefix + "-0000.params",
            input_shapes=[("data", (1, 3, 8, 8))],
            onnx_file_path=str(tmp_path / "m.onnx"))
        g = json.load(open(out))
        ops = [n["op_type"] for n in g["graph"]["nodes"]]
        assert {"Conv", "BatchNormalization", "Relu", "MaxPool",
                "Gemm"} <= set(ops)
        assert g["graph"]["inputs"][0]["name"] == "data"
        assert len(g["graph"]["initializers"]) >= 6

    def test_unsupported_op_raises(self, tmp_path):
        s = mx.sym.erfinv(mx.sym.var("x"))
        with pytest.raises(MXNetError):
            mx.onnx.export_model(s, {}, onnx_file_path=str(tmp_path / "x"))


class TestCustomOp:
    def test_forward_backward(self):
        @mop.register("t_sigmoid")
        class P(mop.CustomOpProp):
            def create_operator(self, ctx, in_shapes, in_dtypes):
                class O(mop.CustomOp):
                    def forward(self, is_train, req, in_data, out_data, aux):
                        x = in_data[0]
                        self.assign(out_data[0], req[0],
                                    1.0 / (1.0 + (-x).exp()))

                    def backward(self, req, out_grad, in_data, out_data,
                                 in_grad, aux):
                        y = out_data[0]
                        self.assign(in_grad[0], req[0],
                                    out_grad[0] * y * (1 - y))
                return O()

        x = mx.nd.array(onp.array([0.0, 1.0, -1.0], onp.float32))
        x.attach_grad()
        with autograd.record():
            y = mx.nd.Custom(x, op_type="t_sigmoid")
        y.backward(mx.nd.ones(3))
        sig = 1 / (1 + onp.exp(-x.asnumpy()))
        onp.testing.assert_allclose(y.asnumpy(), sig, rtol=1e-6)
        onp.testing.assert_allclose(x.grad.asnumpy(), sig * (1 - sig),
                                    rtol=1e-5)

    def test_unregistered_raises(self):
        with pytest.raises(MXNetError):
            mx.nd.Custom(mx.nd.ones(2), op_type="nope")

    def test_grad_req_add(self):
        @mop.register("t_double")
        class P(mop.CustomOpProp):
            def create_operator(self, ctx, in_shapes, in_dtypes):
                class O(mop.CustomOp):
                    def forward(self, is_train, req, in_data, out_data, aux):
                        self.assign(out_data[0], req[0], in_data[0] * 2)

                    def backward(self, req, out_grad, in_data, out_data,
                                 in_grad, aux):
                        self.assign(in_grad[0], req[0], out_grad[0] * 2)
                return O()

        x = mx.nd.ones(3)
        x.attach_grad(grad_req="add")
        for _ in range(2):
            with autograd.record():
                y = mx.nd.Custom(x, op_type="t_double")
            y.backward(mx.nd.ones(3))
        onp.testing.assert_allclose(x.grad.asnumpy(), onp.full(3, 4.0))


class TestONNXImport:
    """onnx2mx importer (VERDICT r1 item 6): round-trip numerics through
    export_model -> import_model -> Executor."""

    def _roundtrip(self, net, x, tmp_path, in_shape):
        net.initialize(mx.init.Xavier())
        ref = net(x)
        prefix = str(tmp_path / "m")
        net.export(prefix)
        path = mx.onnx.export_model(
            prefix + "-symbol.json", prefix + "-0000.params",
            input_shapes=[("data", in_shape)],
            onnx_file_path=str(tmp_path / "m.onnx"))
        sym, arg_params, aux_params = mx.onnx.import_model(path)
        exe = sym.bind(args={**arg_params, "data": x})
        out = exe.forward()[0]
        onp.testing.assert_allclose(out.asnumpy(), ref.asnumpy(),
                                    rtol=1e-4, atol=1e-4)
        return sym, arg_params

    def test_mlp_roundtrip(self, tmp_path):
        net = gluon.nn.HybridSequential()
        net.add(gluon.nn.Dense(16, activation="relu"), gluon.nn.Dense(10))
        x = mx.nd.array(onp.random.rand(3, 20).astype(onp.float32))
        self._roundtrip(net, x, tmp_path, (3, 20))

    def test_conv_bn_pool_roundtrip(self, tmp_path):
        net = gluon.nn.HybridSequential()
        net.add(gluon.nn.Conv2D(4, 3, padding=1), gluon.nn.BatchNorm(),
                gluon.nn.Activation("relu"), gluon.nn.MaxPool2D(2),
                gluon.nn.Flatten(), gluon.nn.Dense(10))
        x = mx.nd.array(onp.random.rand(2, 3, 8, 8).astype(onp.float32))
        net.initialize(mx.init.Xavier())
        net(x)  # settle BN shapes
        self._roundtrip(net, x, tmp_path, (2, 3, 8, 8))

    def test_zoo_model_roundtrip(self, tmp_path):
        """An exported model-zoo network must survive the ONNX round
        trip (the VERDICT's named acceptance check)."""
        from mxnet_tpu.gluon.model_zoo.vision import get_resnet
        net = get_resnet(1, 18, thumbnail=True, classes=10)
        x = mx.nd.array(onp.random.rand(1, 3, 32, 32).astype(onp.float32))
        net.initialize(mx.init.Xavier())
        net(x)
        self._roundtrip(net, x, tmp_path, (1, 3, 32, 32))

    def test_gelu_roundtrip_matches_runtime_variant(self, tmp_path):
        """Activation('gelu') is the TANH approximation at runtime; the
        exporter must emit the matching decomposition (erf would drift up
        to ~5e-4 at |x|~2).  Large activations on purpose — the variants
        coincide near 0."""
        net = gluon.nn.HybridSequential()
        net.add(gluon.nn.Dense(16, activation="gelu", in_units=8))
        x = mx.nd.array((onp.random.RandomState(0).rand(4, 8) * 6 - 3)
                        .astype(onp.float32))
        self._roundtrip(net, x, tmp_path, (4, 8))

    def test_bert_tiny_roundtrip(self, tmp_path):
        """VERDICT r3 item 8: the transformer family survives the ONNX
        round trip — BERT-tiny export -> import -> matching MLM logits
        (2e-4: the fused kernel computes exp(s-m)@v/l while the portable
        decomposition computes softmax(s)@v — same math, different f32
        rounding).  Exercises the r4 converters: flash_attention
        decomposition (MatMul/Mul/Softmax/MatMul with a static
        1/sqrt(head_dim) from the InferShape pass), gelu erf
        decomposition, slice_axis->Slice, broadcast_to->Expand, and
        dot(transpose_b) for the tied MLM head."""
        from mxnet_tpu.models import BERTModel, BERTConfig
        mx.random.seed(0)
        cfg = BERTConfig(vocab_size=211, max_length=32, num_layers=2,
                         units=32, num_heads=2, hidden_size=64,
                         dropout=0.0)
        bert = BERTModel(cfg, use_pooler=False, use_mlm=True)
        bert.initialize(mx.init.Normal(0.05))
        toks = mx.nd.array(
            onp.random.RandomState(0).randint(0, 211, (2, 16)),
            dtype="int32")
        ref = bert(toks)[-1]                       # MLM logits
        bert.hybridize()
        bert(toks)
        prefix = str(tmp_path / "bert")
        bert.export(prefix)
        path = mx.onnx.export_model(
            prefix + "-symbol.json", prefix + "-0000.params",
            input_shapes=[("data", (2, 16))], input_types="int32",
            onnx_file_path=str(tmp_path / "bert.onnx"))
        sym, arg_params, aux_params = mx.onnx.import_model(path)
        exe = sym.bind(args={**arg_params, "data": toks})
        outs = exe.forward()
        onp.testing.assert_allclose(outs[-1].asnumpy(), ref.asnumpy(),
                                    rtol=2e-4, atol=2e-4)

    def test_mha_exports_the_graph_it_did(self, tmp_path):
        """``MultiHeadAttention`` calls ``flash_attention_qkv`` on the
        packed projection (PR 34); its converter emits what the block held
        there before — reshape / transpose / slice into heads,
        ``flash_attention``'s decomposition, transpose and reshape back —
        so the exported node sequence is the one commit b701dcd gave."""
        from mxnet_tpu.models import MultiHeadAttention
        mx.random.seed(0)
        mha = MultiHeadAttention(32, 4)
        mha.initialize()
        x = mx.nd.array(onp.random.RandomState(0).randn(2, 8, 32)
                        .astype(onp.float32))
        ref = mha(x)
        mha.hybridize()
        mha(x)
        prefix = str(tmp_path / "mha")
        mha.export(prefix)
        path = mx.onnx.export_model(
            prefix + "-symbol.json", prefix + "-0000.params",
            input_shapes=[("data", (2, 8, 32))],
            onnx_file_path=str(tmp_path / "mha.onnx"))
        if path.endswith(".json"):
            nodes = json.load(open(path))["graph"]["nodes"]
            assert [n["op_type"] for n in nodes] == [
                "Gemm", "Reshape", "Transpose", "Slice", "Reshape", "Slice",
                "Reshape", "Slice", "Reshape", "Transpose", "MatMul", "Mul",
                "Softmax", "MatMul", "Transpose", "Reshape", "Gemm"]
            assert [n["attrs"]["perm"] for n in nodes
                    if n["op_type"] == "Transpose"] == [
                [2, 0, 3, 1, 4], [0, 1, 3, 2], [0, 2, 1, 3]]
        sym, arg_params, aux_params = mx.onnx.import_model(path)
        outs = sym.bind(args={**arg_params, "data": x}).forward()
        onp.testing.assert_allclose(outs[-1].asnumpy(), ref.asnumpy(),
                                    rtol=2e-4, atol=2e-5)

    def test_unknown_op_raises(self, tmp_path):
        bad = {"opset": 13, "graph": {
            "nodes": [{"op_type": "NoSuchOp", "inputs": ["x"],
                       "outputs": ["y"], "name": "n0", "attrs": {}}],
            "inputs": [{"name": "x"}], "outputs": [{"name": "y"}],
            "initializers": {}}}
        p = tmp_path / "bad.onnx.json"
        p.write_text(json.dumps(bad))
        with pytest.raises(MXNetError, match="no importer"):
            mx.onnx.import_model(str(p))


class TestQuantizedConv:
    """INT8 conv + quantize_net over a conv net (VERDICT r1 item 7)."""

    def test_quantized_conv_int8_exact(self):
        rng = onp.random.RandomState(0)
        x = rng.randint(-127, 128, (2, 3, 8, 8)).astype(onp.int8)
        w = rng.randint(-127, 128, (4, 3, 3, 3)).astype(onp.int8)
        out = mx.nd.quantized_conv_int8(
            mx.nd.array(x, dtype="int8"), mx.nd.array(w, dtype="int8"),
            pad=(1, 1))
        assert out.dtype == onp.int32
        # int32 accumulation is EXACT — compare vs float conv
        import jax.numpy as jnp
        from jax import lax
        ref = lax.conv_general_dilated(
            x.astype("float32"), w.astype("float32"), (1, 1),
            [(1, 1), (1, 1)],
            dimension_numbers=lax.conv_dimension_numbers(
                x.shape, w.shape, ("NCHW", "OIHW", "NCHW")))
        onp.testing.assert_array_equal(out.asnumpy(),
                                       onp.asarray(ref, onp.int32))

    def test_quantized_conv2d_block_close_to_fp32(self):
        from mxnet_tpu.contrib.quantization import QuantizedConv2D
        rng = onp.random.RandomState(1)
        conv = gluon.nn.Conv2D(8, 3, padding=1, in_channels=3)
        conv.initialize(mx.init.Xavier())
        x = mx.nd.array(rng.rand(2, 3, 16, 16).astype(onp.float32))
        ref = conv(x)
        q = QuantizedConv2D(conv, float(onp.abs(x.asnumpy()).max()))
        out = q(x)
        err = onp.abs(out.asnumpy() - ref.asnumpy()).max()
        scale = onp.abs(ref.asnumpy()).max()
        assert err / scale < 0.03, (err, scale)

    def test_quantize_net_resnet_agreement(self):
        """quantize_net over a zoo ResNet-18: conv+dense layers swapped,
        top-1 agreement with fp32 >= 90% on structured inputs (the
        accuracy-drop assertion; real-dataset accuracy needs data the
        sandbox doesn't ship)."""
        from mxnet_tpu.gluon.model_zoo.vision import get_resnet
        from mxnet_tpu.contrib.quantization import (quantize_net,
                                                    QuantizedConv2D,
                                                    QuantizedDense)
        mx.random.seed(0)
        net = get_resnet(1, 18, thumbnail=True, classes=10)
        net.initialize(mx.init.Xavier())
        rng = onp.random.RandomState(0)
        # smooth structured inputs (CIFAR-normalized scale)
        base = rng.rand(32, 3, 32, 32).astype(onp.float32)
        for ax in (2, 3):
            base = (onp.roll(base, 1, ax) + base +
                    onp.roll(base, -1, ax)) / 3.0
        x = mx.nd.array((base - 0.5) * 4.0)
        ref = net(x).asnumpy()
        calib = [mx.nd.array((base[i:i + 8] - 0.5) * 4.0)
                 for i in range(0, 32, 8)]
        qnet = quantize_net(net, calib_data=calib, calib_mode="naive")
        n_q = [0, 0]

        def count(b):
            for c in b._children.values():
                if isinstance(c, QuantizedConv2D):
                    n_q[0] += 1
                elif isinstance(c, QuantizedDense):
                    n_q[1] += 1
                else:
                    count(c)
        count(qnet)
        assert n_q[0] >= 10, f"conv layers quantized: {n_q[0]}"
        out = qnet(x).asnumpy()
        agree = (out.argmax(1) == ref.argmax(1)).mean()
        assert agree >= 0.9, agree


class TestONNXShapeFreeDot:
    """ADVICE r4: a plain 2-D no-transpose dot must export without
    input_shapes (MatMul is semantically identical for rank 2); the
    transpose flags still demand shape proof."""

    def test_plain_dot_exports_without_shapes(self, tmp_path):
        s = mx.sym.dot(mx.sym.var("a"), mx.sym.var("b"))
        out = mx.onnx.export_model(
            s, {}, onnx_file_path=str(tmp_path / "d.onnx"))
        g = json.load(open(out))
        assert "MatMul" in [n["op_type"] for n in g["graph"]["nodes"]]

    def test_transposed_dot_without_shapes_raises(self, tmp_path):
        s = mx.sym.dot(mx.sym.var("a"), mx.sym.var("b"), transpose_b=True)
        with pytest.raises(MXNetError):
            mx.onnx.export_model(
                s, {}, onnx_file_path=str(tmp_path / "dt.onnx"))
