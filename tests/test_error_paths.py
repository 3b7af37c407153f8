"""Error-path probes (the verify skill's 'worthwhile probes' + reference
error-semantics parity): clear MXNetError diagnostics instead of silent
corruption or raw jax tracebacks."""
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd
from mxnet_tpu.base import MXNetError


class TestErrorPaths:
    def test_double_backward_without_retain_raises(self):
        x = mx.nd.array(onp.ones(3, onp.float32))
        x.attach_grad()
        with autograd.record():
            y = (x * x).sum()
        y.backward()
        with pytest.raises(MXNetError):
            y.backward()

    def test_corrupt_params_file(self, tmp_path):
        p = tmp_path / "bad.params"
        p.write_bytes(b"\x00" * 64)
        with pytest.raises(MXNetError, match="magic"):
            mx.nd.load(str(p))

    def test_out_of_range_context(self):
        import jax
        accel = [d for d in jax.devices() if d.platform != "cpu"]
        if accel:
            with pytest.raises(MXNetError):
                mx.tpu(len(accel) + 5).jax_device()
        else:
            # no accelerator: an accelerator context is an error naming
            # the platform found, never a silent host device
            for ctx in (mx.tpu(0), mx.gpu(0), mx.tpu(99)):
                with pytest.raises(MXNetError, match="cpu"):
                    ctx.jax_device()

    def test_uninitialized_parameter_data(self):
        from mxnet_tpu.gluon import Parameter
        p = Parameter("w", shape=(3,))
        with pytest.raises(MXNetError):
            p.data()

    def test_kvstore_unknown_type(self):
        with pytest.raises(MXNetError):
            mx.kv.create("bogus")

    def test_kvstore_push_uninit_key(self):
        kv = mx.kv.create("local")
        with pytest.raises(MXNetError):
            kv.push(42, mx.nd.ones(2))

    def test_shape_mismatch_load_parameters(self, tmp_path):
        from mxnet_tpu import gluon
        a = gluon.nn.Dense(4, in_units=3)
        a.initialize()
        f = str(tmp_path / "p.params")
        a.save_parameters(f)
        b = gluon.nn.Dense(4, in_units=3)
        b.initialize()
        b.load_parameters(f)  # ok
        c = gluon.nn.Dense(4, in_units=5)
        c.initialize()
        with pytest.raises(Exception):
            c.load_parameters(f)

    def test_naive_engine_mode_still_correct(self, monkeypatch):
        monkeypatch.setenv("MXNET_ENGINE_TYPE", "NaiveEngine")
        a = mx.nd.array(onp.arange(6, dtype=onp.float32).reshape(2, 3))
        out = mx.nd.dot(a, a.T)
        onp.testing.assert_allclose(
            out.asnumpy(), a.asnumpy() @ a.asnumpy().T, rtol=1e-6)

    def test_seeded_reproducibility(self):
        mx.random.seed(42)
        a = mx.nd.random_normal(shape=(4,)).asnumpy()
        mx.random.seed(42)
        b = mx.nd.random_normal(shape=(4,)).asnumpy()
        onp.testing.assert_array_equal(a, b)


def test_gpu_memory_info_gauge():
    """HBM gauge (reference mx.context.gpu_memory_info): returns a
    (free, total) pair with free <= total; without an accelerator there
    is no HBM to gauge and the call raises like any accelerator context
    (the reference raises without CUDA)."""
    import jax
    import mxnet_tpu as mx
    if all(d.platform == "cpu" for d in jax.devices()):
        with pytest.raises(MXNetError):
            mx.context.gpu_memory_info(0)
        return
    free, total = mx.context.gpu_memory_info(0)
    assert isinstance(free, int) and isinstance(total, int)
    assert 0 <= free <= total
