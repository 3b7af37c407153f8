"""Sparse kernels (csr/row_sparse): goldens vs scipy + gradients.

Reference test model (SURVEY.md §4-of-reference test strategy): op-level
golden tests vs NumPy + gradient checks on the registered kernels."""
import numpy as onp
import pytest
import scipy.sparse as sp

import mxnet_tpu as mx
from mxnet_tpu import autograd, nd
from mxnet_tpu.ndarray import sparse


def _rand_csr(m, n, density=0.3, seed=0):
    rng = onp.random.RandomState(seed)
    mat = sp.random(m, n, density=density, random_state=rng,
                    format="csr", dtype=onp.float32)
    return mat


class TestCSR:
    def test_construct_lazy(self):
        mat = _rand_csr(8, 6)
        a = sparse.csr_matrix((mat.data, mat.indices, mat.indptr),
                              shape=mat.shape)
        # construction must NOT materialize the dense mirror
        assert a._dense_cache is None
        assert a.stype == "csr"
        assert a.shape == (8, 6)
        onp.testing.assert_allclose(a.asnumpy(), mat.toarray(), rtol=1e-6)

    def test_dot_golden(self):
        mat = _rand_csr(16, 12)
        rhs = onp.random.RandomState(1).randn(12, 5).astype(onp.float32)
        a = sparse.csr_matrix((mat.data, mat.indices, mat.indptr),
                              shape=mat.shape)
        out = sparse.dot(a, nd.array(rhs))
        onp.testing.assert_allclose(out.asnumpy(), mat @ rhs, rtol=1e-5)

    def test_dot_transpose_golden(self):
        mat = _rand_csr(16, 12, seed=2)
        rhs = onp.random.RandomState(3).randn(16, 7).astype(onp.float32)
        a = sparse.csr_matrix((mat.data, mat.indices, mat.indptr),
                              shape=mat.shape)
        out = sparse.dot(a, nd.array(rhs), transpose_a=True)
        onp.testing.assert_allclose(out.asnumpy(), mat.T @ rhs, rtol=1e-5,
                                    atol=1e-6)

    def test_dot_grad_wrt_dense(self):
        mat = _rand_csr(10, 8, seed=4)
        a = sparse.csr_matrix((mat.data, mat.indices, mat.indptr),
                              shape=mat.shape)
        rhs = nd.array(onp.random.RandomState(5).randn(8, 4)
                       .astype(onp.float32))
        rhs.attach_grad()
        with autograd.record():
            out = sparse.dot(a, rhs)
            loss = out.sum()
        loss.backward()
        # d/d(rhs) of sum(csr @ rhs) = csr^T @ ones
        expect = mat.T @ onp.ones((10, 4), onp.float32)
        onp.testing.assert_allclose(rhs.grad.asnumpy(), expect, rtol=1e-5,
                                    atol=1e-6)

    def test_elemwise_union(self):
        a_s = _rand_csr(6, 6, seed=6)
        b_s = _rand_csr(6, 6, seed=7)
        a = sparse.csr_matrix((a_s.data, a_s.indices, a_s.indptr),
                              shape=a_s.shape)
        b = sparse.csr_matrix((b_s.data, b_s.indices, b_s.indptr),
                              shape=b_s.shape)
        out = sparse.add(a, b)
        assert out.stype == "csr"
        onp.testing.assert_allclose(out.asnumpy(),
                                    (a_s + b_s).toarray(), rtol=1e-6)
        out = sparse.multiply(a, b)
        assert out.stype == "csr"
        onp.testing.assert_allclose(out.asnumpy(),
                                    a_s.multiply(b_s).toarray(), rtol=1e-6)

    def test_bf16_refresh_and_elemwise_keep_dtype(self):
        """scipy has no bf16 — the host round-trips must still work and
        must NOT silently promote to f32 (the round-1 dtype-leak trap)."""
        import jax.numpy as jnp
        mat = _rand_csr(6, 6, seed=9)
        a = sparse.csr_matrix((mat.data, mat.indices, mat.indptr),
                              shape=mat.shape, dtype="bfloat16")
        assert a.dtype == onp.dtype("bfloat16") if hasattr(
            onp, "bfloat16") else str(a.dtype) == "bfloat16"
        out = sparse.add(a, a)
        assert str(out.dtype) == "bfloat16"
        # rebind the mirror -> components re-derive through f32 scipy
        a._data = jnp.asarray(a._data) * 2
        assert str(a.data.dtype) == "bfloat16"
        onp.testing.assert_allclose(
            onp.asarray(a.asnumpy(), onp.float32),
            onp.asarray((2 * mat).toarray().astype("float32")), rtol=2e-2,
            atol=1e-2)

    def test_csr_shape_mismatch_raises(self):
        a_s, b_s = _rand_csr(4, 4), _rand_csr(5, 4, seed=1)
        a = sparse.csr_matrix((a_s.data, a_s.indices, a_s.indptr),
                              shape=a_s.shape)
        b = sparse.csr_matrix((b_s.data, b_s.indices, b_s.indptr),
                              shape=b_s.shape)
        with pytest.raises(mx.base.MXNetError):
            sparse.add(a, b)

    def test_cast_storage_round_trip(self):
        dense = onp.random.RandomState(8).randn(5, 5).astype(onp.float32)
        dense[dense < 0.5] = 0
        a = sparse.cast_storage(nd.array(dense), "csr")
        assert a.stype == "csr"
        back = sparse.cast_storage(a, "default")
        assert back.stype == "default"
        onp.testing.assert_allclose(back.asnumpy(), dense, rtol=1e-6)


class TestOperatorDispatch:
    """Python operators on sparse operands must route storage-aware
    (reference FComputeEx dispatch): sparse op same-kind-sparse keeps
    the storage type via the union kernels; mixed/scalar pairings
    densify (the reference's storage fallback) instead of crashing."""

    def test_rs_plus_rs_stays_row_sparse(self):
        a = sparse.row_sparse_array(
            (onp.arange(6, dtype=onp.float32).reshape(2, 3),
             onp.array([1, 4])), shape=(6, 3))
        b = sparse.row_sparse_array(
            (onp.ones((2, 3), onp.float32), onp.array([4, 5])),
            shape=(6, 3))
        s = a + b
        assert s.stype == "row_sparse"
        want = onp.zeros((6, 3), onp.float32)
        want[1] = [0, 1, 2]
        want[4] = [4, 5, 6]
        want[5] = 1
        onp.testing.assert_allclose(s.asnumpy(), want)
        m = a * b
        assert m.stype == "row_sparse"
        wm = onp.zeros((6, 3), onp.float32)
        wm[4] = [3, 4, 5]
        onp.testing.assert_allclose(m.asnumpy(), wm)

    def test_csr_minus_csr_stays_csr(self):
        a_s = _rand_csr(6, 6, seed=20)
        b_s = _rand_csr(6, 6, seed=21)
        a = sparse.csr_matrix((a_s.data, a_s.indices, a_s.indptr),
                              shape=a_s.shape)
        b = sparse.csr_matrix((b_s.data, b_s.indices, b_s.indptr),
                              shape=b_s.shape)
        out = a - b
        assert out.stype == "csr"
        onp.testing.assert_allclose(out.asnumpy(),
                                    (a_s - b_s).toarray(), rtol=1e-6)

    def test_mixed_densifies_scalar_scale_keeps_storage(self):
        a = sparse.row_sparse_array(
            (onp.ones((1, 3), onp.float32), onp.array([2])), shape=(4, 3))
        m = a + nd.ones((4, 3))
        assert m.stype == "default"
        onp.testing.assert_allclose(m.asnumpy()[2], [2, 2, 2])
        # scalar mul/div preserve storage (reference _mul_scalar
        # FComputeEx): no dense mirror materialization
        for out, want in [(a * 2.0, 2.0), (2.0 * a, 2.0), (a / 2.0, 0.5)]:
            assert out.stype == "row_sparse"
            assert out._dense_cache is None  # mirror never built
            onp.testing.assert_allclose(out.asnumpy()[2], [want] * 3)
        sc = 2.0 / a  # reverse div is not a scale -> dense fallback
        assert sc.stype == "default"
        # scalar add destroys sparsity -> dense
        assert (a + 1.0).stype == "default"
        # csr scalar scale also keeps storage
        c = sparse.csr_matrix(
            (onp.array([3.0], onp.float32), onp.array([1]),
             onp.array([0, 1, 1])), shape=(2, 3))
        cs = c * 3.0
        assert cs.stype == "csr" and cs._dense_cache is None
        onp.testing.assert_allclose(cs.asnumpy()[0, 1], 9.0)

    def test_broadcast_shapes_densify_not_crash(self):
        a = sparse.row_sparse_array(
            (onp.ones((2, 3), onp.float32), onp.array([0, 2])),
            shape=(4, 3))
        b = sparse.row_sparse_array(
            (onp.full((1, 3), 2.0, onp.float32), onp.array([0])),
            shape=(1, 3))
        out = a * b  # (4,3)*(1,3): union kernels can't broadcast ->
        assert out.stype == "default"  # dense fallback, correct values
        want = onp.zeros((4, 3), onp.float32)
        want[0] = want[2] = 2.0
        onp.testing.assert_allclose(out.asnumpy(), want)

    def test_operator_grads_flow_under_record(self):
        """Under autograd.record() the operators must take the RECORDED
        dense path (the union kernels build results structurally and
        record nothing) — gradients land on the sparse operands as
        dense grads, not silent zeros."""
        from mxnet_tpu import autograd
        a = sparse.row_sparse_array(
            (onp.arange(6, dtype=onp.float32).reshape(2, 3),
             onp.array([1, 4])), shape=(6, 3))
        b = sparse.row_sparse_array(
            (onp.ones((2, 3), onp.float32), onp.array([4, 5])),
            shape=(6, 3))
        a.attach_grad()
        b.attach_grad()
        with autograd.record():
            s = a * b
            loss = nd.sum(s)
        loss.backward()
        # d(sum(a*b))/da = dense(b); nonzero exactly on b's rows
        want_da = onp.zeros((6, 3), onp.float32)
        want_da[4] = want_da[5] = 1.0
        onp.testing.assert_allclose(a.grad.asnumpy(), want_da)
        # d(sum(a*b))/db = dense(a): rows 1 and 4
        want_db = onp.zeros((6, 3), onp.float32)
        want_db[1] = [0, 1, 2]
        want_db[4] = [3, 4, 5]
        onp.testing.assert_allclose(b.grad.asnumpy(), want_db)

    def test_huge_row_count_guard(self):
        class FakeRS(sparse.RowSparseNDArray):
            def __init__(self):
                pass

            @property
            def shape(self):
                return (2 ** 31, 3)

        from mxnet_tpu.base import MXNetError
        with pytest.raises(MXNetError, match="int32 row keys"):
            sparse._rs_elemwise("add", FakeRS(), FakeRS())
        with pytest.raises(MXNetError, match="int32 row indices"):
            sparse.retain(FakeRS(), nd.array(onp.array([1])))


class TestRowSparse:
    def test_dot_golden(self):
        vals = onp.random.RandomState(0).randn(3, 6).astype(onp.float32)
        idx = onp.array([1, 4, 7])
        a = sparse.row_sparse_array((vals, idx), shape=(9, 6))
        assert a._dense_cache is None  # lazy
        rhs = onp.random.RandomState(1).randn(6, 4).astype(onp.float32)
        out = sparse.dot(a, nd.array(rhs))
        dense = onp.zeros((9, 6), onp.float32)
        dense[idx] = vals
        onp.testing.assert_allclose(out.asnumpy(), dense @ rhs, rtol=1e-5)

    def test_dot_transpose_golden(self):
        vals = onp.random.RandomState(2).randn(3, 6).astype(onp.float32)
        idx = onp.array([0, 2, 5])
        a = sparse.row_sparse_array((vals, idx), shape=(7, 6))
        rhs = onp.random.RandomState(3).randn(7, 4).astype(onp.float32)
        out = sparse.dot(a, nd.array(rhs), transpose_a=True)
        dense = onp.zeros((7, 6), onp.float32)
        dense[idx] = vals
        onp.testing.assert_allclose(out.asnumpy(), dense.T @ rhs,
                                    rtol=1e-5, atol=1e-6)

    def test_retain(self):
        vals = onp.arange(12, dtype=onp.float32).reshape(4, 3)
        idx = onp.array([0, 2, 5, 6])
        a = sparse.row_sparse_array((vals, idx), shape=(8, 3))
        kept = sparse.sparse_retain(a, nd.array(onp.array([2, 6])))
        onp.testing.assert_array_equal(kept.indices.asnumpy(), [2, 6])
        onp.testing.assert_allclose(kept.data.asnumpy(), vals[[1, 3]])

    def test_elemwise_index_union(self):
        a = sparse.row_sparse_array(
            (onp.ones((2, 3), onp.float32), onp.array([1, 3])), shape=(6, 3))
        b = sparse.row_sparse_array(
            (2 * onp.ones((2, 3), onp.float32), onp.array([3, 5])),
            shape=(6, 3))
        out = sparse.add(a, b)
        assert out.stype == "row_sparse"
        onp.testing.assert_array_equal(out.indices.asnumpy(), [1, 3, 5])
        expect = onp.zeros((6, 3), onp.float32)
        expect[1] = 1
        expect[3] = 3
        expect[5] = 2
        onp.testing.assert_allclose(out.asnumpy(), expect)

    def test_rebind_refreshes_components(self):
        """After something outside the sparse API rebinds ._data, the
        component accessors re-derive from the dense mirror."""
        a = sparse.row_sparse_array(
            (onp.ones((1, 2), onp.float32), onp.array([1])), shape=(4, 2))
        import jax.numpy as jnp
        new = onp.zeros((4, 2), onp.float32)
        new[3] = 7
        a._data = jnp.asarray(new)
        onp.testing.assert_array_equal(a.indices.asnumpy(), [3])
        onp.testing.assert_allclose(a.data.asnumpy(), [[7, 7]])

    def test_shape_mismatch_raises(self):
        a = sparse.row_sparse_array(
            (onp.ones((1, 3), onp.float32), onp.array([1])), shape=(4, 3))
        b = sparse.row_sparse_array(
            (onp.ones((1, 3), onp.float32), onp.array([5])), shape=(6, 3))
        with pytest.raises(mx.base.MXNetError):
            sparse.add(a, b)

    def test_zeros(self):
        z = sparse.zeros("row_sparse", (5, 4))
        assert z.stype == "row_sparse" and z.shape == (5, 4)
        assert onp.all(z.asnumpy() == 0)
        z = sparse.zeros("csr", (5, 4))
        assert z.stype == "csr"
        assert onp.all(z.asnumpy() == 0)


class TestJittableCSRUnion:
    """The r4 padded-nnz union kernel (VERDICT r3 item 6): pattern math
    entirely in jax, parity vs scipy across randomized patterns, and the
    kernel itself compiles under jax.jit (static shapes, no host sync)."""

    def _rand_csr(self, rng, shape, density):
        import scipy.sparse as sp
        m = sp.random(*shape, density=density, random_state=rng,
                      format="csr", dtype=onp.float32)
        m.sort_indices()
        from mxnet_tpu.ndarray.sparse import CSRNDArray
        return CSRNDArray(m.data, m.indptr, m.indices, shape), m

    @pytest.mark.parametrize("opname", ["add", "subtract", "multiply"])
    @pytest.mark.parametrize("density", [0.0, 0.05, 0.4])
    def test_parity_vs_scipy(self, opname, density):
        import scipy.sparse as sp
        from mxnet_tpu.ndarray import sparse as mxsp
        seed = ({"add": 1, "subtract": 2, "multiply": 3}[opname] * 1000
                + int(density * 100))
        rng = onp.random.RandomState(seed)
        a, sa = self._rand_csr(rng, (13, 17), density)
        b, sb = self._rand_csr(rng, (13, 17), density * 0.7)
        out = getattr(mxsp, opname)(a, b)
        ref = {"add": lambda: sa + sb,
               "subtract": lambda: sa - sb,
               "multiply": lambda: sa.multiply(sb).tocsr()}[opname]()
        ref.sort_indices()
        ref.eliminate_zeros()
        got = sp.csr_matrix(
            (onp.asarray(out.data.asnumpy(), onp.float32),
             onp.asarray(out.indices.asnumpy()),
             onp.asarray(out.indptr.asnumpy())), shape=out.shape)
        onp.testing.assert_allclose(got.toarray(), ref.toarray(),
                                    rtol=1e-5, atol=1e-6)

    def test_cancellation_prunes_explicit_zeros(self):
        """subtract(a, a) must return an EMPTY pattern (nnz 0), matching
        the scipy/reference csr binop pruning — explicit zeros from
        cancellation are not kept."""
        from mxnet_tpu.ndarray import sparse as mxsp
        rng = onp.random.RandomState(11)
        a, _ = self._rand_csr(rng, (7, 9), 0.3)
        out = mxsp.subtract(a, a)
        assert out.data.shape[0] == 0
        assert int(out.indptr.asnumpy()[-1]) == 0
        onp.testing.assert_allclose(out.tostype("default").asnumpy(), 0.0)

    def test_union_kernel_jits(self):
        import jax
        import jax.numpy as jnp
        from mxnet_tpu.ndarray.sparse import _csr_union_device
        ka = jnp.asarray([1, 5, 9], jnp.int32)
        va = jnp.asarray([1.0, 2.0, 3.0], jnp.float32)
        kb = jnp.asarray([5, 7], jnp.int32)
        vb = jnp.asarray([10.0, 20.0], jnp.float32)
        f = jax.jit(lambda *a: _csr_union_device(*a, mode="sum"))
        keys, vals, valid = f(ka, va, kb, vb)
        assert keys.shape == (5,) and vals.shape == (5,)
        assert int(valid.sum()) == 4
        onp.testing.assert_array_equal(onp.asarray(keys[:4]), [1, 5, 7, 9])
        onp.testing.assert_allclose(onp.asarray(vals[:4]),
                                    [1.0, 12.0, 20.0, 3.0])
        g = jax.jit(lambda *a: _csr_union_device(*a, mode="prod"))
        keys, vals, valid = g(ka, va, kb, vb)
        assert int(valid.sum()) == 1
        assert int(keys[0]) == 5 and float(vals[0]) == 20.0

    def test_sparse_ops_never_touch_the_dense_mirror(self):
        """dot and elemwise on CSR operands must not materialize the
        dense cache (the r3 'lazy dense mirror' stays for generic dense
        interop only)."""
        from mxnet_tpu.ndarray import sparse as mxsp
        rng = onp.random.RandomState(3)
        a, _ = self._rand_csr(rng, (9, 11), 0.3)
        b, _ = self._rand_csr(rng, (9, 11), 0.3)
        rhs = mx.nd.array(rng.rand(11, 4).astype("float32"))
        mxsp.add(a, b)
        mxsp.multiply(a, b)
        mxsp.dot(a, rhs)
        assert a._dense_cache is None and b._dense_cache is None

    def test_rs_union_device_jittable(self):
        """The row_sparse union kernel is a pure static-shape jax
        function (VERDICT r4 item 5): jit it directly, check keys,
        union semantics (multiply keeps the union pattern with zero
        rows outside the intersection), and the packed layout."""
        import jax
        import jax.numpy as jnp
        from mxnet_tpu.ndarray.sparse import _rs_union_device
        ka = jnp.asarray([1, 5], jnp.int32)
        va = jnp.asarray([[1., 2.], [3., 4.]])
        kb = jnp.asarray([5, 9], jnp.int32)
        vb = jnp.asarray([[10., 10.], [7., 8.]])
        f = jax.jit(lambda *a: _rs_union_device(*a, opname="add"))
        keys, vals, valid = f(ka, va, kb, vb)
        assert keys.shape == (4,) and vals.shape == (4, 2)
        assert int(valid.sum()) == 3
        onp.testing.assert_array_equal(onp.asarray(keys[:3]), [1, 5, 9])
        onp.testing.assert_allclose(onp.asarray(vals[:3]),
                                    [[1, 2], [13, 14], [7, 8]])
        g = jax.jit(lambda *a: _rs_union_device(*a, opname="multiply"))
        keys, vals, valid = g(ka, va, kb, vb)
        assert int(valid.sum()) == 3  # union pattern, not intersection
        onp.testing.assert_allclose(onp.asarray(vals[:3]),
                                    [[0, 0], [30, 40], [0, 0]])

    def test_rs_ops_never_touch_the_dense_mirror(self):
        """row_sparse elemwise and sparse_retain must not materialize
        the dense cache (r4 item 5 extends the csr-only regression)."""
        from mxnet_tpu.ndarray import sparse as mxsp
        rng = onp.random.RandomState(4)
        da = onp.zeros((10, 3), "float32")
        db = onp.zeros((10, 3), "float32")
        da[[1, 4, 7]] = rng.rand(3, 3)
        db[[4, 8]] = rng.rand(2, 3)
        a = mx.nd.array(da).tostype("row_sparse")
        b = mx.nd.array(db).tostype("row_sparse")
        a._dense_cache = None
        b._dense_cache = None
        s = mxsp.add(a, b)
        m = mxsp.multiply(a, b)
        r = mxsp.sparse_retain(a, mx.nd.array(
            onp.asarray([1, 7], "float32")))
        assert a._dense_cache is None and b._dense_cache is None
        onp.testing.assert_allclose(onp.asarray(s.asnumpy()), da + db,
                                    rtol=1e-6)
        onp.testing.assert_allclose(onp.asarray(m.asnumpy()), da * db,
                                    rtol=1e-6)
        onp.testing.assert_array_equal(onp.asarray(r.indices.asnumpy()),
                                       [1, 7])


class TestSparseScalarDtypeGate:
    """Scalar mul/div storage-preservation is gated to floating dtypes
    and nonzero divisors — int sparse must promote like the dense op
    instead of truncating the scale factor to 0 (a review finding, since fixed)."""

    def _int_rs(self):
        d = onp.zeros((4, 5), "int32")
        d[1] = [1, 2, 0, 4, 5]
        d[3] = [0, 0, 3, 0, 0]
        return d, mx.nd.array(d.astype("float32")).tostype(
            "row_sparse"), sparse.RowSparseNDArray(
                onp.asarray([[1, 2, 0, 4, 5], [0, 0, 3, 0, 0]], "int32"),
                onp.asarray([1, 3]), (4, 5))

    def test_int_rowsparse_div_promotes(self):
        import jax.numpy as jnp
        d, _, rs = self._int_rs()
        out = rs / 2
        # dense semantics: int / 2 -> float, 0.5 not truncated to 0
        onp.testing.assert_allclose(onp.asarray(out.asnumpy()), d / 2,
                                    rtol=1e-6)
        assert jnp.issubdtype(jnp.dtype(out.dtype), jnp.floating)

    def test_int_rowsparse_mul_matches_dense(self):
        d, _, rs = self._int_rs()
        # the dense scalar op casts the scalar to the array dtype
        # (reference NDArray scalar semantics) — int sparse must agree
        # with the dense result instead of scaling through _scale
        dense = mx.nd.array(d) * 0.5
        onp.testing.assert_allclose(onp.asarray((rs * 0.5).asnumpy()),
                                    onp.asarray(dense.asnumpy()),
                                    rtol=1e-6)
        dense3 = mx.nd.array(d) * 3
        onp.testing.assert_allclose(onp.asarray((rs * 3).asnumpy()),
                                    onp.asarray(dense3.asnumpy()),
                                    rtol=1e-6)

    def test_float_rowsparse_scalar_keeps_storage(self):
        _, f, _ = self._int_rs()
        out = f / 2
        assert out.stype == "row_sparse"
        onp.testing.assert_allclose(
            onp.asarray(out.asnumpy())[1], [0.5, 1, 0, 2, 2.5], rtol=1e-6)
        out2 = f * 3.0
        assert out2.stype == "row_sparse"

    def test_nonfinite_scalar_goes_dense(self):
        _, f, _ = self._int_rs()
        # 0 * inf = nan at UNSTORED positions — only the dense op can
        # represent that, so inf/nan scalars must bypass _scale
        out = f * float("inf")
        a = onp.asarray(out.asnumpy())
        assert onp.isnan(a[0]).all()      # unstored row: 0 * inf
        assert onp.isinf(a[1][0])         # stored value: 1 * inf
        out2 = f / float("nan")
        assert onp.isnan(onp.asarray(out2.asnumpy())).all()

    def test_float_div_by_zero_goes_dense(self):
        _, f, _ = self._int_rs()
        out = f / 0
        # dense semantics: unstored zeros become 0/0 = nan (the sparse
        # _scale path could only scale stored values)
        a = onp.asarray(out.asnumpy())
        assert onp.isnan(a[0]).all()
        assert onp.isinf(a[1][0])

    def test_int_csr_div_promotes(self):
        d = onp.zeros((3, 4), "int32")
        d[0, 1] = 6
        d[2, 3] = 9
        mat = sp.csr_matrix(d)
        a = sparse.csr_matrix((onp.asarray(mat.data, "int32"),
                               mat.indices, mat.indptr), shape=(3, 4))
        out = a / 4
        onp.testing.assert_allclose(onp.asarray(out.asnumpy()), d / 4,
                                    rtol=1e-6)
