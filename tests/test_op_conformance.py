"""Registry-wide operator conformance sweep (VERDICT r3 item 3).

Mirrors the reference's registry-wide ``check_consistency`` strategy
(SURVEY.md §7): iterate EVERY op in the registry — nothing is tested "by
name"; a newly registered op is swept automatically.  For each op:

- **forward smoke on ≥2 dtypes** (float32 + bfloat16 for float ops; ops
  with a fixed natural dtype — int indices, int8 quantized, bool — run
  twice with their natural inputs and are listed in ``FIXED_DTYPE`` with
  the reason), all outputs finite;
- **vjp check** for every op registered ``differentiable=True``: the
  gradient of the summed float outputs w.r.t. every float input computes
  and is finite.

``SPECIALS`` supplies inputs for ops whose generic inputs don't fit
(shape/rank/dtype constraints); ``SKIP`` documents every exemption with
the reason and the place the op IS exercised.  A meta-test asserts the
tables only name real ops, so entries cannot go stale silently.
"""
import inspect

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

from mxnet_tpu.ops import registry

# --------------------------------------------------------------------- #
# input builders
# --------------------------------------------------------------------- #
_SEED = 0


def F(*shape):
    """Positive-ish float array factory (dtype applied per sweep)."""
    def make(dt):
        rng = onp.random.RandomState(_SEED)
        return jnp.asarray(rng.rand(*shape) + 0.1, dt)
    return make


def FN(*shape):
    """Zero-centered float array factory."""
    def make(dt):
        rng = onp.random.RandomState(_SEED + 1)
        return jnp.asarray(rng.randn(*shape), dt)
    return make


def I(*shape, lo=0, hi=3):
    def make(dt):
        rng = onp.random.RandomState(_SEED + 2)
        return jnp.asarray(rng.randint(lo, hi, shape), jnp.int32)
    return make


def B(*shape):
    def make(dt):
        rng = onp.random.RandomState(_SEED + 3)
        return jnp.asarray(rng.rand(*shape) > 0.5)
    return make


def I8(*shape):
    def make(dt):
        rng = onp.random.RandomState(_SEED + 4)
        return jnp.asarray(rng.randint(-10, 10, shape), jnp.int8)
    return make


def PSD(n):
    """Symmetric positive-definite matrix (for potrf/potri/syevd)."""
    def make(dt):
        rng = onp.random.RandomState(_SEED + 5)
        a = rng.randn(n, n)
        return jnp.asarray(a @ a.T + n * onp.eye(n), dt)
    return make


def TRI(n):
    """Lower-triangular non-singular matrix."""
    def make(dt):
        rng = onp.random.RandomState(_SEED + 6)
        return jnp.asarray(onp.tril(rng.rand(n, n)) + onp.eye(n), dt)
    return make


def SORTED(n):
    def make(dt):
        return jnp.asarray(onp.linspace(0.0, 1.0, n), dt)
    return make


def U(*shape, lo=0.05, hi=0.85):
    """Uniform in an open sub-interval — for domain-restricted ops
    (arcsin/arccos/logit/erfinv/arctanh need |x| < 1 or x in (0,1))."""
    def make(dt):
        rng = onp.random.RandomState(_SEED + 7)
        return jnp.asarray(rng.uniform(lo, hi, shape), dt)
    return make


def Z(*shape):
    return lambda dt: jnp.zeros(shape, dt)


def KEY():
    def make(dt):
        return jax.random.PRNGKey(0)
    return make


def spec(*arg_makers, **kwargs):
    """(args..., kwargs) special-case entry."""
    return lambda dt: ([m(dt) for m in arg_makers], dict(kwargs))


# --------------------------------------------------------------------- #
# exemptions — every entry carries its reason (VERDICT: explicit
# skip-list documenting every exemption)
# --------------------------------------------------------------------- #
SKIP = {
    "ring_attention": "requires an 'sp' mesh axis; parity-tested in "
                      "tests/test_parallel.py and the __graft_entry__ "
                      "dryrun (ring == dense attention, loss + grads)",
}

# ops whose inputs have one natural dtype (indices, quantized int8,
# packed bits, ...): the two sweep passes run the same natural inputs —
# there is no second meaningful dtype for them
FIXED_DTYPE = {
    "bitwise_and": "int-only by definition",
    "bitwise_or": "int-only by definition",
    "bitwise_xor": "int-only by definition",
    "bitwise_not": "int-only by definition",
    "left_shift": "int-only by definition",
    "right_shift": "int-only by definition",
    "quantized_conv_int8": "int8 storage is the op's contract",
    "quantized_matmul_int8": "int8 storage is the op's contract",
}

# float ops whose backing XLA kernels are f32/f64-only on every backend
# (lax.linalg decompositions and FFT) — swept at float32 twice
F32_ONLY = {
    "linalg_potrf", "linalg_potri", "linalg_syevd", "linalg_inverse",
    "linalg_det", "linalg_slogdet", "linalg_trsm", "linalg_trmm",
    "linalg_gelqf", "linalg_extracttrian", "linalg_maketrian",
    "linalg_sumlogdiag", "linalg_syrk", "linalg_gemm", "linalg_gemm2",
    "fft", "ifft", "interp_op", "searchsorted",
    "_DropoutImpl",  # PRNG key input; bf16 data path covered via p=0
}

# --------------------------------------------------------------------- #
# static-kwarg defaults by parameter name (applied when a required
# keyword-only parameter has no entry in SPECIALS)
# --------------------------------------------------------------------- #
KWARG_DEFAULTS = {
    "lr": 0.05,
    "axis": 0,
    "shift": 1,
    "repeats": 2,
    "depth": 3,
    "q": 0.5,  # valid for both quantile ([0,1]) and percentile ([0,100])
    "dtype": "float32",
    "a_min": 0.2,
    "a_max": 0.8,
    "max_norm": 1.0,
    "indices_or_sections": 2,
}

# --------------------------------------------------------------------- #
# per-op input specials
# --------------------------------------------------------------------- #
SPECIALS = {
    # ---- NCHW / vision ------------------------------------------------ #
    "LRN": spec(F(1, 3, 8, 8)),
    "ROIPooling": spec(F(1, 3, 8, 8),
                       lambda dt: jnp.asarray(
                           [[0, 0, 0, 6, 6], [0, 1, 1, 7, 7]], jnp.float32),
                       pooled_size=(2, 2), spatial_scale=1.0),
    "_contrib_ROIAlign": spec(
        F(1, 3, 8, 8),
        lambda dt: jnp.asarray([[0, 0, 0, 6, 6]], jnp.float32),
        pooled_size=(2, 2), spatial_scale=1.0),
    "SpatialTransformer": spec(
        F(1, 3, 8, 8),
        lambda dt: jnp.asarray([[1, 0, 0, 0, 1, 0]], dt),
        target_shape=(8, 8)),
    "UpSampling": spec(F(1, 3, 4, 4), scale=2, sample_type="nearest"),
    "_contrib_BilinearResize2D": spec(F(1, 3, 4, 4), height=8, width=8),
    "_contrib_DeformableConvolution": spec(
        F(1, 4, 8, 8), FN(1, 18, 8, 8), FN(2, 4, 3, 3),
        kernel=(3, 3), num_filter=2, pad=(1, 1)),
    "_contrib_MultiBoxPrior": spec(F(1, 3, 8, 8), sizes=(0.5, 0.25),
                                   ratios=(1.0, 2.0)),
    "_contrib_MultiBoxDetection": spec(
        F(1, 2, 4),                       # cls_prob (N, classes+1, A)
        FN(1, 16),                        # loc_pred (N, A*4)
        lambda dt: jnp.asarray(
            onp.random.RandomState(9).rand(1, 4, 4) * 0.5, jnp.float32)),
    "_contrib_MultiBoxTarget": spec(
        lambda dt: jnp.asarray(
            onp.random.RandomState(9).rand(1, 4, 4) * 0.5, jnp.float32),
        lambda dt: jnp.asarray([[[0, 0.1, 0.1, 0.4, 0.4]]], jnp.float32),
        F(1, 2, 4)),                      # cls_pred (N, classes+1, A)
    "_contrib_Proposal": spec(
        F(1, 2, 4, 4), FN(1, 4, 4, 4),
        lambda dt: jnp.asarray([[64, 64, 1.0]], jnp.float32),
        scales=(8,), ratios=(1.0,), rpn_pre_nms_top_n=8,
        rpn_post_nms_top_n=4, rpn_min_size=1),
    "pad": spec(F(1, 1, 4, 4), mode="constant",
                pad_width=(0, 0, 0, 0, 1, 1, 1, 1)),
    "im2col": spec(F(1, 3, 8, 8), kernel=(3, 3)),
    "col2im": spec(F(1, 27, 36), output_size=(8, 8), kernel=(3, 3)),
    "depth_to_space": spec(F(1, 4, 4, 4), block_size=2),
    "space_to_depth": spec(F(1, 1, 4, 4), block_size=2),

    # ---- image (HWC / NHWC) ------------------------------------------ #
    "_image_to_tensor": spec(F(8, 8, 3)),
    "_image_normalize": spec(F(3, 8, 8), mean=(0.2, 0.3, 0.4),
                             std=(0.5, 0.5, 0.5)),
    "_image_crop": spec(F(8, 8, 3), x=1, y=1, width=4, height=4),
    "_image_resize": spec(F(8, 8, 3), size=(4, 4)),
    "_image_flip_top_bottom": spec(F(8, 8, 3)),
    "_image_random_flip_top_bottom": spec(F(8, 8, 3)),
    "_image_random_contrast": spec(F(8, 8, 3)),
    "_image_random_saturation": spec(F(8, 8, 3)),

    # ---- norm layers -------------------------------------------------- #
    "LayerNorm": spec(FN(4, 5), F(5), FN(5)),
    "RMSNorm": spec(FN(4, 5), F(5)),
    "_BatchNormStats": spec(FN(2, 5, 4, 4), F(5), FN(5), FN(5), F(5)),
    "GroupNorm": spec(FN(2, 4, 3, 3), F(4), FN(4), num_groups=2),
    "InstanceNorm": spec(FN(2, 4, 3, 3), F(4), FN(4)),
    "prelu": spec(FN(2, 4), F(4)),

    # ---- conv family -------------------------------------------------- #
    "Convolution": spec(F(1, 3, 8, 8), FN(2, 3, 3, 3),
                        kernel=(3, 3), num_filter=2, no_bias=True),
    "Deconvolution": spec(F(1, 3, 8, 8), FN(3, 2, 3, 3),
                          kernel=(3, 3), num_filter=2),
    "Correlation": spec(F(1, 3, 8, 8), F(1, 3, 8, 8)),
    "BilinearSampler": spec(
        F(1, 3, 8, 8),
        lambda dt: jnp.asarray(onp.random.RandomState(8).uniform(
            -0.9, 0.9, (1, 2, 8, 8)), dt)),
    "GridGenerator": spec(
        lambda dt: jnp.asarray([[1, 0, 0, 0, 1, 0]], dt),
        transform_type="affine", target_shape=(8, 8)),

    # ---- losses with class labels ------------------------------------ #
    "CTCLoss": spec(FN(5, 2, 4),
                    lambda dt: jnp.asarray([[1, 2], [2, 1]], jnp.float32)),
    "SVMOutput": spec(FN(4, 5),
                      lambda dt: jnp.asarray([0, 1, 2, 3], jnp.float32)),

    # ---- domain-restricted elementwise -------------------------------- #
    "arcsin": spec(U(4, 5)),
    "arccos": spec(U(4, 5)),
    "arctanh": spec(U(4, 5)),
    "erfinv": spec(U(4, 5)),
    "logit": spec(U(4, 5)),
    "arccosh": spec(lambda dt: 1.0 + F(4, 5)(dt)),
    "log1mexp": spec(lambda dt: -F(4, 5)(dt)),

    # ---- indexing/selection ------------------------------------------ #
    "batch_take": spec(F(4, 5), I(4, hi=5)),
    "choose_element_0index": spec(F(4, 5), I(4, hi=5)),
    "pick": spec(F(4, 5), I(4, hi=5)),
    "fill_element_0index": spec(F(4, 5), F(4), I(4, hi=5)),
    "softmax_cross_entropy": spec(FN(4, 5), I(4, hi=5)),
    "one_hot": spec(I(4, hi=3), depth=3),
    "gather_nd": spec(F(4, 5), I(2, 3, hi=4)),
    "scatter_nd": spec(F(3), I(2, 3, hi=3), shape=(4, 5)),
    "boolean_mask": spec(F(4, 5), B(4)),
    "_contrib_index_add": spec(F(4, 5), I(2, hi=4), F(2, 5)),
    "_contrib_index_copy": spec(F(4, 5), I(2, hi=4), F(2, 5)),
    "bincount_op": spec(I(10, hi=5), length=5),
    "searchsorted": spec(SORTED(5), F(3)),
    "unravel_index": spec(I(4, hi=19), shape=(4, 5)),
    "ravel_multi_index": spec(I(2, 3, hi=3), shape=(4, 5)),
    "interp_op": spec(F(4), SORTED(5), FN(5)),

    # ---- shape manipulation ------------------------------------------ #
    "reshape": spec(F(4, 5), shape=(5, 4)),
    "_onnx_expand": spec(F(4, 1), shape=(1, 5)),
    "broadcast_to": spec(F(1, 5), shape=(4, 5)),
    "broadcast_axis": spec(F(1, 5), axis=0, size=4),
    "slice": spec(F(4, 5), begin=(0, 1), end=(3, 4)),
    "slice_axis": spec(F(4, 5), axis=0, begin=0, end=2),
    "split": spec(F(4, 6), num_outputs=2),
    "dsplit": spec(F(4, 4, 4), indices_or_sections=2),
    "hsplit": spec(F(4, 4), indices_or_sections=2),
    "tile": spec(F(4, 5), reps=(2, 1)),
    "moveaxis": spec(F(4, 5), source=0, destination=1),
    "resize_op": spec(F(4, 5), new_shape=(2, 10)),
    "flip": spec(F(4, 5), axis=0),
    "cast": spec(F(4, 5), dtype="float16"),

    # ---- int/bool dtype ops ------------------------------------------ #
    "bitwise_and": spec(I(4, 5, hi=7), I(4, 5, hi=7)),
    "bitwise_or": spec(I(4, 5, hi=7), I(4, 5, hi=7)),
    "bitwise_xor": spec(I(4, 5, hi=7), I(4, 5, hi=7)),
    "bitwise_not": spec(I(4, 5, hi=7)),
    "left_shift": spec(I(4, 5, hi=7), I(4, 5, hi=2)),
    "right_shift": spec(I(4, 5, hi=7), I(4, 5, hi=2)),

    # ---- matmul/linalg ------------------------------------------------ #
    "dot": spec(F(4, 5), F(5, 3)),
    "matmul": spec(F(4, 5), F(5, 3)),
    "batch_dot": spec(F(2, 4, 5), F(2, 5, 3)),
    "linalg_gemm": spec(F(4, 5), F(5, 3), FN(4, 3)),
    "linalg_gemm2": spec(F(4, 5), F(5, 3)),
    "linalg_det": spec(PSD(4)),
    "linalg_slogdet": spec(PSD(4)),
    "linalg_inverse": spec(PSD(4)),
    "linalg_potrf": spec(PSD(4)),
    "linalg_potri": spec(PSD(4)),
    "linalg_syevd": spec(PSD(4)),
    "linalg_trmm": spec(TRI(4), F(4, 3)),
    "linalg_trsm": spec(TRI(4), F(4, 3)),
    "linalg_maketrian": spec(F(2, 6)),
    "linalg_extracttrian": spec(PSD(4)),
    "gcd": spec(I(4, 5, lo=1, hi=30), I(4, 5, lo=1, hi=30)),
    "lcm": spec(I(4, 5, lo=1, hi=12), I(4, 5, lo=1, hi=12)),
    "ldexp": spec(F(4, 5), I(4, 5, hi=4)),
    "cross_op": spec(F(4, 3), F(4, 3)),
    "ifft": spec(F(4, 8)),

    # ---- attention / rnn / rope -------------------------------------- #
    "flash_attention": spec(FN(2, 2, 8, 16), FN(2, 2, 8, 16),
                            FN(2, 2, 8, 16)),
    "flash_attention_qkv": spec(FN(2, 8, 96), num_heads=2),
    "rope": spec(FN(2, 2, 8, 16)),
    "_contrib_interleaved_matmul_selfatt_qk": spec(FN(4, 2, 24), heads=2),
    "_contrib_interleaved_matmul_selfatt_valatt": spec(
        FN(4, 2, 24), F(4, 4, 4), heads=2),
    "fused_rnn": spec(FN(3, 2, 4), FN(1, 2, 5), FN(1, 2, 5),
                      FN(20, 4), FN(20, 5), FN(20), FN(20),
                      mode="lstm"),
    "rnn_param_concat": spec(FN(3, 4), FN(3, 4)),
    "_DropoutImpl": spec(FN(4, 5), KEY(), p=0.5),

    # ---- quantization ------------------------------------------------- #
    "quantized_matmul_int8": spec(I8(4, 5), I8(3, 5), transpose_b=True),
    "quantized_conv_int8": spec(I8(1, 3, 8, 8), I8(2, 3, 3, 3)),

    # ---- optimizer states with domain constraints --------------------- #
    # centered RMSProp: n - g² must stay ≥ 0 (it is a running variance);
    # start from the optimizer's real init (zeros) like the reference
    "rmspropalex_update": spec(F(4, 5), FN(4, 5), Z(4, 5), Z(4, 5),
                               Z(4, 5), lr=0.05),

    # ---- sparse kernels ----------------------------------------------- #
    "_sparse_segment_dot": spec(F(4), I(4, hi=5), I(4, hi=3), F(5, 3),
                                num_segments=3),
    "_sparse_rowsparse_dot": spec(F(2, 5), I(2, hi=4), F(5, 3),
                                  num_rows=4),
    # rhs must have num_rows(=4) rows — the transposed dot gathers
    # rhs[indices] (the value sweep caught the old undersized rhs: jnp
    # clamps out-of-bounds gathers silently)
    "_sparse_rowsparse_dot_t": spec(F(2, 5), I(2, hi=4), F(4, 3),
                                    num_cols=4),

    # ---- distribution samplers with domain constraints ---------------- #
    "sample_negative_binomial": spec(F(3), U(3)),       # k > 0, p in (0,1)
    "sample_generalized_negative_binomial": spec(F(3), F(3)),

    # ---- variadic / multi-tensor ------------------------------------- #
    "concat": spec(F(4, 5), F(4, 5)),
    "stack": spec(F(4, 5), F(4, 5)),
    "dstack": spec(F(4, 5), F(4, 5)),
    "meshgrid": spec(F(4), F(5)),
    "broadcast_arrays": spec(F(4, 1), F(1, 5)),
    "amp_multicast": spec(F(4, 5), F(4, 5), num_outputs=2),
    "multi_all_finite": spec(F(4, 5), F(4, 5)),
    "reset_arrays": spec(F(4, 5), F(4, 5)),
    "clip_global_norm": spec(FN(4, 5), FN(3), max_norm=1.0),
    "multi_sgd_update": spec(F(4, 5), FN(4, 5), F(3), FN(3),
                             lrs=(0.05, 0.05), wds=(0.0, 0.0)),
    "multi_sgd_mom_update": spec(F(4, 5), FN(4, 5), FN(4, 5),
                                 lrs=(0.05,), wds=(0.0,)),
    "multi_mp_sgd_update": spec(F(4, 5), FN(4, 5), F(4, 5),
                                lrs=(0.05,), wds=(0.0,)),
    "multi_mp_sgd_mom_update": spec(F(4, 5), FN(4, 5), FN(4, 5), F(4, 5),
                                    lrs=(0.05,), wds=(0.0,)),
    "multi_adamw_update": spec(F(4, 5), FN(4, 5), FN(4, 5), F(4, 5),
                               lrs=(0.05,), etas=(1.0,)),
    "multi_lamb_update": spec(F(4, 5), FN(4, 5), FN(4, 5), F(4, 5),
                              learning_rates=(0.05,)),
    "preloaded_multi_sgd_update": spec(
        F(4, 5), FN(4, 5), lambda dt: jnp.asarray([0.05], jnp.float32),
        lambda dt: jnp.asarray([0.0], jnp.float32)),
    "preloaded_multi_sgd_mom_update": spec(
        F(4, 5), FN(4, 5), FN(4, 5),
        lambda dt: jnp.asarray([0.05], jnp.float32),
        lambda dt: jnp.asarray([0.0], jnp.float32)),
}


# --------------------------------------------------------------------- #
# generic builder for everything else
# --------------------------------------------------------------------- #
def build_inputs(o, dt):
    if o.name in SPECIALS:
        return SPECIALS[o.name](dt)
    sig = inspect.signature(o.fn)
    if o.variadic:
        return [F(4, 5)(dt), F(4, 5)(dt)], {}
    args = []
    kwargs = {}
    for p in sig.parameters.values():
        if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD):
            if p.default is inspect.Parameter.empty:
                args.append(F(4, 5)(dt))
        elif p.kind == p.KEYWORD_ONLY and \
                p.default is inspect.Parameter.empty:
            if p.name not in KWARG_DEFAULTS:
                raise AssertionError(
                    f"op {o.name}: required kwarg {p.name!r} has no "
                    "KWARG_DEFAULTS entry and no SPECIALS entry — add one")
            kwargs[p.name] = KWARG_DEFAULTS[p.name]
    return args, kwargs


def _flat_outputs(res):
    return list(res) if isinstance(res, (tuple, list)) else [res]


def _assert_finite(res, name, dt):
    for r in _flat_outputs(res):
        # check via jnp: onp.asarray(bf16).dtype.kind is 'V', which would
        # silently skip the whole bfloat16 half of the sweep
        if jnp.issubdtype(jnp.asarray(r).dtype, jnp.floating):
            a = onp.asarray(jnp.asarray(r).astype(jnp.float32))
            assert onp.isfinite(a).all(), \
                f"{name}[{dt}]: non-finite output"


def _sweep_dtypes(name):
    if name in FIXED_DTYPE or name in F32_ONLY:
        return [jnp.float32, jnp.float32]
    return [jnp.float32, jnp.bfloat16]


ALL_OPS = registry.list_ops()


@pytest.mark.parametrize("name", ALL_OPS)
def test_forward_smoke(name):
    if name in SKIP:
        pytest.skip(SKIP[name])
    o = registry.OPS[name]
    for dt in _sweep_dtypes(name):
        args, kwargs = build_inputs(o, dt)
        res = o.fn(*args, **kwargs)
        jax.block_until_ready(res)
        _assert_finite(res, name, dt)


@pytest.mark.parametrize(
    "name", [n for n in ALL_OPS if registry.OPS[n].differentiable])
def test_vjp(name):
    if name in SKIP:
        pytest.skip(SKIP[name])
    o = registry.OPS[name]
    args, kwargs = build_inputs(o, jnp.float32)
    flat = list(args)
    diff_idx = [i for i, a in enumerate(flat)
                if hasattr(a, "dtype") and
                jnp.issubdtype(jnp.asarray(a).dtype, jnp.floating)]
    if not diff_idx:
        pytest.skip(f"{name}: no float inputs to differentiate")

    def scalar_loss(*diff_args):
        full = list(flat)
        for i, a in zip(diff_idx, diff_args):
            full[i] = a
        res = o.fn(*full, **kwargs)
        outs = [r for r in _flat_outputs(res)
                if jnp.issubdtype(jnp.asarray(r).dtype, jnp.floating)]
        if not outs:
            return jnp.float32(0.0)
        return sum(jnp.sum(r.astype(jnp.float32)) for r in outs)

    grads = jax.grad(scalar_loss, argnums=tuple(range(len(diff_idx))))(
        *[flat[i] for i in diff_idx])
    for g in grads:
        assert onp.isfinite(onp.asarray(g)).all(), \
            f"{name}: non-finite gradient"


def test_exemption_tables_are_live():
    """SKIP/SPECIALS/FIXED_DTYPE/F32_ONLY entries must name real ops —
    stale entries fail here instead of silently shrinking coverage."""
    known = set(ALL_OPS)
    for table, tname in ((SKIP, "SKIP"), (SPECIALS, "SPECIALS"),
                         (FIXED_DTYPE, "FIXED_DTYPE"),
                         (F32_ONLY, "F32_ONLY")):
        stale = set(table) - known
        assert not stale, f"{tname} names unknown ops: {sorted(stale)}"


def test_sweep_covers_registry():
    """The sweep runs every registered op minus the documented SKIPs —
    and the SKIP list stays short, so coverage cannot quietly erode."""
    assert len(ALL_OPS) >= 370
    assert set(SKIP) <= set(ALL_OPS)
    assert len(SKIP) <= 5, "document the op in SPECIALS instead of SKIP"


# ===================================================================== #
# VALUE-LEVEL checks (VERDICT r4 item 3): finiteness is smoke, not
# correctness.  Two layers, mirroring the reference's check_consistency:
#
# 1. test_forward_values — f32 forward outputs compared against a
#    NumPy/SciPy reference computation.  References come from three
#    sources: the op name resolving in numpy (139 ops), scipy.special,
#    or the explicit VALUE_REF table.  Ops with no derivable reference
#    are listed in VALUE_EXEMPT with the reason and the place their
#    values ARE asserted.
# 2. test_dtype_consistency — the same op run on f32 inputs and on the
#    bf16-rounded inputs must agree at bf16-scaled tolerance (the
#    reference's cross-dtype check_consistency).
# ===================================================================== #
import scipy.special as _sps
import scipy.linalg as _spl

_NPF = onp.float32


def _np(x):
    a = onp.asarray(x)
    return a.astype(_NPF) if a.dtype == onp.float64 else a


def _sigmoid(x):
    return 1.0 / (1.0 + onp.exp(-x))


def _np_seq_mask(data, length=None, *, use_sequence_length=False,
                 value=0.0, axis=0):
    out = onp.array(data, copy=True)
    if not use_sequence_length or length is None:
        return out
    T = out.shape[axis]
    sw = onp.moveaxis(out, axis, 0)
    for b, L in enumerate(onp.asarray(length).astype(int)):
        sw[L:, b] = value
    return onp.moveaxis(sw, 0, axis)


VALUE_REF = {
    # ---- broadcast_* = plain numpy broadcasting ----------------------- #
    "broadcast_add": onp.add, "broadcast_sub": onp.subtract,
    "broadcast_mul": onp.multiply, "broadcast_div": onp.divide,
    "broadcast_mod": onp.mod, "broadcast_power": onp.power,
    "broadcast_maximum": onp.maximum, "broadcast_minimum": onp.minimum,
    "broadcast_hypot": onp.hypot,
    "broadcast_equal": onp.equal, "broadcast_not_equal": onp.not_equal,
    "broadcast_greater": onp.greater,
    "broadcast_greater_equal": onp.greater_equal,
    "broadcast_lesser": onp.less,
    "broadcast_lesser_equal": onp.less_equal,
    "broadcast_logical_and": onp.logical_and,
    "broadcast_logical_or": onp.logical_or,
    "broadcast_logical_xor": onp.logical_xor,
    "broadcast_like": lambda a, b: onp.broadcast_to(a, b.shape),
    "broadcast_to": lambda a, *, shape: onp.broadcast_to(a, shape),
    "broadcast_axis": lambda a, *, axis, size: onp.broadcast_to(
        a, tuple(size if i == axis else s
                 for i, s in enumerate(a.shape))),
    "broadcast_arrays": lambda *a: list(onp.broadcast_arrays(*a)),

    # ---- activations / simple elementwise ----------------------------- #
    "relu": lambda x: onp.maximum(x, 0),
    "relu6": lambda x: onp.clip(x, 0, 6),
    "sigmoid": _sigmoid,
    "log_sigmoid": lambda x: onp.log(_sigmoid(x)),
    "hard_sigmoid": lambda x, *, alpha=0.2, beta=0.5: onp.clip(
        alpha * x + beta, 0, 1),
    "hard_swish": lambda x: x * onp.clip(x + 3, 0, 6) / 6,
    "softsign": lambda x: x / (1 + onp.abs(x)),
    "softrelu": lambda x: onp.log1p(onp.exp(x)),
    "selu": lambda x: 1.0507009873554805 * onp.where(
        x > 0, x, 1.6732632423543772 * (onp.exp(x) - 1)),
    "elu": lambda x, *, alpha=1.0: onp.where(
        x > 0, x, alpha * (onp.exp(x) - 1)),
    "gelu": lambda x: 0.5 * x * (1 + _sps.erf(x / onp.sqrt(2))),
    "mish": lambda x: x * onp.tanh(onp.log1p(onp.exp(x))),
    "prelu": lambda x, g: onp.where(x > 0, x, g * x),
    "rsqrt": lambda x: 1.0 / onp.sqrt(x),
    "rcbrt": lambda x: 1.0 / onp.cbrt(x),
    "log1mexp": lambda x: onp.log1p(-onp.exp(x)),
    "logit": _sps.logit,
    "smooth_l1": lambda x, *, scalar=1.0: onp.where(
        onp.abs(x) < 1.0 / scalar ** 2,
        0.5 * (x * scalar) ** 2, onp.abs(x) - 0.5 / scalar ** 2),
    "squared_difference": lambda a, b: (a - b) ** 2,
    "quadratic": lambda x, *, a=0.0, b=0.0, c=0.0: a * x * x + b * x + c,
    "_contrib_div_sqrt_dim": lambda x: x / onp.sqrt(x.shape[-1]),
    "_contrib_gradientmultiplier": lambda x, *, scalar=1.0: x,
    "BlockGrad": lambda x: x,
    "MakeLoss": lambda x: x,
    "shape_array": lambda x: onp.asarray(x.shape, onp.int64),
    "size_array": lambda x: onp.asarray([x.size], onp.int64),
    "polyval_op": lambda p, x: onp.polyval(onp.asarray(p), x),
    "trapz_op": lambda y, *, dx=1.0: onp.trapz(y, dx=dx, axis=-1),
    "inner_op": lambda a, b: onp.inner(a, b),
    "vdot_op": lambda a, b: onp.vdot(a, b),
    "cross_op": lambda a, b: onp.cross(a, b),
    "unique_op": lambda x: onp.unique(x),
    "bincount_op": lambda x, *, length: onp.bincount(
        x.ravel(), minlength=length)[:length],
    "interp_op": lambda x, xp, fp: onp.interp(x, xp, fp),
    "searchsorted": lambda a, v, *, side="left": onp.searchsorted(
        a, v, side=side),

    # ---- reductions / norms ------------------------------------------ #
    "norm": lambda x, *, ord=2, axis=None, keepdims=False:
        onp.linalg.norm(x.ravel() if axis is None else x,
                        ord=ord, axis=axis, keepdims=keepdims),
    "moments": lambda x, *, axes=None, keepdims=False: [
        onp.mean(x, axis=tuple(axes) if axes else None, keepdims=keepdims),
        onp.var(x, axis=tuple(axes) if axes else None, keepdims=keepdims)],
    "L2Normalization": lambda x, *, mode="instance", eps=1e-10:
        x / onp.sqrt((x.reshape(x.shape[0], -1) ** 2)
                     .sum(1).reshape((-1,) + (1,) * (x.ndim - 1)) + eps),
    "argmax_channel": lambda x: onp.argmax(x, 1).astype(_NPF),

    # ---- softmax family ----------------------------------------------- #
    "softmin": lambda x, *, axis=-1: _sps.softmax(-_np(x), axis=axis),
    "SoftmaxActivation": lambda x, *, mode="instance": _sps.softmax(
        _np(x), axis=1 if mode == "channel" else -1),
    "masked_softmax": lambda x, mask=None, *, axis=-1: _sps.softmax(
        onp.where(onp.asarray(mask, bool), _np(x), -1e30)
        if mask is not None else _np(x), axis=axis),
    "masked_log_softmax": lambda x, mask=None, *, axis=-1:
        onp.log(_sps.softmax(
            onp.where(onp.asarray(mask, bool), _np(x), -1e30)
            if mask is not None else _np(x), axis=axis) + 1e-30),

    # ---- manipulation -------------------------------------------------- #
    "slice": lambda x, *, begin, end, step=None: x[tuple(
        __import__("builtins").slice(b, e, s) for b, e, s in zip(
            begin, end, step or (None,) * len(begin)))],
    "slice_axis": lambda x, *, axis, begin, end:
        onp.take(x, onp.arange(begin, end if end is not None
                               else x.shape[axis]), axis=axis),
    "slice_like": lambda a, b, *, axes=None: a[tuple(
        __import__("builtins").slice(0, b.shape[i]
                                     if (axes is None or i in axes)
                                     else None)
        for i in range(a.ndim))],
    "flatten": lambda x: x.reshape(x.shape[0], -1),
    "reshape": lambda x, *, shape: x.reshape(shape),
    "reshape_like": lambda a, b: a.reshape(b.shape),
    "resize_op": lambda x, *, new_shape: onp.resize(x, new_shape),
    "one_hot": lambda i, *, depth, on_value=1.0, off_value=0.0:
        onp.where(onp.eye(depth)[i.astype(int)] > 0, on_value, off_value),
    "pick": lambda x, i, *, axis=-1, keepdims=False:
        onp.take_along_axis(
            x, onp.expand_dims(i.astype(int), 1), axis=1).squeeze(1),
    "choose_element_0index": lambda x, i:
        x[onp.arange(x.shape[0]), i.astype(int)],
    "batch_take": lambda x, i: x[onp.arange(x.shape[0]), i.astype(int)],
    "fill_element_0index": lambda x, v, i: _fill0(x, v, i),
    "gather_nd": lambda d, i: d[tuple(i.astype(int))],
    "scatter_nd": lambda d, i, *, shape: _scatter_nd(d, i, shape),
    "take": lambda a, i, *, axis=0, mode="clip": onp.take(
        a, onp.clip(i.astype(int), 0, a.shape[axis] - 1), axis=axis),
    "tile": lambda x, *, reps: onp.tile(x, reps),
    "flip": lambda x, *, axis: onp.flip(x, axis),
    "depth_to_space": lambda x, *, block_size: _d2s(x, block_size),
    "space_to_depth": lambda x, *, block_size: _s2d(x, block_size),
    "_onnx_expand": lambda x, *, shape: x * onp.ones(shape, x.dtype),
    "sequence_mask": _np_seq_mask,
    "sequence_reverse": lambda data, length=None, *,
        use_sequence_length=False, axis=0: _seq_rev(
            data, length, use_sequence_length, axis),
    "sequence_last": lambda data, length=None, *,
        use_sequence_length=False, axis=0: _seq_last(
            data, length, use_sequence_length, axis),
    "index_array": lambda x, *, axes=None: _index_array(x, axes),
    "arange_like": lambda x, *, start=0.0, step=1.0, axis=None:
        (start + step * onp.arange(x.size)).reshape(x.shape).astype(_NPF)
        if axis is None else
        (start + step * onp.arange(x.shape[axis])).astype(_NPF),
    "cast": lambda x, *, dtype: x.astype(dtype),
    "amp_cast": lambda x, *, dtype="float32": x.astype(dtype),
    "amp_multicast": lambda *a, num_outputs: list(a),
    "reset_arrays": lambda *a: [onp.zeros_like(x) for x in a],
    "add_n": lambda *a: sum(a),
    "rnn_param_concat": lambda *a: onp.concatenate(
        [x.ravel() for x in a]),
    "khatri_rao": lambda a, b: onp.vstack(
        [onp.kron(a[:, k], b[:, k]) for k in range(a.shape[1])]).T,

    # ---- linalg -------------------------------------------------------- #
    "linalg_det": lambda a: onp.linalg.det(a),
    "linalg_slogdet": lambda a: list(onp.linalg.slogdet(a)),
    "linalg_inverse": lambda a: onp.linalg.inv(a),
    "linalg_potrf": lambda a: onp.linalg.cholesky(a),
    "linalg_syevd": lambda a: [onp.linalg.eigh(a)[1].T,
                               onp.linalg.eigh(a)[0]],
    "linalg_gemm": lambda a, b, c, *, alpha=1.0, beta=1.0,
        transpose_a=False, transpose_b=False:
        alpha * (a.T if transpose_a else a) @ (b.T if transpose_b else b)
        + beta * c,
    "linalg_gemm2": lambda a, b, *, alpha=1.0, transpose_a=False,
        transpose_b=False:
        alpha * (a.T if transpose_a else a) @ (b.T if transpose_b else b),
    "linalg_syrk": lambda a, *, alpha=1.0, transpose=False:
        alpha * (a.T @ a if transpose else a @ a.T),
    "linalg_trmm": lambda a, b, *, transpose=False, rightside=False,
        alpha=1.0: alpha * ((b @ (a.T if transpose else a))
                            if rightside else
                            ((a.T if transpose else a) @ b)),
    "linalg_trsm": lambda a, b, *, transpose=False, rightside=False,
        alpha=1.0: alpha * (_spl.solve_triangular(
            a, b.T if rightside else b, trans=1 if transpose else 0,
            lower=True).T if rightside else _spl.solve_triangular(
            a, b, trans=1 if transpose else 0, lower=True)),
    "linalg_sumlogdiag": lambda a: onp.log(onp.diag(a)).sum(),
    "linalg_extractdiag": lambda a, *, offset=0: onp.diag(a, k=offset),
    "linalg_extracttrian": lambda a, *, offset=0, lower=True:
        _extracttrian(a, offset, lower),
    "linalg_maketrian": lambda a, *, offset=0, lower=True:
        _maketrian(a, offset, lower),

    # ---- matmul family ------------------------------------------------- #
    "batch_dot": lambda a, b, *, transpose_a=False, transpose_b=False:
        onp.matmul(a.transpose(0, 2, 1) if transpose_a else a,
                   b.transpose(0, 2, 1) if transpose_b else b),
    "Embedding": lambda i, w, *, input_dim=0, output_dim=0:
        w[i.astype(int)],

    # ---- regression / loss heads -------------------------------------- #
    "LinearRegressionOutput": lambda d, l: d,
    "MAERegressionOutput": lambda d, l: d,
    "LogisticRegressionOutput": lambda d, l: _sigmoid(d),
    "SoftmaxOutput": lambda d, l, *, grad_scale=1.0: _sps.softmax(
        _np(d), axis=-1),
    "softmax_cross_entropy": lambda d, l: -onp.log(_sps.softmax(
        _np(d), -1)[onp.arange(d.shape[0]), l.astype(int)] + 1e-30).sum(),
    "IdentityAttachKLSparseReg": lambda x: x,

    # ---- im2col/col2im ------------------------------------------------- #
    "im2col": lambda x, *, kernel, stride=(1, 1), dilate=(1, 1),
        pad=(0, 0): _im2col(x, kernel, stride, dilate, pad),

    # ---- optimizer updates with simple closed forms -------------------- #
    "sgd_update": lambda w, g, *, lr, wd=0.0, rescale_grad=1.0,
        clip_gradient=-1.0, lazy_update=True:
        w - lr * (_clipg(rescale_grad * g, clip_gradient) + wd * w),
    "signsgd_update": lambda w, g, *, lr, wd=0.0, rescale_grad=1.0,
        clip_gradient=-1.0:
        w - lr * (onp.sign(_clipg(rescale_grad * g, clip_gradient))
                  + wd * w),
}


def _clipg(g, c):
    return onp.clip(g, -c, c) if c is not None and c > 0 else g


def _fill0(x, v, i):
    out = onp.array(x, copy=True)
    out[onp.arange(x.shape[0]), i.astype(int)] = v
    return out


def _scatter_nd(d, i, shape):
    out = onp.zeros(shape, d.dtype)
    onp.add.at(out, tuple(i.astype(int)), d)
    return out


def _d2s(x, bs):
    n, c, h, w = x.shape
    return x.reshape(n, bs, bs, c // bs ** 2, h, w).transpose(
        0, 3, 4, 1, 5, 2).reshape(n, c // bs ** 2, h * bs, w * bs)


def _s2d(x, bs):
    n, c, h, w = x.shape
    return x.reshape(n, c, h // bs, bs, w // bs, bs).transpose(
        0, 3, 5, 1, 2, 4).reshape(n, c * bs ** 2, h // bs, w // bs)


def _seq_rev(data, length, use_len, axis):
    out = onp.array(data, copy=True)
    sw = onp.moveaxis(out, axis, 0)
    T = sw.shape[0]
    if not use_len or length is None:
        res = sw[::-1]
    else:
        res = onp.array(sw, copy=True)
        for b, L in enumerate(onp.asarray(length).astype(int)):
            res[:L, b] = sw[:L, b][::-1]
    return onp.moveaxis(res, 0, axis)


def _seq_last(data, length, use_len, axis):
    sw = onp.moveaxis(onp.asarray(data), axis, 0)
    if not use_len or length is None:
        return sw[-1]
    idx = onp.asarray(length).astype(int) - 1
    return sw[idx, onp.arange(sw.shape[1])]


def _index_array(x, axes):
    axes = tuple(range(x.ndim)) if axes is None else tuple(axes)
    grids = onp.indices(x.shape)
    return onp.stack([grids[a] for a in axes], -1).astype(onp.int64)


def _extracttrian(a, offset, lower):
    mask = onp.tril(onp.ones_like(a), k=offset) if lower else \
        onp.triu(onp.ones_like(a), k=offset)
    idx = onp.nonzero(mask)
    return a[idx]


def _maketrian(a, offset, lower):
    # inverse of extracttrian for the swept (2, 6) input: 6 = 3*(3+1)/2
    k = a.shape[-1]
    n = int((onp.sqrt(8 * k + 1) - 1) / 2)
    out = onp.zeros(a.shape[:-1] + (n, n), a.dtype)
    for b in range(a.shape[0]):
        m = onp.zeros((n, n), a.dtype)
        m[onp.tril_indices(n, offset)] = a[b]
        out[b] = m if lower else m.T
    return out


def _im2col(x, kernel, stride, dilate, pad):
    n, c, h, w = x.shape
    kh, kw = kernel
    sh, sw_ = stride
    xp = onp.pad(x, ((0, 0), (0, 0), (pad[0], pad[0]), (pad[1], pad[1])))
    oh = (xp.shape[2] - (kh - 1) * dilate[0] - 1) // sh + 1
    ow = (xp.shape[3] - (kw - 1) * dilate[1] - 1) // sw_ + 1
    # layout: (c, kh, kw) fastest over kw — build directly
    cols = onp.stack([
        xp[:, :, i * dilate[0]:i * dilate[0] + oh * sh:sh,
           j * dilate[1]:j * dilate[1] + ow * sw_:sw_].reshape(n, c, -1)
        for i in range(kh) for j in range(kw)], axis=2)
    return cols.reshape(n, c * kh * kw, oh * ow)


def _ln_ref(x, g, b, *, axis=-1, eps=1e-5, output_mean_var=False):
    mu = x.mean(axis, keepdims=True)
    var = x.var(axis, keepdims=True)
    return (x - mu) / onp.sqrt(var + eps) * g + b


VALUE_REF.update({
    "Activation": lambda x, *, act_type="relu": {
        "relu": lambda v: onp.maximum(v, 0),
        "sigmoid": _sigmoid,
        "tanh": onp.tanh,
        "softrelu": lambda v: onp.log1p(onp.exp(v)),
        "softsign": lambda v: v / (1 + onp.abs(v)),
    }[act_type](x),
    "LeakyReLU": lambda x, g=None, *, act_type="leaky", slope=0.25,
        lower_bound=0.125, upper_bound=0.334: {
        "leaky": lambda v: onp.where(v > 0, v, slope * v),
        "elu": lambda v: onp.where(v > 0, v, slope * (onp.exp(v) - 1)),
        "prelu": lambda v: onp.where(v > 0, v, (g if g is not None
                                                else slope) * v),
        "gelu": lambda v: 0.5 * v * (1 + _sps.erf(v / onp.sqrt(2))),
        "selu": lambda v: 1.0507009873554805 * onp.where(
            v > 0, v, 1.6732632423543772 * (onp.exp(v) - 1)),
    }[act_type](x),
    "LayerNorm": _ln_ref,
    "RMSNorm": lambda x, g, *, axis=-1, eps=1e-6:
        x / onp.sqrt((x.astype(_NPF) ** 2).mean(axis, keepdims=True)
                     + eps) * g,
    "InstanceNorm": lambda x, g, b, *, eps=1e-3:
        (x - x.mean((2, 3), keepdims=True)) /
        onp.sqrt(x.var((2, 3), keepdims=True) + eps)
        * g.reshape(1, -1, 1, 1) + b.reshape(1, -1, 1, 1),
    "GroupNorm": lambda x, g, b, *, num_groups=1, eps=1e-5:
        _gn_ref(x, g, b, num_groups, eps),
    "topk": lambda x, *, axis=-1, k=1, ret_typ="indices",
        is_ascend=False, dtype="float32": _topk_ref(
            x, axis, k, ret_typ, is_ascend, dtype),
    "split": lambda x, *, num_outputs, axis=1, squeeze_axis=False:
        [s.squeeze(axis) if squeeze_axis else s
         for s in onp.split(x, num_outputs, axis)],
    "allclose_op": lambda a, b, *, rtol=1e-5, atol=1e-8,
        equal_nan=False: onp.asarray(
            onp.allclose(a, b, rtol, atol, equal_nan), onp.float32),
    "clip_global_norm": lambda *arrays, max_norm, scale=1.0:
        [a * min(1.0, max_norm / (onp.sqrt(sum(
            (x.astype(_NPF) ** 2).sum() for x in arrays)) + 1e-12))
         * scale for a in arrays],
    "_image_flip_left_right": lambda x: x[..., ::-1, :],
    "quantile": lambda a, *, q, axis=None, keepdims=False,
        interpolation="linear": onp.quantile(
            a, q, axis=axis, keepdims=keepdims),
    "histogram_op": lambda x, *, bin_cnt=10, range=None: list(
        onp.histogram(onp.asarray(x).ravel(), bins=int(bin_cnt),
                      range=range if range is not None else (0.0, 1.0))),
    "_image_flip_top_bottom": lambda x: x[..., ::-1, :, :]
        if x.ndim == 4 else x[::-1],
    "_image_normalize": lambda x, *, mean=(0.0,), std=(1.0,):
        (x - onp.asarray(mean).reshape(-1, 1, 1)) /
        onp.asarray(std).reshape(-1, 1, 1),
    "_image_to_tensor": lambda x: (x.transpose(2, 0, 1)
                                   if x.ndim == 3 else
                                   x.transpose(0, 3, 1, 2)) / 255.0,
    "_sparse_segment_dot": lambda data, gi, si, rhs, *, num_segments:
        _seg_dot_ref(data, gi, si, rhs, num_segments),
    "_sparse_rowsparse_dot": lambda v, i, rhs, *, num_rows:
        _rs_dot_ref(v, i, rhs, num_rows),
    "_contrib_index_add": lambda x, idx, val: _idx_binop(x, idx, val, True),
    "_contrib_index_copy": lambda x, idx, val: _idx_binop(x, idx, val,
                                                          False),
    "sgd_mom_update": lambda w, g, m, *, lr, momentum=0.0, wd=0.0,
        rescale_grad=1.0, clip_gradient=-1.0, lazy_update=True:
        _sgd_mom_ref(w, g, m, lr, momentum, wd, rescale_grad,
                     clip_gradient),
    "adam_update": lambda w, g, m, v, *, lr, beta1=0.9, beta2=0.999,
        epsilon=1e-8, wd=0.0, rescale_grad=1.0, clip_gradient=-1.0,
        lazy_update=True: _adam_ref(w, g, m, v, lr, beta1, beta2,
                                    epsilon, wd, rescale_grad,
                                    clip_gradient),
    # ---- variadic stacking (numpy wants one sequence argument) -------- #
    "concat": lambda *a, dim=1: onp.concatenate(a, axis=dim),
    "stack": lambda *a, axis=0: onp.stack(a, axis=axis),
    "dstack": lambda *a: onp.dstack(a),
    "hstack": lambda *a: onp.hstack(a),
    "vstack": lambda *a: onp.vstack(a),
    "column_stack": lambda *a: onp.column_stack(a),
    "meshgrid": lambda *a, indexing="xy": list(
        onp.meshgrid(*a, indexing=indexing)),
    # ---- axis-default / signature divergences from numpy/scipy -------- #
    "softmax": lambda x, length=None, *, axis=-1, temperature=None,
        use_length=False: _sps.softmax(
            _np(x) / (temperature or 1.0), axis=axis),
    "log_softmax": lambda x, *, axis=-1, temperature=None:
        _sps.log_softmax(_np(x) / (temperature or 1.0), axis=axis),
    "identity": lambda x: x,
    "full_like": lambda x, *, fill_value=0.0: onp.full_like(
        x, fill_value),
    "swapaxes": lambda x, *, dim1=0, dim2=1: onp.swapaxes(x, dim1, dim2),
    "pad": lambda x, *, mode="constant", pad_width=(), constant_value=0:
        onp.pad(x, onp.asarray(pad_width).reshape(-1, 2),
                mode={"constant": "constant", "edge": "edge",
                      "reflect": "reflect"}[mode],
                **({"constant_values": constant_value}
                   if mode == "constant" else {})),
    "unravel_index": lambda i, *, shape: onp.stack(
        onp.unravel_index(i.astype(int), shape)),
    "ravel_multi_index": lambda i, *, shape: onp.ravel_multi_index(
        tuple(i.astype(int)), dims=shape),
    "gcd": lambda a, b: onp.gcd(a.astype(onp.int64), b.astype(onp.int64)),
    "lcm": lambda a, b: onp.lcm(a.astype(onp.int64), b.astype(onp.int64)),
    "ldexp": lambda a, b: onp.ldexp(a, b.astype(int)),
    "FullyConnected": lambda x, w, b=None, *, num_hidden=0,
        no_bias=False, flatten=True:
        (x.reshape(x.shape[0], -1) if flatten else x) @ w.T
        + (0 if (b is None or no_bias) else b),
    "_image_crop": lambda img, **kw: img[
        kw.get("y", 0):kw.get("y", 0) + kw.get("height", 1),
        kw.get("x", 0):kw.get("x", 0) + kw.get("width", 1), :],
    "linalg_potri": lambda a: onp.linalg.inv(onp.tril(a) @ onp.tril(a).T),
    "linalg_makediag": lambda a, *, offset=0: onp.stack(
        [onp.diag(v, k=offset) for v in a]) if a.ndim == 2 else
        onp.diag(a, k=offset),
    "_sparse_rowsparse_dot_t": lambda v, i, rhs, *, num_cols:
        v.T.astype(_NPF) @ rhs[onp.asarray(i).astype(int)],
    "all_finite": lambda x, *, init_output=True: onp.asarray(
        [onp.isfinite(x).all()], onp.float32),
    "multi_all_finite": lambda *a, **kw: onp.asarray(
        [all(onp.isfinite(x).all() for x in a)], onp.float32),
})


def _sgd_mom_ref(w, g, m, lr, momentum, wd, rg, cg):
    m2 = momentum * m - lr * (_clipg(rg * g, cg) + wd * w)
    return [w + m2, m2]


def _gn_ref(x, g, b, ng, eps):
    n, c, h, w = x.shape
    xr = x.reshape(n, ng, c // ng, h, w)
    mu = xr.mean((2, 3, 4), keepdims=True)
    var = xr.var((2, 3, 4), keepdims=True)
    xn = ((xr - mu) / onp.sqrt(var + eps)).reshape(n, c, h, w)
    return xn * g.reshape(1, -1, 1, 1) + b.reshape(1, -1, 1, 1)


def _topk_ref(x, axis, k, ret_typ, is_ascend, dtype):
    key = x if is_ascend else -x
    idx = onp.argsort(key, axis=axis, kind="stable")
    idx = onp.take(idx, onp.arange(k), axis=axis)
    vals = onp.take_along_axis(x, idx, axis=axis)
    if ret_typ == "value":
        return vals
    if ret_typ == "both":
        return [vals, idx.astype(dtype)]
    return idx.astype(dtype)


def _seg_dot_ref(data, gi, si, rhs, num_segments):
    out = onp.zeros((num_segments, rhs.shape[1]), _NPF)
    for j in range(data.shape[0]):
        out[int(si[j])] += data[j] * rhs[int(gi[j])]
    return out


def _rs_dot_ref(v, i, rhs, num_rows):
    out = onp.zeros((num_rows, rhs.shape[1]), _NPF)
    out[i.astype(int)] = v @ rhs
    return out


def _idx_binop(x, idx, val, add):
    out = onp.array(x, copy=True)
    if add:
        onp.add.at(out, idx.astype(int), val)
    else:
        out[idx.astype(int)] = val
    return out


def _adam_ref(w, g, m, v, lr, b1, b2, eps, wd, rg, cg):
    gr = _clipg(rg * g, cg) + wd * w
    m2 = b1 * m + (1 - b1) * gr
    v2 = b2 * v + (1 - b2) * gr * gr
    return [w - lr * m2 / (onp.sqrt(v2) + eps), m2, v2]


# ops with no derivable closed-form numpy reference; each entry names
# where the op's VALUES are asserted instead
VALUE_EXEMPT = {
    # conv/pool families: golden-tested against scipy/torch-free
    # references in their family tests
    "Convolution": "golden vs explicit loops: tests/test_operator.py",
    "Deconvolution": "golden: tests/test_operator.py",
    "Pooling": "golden: tests/test_operator.py",
    "Correlation": "golden: tests/test_vision_ops.py",
    "col2im": "inverse-of-im2col asserted in tests/test_extended_ops.py",
    "LRN": "cross-channel normalization golden in tests/test_legacy_ops.py",
    "UpSampling": "golden: tests/test_legacy_ops.py",
    # attention / rnn: parity vs naive implementations
    "flash_attention": "parity vs naive attention: tests/test_attention.py",
    "flash_attention_qkv":
        "parity vs the split + flash_attention: tests/test_attention.py",
    "rope": "rotation identities: tests/test_llama.py",
    "fused_rnn": "parity vs unrolled cells: tests/test_rnn.py",
    "_contrib_interleaved_matmul_selfatt_qk":
        "parity vs explicit qk matmul: tests/test_models.py",
    "_contrib_interleaved_matmul_selfatt_valatt":
        "parity vs explicit attention: tests/test_models.py",
    # vision contrib: behavioral tests in tests/test_vision_ops.py
    "BilinearSampler": "grid-sampling goldens: tests/test_vision_ops.py",
    "GridGenerator": "affine grid goldens: tests/test_vision_ops.py",
    "SpatialTransformer": "goldens: tests/test_vision_ops.py",
    "ROIPooling": "goldens: tests/test_vision_ops.py",
    "_contrib_ROIAlign": "goldens: tests/test_vision_ops.py",
    "_contrib_BilinearResize2D": "goldens: tests/test_vision_ops.py",
    "_contrib_DeformableConvolution":
        "reduces-to-Convolution-at-zero-offset: tests/test_vision_ops.py",
    "_contrib_MultiBoxPrior": "anchor goldens: tests/test_vision_ops.py",
    "_contrib_MultiBoxDetection": "goldens: tests/test_vision_ops.py",
    "_contrib_MultiBoxTarget": "goldens: tests/test_vision_ops.py",
    "_contrib_Proposal": "rpn goldens: tests/test_vision_ops.py",
    "_contrib_box_nms": "nms goldens: tests/test_vision_ops.py",
    "im2col": "patch-extraction goldens: tests/test_extended_ops.py",
    # losses with nontrivial dynamic programming
    "CTCLoss": "vs hand-computed alignments: tests/test_op_conformance "
               "vjp + tests/test_gluon.py loss goldens",
    "SVMOutput": "margin semantics: tests/test_legacy_ops.py",
    # quantization: int8 contracts tested end-to-end
    "_contrib_quantize_v2": "roundtrip: tests/test_quantization_onnx_custom.py",
    "_contrib_dequantize": "roundtrip: tests/test_quantization_onnx_custom.py",
    "_contrib_requantize": "roundtrip: tests/test_quantization_onnx_custom.py",
    "quantized_conv_int8": "vs f32 conv: tests/test_quantization_onnx_custom.py",
    "quantized_matmul_int8": "vs f32 matmul: tests/test_quantization_onnx_custom.py",
    "quantized_act_int8": "vs f32 act: tests/test_quantization_onnx_custom.py",
    "quantized_pooling_int8": "vs f32 pool: tests/test_quantization_onnx_custom.py",
    # random draws have no deterministic reference; distribution moments
    # are asserted in tests/test_numpy.py / test_samplers_image_ops.py
    "_random_exponential": "moment tests", "_random_gamma": "moment tests",
    "_random_generalized_negative_binomial": "moment tests",
    "_random_negative_binomial": "moment tests",
    "_random_normal": "moment tests", "_random_poisson": "moment tests",
    "_random_randint": "support tests", "_random_uniform": "support tests",
    "sample_exponential": "moment tests", "sample_gamma": "moment tests",
    "sample_generalized_negative_binomial": "moment tests",
    "sample_multinomial": "support tests",
    "sample_negative_binomial": "moment tests",
    "sample_normal": "moment tests", "sample_poisson": "moment tests",
    "sample_uniform": "support tests",
    "_DropoutImpl": "mask statistics: tests/test_attention.py dropout",
    "_BatchNormStats": "vs jnp closed form: tests/test_parallel.py BN",
    "boolean_mask": "compaction semantics: tests/test_extended_ops.py",
    "_image_random_brightness": "random draw: tests/test_samplers_image_ops.py",
    "_image_random_contrast": "random draw: tests/test_samplers_image_ops.py",
    "_image_random_saturation": "random draw: tests/test_samplers_image_ops.py",
    "_image_random_flip_left_right": "random draw: tests/test_samplers_image_ops.py",
    "_image_random_flip_top_bottom": "random draw: tests/test_samplers_image_ops.py",
    "_image_resize": "interp goldens: tests/test_samplers_image_ops.py",
    "_sparse_softmax_ce": "fused sparse-label CE vs dense CE: tests/test_models.py",
    "fft": "packed real/imag layout: tests/test_legacy_ops.py",
    "ifft": "packed real/imag layout: tests/test_legacy_ops.py",
    "ring_attention": "parity-asserted in __graft_entry__ dryrun",
    # optimizer update ops beyond the closed forms above: each is the
    # registered kernel behind an Optimizer whose trajectory is asserted
    # in tests/test_optimizer_metric.py
    "adadelta_update": "tests/test_optimizer_metric.py",
    "adagrad_update": "tests/test_optimizer_metric.py",
    "adamw_update": "tests/test_optimizer_metric.py",
    "ftml_update": "tests/test_optimizer_metric.py",
    "ftrl_update": "tests/test_optimizer_metric.py",
    "group_adagrad_update": "tests/test_optimizer_metric.py",
    "lamb_update_phase1": "tests/test_optimizer_metric.py",
    "lamb_update_phase2": "tests/test_optimizer_metric.py",
    "lans_update": "tests/test_optimizer_metric.py",
    "mp_adamw_update": "tests/test_optimizer_metric.py",
    "mp_nag_mom_update": "tests/test_optimizer_metric.py",
    "mp_sgd_mom_update": "tests/test_optimizer_metric.py",
    "mp_sgd_update": "tests/test_optimizer_metric.py",
    "multi_adamw_update": "tests/test_optimizer_metric.py",
    "multi_lamb_update": "tests/test_optimizer_metric.py",
    "multi_mp_sgd_mom_update": "tests/test_optimizer_metric.py",
    "multi_mp_sgd_update": "tests/test_optimizer_metric.py",
    "multi_sgd_mom_update": "tests/test_optimizer_metric.py",
    "multi_sgd_update": "tests/test_optimizer_metric.py",
    "nag_mom_update": "tests/test_optimizer_metric.py",
    "preloaded_multi_sgd_mom_update": "tests/test_optimizer_metric.py",
    "preloaded_multi_sgd_update": "tests/test_optimizer_metric.py",
    "rmsprop_update": "tests/test_optimizer_metric.py",
    "rmspropalex_update": "tests/test_optimizer_metric.py",
    "signum_update": "tests/test_optimizer_metric.py",
    "linalg_gelqf": "QR/LQ reconstruction identity: tests/test_linalg_ops.py",
}


def _resolve_ref(name):
    if name in VALUE_REF:
        return VALUE_REF[name]
    f = getattr(onp, name, None)
    if f is not None and callable(f):
        return f
    f = getattr(_sps, name, None)
    if f is not None and callable(f):
        return f
    return None


VALUE_CHECKED = [n for n in ALL_OPS
                 if n not in VALUE_EXEMPT and n not in SKIP
                 and _resolve_ref(n) is not None]
_UNCOVERED = [n for n in ALL_OPS
              if n not in VALUE_EXEMPT and n not in SKIP
              and _resolve_ref(n) is None]


@pytest.mark.parametrize("name", VALUE_CHECKED)
def test_forward_values(name):
    """f32 forward outputs == the independent NumPy/SciPy computation
    (the upgrade from finiteness smoke to value correctness)."""
    o = registry.OPS[name]
    args, kwargs = build_inputs(o, jnp.float32)
    res = _flat_outputs(o.fn(*args, **kwargs))
    np_args = [onp.asarray(a) if hasattr(a, "dtype") else a for a in args]
    expected = _flat_outputs(_resolve_ref(name)(*np_args, **kwargs))
    assert len(res) == len(expected), \
        f"{name}: {len(res)} outputs vs reference {len(expected)}"
    for got, exp in zip(res, expected):
        g = onp.asarray(got)
        e = onp.asarray(exp)
        assert g.shape == tuple(e.shape), \
            f"{name}: shape {g.shape} vs reference {e.shape}"
        onp.testing.assert_allclose(
            g.astype(_NPF), e.astype(_NPF), rtol=2e-3, atol=1e-4,
            err_msg=f"{name}: forward values diverge from numpy reference")


# dtype consistency needs deterministic ops; PRNG-consuming ops are the
# only exclusion beyond the fixed-dtype tables
_CONSISTENCY_EXEMPT = {n for n in ALL_OPS
                       if n.startswith(("_random_", "sample_",
                                        "_image_random_"))} | {
    "_DropoutImpl",  # mask threshold moves under bf16 rounding
    # bilinear sampling positions come FROM the (bf16-rounded) offset
    # input — a rounded offset moves the sample cell, a legitimate
    # discontinuity, not a numeric error
    "_contrib_DeformableConvolution",
}


@pytest.mark.parametrize(
    "name", [n for n in ALL_OPS
             if n not in SKIP and n not in FIXED_DTYPE
             and n not in F32_ONLY and n not in _CONSISTENCY_EXEMPT])
def test_dtype_consistency(name):
    """f32 vs bf16 runs agree at bf16-scaled tolerance (the reference's
    cross-dtype check_consistency, SURVEY.md §7).  Float outputs only —
    integer outputs (argmax/topk indices) may legitimately flip when
    bf16 rounding creates ties."""
    o = registry.OPS[name]
    a32, k32 = build_inputs(o, jnp.float32)
    a16, k16 = build_inputs(o, jnp.bfloat16)
    r32 = _flat_outputs(o.fn(*a32, **k32))
    r16 = _flat_outputs(o.fn(*a16, **k16))
    assert len(r32) == len(r16)
    for g32, g16 in zip(r32, r16):
        if not jnp.issubdtype(jnp.asarray(g32).dtype, jnp.floating):
            continue
        x32 = onp.asarray(jnp.asarray(g32).astype(jnp.float32))
        x16 = onp.asarray(jnp.asarray(g16).astype(jnp.float32))
        assert x32.shape == x16.shape, f"{name}: shape drift across dtype"
        onp.testing.assert_allclose(
            x16, x32, rtol=6e-2, atol=6e-2,
            err_msg=f"{name}: f32 vs bf16 runs diverge beyond bf16 "
                    "tolerance")


def test_value_tables_are_live_and_cover_registry():
    """Extends the staleness meta-test to the value tables (VERDICT r4
    item 3): entries must name real ops, every op must be value-checked
    or explicitly exempted with a reason, and coverage must stay >= 60%
    of the registry."""
    known = set(ALL_OPS)
    for table, tname in ((VALUE_REF, "VALUE_REF"),
                         (VALUE_EXEMPT, "VALUE_EXEMPT")):
        stale = set(table) - known
        assert not stale, f"{tname} names unknown ops: {sorted(stale)}"
    assert not _UNCOVERED, \
        (f"ops with neither a value reference nor a VALUE_EXEMPT entry: "
         f"{sorted(_UNCOVERED)}")
    frac = len(VALUE_CHECKED) / len(ALL_OPS)
    assert frac >= 0.60, \
        f"value-checked coverage {frac:.0%} fell below the 60% floor"
