"""The routed experts' grouped-product kernel (ISSUE 39,
``ops/grouped_matmul.py``, ``mx_moe_gmm``) in interpret mode against the XLA
form it replaces on a TPU lowering — ``ops/moe.py::_ragged``, two
``lax.ragged_dot`` calls — on the same sorted rows: empty groups, every row
in one group, a dead tail bound for other chips' experts, a group straddling
two row tiles, a stacked run indexed by a traced layer, and
``routed_experts`` whole at the ``dots3`` and ``trinity`` widths scaled down
(top-8 and top-4).  Besides: the kernel's device time reads under
``mx.moe_experts``, and the static shapes decide which path runs.

Tolerance.  Float32 rows: the two forms sum the first product in another
order, ``1e-5`` of the largest entry.  Bfloat16 rows: that order can move
``silu(g) * u`` across a bfloat16 rounding boundary before the second
product, so an entry may differ by one rounding of ``a`` (2**-8 relative)
carried through ``W_d``: ``2**-7`` of the largest entry.  Only the live rows
are compared: the rest are the caller's to mask.
"""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

from mxnet_tpu import profiler_xla
from mxnet_tpu.ops import grouped_matmul as gm
from mxnet_tpu.ops import moe


def _tol(dtype):
    return 1e-5 if dtype == jnp.float32 else 2.0 ** -7


def _weights(rng, G, H, I, dtype):
    wgu = jnp.asarray(rng.normal(size=(G, H, 2 * I)) / H ** 0.5, dtype)
    wd = jnp.asarray(rng.normal(size=(G, I, H)) / I ** 0.5, dtype)
    return wgu, wd


# name: (rows M, H, I, group sizes, row tile, dtype, stacked layers, layer)
CASES = {
    "empty_groups": (32, 128, 128, [0, 5, 0, 0, 9, 0, 2, 0], 8,
                     jnp.float32, 1, 0),
    "one_group": (32, 128, 128, [0, 0, 32, 0], 8, jnp.float32, 1, 0),
    # 11 live rows of 48: the tail is bound for other chips' experts
    "dead_tail": (48, 128, 128, [4, 0, 7, 0], 16, jnp.bfloat16, 1, 0),
    # group 1 covers rows 5-20: three tiles of 8
    "straddle": (32, 256, 128, [5, 16, 3, 0], 8, jnp.float32, 1, 0),
    "nothing_live": (32, 128, 128, [0, 0, 0, 0], 8, jnp.float32, 1, 0),
    # a run of 3 layers of 4 experts: layer 2's are groups 8-11
    "stacked_run": (32, 128, 128, [3, 0, 6, 2], 8, jnp.float32, 3, 2),
    "stacked_run_bf16": (32, 256, 128, [0, 9, 1, 4], 16, jnp.bfloat16, 2, 1),
}


@pytest.mark.parametrize("case", list(CASES))
def test_kernel_matches_the_ragged_dot_form(case):
    M, H, I, sizes, tm, dtype, L, layer = CASES[case]
    n = len(sizes)
    rng = onp.random.default_rng(len(case))
    xs = jnp.asarray(rng.normal(size=(M, H)), dtype)
    wgu, wd = _weights(rng, L * n, H, I, dtype)
    load = jnp.asarray(sizes, jnp.int32)
    base = jnp.int32(layer * n)
    tiles = (tm,) + gm.plan(M, H, I, dtype)[1:]
    got = jax.jit(lambda x, a, b, s, o: gm.grouped_swiglu(
        x, a, b, s, o, interpret=True, tiles=tiles))(xs, wgu, wd, load, base)
    want = moe._ragged(xs, wgu, wd, load, base)
    live = sum(sizes)
    got, want = onp.asarray(got)[:live], onp.asarray(want)[:live]
    scale = max(float(onp.abs(want).max()), 1e-30) if live else 1.0
    onp.testing.assert_allclose(got, want, rtol=0, atol=_tol(dtype) * scale)
    if case == "straddle":
        gid, tid, _, visits = gm.metadata(load, M, tm)
        v = int(visits)
        # group 0 in tile 0, group 1 in tiles 0-2, group 2 in tile 2
        assert list(onp.asarray(gid)[:v]) == [0, 1, 1, 1, 2]
        assert list(onp.asarray(tid)[:v]) == [0, 0, 1, 2, 2]


# (hidden, expert width, held experts of the router's, top_k, tokens): the
# two cells' widths cut 40 / 24 times, 4 held of 32 like 32 of 256
WIDTHS = {"dots3": (128, 64, (4, 32), 8, 16),
          "trinity": (128, 128, (4, 32), 4, 24)}


@pytest.mark.parametrize("stacked", [False, True])
@pytest.mark.parametrize("family", list(WIDTHS))
def test_routed_experts_take_the_kernel(family, stacked, monkeypatch):
    """``routed_experts`` whole, the kernel interpreted against the XLA
    form, at scaled widths; with ``stacked`` the experts are a run of two
    layers and the layer a traced index inside a scan."""
    H, I, (n, E), K, N = WIDTHS[family]
    rng = onp.random.default_rng(7)
    dtype = jnp.bfloat16
    x = jnp.asarray(rng.normal(size=(N, H)), dtype)
    wr = jnp.asarray(rng.normal(size=(H, E)) / H ** 0.5, jnp.float32)
    L = 2 if stacked else 1
    wgu, wd = _weights(rng, L * n, H, I, dtype)
    wgu, wd = wgu.reshape(L, n, H, 2 * I), wd.reshape(L, n, I, H)
    lo = 8

    def run():
        idx, wts = moe.route(x, wr, jnp.zeros((E,)), K)
        if not stacked:
            return moe.routed_experts(x, idx, wts, wgu[0], wd[0], lo)

        def layer(_, j):
            return None, moe.routed_experts(x, idx, wts, wgu, wd, lo, j)
        return jax.lax.scan(layer, None, jnp.arange(L, dtype=jnp.int32))[1]

    want_y, want_load = jax.jit(run)()
    monkeypatch.setenv("MXNET_FLASH_INTERPRET", "1")
    # a function of its own: jit's trace cache keys on the function
    kernel = lambda: run()          # noqa: E731
    text = str(jax.make_jaxpr(kernel)())
    assert "pallas_call" in text and "ragged_dot" not in text
    got_y, got_load = jax.jit(kernel)()
    onp.testing.assert_array_equal(onp.asarray(got_load),
                                   onp.asarray(want_load))
    assert int(onp.asarray(want_load).sum()) > 0
    want_y = onp.asarray(want_y, onp.float32)
    onp.testing.assert_allclose(
        onp.asarray(got_y, onp.float32), want_y, rtol=0,
        atol=_tol(dtype) * float(onp.abs(want_y).max()))


def test_kernel_reads_under_moe_experts():
    """The kernel's events carry no provenance: its name says its region,
    as ``ragged-dot``'s does."""
    assert profiler_xla.region_of(None, gm._NAME + ".4") == "mx.moe_experts"
    assert profiler_xla.region_of("", "ragged-dot-none.1") == \
        "mx.moe_experts"


@pytest.mark.parametrize("shape, want", [
    # the four shapes of the cells (bfloat16): rows, H, I -> (tm, tk, ti)
    ((96, 3072, 3072), (32, 256, 512)),
    ((256, 5120, 1536), (128, 640, 384)),
    ((512, 3072, 3072), (128, 256, 512)),
    ((1024, 5120, 1536), (128, 640, 384)),
    # no whole bfloat16 row tile divides 24 rows: the XLA form
    ((24, 128, 128), None),
])
def test_shape_rule_picks_the_documented_path(shape, want, monkeypatch):
    M, H, I = shape
    assert gm.plan(M, H, I, jnp.bfloat16) == want
    if M > 96:
        return
    monkeypatch.setenv("MXNET_FLASH_INTERPRET", "1")
    xs = jax.ShapeDtypeStruct((M, H), jnp.bfloat16)
    wgu = jax.ShapeDtypeStruct((4, H, 2 * I), jnp.bfloat16)
    wd = jax.ShapeDtypeStruct((4, I, H), jnp.bfloat16)
    load = jax.ShapeDtypeStruct((4,), jnp.int32)
    text = str(jax.make_jaxpr(
        lambda x, a, b, s: moe._products(x, a, b, s, 0))(xs, wgu, wd, load))
    assert ("pallas_call" in text) == (want is not None)
    assert ("ragged_dot" in text) == (want is None)
