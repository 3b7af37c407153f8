"""The paged-attention kernel (ISSUE 30, ``ops/paged_attention.py``) in
interpret mode against the view path it replaces — ``_paged_rows`` +
the new row landed in the view + ``_flat_attention`` — on the same pools:
every length from an empty cache to the last column, mixed lengths in one
batch, a retired slot, pages out of order and shared between slots, native
bfloat16 and float32 pools, one and several query heads a K/V head.  Then
the paged decode step through the kernel, greedy token for token against
the dense step, and the page-walk counters of a CPU server.
"""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu.models import decoding
from mxnet_tpu.ops import paged_attention as pa

B, PAGE, MAXP, NL, D = 4, 16, 20, 2, 64
T = PAGE * MAXP                 # 320: one full compute block and a part
ROWS = pa._ROWS
NPAGES = 48
SCALE = 0.125
RETIRED = 3
HEADS = {"mha": (2, 2), "gqa": (4, 2)}          # (H, KV)


assert ROWS < T < 2 * ROWS


def _tables(rng):
    """Slot 0 and slot 2 own their pages, handed out in shuffled order;
    slot 1 shares slot 2's first two pages (a cached prefix) and owns the
    rest; slot 3 is retired: a sentinel row."""
    ids = list(rng.permutation(NPAGES))
    pt = onp.full((B, MAXP), NPAGES, onp.int32)
    pt[0] = [ids.pop() for _ in range(MAXP)]
    pt[2] = [ids.pop() for _ in range(MAXP)]
    pt[1, :2] = pt[2, :2]
    pt[1, 2:5] = [ids.pop() for _ in range(3)]
    return pt


@pytest.fixture(scope="module", params=["float32", "bfloat16"])
def dtype(request):
    return jnp.dtype(request.param)


@pytest.fixture(scope="module", params=sorted(HEADS))
def case(request, dtype):
    """Pools, table and one layer's new rows, with the kernel (interpreted)
    and the view path jitted once over the positions."""
    H, KV = HEADS[request.param]
    rng = onp.random.RandomState(3)
    F = KV * D
    kp = jnp.asarray(rng.randn(NL, NPAGES, PAGE, F), dtype)
    vp = jnp.asarray(rng.randn(NL, NPAGES, PAGE, F), dtype)
    q = jnp.asarray(rng.randn(B, H, D), dtype)
    kn = jnp.asarray(rng.randn(B, F), dtype)
    vn = jnp.asarray(rng.randn(B, F), dtype)
    pt = jnp.asarray(_tables(rng))
    layer = jnp.int32(1)

    def view(pos):
        iB = jnp.arange(B)
        kc = decoding._paged_rows(kp, pt, layer, dtype).at[iB, pos].set(kn)
        vc = decoding._paged_rows(vp, pt, layer, dtype).at[iB, pos].set(vn)
        idx = jnp.arange(T)[None, None, :]
        return decoding._flat_attention(
            q[:, None], kc, vc, idx <= pos[:, None, None], SCALE,
            dtype).reshape(B, H * D)

    def kernel(pos):
        lengths = pa.walk_lengths(pt, pos, PAGE, NPAGES)
        return pa._kernel_call(q, kn, vn, kp, vp, layer, pt, lengths,
                               SCALE, interpret=True)

    def exact(pos):
        """float64 on the host from the same operands."""
        f = lambda a: onp.asarray(a.astype(jnp.float32), onp.float64)
        k_all, v_all, table = f(kp[1]), f(vp[1]), onp.asarray(pt)
        out = onp.zeros((B, H * D))
        for b in range(B):
            if b == RETIRED:
                continue
            rows = [(table[b, t // PAGE], t % PAGE) for t in range(pos[b])]
            K = onp.stack([k_all[r] for r in rows] + [f(kn)[b]])
            V = onp.stack([v_all[r] for r in rows] + [f(vn)[b]])
            for h in range(H):
                lanes = slice(h // (H // KV) * D, (h // (H // KV) + 1) * D)
                s = K[:, lanes] @ f(q)[b, h] * SCALE
                p = onp.exp(s - s.max())
                out[b, h * D:(h + 1) * D] = p / p.sum() @ V[:, lanes]
        return out

    return jax.jit(view), jax.jit(kernel), exact


# slot 0's position: an empty cache, one token, the last row of a page,
# a whole page, mid-page, either side of a compute block, the last column
@pytest.mark.parametrize("pos0", [0, 1, PAGE - 1, PAGE, 100, ROWS - 1, ROWS,
                                  ROWS + 1, T - 1])
def test_kernel_matches_the_view_path(case, dtype, pos0):
    view, kernel, exact = case
    # slot 1 reads shared prefix pages; slot 3's pos is stale
    pos = onp.array([pos0, 37, 300, 77], onp.int32)
    live = onp.arange(B) != RETIRED
    got = onp.asarray(kernel(jnp.asarray(pos)).astype(jnp.float32))
    want = onp.asarray(view(jnp.asarray(pos)).astype(jnp.float32))
    assert onp.isfinite(got).all()
    if dtype == jnp.float32:
        # the same products, summed in another order
        onp.testing.assert_allclose(got[live], want[live], rtol=1e-5,
                                    atol=1e-5)
        return
    # bfloat16: both round p before p·V (the kernel before the softmax's
    # division, the view path after) and the result once; neither may lie
    # further from the exact value than bfloat16's step allows
    ref = exact(pos)
    err_kernel = onp.abs(got - ref)[live].max()
    err_view = onp.abs(want - ref)[live].max()
    assert err_kernel < 2e-2, (err_kernel, err_view)
    assert err_kernel < 2 * err_view + 2e-3, (err_kernel, err_view)


def test_retired_slot_walks_nothing(case):
    """A sentinel row walks no page whatever its stale ``pos``; what comes
    out for it is its own new V row (one key, weight 1)."""
    _, kernel, _ = case
    pt = jnp.asarray(_tables(onp.random.RandomState(3)))
    pos = jnp.asarray([5, 37, 300, 77], jnp.int32)
    assert onp.asarray(pa.walk_lengths(pt, pos, PAGE, NPAGES)).tolist() == \
        [5, 37, 300, 0]
    assert onp.isfinite(onp.asarray(
        kernel(pos).astype(jnp.float32))[RETIRED]).all()


def test_walk_stops_at_the_first_sentinel_and_the_table_width():
    pt = jnp.asarray([[3, 4, NPAGES, 5], [0, 1, 2, 3], [NPAGES] * 4,
                      [7, NPAGES, NPAGES, NPAGES]], jnp.int32)
    pos = jnp.asarray([60, 999, 9, 16], jnp.int32)
    assert onp.asarray(pa.walk_lengths(pt, pos, PAGE, NPAGES)).tolist() == \
        [2 * PAGE, 4 * PAGE, 0, 16]


@pytest.mark.parametrize("lanes, dtype, page, heads, ok", [
    (1280, "bfloat16", 16, 20, True),       # GPT-2-large's pool
    (128, "float32", 8, 2, True),
    (256, "bfloat16", 16, 16, True),        # four query heads a K/V head
    (1280, "bfloat16", 8, 20, False),       # half a bfloat16 sublane tile
    (64, "float32", 16, 4, False),          # half a lane tile
    (1280, "int8", 32, 20, False),          # codes: the view dequantizes
    (128, "float32", 24, 2, False),         # pages do not fill a block
], ids=["gpt2_large", "f32_page8", "gqa", "short_page", "narrow_rows",
        "int8", "odd_page"])
def test_supported_pool_structures(lanes, dtype, page, heads, ok):
    assert pa.supports(lanes, dtype, page, heads, 64) is ok


# --------------------------------------------------------------------------- #
# the decode step through the kernel
# --------------------------------------------------------------------------- #

S_B, S_T, S_PAGE = 3, 64, 8
S_MAXP = S_T // S_PAGE
S_NPAGES = S_B * S_MAXP + 2


def _net(family):
    mx.random.seed(0)
    if family == "gpt_mha":
        from mxnet_tpu.models import GPT, GPTConfig
        net = GPT(GPTConfig(vocab_size=97, max_length=S_T, num_layers=2,
                            units=128, num_heads=2, hidden_size=256))
    else:
        from mxnet_tpu.models import llama_tiny
        net, cfg = llama_tiny(units=256, num_heads=4, num_kv_heads=2,
                              hidden_size=256, max_length=S_T)
        assert cfg.num_kv_heads < cfg.num_heads
    net.initialize(mx.init.Normal(0.2))
    return net


@pytest.mark.parametrize("lowering", ["kernel_interpreted", "cpu_default"])
@pytest.mark.parametrize("family", ["gpt_mha", "llama_gqa"])
def test_paged_step_through_the_kernel_is_greedy_exact(family, lowering,
                                                       monkeypatch):
    """``tests/test_paged_parity.py``'s greedy check on a pool the kernel
    takes (rows of 128 lanes, pages of 8 float32 rows): the paged step,
    with the kernel interpreted and as a CPU lowers it (the view path
    behind ``platform_dependent``), against the dense step, token for
    token, with a retired lane and a previous tenant's values behind every
    mask."""
    from mxnet_tpu.gluon.parameter import params_swapped
    from mxnet_tpu.models.decoding import _DecodeEngine, _TRACE_LOCK
    if lowering == "kernel_interpreted":
        monkeypatch.setenv("MXNET_FLASH_INTERPRET", "1")
    else:
        monkeypatch.delenv("MXNET_FLASH_INTERPRET", raising=False)
    eng = _DecodeEngine(_net(family), S_B, 1, S_T, 0.0, 0, "batched",
                        "native", "auto")
    assert eng.walks_pages(S_PAGE) and not eng.walks_pages(S_PAGE, True)
    param_vals, q8, sw = eng.take_operands()
    NL, KV, Dh = eng.NL, eng.KV, eng.D
    rng = onp.random.RandomState(7)
    pos = onp.array([5, 17, 40], onp.int32)
    live = onp.array([True, False, True])
    steps, stale = 10, 1.0e4
    order = [S_NPAGES - 1] + list(rng.permutation(S_NPAGES - 1))
    table = onp.full((S_B, S_MAXP), S_NPAGES, onp.int32)
    for b in range(S_B):
        if live[b]:
            for j in range(-(-(pos[b] + steps) // S_PAGE)):
                table[b, j] = order.pop(0)
    kd = rng.randn(NL, S_B, KV, S_T, Dh).astype("float32")
    vd = rng.randn(NL, S_B, KV, S_T, Dh).astype("float32")
    written = ((onp.arange(S_T)[None] < pos[:, None])
               & live[:, None])[None, :, None, :, None]
    kd, vd = onp.where(written, kd, 0.0), onp.where(written, vd, 0.0)

    def pool_of(dense):
        pool = onp.full((NL, S_NPAGES, S_PAGE, KV * Dh), stale, onp.float32)
        rows = onp.where(written, dense, stale).transpose(
            0, 1, 3, 2, 4).reshape(NL, S_B, S_MAXP, S_PAGE, KV * Dh)
        for b in range(S_B):
            for j in range(S_MAXP):
                if table[b, j] < S_NPAGES:
                    pool[:, table[b, j]] = rows[:, b, j]
        return jnp.asarray(pool)

    def dense_step(tok, pos, ck, cv):
        with _TRACE_LOCK, params_swapped(eng.params, param_vals):
            return eng.pool_token(tok, pos, ck, cv, sw, q8)

    def paged_step(tok, pos, kp, vp, pt):
        with _TRACE_LOCK, params_swapped(eng.params, param_vals):
            return eng.pool_token_paged(tok, pos, kp, vp, pt, S_PAGE, sw, q8)

    dense_step, paged_step = jax.jit(dense_step), jax.jit(paged_step)
    tok_d = tok_p = jnp.asarray(rng.randint(0, 97, S_B), jnp.int32)
    ck, cv, kp, vp = jnp.asarray(kd), jnp.asarray(vd), pool_of(kd), \
        pool_of(vd)
    pt = jnp.asarray(table)
    for step in range(steps):
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


# --------------------------------------------------------------------------- #
# the scheduler's page-walk counters
# --------------------------------------------------------------------------- #

def _server(net, **kw):
    from mxnet_tpu import serve
    return serve.DecodeServer(
        net, autostart=False, pool_sizes=(4,), admit_sizes=(1, 2),
        prefill_buckets=(8, 16, 32), spec=False, max_total_len=S_T,
        page_size=S_PAGE, prefix_cache=False, **kw)


def test_server_counts_the_pages_a_walk_reads():
    """``stats()["step_pages_walked"]`` / ``["step_pages_table"]`` after a
    known schedule: three requests of known lengths, of which the short
    one retires while the others step on.  Every step dispatch adds, for
    each slot live in its table operand, the pages that hold the slot's
    cached tokens and the table's width — nothing for a retired slot."""
    srv = _server(_net("gpt_mha"))
    assert srv._progs.step_walks
    page, maxp = srv._progs.page, srv._progs.maxp
    step = srv._progs.step_fn()
    seen = []       # per step dispatch: {slot live in its table: prompt}

    def spy(*args):
        rows = onp.asarray(args[4])[:, 0] < srv._progs.num_pages
        seen.append({int(i): int(srv._slots[i].prompt.size)
                     for i in onp.nonzero(rows)[0]})
        return step(*args)

    srv._progs._step = spy
    want = [(5, 3), (19, 30), (9, 12)]              # prompt, new tokens
    rng = onp.random.RandomState(0)
    streams = [srv.submit(rng.randint(1, 97, L), max_new_tokens=n)
               for L, n in want]
    while srv.pump():
        pass
    assert [len(s.tokens()) for s in streams] == [n for _, n in want]
    st = srv.stats()
    assert st["steps"] == len(seen) == st["counters"]["step_dispatches"]
    assert st["step_pages_table"] == sum(len(d) for d in seen) * maxp
    # the short request left its slot long before the last dispatch
    assert all(seen) and min(len(d) for d in seen) < len(want)
    # a slot admitted with a prompt of L steps at positions L, L + 1, ...
    walked, stepped = 0, {}
    for d in seen:
        for slot, L in d.items():
            j = stepped[slot] = stepped.get(slot, -1) + 1
            walked += -(-(L + j) // page)
    assert st["step_pages_walked"] == walked
    assert 0 < st["step_pages_walked"] < st["step_pages_table"]
    srv.reset_counters()
    st = srv.stats()
    assert st["step_pages_walked"] == st["step_pages_table"] == 0
    srv.close()


def test_server_counts_no_walk_where_the_step_builds_the_view():
    """An int8 pool's step gathers the view: the counters stay 0 / 0."""
    srv = _server(_net("gpt_mha"), kv_dtype="int8")
    assert not srv._progs.step_walks
    s = srv.submit(onp.arange(1, 10), max_new_tokens=4)
    while srv.pump():
        pass
    assert len(s.tokens()) == 4
    st = srv.stats()
    assert st["steps"] > 0
    assert st["step_pages_walked"] == st["step_pages_table"] == 0
    srv.close()
