"""``models.trinity`` against the plain float32 reference
(``chipbench/reference_trinity.py``) on seeded weights, at a tiny size on the
CPU (one dense layer, two periods of three sliding layers and a full one, a
window of 9), comparing LOGITS: the full forward; prefill then decode through
the paged pools and the window table's ring, well past the window; a chunk
against a cached prefix, hit against miss, for a document shorter and one
longer than the window; the ring's bound; every part of the layer planted out
of the reference in turn; the eight expert shares; the router's bias; the
page-walk kernel with a start, interpreted, against the view path.

Tolerance ``TOL``: program and reference are both float32 here and differ in
the ORDER of their sums only (a grouped product against every expert for
every token, an online softmax over pages against one over all keys, heads
contracted in groups): logits of magnitude 1-3 agree to a few 1e-6, and 2e-4
leaves two orders of room, while a part left out (the gate, the q/k norms,
the rotation on the wrong layers, the window, a post norm) moves them by
1e-2 or more (``test_reference_without_a_part_fails`` measures each).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from chipbench import reference_trinity as ref
from chipbench import weights_trinity
from mxnet_tpu import models, serve
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models import decoding, layered, trinity
from mxnet_tpu.ops import moe
from mxnet_tpu.ops import page_walk
from mxnet_tpu.ops import paged_attention as pa
from mxnet_tpu.serve import schema

TOL = 2e-4
INIT = {"qk_gain": 1.7, "expert_out_gain": 3.0}


def _build(seed=5, **over):
    net, cfg = trinity.trinity_tiny(**over)
    net.collect_params().setattr("grad_req", "null")
    net.initialize(mx.init.Zero())
    w = weights_trinity.make(trinity.parameter_shapes(cfg), seed, INIT)
    for n, p in net.collect_params().items():
        p.set_data(w[n[len(net.prefix):]])
    rcfg = {k: getattr(cfg, k) for k in cfg.__dataclass_fields__}
    return net, cfg, w, rcfg


@pytest.fixture(scope="module")
def tiny():
    return _build(held_experts=(4, 8))


def _tokens(n, seed=0, rows=None):
    shape = (n,) if rows is None else (rows, n)
    return np.random.default_rng(seed).integers(0, 96, shape).astype(
        np.int32)


@functools.partial(jax.jit, static_argnames=("frozen", "leave_out"))
def _ref_jit(w, toks, frozen, leave_out=()):
    return ref.full_logits(w, dict(frozen), toks, leave_out=leave_out)


def _ref_logits(w, rcfg, toks, pad=None, leave_out=()):
    """The reference's logits of ``toks``, jitted once a length; ``pad``
    right-pads to one length for every caller (a causal model's earlier
    rows do not see the padding)."""
    toks = np.asarray(toks, np.int32)
    n = toks.size
    if pad is not None:
        toks = np.concatenate([toks, np.zeros(pad - n, np.int32)])
    return np.asarray(_ref_jit(w, jnp.asarray(toks), ref.freeze(rcfg),
                               tuple(leave_out)))[:n]


def _is_ref_stream(w, rcfg, prompt, served):
    """Is ``served`` the reference's greedy stream after ``prompt``?  One
    teacher-forced pass: every served token is the reference's first choice
    at its position."""
    z = _ref_logits(w, rcfg, np.concatenate([prompt, served[:-1]]),
                    pad=128)
    want = z[len(prompt) - 1:].argmax(-1)
    return list(want) == list(served)


# --------------------------------------------------------------------------- #
# the forward pass and the reference
# --------------------------------------------------------------------------- #

def test_full_forward_matches_reference(tiny):
    """40 positions: four windows of 9 deep, through both kinds of layer."""
    net, cfg, w, rcfg = tiny
    toks = _tokens(40, rows=2)
    out = np.asarray(net(jnp.asarray(toks)))
    assert out.shape == (2, 40, 96)
    for b in range(2):
        np.testing.assert_allclose(out[b], _ref_logits(w, rcfg, toks[b]),
                                   atol=TOL, rtol=0)


def test_reference_tail_equals_its_full_pass(tiny):
    _, _, w, rcfg = tiny
    toks = _tokens(64, seed=3)
    full = _ref_logits(w, rcfg, toks)
    tail = np.asarray(jax.jit(lambda w, t: ref.tail_logits(
        w, rcfg, t, 60, 6))(w, jnp.asarray(toks)))
    np.testing.assert_allclose(tail, full[54:60], atol=1e-5, rtol=0)
    # the top layer is a full one: everything under it over every position
    assert ref.tail_rows(rcfg, 64, 6) == [64] * 8 + [6]
    # the benchmark's five layers (sliding, sliding, full, sliding, sliding)
    cut = dict(rcfg, num_hidden_layers=5, layer_types=(
        "sliding_attention",) * 2 + ("full_attention",)
        + ("sliding_attention",) * 2)
    assert ref.tail_rows(cut, 64, 6) == [64, 64, 22, 14, 6]
    assert ref.runs(rcfg) == [1, 3, 1, 3, 1]


@pytest.mark.parametrize("part", ref.PARTS)
def test_reference_without_a_part_fails(tiny, part):
    """Each part of the layer planted out of (or swapped in) the reference
    parts it from the program by far more than ``TOL``: the comparison sees
    the gate, the q/k norms, which layers rotate and how, the window, the
    post norms, the embedding's and the router's multipliers."""
    net, _, w, rcfg = tiny
    toks = _tokens(40, seed=2)
    out = np.asarray(net(jnp.asarray(toks[None])))[0]
    bad = _ref_logits(w, rcfg, toks, leave_out=(part,))
    assert np.abs(out - bad).max() > 100 * TOL, part
    if part == "window":
        # inside the first window both are the same model
        np.testing.assert_allclose(out[:9], bad[:9], atol=TOL, rtol=0)


def test_description_and_row_kinds(tiny):
    net, cfg, _, _ = tiny
    desc = decoding.layer_description(net)
    kinds = ["kv_window"] * 4 + ["kv"] + ["kv_window"] * 3 + ["kv"]
    assert [d["cache"] for d in desc] == kinds
    assert [d["ffn"]["kind"] for d in desc] == ["swiglu"] + ["routed"] * 8
    assert all("theta" in d["attn"] and d["attn"]["rope"] == "halves"
               for d in desc if d["cache"] == "kv_window")
    assert all("theta" not in d["attn"] and "window" not in d["attn"]
               for d in desc if d["cache"] == "kv")
    assert desc[1]["ffn"]["held"] == (4, 8) \
        and desc[1]["ffn"]["experts"] == 16
    eng = decoding.decode_engine(net, 2, 1, 32, 0.0, 0, "batched", "native",
                                 "auto")
    assert isinstance(eng, layered.LayeredEngine) and eng.stacked
    assert eng.runs == [(0, 1), (1, 3), (4, 1), (5, 3), (8, 1)]
    assert eng.window == 9 and eng.slot_kinds == []
    assert schema.pool_rows("kv_window") == ("window", ("k", "v"))
    (k, v), (wk, wv) = eng.pool_zeros(10, 6, 4)
    assert k.shape == v.shape == (2, 10, 4, 128)
    assert wk.shape == wv.shape == (7, 6, 4, 128)
    assert eng.main_page_bytes(4) == 2 * 2 * 4 * 128 * 4
    assert eng.window_page_bytes(4) == 7 * 2 * 4 * 128 * 4
    assert isinstance(models.Trinity, type) and models.trinity_tiny


def test_from_hf_reads_published_keys_and_refuses_the_rest():
    hf = dict(num_hidden_layers=8, layer_types=(
        ["sliding_attention"] * 3 + ["full_attention"]) * 2,
        num_dense_layers=2, hidden_size=64, intermediate_size=128,
        moe_intermediate_size=32, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, vocab_size=512,
        num_experts=16, num_experts_per_tok=4, num_shared_experts=1,
        route_scale=2.448, route_norm=True, score_func="sigmoid",
        sliding_window=32, rope_theta=10000, mup_enabled=True,
        max_position_embeddings=1024, tie_word_embeddings=False,
        rms_norm_eps=1e-5, n_group=1, topk_group=1)
    cfg = trinity.TrinityConfig.from_hf(hf, held_experts=(8, 4),
                                        vocab_slice=(0, 64))
    assert cfg.embedding_multiplier == 8.0 and cfg.max_length == 1024
    assert [n for _, n in trinity.layer_runs(cfg)] == [2, 1, 1, 3, 1]
    assert trinity.parameter_shapes(cfg)["r3_egu_weight"][0] \
        == (3, 4, 64, 64)
    for k, v in (("score_func", "softmax"), ("route_norm", False),
                 ("n_group", 2), ("rope_scaling", {"type": "yarn"}),
                 ("tie_word_embeddings", True)):
        with pytest.raises(ValueError, match=k):
            trinity.TrinityConfig.from_hf(dict(hf, **{k: v}))


# --------------------------------------------------------------------------- #
# through the pools
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("chunk", [8, 5], ids=["aligned", "ragged"])
def test_paged_prefill_then_decode_logits(tiny, chunk):
    """Prefill in chunks, then one token at a time to 60 positions — six
    windows deep, the ring of 6 or 7 pages wrapped twice — through scattered
    pages: the logits of every position against the reference's full pass."""
    net, cfg, w, rcfg = tiny
    page, T = 4, 60
    eng = layered.LayeredEngine(net, 1, 1, T)
    weights = net.weights()
    toks = _tokens(T, seed=5)
    want = _ref_logits(w, rcfg, toks)
    ring = eng.window_span_pages(page, chunk) + 1
    perm = np.random.default_rng(1).permutation(20)[:T // page]
    ptm = jnp.asarray(perm[None].astype(np.int32))
    pools = eng.pool_zeros(20, ring, page)
    ptw = jnp.asarray(np.random.default_rng(2).permutation(ring)[None]
                      .astype(np.int32))
    run = jax.jit(lambda tk, off, pools, last: eng.tokens_paged(
        weights, tk, off, (ptm, ptw), pools, page, last)[:3])
    pos, prefill = 0, 24
    while pos < T:
        n = min(chunk, prefill - pos) if pos < prefill else 1
        logits, kp, vp = run(jnp.asarray(toks[None, pos:pos + n]),
                             jnp.asarray([pos], jnp.int32), pools,
                             jnp.asarray([n - 1], jnp.int32))
        pools = (kp, vp)
        pos += n
        np.testing.assert_allclose(np.asarray(logits)[0], want[pos - 1],
                                   atol=TOL, rtol=0)


def _kernel_model():
    """A toy the page-walk kernel takes: K/V rows of one whole lane tile
    (2 heads of 64), float32 pages of 8."""
    return _build(seed=6, num_attention_heads=4, num_key_value_heads=2,
                  head_dim=64, hidden_size=64, sliding_window=20,
                  num_hidden_layers=5, layer_types=(
                      "sliding_attention",) * 3 + ("full_attention",
                                                   "sliding_attention"),
                  held_experts=(0, 16))


def test_step_through_the_interpreted_kernel(monkeypatch):
    """The decode step with BOTH walks in the kernel (interpret mode): the
    table row from 0 on the full layer, the ring from the window's first
    page on the sliding ones, 70 positions deep (the ring of 5 pages of 8
    wrapped), against the reference and against the view path."""
    net, cfg, w, rcfg = _kernel_model()
    page, T = 8, 72
    eng = layered.LayeredEngine(net, 2, 1, T)
    assert pa.supports(128, jnp.float32, page, 4, 64)
    weights = net.weights()
    toks = _tokens(T, seed=8, rows=2)
    want = [_ref_logits(w, rcfg, toks[b]) for b in range(2)]
    ring = eng.window_span_pages(page, 16) + 1
    assert ring == 7
    ptm = jnp.asarray(np.random.default_rng(3).permutation(18)
                      .reshape(2, 9).astype(np.int32))
    ptw = jnp.asarray(np.random.default_rng(4).permutation(2 * ring)
                      .reshape(2, ring).astype(np.int32))

    def run(tk, off, pools):
        return eng.tokens_paged(weights, tk, off, (ptm, ptw), pools, page,
                                jnp.zeros((2,), jnp.int32) + tk.shape[1] - 1
                                )[:3]

    pools = eng.pool_zeros(18, 2 * ring, page)
    logits, kp, vp = jax.jit(run)(jnp.asarray(toks[:, :16]),
                                  jnp.zeros((2,), jnp.int32), pools)
    pools = (kp, vp)
    view_step = jax.jit(run)
    monkeypatch.setenv("MXNET_FLASH_INTERPRET", "1")
    kernel_step = jax.jit(lambda tk, off, pools: run(tk, off, pools))
    for pos in range(16, T):
        args = (jnp.asarray(toks[:, pos:pos + 1]),
                jnp.full((2,), pos, jnp.int32), pools)
        monkeypatch.setenv("MXNET_FLASH_INTERPRET", "0")
        lv, _, _ = view_step(*args)
        monkeypatch.setenv("MXNET_FLASH_INTERPRET", "1")
        lk, kp, vp = kernel_step(*args)
        pools = (kp, vp)
        np.testing.assert_allclose(np.asarray(lk), np.asarray(lv),
                                   atol=TOL, rtol=0)
        for b in range(2):
            np.testing.assert_allclose(np.asarray(lk)[b], want[b][pos],
                                       atol=TOL, rtol=0)


# the walk's start against the pages: at a page's first row, inside a page,
# one row before an edge; a walk of one page, of several groups of 256 rows;
# a ring that has wrapped (start's entry behind the end's) and one that has
# not; nothing cached
_WALKS = [(0, 64), (40, 64), (255, 64), (256, 64), (300, 17), (1000, 600),
          (1023, 1024), (5000, 700), (7, 8), (16, 1)]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_kernel_walks_a_ring_from_its_start(dtype):
    """``paged_attention`` with a start, interpreted, against float64 on the
    host from the same operands, slot by slot: positions ``max(pos - window
    + 1, 0) .. pos - 1`` through the ring, then the new token."""
    dtype = jnp.dtype(dtype)
    page, width, KV, D, H, NL = 16, 80, 2, 64, 8, 2
    F, npages = KV * D, 200
    rng = np.random.RandomState(0)
    kp = jnp.asarray(rng.randn(NL, npages, page, F), dtype)
    vp = jnp.asarray(rng.randn(NL, npages, page, F), dtype)
    pos = np.array([p for p, _ in _WALKS] + [90], np.int32)
    window = np.array([w for _, w in _WALKS] + [64], np.int32)
    B = pos.size
    q = jnp.asarray(rng.randn(B, H, D), dtype)
    kn = jnp.asarray(rng.randn(B, F), dtype)
    vn = jnp.asarray(rng.randn(B, F), dtype)
    table = np.full((B, width), npages, np.int32)
    free = list(rng.permutation(npages))
    for b in range(B - 1):          # the last slot is retired: all sentinel
        first = max(pos[b] - window[b] + 1, 0) // page
        for lp in range(first, pos[b] // page + 1):
            table[b, lp % width] = free.pop()
    ends, starts = [], []
    for b in range(B):              # one window a slot: one call each
        e, s = page_walk.walk_span(jnp.asarray(table[b:b + 1]),
                                   jnp.asarray(pos[b:b + 1]), page, npages,
                                   int(window[b]))
        ends.append(int(e[0]))
        starts.append(int(s[0]))
    assert ends[-1] <= starts[-1]           # the retired slot walks nothing
    assert ends[:-1] == list(pos[:-1])
    got = pa._kernel_call(q, kn, vn, kp, vp, jnp.int32(1),
                          jnp.asarray(table), jnp.asarray(ends, jnp.int32),
                          0.125, True, jnp.asarray(starts, jnp.int32))
    f = lambda a: np.asarray(a.astype(jnp.float32), np.float64)
    k_all, v_all = f(kp[1]), f(vp[1])
    # float32: the online softmax sums in another order than the host
    # (outputs of magnitude 1 agree to 1e-6); bfloat16: ``p`` and the output
    # are rounded to 8 bits of mantissa (1e-2 on values up to 3), while a
    # column masked or unmasked wrongly moves an output by 1e-1 and more
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    for b in range(B):
        rows = [] if b == B - 1 else [
            (table[b, (t // page) % width], t % page)
            for t in range(starts[b], pos[b])]
        K = np.stack([k_all[r] for r in rows] + [f(kn)[b]])
        V = np.stack([v_all[r] for r in rows] + [f(vn)[b]])
        for h in range(H):
            lanes = slice(h // (H // KV) * D, (h // (H // KV) + 1) * D)
            s = K[:, lanes] @ f(q)[b, h] * 0.125
            p = np.exp(s - s.max())
            np.testing.assert_allclose(
                f(got)[b, h * D:(h + 1) * D], p / p.sum() @ V[:, lanes],
                atol=tol, rtol=0, err_msg=f"slot {b} head {h}")


def test_walk_without_a_start_is_the_walk_from_zero():
    """``walk_lengths``' walk and a window wider than the cache are the same
    positions: the two calls agree."""
    page, KV, D, H = 16, 2, 64, 4
    rng = np.random.RandomState(1)
    kp = jnp.asarray(rng.randn(1, 40, page, KV * D), jnp.float32)
    vp = jnp.asarray(rng.randn(1, 40, page, KV * D), jnp.float32)
    pt = jnp.asarray(rng.permutation(40)[:36].reshape(3, 12).astype(np.int32))
    pos = jnp.asarray([0, 37, 191], jnp.int32)
    q = jnp.asarray(rng.randn(3, H, D), jnp.float32)
    kn = jnp.asarray(rng.randn(3, KV * D), jnp.float32)
    vn = jnp.asarray(rng.randn(3, KV * D), jnp.float32)
    plain = pa._kernel_call(q, kn, vn, kp, vp, jnp.int32(0), pt,
                            page_walk.walk_lengths(pt, pos, page, 40), 0.125,
                            True)
    ends, starts = page_walk.walk_span(pt, pos, page, 40, 10 ** 6)
    assert list(np.asarray(starts)) == [0, 0, 0]
    ring = pa._kernel_call(q, kn, vn, kp, vp, jnp.int32(0), pt, ends, 0.125,
                           True, starts)
    np.testing.assert_allclose(np.asarray(ring), np.asarray(plain),
                               atol=1e-6, rtol=0)


# --------------------------------------------------------------------------- #
# through DecodeServer
# --------------------------------------------------------------------------- #

def _server(net, **over):
    kw = dict(max_total_len=128, pool_sizes=(4,), admit_sizes=(1, 2),
              prefill_buckets=(8, 32), page_size=4, num_pages=96,
              num_window_pages=96, spec=False, autostart=False)
    kw.update(over)
    return serve.DecodeServer(net, **kw)


def _drain(srv, streams):
    for _ in range(400):
        if all(s.done for s in streams):
            break
        srv.pump()
    return [s.tokens(timeout=0) for s in streams]


def test_served_streams_match_reference(tiny):
    """Admit waves (5 and 13 tokens, 21 in the wide bucket), chunked prefill
    (50 and 100 tokens) and 12 decode steps each: token for token the
    reference's greedy stream (float32: identical up to exact ties)."""
    net, _, w, rcfg = tiny
    srv = _server(net)
    assert not srv.sync_mode and srv._progs.layered
    assert srv._progs.window == 9 and srv._progs.slot_kinds == ()
    long_one = _tokens(100, seed=100)
    got, = _drain(srv, [srv.submit(long_one, max_new_tokens=6)])
    assert _is_ref_stream(w, rcfg, long_one, got)
    prompts = [_tokens(n, seed=n) for n in (5, 21, 50, 13)]
    got = _drain(srv, [srv.submit(p, max_new_tokens=12) for p in prompts])
    for p, g in zip(prompts, got):
        assert len(g) == 12 and _is_ref_stream(w, rcfg, p, g)
    st = srv.stats()
    c = st["counters"]
    assert c["admit_dispatches"] >= 1 and c["chunk_dispatches"] >= 2
    assert c["step_dispatches"] == st["steps"]
    # no selecting layer: the index-score walk's counters read 0 / 0 / 0
    assert (st["index_pages_walked"], st["index_copies"],
            st["index_pages_table"]) == (0, 0, 0)
    # the pools' bytes follow the declaration: both tables' pages and the
    # slots' scalar columns
    progs = srv._progs
    assert progs.window_page_bytes() == 7 * 2 * 4 * 128 * 4
    assert st["pool_bytes"] == 96 * progs.page_bytes() \
        + 96 * progs.window_page_bytes() + 4 * schema.slot_state_bytes()
    srv.close()


@pytest.mark.parametrize("doc_len,extra", [(8, 5), (8, 0), (50, 7), (50, 0),
                                           (50, 1), (52, 1)],
                         ids=["short+question", "short_same",
                              "long+question", "long_same", "long_one_more",
                              "long_page_edge"])
def test_hit_and_miss_streams_identical(tiny, doc_len, extra):
    """A cached document shorter (8) or longer (50, 52) than the window of
    9, then the document (+ a question): the prefix pages are mapped
    read-only, the window enters from the tail the index kept — the tail's
    pages land in the slot's ring —, only the rest is chunked, and the
    stream is the miss's and the reference's."""
    net, _, w, rcfg = tiny
    doc = _tokens(doc_len, seed=doc_len)
    prompt = np.concatenate([doc, _tokens(extra, seed=9)])
    miss = _server(net, prefix_cache=False)
    want, = _drain(miss, [miss.submit(prompt, max_new_tokens=14)])
    miss.close()
    assert len(want) == 14 and _is_ref_stream(w, rcfg, prompt, want)
    srv = _server(net)
    _drain(srv, [srv.submit(doc, max_new_tokens=1)])
    assert srv.stats()["prefix_tails"] >= 1
    srv.reset_counters()
    st0 = srv.stats()
    got, = _drain(srv, [srv.submit(prompt, max_new_tokens=14)])
    assert got == want
    st = srv.stats()
    assert st["counters"]["prefix_hits"] == 1
    assert st["counters"]["admit_dispatches"] == 0
    cached = st["prompt_tokens_cached"] - st0["prompt_tokens_cached"]
    # whole pages short of the whole prompt: a window page is never copied
    assert cached == min(doc_len // 4, (prompt.size - 1) // 4) * 4
    srv.close()


@pytest.mark.parametrize("prefix_cache", [False, True])
def test_ring_never_holds_more_than_its_pages(tiny, prefix_cache):
    """A slot holds window pages for its window only (9 positions = at most
    4 pages of 4 with the one being written), whatever its length; retired
    slots hold none; what the index keeps are whole tails."""
    net, _, _, _ = tiny
    srv = _server(net, prefix_cache=prefix_cache)
    streams = [srv.submit(_tokens(n, seed=n), max_new_tokens=60)
               for n in (50, 9, 30)]
    peak = 0
    for _ in range(400):
        if all(s.done for s in streams):
            break
        srv.pump()
        peak = max(peak, srv.stats()["window_pages_in_use"])
    st = srv.stats()
    assert 0 < st["window_pages_slot_max"] <= st["window_pages_slot_bound"]
    assert st["window_pages_slot_bound"] == 4
    assert srv._progs.ring == layered.LayeredEngine.window_span_pages(
        srv._progs.eng, 4, 32) + 1 == 12
    # three live slots, a chunk of 32 in flight and three tails at most
    assert peak <= 3 * 4 + 8 + 3 * 3
    held = {p for t in (srv._prefix._tails.values() if prefix_cache else ())
            for p in t["tail"].values()}
    assert st["window_pages_in_use"] == len(held)
    assert all(not d for d in srv._slot_wpages)
    srv.close()
    assert srv._wpages.in_use == 0 and srv._pages.in_use == 0


def test_step_counters_reach_stats(tiny):
    """The stacked-runs body hands the routed layers' expert ids on: the
    ``moe_*`` keys the layer loop's model reports."""
    net, _, _, _ = tiny
    srv = _server(net)
    _drain(srv, [srv.submit(_tokens(20, seed=2), max_new_tokens=16)])
    st = srv.stats()
    assert 0.0 < st["moe_experts_touched_share"] <= 1.0
    assert st["moe_load_max_over_mean"] >= 1.0
    # 4 of 16 experts a token, 8 of them held: half a token's choices over
    # 8 held experts, one live slot
    assert st["moe_tokens_per_expert_step"] == pytest.approx(0.25, abs=0.15)
    assert "selected_keys_per_query" not in st
    assert st["prompt_tokens"] == 20 and st["prefix_tails"] >= 1
    assert st["window_pages_total"] == 96
    srv.close()


def test_speculation_and_second_pool_size_are_refused(tiny):
    net, _, _, _ = tiny
    with pytest.raises(MXNetError, match="draft-and-verify"):
        _server(net, spec=True)
    with pytest.raises(MXNetError, match="one pool size"):
        _server(net, pool_sizes=(2, 4))
    srv = _server(net)
    assert srv.spec_enabled is False
    with pytest.raises(MXNetError, match="draft-and-verify"):
        srv._progs.verify_fn(2)
    srv.close()


# --------------------------------------------------------------------------- #
# the routed layer
# --------------------------------------------------------------------------- #

def test_expert_shares_add_up_to_the_uncut_layer():
    """Eight chips' shares of the routed sum (2 of 16 experts each), with
    the shared expert counted once, are the reference's uncut layer."""
    net, cfg, w, rcfg = _build(held_experts=(0, 16))
    lw = ref.layer_weights(w, 2, rcfg)
    x = jnp.asarray(np.random.default_rng(3).normal(size=(24, 32)),
                    jnp.float32)
    want = np.asarray(ref.ffn(rcfg, lw, False, x, ref.mm_f32))
    h = layered._rms(x, lw["norm2_gamma"], cfg.rms_norm_eps)
    idx, wts = moe.route(h, lw["router_weight"], lw["router_bias"], 4,
                         cfg.route_scale)
    shared = moe.swiglu(h, lw["sgu_weight"], lw["sdown_weight"])
    total, loads = shared, []
    for lo in range(0, 16, 2):
        y, load = moe.routed_experts(h, idx, wts, lw["egu_weight"][lo:lo + 2],
                                     lw["edown_weight"][lo:lo + 2], lo)
        total = total + y
        loads.append(np.asarray(load))
        # and one share alone is what the reference gives for that share
        part = np.asarray(ref.ffn(dict(rcfg, held_experts=(lo, 2)), dict(
            lw, egu_weight=lw["egu_weight"][lo:lo + 2],
            edown_weight=lw["edown_weight"][lo:lo + 2]), False, x,
            ref.mm_f32))
        np.testing.assert_allclose(np.asarray(y + shared), part, atol=TOL,
                                   rtol=0)
    np.testing.assert_allclose(np.asarray(total), want, atol=TOL, rtol=0)
    assert np.concatenate(loads).sum() == 24 * 4      # no token dropped


def test_router_bias_chooses_and_does_not_weigh():
    """The reference's router against ``ops.moe.route``: a bias of 10 on one
    expert puts it among every token's four, and the weights are the chosen
    sigmoids normalised, times ``route_scale``, whatever the bias."""
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.normal(size=(6, 32)), jnp.float32)
    wr = jnp.asarray(rng.normal(size=(32, 16)) / 32 ** 0.5, jnp.float32)
    cfg = {"num_experts_per_tok": 4, "route_scale": 2.448}
    bias = jnp.zeros(16).at[3].set(10.0)
    plain = np.asarray(ref.route(cfg, {"router_weight": wr,
                                       "router_bias": jnp.zeros(16)}, h))
    dense = np.asarray(ref.route(cfg, {"router_weight": wr,
                                       "router_bias": bias}, h))
    assert (dense[:, 3] > 0).all() and not (plain[:, 3] > 0).all()
    assert ((dense > 0).sum(1) == 4).all()
    np.testing.assert_allclose(dense.sum(1), 2.448, atol=1e-5)
    s = np.asarray(jax.nn.sigmoid(h @ wr))
    chosen = np.where(dense > 0, s, 0.0)
    np.testing.assert_allclose(
        dense, chosen / chosen.sum(1, keepdims=True) * 2.448, atol=1e-6)
    idx, wts = moe.route(h, wr, bias, 4, 2.448)
    np.testing.assert_allclose(
        np.take_along_axis(dense, np.asarray(idx), axis=1), np.asarray(wts),
        atol=1e-6)
