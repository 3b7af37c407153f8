"""``models.dots3`` against the plain float32 reference
(``chipbench/reference_dots3.py``) on seeded weights, at a tiny size on the
CPU, comparing LOGITS: the full forward; prefill then decode through the
paged pools, past the window and past a tiny ``index_topk`` (and equal to
dense attention below it); a chunk against a cached prefix; hit against miss;
the window pool's bound; the eight expert shares; the router's bias.

Tolerance ``TOL``: program and reference are both float32 here and differ in
the ORDER of their sums only (absorbed against expanded latent attention, a
gather of selected rows against a masked dense softmax, a grouped product
against every expert for every token): logits of magnitude 3-4 agree to a few
1e-6, and 2e-4 leaves two orders of room while a dropped term (a missing
gate, rope on the wrong half, one expert left out) moves them by 1e-2 or
more.
"""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from chipbench import reference_dots3 as ref
from chipbench import weights_dots3
from mxnet_tpu import models, serve
from mxnet_tpu.models import decoding, dots3, layered
from mxnet_tpu.ops import moe
from mxnet_tpu.serve import schema

TOL = 2e-4


def _build(seed=7, **over):
    net, cfg = dots3.dots3_tiny(**over)
    net.collect_params().setattr("grad_req", "null")
    net.initialize(mx.init.Zero())
    w = weights_dots3.make(dots3.parameter_shapes(cfg), seed,
                           {"score_gain": 0.7, "expert_out_gain": 3.0})
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


@functools.partial(jax.jit, static_argnames=("frozen",))
def _ref_jit(w, toks, frozen):
    return ref.full_logits(w, dict(frozen), toks)


def _ref_logits(w, rcfg, toks, pad=None):
    """The reference's logits of ``toks``, jitted once a length; ``pad``
    right-pads to one length for every caller (a causal model's earlier
    rows do not see the padding)."""
    toks = np.asarray(toks, np.int32)
    n = toks.size
    if pad is not None:
        toks = np.concatenate([toks, np.zeros(pad - n, np.int32)])
    return np.asarray(_ref_jit(w, jnp.asarray(toks), ref.freeze(rcfg)))[:n]


def _is_ref_stream(w, rcfg, prompt, served):
    """Is ``served`` the reference's greedy stream after ``prompt``?  One
    teacher-forced pass: every served token is the reference's first choice
    at its position (in float32, up to exact ties, the same claim as
    decoding the reference token by token)."""
    z = _ref_logits(w, rcfg, np.concatenate([prompt, served[:-1]]),
                    pad=128)
    want = z[len(prompt) - 1:].argmax(-1)
    return list(want) == list(served)


# --------------------------------------------------------------------------- #
# the forward pass
# --------------------------------------------------------------------------- #

@pytest.fixture(params=["gather_form", "dense_form"])
def form(request, monkeypatch):
    """The selecting attention's two forms: rows gathered by position
    (decode steps, short chunks) and every cached row scored under the
    selection's mask (chunks of ``dense_chunk`` queries and more; 256 as
    served, 8 here so that a 32-token chunk takes it)."""
    if request.param == "dense_form":
        monkeypatch.setattr(layered.LayeredEngine, "dense_chunk", 8)
    return request.param


@pytest.mark.parametrize("index_topk", [8, 4096],
                         ids=["sparse_top8", "dense_below_topk"])
def test_full_forward_matches_reference(index_topk, form):
    """40 positions: past the window of 9 and, at top-8, past the indexer's
    budget; at top-4096 every position is selected and the layer IS dense
    latent attention."""
    net, cfg, w, rcfg = _build(index_topk=index_topk)
    toks = _tokens(40, rows=2)
    out = np.asarray(net(jnp.asarray(toks)))
    assert out.shape == (2, 40, 96)
    for b in range(2):
        np.testing.assert_allclose(out[b], _ref_logits(w, rcfg, toks[b]),
                                   atol=TOL, rtol=0)


def test_sparse_selection_changes_the_answer(tiny):
    """The comparison can tell top-8 from dense: the two references part by
    far more than ``TOL`` once there are more than 8 positions."""
    _, _, w, rcfg = tiny
    toks = _tokens(40)
    a = _ref_logits(w, rcfg, toks)
    b = _ref_logits(w, dict(rcfg, index_topk=4096), toks)
    np.testing.assert_allclose(a[:8], b[:8], atol=TOL, rtol=0)
    assert np.abs(a[16:] - b[16:]).max() > 100 * TOL


def test_reference_tail_equals_its_full_pass(tiny):
    _, _, w, rcfg = tiny
    toks = _tokens(48, seed=3)
    full = _ref_logits(w, rcfg, toks)
    tail = np.asarray(jax.jit(lambda w, t: ref.tail_logits(
        w, rcfg, t, 44, 6))(w, jnp.asarray(toks)))
    np.testing.assert_allclose(tail, full[38:44], atol=1e-5, rtol=0)
    assert ref.tail_rows(rcfg, 48, 6) == [48, 30, 22, 14, 6]


@pytest.fixture(params=[(4, False), (8, False), (8, True)],
                ids=["page4_view", "page8_view", "page8_kernel"])
def lowering(request, monkeypatch):
    """The page size and what scores the decode step's index keys: pages of
    4 float32 rows are no whole sublane tile, so the step gathers the key
    view; pages of 8 the index-score kernel takes — interpreted
    (``page8_kernel``), or as a CPU lowers the step, the view behind
    ``platform_dependent`` (``page8_view``).  Other test modules switch the
    interpreter on process-wide as they are imported."""
    page, interpreted = request.param
    if interpreted:
        monkeypatch.setenv("MXNET_FLASH_INTERPRET", "1")
    else:
        monkeypatch.delenv("MXNET_FLASH_INTERPRET", raising=False)
    return page


@pytest.mark.parametrize("chunk", [8, 5], ids=["aligned", "ragged"])
def test_paged_prefill_then_decode_logits(tiny, chunk, lowering):
    """Prefill in chunks, then one token at a time to 48 positions — past
    the window and, at top-8, past the indexer's budget — through scattered
    pages and a ring of window pages one more than the window needs: the
    logits of every position against the reference's full pass."""
    net, cfg, w, rcfg = tiny
    page, T = lowering, 48
    eng = layered.LayeredEngine(net, 1, 1, T)
    weights = net.weights()
    toks = _tokens(T, seed=5)
    want = _ref_logits(w, rcfg, toks)
    ring = eng.window_span_pages(page, chunk) + 1
    # 256 pages: a pool of at least one of the kernel's compute blocks
    perm = np.random.default_rng(1).permutation(256)[:T // page]
    ptm = jnp.asarray(perm[None].astype(np.int32))
    pools = eng.pool_zeros(256, ring, page)
    ptw = jnp.asarray(np.arange(ring, dtype=np.int32)[None])
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


# --------------------------------------------------------------------------- #
# through DecodeServer
# --------------------------------------------------------------------------- #

def _server(net, **over):
    kw = dict(max_total_len=128, pool_sizes=(4,), admit_sizes=(1, 2),
              prefill_buckets=(8, 32), page_size=4, num_pages=96,
              num_window_pages=64, spec=False, autostart=False)
    kw.update(over)
    return serve.DecodeServer(net, **kw)


def _drain(srv, streams):
    for _ in range(400):
        if all(s.done for s in streams):
            break
        srv.pump()
    return [s.tokens(timeout=0) for s in streams]


def test_served_streams_match_reference(tiny, form):
    """Admit waves (5 and 13 tokens, 21 in the wide bucket), chunked prefill
    (50 and 100 tokens: the long chunks' reach grows by quarters of the
    table) and 12 decode steps each: token for token the reference's greedy
    stream (float32: identical up to exact ties)."""
    net, _, w, rcfg = tiny
    srv = _server(net)
    long_one = _tokens(100, seed=100)
    got, = _drain(srv, [srv.submit(long_one, max_new_tokens=6)])
    assert _is_ref_stream(w, rcfg, long_one, got)
    if form == "dense_form":
        # 32 pages of table: the 32-token chunks were compiled for a reach
        # of 8, 16 and 24 pages as the prompt streamed in
        assert {k for k in srv._progs._chunks if k[0] == 32} == {
            (32, 8), (32, 16), (32, 24)}
    else:
        assert set(srv._progs._chunks) <= {(8, None), (32, None)}
    assert not srv.sync_mode and srv._progs.layered
    prompts = [_tokens(n, seed=n) for n in (5, 21, 50, 13)]
    got = _drain(srv, [srv.submit(p, max_new_tokens=12) for p in prompts])
    for p, g in zip(prompts, got):
        assert len(g) == 12 and _is_ref_stream(w, rcfg, p, g)
    c = srv.stats()["counters"]
    assert c["admit_dispatches"] >= 1 and c["chunk_dispatches"] >= 2
    assert c["step_dispatches"] == srv.stats()["steps"]
    srv.close()


@pytest.mark.parametrize("extra", [0, 7, 1], ids=["same", "question",
                                                  "one_more"])
def test_hit_and_miss_streams_identical(tiny, extra, lowering):
    """A cached 50-token document, then the document (+ a question): the
    prefix pages are mapped read-only, the window enters from the tail the
    index kept, only the rest is chunked — and the stream is the miss's."""
    net, _, w, rcfg = tiny
    page = lowering
    doc = _tokens(50, seed=50)
    prompt = np.concatenate([doc, _tokens(extra, seed=9)])
    server = functools.partial(_server, page_size=page,
                               num_pages=2048 // page,
                               num_window_pages=256 // page)
    miss = server(net, prefix_cache=False)
    want, = _drain(miss, [miss.submit(prompt, max_new_tokens=10)])
    miss.close()
    assert len(want) == 10 and _is_ref_stream(w, rcfg, prompt, want)
    srv = server(net)
    _drain(srv, [srv.submit(doc, max_new_tokens=1)])
    srv.reset_counters()
    st0 = srv.stats()
    got, = _drain(srv, [srv.submit(prompt, max_new_tokens=10)])
    assert got == want
    st = srv.stats()
    assert st["counters"]["prefix_hits"] == 1
    assert st["counters"]["admit_dispatches"] == 0
    cached = st["prompt_tokens_cached"] - st0["prompt_tokens_cached"]
    # whole pages short of the whole prompt: a window page is never copied
    assert cached == min(50 // page, (prompt.size - 1) // page) * page
    walked = st["index_pages_walked"], st["index_pages_table"]
    if os.environ.get("MXNET_FLASH_INTERPRET") == "1":
        # the chunk gives the first token, nine steps the rest; a step of
        # one slot over two selecting layers walks the pages that hold
        # positions 0 .. pos, of a table of 128 / page entries
        n = prompt.size
        assert walked == (2 * sum(-(-(n + j + 1) // page)
                                  for j in range(9)),
                          2 * 9 * (128 // page))
        assert 0 < st["index_copies"] <= walked[0]
    else:
        assert walked == (0, 0) and st["index_copies"] == 0
    srv.close()


@pytest.mark.parametrize("pos", [[5, 40, 100, 127], [0, 8, 64, 77]],
                         ids=["ragged", "edges"])
def test_both_lowerings_select_the_same_set(tiny, pos, monkeypatch):
    """``_select`` of one query a slot over the same pool, scores from the
    interpreted kernel and from the gathered view: the same ``topk`` set
    wherever no two scores around the cut tie within rounding (random
    float32 keys: they do not), the same ``seen``, and only the kernel leg
    counts a walk."""
    net, _, _, _ = tiny
    eng = layered.LayeredEngine(net, 4, 1, 128)
    a = eng.desc[0]["attn"]
    page, npages, B = 8, 256, 4
    rng = np.random.default_rng(4)
    ikp = jnp.asarray(rng.normal(size=(2, npages, page, 128)), jnp.float32)
    ikp = ikp.at[..., a["index_dim"]:].set(0.0)
    iq = jnp.asarray(rng.normal(size=(B, 1, a["index_heads"],
                                      a["index_dim"])), jnp.float32)
    iw = jnp.asarray(rng.random(size=(B, 1, a["index_heads"])), jnp.float32)
    table = np.full((B, 16), npages, np.int32)
    ids = list(rng.permutation(npages))
    posj = jnp.asarray(pos, jnp.int32)[:, None]
    for b in range(B - 1):                  # the last slot is retired
        for j in range(pos[b] // page + 1):
            table[b, j] = ids.pop()
    table = jnp.asarray(table)
    got = {}
    for leg in ("view", "kernel"):
        if leg == "kernel":
            monkeypatch.setenv("MXNET_FLASH_INTERPRET", "1")
        else:
            monkeypatch.delenv("MXNET_FLASH_INTERPRET", raising=False)
        got[leg] = jax.jit(lambda: eng._select(a, iq, iw, ikp, 1, table,
                                               posj, page))()
    (cv, sv, wv), (ck, sk, wk) = got["view"], got["kernel"]
    live = np.arange(B) < B - 1
    np.testing.assert_array_equal(np.asarray(sv), np.asarray(sk))
    np.testing.assert_array_equal(np.asarray(cv & sv)[live],
                                  np.asarray(ck & sk)[live])
    picked = np.asarray(jnp.sum(ck & sk, axis=-1))[:, 0]
    assert picked[live].tolist() == [min(p + 1, a["topk"])
                                     for p in pos[:B - 1]]
    assert np.asarray(wv).tolist() == [[0, 0, 0]] * B
    assert np.asarray(wk)[:, 0].tolist() == [p // page + 1
                                             for p in pos[:B - 1]] + [0]
    assert (np.asarray(wk)[:, 2] == 16).all()


def test_match_is_cut_back_where_no_tail_was_kept(tiny):
    """The index's chain for a prompt is only enterable where a tail covers
    the window in front of it: with the tails evicted the same prompt
    misses (a chunked prefill from 0), and still serves the same stream."""
    net, _, w, rcfg = tiny
    doc = _tokens(50, seed=51)
    srv = _server(net)
    first, = _drain(srv, [srv.submit(doc, max_new_tokens=6)])
    assert srv.stats()["prefix_tails"] >= 1
    srv._prefix.evict_tails(10 ** 6)
    assert srv.stats()["prefix_tails"] == 0
    assert srv._prefix.match(doc, limit=12) == (0, [], {})
    srv.reset_counters()
    again, = _drain(srv, [srv.submit(doc, max_new_tokens=6)])
    assert again == first and _is_ref_stream(w, rcfg, doc, first)
    assert srv.stats()["counters"]["prefix_hits"] == 0
    srv.close()


@pytest.mark.parametrize("prefix_cache", [False, True])
def test_window_pages_released_and_bounded(tiny, prefix_cache):
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
    # three live slots, a chunk of 32 in flight and three tails at most
    assert peak <= 3 * 4 + 8 + 3 * 3
    held = {p for t in (srv._prefix._tails.values() if prefix_cache else ())
            for p in t["tail"].values()}
    assert st["window_pages_in_use"] == len(held)
    assert all(not d for d in srv._slot_wpages)
    srv.close()
    assert srv._wpages.in_use == 0 and srv._pages.in_use == 0


def test_window_pool_too_small_fails_loudly(tiny):
    net, _, _, _ = tiny
    srv = _server(net, num_window_pages=3, prefix_cache=False)
    s = srv.submit(_tokens(50, seed=1), max_new_tokens=4)
    with pytest.raises(mx.base.MXNetError, match="window pool exhausted"):
        for _ in range(50):
            srv.pump()
    assert s.done or True
    srv.close(drain=False)


def test_step_counters_reach_stats(tiny):
    net, _, _, _ = tiny
    srv = _server(net)
    _drain(srv, [srv.submit(_tokens(20, seed=2), max_new_tokens=16)])
    st = srv.stats()
    assert st["selected_keys_per_query"] == pytest.approx(8.0, abs=0.3)
    assert 0.0 < st["moe_experts_touched_share"] <= 1.0
    assert st["moe_load_max_over_mean"] >= 1.0
    # 4 of 16 experts a token, 8 of them held: half a token's choices
    assert 0.05 < st["moe_tokens_per_expert_step"] < 1.0
    srv.close()


def test_speculation_is_refused_loudly(tiny):
    net, _, _, _ = tiny
    with pytest.raises(mx.base.MXNetError, match="draft-and-verify"):
        _server(net, spec=True)
    srv = _server(net)
    assert srv.spec_enabled is False
    with pytest.raises(mx.base.MXNetError, match="draft-and-verify"):
        srv._progs.verify_fn(2)
    srv.close()


# --------------------------------------------------------------------------- #
# the description, the row kinds, the routed layer
# --------------------------------------------------------------------------- #

def test_description_drives_the_engine(tiny):
    net, cfg, _, _ = tiny
    desc = decoding.layer_description(net)
    assert [d["cache"] for d in desc] == ["latent_index"] * 2 \
        + ["latent_window"] * 3
    assert [d["ffn"]["kind"] for d in desc] == ["swiglu"] + ["routed"] * 4
    assert desc[1]["ffn"]["held"] == (4, 8) \
        and desc[1]["ffn"]["experts"] == 16
    eng = decoding.decode_engine(net, 2, 1, 32, 0.0, 0, "batched", "native",
                                 "auto")
    assert isinstance(eng, layered.LayeredEngine)
    assert schema.pool_rows("latent_index") == ("main",
                                                ("latent", "index_key"))
    assert schema.pool_rows("latent_window") == ("window", ("latent",))


def test_layered_engine_hands_over_three_operands(tiny):
    """Same face as ``_DecodeEngine.take_operands``: (parameter values, q8,
    stacked weights), the last two empty here, and the engine keeps none."""
    eng = layered.LayeredEngine(tiny[0], 2, 1, 32)
    param_vals, q8, sw = eng.take_operands()
    assert len(param_vals) == len(eng.params) > 0
    assert q8 is None and sw is None and eng.param_vals is None


@pytest.mark.parametrize("family", ["gpt", "llama"])
def test_uniform_families_stay_on_the_stacked_scan(family):
    if family == "gpt":
        net = models.GPT(models.GPTConfig(vocab_size=64, num_layers=2,
                                          units=32, num_heads=2,
                                          hidden_size=64, max_length=32))
    else:
        net, _ = models.llama_tiny()
    net.initialize()
    desc = decoding.layer_description(net)
    assert {d["cache"] for d in desc} == {"kv"}
    assert schema.pool_rows("kv") == ("main", ("k", "v"))
    eng = decoding.decode_engine(net, 2, 1, 32, 0.0, 0, "batched", "native",
                                 "auto")
    assert type(eng) is decoding._DecodeEngine and eng.mode == "stacked"


@pytest.mark.parametrize("width,lanes", [(576, 640), (1088, 1152),
                                         (128, 128), (64, 128)])
def test_rows_are_whole_lane_tiles(width, lanes):
    """The published rows (512 + 64, 1024 + 64) are not multiples of the
    128-lane tile: they are stored padded (PERF.md, PR 27)."""
    assert schema.row_lanes(width) == lanes and lanes % schema.LANE_TILE == 0


def test_expert_shares_add_up_to_the_uncut_layer():
    """Eight chips' shares of the routed sum (2 of 16 experts each), with
    the shared expert counted once, are the reference's uncut layer."""
    net, cfg, w, rcfg = _build(held_experts=(0, 16))
    lw = ref.layer_weights(w, 1)
    x = jnp.asarray(np.random.default_rng(3).normal(size=(24, 32)),
                    jnp.float32)
    want = np.asarray(ref.ffn(rcfg, lw, False, x, ref.mm_f32))
    h = layered._rms(x, lw["norm2_gamma"], cfg.rms_norm_eps)
    idx, wts = moe.route(h, lw["router_weight"], lw["router_bias"], 4)
    total, loads = moe.swiglu(h, lw["sgu_weight"], lw["sdown_weight"]), []
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
        np.testing.assert_allclose(
            np.asarray(y + moe.swiglu(h, lw["sgu_weight"],
                                      lw["sdown_weight"])),
            part, atol=TOL, rtol=0)
    np.testing.assert_allclose(np.asarray(total), want, atol=TOL, rtol=0)
    assert np.concatenate(loads).sum() == 24 * 4      # no token dropped


def test_router_bias_chooses_and_does_not_weigh():
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.normal(size=(6, 32)), jnp.float32)
    wr = jnp.asarray(rng.normal(size=(32, 16)) / 32 ** 0.5, jnp.float32)
    plain_idx, _ = moe.route(h, wr, jnp.zeros(16), 4)
    bias = jnp.zeros(16).at[3].set(10.0)
    idx, wts = moe.route(h, wr, bias, 4)
    idx, wts = np.asarray(idx), np.asarray(wts)
    assert (idx == 3).any(axis=1).all()         # the bias put expert 3 in
    assert not (np.asarray(plain_idx) == 3).any(axis=1).all()
    s = np.asarray(jax.nn.sigmoid(h @ wr))
    chosen = np.take_along_axis(s, idx, axis=1)
    np.testing.assert_allclose(wts, chosen / chosen.sum(1, keepdims=True),
                               atol=1e-6)
    np.testing.assert_allclose(wts.sum(1), 1.0, atol=1e-6)


def _positions(chosen, k):
    """``mask_positions`` in NumPy: a row's set positions in order; where
    it has fewer than ``k`` the rest read the last block's first position
    (what the gather form of PR 29 returned there, pinned on its code
    before it went: PERF.md, PR 37)."""
    N, T = chosen.shape
    out = np.full((N, k), (-(-T // 128) - 1) * 128, np.int32)
    for n in range(N):
        at = np.flatnonzero(chosen[n])[:k]
        out[n, :at.size] = at
    return out


def _chosen(case, N, T, k):
    """A ``(N, T)`` mask for a ``mask_positions`` case, ``k`` set a row
    unless the case says otherwise."""
    rng = np.random.default_rng(N * 7919 + T)
    chosen = np.zeros((N, T), bool)
    for n in range(N):
        if case == "one_block":         # all of them inside one block
            lo = 128 * int(rng.integers(0, T // 128))
            at = lo + rng.choice(min(128, T - lo), k, replace=False)
        elif case == "last_block":      # only in the last, padded block
            lo = (T - 1) // 128 * 128
            at = lo + rng.choice(T - lo, k, replace=False)
        elif case == "short":           # fewer than k: 0, 1, k - 1, ...
            at = rng.choice(T, (0, 1, k - 1, k // 2)[n % 4], replace=False)
        else:
            at = rng.choice(T, k, replace=False)
        chosen[n, at] = True
    return chosen


@pytest.mark.parametrize("case,N,T,k", [
    ("spread", 1, 1000, 100), ("spread", 32, 1000, 100),
    ("spread", 128, 700, 64), ("spread", 3, 33152, 2048),
    ("all", 2, 300, 300), ("all", 4, 128, 128), ("one", 2, 257, 1),
    ("one", 32, 100, 1), ("one_block", 5, 1000, 100),
    ("one_block", 3, 640, 128), ("last_block", 4, 1000, 60),
    ("last_block", 2, 257, 1), ("short", 8, 1000, 100),
    ("short", 4, 128, 40), ("short", 4, 33152, 2048)],
    ids=lambda v: str(v))
def test_mask_positions_are_the_set_positions_in_order(case, N, T, k):
    """The gather-free ``mask_positions`` against ``np.flatnonzero``: the
    same int32 positions, ascending, for one row, a step's 32 and a
    chunk's 128, ``T`` off the blocks' grid, every position chosen, one
    chosen, all inside one block, all inside the padded last block, and
    rows with fewer than ``k`` set."""
    chosen = _chosen(case, N, T, k)
    got = jax.jit(layered.mask_positions, static_argnums=1)(
        jnp.asarray(chosen), k)
    assert got.dtype == jnp.int32 and got.shape == (N, k)
    np.testing.assert_array_equal(np.asarray(got), _positions(chosen, k))


@pytest.mark.parametrize("B,C,T,k", [(4, 1, 300, 64), (1, 16, 200, 200),
                                     (3, 2, 1000, 128)])
def test_positions_past_a_query_are_not_ok(B, C, T, k):
    """``ok`` as ``tokens_paged`` takes it — a selected position is one the
    query has seen iff it is at or before the query's own — equals ``seen``
    read at the positions, also where a short query's fill points past
    it."""
    rng = np.random.default_rng(B)
    pos = rng.integers(0, T, size=(B, C)).astype(np.int32)
    pos[0, 0], pos[-1, -1] = 0, T - 1
    seen = np.arange(T)[None, None] <= pos[..., None]
    score = rng.normal(size=(B * C, T)).astype(np.float32)
    sel = np.asarray(jax.jit(layered.top_positions, static_argnums=2)(
        jnp.asarray(score), jnp.asarray(seen.reshape(B * C, T)),
        k)).reshape(B, C, k)
    np.testing.assert_array_equal(
        sel <= pos[..., None], np.take_along_axis(seen, sel, axis=2))
    assert (~(sel <= pos[..., None])).any()


@pytest.mark.parametrize("N,T,k,ties", [
    (4, 300, 64, False), (3, 1000, 128, True), (5, 130, 130, False),
    (2, 33152, 2048, False), (3, 500, 100, True)],
    ids=["plain", "ties", "all", "cell_size", "ties_few_valid"])
def test_top_positions_is_an_exact_top_k(N, T, k, ties):
    """The sort-free selection is the stable top-k's SET, in ascending
    position, whatever the ties and however few positions are valid."""
    rng = np.random.default_rng(0)
    s = rng.normal(size=(N, T)).astype(np.float32)
    if ties:
        s = np.round(s * 3) / 3
    nvalid = rng.integers(1, T + 1, size=N)
    nvalid[0], nvalid[-1] = T, min(T, k // 2 + 1)
    valid = np.arange(T)[None] < nvalid[:, None]
    got = np.asarray(jax.jit(layered.top_positions, static_argnums=2)(
        jnp.asarray(s), jnp.asarray(valid), k))
    assert got.shape == (N, k)
    for n in range(N):
        assert (np.diff(got[n]) > 0).all()
        v = int(nvalid[n])
        want = np.lexsort((np.arange(v), -s[n, :v]))[:min(k, v)]
        assert set(got[n][got[n] < v].tolist()) == set(want.tolist())


def _table(B, n, npages, seed):
    """Random page ids below ``npages``, every fifth entry its largest
    (``npages - 1``, where the program clamps a sentinel)."""
    t = np.random.default_rng(seed).integers(0, npages, (B, n))
    t[:, ::5] = npages - 1
    return t.astype(np.int32)


@pytest.mark.parametrize("case,B,C,n,page,k,npages", [
    ("spread", 1, 1, 65, 16, 100, 65536),       # T 1,040: off the grid
    ("spread", 32, 1, 2072, 16, 2048, 65536),   # the step's 32 rows
    ("spread", 1, 128, 44, 16, 64, 65536),      # a chunk's 128 rows
    ("spread", 3, 2, 250, 4, 100, 300),
    ("spread", 2, 3, 21, 48, 100, 70000),       # pages across blocks
    ("spread", 2, 1, 4, 256, 64, 9),            # blocks inside a page
    ("all", 2, 1, 75, 4, 300, 65536),           # k = T
    ("one", 2, 2, 33, 8, 1, 65536),
    ("one_block", 5, 1, 125, 8, 100, 65536),
    ("last_block", 2, 2, 125, 8, 60, 65536),    # the padded last block
    ("short", 2, 4, 125, 8, 100, 65536)],       # fewer than k set
    ids=lambda v: str(v))
def test_block_pages_are_the_tables_ids(case, B, C, n, page, k, npages):
    """``block_pages`` of ``block_positions``' one-hot against
    ``np.take_along_axis`` over the table: the same int32 ids for one
    query, a step's 32 slots and a chunk's 128 queries of one slot; ``T``
    off the blocks' grid, every position chosen, one chosen, all in one
    block, all in the padded last block, rows with fewer than ``k`` set
    (their rest read the last block's first position); ids of one, two and
    three bytes, the largest of them ``npages - 1``."""
    T = n * page
    chosen = _chosen(case, B * C, T, k)
    table = _table(B, n, npages, seed=n)
    table[0, _positions(chosen, k)[0, 0] // page] = npages - 1

    def run(chosen, table):
        sel, blocks = layered.block_positions(chosen, k)
        sel = sel.reshape(B, C, k)
        return sel, layered.block_pages(blocks.reshape(B, C, k, -1), sel,
                                        table, page, npages)

    sel, got = jax.jit(run)(jnp.asarray(chosen), jnp.asarray(table))
    assert got.dtype == jnp.int32 and got.shape == (B, C, k)
    np.testing.assert_array_equal(np.asarray(sel).reshape(B * C, k),
                                  _positions(chosen, k))
    want = np.take_along_axis(table[:, None, :], np.asarray(sel) // page,
                              axis=2)
    np.testing.assert_array_equal(np.asarray(got), want)
    assert (want == npages - 1).any()


def _take_along_pages(onehot, sel, table, page, npages):
    """The page ids as a gather over the table finds them."""
    return jnp.take_along_axis(table[:, None, :], sel // page, axis=2)


@pytest.mark.parametrize("C", [1, 5], ids=["step", "chunk"])
def test_gather_form_is_bit_identical_to_take_along_axis(tiny, C,
                                                         monkeypatch):
    """The selecting layers' gather form through ``block_pages`` against
    the same code with the page ids of a ``take_along_axis`` over the table
    (``_take_along_pages``): two slots, 40 cached positions each (past the
    indexer's top-8) through scattered pages, then a step or a 5-token
    chunk — the same logits and pools, bit for bit."""
    net, _, _, _ = tiny
    page, B, P = 4, 2, 40
    eng = layered.LayeredEngine(net, B, 1, 64)
    assert C < eng.dense_chunk
    weights = net.weights()
    toks = _tokens(P + C, seed=9, rows=B)
    ring = eng.window_span_pages(page, 8) + 1
    perm = np.random.default_rng(2).permutation(256)[:B * 16]
    ptm = jnp.asarray(perm.reshape(B, 16).astype(np.int32))
    ptw = jnp.asarray(np.arange(B * ring, dtype=np.int32).reshape(B, ring))
    pools = eng.pool_zeros(256, B * ring, page)

    def program():      # traced anew: it reads ``block_pages`` then
        return jax.jit(lambda tk, off, pools: eng.tokens_paged(
            weights, tk, off, (ptm, ptw), pools, page,
            jnp.full((B,), tk.shape[1] - 1, jnp.int32))[:3])

    fill = program()
    for at in range(0, P, 8):
        _, kp, vp = fill(jnp.asarray(toks[:, at:at + 8]),
                         jnp.full((B,), at, jnp.int32), pools)
        pools = (kp, vp)
    last = (jnp.asarray(toks[:, P:]), jnp.full((B,), P, jnp.int32), pools)
    got = program()(*last)
    monkeypatch.setattr(layered, "block_pages", _take_along_pages)
    want = program()(*last)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
