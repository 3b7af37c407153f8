"""``models.pangu_moe`` against the plain float32 reference
(``chipbench/reference_pangu.py``) on seeded weights, at a tiny size on the
CPU (one dense layer, then routed ones, every attention over every
position), comparing LOGITS: the full forward; prefill then decode through
the bodies the pool executables run (``chunk_tokens``, ``pool_token_paged``)
over scattered pages, through the gathered form and through the page-walk
kernel interpreted; a prefix hit and one chunk against a cold prompt; each
post norm planted out of the reference; four expert shares against the uncut
layer; the kernel against the gathered form at ragged lengths; and the
served streams through ``DecodeServer``.

Tolerance ``TOL``: program and reference are both float32 here and differ in
the ORDER of their sums only (absorbed against expanded latent attention, an
online softmax over pages against one over all keys, a grouped product
against every expert for every token): logits of magnitude 1-4 agree to a
few 1e-6, and 2e-4 leaves two orders of room, while a post norm left out
moves them by 1e-2 or more (``test_reference_without_a_post_norm_fails``
measures each).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from chipbench import reference_pangu as ref
from chipbench import weights_pangu
from mxnet_tpu import serve
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models import dots3, layered, pangu_moe, trinity
from mxnet_tpu.ops import latent_attention as la
from mxnet_tpu.ops import moe
from mxnet_tpu.serve import schema

TOL = 2e-4
INIT = {"score_gain": 1.9, "expert_out_gain": 3.0, "router_pairs": True}


def _build(seed=11, **over):
    net, cfg = pangu_moe.pangu_tiny(**over)
    net.collect_params().setattr("grad_req", "null")
    net.initialize(mx.init.Zero())
    w = weights_pangu.make(pangu_moe.parameter_shapes(cfg), seed, INIT)
    for n, p in net.collect_params().items():
        p.set_data(w[n[len(net.prefix):]])
    rcfg = {k: getattr(cfg, k) for k in cfg.__dataclass_fields__}
    return net, cfg, w, rcfg


@pytest.fixture(scope="module")
def tiny():
    return _build(held_experts=(4, 8))


def _kernel_model():
    """A toy the page-walk kernel takes: latent rows whose context lanes are
    a whole lane tile (rank 128, 256 lanes stored), float32 pages of 8."""
    return _build(seed=12, kv_lora_rank=128, held_experts=(0, 16))


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
    net, _, w, rcfg = tiny
    toks = _tokens(40, rows=2)
    out = np.asarray(net(jnp.asarray(toks)))
    assert out.shape == (2, 40, 96)
    for b in range(2):
        np.testing.assert_allclose(out[b], _ref_logits(w, rcfg, toks[b]),
                                   atol=TOL, rtol=0)


def test_reference_tail_equals_its_full_pass(tiny):
    _, _, w, rcfg = tiny
    toks = _tokens(48, seed=3)
    full = _ref_logits(w, rcfg, toks)
    tail = np.asarray(jax.jit(lambda w, t: ref.tail_logits(
        w, rcfg, t, 44, 6))(w, jnp.asarray(toks)))
    np.testing.assert_allclose(tail, full[38:44], atol=1e-5, rtol=0)
    assert ref.tail_rows(rcfg, 48, 6) == [48, 48, 48, 6]


@pytest.mark.parametrize("part", ref.PARTS)
def test_reference_without_a_post_norm_fails(tiny, part):
    """Either post norm planted out of the reference parts it from the
    program by far more than ``TOL``: the comparison sees the sandwich."""
    net, _, w, rcfg = tiny
    toks = _tokens(40, seed=2)
    out = np.asarray(net(jnp.asarray(toks[None])))[0]
    bad = _ref_logits(w, rcfg, toks, leave_out=(part,))
    assert np.abs(out - bad).max() > 100 * TOL, part


def test_description_and_row_kinds(tiny):
    """Every layer is kind ``latent`` with cache kind ``latent`` and post
    norms: one latent row a layer under the main table, no index-key pool
    of dead bytes, no window table."""
    net, cfg, _, _ = tiny
    desc = net.decode_description()
    assert {d["attn"]["kind"] for d in desc} == {"latent"}
    assert {d["cache"] for d in desc} == {"latent"}
    assert all(d["post_norms"] for d in desc)
    assert [d["ffn"]["kind"] for d in desc] == ["swiglu"] + ["routed"] * 3
    eng = layered.LayeredEngine(net, 2, 1, 64)
    assert eng.full == [0, 1, 2, 3] and eng.idx == [] and eng.window is None
    assert eng.rows["latent"] == 128 and eng.rows["index_key"] == 0
    assert eng.main_page_bytes(4) == 4 * 128 * 4 * 4
    (lat, ikp), wlat = eng.pool_zeros(32, 0, 4)
    assert lat.shape == (4, 32, 4, 128)
    assert ikp.size == 0 and wlat.size == 0


def test_from_hf_reads_published_keys_and_refuses_the_rest():
    hf = {"hidden_size": 7680, "num_hidden_layers": 61,
          "first_k_dense_replace": 3, "intermediate_size": 18432,
          "num_attention_heads": 128, "num_key_value_heads": 128,
          "q_lora_rank": 1536, "kv_lora_rank": 512, "qk_nope_head_dim": 128,
          "qk_rope_head_dim": 64, "v_head_dim": 128,
          "rope_theta": 25600000, "n_routed_experts": 256,
          "n_shared_experts": 1, "num_experts_per_tok": 8,
          "moe_intermediate_size": 2048, "routed_scaling_factor": 2.5,
          "norm_topk_prob": True, "sandwich_norm": True,
          "vocab_size": 153600, "max_position_embeddings": 131072,
          "hidden_act": "silu", "tie_word_embeddings": False,
          "attention_bias": False, "rms_norm_eps": 1e-5}
    cfg = pangu_moe.PanguUltraMoEConfig.from_hf(
        hf, num_hidden_layers=5, held_experts=(0, 16),
        vocab_slice=(0, 19200), max_length=33152, dtype="bfloat16")
    assert (cfg.num_hidden_layers, cfg.held_experts, cfg.vocab_slice) == (
        5, (0, 16), (0, 19200))
    a = cfg.attention(1)
    assert (a["heads"], a["q_rank"], a["kv_rank"], a["nope"], a["rope"],
            a["v"], a["theta"]) == (128, 1536, 512, 128, 64, 128, 25.6e6)
    assert cfg.ffn(3)["scale"] == 2.5 and cfg.ffn(2)["kind"] == "swiglu"
    for key, bad in (("sandwich_norm", False), ("norm_topk_prob", False),
                     ("tie_word_embeddings", True), ("num_key_value_heads",
                                                     8),
                     ("scoring_func", "softmax")):
        with pytest.raises(ValueError, match=key):
            pangu_moe.PanguUltraMoEConfig.from_hf(dict(hf, **{key: bad}))


# --------------------------------------------------------------------------- #
# through the pools
# --------------------------------------------------------------------------- #

@pytest.fixture(params=["gathered", "kernel"])
def lowering(request, monkeypatch):
    """What the decode step attends with: the rows gathered through the
    table (pages of 4 float32 rows are no whole sublane tile: the kernel's
    ``supports`` says no), or the page-walk kernel, interpreted, on a model
    whose rows it takes (pages of 8)."""
    if request.param == "kernel":
        monkeypatch.setenv("MXNET_FLASH_INTERPRET", "1")
        return _kernel_model(), 8
    monkeypatch.delenv("MXNET_FLASH_INTERPRET", raising=False)
    return _build(held_experts=(4, 8)), 4


@pytest.mark.parametrize("chunk", [8, 5], ids=["aligned", "ragged"])
def test_prefill_then_decode_logits(lowering, chunk):
    """What the chunk executable runs (``chunk_tokens``), then what the step
    executable runs (``pool_token_paged``) for two slots, to 44 positions,
    through scattered pages: the logits of every position against the
    reference's full pass."""
    (net, _, w, rcfg), page = lowering
    T, prefill, npages = 44, 24, 256
    eng = layered.LayeredEngine(net, 2, 1, 48)
    toks = _tokens(T, seed=5, rows=2)
    want = [_ref_logits(w, rcfg, toks[b]) for b in range(2)]
    maxp = 48 // page
    perm = np.random.default_rng(1).permutation(npages)[:2 * maxp]
    pt = jnp.asarray(perm.reshape(2, maxp).astype(np.int32))
    pools = eng.pool_zeros(npages, 0, page)
    chunk_fn = jax.jit(lambda tk, off, nl, row, kp, vp: eng.chunk_tokens(
        tk, off, nl, row, page, kp, vp)[:3])
    for b in range(2):
        pos = 0
        while pos < prefill:
            n = min(chunk, prefill - pos)
            tk = np.zeros(chunk, np.int32)
            tk[:n] = toks[b, pos:pos + n]
            logits, kp, vp = chunk_fn(jnp.asarray(tk), jnp.int32(pos),
                                      jnp.int32(n - 1), pt[b], *pools)
            pools = (kp, vp)
            pos += n
            np.testing.assert_allclose(np.asarray(logits)[0],
                                       want[b][pos - 1], atol=TOL, rtol=0)
    step = jax.jit(lambda tok, pos, kp, vp: eng.pool_token_paged(
        tok, pos, kp, vp, pt, page))
    walked = 0
    for pos in range(prefill, T):
        logits, kp, vp, aux = step(jnp.asarray(toks[:, pos]),
                                   jnp.full((2,), pos, jnp.int32), *pools)
        pools = (kp, vp)
        for b in range(2):
            np.testing.assert_allclose(np.asarray(logits)[b], want[b][pos],
                                       atol=TOL, rtol=0)
        if "latent_walk" in aux:
            walked += int(np.asarray(aux["latent_walk"])[..., 0].sum())
    # the kernel walks every cached row and the new one, each slot, each
    # of the four layers; the gathered form counts nothing
    assert walked == (4 * 2 * sum(p + 1 for p in range(prefill, T))
                      if page == 8 else 0)


@pytest.mark.parametrize("lowering_kind", ["gathered", "kernel"])
def test_hit_and_one_chunk_equal_a_cold_prompt(lowering_kind, monkeypatch):
    """A document's pages written by one pass, then mapped into a second
    slot's table and the question taken by ONE chunk from the document's
    end (a prefix hit), give the same logits as the whole prompt from cold,
    and the reference's."""
    if lowering_kind == "kernel":
        monkeypatch.setenv("MXNET_FLASH_INTERPRET", "1")
        (net, _, w, rcfg), page = _kernel_model(), 8
    else:
        monkeypatch.delenv("MXNET_FLASH_INTERPRET", raising=False)
        (net, _, w, rcfg), page = _build(held_experts=(4, 8)), 4
    eng = layered.LayeredEngine(net, 1, 1, 64)
    doc, question = _tokens(32, seed=31), _tokens(7, seed=32)
    prompt = np.concatenate([doc, question])
    maxp = 64 // page
    pools = eng.pool_zeros(256, 0, page)
    run = jax.jit(lambda tk, off, nl, row, kp, vp: eng.chunk_tokens(
        tk, off, nl, row, page, kp, vp)[:3], static_argnums=())
    cold = jnp.asarray(np.arange(maxp, dtype=np.int32))
    pad = lambda t, n: jnp.asarray(np.pad(t, (0, n - t.size)))
    z_cold, _, _ = run(pad(prompt, 64), jnp.int32(0),
                       jnp.int32(prompt.size - 1), cold, *pools)
    # the document alone in pages 100.., then its pages mapped into a row
    # whose question page is fresh
    doc_row = jnp.asarray(100 + np.arange(maxp, dtype=np.int32))
    _, kp, vp = run(pad(doc, 64), jnp.int32(0), jnp.int32(doc.size - 1),
                    doc_row, *pools)
    hit_row = np.array(doc_row)
    hit_row[doc.size // page:] = 200 + np.arange(maxp - doc.size // page)
    z_hit, _, _ = run(pad(question, 8), jnp.int32(doc.size),
                      jnp.int32(question.size - 1), jnp.asarray(hit_row),
                      kp, vp)
    np.testing.assert_allclose(np.asarray(z_hit), np.asarray(z_cold),
                               atol=TOL, rtol=0)
    np.testing.assert_allclose(np.asarray(z_hit)[0],
                               _ref_logits(w, rcfg, prompt)[-1], atol=TOL,
                               rtol=0)


# the walk's length against the pages (of 8 rows, groups of 1,024): one
# row, a page's last row, inside a page, a group's edge and one past it,
# two groups and a ragged end; the last slot is retired (all sentinels)
_LENGTHS = [1, 8, 13, 1024, 1025, 2047, 700]


@pytest.mark.parametrize("runs", [True, False], ids=["runs", "page_copies"])
def test_kernel_equals_the_gathered_form(runs, monkeypatch):
    """``latent_paged_attention`` interpreted against its fallback (the
    rows gathered through the table and ``_attend``'s contractions) over
    the same pool, slot by slot: table rows in runs of consecutive pages
    and scattered, a slot whose row ends in sentinels before its width, a
    retired slot (reads 0 and walks nothing); the counts are the rows
    walked and at least one copy a slot that walked."""
    net, _, _, _ = _kernel_model()
    eng = layered.LayeredEngine(net, 8, 1, 2048)
    a = eng.desc[1]["attn"]
    page, npages, maxp = 8, 1024, 272
    lanes = eng.rows["latent"]
    rng = np.random.default_rng(4)
    pool = jnp.asarray(rng.normal(size=(2, npages, page, lanes)),
                       jnp.float32)
    B = len(_LENGTHS) + 1
    table = np.full((B, maxp), npages, np.int32)
    ids = list(rng.permutation(npages)) if not runs else None
    nxt = 0
    for b, n in enumerate(_LENGTHS):
        held = -(-n // page)
        if runs:
            table[b, :held] = nxt + np.arange(held)
            nxt += held
        else:
            table[b, :held] = [ids.pop() for _ in range(held)]
    table = jnp.asarray(table)
    ends = jnp.asarray(_LENGTHS + [0], jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, a["heads"], lanes)) * 0.1,
                    jnp.float32)
    scale = 1.0 / 12 ** 0.5

    def fallback():
        rows = pool.at[1, jnp.minimum(table, npages - 1)].get(
            mode="promise_in_bounds").reshape(B, maxp * page, lanes)
        ok = jnp.arange(maxp * page)[None, :] < ends[:, None]
        s = jnp.einsum("bhf,btf->bht", q, rows) * scale
        p = jax.nn.softmax(jnp.where(ok[:, None], s, -1e30), axis=-1)
        return jnp.einsum("bht,btr->bhr", p, rows[..., :a["kv_rank"]])

    monkeypatch.setenv("MXNET_FLASH_INTERPRET", "1")
    got, counts = jax.jit(lambda: la._kernel_call(
        q, pool, 1, table, ends, scale, a["kv_rank"], True,
        runs=runs))()
    want = np.asarray(jax.jit(fallback)())
    live = np.arange(B) < B - 1
    np.testing.assert_allclose(np.asarray(got)[live], want[live], atol=1e-5,
                               rtol=0)
    assert not np.asarray(got)[~live].any()
    counts = np.asarray(counts)
    assert counts[:, 0].tolist() == _LENGTHS + [0]
    assert (counts[live, 1] >= 1).all() and counts[~live, 1] == 0
    if not runs:
        # every page a copy of its own
        assert counts[:, 1].tolist() == [-(-n // page) for n in _LENGTHS] \
            + [0]


# a chunk of C queries a slot: (C, heads, dtype, table rows in runs, query
# rows a tile — None: the served tile).  The last query of each live slot
# ends mid-page; 40 heads (48 padded) make 42 queries a tile, so a 512 chunk
# ends in a partial tile; tiles of 2 queries walk eight tiles a slot, each to
# its own end, prefetching across tiles and slots
_CHUNKS = {
    "C2_float32": (2, 4, "float32", True, None),
    "C16_float32_scattered": (16, 4, "float32", False, None),
    "C16_tiles_of_2_queries": (16, 4, "float32", True, 32),
    "C128_bfloat16": (128, 4, "bfloat16", True, None),
    "C512_partial_tile_bfloat16_scattered": (512, 40, "bfloat16", False,
                                             None),
}


@pytest.mark.parametrize("case", list(_CHUNKS))
def test_chunk_kernel_equals_the_dense_form(case, monkeypatch):
    """``latent_chunk_attention`` interpreted against the masked dense form
    over the same pool: a wave of two live slots at different offsets
    (each query sees its own position and what lies before it; the held
    pages past the chunk are other rows and masked) and a retired slot,
    which reads 0 and walks nothing.  Counts: each live slot's last query's
    end (once, however many tiles re-read the rows) and at least one copy."""
    C, H, dtype, runs, tile = _CHUNKS[case]
    dtype = jnp.dtype(dtype)
    lanes, rank = 256, 128
    page = 8 * 4 // dtype.itemsize
    npages = 512
    offs = [1003 - C, 501]
    rng = np.random.default_rng(C + H)
    pool = jnp.asarray(rng.normal(size=(2, npages, page, lanes)), dtype)
    held = [-(-(o + C) // page) + 2 for o in offs]
    maxp = max(held) + 3
    B = len(offs) + 1
    table = np.full((B, maxp), npages, np.int32)
    ids = iter(rng.permutation(npages))
    nxt = 0
    for b, n in enumerate(held):
        table[b, :n] = nxt + np.arange(n) if runs \
            else [next(ids) for _ in range(n)]
        nxt += n
    pos = np.asarray(offs + [0])[:, None] + np.arange(C)[None]
    ends = np.minimum(pos + 1, np.asarray(held + [0])[:, None] * page)
    q = jnp.asarray(rng.normal(size=(B, C, H, lanes)) * 0.1, dtype)
    scale = 1.0 / 12 ** 0.5

    rows = np.asarray(pool[1].astype(jnp.float32))[
        np.minimum(table, npages - 1)].reshape(B, maxp * page, lanes)
    s = np.einsum("bchf,btf->bcht", np.asarray(q.astype(jnp.float32)),
                  rows) * scale
    s = np.where(np.arange(maxp * page)[None, None, None]
                 < ends[:, :, None, None], s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("bcht,btr->bchr", p / p.sum(-1, keepdims=True),
                     rows[..., :rank])

    monkeypatch.setenv("MXNET_FLASH_INTERPRET", "1")
    got, counts = jax.jit(lambda q, pool, table, ends: la._chunk_call(
        q, pool, 1, table, ends, scale, rank, True, tile=tile))(
            q, pool, jnp.asarray(table), jnp.asarray(ends, jnp.int32))
    got = np.asarray(got.astype(jnp.float32))
    assert got.shape == (B, C, H, rank)
    live = slice(0, B - 1)
    if dtype == jnp.float32:
        np.testing.assert_allclose(got[live], want[live], atol=1e-5, rtol=0)
    else:
        # bfloat16 rows and weights: the two sum in another order and round
        # p apart (the chip's bench holds them to the same 2e-2)
        assert np.abs(got[live] - want[live]).max() \
            <= 2e-2 * np.abs(want[live]).max()
    assert not got[B - 1].any()
    counts = np.asarray(counts)
    assert counts[:, 0].tolist() == [int(e[-1]) for e in ends[:-1]] + [0]
    assert (counts[live, 1] >= 1).all() and counts[B - 1, 1] == 0


# --------------------------------------------------------------------------- #
# through DecodeServer
# --------------------------------------------------------------------------- #

def _server(net, **over):
    kw = dict(max_total_len=128, pool_sizes=(4,), admit_sizes=(1, 2),
              prefill_buckets=(8, 32), page_size=4, num_pages=128,
              spec=False, autostart=False)
    kw.update(over)
    return serve.DecodeServer(net, **kw)


def _drain(srv, streams):
    for _ in range(400):
        if all(s.done for s in streams):
            break
        srv.pump()
    return [s.tokens(timeout=0) for s in streams]


def test_served_streams_match_reference(tiny):
    """Admit waves, chunked prefill (50 and 100 tokens) and 12 decode steps
    each: token for token the reference's greedy stream."""
    net, _, w, rcfg = tiny
    srv = _server(net)
    assert not srv.sync_mode and srv._progs.layered
    assert srv._progs.window is None
    long_one = _tokens(100, seed=100)
    got, = _drain(srv, [srv.submit(long_one, max_new_tokens=6)])
    assert _is_ref_stream(w, rcfg, long_one, got)
    prompts = [_tokens(n, seed=n) for n in (5, 21, 50, 13)]
    got = _drain(srv, [srv.submit(p, max_new_tokens=12) for p in prompts])
    for p, g in zip(prompts, got):
        assert len(g) == 12 and _is_ref_stream(w, rcfg, p, g)
    st = srv.stats()
    assert st["counters"]["chunk_dispatches"] >= 2
    assert 0.0 < st["moe_experts_touched_share"] <= 1.0
    # the gathered form's step counts no walk
    assert st["counters"]["latent_rows_walked"] == 0
    assert "latent_rows_walked_per_step" not in st
    assert st["pool_bytes"] == 128 * srv._progs.page_bytes() \
        + 4 * schema.slot_state_bytes()
    srv.close()


def test_prefix_hit_and_one_chunk_serve_the_cold_stream(tiny):
    """A cached 48-token document, then the document + a question: the
    prefix pages are mapped, ONE chunk takes the question, and the stream
    is the cold one's."""
    net, _, w, rcfg = tiny
    doc = _tokens(48, seed=48)
    prompt = np.concatenate([doc, _tokens(9, seed=9)])
    miss = _server(net, prefix_cache=False)
    want, = _drain(miss, [miss.submit(prompt, max_new_tokens=10)])
    miss.close()
    assert _is_ref_stream(w, rcfg, prompt, want)
    srv = _server(net)
    _drain(srv, [srv.submit(doc, max_new_tokens=1)])
    srv.reset_counters()
    got, = _drain(srv, [srv.submit(prompt, max_new_tokens=10)])
    assert got == want
    c = srv.stats()["counters"]
    assert c["prefix_hits"] == 1 and c["chunk_dispatches"] == 1
    assert c["admit_dispatches"] == 0
    srv.close()


def test_walk_counter_reaches_stats(monkeypatch):
    """With the kernels interpreted the step's walks count every cached row
    and the new one of every live slot in every latent layer: the server's
    counter and ``stats()``'s mean a step.  The chunks' walks count apart,
    each its last query's end a layer; the streams are the dense form's."""
    monkeypatch.delenv("MXNET_FLASH_INTERPRET", raising=False)
    net, _, _, _ = _kernel_model()
    kw = dict(page_size=8, num_pages=128, prefill_buckets=(16,),
              admit_sizes=(1,))
    prompt = _tokens(20, seed=1)
    dense = _server(net, **kw)
    want, = _drain(dense, [dense.submit(prompt, max_new_tokens=5)])
    assert dense.stats()["counters"]["chunk_latent_rows_walked"] == 0
    dense.close()
    monkeypatch.setenv("MXNET_FLASH_INTERPRET", "1")
    srv = _server(net, **kw)
    got, = _drain(srv, [srv.submit(prompt, max_new_tokens=5)])
    assert got == want
    for _ in range(3):      # the readbacks of steps still in flight
        srv.pump()
    st = srv.stats()
    # four steps after the chunk's first token, positions 20..23
    rows = 4 * sum(p + 1 for p in range(20, 24))
    assert st["counters"]["latent_rows_walked"] == rows
    assert st["latent_rows_walked_per_step"] == pytest.approx(
        rows / st["steps"])
    assert st["latent_copies_per_step"] * st["steps"] >= 4 * 4
    # two chunks of 16 in four layers: positions 0-15, then 16-31 of the
    # four pages the prompt and its answer hold (its padding rows too)
    assert st["counters"]["chunk_dispatches"] == 2
    assert st["counters"]["chunk_latent_rows_walked"] == 4 * (16 + 32)
    srv.close()


def test_speculation_is_refused(tiny):
    net, _, _, _ = tiny
    with pytest.raises(MXNetError, match="draft-and-verify"):
        _server(net, spec=True)


# --------------------------------------------------------------------------- #
# the routed layer, and the models that share the layer loop
# --------------------------------------------------------------------------- #

def test_expert_shares_add_up_to_the_uncut_layer():
    """Four chips' shares of the routed sum (4 of 16 experts each), with the
    shared expert counted once, are the reference's uncut layer; one share
    alone is what the reference gives for that share."""
    _, cfg, w, rcfg = _build(held_experts=(0, 16))
    lw = ref.layer_weights(w, 2)
    x = jnp.asarray(np.random.default_rng(3).normal(size=(24, 32)),
                    jnp.float32)
    want = np.asarray(ref.ffn(rcfg, lw, False, x, ref.mm_f32))
    h = layered._rms(x, lw["norm2_gamma"], cfg.rms_norm_eps)
    idx, wts = moe.route(h, lw["router_weight"], lw["router_bias"], 4,
                         cfg.routed_scaling_factor)
    shared = moe.swiglu(h, lw["sgu_weight"], lw["sdown_weight"])
    total, loads = shared, []
    for lo in range(0, 16, 4):
        y, load = moe.routed_experts(h, idx, wts, lw["egu_weight"][lo:lo + 4],
                                     lw["edown_weight"][lo:lo + 4], lo)
        total = total + y
        loads.append(np.asarray(load))
        part = np.asarray(ref.ffn(dict(rcfg, held_experts=(lo, 4)), dict(
            lw, egu_weight=lw["egu_weight"][lo:lo + 4],
            edown_weight=lw["edown_weight"][lo:lo + 4]), False, x,
            ref.mm_f32))
        np.testing.assert_allclose(np.asarray(y + shared), part, atol=TOL,
                                   rtol=0)
    np.testing.assert_allclose(np.asarray(total), want, atol=TOL, rtol=0)
    assert np.concatenate(loads).sum() == 24 * 4      # no token dropped


@pytest.mark.parametrize("family", ["dots3", "trinity"])
def test_models_without_post_norms_in_the_loop_are_unchanged(family):
    """The layer loop's ``post_norms`` and optional gate leave ``dots3``
    (the loop, its gate declared) and ``trinity`` (the stacked runs) as
    their references have them."""
    if family == "dots3":
        from chipbench import reference_dots3 as fref
        from chipbench import weights_dots3 as fw
        net, cfg = dots3.dots3_tiny()
        init = {"score_gain": 0.7, "expert_out_gain": 3.0}
        shapes = dots3.parameter_shapes(cfg)
        assert all(d["attn"]["gate"] and not d.get("post_norms")
                   for d in net.decode_description())
    else:
        from chipbench import reference_trinity as fref
        from chipbench import weights_trinity as fw
        net, cfg = trinity.trinity_tiny()
        init = {"qk_gain": 1.7, "expert_out_gain": 3.0}
        shapes = trinity.parameter_shapes(cfg)
    net.collect_params().setattr("grad_req", "null")
    net.initialize(mx.init.Zero())
    w = fw.make(shapes, 5, init)
    for n, p in net.collect_params().items():
        p.set_data(w[n[len(net.prefix):]])
    rcfg = {k: getattr(cfg, k) for k in cfg.__dataclass_fields__}
    toks = _tokens(24, seed=4)
    out = np.asarray(net(jnp.asarray(toks[None])))[0]
    want = np.asarray(jax.jit(lambda w, t: fref.full_logits(w, rcfg, t))(
        w, jnp.asarray(toks)))
    np.testing.assert_allclose(out, want, atol=TOL, rtol=0)


def test_weights_pair_the_router_and_nothing_else():
    """``init.router_pairs``: expert ``2k + 1``'s router column and bias are
    expert ``2k``'s with the sign turned; every other leaf is
    ``weights_dots3``'s."""
    from chipbench import weights_dots3
    shapes = {"h1_router_weight": ((16, 8), "float32"),
              "h1_router_bias": ((8,), "float32"),
              "h1_egu_weight": ((4, 16, 8), "float32")}
    paired = weights_pangu.make(shapes, 3000000019, {"router_pairs": True})
    plain = weights_dots3.make(shapes, 3000000019, {})
    for name in ("h1_router_weight", "h1_router_bias"):
        x = np.asarray(paired[name])
        assert np.array_equal(x[..., 1::2], -x[..., 0::2]) and x.any()
        assert np.array_equal(x[..., 0::2], np.asarray(plain[name])[..., 0::2])
    assert np.array_equal(paired["h1_egu_weight"], plain["h1_egu_weight"])
