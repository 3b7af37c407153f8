"""``models.granite_hybrid`` against the plain float32 reference
(``chipbench/reference_granite.py``: token-by-token recurrence) on seeded
weights, at a tiny size on the CPU, comparing LOGITS: the full forward;
prefill then decode through the pools; a prompt whole against the same prompt
in chunks that do not divide it; a right-padded wave against each prompt
alone; a slot reused after a longer tenant; the chunked scan and the step's
update against the recurrence; attention with its multiplier and no
positions; each multiplier left out of the reference.

Tolerance ``TOL``: program and reference are both float32 here and differ in
the ORDER of their sums only (the chunked form's ``(C B^T * L)(dt x)`` and
carried state against one rank-one update a token, grouped einsums against
per-head ones, a tied head contracted as it lies): logits of magnitude 1-3
agree to a few 1e-6, and 2e-4 leaves two orders of room, while a dropped term
moves them by 1e-2 or more (``test_reference_without_a_multiplier_fails``
measures that for each multiplier).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from chipbench import reference_granite as ref
from chipbench import weights_granite
from mxnet_tpu import serve
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models import granite_hybrid as gh
from mxnet_tpu.models import layered
from mxnet_tpu.ops import ssd

TOL = 2e-4


def _build(seed=11, **over):
    net, cfg = gh.granite_hybrid_tiny(**over)
    net.collect_params().setattr("grad_req", "null")
    net.initialize(mx.init.Zero())
    w = weights_granite.make(gh.parameter_shapes(cfg), seed,
                             {"embed_gain": 0.25, "final_norm_gain": 14.0})
    for n, p in net.collect_params().items():
        p.set_data(w[n[len(net.prefix):]])
    rcfg = {k: getattr(cfg, k) for k in cfg.__dataclass_fields__}
    return net, cfg, w, rcfg


@pytest.fixture(scope="module")
def tiny():
    return _build()


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, 96, n).astype(np.int32)


@functools.partial(jax.jit, static_argnames=("frozen", "leave_out"))
def _ref_jit(w, toks, frozen, leave_out=()):
    return ref.full_logits(w, dict(frozen), toks, leave_out=leave_out)


def _ref_logits(w, rcfg, toks, leave_out=()):
    return np.asarray(_ref_jit(w, jnp.asarray(np.asarray(toks, np.int32)),
                               ref.freeze(rcfg), tuple(leave_out)))


# --------------------------------------------------------------------------- #
# the operations
# --------------------------------------------------------------------------- #

def _recurrence(x, dt, a, b, c, s):
    ys = []
    for t in range(x.shape[1]):
        s = jnp.exp(dt[:, t] * a)[..., None, None] * s \
            + (dt[:, t, :, None] * x[:, t])[..., None] \
            * b[:, t, None, None, :]
        ys.append(jnp.einsum("bhpn,bn->bhp", s, c[:, t]))
    return jnp.stack(ys, 1), s


def _ssm_inputs(B, T, H=4, P=16, N=8, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    return (jax.random.normal(k[0], (B, T, H, P)),
            jax.nn.softplus(jax.random.normal(k[1], (B, T, H)) - 2.0),
            -jnp.exp(jax.random.normal(k[2], (H,))),
            jax.random.normal(k[3], (B, T, N)),
            jax.random.normal(k[4], (B, T, N)),
            jax.random.normal(k[5], (B, H, P, N)))


@pytest.mark.parametrize("T", [1, 7, 8, 19, 37])
def test_chunked_scan_is_the_recurrence(T):
    """Chunk 8 at lengths that are and are not its multiples, from a state
    that is not zero.  Sums in another order: 1e-5 of values of order 1-10."""
    x, dt, a, b, c, s0 = _ssm_inputs(2, T)
    with jax.default_matmul_precision("highest"):
        y, s1 = ssd.chunk_scan(x, dt, a, b, c, s0, 8)
        want_y, want_s = _recurrence(x, dt, a, b, c, s0)
    np.testing.assert_allclose(y, want_y, atol=5e-5, rtol=1e-5)
    np.testing.assert_allclose(s1, want_s, atol=5e-5, rtol=1e-5)


@pytest.mark.parametrize("count", [3, 8, 13])
def test_padding_leaves_the_state_at_the_true_length(count):
    """``dt = 0`` past a row's true tokens: the state after 13 columns is
    the state after ``count``."""
    x, dt, a, b, c, s0 = _ssm_inputs(1, 13, seed=1)
    true = jnp.arange(13)[None, :, None] < count
    with jax.default_matmul_precision("highest"):
        _, s1 = ssd.chunk_scan(x, jnp.where(true, dt, 0.0), a, b, c, s0, 8)
        _, want = _recurrence(x[:, :count], dt[:, :count], a, b[:, :count],
                              c[:, :count], s0)
    np.testing.assert_allclose(s1, want, atol=5e-5, rtol=1e-5)


@pytest.mark.parametrize("interpret", [False, True],
                         ids=["plain", "kernel-interpreted"])
def test_step_update_is_one_more_position_of_the_scan(monkeypatch,
                                                      interpret):
    """The step's in-place update (its plain form, and the Pallas kernel
    interpreted) against position T of a scan over T + 1; a slot that is
    not live keeps its state, another layer's entry is untouched."""
    if interpret:
        monkeypatch.setenv("MXNET_FLASH_INTERPRET", "1")
    S, T, H, P, N = 3, 9, 4, 64, 16      # P = 64: two heads a lane row
    x, dt, a, b, c, s0 = _ssm_inputs(S, T + 1, H, P, N, seed=2)
    with jax.default_matmul_precision("highest"):
        _, mid = ssd.chunk_scan(x[:, :T], dt[:, :T], a, b[:, :T], c[:, :T],
                                s0, 4)
        want_y, want_s = ssd.chunk_scan(x, dt, a, b, c, s0, 4)
        stored = jnp.stack([ssd.pack(s0), ssd.pack(mid)])
        live = jnp.asarray([True, False, True])
        y, new = ssd.state_update(stored, jnp.int32(1), x[:, T], dt[:, T],
                                  a, b[:, T], c[:, T], live)
    got = ssd.unpack(new[1], P)
    np.testing.assert_allclose(y[live], want_y[:, T][live], atol=5e-5,
                               rtol=1e-5)
    np.testing.assert_allclose(got[live], want_s[live], atol=5e-5, rtol=1e-5)
    np.testing.assert_array_equal(got[1], mid[1])
    np.testing.assert_array_equal(new[0], stored[0])


def test_stored_layout_round_trips():
    s = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 4, 64, 16))
    packed = ssd.pack(s)
    assert packed.shape == (2, 3, 2, 16, 128)        # whole lane tiles
    np.testing.assert_array_equal(ssd.unpack(packed, 64), s)
    assert ssd.heads_per_row(64, 64) == 2 and ssd.heads_per_row(3, 64) == 1


@pytest.mark.parametrize("count", [1, 2, 5])
def test_conv_tail_is_the_last_true_inputs(count):
    """The tail after a right-padded row is its last ``K - 1`` TRUE inputs
    (what was stored before fills in where the row has fewer)."""
    k = jax.random.split(jax.random.PRNGKey(3), 4)
    tail, u = jax.random.normal(k[0], (1, 3, 6)), \
        jax.random.normal(k[1], (1, 5, 6))
    w, bias = jax.random.normal(k[2], (4, 6)), jax.random.normal(k[3], (6,))
    out, new = ssd.conv_seq(tail, u, w, bias, jnp.asarray([count]))
    stream = jnp.concatenate([tail, u[:, :count]], axis=1)
    np.testing.assert_array_equal(new, stream[:, -3:])
    step, tail1 = ssd.conv_step(tail, u[:, 0], w, bias)
    np.testing.assert_allclose(step, out[:, 0], atol=1e-6)
    np.testing.assert_array_equal(tail1, stream[:, 1:4])


# --------------------------------------------------------------------------- #
# the model against the reference
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("chunk", [8, 256], ids=["chunk8", "one-chunk"])
@pytest.mark.parametrize("length", [21, 40])
def test_full_forward_matches_reference(length, chunk):
    net, cfg, w, rcfg = _build(mamba_chunk_size=chunk)
    toks = _tokens(length, seed=3)
    got = np.asarray(net(mx.nd.array(toks[None], dtype="int32"))._data)[0]
    np.testing.assert_allclose(got, _ref_logits(w, rcfg, toks), atol=TOL,
                               rtol=0)


@pytest.mark.parametrize("name", ref.MULTIPLIERS)
def test_reference_without_a_multiplier_fails(tiny, name):
    """Each of the four multipliers and ``D`` left out of the REFERENCE
    moves its logits by far more than ``TOL``: none can be dropped from the
    program unnoticed."""
    net, cfg, w, rcfg = tiny
    toks = _tokens(21, seed=3)
    got = np.asarray(net(mx.nd.array(toks[None], dtype="int32"))._data)[0]
    without = _ref_logits(w, rcfg, toks, leave_out=(name,))
    assert np.abs(got - without).max() > 50 * TOL


def test_attention_has_no_positions():
    """One attention layer alone: the last position's logits do not change
    when the tokens before it change places; and the scale is the
    multiplier (the reference with ``1 / sqrt(head width)`` disagrees)."""
    net, cfg, w, rcfg = _build(num_hidden_layers=1,
                               layer_types=("attention",))
    toks = _tokens(19, seed=4)
    shuffled = np.concatenate([np.random.default_rng(0).permutation(
        toks[:-1]), toks[-1:]])
    fwd = lambda t: np.asarray(
        net(mx.nd.array(t[None], dtype="int32"))._data)[0]
    a, b = fwd(toks), fwd(shuffled)
    np.testing.assert_allclose(a[-1], b[-1], atol=1e-5, rtol=0)
    assert np.abs(a[3] - b[3]).max() > 1e-2
    np.testing.assert_allclose(a, _ref_logits(w, rcfg, toks), atol=TOL,
                               rtol=0)
    assert np.abs(a - _ref_logits(
        w, rcfg, toks, leave_out=("attention_multiplier",))).max() > 50 * TOL


# --------------------------------------------------------------------------- #
# through the pools
# --------------------------------------------------------------------------- #

class _Pools:
    """One engine, ``slots`` slots of scattered pages, jitted programs."""

    def __init__(self, net, slots, T, page=4):
        self.eng = layered.LayeredEngine(net, slots, 1, T)
        self.page, self.w = page, net.weights()
        n = -(-T // page)
        perm = np.random.default_rng(1).permutation(slots * n + 3)
        self.pt = jnp.asarray(perm[:slots * n].reshape(slots, n).astype(
            np.int32))
        self.pools = self.eng.pool_zeros(slots * n + 3, 0, page, slots)
        self.run = jax.jit(self._run)

    def _run(self, pools, toks, off, last, rows, live):
        return self.eng.tokens_paged(
            self.w, toks, off, self.pt[rows], pools, self.page, last,
            slots=rows, live=live)[:3]

    def prefill(self, rows, prompts, off=0):
        """Right-padded rows ``prompts`` into slots ``rows`` at ``off``."""
        lens = [len(p) for p in prompts]
        block = np.zeros((len(rows), max(lens)), np.int32)
        for i, p in enumerate(prompts):
            block[i, :len(p)] = p
        return self._call(jnp.asarray(block),
                          jnp.full((len(rows),), off, jnp.int32),
                          jnp.asarray(lens, jnp.int32) - 1,
                          jnp.asarray(rows, jnp.int32), None)

    def step(self, toks, pos, live=None):
        S = self.pt.shape[0]
        live = jnp.ones((S,), bool) if live is None else jnp.asarray(live)
        return self._call(jnp.asarray(toks, jnp.int32)[:, None],
                          jnp.asarray(pos, jnp.int32),
                          jnp.zeros((S,), jnp.int32),
                          jnp.arange(S, dtype=jnp.int32), live)

    def _call(self, toks, off, last, rows, live):
        logits, kp, vp = self.run(self.pools, toks, off, last, rows, live)
        self.pools = (kp, vp)
        return np.asarray(logits)


@pytest.mark.parametrize("chunk", [24, 8, 5], ids=["whole", "aligned",
                                                   "ragged"])
def test_paged_prefill_then_decode_logits(tiny, chunk):
    """A 24-token prompt prefilled whole, in chunks of 8 and in chunks of 5
    (which does not divide it: state and tail carried across a ragged
    boundary), then one token at a time to 64 positions — 40 steps, longer
    than most heads remember — through scattered pages: the logits of every
    position against the reference's full pass."""
    net, cfg, w, rcfg = tiny
    T, prefill = 64, 24
    toks = _tokens(T, seed=5)
    want = _ref_logits(w, rcfg, toks)
    pools = _Pools(net, 1, T)
    pos = 0
    while pos < T:
        if pos < prefill:
            n = min(chunk, prefill - pos)
            logits = pools.prefill([0], [toks[pos:pos + n]], off=pos)
        else:
            n = 1
            logits = pools.step(toks[pos:pos + 1], [pos])
        pos += n
        np.testing.assert_allclose(logits[0], want[pos - 1], atol=TOL,
                                   rtol=0)


def test_padded_wave_equals_each_prompt_alone(tiny):
    """Three unequal prompts right-padded into one wave, then four steps of
    all three: every row's logits are those of its prompt alone (a row's
    padding reaches neither its state, its tail nor its K/V)."""
    net, cfg, w, rcfg = tiny
    lens = [5, 16, 11]
    seqs = [_tokens(n + 4, seed=10 + i) for i, n in enumerate(lens)]
    want = [_ref_logits(w, rcfg, s) for s in seqs]
    pools = _Pools(net, 3, 32)
    logits = pools.prefill([0, 1, 2], [s[:n] for s, n in zip(seqs, lens)])
    for i, n in enumerate(lens):
        np.testing.assert_allclose(logits[i], want[i][n - 1], atol=TOL,
                                   rtol=0)
    for k in range(4):
        logits = pools.step([s[n + k] for s, n in zip(seqs, lens)],
                            [n + k for n in lens])
        for i, n in enumerate(lens):
            np.testing.assert_allclose(logits[i], want[i][n + k], atol=TOL,
                                       rtol=0)


def test_reused_slot_starts_from_zero(tiny):
    """A 20-token tenant and six of its steps, then a 7-token prompt into
    the SAME slot: the newcomer's logits are those of its prompt alone
    (state and tail reset), while a step with the slot not live left the
    first tenant's state as it was."""
    net, cfg, w, rcfg = tiny
    first, second = _tokens(28, seed=20), _tokens(12, seed=21)
    pools = _Pools(net, 1, 32)
    pools.prefill([0], [first[:20]])
    for k in range(6):
        pools.step(first[20 + k:21 + k], [20 + k])
    before = jax.tree.map(np.asarray, pools.pools[1])
    pools.step(first[26:27], [26], live=[False])
    for a, b in zip(before, pools.pools[1]):
        np.testing.assert_array_equal(a, np.asarray(b))
    want = _ref_logits(w, rcfg, second)
    logits = pools.prefill([0], [second[:7]])
    np.testing.assert_allclose(logits[0], want[6], atol=TOL, rtol=0)
    for k in range(5):
        logits = pools.step(second[7 + k:8 + k], [7 + k])
        np.testing.assert_allclose(logits[0], want[7 + k], atol=TOL, rtol=0)


# --------------------------------------------------------------------------- #
# through DecodeServer
# --------------------------------------------------------------------------- #

def _server(net, **over):
    kw = dict(max_total_len=64, pool_sizes=(2,), admit_sizes=(1, 2),
              prefill_buckets=(8, 16), page_size=4, spec=False,
              autostart=False)
    kw.update(over)
    return serve.DecodeServer(net, **kw)


def _drain(srv, streams):
    for _ in range(400):
        if all(s.done for s in streams):
            break
        srv.pump()
    return [s.tokens(timeout=0) for s in streams]


def test_served_streams_match_reference(tiny):
    """Five requests through two slots — admitted in a wave, chunked (a
    21- and a 30-token prompt against buckets of 16), every slot reused:
    each served token is the reference's first choice at its position
    (teacher-forced), by a margin that rounding does not reach."""
    net, cfg, w, rcfg = tiny
    srv = _server(net)
    prompts = [_tokens(n, seed=30 + i)
               for i, n in enumerate([5, 21, 13, 30, 3])]
    got = _drain(srv, [srv.submit(p, max_new_tokens=10) for p in prompts])
    stats = srv.stats()
    srv.close()
    for p, g in zip(prompts, got):
        z = _ref_logits(w, rcfg, np.concatenate([p, g[:-1]]))[len(p) - 1:]
        served = z[np.arange(len(g)), np.asarray(g)]
        assert np.all(z.max(-1) - served <= TOL)
    assert stats["state_resets"] == 5 and stats["prefix_cache"] is False
    assert stats["slot_kinds"] == ["ssm_state"]
    assert stats["counters"]["chunk_dispatches"] >= 4
    # no selecting layer: the index-score walk's counters read 0 / 0
    assert stats["index_pages_walked"] == stats["index_pages_table"] == 0
    eng = srv._progs.eng if srv._progs else None
    assert stats["state_bytes_per_slot"] == eng.slot_state_bytes() > 0


@pytest.mark.parametrize("what,kwargs,names", [
    ("prefix", dict(prefix_cache=True), "ssm_state"),
    ("spec", dict(spec=True), "ssm_state"),
    ("int8", dict(kv_dtype="int8"), "ssm_state"),
    ("pools", dict(pool_sizes=(2, 4)), "one pool size"),
])
def test_unsupported_options_are_refused_loudly(tiny, what, kwargs, names):
    """An explicit prefix cache, draft-and-verify, int8 rows and a growing
    pool each raise, with a message that names the kind."""
    net = tiny[0]
    with pytest.raises(MXNetError, match=names):
        srv = _server(net, **kwargs)
        srv.close()     # a server that fell back to sync mode says why
        raise MXNetError(srv.sync_reason or "served")


def test_verify_program_names_the_state(tiny):
    srv = _server(tiny[0])
    with pytest.raises(MXNetError, match="ssm_state.*rolled back"):
        srv._progs.verify_fn(2)
    srv.close()


def test_description_and_runs(tiny):
    net, cfg = tiny[0], tiny[1]
    desc = net.decode_description()
    assert [d["cache"] for d in desc] == ["ssm_state", "ssm_state", "kv",
                                          "ssm_state", "ssm_state",
                                          "ssm_state"]
    assert gh.layer_runs(cfg) == [("ssm", 0, 2), ("gqa", 2, 1),
                                  ("ssm", 3, 3)]
    eng = layered.LayeredEngine(net, 1, 1, 16)
    assert eng.runs == [(0, 2), (2, 1), (3, 3)] and eng.dense_chunk is None
    assert desc[2]["attn"]["scale"] == cfg.attention_multiplier
    assert all(d["residual"] == cfg.residual_multiplier for d in desc)
    # the published pattern: nine runs, 36 + 4 layers
    hf = dict(num_hidden_layers=40, layer_types=["mamba"] * 5 + (
        ["attention"] + ["mamba"] * 9) * 3 + ["attention"] + ["mamba"] * 4,
        max_position_embeddings=131072)
    full = gh.GraniteHybridConfig.from_hf(hf)
    assert [n for _, _, n in gh.layer_runs(full)] == [5, 1, 9, 1, 9, 1, 9,
                                                      1, 4]
    shapes = gh.parameter_shapes(full)
    assert sum(int(np.prod(s)) for s, _ in shapes.values()) == 3191396096


@pytest.mark.parametrize("kind", ["sliding_gqa", "latent_window"],
                         ids=["unknown", "other-body"])
def test_engine_refuses_kinds_it_has_no_body_for(tiny, monkeypatch, kind):
    """A kind the engine's table lacks, or one of the layer loop beside
    kinds of the stacked runs, raises at build and names the kinds: neither
    silently runs another kind's mixer."""
    net = tiny[0]
    desc = [dict(d) for d in net.decode_description()]
    desc[2] = dict(desc[2], attn=dict(desc[2]["attn"], kind=kind))
    monkeypatch.setattr(net, "decode_description", lambda: desc)
    with pytest.raises(MXNetError, match=kind):
        layered.LayeredEngine(net, 1, 1, 16)
