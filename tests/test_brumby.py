"""``models.brumby`` and ``ops.power_retention`` against the plain float32
reference (``chipbench/reference_brumby.py``: the ATTENTION form, no state) on
seeded weights, at a tiny size on the CPU, comparing LOGITS: the expansion's
inner product; the step's kernel (interpreted) against its ``jax.numpy``
form, live and retired slots, bfloat16 inputs; the chunked scan against the
attention form across chunk boundaries, with right padding and from a state
that is not zero; prefill then decode through ``DecodeServer`` with a reused
slot; what ``from_hf`` refuses; the gate init's decays.

Tolerance ``TOL``: program and reference are both float32 here and differ in
the ORDER and FORM of their sums only (a state carried token by token or
chunk by chunk against every score summed afresh): logits of magnitude 1-3
agree to a few 1e-6, and 2e-4 leaves two orders of room.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from chipbench import reference_brumby as ref
from chipbench import weights_brumby
from mxnet_tpu import serve
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models import brumby as bm
from mxnet_tpu.models import layered
from mxnet_tpu.ops import power_retention as pr

TOL = 2e-4
# the sink channel and the decays the tiny model's gate is built for
INIT = {"sink": 32.0, "gate_logits": [3.0, 6.0], "gate_noise": 1.0}


def _build(seed=11, **over):
    net, cfg = bm.brumby_tiny(**over)
    net.collect_params().setattr("grad_req", "null")
    net.initialize(mx.init.Zero())
    w = weights_brumby.make(bm.parameter_shapes(cfg), seed, INIT)
    for n, p in net.collect_params().items():
        p.set_data(w[n[len(net.prefix):]])
    rcfg = {k: getattr(cfg, k) for k in cfg.__dataclass_fields__}
    return net, cfg, w, rcfg


@pytest.fixture(scope="module")
def tiny():
    return _build()


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, 96, n).astype(np.int32)


@functools.partial(jax.jit, static_argnames=("frozen",))
def _ref_jit(w, toks, frozen):
    return ref.full_logits(w, dict(frozen), toks)


def _ref_logits(w, rcfg, toks):
    return np.asarray(_ref_jit(w, jnp.asarray(np.asarray(toks, np.int32)),
                               ref.freeze(rcfg)))


# --------------------------------------------------------------------------- #
# the operations
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("d", [8, 32, 128])
def test_expansion_inner_product_is_the_squared_dot(d):
    """``phi(q) . phi(k) = (q . k)^2`` for the stored tiled layout and for
    the reference's exact one, whose rows are the ``d (d + 1) / 2``
    distinct products; the tiled layout has ``8,704`` rows at ``d = 128``."""
    rng = np.random.default_rng(d)
    q = jnp.asarray(rng.normal(size=(5, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(5, d)), jnp.float32)
    want = np.asarray(jnp.sum(q * k, -1)) ** 2
    inner = jax.jit(lambda phi, a, b: jnp.sum(phi(a) * phi(b), -1),
                    static_argnums=0)
    np.testing.assert_allclose(inner(pr.expand, q, k), want, rtol=1e-5,
                               atol=1e-4)
    np.testing.assert_allclose(inner(ref.phi_exact, q, k), want, rtol=1e-5,
                               atol=1e-4)
    assert jax.eval_shape(pr.expand, q).shape[-1] == pr.expanded_rows(d)
    assert jax.eval_shape(ref.phi_exact, q).shape[-1] == pr.exact_rows(d)
    assert (pr.expanded_rows(128), pr.exact_rows(128)) == (8704, 8256)


def _step_inputs(S=5, G=2, hpg=3, d=32, L=2, seed=0, dtype=jnp.float32,
                 tokens=0):
    """A state of random entries; or, with ``tokens``, the state that many
    earlier tokens of a stream leave (``S = sum v phi(k)^T``, ``z = sum
    phi(k)``), whose readout's denominator is a sum of squares: at the
    expansion's 8,704 rows random entries would cancel in it, and two
    float32 orders of one sum would part by more than rounding."""
    rng = np.random.default_rng(seed)
    E = pr.expanded_rows(d)
    if tokens:
        ks = jnp.asarray(rng.normal(size=(L, S, G, tokens, d)), jnp.float32)
        vs = jnp.asarray(rng.normal(size=(L, S, G, tokens, d)), jnp.float32)
        phi = pr.expand(ks)
        state = jnp.einsum("lsgjv,lsgjE->lsgvE", vs, phi,
                           precision=jax.lax.Precision.HIGHEST)
        z = jnp.sum(phi, axis=3)
    else:
        state = jnp.asarray(rng.normal(size=(L, S, G, d, E)), jnp.float32)
        z = jnp.asarray(rng.uniform(size=(L, S, G, E)), jnp.float32) * 10
    q = jnp.asarray(rng.normal(size=(S, G * hpg, d)), dtype)
    k = jnp.asarray(rng.normal(size=(S, G, d)), dtype)
    v = jnp.asarray(rng.normal(size=(S, G, d)), dtype)
    la = jnp.asarray(-rng.uniform(size=(S, G)) * 0.1, jnp.float32)
    return state, z, q, k, v, la


_LIVE = {"mixed": [1, 0, 1, 1, 0], "leading-retired": [0, 0, 1, 0, 1],
         "none": [0, 0, 0, 0, 0], "all": [1, 1, 1, 1, 1]}
_DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16}
# (live slots, dtype, widths): the small cases, and the cell's widths (heads
# of 128, 8 KV heads of five query heads) over three slots, one not live
_STEP_CASES = {f"{dn}-{ln}": (live, dt, {})
               for dn, dt in _DTYPES.items() for ln, live in _LIVE.items()}
_STEP_CASES["published-bf16"] = ([1, 0, 1], jnp.bfloat16,
                                 dict(S=3, G=8, hpg=5, d=128, tokens=16))


@pytest.mark.parametrize("live,dtype,widths", list(_STEP_CASES.values()),
                         ids=list(_STEP_CASES))
def test_step_kernel_is_its_plain_form(monkeypatch, live, dtype, widths):
    """The Pallas kernel (interpreted), which builds ``phi`` of the slot's
    vectors in VMEM, against the ``jax.numpy`` form over ``expand`` at
    layer 1 of two: the same readouts and the same new state and ``z`` for
    the live slots; a retired slot, and the other layer, keep theirs."""
    state, z, q, k, v, la = _step_inputs(dtype=dtype, **widths)
    live = jnp.asarray(live, bool)
    call = jax.jit(lambda *a: pr.state_update(*a, 1e-6))
    y0, s0, z0 = call(state, z, jnp.int32(1), q, k, v, la, live)
    monkeypatch.setenv("MXNET_FLASH_INTERPRET", "1")
    call = jax.jit(lambda *a: pr.state_update(*a, 1e-6))
    y1, s1, z1 = call(state, z, jnp.int32(1), q, k, v, la, live)
    np.testing.assert_allclose(y1, y0, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(s1, s0, rtol=1e-6, atol=1e-5)
    np.testing.assert_allclose(z1, z0, rtol=1e-6, atol=1e-5)
    np.testing.assert_array_equal(s1[0], state[0])
    np.testing.assert_array_equal(s1[1][~live], state[1][~live])
    np.testing.assert_array_equal(z1[1][~live], z[1][~live])
    assert np.all(np.asarray(y1)[~live] == 0)
    if live.any():
        assert np.abs(np.asarray(s1[1][live] - state[1][live])).max() > 1e-3


@pytest.mark.parametrize("d", [32, 128])
def test_step_kernel_phi_is_expand(d):
    """The step kernel's ``phi`` of a slot's query heads and keys, built in
    VMEM from the packed block (``_phi_block``, interpreted): ``expand``'s
    rows, each vector at its group's row.  ``expand`` multiplies the product
    by ``sqrt(2)``, the kernel one factor: each order rounds twice, so a row
    may differ by 2 ulp (about one product in 250 of normal entries)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    G, hpg = 8, 5
    rng = np.random.default_rng(d)
    q = jnp.asarray(rng.normal(size=(1, G * hpg, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, G, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, G, d)), jnp.float32)
    la = jnp.asarray(-rng.uniform(size=(1, G)), jnp.float32)
    x = pr._packed(q, k, v, la)
    R, W = x.shape[1:]
    P, t, E = pr._rows_per_group(hpg), d // 8, pr.expanded_rows(d)
    phi = pl.pallas_call(
        lambda x_ref, o_ref, xt_ref, rows_ref: pr._phi_block(
            x_ref, xt_ref, rows_ref, o_ref, t=t),
        out_shape=jax.ShapeDtypeStruct((G, P, E), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, W // 8, 8, R), jnp.float32),
                        pltpu.VMEM((32 * t, R), jnp.float32)],
        interpret=True)(x)
    np.testing.assert_array_max_ulp(phi[:, :hpg],
                                    pr.expand(q.reshape(G, hpg, d)), 2)
    np.testing.assert_array_max_ulp(phi[:, hpg], pr.expand(k[0]), 2)


def _attention(q, k, v, la, s0=None, z0=None, eps=1e-6):
    """The attention form of one row, plus what a state ``(s0, z0)`` at
    position -1 adds, decayed (read through the tiled expansion)."""
    T, H, _ = q.shape
    G = k.shape[1]
    cum = np.cumsum(np.asarray(la, np.float64), 0)
    out = np.zeros(q.shape[:2] + (v.shape[-1],))
    for t in range(T):
        for h in range(H):
            g = h // (H // G)
            w = (np.asarray(k[:t + 1, g], np.float64)
                 @ np.asarray(q[t, h], np.float64)) ** 2 \
                * np.exp(cum[t, g] - cum[:t + 1, g])
            num = w @ np.asarray(v[:t + 1, g], np.float64)
            den = w.sum()
            if s0 is not None:
                p = np.asarray(pr.expand(q[t, h]), np.float64)
                num = num + np.exp(cum[t, g]) * (np.asarray(s0[g]) @ p)
                den = den + np.exp(cum[t, g]) * (np.asarray(z0[g]) @ p)
            out[t, h] = num / (den + eps)
    return out


def _scan_inputs(T, G=2, hpg=2, d=16, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(1, T, G * hpg, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, T, G, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, T, G, d)), jnp.float32)
    la = jnp.asarray(-rng.uniform(size=(1, T, G)) * 0.3, jnp.float32)
    return q, k, v, la


def _zero_state(G, d):
    E = pr.expanded_rows(d)
    return jnp.zeros((1, G, d, E)), jnp.zeros((1, G, E))


@pytest.mark.parametrize("T", [1, 7, 8, 19], ids=lambda t: f"T{t}")
def test_chunk_scan_is_the_attention_form(T):
    """Chunks of 8 at lengths that are and are not its multiples, from zero:
    every position's readout against the attention form."""
    q, k, v, la = _scan_inputs(T)
    s0, z0 = _zero_state(2, 16)
    with jax.default_matmul_precision("highest"):
        y, _, _ = pr.chunk_scan(q, k, v, la, s0, z0, jnp.asarray([T]), 8,
                                1e-6)
    np.testing.assert_allclose(y[0], _attention(q[0], k[0], v[0], la[0]),
                               rtol=2e-4, atol=2e-5)


def test_chunk_scan_from_a_state_and_its_final_state():
    """Nineteen tokens from a nonzero state: the readouts add what the state
    holds, decayed; the final state, read after one more zero-key token, is
    the state a second scan starts from."""
    q, k, v, la = _scan_inputs(19, seed=3)
    rng = np.random.default_rng(4)
    E = pr.expanded_rows(16)
    s0 = jnp.asarray(rng.normal(size=(1, 2, 16, E)), jnp.float32)
    z0 = jnp.asarray(rng.uniform(size=(1, 2, E)) * 5, jnp.float32)
    with jax.default_matmul_precision("highest"):
        y, s1, z1 = pr.chunk_scan(q, k, v, la, s0, z0, jnp.asarray([19]), 8,
                                  1e-6)
        ya, sa, za = pr.chunk_scan(q[:, :11], k[:, :11], v[:, :11],
                                   la[:, :11], s0, z0, jnp.asarray([11]), 8,
                                   1e-6)
        yb, sb, zb = pr.chunk_scan(q[:, 11:], k[:, 11:], v[:, 11:],
                                   la[:, 11:], sa, za, jnp.asarray([8]), 8,
                                   1e-6)
    want = _attention(q[0], k[0], v[0], la[0], s0[0], z0[0])
    np.testing.assert_allclose(y[0], want, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(jnp.concatenate([ya, yb], 1), y, rtol=2e-4,
                               atol=2e-5)
    np.testing.assert_allclose(sb, s1, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(zb, z1, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("count", [3, 8, 13])
def test_padding_leaves_the_state_at_the_true_length(count):
    """A row of 13 columns of which ``count`` are true: its state and ``z``
    are those after ``count`` tokens."""
    q, k, v, la = _scan_inputs(13, seed=1)
    s0, z0 = _zero_state(2, 16)
    with jax.default_matmul_precision("highest"):
        _, s1, z1 = pr.chunk_scan(q, k, v, la, s0, z0, jnp.asarray([count]),
                                  8, 1e-6)
        _, s2, z2 = pr.chunk_scan(q[:, :count], k[:, :count], v[:, :count],
                                  la[:, :count], s0, z0,
                                  jnp.asarray([count]), 8, 1e-6)
    np.testing.assert_allclose(s1, s2, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(z1, z2, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("d,T,dtype", [(32, 19, jnp.float32),
                                       (32, 40, jnp.bfloat16),
                                       (128, 9, jnp.float32)],
                         ids=["d32-f32", "d32-bf16", "d128-f32"])
def test_prefill_kernels_are_their_plain_form(monkeypatch, d, T, dtype):
    """Prefill's two kernels (interpreted), ``S_0 phi(Q)^T`` and ``V phi(c
    K)``, against ``expand`` and ``einsum``: two rows from a state that is
    not zero, one of them right-padded, the readouts, the final state and
    ``z``."""
    rng = np.random.default_rng(d + T)
    G, hpg = 2, 3
    E = pr.expanded_rows(d)
    q = jnp.asarray(rng.normal(size=(2, T, G * hpg, d)) * 0.5, dtype)
    k = jnp.asarray(rng.normal(size=(2, T, G, d)) * 0.5, dtype)
    v = jnp.asarray(rng.normal(size=(2, T, G, d)), dtype)
    la = jnp.asarray(-rng.uniform(size=(2, T, G)) * 0.2, jnp.float32)
    s0 = jnp.asarray(rng.normal(size=(2, G, d, E)), jnp.float32)
    z0 = jnp.asarray(rng.uniform(size=(2, G, E)) * 5, jnp.float32)
    count = jnp.asarray([T, T - 3])
    assert pr.prefill_kernels(d)
    outs = []
    for interpret in ("0", "1"):
        monkeypatch.setenv("MXNET_FLASH_INTERPRET", interpret)
        scan = jax.jit(lambda *a: pr.chunk_scan(*a, count, 8, 1e-6))
        with jax.default_matmul_precision("highest"):
            outs.append(scan(q, k, v, la, s0, z0))
    for plain, kernel in zip(*outs):
        scale = float(jnp.max(jnp.abs(plain)))
        np.testing.assert_allclose(kernel, plain, rtol=0,
                                   atol=3e-5 * scale)


# --------------------------------------------------------------------------- #
# the model
# --------------------------------------------------------------------------- #

def test_full_forward_matches_reference(tiny):
    """The model's forward (the engine's fresh-pool form: one prefill of
    every position) against the reference's attention form."""
    net, cfg, w, rcfg = tiny
    toks = _tokens(21, seed=4)
    got = np.asarray(net(mx.nd.array(toks[None], dtype="int32"))._data)[0]
    np.testing.assert_allclose(got, _ref_logits(w, rcfg, toks), atol=TOL,
                               rtol=0)


def _server(net, **over):
    kw = dict(max_total_len=64, pool_sizes=(2,), admit_sizes=(1, 2),
              prefill_buckets=(8, 16), spec=False, autostart=False)
    kw.update(over)
    return serve.DecodeServer(net, **kw)


def _drain(srv, streams):
    for _ in range(400):
        if all(s.done for s in streams):
            break
        srv.pump()
    return [s.tokens(timeout=0) for s in streams]


@pytest.mark.parametrize("interpret", [False, True],
                         ids=["plain", "kernel-interpreted"])
def test_served_logits_match_reference(tiny, monkeypatch, interpret):
    """Five requests through two slots — a wave, chunked prompts (21 and 30
    tokens against buckets of 16), every slot reused, so its state is reset
    for the next tenant: each served token is the reference's first choice
    at its position (teacher-forced), by a margin that rounding does not
    reach; the slot's final state read back agrees with the reference's sum
    form; no page is reserved."""
    if interpret:
        monkeypatch.setenv("MXNET_FLASH_INTERPRET", "1")
    net, cfg, w, rcfg = tiny
    srv = _server(net)
    prompts = [_tokens(n, seed=30 + i)
               for i, n in enumerate([5, 21, 13, 30, 3])]
    streams = [srv.submit(p, max_new_tokens=10) for p in prompts]
    got = _drain(srv, streams)
    while srv.pump():       # the last readbacks: the server goes idle
        pass
    stats = srv.stats()
    tenants = [srv.slot_state(s) for s in range(2)]
    srv.close()
    for p, g in zip(prompts, got):
        z = _ref_logits(w, rcfg, np.concatenate([p, g[:-1]]))[len(p) - 1:]
        served = z[np.arange(len(g)), np.asarray(g)]
        assert np.all(z.max(-1) - served <= TOL)
    assert stats["state_resets"] == 5 and stats["prefix_cache"] is False
    assert stats["slot_kinds"] == ["retention_state"]
    assert stats["pages_total"] == 0 and stats["pages_in_use"] == 0
    c = stats["counters"]
    assert c["chunk_dispatches"] >= 4
    assert c["admit_tokens"] == sum(p.size for p in prompts)
    # the chunks past a prompt's first 16 tokens read the state they left
    assert c["chunk_carried_tokens"] == (21 - 16) + (30 - 16)
    E = pr.expanded_rows(cfg.head_dim)
    assert stats["state_bytes_per_slot"] == cfg.num_hidden_layers * 2 * (
        32 * E + E) * 4
    # the final state of each slot's last tenant, read by probes
    ids = {s.request_id: (p, g) for s, p, g in zip(streams, prompts, got)}
    probes = jnp.asarray(np.random.default_rng(5).normal(
        size=(cfg.num_hidden_layers, 2, 3, cfg.head_dim)), jnp.float32)
    for rid, (state, zz) in tenants:
        p, g = ids[rid]
        _, read = ref.full_logits(w, rcfg, jnp.asarray(
            np.concatenate([p, g[:-1]])), probes=probes)
        mine = [(jnp.einsum("gnE,gvE->gnv", pr.expand(probes[j]), state[j]),
                 jnp.einsum("gnE,gE->gn", pr.expand(probes[j]), zz[j]))
                for j in range(cfg.num_hidden_layers)]
        assert ref.state_error(read, mine) < 1e-4


def test_slot_state_waits_for_an_idle_server(tiny):
    srv = _server(tiny[0])
    srv.submit(_tokens(5), max_new_tokens=4)
    with pytest.raises(MXNetError, match="idle"):
        srv.slot_state(0)
    srv.close(drain=False)


@pytest.mark.parametrize("what,kwargs,names", [
    ("prefix", dict(prefix_cache=True), "retention_state"),
    ("spec", dict(spec=True), "retention_state"),
    ("pools", dict(pool_sizes=(2, 4)), "one pool size"),
])
def test_unsupported_options_are_refused_loudly(tiny, what, kwargs, names):
    net = tiny[0]
    with pytest.raises(MXNetError, match=names):
        srv = _server(net, **kwargs)
        srv.close()
        raise MXNetError(srv.sync_reason or "served")


@pytest.mark.parametrize("key,value", [
    ("use_sliding_window", True), ("sliding_window", 4096),
    ("tie_word_embeddings", True)])
def test_from_hf_refuses_what_it_does_not_implement(key, value):
    hf = dict(hidden_size=64, num_hidden_layers=2, vocab_size=96,
              max_position_embeddings=128, **{key: value})
    with pytest.raises(ValueError, match=key):
        bm.BrumbyConfig.from_hf(hf)


def test_description_and_one_run(tiny):
    net, cfg = tiny[0], tiny[1]
    desc = net.decode_description()
    assert [d["cache"] for d in desc] == ["retention_state"] * 2
    assert desc[0]["attn"]["kind"] == "retention"
    eng = layered.LayeredEngine(net, 1, 1, 16)
    assert eng.runs == [(0, 2)] and eng.main_page_bytes(16) == 0
    assert eng.slot_kinds == ["retention_state"]
    full = bm.BrumbyConfig()
    shapes = bm.parameter_shapes(full)
    per_layer = sum(int(np.prod(s[1:])) for n, (s, _) in shapes.items()
                    if n.startswith("r0_"))
    # 330.3M of matrices and 10k of norm gains a layer
    assert abs(per_layer / 1e6 - 330.35) < 0.01


def test_gate_init_decays_lie_in_the_band(tiny):
    """The sink construction of ``weights_brumby``: over a stream, the
    decays' median across the KV heads lies between 0.9 and 0.9999, each
    head's near ``sigmoid(gate_logits)``."""
    net, cfg, w, rcfg = tiny
    a = np.asarray(ref.decays(w, rcfg, jnp.asarray(_tokens(48, seed=9))))
    assert 0.9 < np.median(a) < 0.9999
    want = 1 / (1 + np.exp(-np.asarray(INIT["gate_logits"])))
    np.testing.assert_allclose(np.median(a, axis=(0, 1)), want, atol=0.03)
