"""The kind-driven decode engine: serves a model from the per-layer
description it exports (``model.decode_description()``: attention kind,
feed-forward kind, cache kind, each with its sizes) instead of from a
family name.  ``models.decoding.decode_engine`` picks it for every model
whose layers are not all the uniform K/V kind, which stays on
``_DecodeEngine``'s stacked scan.

Kinds implemented here:

- attention ``latent_sparse``: latent attention in the absorbed form over
  the ``topk`` positions an indexer selects (indexer scores over the paged
  index keys, an exact top-k WITHOUT a sort — ``top_positions`` —, a gather of
  the selected latent rows through the page table); cache kind ``latent_index``: a latent row
  ``[c_kv | k_rope]`` and an index-key row under the MAIN page table;
- attention ``latent_window``: latent attention over the last ``window``
  positions; cache kind ``latent_window``: one latent row under the WINDOW
  page table, a ring of ``ring`` entries a slot indexed by ``(position //
  page) % ring``, so a slot holds pages for its window only;
- feed-forward ``swiglu`` and ``routed`` (``ops.moe``).

Every row is stored in whole 128-lane tiles (``serve.schema.row_lanes``):
a 576- or 1088-wide minor dimension would make the chip re-lay the pool out
for every consumer (PERF.md, PR 27).

ONE function, ``tokens_paged``, runs ``C`` tokens a row for ``B`` rows
against the pools: the pool step is ``(S, 1)``, a prefill chunk ``(1, C)``,
an admission wave ``(A, P)`` at offset 0, and the model's own ``forward``
the same with identity tables.  New rows are written through the tables
first and read back with everything else, so a query always finds its own
position.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..ops import moe
from ..serve.schema import row_lanes

__all__ = ["LayeredEngine", "top_mask", "mask_positions", "top_positions"]

# the indexer's scores of one head block may take this many bytes
_INDEX_BLOCK_BYTES = 512 << 20


def _rms(x, gamma, eps, scale=1.0):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True)
                            + eps)
    return (y * gamma.astype(jnp.float32) * scale).astype(x.dtype)


def _layer_norm(x, gamma, beta, eps):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean((x32 - mu) ** 2, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * gamma + beta).astype(x.dtype)


def _rope(x, pos, theta):
    """Rotate consecutive (even, odd) pairs of the last axis of ``x``
    ``(B, C, [heads,] d)`` by the angles of positions ``pos`` ``(B, C)``."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(d // 2, dtype=jnp.float32) * 2.0 / d)
    ang = pos.astype(jnp.float32)[..., None] * inv
    if x.ndim == 4:
        ang = ang[:, :, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., 0::2], x32[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _dot(x, w):
    return jnp.dot(x, w, preferred_element_type=jnp.float32).astype(x.dtype)


def _pad_last(x, width):
    pad = width - x.shape[-1]
    return x if pad == 0 else jnp.pad(
        x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def _table_pages(table, lp, sentinel):
    """Page ids of logical pages ``lp`` ``(B, n)`` out of ``table``
    ``(B, W)``; a logical page outside the table reads the sentinel."""
    W = table.shape[1]
    pg = jnp.take_along_axis(table, jnp.clip(lp, 0, W - 1), axis=1)
    return jnp.where((lp >= 0) & (lp < W), pg, sentinel)


def _sortable(x):
    """float32 -> uint32 whose unsigned order is the floats' order."""
    u = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(u >> 31 == 1, ~u, u | jnp.uint32(0x80000000))


def top_mask(score, valid, k):
    """Which ``k`` positions of every row of ``score`` ``(N, T)`` are its
    largest among ``valid``: ``(N, T)`` bool with exactly ``k`` set, EXACT
    (ties broken towards the earlier position, as a stable sort would),
    without sorting.  Where a row has fewer than ``k`` valid positions the
    rest of its ``k`` are its earliest invalid ones; the caller masks them.

    1. the k-th largest value by bisection on the bits: 32 counts of
       ``key >= mid`` over the row;
    2. the selected set: every key above it, and of those equal to it the
       earliest ones up to ``k``."""
    N, T = score.shape
    keys = jnp.where(valid, _sortable(score), jnp.uint32(0))

    def halve(_, lohi):
        lo, hi = lohi
        mid = lo + (hi - lo) // 2 + (hi - lo) % 2      # no overflow
        enough = jnp.sum(keys >= mid[:, None], axis=1) >= k
        return jnp.where(enough, mid, lo), jnp.where(enough, hi, mid - 1)

    kth, _ = jax.lax.fori_loop(
        0, 32, halve, (jnp.zeros((N,), jnp.uint32),
                       jnp.full((N,), 0xFFFFFFFF, jnp.uint32)))
    above = keys > kth[:, None]
    equal = keys == kth[:, None]
    room = k - jnp.sum(above, axis=1, keepdims=True)
    return above | (equal & (jnp.cumsum(equal, axis=1) <= room))


def mask_positions(chosen, k):
    """The positions of the ``k`` set entries of every row of ``chosen``
    ``(N, T)``, ascending, by blocks of 128: the block of the j-th from the
    blocks' running counts, then its place inside that block from the
    block's own mask (one gather of ``(N, k)`` mask rows; the running count
    inside a block is a product with a triangle of ones)."""
    N, T = chosen.shape
    W = 128
    nb = -(-T // W)
    blocks = jnp.pad(chosen, ((0, 0), (0, nb * W - T))).reshape(N, nb, W)
    count = jnp.sum(blocks, axis=-1, dtype=jnp.int32)           # (N, nb)
    before = jnp.cumsum(count, axis=-1) - count
    j = jnp.arange(k, dtype=jnp.int32)
    blk = jnp.sum(before[:, None, :] + count[:, None, :] <= j[None, :, None],
                  axis=-1, dtype=jnp.int32)                     # (N, k)
    blk = jnp.minimum(blk, nb - 1)
    nth = j[None] - jnp.take_along_axis(before, blk, axis=1)    # (N, k)
    rows = jnp.take_along_axis(blocks, blk[..., None], axis=1)  # (N, k, W)
    tri = jnp.triu(jnp.ones((W, W), jnp.bfloat16))
    # counts up to 128 are exact in bfloat16
    running = jnp.einsum("nkw,wv->nkv", rows.astype(jnp.bfloat16), tri,
                         preferred_element_type=jnp.bfloat16)
    here = rows & (running == (nth + 1)[..., None].astype(jnp.bfloat16))
    return blk * W + jnp.argmax(here, axis=-1).astype(jnp.int32)


def top_positions(score, valid, k):
    """``mask_positions`` of ``top_mask``: the positions of the ``k``
    largest, ascending.  (The chip's ``lax.top_k`` is a full sort of every
    row; at a few dozen rows the two cost the same, 1.3-1.5 ms for 32 rows
    of 33,152, but this one's set comes first and alone where nothing needs
    the positions: PERF.md, PR 29.)"""
    return mask_positions(top_mask(score, valid, k), k)


class LayeredEngine:
    """The decode programs' bodies for a model that exports a per-layer
    description.  Same face as ``_DecodeEngine`` where
    ``serve.engine.PoolPrograms`` touches it."""

    mode = "layered"
    # rows of queries from which the selecting attention takes its DENSE
    # form: every cached row of the slot scored, masked to the selected set
    # (no positions, no row gather).  Below it, the gather form: 2048 rows
    # a query through the page table.  On the chip a 512-query chunk's
    # gather form spends 42 ms a layer finding and fetching rows, its dense
    # form 41 ms over ALL 33,152 positions and in proportion over fewer
    # (``key_pages``); a decode step's 32 queries are far better off
    # gathering (PERF.md, PR 29)
    dense_chunk = 256

    def __init__(self, model, B, P, total, temperature=0.0, top_k=0,
                 prefill="batched", weights="native"):
        if weights != "native":
            from ..base import MXNetError
            raise MXNetError("the layered decode engine serves native "
                             f"weights only, not {weights!r}")
        self.model, self.cfg = model, model._cfg
        self.desc = model.decode_description()
        self.B, self.P, self.total = B, P, total
        self.temperature, self.top_k = temperature, top_k
        self.params = [p for p in model.collect_params().values()
                       if p._data is not None]
        self.param_vals = [p._data._data for p in self.params]
        self.NL = len(self.desc)
        self.cdtype = jnp.dtype(self.cfg.dtype)
        self.full = [i for i, d in enumerate(self.desc)
                     if d["cache"] == "latent_index"]
        self.win = [i for i, d in enumerate(self.desc)
                    if d["cache"] == "latent_window"]
        # stored row widths, in whole lane tiles
        self.rows = {}
        if self.full:
            a = self.desc[self.full[0]]["attn"]
            self.rows["latent"] = row_lanes(a["kv_rank"] + a["rope"])
            self.rows["index_key"] = row_lanes(a["index_dim"])
        if self.win:
            a = self.desc[self.win[0]]["attn"]
            self.rows["window_latent"] = row_lanes(a["kv_rank"]
                                                   + a["rope"])
            self.window = int(a["window"])
        else:
            self.window = None

    # -- what serve.engine.PoolPrograms reads --------------------------- #
    def take_operands(self):
        operands = (self.param_vals, None, None)
        self.param_vals = None
        return operands

    def _sample_logits(self, logits):
        from .decoding import _DecodeEngine
        return _DecodeEngine._sample_logits(self, logits)

    def window_back_pages(self, page):
        """Whole pages behind a query's own that its window can reach."""
        return -(-(self.window - 1) // page)

    def window_span_pages(self, page, C):
        """Pages a row of ``C`` queries reads of the window pool."""
        return self.window_back_pages(page) + 1 + \
            (0 if C == 1 else (C - 2) // page + 1)

    def main_page_bytes(self, page):
        """Bytes of one main-table page over every layer that has one."""
        w = self.rows.get("latent", 0) + self.rows.get("index_key", 0)
        return len(self.full) * page * w * self.cdtype.itemsize

    def window_page_bytes(self, page):
        return len(self.win) * page * self.rows.get("window_latent", 0) \
            * self.cdtype.itemsize

    def pool_zeros(self, num_pages, window_pages, page):
        """``(kp, vp)``: the main-table pools ``(latent, index key)`` and
        the window-table pool, each ``(layers, pages, page, lanes)``."""
        z = lambda n, p, w: jnp.zeros((n, p, page, w), self.cdtype)
        kp = (z(len(self.full), num_pages, self.rows["latent"]),
              z(len(self.full), num_pages, self.rows["index_key"]))
        vp = z(len(self.win), window_pages, self.rows["window_latent"])
        return kp, vp

    def cache_bytes(self):
        return 0

    def step_counters(self, aux, active):
        """A step's counters reduced over the live slots: tokens each held
        expert of each routed layer got, keys the indexer selected and the
        queries that selected them."""
        out = {}
        if "expert" in aux:
            n = next(d["ffn"]["held"][1] for d in self.desc
                     if d["ffn"]["kind"] == "routed")
            hit = (aux["expert"][..., None] == jnp.arange(n)) \
                & active[None, :, None, None]
            out["expert_load"] = jnp.sum(hit, axis=(1, 2)).astype(jnp.int32)
        if "selected" in aux:
            sel = aux["selected"][:, :, 0]
            out["selected"] = jnp.sum(jnp.where(active[None], sel, 0))
            out["queries"] = jnp.sum(active) * sel.shape[0]
        return out

    # -- the programs' bodies ------------------------------------------- #
    def pool_token_paged(self, x_tok, pos, kp, vp, pt, page, sw=None,
                         q8=None):
        """The pool step: one token a slot.  Returns ``(logits, kp, vp,
        aux)``; ``aux`` holds per-slot counters the step reduces."""
        S = x_tok.shape[0]
        return self.tokens_paged(
            self.model.weights(), x_tok[:, None], pos, pt, (kp, vp),
            page, jnp.zeros((S,), jnp.int32))

    def chunk_tokens(self, toks, off, nlast, ptrow, page, kp, vp, sw=None,
                     q8=None, key_pages=None):
        """``C`` tokens of one slot at offset ``off``; ``key_pages`` bounds
        the main-table pages the chunk can reach (its last position's)."""
        tables = tuple(t[None] for t in ptrow)
        logits, kp, vp, _ = self.tokens_paged(
            self.model.weights(), toks[None], off[None], tables, (kp, vp),
            page, nlast[None], key_pages=key_pages)
        return logits, kp, vp

    def admit_tokens(self, prompts, last, tables, page, kp, vp):
        """An admission wave: ``(A, P)`` right-padded prompts from offset
        0 through each row's own table rows; only the pages a prompt of
        ``P`` tokens can reach are read."""
        A, P = prompts.shape
        logits, kp, vp, _ = self.tokens_paged(
            self.model.weights(), prompts, jnp.zeros((A,), jnp.int32),
            tables, (kp, vp), page, last, key_pages=-(-P // page))
        return logits, kp, vp

    def forward_dense(self, w, toks):
        """The full causal pass, logits at every position: the same code
        over fresh pools with identity tables."""
        B, L = toks.shape
        page = 16
        npg = -(-L // page)
        ids = jnp.arange(B * npg, dtype=jnp.int32).reshape(B, npg)
        pools = self.pool_zeros(B * npg, B * npg, page)
        logits, _, _, _ = self.tokens_paged(
            w, toks, jnp.zeros((B,), jnp.int32), (ids, ids), pools, page,
            None)
        return logits

    @jax.named_scope("mx.dense")
    def tokens_paged(self, w, toks, off, tables, pools, page, last,
                     key_pages=None):
        """``toks`` ``(B, C)`` at positions ``off[b] + c`` through
        ``tables = (main (B, MAXP), window (B, ring))`` against ``pools =
        ((latent, index key), window latent)``.  Returns ``(logits (B, V)
        float32 at column last[b] — every column, (B, C, V), when ``last``
        is None —, kp, vp, aux)``."""
        cfg = self.cfg
        (lat, ikp), wlat = pools
        ptm, ptw = tables
        B, C = toks.shape
        pos = off[:, None] + jnp.arange(C, dtype=jnp.int32)[None]
        lp, row = pos // page, pos % page
        x = w["wte"][toks]
        aux = {"expert": [], "selected": []}
        fi = wi = 0
        for i, d in enumerate(self.desc):
            lw, a = w["layers"][i], d["attn"]
            h = _rms(x, lw["norm1_gamma"], cfg.rms_norm_eps)
            q_nope, q_rope, new_row, cq = self._latent_qkv(lw, a, h, pos)
            if a["kind"] == "latent_sparse":
                with jax.named_scope("mx.index"):
                    iq, ik_row, iw = self._index_qkw(lw, a, h, cq, pos)
                with jax.named_scope("mx.latent_write"):
                    pg = _table_pages(ptm, lp, lat.shape[1])
                    lat = lat.at[fi, pg, row].set(
                        _pad_last(new_row, lat.shape[-1]), mode="drop")
                    ikp = ikp.at[fi, pg, row].set(
                        _pad_last(ik_row, ikp.shape[-1]), mode="drop")
                kp_n = ptm.shape[1] if key_pages is None else key_pages
                reach = jnp.minimum(ptm[:, :kp_n], lat.shape[1] - 1)
                with jax.named_scope("mx.index"):
                    full, seen = self._select(a, iq, iw, ikp, fi, reach,
                                              pos, page)    # (B, C, T)
                    chosen = full & seen
                aux["selected"].append(jnp.sum(chosen, axis=-1))
                if C >= self.dense_chunk:
                    with jax.named_scope("mx.latent_gather"):
                        rows = lat.at[fi, reach].get(
                            mode="promise_in_bounds").reshape(
                                B, kp_n * page, -1)         # (B, T, W)
                    with jax.named_scope("mx.latent_attn"):
                        o = self._attend_dense(lw, a, q_nope, q_rope, rows,
                                               chosen)
                else:
                    K = min(int(a["topk"]), kp_n * page)
                    with jax.named_scope("mx.index"):
                        sel = mask_positions(full.reshape(B * C, -1),
                                             K).reshape(B, C, K)
                        # fewer than K seen: the rest point past ``pos``
                        ok = jnp.take_along_axis(seen, sel, axis=2)
                    with jax.named_scope("mx.latent_gather"):
                        pgs = jnp.take_along_axis(reach[:, None, :],
                                                  sel // page, axis=2)
                        rows = lat.at[fi, pgs, sel % page].get(
                            mode="promise_in_bounds")       # (B, C, K, W)
                    with jax.named_scope("mx.latent_attn"):
                        o = self._attend(lw, a, q_nope, q_rope, rows, ok,
                                         "bchf,bckf->bchk",
                                         "bchk,bckr->bchr")
                fi += 1
            else:
                with jax.named_scope("mx.latent_write"):
                    ring = ptw.shape[1]
                    pg = jnp.take_along_axis(ptw, lp % ring, axis=1)
                    wlat = wlat.at[wi, pg, row].set(
                        _pad_last(new_row, wlat.shape[-1]), mode="drop")
                with jax.named_scope("mx.window_attn"):
                    rows, ok = self._window_rows(a, wlat, wi, ptw, off,
                                                 pos, page, C)
                    o = self._attend(lw, a, q_nope, q_rope, rows, ok,
                                     "bchf,btf->bcht", "bcht,btr->bchr")
                wi += 1
            gate = jax.nn.sigmoid(jnp.dot(
                h, lw["gate_weight"], preferred_element_type=jnp.float32))
            o = (o.astype(jnp.float32) * gate[..., None]).astype(x.dtype)
            x = x + _dot(o.reshape(B, C, -1), lw["o_weight"])
            h = _rms(x, lw["norm2_gamma"], cfg.rms_norm_eps)
            y, eidx = self._ffn(lw, d["ffn"], h.reshape(B * C, -1))
            if eidx is not None:
                aux["expert"].append(eidx)
            x = x + y.reshape(B, C, -1)
        with jax.named_scope("mx.head"):
            if last is not None:
                x = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
            logits = jnp.dot(_rms(x, w["normf"], cfg.rms_norm_eps),
                             w["head"],
                             preferred_element_type=jnp.float32)
        aux = {k: jnp.stack(v) for k, v in aux.items() if v}
        return logits, (lat, ikp), wlat, aux

    # -- attention ------------------------------------------------------ #
    def _latent_qkv(self, lw, a, h, pos):
        """Queries ``(B, C, heads, nope | rope)``, the cache row ``[c_kv |
        k_rope]`` of every token, and the query latent (the indexer reads
        it too)."""
        cfg = self.cfg
        B, C, H = h.shape
        rq, r = a["q_rank"], a["kv_rank"]
        rescale = cfg.apply_mla_qkv_lora_rescale
        cq = _rms(_dot(h, lw["qa_weight"]), lw["qnorm_gamma"],
                  cfg.rms_norm_eps, (H / rq) ** 0.5 if rescale else 1.0)
        q = _dot(cq, lw["qb_weight"]).reshape(
            B, C, a["heads"], a["nope"] + a["rope"])
        q_nope, q_rope = q[..., :a["nope"]], q[..., a["nope"]:]
        q_rope = _rope(q_rope, pos, a["theta"])
        kva = _dot(h, lw["kva_weight"])
        ckv = _rms(kva[..., :r], lw["kvnorm_gamma"], cfg.rms_norm_eps,
                   (H / r) ** 0.5 if rescale else 1.0)
        kr = _rope(kva[..., r:], pos, a["theta"])
        return q_nope, q_rope, jnp.concatenate([ckv, kr], axis=-1), cq

    def _index_qkw(self, lw, a, h, cq, pos):
        B, C, _ = h.shape
        J, dI, dr = a["index_heads"], a["index_dim"], a["rope"]
        iq = _dot(cq, lw["iq_weight"]).reshape(B, C, J, dI)
        iq = jnp.concatenate([_rope(iq[..., :dr], pos, a["theta"]),
                              iq[..., dr:]], axis=-1)
        ik = _layer_norm(_dot(h, lw["ik_weight"]), lw["iknorm_gamma"],
                         lw["iknorm_beta"], self.cfg.index_norm_eps)
        ik = jnp.concatenate([_rope(ik[..., :dr], pos, a["theta"]),
                              ik[..., dr:]], axis=-1)
        iw = jnp.dot(h, lw["iw_weight"],
                     preferred_element_type=jnp.float32)
        return iq, ik, iw

    def _select(self, a, iq, iw, ikp, fi, ptm, pos, page):
        """Which positions every query attends to: the ``topk`` of largest
        indexer score among ``s <= pos``, all of them while there are
        fewer — ``(B, C, T)`` bool over the ``T`` positions the table rows
        ``ptm`` reach, with exactly ``topk`` set a query (the earliest
        unseen positions fill up a short one), and ``seen`` itself."""
        B, C, J, dI = iq.shape
        keys = ikp.at[fi, ptm].get(mode="promise_in_bounds")
        T = ptm.shape[1] * page
        keys = keys.reshape(B, T, -1)[..., :dI]

        def block(q, wj):
            s = jnp.einsum("bcjd,btd->bcjt", q, keys,
                           preferred_element_type=jnp.float32)
            return jnp.einsum("bcj,bcjt->bct", wj, jax.nn.relu(s))

        jb = max(1, min(J, _INDEX_BLOCK_BYTES // max(1, B * C * T * 4)))
        while J % jb:
            jb -= 1
        if jb == J:
            score = block(iq, iw)
        else:
            nb = J // jb
            qs = jnp.moveaxis(iq.reshape(B, C, nb, jb, dI), 2, 0)
            ws = jnp.moveaxis(iw.reshape(B, C, nb, jb), 2, 0)
            score, _ = jax.lax.scan(
                lambda acc, xs: (acc + block(*xs), None),
                jnp.zeros((B, C, T), jnp.float32), (qs, ws))
        seen = jnp.arange(T, dtype=jnp.int32)[None, None] <= pos[..., None]
        chosen = top_mask(score.reshape(B * C, T), seen.reshape(B * C, T),
                          min(int(a["topk"]), T)).reshape(B, C, T)
        return chosen, seen

    def _window_rows(self, a, wlat, wi, ptw, off, pos, page, C):
        """The window pool's rows a row of queries can reach, ``(B, T',
        W)``, and which of them each query may see, ``(B, C, T')``."""
        B, ring = ptw.shape
        n = self.window_span_pages(page, C)
        lps = (off // page - self.window_back_pages(page))[:, None] \
            + jnp.arange(n, dtype=jnp.int32)[None]
        pgs = jnp.take_along_axis(ptw, lps % ring, axis=1)
        pgs = jnp.where(lps >= 0, pgs, wlat.shape[1])
        rows = wlat.at[wi, jnp.minimum(pgs, wlat.shape[1] - 1)].get(
            mode="promise_in_bounds").reshape(B, n * page, -1)
        kpos = (lps[:, :, None] * page
                + jnp.arange(page, dtype=jnp.int32)[None, None]
                ).reshape(B, 1, n * page)
        p = pos[..., None]
        ok = (kpos <= p) & (kpos > p - a["window"]) & (kpos >= 0)
        return rows, ok

    def _attend_dense(self, lw, a, q_nope, q_rope, rows, chosen):
        """``_attend`` of every query against ALL the slot's rows ``(B, T,
        W)``, masked to ``chosen`` ``(B, C, T)``, a block of heads at a
        time so that the scores fit."""
        B, C, hh, _ = q_nope.shape
        T = rows.shape[1]
        hb = max(1, min(hh, _INDEX_BLOCK_BYTES // max(1, B * C * T * 4)))
        while hh % hb:
            hb -= 1
        wkv = lw["kvb_weight"].reshape(a["kv_rank"], hh // hb, hb,
                                       a["nope"] + a["v"])
        blocks = lambda q: jnp.moveaxis(
            q.reshape(B, C, hh // hb, hb, q.shape[-1]), 2, 0)
        out = jax.lax.map(
            lambda xs: self._attend(lw, a, xs[0], xs[1], rows, chosen,
                                    "bchf,btf->bcht", "bcht,btr->bchr",
                                    wkv=xs[2]),
            (blocks(q_nope), blocks(q_rope), jnp.moveaxis(wkv, 1, 0)))
        return jnp.moveaxis(out, 0, 2).reshape(B, C, hh, -1)

    def _attend(self, lw, a, q_nope, q_rope, rows, ok, scores, context,
                wkv=None):
        """Latent attention in the absorbed form: the queries are taken
        into the latent space (``W_kvb``'s key half), scored against the
        stored rows ``[c_kv | k_rope | 0]`` in one contraction, and the
        context comes back through ``W_kvb``'s value half (``wkv``: that
        matrix for the heads given, all of them by default)."""
        r, hh = a["kv_rank"], a["heads"]
        if wkv is None:
            wkv = lw["kvb_weight"].reshape(r, hh, a["nope"] + a["v"])
        qabs = jnp.einsum("bchd,rhd->bchr", q_nope, wkv[..., :a["nope"]],
                          preferred_element_type=jnp.float32
                          ).astype(q_nope.dtype)
        qf = _pad_last(jnp.concatenate([qabs, q_rope], axis=-1),
                       rows.shape[-1])
        s = jnp.einsum(scores, qf, rows,
                       preferred_element_type=jnp.float32)
        s = s * (1.0 / (a["nope"] + a["rope"]) ** 0.5)
        s = jnp.where(ok[:, :, None, :], s, -1e30)
        p = jax.nn.softmax(s, axis=-1).astype(rows.dtype)
        ctx = jnp.einsum(context, p, rows[..., :r],
                         preferred_element_type=jnp.float32
                         ).astype(rows.dtype)
        return jnp.einsum("bchr,rhv->bchv", ctx, wkv[..., a["nope"]:],
                          preferred_element_type=jnp.float32)

    # -- feed-forward --------------------------------------------------- #
    def _ffn(self, lw, f, h):
        """``(y (N, H), local expert ids (N, top_k) or None)``; an id
        outside ``[0, held)`` is an expert another chip holds."""
        if f["kind"] == "swiglu":
            return moe.swiglu(h, lw["gu_weight"], lw["down_weight"]), None
        lo, n = f["held"]
        with jax.named_scope("mx.moe_route"):
            idx, wts = moe.route(h, lw["router_weight"],
                                 lw["router_bias"], f["top_k"], f["scale"])
        with jax.named_scope("mx.moe_experts"):
            y, _ = moe.routed_experts(h, idx, wts, lw["egu_weight"],
                                      lw["edown_weight"], lo)
        with jax.named_scope("mx.moe_shared"):
            y = y + moe.swiglu(h, lw["sgu_weight"], lw["sdown_weight"])
        local = idx - lo
        return y, jnp.where((local >= 0) & (local < n), local, -1)
