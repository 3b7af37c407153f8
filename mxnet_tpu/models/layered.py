"""The kind-driven decode engine: serves a model from the per-layer
description it exports (``model.decode_description()``: attention kind,
feed-forward kind, cache kind, each with its sizes) instead of from a
family name.  ``models.decoding.decode_engine`` picks it for every model
whose layers are not all the uniform K/V kind, which stays on
``_DecodeEngine``'s stacked scan.

Kinds implemented here:

- attention ``latent_sparse``: latent attention in the absorbed form over
  the ``topk`` positions an indexer selects (indexer scores over the paged
  index keys — the single-query step walks them where they lie,
  ``ops.index_scores``; a prefill gathers their view —, an exact top-k
  WITHOUT a sort and its positions without a gather — ``top_positions``
  —, the selected positions' page ids by a one-hot product, not a gather
  — ``block_pages`` —, a gather of the selected latent rows); cache kind
  ``latent_index``: a latent row ``[c_kv | k_rope]`` and an index-key row
  under the MAIN page table;
- attention ``latent_window``: latent attention over the last ``window``
  positions; cache kind ``latent_window``: one latent row under the WINDOW
  page table, a ring of ``ring`` entries a slot indexed by ``(position //
  page) % ring``, so a slot holds pages for its window only;
- attention ``latent``: latent attention in the absorbed form over EVERY
  position ``s <= t`` (no indexer, no window); cache kind ``latent``: the
  latent row alone under the MAIN page table.  The single-query step walks
  each slot's pages in place (``ops.latent_attention``: one read of a row
  serves every head, as key and as value); a prefill reads the rows its
  queries can reach back through the table into the masked dense form
  (``_attend_dense``);
- attention ``ssm``: a Mamba-2 state-space mixer (``ops.ssd``); cache kind
  ``ssm_state`` under the SLOT table: the recurrent state and the
  convolution's tail, one entry a slot addressed by the slot's own index,
  updated in place at every token, never shared, never paged;
- attention ``retention``: degree-2 power retention (``ops.power_retention``:
  grouped-query projections with ``qk_norm`` and RoPE, a decay a token a KV
  head, the state of each KV head read by its group's query heads); cache
  kind ``retention_state`` under the SLOT table: the state ``S`` and its
  normaliser ``z``, one entry a slot, updated in place at every token by the
  kernel ``mx_retention_update``.  A model of such layers alone holds no
  pages;
- attention ``gqa``: grouped-query attention at the description's own
  ``scale``, by the description's optional keys: ``theta`` (RoPE on q and k;
  none without it: no positions at all), ``rope`` ``"halves"`` (the halves
  of a head rotated together, not consecutive pairs), ``qk_norm`` (an
  RMSNorm over each head of q and of k), ``gate`` (a sigmoid gate on the
  output, elementwise), ``window`` (keys ``t - window < s <= t``).  Cache
  kind ``kv``: a K row and a V row under the MAIN page table; with
  ``window``, cache kind ``kv_window``: the same two rows under the WINDOW
  table's ring.  The single-query step walks the pages in
  ``ops.paged_attention`` — the whole table row, or the ring from the
  window's first page —, a prefill reads the rows its queries can reach
  through a masked dense form;
- feed-forward ``swiglu`` and ``routed`` (``ops.moe``).

A layer of either body may declare ``post_norms`` (an RMSNorm AFTER each
sub-block, before it joins the stream: four norms a layer); a latent layer
``gate`` (a head-wise sigmoid gate on its output); a layer of the
stacked-runs body ``residual`` (a multiplier on what joins); the model an
untied ``head`` and an ``embedding_multiplier``.

A model whose ``weights()`` come as ``runs`` — the parameters of each maximal
run of like layers stacked along a leading axis — has each run SCANNED
(``_tokens_runs``): forty layers trace and compile as nine bodies.  A model
that hands ``layers`` one by one keeps the Python loop.

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

import itertools
import math

import jax
import jax.numpy as jnp

from ..ops import index_scores as _index
from ..ops import latent_attention as _latent
from ..ops import moe, ssd
from ..ops import paged_attention as _paged
from ..ops import power_retention as _ret
from ..serve.schema import pool_rows, row_lanes

__all__ = ["LayeredEngine", "top_mask", "mask_positions", "block_positions",
           "block_pages", "top_positions"]

# a routed layer's parameters that a scan over a stacked run does not slice
_EXPERT_WEIGHTS = ("egu_weight", "edown_weight")
# the indexer's scores of one head block, and a prefill's attention scores
# of one block of K/V heads, may take this many bytes
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


def _rope(x, pos, theta, halves=False):
    """Rotate pairs of the last axis of ``x`` ``(B, C, [heads,] d)`` by the
    angles of positions ``pos`` ``(B, C)``: consecutive (even, odd) pairs,
    or with ``halves`` the pairs ``(j, j + d / 2)`` (``rotate_half``)."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(d // 2, dtype=jnp.float32) * 2.0 / d)
    ang = pos.astype(jnp.float32)[..., None] * inv
    if x.ndim == 4:
        ang = ang[:, :, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x32 = x.astype(jnp.float32)
    # a strided slice, not ``x32[..., 0::2]``: that index lowers to a gather
    x1, x2 = (x32[..., :d // 2], x32[..., d // 2:]) if halves \
        else (jax.lax.slice_in_dim(x32, 0, d, 2, axis=x32.ndim - 1),
              jax.lax.slice_in_dim(x32, 1, d, 2, axis=x32.ndim - 1))
    pair = (x1 * cos - x2 * sin, x1 * sin + x2 * cos)
    out = jnp.concatenate(pair, axis=-1) if halves \
        else jnp.stack(pair, axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


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


def _reach_rows(lat, fi, reach):
    """Layer ``fi``'s rows of the pages ``reach`` ``(B, n)`` (ids in the
    pool) of the latent pool, one view a row: ``(B, n * page, lanes)``."""
    B, n = reach.shape
    with jax.named_scope("mx.latent_gather"):
        return lat.at[fi, reach].get(mode="promise_in_bounds").reshape(
            B, n * lat.shape[2], lat.shape[3])


def _slot_rows(arr, slots):
    """Every layer's entries of slots ``slots`` ``(B,)`` out of a
    slot-table array ``(layers, slots, ...)``: ``(layers, B, ...)``; a slot
    past the end reads the last one (the caller drops what it builds from
    it)."""
    return arr.at[:, jnp.minimum(slots, arr.shape[1] - 1)].get(
        mode="promise_in_bounds")


def _slot_rows_set(arr, slots, vals):
    """``arr`` with every layer's entries of ``slots`` := ``vals`` ``(layers,
    B, ...)``, scattered in place; a slot past the end (an idle row of a
    wave) is dropped."""
    return arr.at[:, slots].set(vals.astype(arr.dtype), mode="drop")


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
    ``(N, T)``, ascending (``block_positions`` without its one-hot)."""
    return block_positions(chosen, k)[0]


def block_positions(chosen, k):
    """The positions of the ``k`` set entries of every row of ``chosen``
    ``(N, T)``, ascending, by blocks of 128, WITHOUT a gather (the chip
    walks a gather's indices one by one: three of them were 1.76 ms a layer
    of the ``dots3`` step), and the one-hot ``(N, k,
    blocks)`` bfloat16 of the block each lies in, for ``block_pages``:

    1. every set entry's rank inside its block, 1 .. 128, from a product
       with a triangle of ones (0 where the entry is not set), and with it
       the blocks' counts;
    2. the block of the j-th from the blocks' running counts — the blocks
       that end at or before it are a prefix, so their number is its block
       and their counts' sum what it leaves to find inside: two reductions
       of one ``(N, k, blocks)`` comparison;
    3. that block's ranks by a one-hot product over the blocks (the MXU
       takes the one-hot as it is compared; no ``(N, k, 128)`` rows are
       fetched), and the place whose rank is the one sought.

    0 / 1 and ranks up to 128 are exact in bfloat16, and a one-hot row sums
    one term.  Where a row has fewer than ``k`` set, the rest read the last
    block's first position."""
    N, T = chosen.shape
    W = 128
    nb = -(-T // W)
    blocks = jnp.pad(chosen, ((0, 0), (0, nb * W - T))).reshape(N, nb, W)
    tri = jnp.triu(jnp.ones((W, W), jnp.bfloat16))
    rank = jnp.einsum("nbw,wv->nbv", blocks.astype(jnp.bfloat16), tri,
                      preferred_element_type=jnp.float32)
    count = rank[:, :, W - 1].astype(jnp.int32)                 # (N, nb)
    rank = jnp.where(blocks, rank, 0).astype(jnp.bfloat16)
    # the last block is never passed: a short row's rest stay in it
    upto = jnp.cumsum(count, axis=-1)[:, :nb - 1]
    j = jnp.arange(k, dtype=jnp.int32)
    passed = upto[:, None, :] <= j[None, :, None]               # (N, k, nb-1)
    blk = jnp.sum(passed, axis=-1, dtype=jnp.int32)             # (N, k)
    nth = j[None] - jnp.sum(
        jnp.where(passed, count[:, None, :nb - 1], 0), axis=-1)
    onehot = (jnp.arange(nb, dtype=jnp.int32) == blk[..., None]).astype(
        jnp.bfloat16)
    picked = jnp.einsum("nkb,nbw->nkw", onehot, rank,
                        preferred_element_type=jnp.float32)     # (N, k, W)
    here = picked == (nth + 1)[..., None].astype(jnp.float32)
    return blk * W + jnp.argmax(here, axis=-1).astype(jnp.int32), onehot


def block_pages(onehot, sel, table, page, npages):
    """``table[b, sel // page]`` — the page id of each selected position —
    WITHOUT a gather (a ``take_along_axis`` over the table was 0.67 ms a
    layer of the ``dots3`` step and 1.87 ms of a question chunk, PERF.md
    section 5).  ``sel`` ``(B, C, k)`` and
    its blocks' one-hot ``onehot`` ``(B, C, k, blocks)`` from
    ``block_positions``; ``table`` ``(B, n)`` of ids below ``npages``, one
    row for the ``C`` queries of a slot.

    A stretch of ``L = lcm(page, 128)`` positions is ``L // 128`` blocks and
    ``L // page`` pages.  Each block takes its stretch's page ids as columns,
    split into bytes (exact in bfloat16); the one-hot product picks the
    position's block's columns (a one-hot row sums one term: exact in
    float32), a select the page inside the stretch, and the bytes join
    again in int32."""
    W = 128
    nb = onehot.shape[-1]
    B, n = table.shape
    L = math.lcm(page, W)
    per = L // page
    spans = -(-nb * W // L)
    parts = max(1, -(-(npages - 1).bit_length() // 8))
    ids = jnp.pad(table, ((0, 0), (0, spans * per - n)))
    cols = jnp.stack([(ids >> (8 * i)) & 0xFF for i in range(parts)], -1)
    cols = jnp.repeat(cols.reshape(B, spans, per * parts), L // W,
                      axis=1)[:, :nb].astype(jnp.bfloat16)
    got = jnp.einsum("bckn,bnp->bckp", onehot, cols,
                     preferred_element_type=jnp.float32)
    got = got.reshape(*sel.shape, per, parts)
    inner = (sel % L) // page
    byte = jnp.sum(jnp.where(
        (inner[..., None] == jnp.arange(per, dtype=jnp.int32))[..., None],
        got, 0.0), axis=-2).astype(jnp.int32)                  # (.., parts)
    return sum(byte[..., i] << (8 * i) for i in range(parts))


def _index_scores_view(iq, iw, ikp, fi, table, page):
    """The indexer's scores ``(B, C, T)`` float32 of queries ``iq`` ``(B, C,
    J, d)`` weighted ``iw`` ``(B, C, J)`` over the ``T`` positions the table
    rows ``table`` ``(B, n)`` reach in layer ``fi`` of the index-key pool
    ``ikp``: ``sum_j iw[j] * relu(iq[j] . key[t])``.  The keys are gathered
    into a ``(B, T, d)`` view (a sentinel entry reads the last page) and
    contracted a block of heads at a time, so that a block's ``(B, C, jb,
    T)`` scores fit ``_INDEX_BLOCK_BYTES``."""
    B, C, J, dI = iq.shape
    T = table.shape[1] * page
    keys = ikp.at[fi, jnp.minimum(table, ikp.shape[1] - 1)].get(
        mode="promise_in_bounds")
    keys = keys.reshape(B, T, -1)[..., :dI]

    def block(q, wj):
        s = jnp.einsum("bcjd,btd->bcjt", q, keys,
                       preferred_element_type=jnp.float32)
        return jnp.einsum("bcj,bcjt->bct", wj, jax.nn.relu(s))

    jb = max(1, min(J, _INDEX_BLOCK_BYTES // max(1, B * C * T * 4)))
    while J % jb:
        jb -= 1
    if jb == J:
        return block(iq, iw)
    nb = J // jb
    qs = jnp.moveaxis(iq.reshape(B, C, nb, jb, dI), 2, 0)
    ws = jnp.moveaxis(iw.reshape(B, C, nb, jb), 2, 0)
    score, _ = jax.lax.scan(
        lambda acc, xs: (acc + block(*xs), None),
        jnp.zeros((B, C, T), jnp.float32), (qs, ws))
    return score


def top_positions(score, valid, k):
    """``mask_positions`` of ``top_mask``: the positions of the ``k``
    largest, ascending.  (The chip's ``lax.top_k`` is a full sort of every
    row, 1.3 ms for 32 rows of 33,152 — PERF.md, PR 29; the bisection is
    0.10 ms and the positions 0.12 at 32 rows, 0.33 at 128 — PR 37 —, and
    the set comes first and alone where nothing needs the positions.)"""
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
    # attention kind -> the body that runs it: a mixer ``_tokens_runs``
    # scans over a stacked run, or the Python loop of ``tokens_paged`` (its
    # two latent branches).  A model is served by one body, so it names
    # kinds of one body only; a kind the table lacks raises at build
    _KINDS = {"ssm": "_ssm_mixer", "gqa": "_gqa_mixer",
              "retention": "_retention_mixer",
              "latent_sparse": None, "latent_window": None, "latent": None}

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
        kinds = {d["attn"]["kind"] for d in self.desc}
        if not kinds <= set(self._KINDS) \
                or len({self._KINDS[k] is None for k in kinds}) > 1:
            from ..base import MXNetError
            raise MXNetError(
                f"the layered decode engine has no body for the attention "
                f"kinds {sorted(kinds)} together: stacked runs serve "
                f"{sorted(k for k, m in self._KINDS.items() if m)}, the "
                f"layer loop "
                f"{sorted(k for k, m in self._KINDS.items() if not m)}")
        self.cdtype = jnp.dtype(self.cfg.dtype)
        # kinds with a mixer run as scans over the model's stacked runs
        # (``weights()["runs"]``), the others layer by layer (``["layers"]``)
        self.stacked = all(self._KINDS[k] for k in kinds)
        of_kind = lambda kind: [i for i, d in enumerate(self.desc)
                                if d["cache"] == kind]
        # every layer with a latent row under the main table; of those the
        # selecting ones keep an index-key row beside it
        self.full = sorted(of_kind("latent_index") + of_kind("latent"))
        self.idx, self.win = of_kind("latent_index"), \
            of_kind("latent_window")
        self.kv, self.ssm = of_kind("kv"), of_kind("ssm_state")
        self.kvw = of_kind("kv_window")
        self.ret = of_kind("retention_state")
        # the cache kinds whose arrays are addressed by the slot's own
        # index (``serve.schema.POOL_ROWS``: table "slot")
        self.slot_kinds = sorted({d["cache"] for d in self.desc
                                  if pool_rows(d["cache"])[0] == "slot"})
        if len(self.slot_kinds) > 1:
            from ..base import MXNetError
            raise MXNetError(
                "the layered decode engine keeps one kind of state under "
                f"the slot table, not {self.slot_kinds}")
        # what ``vp`` carries besides the main table's arrays: the window
        # table's, or the slot table's (``ssm_state`` where neither)
        self.other_kind = "kv_window" if self.kvw else \
            (self.slot_kinds or ["ssm_state"])[0]
        # maximal runs of like layers ``(first layer, layers)``: what a
        # model that stacks its weights hands a scan each
        self.runs, i = [], 0
        for _, grp in itertools.groupby(self.desc):
            n = len(list(grp))
            self.runs.append((i, n))
            i += n
        if not self.full:
            # no latent attention over the main table: no dense form, no
            # key-page bound
            self.dense_chunk = None
        # stored row widths, in whole lane tiles
        self.rows = {}
        if self.full:
            a = self.desc[self.full[0]]["attn"]
            self.rows["latent"] = row_lanes(a["kv_rank"] + a["rope"])
            self.rows["index_key"] = row_lanes(
                self.desc[self.idx[0]]["attn"]["index_dim"]) \
                if self.idx else 0
        self.window = None
        if self.win:
            a = self.desc[self.win[0]]["attn"]
            self.rows["window_latent"] = row_lanes(a["kv_rank"]
                                                   + a["rope"])
            self.window = int(a["window"])
        # a model of stacked runs with no layer of a kind keeps that kind's
        # arrays with no layers in them
        self.state_shape = (0, 0, 0)
        if self.stacked:
            self.rows.update(k=0, v=0, conv_tail=0)
        if self.kv:
            a = self.desc[self.kv[0]]["attn"]
            self.rows["k"] = self.rows["v"] = row_lanes(
                a["kv_heads"] * a["head_dim"])
        if self.kvw:
            if self.slot_kinds:
                # ``vp`` carries the window table's arrays or the slot
                # table's, not both
                from ..base import MXNetError
                raise MXNetError(
                    "the layered decode engine keeps no window table "
                    "beside state under the slot table")
            a = self.desc[self.kvw[0]]["attn"]
            self.rows["window_k"] = self.rows["window_v"] = row_lanes(
                a["kv_heads"] * a["head_dim"])
            self.window = int(a["window"])
        if self.ssm:
            a = self.desc[self.ssm[0]]["attn"]
            g = ssd.heads_per_row(a["heads"], a["head_dim"])
            # a slot's state as stored (``ops.ssd``) and its tail of
            # ``conv - 1`` inputs side by side in one row
            self.state_shape = (a["heads"] // g, a["state"],
                                g * a["head_dim"])
            self.conv_width = a["heads"] * a["head_dim"] + 2 * a["state"]
            self.rows["conv_tail"] = (a["conv"] - 1) * row_lanes(
                self.conv_width)
        if self.ret:
            a = self.desc[self.ret[0]]["attn"]
            # a slot's state as stored (``ops.power_retention``: ``v``'s
            # coordinates on the sublanes, the expansion on the lanes) and
            # its normaliser
            self.state_shape = (a["kv_heads"], a["head_dim"],
                                _ret.expanded_rows(a["head_dim"]))

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
        kv = self.rows.get("k", 0) + self.rows.get("v", 0)
        return (len(self.full) * self.rows.get("latent", 0)
                + len(self.idx) * self.rows.get("index_key", 0)
                + len(self.kv) * kv) * page * self.cdtype.itemsize

    def slot_state_bytes(self):
        """Bytes ONE slot keeps under the slot table over every layer that
        has such state (0 where the model has none)."""
        if self.ret:
            G, _, E = self.state_shape
            return len(self.ret) * (math.prod(self.state_shape) + G * E) \
                * _ret.STATE_DTYPE.itemsize
        if not self.ssm:
            return 0
        return len(self.ssm) * (
            math.prod(self.state_shape) * ssd.STATE_DTYPE.itemsize
            + self.rows["conv_tail"] * self.cdtype.itemsize)

    def window_page_bytes(self, page):
        """Bytes of one window-table page over every layer that has one."""
        w = len(self.win) * self.rows.get("window_latent", 0) \
            + len(self.kvw) * (self.rows.get("window_k", 0)
                               + self.rows.get("window_v", 0))
        return w * page * self.cdtype.itemsize

    def pool_zeros(self, num_pages, window_pages, page, slots=0):
        """``(kp, vp)``: ``kp`` the main-table pools (``(latent, index
        key)`` or ``(k, v)``), each ``(layers, pages, page, lanes)``;
        ``vp`` the window-table pools (the latent one, or ``(k, v)``), or —
        a model with state under the SLOT table — ``(state, conv tail)`` or
        ``(state, z)``, each ``(layers, slots, ...)``."""
        z = lambda n, p, w: jnp.zeros((n, p, page, w), self.cdtype)
        if self.stacked:
            kp = (z(len(self.kv), num_pages, self.rows["k"]),
                  z(len(self.kv), num_pages, self.rows["v"]))
            if self.kvw:
                return kp, (z(len(self.kvw), window_pages,
                              self.rows["window_k"]),
                            z(len(self.kvw), window_pages,
                              self.rows["window_v"]))
            if self.ret:
                G, _, E = self.state_shape
                n = len(self.ret)
                return kp, (jnp.zeros((n, slots) + self.state_shape,
                                      _ret.STATE_DTYPE),
                            jnp.zeros((n, slots, G, E), _ret.STATE_DTYPE))
            vp = (jnp.zeros((len(self.ssm), slots) + self.state_shape,
                            ssd.STATE_DTYPE),
                  jnp.zeros((len(self.ssm), slots, self.rows["conv_tail"]),
                            self.cdtype))
            return kp, vp
        kp = (z(len(self.full), num_pages, self.rows["latent"]),
              z(len(self.idx), num_pages, self.rows["index_key"]))
        vp = z(len(self.win), window_pages,
               self.rows.get("window_latent", 0))
        return kp, vp

    def cache_bytes(self):
        return 0

    def step_counters(self, aux, active):
        """A step's counters reduced over the live slots: tokens each held
        expert of each routed layer got, keys the indexer selected and the
        queries that selected them, and what the index-score kernel's and
        the latent attention kernel's page walks counted (nothing where the
        step gathers the view)."""
        out = {}
        if "expert" in aux:
            out["expert_load"] = self._expert_load(aux["expert"], active)
        if "selected" in aux:
            sel = aux["selected"][:, :, 0]
            out["selected"] = jnp.sum(jnp.where(active[None], sel, 0))
            out["queries"] = jnp.sum(active) * sel.shape[0]
        if "index_walk" in aux:
            # (full layers, slots, [pages walked, copies, table width])
            out["index_walk"] = jnp.sum(jnp.where(
                active[None, :, None], aux["index_walk"], 0), axis=(0, 1))
        if "latent_walk" in aux:
            # (latent layers, slots, [rows walked, copies])
            out["latent_walk"] = jnp.sum(jnp.where(
                active[None, :, None], aux["latent_walk"], 0), axis=(0, 1))
        return out

    def _expert_load(self, expert, rows):
        """``(routed layers, held)`` int32: the tokens each held expert of
        each routed layer got from the rows ``rows`` ``(R,)`` of ``expert``
        ``(routed layers, R, top_k)`` (local ids, -1 where another chip's)."""
        n = next(d["ffn"]["held"][1] for d in self.desc
                 if d["ffn"]["kind"] == "routed")
        hit = (expert[..., None] == jnp.arange(n)) \
            & rows[None, :, None, None]
        return jnp.sum(hit, axis=(1, 2)).astype(jnp.int32)

    # -- the programs' bodies ------------------------------------------- #
    def pool_token_paged(self, x_tok, pos, kp, vp, pt, page, sw=None,
                         q8=None, live=None):
        """The pool step: one token a slot.  Returns ``(logits, kp, vp,
        aux)``; ``aux`` holds per-slot counters the step reduces.  ``live``
        ``(S,)``: the slots that step — state under the slot table is
        left as it is for every other (a chunked prefill may be filling
        it), as the sentinel rows of its page table leave its pages."""
        S = x_tok.shape[0]
        return self.tokens_paged(
            self.model.weights(), x_tok[:, None], pos, pt, (kp, vp),
            page, jnp.zeros((S,), jnp.int32), live=live)

    def chunk_tokens(self, toks, off, nlast, ptrow, page, kp, vp, sw=None,
                     q8=None, key_pages=None, slot=None):
        """``C`` tokens of slot ``slot`` at offset ``off``; ``key_pages``
        bounds the main-table pages the chunk can reach (its last
        position's).  State under the slot table starts from zero at
        offset 0 and from what the last chunk stored after it.  What the
        chunk counted itself — its experts' load, its latent walks' rows
        and copies summed over the layers — comes back as a fourth
        value where there is any."""
        tables = tuple(t[None] for t in ptrow) \
            if isinstance(ptrow, tuple) else ptrow[None]
        logits, kp, vp, aux = self.tokens_paged(
            self.model.weights(), toks[None], off[None], tables, (kp, vp),
            page, nlast[None], key_pages=key_pages,
            slots=None if slot is None else slot[None])
        counted = {}
        if "expert" in aux:
            # the experts' load over EVERY row the chunk computes: the rows
            # past the prompt route and run through the experts as well
            every = jnp.ones((toks.shape[0],), jnp.bool_)
            counted["expert_load"] = self._expert_load(aux["expert"], every)
        if "latent_walk" in aux:
            # (latent layers, 1, [rows walked, copies])
            counted["latent_walk"] = jnp.sum(aux["latent_walk"], axis=(0, 1))
        if not counted:
            return logits, kp, vp
        return logits, kp, vp, counted

    def admit_tokens(self, prompts, last, tables, page, kp, vp, slots=None):
        """An admission wave: ``(A, P)`` right-padded prompts from offset
        0 through each row's own table rows; only the pages a prompt of
        ``P`` tokens can reach are read.  ``slots`` ``(A,)``: where each
        row's state under the slot table lands (an idle row names the
        one-past-the-end slot: dropped); it starts from zero whatever the
        slot's last tenant left."""
        A, P = prompts.shape
        logits, kp, vp, _ = self.tokens_paged(
            self.model.weights(), prompts, jnp.zeros((A,), jnp.int32),
            tables, (kp, vp), page, last, key_pages=-(-P // page),
            slots=slots)
        return logits, kp, vp

    def forward_dense(self, w, toks):
        """The full causal pass, logits at every position: the same code
        over fresh pools with identity tables."""
        B, L = toks.shape
        page = 16
        npg = -(-L // page)
        ids = jnp.arange(B * npg, dtype=jnp.int32).reshape(B, npg)
        pools = self.pool_zeros(B * npg, B * npg, page, B)
        logits, _, _, _ = self.tokens_paged(
            w, toks, jnp.zeros((B,), jnp.int32),
            ids if self.window is None else (ids, ids), pools, page, None,
            slots=jnp.arange(B, dtype=jnp.int32))
        return logits

    @jax.named_scope("mx.dense")
    def tokens_paged(self, w, toks, off, tables, pools, page, last,
                     key_pages=None, slots=None, live=None):
        """``toks`` ``(B, C)`` at positions ``off[b] + c`` through
        ``tables = (main (B, MAXP), window (B, ring))`` (the main table
        alone where the model has no window) against ``pools = ((latent,
        index key), window latent)``.  Returns ``(logits (B, V)
        float32 at column last[b] — every column, (B, C, V), when ``last``
        is None —, kp, vp, aux)``."""
        if self.stacked:
            return self._tokens_runs(w, toks, off, tables, pools, page,
                                     last, key_pages, slots, live)
        cfg = self.cfg
        (lat, ikp), wlat = pools
        ptm, ptw = tables if isinstance(tables, tuple) else (tables, None)
        B, C = toks.shape
        pos = off[:, None] + jnp.arange(C, dtype=jnp.int32)[None]
        lp, row = pos // page, pos % page
        x = w["wte"][toks]
        eps = cfg.rms_norm_eps
        aux = {"expert": [], "selected": [], "index_walk": [],
               "latent_walk": []}
        # a layer's place among the main table's latent layers, the index
        # keys' layers and the window's
        fi = ii = wi = 0
        for i, d in enumerate(self.desc):
            lw, a = w["layers"][i], d["attn"]
            h = _rms(x, lw["norm1_gamma"], eps)
            q_nope, q_rope, new_row, cq = self._latent_qkv(lw, a, h, pos)
            if a["kind"] == "latent_window":
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
            else:
                sparse = a["kind"] == "latent_sparse"
                if sparse:
                    with jax.named_scope("mx.index"):
                        iq, ik_row, iw = self._index_qkw(lw, a, h, cq, pos)
                with jax.named_scope("mx.latent_write"):
                    pg = _table_pages(ptm, lp, lat.shape[1])
                    lat = lat.at[fi, pg, row].set(
                        _pad_last(new_row, lat.shape[-1]), mode="drop")
                    if sparse:
                        ikp = ikp.at[ii, pg, row].set(
                            _pad_last(ik_row, ikp.shape[-1]), mode="drop")
                kp_n = ptm.shape[1] if key_pages is None else key_pages
                reach = jnp.minimum(ptm[:, :kp_n], lat.shape[1] - 1)
                if sparse:
                    o = self._sparse_attend(lw, a, q_nope, q_rope, lat, fi,
                                            iq, iw, ikp, ii, ptm[:, :kp_n],
                                            reach, pos, page, aux)
                    ii += 1
                else:
                    o, walk = self._every_attend(lw, a, q_nope, q_rope, lat,
                                                 fi, ptm, reach, pos, page)
                    if walk is not None:
                        aux["latent_walk"].append(walk)
                fi += 1
            if a.get("gate"):
                gate = jax.nn.sigmoid(jnp.dot(
                    h, lw["gate_weight"], preferred_element_type=jnp.float32))
                o = o.astype(jnp.float32) * gate[..., None]
            post = d.get("post_norms")
            y = _dot(o.astype(x.dtype).reshape(B, C, -1), lw["o_weight"])
            if post:
                y = _rms(y, lw["post1_gamma"], eps)
            x = x + y
            h = _rms(x, lw["norm2_gamma"], eps)
            y, eidx = self._ffn(lw, d["ffn"], h.reshape(B * C, -1))
            if eidx is not None:
                aux["expert"].append(eidx)
            y = y.reshape(B, C, -1)
            if post:
                y = _rms(y, lw["post2_gamma"], eps)
            x = x + y
        with jax.named_scope("mx.head"):
            if last is not None:
                x = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
            logits = jnp.dot(_rms(x, w["normf"], eps),
                             w["head"],
                             preferred_element_type=jnp.float32)
        aux = {k: jnp.stack(v) for k, v in aux.items() if v}
        return logits, (lat, ikp), wlat, aux

    # -- stacked runs: state-space and grouped-query layers --------------- #
    def _tokens_runs(self, w, toks, off, tables, pools, page, last,
                     key_pages, slots, live):
        """``tokens_paged`` of a model whose weights come as stacked runs
        of like layers: each run is ONE ``lax.scan`` over its layers.

        ``C == 1`` is the step: every slot one token, the pools a run
        writes — ``(k, v)`` under the main table, ``(k, v)`` under the
        window table's ring, ``(state, conv tail)`` under the slot table —
        carried whole and updated in place at ``(layer, ...)``; ``live``
        masks the slot table's update.  ``tables`` is the main table, or
        the pair ``(main, window ring)`` where the model keeps a window.

        ``C > 1`` is a prefill: the K and V rows go through the tables as in
        the step, but the slot table's entries of the rows' ``slots`` are
        read out BEFORE the scans (zero for a row at offset 0, whatever its
        slot held), ride each scan as its ``xs``, come back as its ``ys``
        and are written in place AFTER the scans, so that the scans never
        carry the whole state: carried, the chip's compiler gave it a
        layout that suits the prefill's transposes and converted the WHOLE
        array to it and back, every dispatch.  A row leaves the state of its
        last TRUE token (column ``last[b]``).

        A routed layer's expert ids ride out as its scan's ``ys``: ``aux``
        holds them for every routed layer, as the layer loop's does."""
        cfg = self.cfg
        eps = cfg.rms_norm_eps
        main, other = pools
        slot = self.other_kind if self.slot_kinds else None
        held = {"kv": main, "kv_window": (), "ssm_state": (),
                self.other_kind: other}
        ptm, ptw = tables if isinstance(tables, tuple) else (tables, None)
        B, C = toks.shape
        pos = off[:, None] + jnp.arange(C, dtype=jnp.int32)[None]
        count = jnp.full((B,), C, jnp.int32) if last is None else last + 1
        x = (w["wte"][toks].astype(jnp.float32)
             * getattr(cfg, "embedding_multiplier", 1.0)).astype(self.cdtype)
        ctx = {"pos": pos, "count": count, "page": page, "table": ptm,
               "ring": ptw, "key_pages": key_pages, "live": live}
        step = C == 1
        mem = None
        if not step and slot:
            fresh = off == 0
            mem = tuple(jnp.where(
                fresh.reshape((1, B) + (1,) * (a.ndim - 2)), 0,
                _slot_rows(a, slots)) for a in held[slot])
        seen, after, experts = dict.fromkeys(held, 0), [], []
        for rw, (first, n) in zip(w["runs"], self.runs):
            d = self.desc[first]
            kind, cache = d["attn"]["kind"], d["cache"]
            mixer = getattr(self, self._KINDS[kind])
            rowwise = cache == slot and not step
            carried = () if rowwise else held[cache]
            lo = seen[cache]
            rows = tuple(a[lo:lo + n] for a in mem) if rowwise else ()
            # the run's routed experts stay whole, closed over: the
            # grouped product takes the layer's by index (``ops.moe``)
            whole = {k: v for k, v in rw.items() if k in _EXPERT_WEIGHTS}
            rw = {k: v for k, v in rw.items() if k not in whole}

            def layer(carry, xs, d=d, mixer=mixer, lo=lo, whole=whole):
                x, pools = carry
                lw, j, rows = xs
                li = lo + j         # the layer's place among its cache kind
                res, post = d.get("residual", 1.0), d.get("post_norms")
                h = _rms(x, lw["norm1_gamma"], eps)
                o, pools, rows = mixer(lw, d["attn"], h, pools, li, ctx,
                                       rows)
                if post:
                    o = _rms(o, lw["post1_gamma"], eps)
                x = x + (o * res).astype(x.dtype)
                h = _rms(x, lw["norm2_gamma"], eps)
                y, eidx = self._ffn(dict(lw, **whole), d["ffn"],
                                    h.reshape(B * C, -1), j if whole else None)
                y = y.reshape(B, C, -1)
                if post:
                    y = _rms(y, lw["post2_gamma"], eps)
                x = x + (y * res).astype(x.dtype)
                return (x, pools), (rows, () if eidx is None else eidx)

            (x, carried), (rows, eidx) = jax.lax.scan(
                layer, (x, carried),
                (rw, jnp.arange(n, dtype=jnp.int32), rows))
            seen[cache] += n
            if rowwise:
                after.append(rows)
            else:
                held[cache] = carried
            if d["ffn"]["kind"] == "routed":
                experts.append(eidx)
        if after:
            held[slot] = tuple(
                _slot_rows_set(a, slots, jnp.concatenate(parts))
                for a, parts in zip(held[slot], zip(*after)))
        with jax.named_scope("mx.head"):
            if last is not None:
                x = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
            xn = _rms(x, w["normf"], eps)
            if "head" in w:
                logits = jnp.dot(xn, w["head"],
                                 preferred_element_type=jnp.float32)
            else:
                # the tied head: the embedding's rows contracted as they lie
                logits = jnp.einsum("...h,vh->...v", xn, w["wte"],
                                    preferred_element_type=jnp.float32)
            logits = logits / getattr(cfg, "logits_scaling", 1.0)
        aux = {"expert": jnp.concatenate(experts)} if experts else {}
        return logits, held["kv"], held[self.other_kind], aux

    def _ssm_mixer(self, lw, a, h, held, li, ctx, rows):
        """One state-space layer over ``h`` ``(B, C, H)``.  The step (``C ==
        1``) updates layer ``li`` of ``held = (state, conv tail)`` in place
        for the live slots; a prefill starts from ``rows = (state (B, ...),
        tail (B, ...))`` as stored and hands the rows' new entries back."""
        B, C, _ = h.shape
        hh, P, N, K = a["heads"], a["head_dim"], a["state"], a["conv"]
        inner, W = hh * P, self.conv_width
        Wl = row_lanes(W)
        f32 = jnp.float32
        zxdt = _dot(h, lw["in_weight"])
        z, u, dt = zxdt[..., :inner], zxdt[..., inner:inner + W], \
            zxdt[..., inner + W:]
        dt = jax.nn.softplus(dt.astype(f32) + lw["dt_bias"])
        a_neg = -jnp.exp(lw["a_log"].astype(f32))
        step = C == 1
        if step:
            state, tail = held
            live = jnp.ones((B,), jnp.bool_) if ctx["live"] is None \
                else ctx["live"]
        else:
            s0, t0 = rows
        with jax.named_scope("mx.ssm_conv"):
            if step:
                old = jax.lax.dynamic_index_in_dim(tail, li, 0, False)
                u, t1 = ssd.conv_step(old.reshape(B, K - 1, Wl)[..., :W],
                                      u[:, 0], lw["conv_weight"],
                                      lw["conv_bias"])
                t1 = _pad_last(t1, Wl).reshape(B, -1)
                tail = jax.lax.dynamic_update_index_in_dim(
                    tail, jnp.where(live[:, None], t1, old), li, 0)
                u = u[:, None]
            else:
                u, t1 = ssd.conv_seq(t0.reshape(B, K - 1, Wl)[..., :W], u,
                                     lw["conv_weight"], lw["conv_bias"],
                                     ctx["count"])
                t1 = _pad_last(t1, Wl).reshape(B, -1).astype(t0.dtype)
        xs = u[..., :inner].reshape(B, C, hh, P)
        bm, cm = u[..., inner:inner + N], u[..., inner + N:]
        if step:
            with jax.named_scope("mx.ssm_state"):
                y, state = ssd.state_update(state, li, xs[:, 0], dt[:, 0],
                                            a_neg, bm[:, 0], cm[:, 0], live)
                y = y[:, None]
        else:
            with jax.named_scope("mx.ssm_scan"):
                # padding adds nothing: dt = 0 past a row's true tokens
                true = jnp.arange(C)[None] < ctx["count"][:, None]
                y, s1 = ssd.chunk_scan(
                    xs, jnp.where(true[..., None], dt, 0.0), a_neg, bm, cm,
                    ssd.unpack(s0, P), a["chunk"])
                s1 = ssd.pack(s1)
        with jax.named_scope("mx.ssm_gate"):
            y = y + lw["d_skip"].astype(f32)[:, None] * xs.astype(f32)
            g = y.reshape(B, C, inner) * jax.nn.silu(z.astype(f32))
            g = _rms(g, lw["gnorm_gamma"], self.cfg.rms_norm_eps
                     ).astype(h.dtype)
        o = _dot(g, lw["out_weight"]).astype(f32)
        return (o, (state, tail), ()) if step else (o, held, (s1, t1))

    def _retention_mixer(self, lw, a, h, held, li, ctx, rows):
        """One power-retention layer over ``h`` ``(B, C, H)``
        (``ops.power_retention``): q, k, v by grouped-query projections,
        q and k through their RMSNorm and RoPE (``rope`` ``"halves"``), a
        decay ``log a = logsigmoid(h W_a)`` a token a KV head.  The step
        (``C == 1``) updates layer ``li`` of ``held = (state, z)`` in place
        for the live slots; a prefill starts from ``rows = (state (B, ...),
        z (B, ...))`` as stored and hands the rows' new entries back."""
        B, C, _ = h.shape
        hq, kvh, d = a["heads"], a["kv_heads"], a["head_dim"]
        eps = self.cfg.rms_norm_eps
        f32 = jnp.float32
        q = _dot(h, lw["q_weight"]).reshape(B, C, hq, d)
        k = _dot(h, lw["k_weight"]).reshape(B, C, kvh, d)
        v = _dot(h, lw["v_weight"]).reshape(B, C, kvh, d)
        with jax.named_scope("mx.qk_norm_rope"):
            halves = a.get("rope") == "halves"
            q = _rope(_rms(q, lw["qnorm_gamma"], eps), ctx["pos"],
                      a["theta"], halves)
            k = _rope(_rms(k, lw["knorm_gamma"], eps), ctx["pos"],
                      a["theta"], halves)
        log_a = jax.nn.log_sigmoid(jnp.dot(h, lw["gate_weight"],
                                           preferred_element_type=f32))
        step = C == 1
        # the regions of a slot-table state: the step's in-place update, and
        # prefill's chunked form
        if step:
            state, z = held
            live = jnp.ones((B,), jnp.bool_) if ctx["live"] is None \
                else ctx["live"]
            with jax.named_scope("mx.ssm_state"):
                y, state, z = _ret.state_update(
                    state, z, li, q[:, 0], k[:, 0], v[:, 0], log_a[:, 0],
                    live, a["eps"])
            y = y[:, None]
        else:
            with jax.named_scope("mx.ssm_scan"):
                y, s1, z1 = _ret.chunk_scan(q, k, v, log_a, *rows,
                                            ctx["count"], a["chunk"],
                                            a["eps"])
        o = _dot(y.astype(h.dtype).reshape(B, C, hq * d),
                 lw["o_weight"]).astype(f32)
        return (o, (state, z), ()) if step else (o, held, (s1, z1))

    def _gqa_mixer(self, lw, a, h, held, li, ctx, mem=()):
        """One grouped-query attention layer by the description's keys:
        ``qk_norm`` (an RMSNorm over each head of q and k), ``theta`` (RoPE
        on q and k, ``rope`` ``"halves"`` or consecutive pairs; without it
        no positions), ``window``, ``gate`` (a sigmoid gate on the output).
        The new K and V rows go through the table into layer ``li``'s pages:
        the main table, or with ``window`` the window table's ring, where a
        position's page is entry ``(position // page) % ring``.  The
        single-query step walks the slot's pages (the table row from 0, or
        the ring from the window's first page); a prefill reads the rows
        its queries can reach — the first ``key_pages`` of the row, or the
        ``window_span_pages`` of the ring — back through the table into a
        masked dense form."""
        from .decoding import _flat_attention

        kpool, vpool = held
        B, C, _ = h.shape
        hq, kvh, D = a["heads"], a["kv_heads"], a["head_dim"]
        page, pos, window = ctx["page"], ctx["pos"], a.get("window")
        pt = ctx["table"] if window is None else ctx["ring"]
        npages, lanes = kpool.shape[1], kpool.shape[-1]
        q = _dot(h, lw["q_weight"]).reshape(B, C, hq, D)
        kv = _dot(h, lw["kv_weight"])
        k, v = kv[..., :kvh * D], kv[..., kvh * D:]
        if a.get("qk_norm") or a.get("theta") is not None:
            with jax.named_scope("mx.qk_norm_rope"):
                k = k.reshape(B, C, kvh, D)
                if a.get("qk_norm"):
                    q = _rms(q, lw["qnorm_gamma"], self.cfg.rms_norm_eps)
                    k = _rms(k, lw["knorm_gamma"], self.cfg.rms_norm_eps)
                if a.get("theta") is not None:
                    halves = a.get("rope") == "halves"
                    q = _rope(q, pos, a["theta"], halves)
                    k = _rope(k, pos, a["theta"], halves)
                k = k.reshape(B, C, kvh * D)

        def pages_of(lp):
            """Page ids of logical pages ``lp`` ``(B, n)``."""
            if window is None:
                return _table_pages(pt, lp, npages)
            pg = jnp.take_along_axis(pt, lp % pt.shape[1], axis=1)
            return jnp.where(lp >= 0, pg, npages)

        def write(kpool, vpool):
            with jax.named_scope("mx.kv_write"):
                pg = pages_of(pos // page)
                return (kpool.at[li, pg, pos % page].set(
                            _pad_last(k, lanes), mode="drop"),
                        vpool.at[li, pg, pos % page].set(
                            _pad_last(v, lanes), mode="drop"))

        # the pages a row of queries reads, the position of their first
        # column and which columns each query may see
        if window is None:
            n = pt.shape[1] if C == 1 or ctx["key_pages"] is None \
                else ctx["key_pages"]
            reach, base = pt[:, :n], 0
            kpos = jnp.arange(n * page, dtype=jnp.int32)[None, None]
            ok = kpos <= pos[..., None]
        else:
            n = self.window_span_pages(page, C)
            lps = (pos[:, :1] // page - self.window_back_pages(page)) \
                + jnp.arange(n, dtype=jnp.int32)[None]
            reach, base = pages_of(lps), lps[:, 0] * page
            kpos = (lps[:, :, None] * page
                    + jnp.arange(page, dtype=jnp.int32)[None, None]
                    ).reshape(B, 1, n * page)
            p = pos[..., None]
            ok = (kpos <= p) & (kpos > p - window) & (kpos >= 0)
        reach = jnp.minimum(reach, npages - 1)

        def rows(pool):
            return pool.at[li, reach].get(mode="promise_in_bounds").reshape(
                B, n * page, lanes)

        region = "mx.attn" if window is None else "mx.window_attn"
        if C == 1:
            def view():
                iB = jnp.arange(B)
                p0 = jnp.clip(pos[:, 0] - base, 0, n * page - 1)
                kc = rows(kpool).at[iB, p0].set(_pad_last(k[:, 0], lanes))
                vc = rows(vpool).at[iB, p0].set(_pad_last(v[:, 0], lanes))
                return _flat_attention(q, kc[..., :kvh * D],
                                       vc[..., :kvh * D], ok, a["scale"],
                                       self.cdtype)[:, 0]

            with jax.named_scope(region):
                if _paged.supports(lanes, self.cdtype, page, hq, D) \
                        and lanes == kvh * D:
                    if window is None:
                        ends, starts = _paged.walk_lengths(
                            pt, pos[:, 0], page, npages), None
                    else:
                        ends, starts = _paged.walk_span(
                            pt, pos[:, 0], page, npages, window)
                    o = _paged.paged_attention(
                        q[:, 0], k[:, 0], v[:, 0], kpool, vpool, li, pt,
                        ends, a["scale"], view, starts)
                else:
                    o = view()
            kpool, vpool = write(kpool, vpool)
            o = o[:, None]
        else:
            kpool, vpool = write(kpool, vpool)
            G, T = hq // kvh, n * page

            def attend(qg, kc, vc):
                """``qg`` ``(B, C, kb, G, D)`` over ``kc`` / ``vc`` ``(B, T,
                kb, D)``: a block of ``kb`` K/V heads."""
                s = jnp.einsum("bckgd,btkd->bkgct", qg, kc,
                               preferred_element_type=jnp.float32) \
                    * a["scale"]
                s = jnp.where(ok[:, None, None], s, -1e30)
                p = jax.nn.softmax(s, axis=-1).astype(self.cdtype)
                return jnp.einsum("bkgct,btkd->bckgd", p, vc,
                                  preferred_element_type=jnp.float32
                                  ).astype(self.cdtype)

            with jax.named_scope(region):
                qg = q.reshape(B, C, kvh, G, D)
                kc = rows(kpool)[..., :kvh * D].reshape(B, T, kvh, D)
                vc = rows(vpool)[..., :kvh * D].reshape(B, T, kvh, D)
                # K/V heads a block, so that a block's scores fit
                kb = max(1, min(kvh, _INDEX_BLOCK_BYTES
                                // max(1, B * G * C * T * 4)))
                while kvh % kb:
                    kb -= 1
                if kb == kvh:
                    o = attend(qg, kc, vc)
                else:
                    blocks = lambda x, axis: jnp.moveaxis(
                        x.reshape(x.shape[:axis] + (kvh // kb, kb)
                                  + x.shape[axis + 1:]), axis, 0)
                    o = jnp.moveaxis(jax.lax.map(
                        lambda xs: attend(*xs),
                        (blocks(qg, 2), blocks(kc, 2), blocks(vc, 2))), 0, 2)
        o = o.reshape(B, C, hq * D)
        if a.get("gate"):
            gate = jax.nn.sigmoid(jnp.dot(
                h, lw["gate_weight"], preferred_element_type=jnp.float32))
            o = (o.astype(jnp.float32) * gate).astype(self.cdtype)
        return _dot(o, lw["o_weight"]).astype(jnp.float32), \
            (kpool, vpool), mem

    # -- attention ------------------------------------------------------ #
    def _latent_qkv(self, lw, a, h, pos):
        """Queries ``(B, C, heads, nope | rope)``, the cache row ``[c_kv |
        k_rope]`` of every token, and the query latent (the indexer reads
        it too)."""
        cfg = self.cfg
        B, C, H = h.shape
        rq, r = a["q_rank"], a["kv_rank"]
        rescale = getattr(cfg, "apply_mla_qkv_lora_rescale", False)
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

    def _select(self, a, iq, iw, ikp, ii, table, pos, page):
        """Which positions every query attends to: the ``topk`` of largest
        indexer score among ``s <= pos``, all of them while there are
        fewer — ``(B, C, T)`` bool over the ``T`` positions the table rows
        ``table`` (sentinels and all) reach, with exactly ``topk`` set a
        query (the earliest unseen positions fill up a short one), ``seen``
        itself, and what the page walk counted (``ops.index_scores``;
        ``None`` where the scores come from the gathered view).

        One query a row (the step) over a pool the kernel takes: the
        scores come out of ``ops.index_scores``, which walks each row's
        pages where they lie as far as ``pos + 1`` and leaves 0 past it.
        ``top_mask`` keys every column that is not ``seen`` to 0 whatever
        it holds, so such a column is never chosen ahead of a seen one.
        Every other caller — a chunk's or a wave's ``C`` queries a row —
        gathers the key view and contracts it; that form is also what the
        step lowers to off the TPU."""
        B, C = iq.shape[:2]
        npages = ikp.shape[1]
        T = table.shape[1] * page
        view = lambda: _index_scores_view(iq, iw, ikp, ii, table, page)
        walk = None
        if C == 1 and _index.supports(ikp.shape[-1], ikp.dtype, page,
                                      npages):
            # the new token's key is in the pool already: the walk ends
            # AFTER its position
            ends = _paged.walk_lengths(table, pos[:, 0] + 1, page, npages)
            score, walk = _index.index_scores(
                iq[:, 0], iw[:, 0], ikp, ii, table, ends,
                lambda: view()[:, 0])
        else:
            score = view()
        seen = jnp.arange(T, dtype=jnp.int32)[None, None] <= pos[..., None]
        chosen = top_mask(score.reshape(B * C, T), seen.reshape(B * C, T),
                          min(int(a["topk"]), T)).reshape(B, C, T)
        return chosen, seen, walk

    def _sparse_attend(self, lw, a, q_nope, q_rope, lat, fi, iq, iw, ikp, ii,
                       table, reach, pos, page, aux):
        """A ``latent_sparse`` layer's attention over the ``topk`` positions
        its indexer selects (``_select``): the dense form masked to the set
        from ``dense_chunk`` queries a row, else the selected rows gathered
        from the pool at the page ids ``block_pages`` finds.  ``aux`` gains
        what the selection counted."""
        B, C = pos.shape
        kp_n = table.shape[1]
        with jax.named_scope("mx.index"):
            full, seen, walk = self._select(a, iq, iw, ikp, ii, table, pos,
                                            page)           # (B, C, T)
            chosen = full & seen
        aux["selected"].append(jnp.sum(chosen, axis=-1))
        if walk is not None:
            aux["index_walk"].append(walk)
        if C >= self.dense_chunk:
            rows = _reach_rows(lat, fi, reach)
            with jax.named_scope("mx.latent_attn"):
                return self._attend_dense(lw, a, q_nope, q_rope, rows,
                                          chosen)
        K = min(int(a["topk"]), kp_n * page)
        with jax.named_scope("mx.index"):
            sel, blocks = block_positions(full.reshape(B * C, -1), K)
            sel, blocks = sel.reshape(B, C, K), blocks.reshape(B, C, K, -1)
            # fewer than K seen: the rest point past ``pos``
            ok = sel <= pos[..., None]
        with jax.named_scope("mx.latent_gather"):
            pgs = block_pages(blocks, sel, reach, page, lat.shape[1])
            rows = lat.at[fi, pgs, sel % page].get(
                mode="promise_in_bounds")                   # (B, C, K, W)
        with jax.named_scope("mx.latent_attn"):
            return self._attend(lw, a, q_nope, q_rope, rows, ok,
                                "bchf,bckf->bchk", "bchk,bckr->bchr")

    def _every_attend(self, lw, a, q_nope, q_rope, lat, fi, table, reach,
                      pos, page):
        """A ``latent`` layer's attention over every position ``s <= pos``
        (the new rows are in the pool already).  Over a pool the kernels
        take, the single-query step walks each slot's pages in place
        (``ops.latent_attention``), and a chunk's or a wave's ``C`` queries
        a row walk the WHOLE table row (``table``) as far as each tile's
        last query reaches, whatever bound ``reach`` carries; both hand
        back what the walk counted.  Every other caller — the CPU, a pool
        the kernels refuse — gathers the rows its queries can reach
        (``reach``: the table's first pages) and takes the masked dense
        form, which is also the walks' reference.  Returns ``(o (B, C,
        heads, v) float32, walk counts or None)``."""
        B, C = pos.shape
        T = reach.shape[1] * page
        npages, lanes = lat.shape[1], lat.shape[-1]

        def dense(expand=True):
            seen = jnp.arange(T, dtype=jnp.int32)[None, None] \
                <= pos[..., None]
            return self._attend_dense(lw, a, q_nope, q_rope,
                                      _reach_rows(lat, fi, reach), seen,
                                      expand)

        with jax.named_scope("mx.latent_attn"):
            if not _latent.supports(lanes, a["kv_rank"], lat.dtype, page,
                                    npages):
                return dense(), None
            wkv = self._wkv(lw, a)
            if C > 1:
                qf = self._absorbed(a, q_nope, q_rope, wkv, lanes)
                held = _paged.walk_lengths(
                    table, jnp.full((B,), table.shape[1] * page, jnp.int32),
                    page, npages)
                ctx, walk = _latent.latent_chunk_attention(
                    qf, lat, fi, table, jnp.minimum(pos + 1, held[:, None]),
                    self._scale(a), a["kv_rank"],
                    lambda: dense(expand=False))
                return jnp.einsum("bchr,rhv->bchv", ctx,
                                  wkv[..., a["nope"]:],
                                  preferred_element_type=jnp.float32), walk
            qf = self._absorbed(a, q_nope[:, 0], q_rope[:, 0], wkv, lanes)
            ends = _paged.walk_lengths(table, pos[:, 0] + 1, page, npages)
            ctx, walk = _latent.latent_paged_attention(
                qf, lat, fi, table, ends, self._scale(a), a["kv_rank"],
                lambda: dense(expand=False)[:, 0])
            o = jnp.einsum("bhr,rhv->bhv", ctx, wkv[..., a["nope"]:],
                           preferred_element_type=jnp.float32)
        return o[:, None], walk

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

    def _attend_dense(self, lw, a, q_nope, q_rope, rows, chosen,
                      expand=True):
        """``_attend`` of every query against ALL the slot's rows ``(B, T,
        W)``, masked to ``chosen`` ``(B, C, T)``, a block of heads at a
        time so that the scores fit."""
        B, C, hh, _ = q_nope.shape
        T = rows.shape[1]
        hb = max(1, min(hh, _INDEX_BLOCK_BYTES // max(1, B * C * T * 4)))
        while hh % hb:
            hb -= 1
        wkv = self._wkv(lw, a).reshape(a["kv_rank"], hh // hb, hb, -1)
        blocks = lambda q: jnp.moveaxis(
            q.reshape(B, C, hh // hb, hb, q.shape[-1]), 2, 0)
        out = jax.lax.map(
            lambda xs: self._attend(lw, a, xs[0], xs[1], rows, chosen,
                                    "bchf,btf->bcht", "bcht,btr->bchr",
                                    wkv=xs[2], expand=expand),
            (blocks(q_nope), blocks(q_rope), jnp.moveaxis(wkv, 1, 0)))
        return jnp.moveaxis(out, 0, 2).reshape(B, C, hh, -1)

    @staticmethod
    def _wkv(lw, a):
        """``W_kvb`` by head: ``(kv_rank, heads, nope + v)``."""
        return lw["kvb_weight"].reshape(a["kv_rank"], a["heads"],
                                        a["nope"] + a["v"])

    @staticmethod
    def _scale(a):
        return 1.0 / (a["nope"] + a["rope"]) ** 0.5

    @staticmethod
    def _absorbed(a, q_nope, q_rope, wkv, lanes):
        """The queries taken into the latent space (``W_kvb``'s key half)
        beside their rotary part, padded to the stored rows' ``lanes``:
        ``[q_nope W_kvb,k | q_rope | 0]`` in the queries' dtype."""
        qabs = jnp.einsum("...hd,rhd->...hr", q_nope, wkv[..., :a["nope"]],
                          preferred_element_type=jnp.float32
                          ).astype(q_nope.dtype)
        return _pad_last(jnp.concatenate([qabs, q_rope], axis=-1), lanes)

    def _attend(self, lw, a, q_nope, q_rope, rows, ok, scores, context,
                wkv=None, expand=True):
        """Latent attention in the absorbed form: the queries are taken
        into the latent space (``W_kvb``'s key half), scored against the
        stored rows ``[c_kv | k_rope | 0]`` in one contraction, and the
        context comes back through ``W_kvb``'s value half (``wkv``: that
        matrix for the heads given, all of them by default) — or, without
        ``expand``, is returned as it is, ``(..., heads, kv_rank)``."""
        r = a["kv_rank"]
        if wkv is None:
            wkv = self._wkv(lw, a)
        qf = self._absorbed(a, q_nope, q_rope, wkv, rows.shape[-1])
        s = jnp.einsum(scores, qf, rows,
                       preferred_element_type=jnp.float32)
        s = s * self._scale(a)
        s = jnp.where(ok[:, :, None, :], s, -1e30)
        p = jax.nn.softmax(s, axis=-1).astype(rows.dtype)
        ctx = jnp.einsum(context, p, rows[..., :r],
                         preferred_element_type=jnp.float32
                         ).astype(rows.dtype)
        if not expand:
            return ctx
        return jnp.einsum("bchr,rhv->bchv", ctx, wkv[..., a["nope"]:],
                          preferred_element_type=jnp.float32)

    # -- feed-forward --------------------------------------------------- #
    def _ffn(self, lw, f, h, layer=None):
        """``(y (N, H), local expert ids (N, top_k) or None)``; an id
        outside ``[0, held)`` is an expert another chip holds.  With
        ``layer`` the routed experts' two arrays are a stacked run's and
        ``layer`` the index into it."""
        if f["kind"] == "swiglu":
            return moe.swiglu(h, lw["gu_weight"], lw["down_weight"]), None
        lo, n = f["held"]
        with jax.named_scope("mx.moe_route"):
            idx, wts = moe.route(h, lw["router_weight"],
                                 lw["router_bias"], f["top_k"], f["scale"])
        with jax.named_scope("mx.moe_experts"):
            y, _ = moe.routed_experts(h, idx, wts, lw["egu_weight"],
                                      lw["edown_weight"], lo, layer)
        with jax.named_scope("mx.moe_shared"):
            y = y + moe.swiglu(h, lw["sgu_weight"], lw["sdown_weight"])
        local = idx - lo
        return y, jnp.where((local >= 0) & (local < n), local, -1)
