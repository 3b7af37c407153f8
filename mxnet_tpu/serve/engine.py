"""Paged slot-pool decode programs — the device side of the continuous-
batching server (``mxnet_tpu.serve.server``).

The resident K/V store is a PAGE POOL: one ``(NL, NPAGES, PAGE, KV·D)``
array pair shared by all in-flight sequences — one layer's page is one
contiguous ``(PAGE, KV·D)`` block, a token a lane-dense row of it (an
int8 pool: codes in that layout + ``(NL, NPAGES, KV)`` scales) —
addressed through per-slot
page tables (``(S, MAXP)`` int32 rows, host-owned, passed as TRACED
OPERANDS on every dispatch — allocation churn changes table VALUES,
never shapes, so the compiled programs survive any admit/retire/append
pattern with zero retraces).  A sequence holds only the pages its tokens
occupy, so a long-context ragged mix packs ~T/len(x) more sequences into
the same HBM than the dense per-slot ``T``-column layout this replaces.
Per-slot position / last-token / active / stop / sampling-key /
wall-clock-deadline state rides alongside, so admission and retirement —
including deadline expiry against the step's ``now`` operand — stay
device-side masked updates: no recompile, no host sync in the step.

The one-past-the-end page id ``NPAGES`` is the table SENTINEL: scatters
through it DROP, and gathers CLAMP it onto the last page, whose values
never count — the position mask gives them weight exactly 0, or what was
built from them drops on the way back.  Retired/idle slots
carry all-sentinel rows, which is what makes masked zombie lanes safe —
a freed (or reused) page can never be corrupted by a slot that no longer
owns it, and the overwrite-before-unmask invariant (a decode step at
position ``q`` writes its own column before attending) covers everything
a live slot can read.

Every executable below reads the pool with ONE gather at ``(layer, page
id)`` out of the whole array and writes it with a scatter at explicit
``(layer, page[, row])`` indices (``models.decoding._pages_get`` /
``_pages_set`` / ``_rows_set``).  With the lane-dense layout those two
idioms are what lets the chip's compiler keep the pool as declared and
update the donated arrays in place: no per-layer slice, no layout
conversion, no second pool as scratch (``tests/test_serve_pool_layout.py``
holds a TPU-target compile to it; PERF.md PR 27 has the times).

Compiled units per pool size ``S``:

- **step** — ``_DecodeEngine.pool_token_paged`` (the stacked-layer scan
  gathering/scattering through the page tables) + per-slot sampling +
  retirement flags, jitted with the page pools donated: ONE executable
  dispatch per decode step (``tests/test_serve.py`` pins the count).
- **admit(A_bucket, P_bucket)** — ONE causal prefill over an ``(A, P)``
  block of right-padded prompts; the K/V stream lands in the admitted
  slots' RESERVED PAGES via one masked page scatter (rows/pages beyond
  the wave aim at the sentinel and drop), and the ``A`` first tokens +
  done flags come back in one readback.
- **admit_hit(A_bucket)** — prefix-cache hit admission: NO model
  forward at all.  The slot enters at ``pos = L - 1`` mapping the shared
  prefix pages read-only (plus at most one copy-on-write page copy per
  row when the prompt ends exactly on a shared page boundary), and the
  next regular step recomputes the last prompt token — sampling with
  ``fold_in(key, L-1)``, the exact key the batched admit uses, so hit
  and miss streams are token-identical while a hit's TTFT is one step.
- **chunk(C_bucket)** — chunked prefill: one ``C``-token slice of a
  single long prompt runs against the slot's page-table row
  (``_DecodeEngine.chunk_tokens``); the landing offset is a traced
  scalar, so a prompt of any length streams in over ``ceil(L/C)``
  dispatches of the same compiled program.  Only the FINAL chunk's
  masked scatter activates the slot.
- **sampling** — per-slot ``fold_in(key_slot, pos_slot)`` +
  ``categorical`` on that slot's row, matching ``kv_generate``'s
  batch-1 stream for the same seed token-for-token (greedy is argmax).

``PagePool`` is the host-side free-list allocator with REFCOUNTS: the
prefix cache maps one page into many slots' tables, and a page returns
to the free list only when its last owner (slot or cache index) lets go.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import telemetry
from ..base import MXNetError
from ..models.decoding import (_DecodeEngine, _TRACE_LOCK, _kv_requant,
                               _pages_get, _pages_set, decode_engine,
                               _KV_CODE_DTYPE, _KV_SCALE_DTYPE)
from . import schema

__all__ = ["PoolPrograms", "PagePool", "pool_state_init",
           "pool_state_grow", "pool_state_bytes",
           "admit_scratch_bytes"]


# per-slot scalar state bytes, derived from the operand schema's
# SLOT_STATE layout (pos/tok/stop/spec int32 + active bool + PRNG key
# 2x uint32 + deadline float32 = 29) — see pool_state_init, which
# builds the columns in the same declared order
_SLOT_STATE_BYTES = schema.slot_state_bytes()

# meta-row column maps, derived from the same declarations the jitted
# bodies below unpack through (tracelint TL017 holds these bodies to
# the accessors — a hand-written column index is exactly the drift
# that threaded PR-13's deadline and PR-17's spec-depth through four
# scatter sites by eye)
_AM = schema.meta_cols("admit")
_HM = schema.meta_cols("hit")
_CM = schema.meta_cols("chunk")


class PagePool:
    """Host-side page allocator with refcounts (LIFO free list — a just-
    freed page is the hottest candidate for reuse).  Pages are ints in
    ``[0, num_pages)``; the COW prefix cache increfs shared pages into
    many owners, and a page returns to the free list only at refcount
    zero.  Purely host bookkeeping: the device never sees this object,
    only the page-table rows built from it."""

    def __init__(self, num_pages):
        self.num_pages = int(num_pages)
        self._free = list(range(self.num_pages - 1, -1, -1))
        self._ref = {}

    @property
    def free_pages(self):
        return len(self._free)

    @property
    def in_use(self):
        return self.num_pages - len(self._free)

    def alloc(self, n):
        """``n`` fresh pages at refcount 1, or ``None`` if the pool
        cannot cover the request (nothing is allocated on failure —
        admission is all-or-nothing so a half-reserved request can
        never deadlock the pool)."""
        if n > len(self._free):
            return None
        out = [self._free.pop() for _ in range(n)]
        for p in out:
            self._ref[p] = 1
        return out

    def incref(self, page):
        self._ref[page] += 1

    def decref(self, page):
        """Drop one owner; frees the page at refcount zero."""
        r = self._ref[page] - 1
        if r:
            self._ref[page] = r
        else:
            del self._ref[page]
            self._free.append(page)

    def grow(self, new_num):
        """Extend the pool with pages ``[num_pages, new_num)`` (pool
        growth allocates a bigger device array; the new ids join the
        free list)."""
        if new_num < self.num_pages:
            raise MXNetError(f"page pool can only grow: "
                             f"{self.num_pages} -> {new_num}")
        self._free.extend(range(new_num - 1, self.num_pages - 1, -1))
        self.num_pages = int(new_num)


def pool_state_bytes(progs, num_slots=None, num_pages=None):
    """Device bytes of the pool state at ``num_slots`` slots /
    ``num_pages`` pages (defaults: the programs' own geometry; the
    default page count is ``num_slots * MAXP`` — the dense-equivalent
    allotment, so the figure stays LINEAR in the slot count and the
    budget thresholds keep their PR-10 meaning).  Priced at the
    programs' OWN ``kv_dtype`` via ``page_bytes()`` — an int8 pool's
    pages cost codes + per-page scales, not the f32 itemsize.  Pure
    arithmetic, so ``DecodeServer`` can price a growth (or the initial
    pool) BEFORE allocating it; ``tests/test_memory.py`` pins this
    equal to the allocator-reported ``nbytes_of`` of the live state
    for BOTH dtypes."""
    S = progs.S if num_slots is None else int(num_slots)
    npages = S * progs.maxp if num_pages is None else int(num_pages)
    return npages * progs.page_bytes() \
        + S * (_SLOT_STATE_BYTES + progs.slot_state_bytes()) \
        + progs.window_pages * progs.window_page_bytes()


def admit_scratch_bytes(progs, a_bucket):
    """Transient device bytes of an ``a_bucket``-row admission wave:
    the dense ``(A, Tp)`` prefill scratch cache pair at the model's
    NATIVE cache dtype plus the wave's slot-state rows.  The admit
    program always prefills into a dense float scratch and quantizes
    on the page scatter, so this figure is dtype-INDEPENDENT — under
    ``kv_dtype="int8"`` it deliberately does NOT shrink with
    ``pool_state_bytes`` (which it equals for a native-dtype pool at
    the dense-equivalent page count), keeping the budget clamp honest
    about the admission spike."""
    e = progs.eng
    A = int(a_bucket)
    if progs.layered:
        # a layered engine prefills straight through the page tables:
        # no dense scratch cache, only the wave's slot-state rows
        return A * _SLOT_STATE_BYTES
    return 2 * e.NL * A * e.KV * progs.Tp * e.D \
        * jnp.dtype(e.cdtype).itemsize + A * _SLOT_STATE_BYTES


def _kv_pool_zeros(progs):
    """The uniform K/V kind's pool pair."""
    eng = progs.eng
    # one layer's page is one contiguous, lane-dense (page, KV·D) block
    shape = (eng.NL, progs.num_pages, progs.page, eng.KV * eng.D)
    if progs.quant_kv:
        # int8 pool: each of K and V is a (codes, scales) PAIR riding
        # ONE state slot as a pytree — every executable threads, donates
        # and scans it exactly like the single f32 array it replaces
        sshape = (eng.NL, progs.num_pages, eng.KV)
        return ((jnp.zeros(shape, _KV_CODE_DTYPE),
                 jnp.zeros(sshape, _KV_SCALE_DTYPE)),
                (jnp.zeros(shape, _KV_CODE_DTYPE),
                 jnp.zeros(sshape, _KV_SCALE_DTYPE)))
    return jnp.zeros(shape, eng.cdtype), jnp.zeros(shape, eng.cdtype)


def pool_state_init(progs, device=None):
    """Fresh all-idle pool state for ``progs``: ``(kp, vp, pos, tok,
    active, stop, keys, deadline, spec)`` — the traced-operand set every
    step/admit/hit/chunk/verify executable threads through (the page
    TABLES are not in it: they are host numpy, rebuilt per dispatch).
    ``deadline`` is the per-slot wall-clock retirement budget (seconds
    on the server's monotonic epoch; ``+inf`` = none), checked ON
    DEVICE by the step against its ``now`` operand; ``spec`` is the
    per-slot speculation-depth cap (0 = never speculate) the verify
    program clamps draft acceptance against — riding the slot-state
    vector like keys and deadlines do, so per-request depth never
    shapes a trace.

    Every array is COMMITTED to ``device`` (default: the backend's
    first device).  jit keys its executable cache on each argument's
    committed placement, so an uncommitted ``jnp.zeros`` init state
    would compile one signature for the first step and a SECOND
    (identical-aval) signature once the state is jit outputs — a
    silent ~seconds retrace on the serving hot path at steady state."""
    S = progs.S
    eng = progs.eng
    if device is None:
        device = jax.devices()[0]
    if progs.layered:
        # the declared row kinds of the model's cache kinds: main-table
        # arrays in ``kp``, window-table or slot-table arrays in ``vp``
        kpool, vpool = eng.pool_zeros(progs.num_pages, progs.window_pages,
                                      progs.page, S)
    else:
        kpool, vpool = _kv_pool_zeros(progs)
    state = (kpool,                          # K page pool
             vpool,                          # V page pool
             jnp.zeros((S,), jnp.int32),     # pos: next write index
             jnp.zeros((S,), jnp.int32),     # tok: last sampled
             jnp.zeros((S,), jnp.bool_),     # active
             jnp.zeros((S,), jnp.int32),     # stop: retire position
             jnp.zeros((S, 2), jnp.uint32),  # per-slot PRNG keys
             jnp.full((S,), jnp.inf, jnp.float32),  # per-slot deadline
             jnp.zeros((S,), jnp.int32))     # spec: speculation depth
    return jax.device_put(state, device)


def pool_state_grow(state, new_s, new_pages=None):
    """Pad the slot-axis arrays of ``state`` up to ``new_s`` slots and
    (optionally) the page pools up to ``new_pages`` pages — new lanes
    come up idle, new pages come up zero (the caller hands their ids to
    its ``PagePool``).  Runs eagerly — pool growth happens at a step
    boundary, a handful of times per server lifetime.  NOTE the table
    sentinel moves with the page count: rows must be rebuilt against
    the grown pool before the next dispatch (the server regenerates
    them from its allocator every dispatch, so this is automatic)."""
    kp, vp, pos, tok, active, stop, keys, dl, spec = state
    if isinstance(kp, tuple) and not isinstance(vp, tuple):
        raise MXNetError("a pool of declared row kinds with a window "
                         "table has one size: pin a single pool_sizes "
                         "entry")
    kp0 = kp[0] if isinstance(kp, tuple) else kp
    grow = new_s - pos.shape[0]
    if grow <= 0:
        raise MXNetError(f"pool can only grow: {pos.shape[0]} -> "
                         f"{new_s}")
    pgrow = 0 if new_pages is None else int(new_pages) - kp0.shape[1]
    if pgrow < 0:
        raise MXNetError(f"page pool can only grow: {kp0.shape[1]} -> "
                         f"{new_pages}")
    pad = lambda a, axis, n: jnp.pad(
        a, [(0, n) if i == axis else (0, 0) for i in range(a.ndim)])
    # int8 pools pad codes AND scales along the shared page axis
    padp = lambda p, n: (pad(p[0], 1, n), pad(p[1], 1, n)) \
        if isinstance(p, tuple) else pad(p, 1, n)
    grown = (padp(kp, pgrow), padp(vp, pgrow), pad(pos, 0, grow),
             pad(tok, 0, grow), pad(active, 0, grow), pad(stop, 0, grow),
             pad(keys, 0, grow),
             # idle-lane deadlines pad as +inf, matching pool_state_init
             jnp.pad(dl, (0, grow), constant_values=jnp.inf),
             pad(spec, 0, grow))
    # committed placement, same contract as pool_state_init
    return jax.device_put(grown, list(kp0.devices())[0])


class PoolPrograms:
    """Compiled decode-step + admission executables for ONE pool size
    (slot count) ``num_slots`` against a ``num_pages``-page pool of
    ``page_size``-token pages (cache horizon ``max_total`` rounded up
    to whole pages).  ``temperature``/``top_k``/``eos_id`` are
    server-level static config (they shape the compiled sampler);
    per-request variation rides in the operands (seed key, stop
    position, page-table rows)."""

    def __init__(self, model, num_slots, max_total, temperature=0.0,
                 top_k=0, eos_id=None, weights="native",
                 telemetry_label=None, page_size=16, num_pages=None,
                 kv_dtype="native", window_pages=None, max_chunk=None):
        self.model = model
        self.telemetry_label = telemetry_label
        # "native" stores pages at the engine cache dtype (the exact
        # pre-PR behavior); "int8" stores codes + per-page-per-head f32
        # scales, quantized inside the SAME write executables and
        # dequantized inside the scan body on read (lossy — PARITY.md
        # pins the tolerance)
        if kv_dtype not in ("native", "int8"):
            raise MXNetError(f"kv_dtype must be 'native' or 'int8', "
                             f"got {kv_dtype!r}")
        self.kv_dtype = kv_dtype
        self.quant_kv = kv_dtype == "int8"
        self.S, self.T = int(num_slots), int(max_total)
        self.page = int(page_size)
        if self.page < 1:
            raise MXNetError(f"page_size must be >= 1, got {self.page}")
        # cache horizon rounded up to whole pages: the step's attention
        # span and every table row cover MAXP pages
        self.Tp = -(-self.T // self.page) * self.page
        self.maxp = self.Tp // self.page
        self.temperature, self.top_k = float(temperature), int(top_k)
        self.eos_id = None if eos_id is None else int(eos_id)
        self.weights = weights
        self.eng = decode_engine(model, self.S, 1, self.Tp, temperature,
                                 top_k, "batched", weights, "auto")
        # a layered model with no layer under the main table (every layer's
        # memory under the slot table) holds no pages: none is reserved,
        # and its page pool is empty
        self.paged = not (self.eng.mode == "layered"
                          and getattr(self.eng, "window", None) is None
                          and self.eng.main_page_bytes(self.page) == 0)
        self.num_pages = (self.S * self.maxp if self.paged else 0) \
            if num_pages is None else int(num_pages)
        if self.paged and self.num_pages < 1:
            raise MXNetError(f"num_pages must be >= 1, "
                             f"got {self.num_pages}")
        # one-past-the-end page id: gathers clamp it, scatters drop
        self.sentinel = self.num_pages
        # a layered engine (per-layer kinds) brings its own row kinds and,
        # where a kind keeps a window, a second page table: a ring of
        # ``ring`` entries a slot, wide enough for the window plus the
        # longest chunk a dispatch runs
        self.layered = self.eng.mode == "layered"
        self.window = getattr(self.eng, "window", None)
        # cache kinds kept under the SLOT table (a recurrent layer's
        # state): one entry a slot beside its pages
        self.slot_kinds = tuple(getattr(self.eng, "slot_kinds", ()))
        self.ring, self.window_pages = 0, 0
        if self.layered and self.quant_kv:
            kinds = sorted({d["cache"] for d in self.eng.desc})
            raise MXNetError("kv_dtype='int8' is not implemented for "
                             "pools of declared row kinds "
                             f"({', '.join(kinds)})")
        if self.window is not None:
            self.ring = self.eng.window_span_pages(
                self.page, int(max_chunk or self.page)) + 1
            self.window_pages = self.S * self.ring \
                if window_pages is None else int(window_pages)
        if self.eng.mode not in ("stacked", "layered"):
            raise MXNetError(
                "slot-pool serving needs the stacked-layer scan decode "
                "step (uniform GPT/Llama stack — see models/decoding."
                "stacked_decode_supported); this model resolved to "
                f"{self.eng.mode!r}.  MXNET_SERVE_SYNC=1 serves it "
                "through the synchronous kv_generate fallback instead.")
        # whether the step walks each slot's pages as far as its length
        # (the paged-attention kernel) or builds the T-wide view
        self.step_walks = not self.layered and self.eng.walks_pages(
            self.page, self.quant_kv)
        # the server owns the weight operands (engine refs dropped so
        # the cached executables' closures can't pin stale arrays)
        self.operands = self.eng.take_operands()
        self._step = None
        self._admits = {}          # (A, P) bucket pair -> jitted fn
        self._hits = {}            # A bucket -> jitted hit-admission fn
        self._chunks = {}          # C bucket -> jitted chunk-prefill fn
        self._verifies = {}        # k bucket -> jitted verify fn

    def page_bytes(self):
        """Device bytes of ONE page across all layers, K and V pools
        together — the pricing unit ``pool_state_bytes`` scales.  An
        int8 page costs its codes (1 byte/element) plus one f32 scale
        per (layer, KV head) for each of K and V — the ~4x shrink vs a
        float32 pool is what converts an HBM budget into ~2x resident
        sequences at equal bytes."""
        e = self.eng
        if self.layered:
            return e.main_page_bytes(self.page)
        if self.quant_kv:
            return schema.kv_page_int8_bytes(e.NL, e.KV, self.page,
                                             e.D)
        return 2 * e.NL * e.KV * self.page * e.D \
            * jnp.dtype(e.cdtype).itemsize

    def window_page_bytes(self):
        """Device bytes of ONE window-table page over every layer that
        keeps a window (0 where the model has none)."""
        return self.eng.window_page_bytes(self.page) \
            if self.window is not None else 0

    def slot_state_bytes(self):
        """Device bytes ONE slot keeps under the slot table over every
        layer with such state (0 where the model has none) — beside the
        scalar columns, which ``pool_state_bytes`` prices itself."""
        return self.eng.slot_state_bytes() if self.slot_kinds else 0

    def pages_for(self, total_len):
        """Pages a sequence of ``total_len`` cached positions needs (none
        where the model holds no pages)."""
        return -(-int(total_len) // self.page) if self.paged else 0

    def key_pages_for(self, c_bucket, reach):
        """How many of a slot's table pages a ``c_bucket``-token chunk
        whose last token is position ``reach - 1`` is compiled to read:
        ``None`` (all ``maxp``) but for a layered engine's long chunks,
        whose selecting attention scores every cached position — those
        take the smallest quarter of ``maxp`` that covers ``reach``, so a
        long prompt's early chunks do not pay for its whole horizon (four
        executables a long bucket, all met while the first long prompt
        streams in)."""
        if not self.layered or self.eng.dense_chunk is None \
                or c_bucket < self.eng.dense_chunk:
            return None
        need = self.pages_for(reach)
        return next(kp for kp in (-(-self.maxp * i // 4) for i in (1, 2, 3, 4))
                    if kp >= need)

    # -- sampling ------------------------------------------------------- #
    @jax.named_scope("mx.head")
    def _sample_slots(self, keys, logits, pos):
        """Per-slot next token: slot ``i`` draws with
        ``fold_in(keys[i], pos[i])`` over its own logits row — the exact
        key/categorical stream ``kv_generate(seed=...)`` runs at batch 1,
        so a served request reproduces the offline stream.  The
        temperature/top_k prep is ``_DecodeEngine._sample_logits``, the
        SAME prep the offline sampler draws from."""
        lg = self.eng._sample_logits(logits)
        if lg is None:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)

        def draw(key, row, p):
            return jax.random.categorical(
                jax.random.fold_in(key, p), row[None, :], axis=-1)[0]

        return jax.vmap(draw)(keys, lg, pos).astype(jnp.int32)

    def _retire_flags(self, active, nxt, newpos, stop, now=None,
                      deadline=None):
        done = active & (newpos >= stop)
        if self.eos_id is not None:
            done = done | (active & (nxt == self.eos_id))
        if now is not None:
            # wall-clock deadline expiry, folded into the SAME done
            # mask as EOS/budget: retirement stays a masked device-side
            # update, never an extra dispatch (inf = no deadline)
            done = done | (active & (now >= deadline))
        return done

    # -- the decode step ------------------------------------------------ #
    def step_fn(self):
        """The jitted pool step (cached): ``step(param_vals, q8, sw,
        now, pt, kp, vp, pos, tok, active, stop, keys, deadline)`` → new
        state + ``(emit_tok, emitted, done)`` readback arrays.  ``now``
        is the host's monotonic clock (server-epoch seconds, a float32
        scalar operand refreshed per dispatch); ``pt`` is the ``(S,
        MAXP)`` int32 page-table block — BOTH are operands, not
        constants, so neither clock ticks nor page churn ever retrace.
        Page pools are donated — steady-state serving is one
        donated-buffer executable dispatch per emitted token wave."""
        if self._step is not None:
            return self._step
        from ..gluon.parameter import params_swapped

        eng = self
        deng = self.eng
        page = self.page

        def step(param_vals, q8, sw, now, pt, kp, vp, pos, tok, active,
                 stop, keys, dl, spec):
            # state under the slot table is rewritten by the step itself:
            # only a live slot's may be
            live = {"live": active} if self.slot_kinds else {}
            with _TRACE_LOCK, params_swapped(deng.params, param_vals):
                logits, kp, vp, *aux = deng.pool_token_paged(
                    tok, pos, kp, vp, pt, page, sw, q8, **live)
                nxt = eng._sample_slots(keys, logits, pos)
            nxt = jnp.where(active, nxt, tok)
            newpos = jnp.where(active, pos + 1, pos)
            done = eng._retire_flags(active, nxt, newpos, stop, now, dl)
            emitted = active
            new_state = (kp, vp, newpos, nxt, active & ~done, stop,
                         keys, dl, spec)
            # an engine with counters of its own (experts' load, keys
            # selected) hands them back reduced over the live slots: a
            # fourth readback array the scheduler adds up
            extra = (deng.step_counters(aux[0], active),) \
                if aux and aux[0] else ()
            return new_state, (nxt, emitted, done) + extra

        self._step = telemetry.instrument_jit(
            jax.jit(step, donate_argnums=schema.jit_donate("step", step)),
            "serve.step",
            key=(self.telemetry_label, self.S),
            fields={"server": self.telemetry_label, "pool": self.S,
                    "num_pages": self.num_pages,
                    "cache_bytes": self.num_pages * self.page_bytes()})
        return self._step

    # -- admission ------------------------------------------------------ #
    def admit_fn(self, a_bucket, p_bucket):
        """The jitted BATCHED admission program for a wave of up to
        ``a_bucket`` prompts right-padded to ``p_bucket`` tokens (cached
        per ``(A, P)`` bucket pair): ``admit(param_vals, prompts
        (A, P) int32, meta (A, 6) int32 rows = [valid, true_len, slot,
        stop_pos, seed, spec_depth], dls (A,) float32 per-row deadlines,
        pages (A, NPB) int32 reserved-page rows, zpages (A, MAXP) int32
        full reserved rows (sentinel-padded; int8 pools zero these
        pages' SCALES before anything writes — see below), kp, vp, pos,
        tok, active, stop, keys, dl, spec)`` → new state + ``(first_tok
        (A,), done (A,))``.

        ONE causal prefill over the whole block fills a dense ``(A,
        Ppad)`` scratch cache, which lands in the wave's RESERVED PAGES
        via one masked page scatter: row ``i``'s page ``j`` goes to
        pool page ``pages[i, j]``; idle rows and unreserved tail pages
        carry the sentinel and are DROPPED, so a half-full wave (or a
        short prompt) reuses the same compiled program.  The first
        continuation token of each row is sampled at its own
        ``true_len - 1``; a request whose budget is a single token (or
        whose first token is EOS) comes back ``done`` and never
        occupies a step lane.  Admitting a wave of k requests is one
        H2D of the prompt block + meta + page rows and ONE executable
        dispatch, not k of either."""
        key2 = (int(a_bucket), int(p_bucket))
        fn = self._admits.get(key2)
        if fn is not None:
            return fn
        A, P = key2
        if not 0 < P <= self.T:
            raise MXNetError(f"prompt bucket {P} outside cache "
                             f"length {self.T}")
        if A < 1:
            raise MXNetError(f"admission bucket {A} must be >= 1")
        from ..gluon.parameter import params_swapped

        page = self.page
        ppad = -(-P // page) * page     # prompt bucket in whole pages
        npb = ppad // page
        if self.layered:
            # the layered engine runs the wave through each row's own
            # table rows (``zpages``): no dense scratch, no page scatter
            peng = self.eng
        else:
            peng = _DecodeEngine(self.model, A, P, ppad,
                                 self.temperature, self.top_k, "batched",
                                 self.weights, "auto")
            peng.take_operands()   # server-held operands are the only refs
            NL, KV, D = peng.NL, peng.KV, peng.D

        @jax.named_scope("mx.page_write")
        def land(kp, vp, ck1, cv1, true_len, pages, zpages):
            """The stacked engine's dense prefill scratch into the wave's
            reserved pages."""
            # page scatter: the dense (A, Ppad) scratch splits into A*NPB
            # page-shaped rows that land at their reserved pool pages in
            # one masked scatter per array (sentinel rows DROP)
            tgt_pg = pages.reshape(A * npb)
            if self.quant_kv:
                # the padded tail's garbage columns are unreachable in
                # the f32 pool but would poison the per-page SCALES
                # here — zero them before the per-page quantization
                colmask = jnp.arange(ppad, dtype=jnp.int32)[None] \
                    < true_len[:, None]                     # (A, ppad)
                ck1 = jnp.where(colmask[None, :, None, :, None],
                                ck1, 0)
                cv1 = jnp.where(colmask[None, :, None, :, None],
                                cv1, 0)
            # (NL, A, KV, Ppad, D) scratch -> (NL, A*NPB, page, KV·D)
            # pages in the pool's row layout
            c1, v1 = (c.reshape(NL, A, KV, npb, page, D)
                       .transpose(0, 1, 3, 4, 2, 5)
                       .reshape(NL, A * npb, page, KV * D)
                      for c in (ck1, cv1))
            if self.quant_kv:
                # fresh whole pages: plain per-page quantization (no
                # floor — nothing lived in these pages), then ONE
                # masked scatter each for codes and scales
                qc1, sc1 = _kv_requant(c1, 0.0, KV)
                qv1, sv1 = _kv_requant(v1, 0.0, KV)
                (kpc, kps), (vpc, vps) = kp, vp
                # recycled-page reset: the pool free list is host-only
                # bookkeeping, so a reallocated page still carries its
                # previous tenant's codes AND scale.  A zero SCALE is a
                # full reset — stale codes dequantize to exact zeros
                # and the first RMW requantizes from floor 0.0, so the
                # old tenant's dynamic range can never ratchet the new
                # tenant's scale.  ``zpages`` holds every page the wave
                # reserved (decode-frontier pages included — those are
                # first WRITTEN by the step/verify RMWs); the prompt
                # pages' scales are immediately overwritten by the
                # scatter below.  Sentinel entries DROP.
                zf = zpages.reshape(A * zpages.shape[1])
                kps = _pages_set(kps, zf, 0.0)
                vps = _pages_set(vps, zf, 0.0)
                kp = (_pages_set(kpc, tgt_pg, qc1),
                      _pages_set(kps, tgt_pg, sc1))
                vp = (_pages_set(vpc, tgt_pg, qv1),
                      _pages_set(vps, tgt_pg, sv1))
            else:
                kp = _pages_set(kp, tgt_pg, c1)
                vp = _pages_set(vp, tgt_pg, v1)
            return kp, vp

        def admit(param_vals, prompts, meta, dls, pages, zpages, kp, vp,
                  pos, tok, active, stop, keys, dl, spec):
            valid = meta[:, _AM["valid"]] != 0
            true_len = meta[:, _AM["true_len"]]
            slot = meta[:, _AM["slot"]]
            stop_pos = meta[:, _AM["stop_pos"]]
            seed = meta[:, _AM["seed"]]
            spec_d = meta[:, _AM["spec_depth"]]
            keys_a = jax.vmap(jax.random.PRNGKey)(seed)       # (A, 2)
            # masked slot-state scatter: invalid rows target slot S
            # (out of bounds) and drop; valid rows carry distinct
            # host-assigned slots
            tgt = jnp.where(valid, slot, self.S)
            with _TRACE_LOCK, params_swapped(peng.params, param_vals):
                if self.layered:
                    logits, kp, vp = peng.admit_tokens(
                        prompts, true_len - 1, zpages, page, kp, vp,
                        slots=tgt)
                else:
                    ck1, cv1 = peng.zero_caches()
                    logits, ck1, cv1 = peng.prefill_batch(
                        prompts, ck1, cv1, last_index=true_len - 1)
                first = self._sample_slots(keys_a, logits,
                                           true_len - 1)
            done = stop_pos <= true_len
            if self.eos_id is not None:
                done = done | (first == self.eos_id)
            if not self.layered:
                kp, vp = land(kp, vp, ck1, cv1, true_len, pages, zpages)
            pos = pos.at[tgt].set(true_len, mode="drop")
            tok = tok.at[tgt].set(first, mode="drop")
            active = active.at[tgt].set(~done, mode="drop")
            stop = stop.at[tgt].set(stop_pos, mode="drop")
            keys = keys.at[tgt].set(keys_a, mode="drop")
            dl = dl.at[tgt].set(dls, mode="drop")
            spec = spec.at[tgt].set(spec_d, mode="drop")
            new_state = (kp, vp, pos, tok, active, stop, keys, dl, spec)
            return new_state, (first, done)

        fn = telemetry.instrument_jit(
            jax.jit(admit,
                    donate_argnums=schema.jit_donate("admit", admit)),
            "serve.admit",
            key=(self.telemetry_label, self.S, A, P),
            fields={"server": self.telemetry_label, "pool": self.S,
                    "a_bucket": A, "p_bucket": P,
                    # the A-lane prefill cache pair — the admit
                    # program's transient scratch the budget check
                    # prices (pool_state_bytes(progs, A))
                    "cache_bytes": peng.cache_bytes()})
        self._admits[key2] = fn
        return fn

    def admit_hit_fn(self, a_bucket):
        """The jitted PREFIX-CACHE-HIT admission program for up to
        ``a_bucket`` rows (cached per bucket): ``hit(meta (A, 7) int32
        rows = [valid, true_len, slot, stop_pos, seed, last_tok,
        spec_depth], dls (A,), src (A,), dst (A,), zpages (A, MAXP)
        int32 fresh-owned-page rows (sentinel-padded; int8 pools zero
        these pages' SCALES), kp, vp, pos, tok, active, stop, keys, dl,
        spec)`` → new state (no readback: a hit emits nothing at
        admission).

        NO model forward runs: the host has already mapped the shared
        prefix pages into the slot's table row, so admission is a
        masked slot-state scatter — the slot enters at ``pos = L - 1``
        with ``tok`` = the last prompt token, and the next regular STEP
        recomputes that position (writing its K/V through the table and
        sampling with ``fold_in(key, L - 1)``, the exact admission key
        of the batched path — hit and miss token streams match while a
        hit's TTFT is one decode step and ZERO prefill dispatches).
        ``src``/``dst`` carry at most one copy-on-write page copy per
        row (needed only when the prompt ends exactly on a shared page
        boundary, where the recompute-write would land in a shared
        page); rows without a copy carry the sentinel on both sides
        (gather fills zeros, scatter drops)."""
        A = int(a_bucket)
        fn = self._hits.get(A)
        if fn is not None:
            return fn
        if A < 1:
            raise MXNetError(f"admission bucket {A} must be >= 1")

        def hit(meta, dls, src, dst, zpages, kp, vp, pos, tok, active,
                stop, keys, dl, spec):
            valid = meta[:, _HM["valid"]] != 0
            true_len = meta[:, _HM["true_len"]]
            slot = meta[:, _HM["slot"]]
            stop_pos = meta[:, _HM["stop_pos"]]
            seed = meta[:, _HM["seed"]]
            last_tok = meta[:, _HM["last_tok"]]
            spec_d = meta[:, _HM["spec_depth"]]
            keys_a = jax.vmap(jax.random.PRNGKey)(seed)       # (A, 2)
            # copy-on-write boundary pages: one gather + one masked
            # scatter covers the whole wave's copies.  An int8 pool
            # copies codes AND scales together — a page's quantization
            # grid is part of its identity, refcounted as one unit.
            with jax.named_scope("mx.page_write"):
                if self.quant_kv:
                    (kpc, kps), (vpc, vps) = kp, vp
                    kcb, ksb = _pages_get(kpc, src), _pages_get(kps, src)
                    vcb, vsb = _pages_get(vpc, src), _pages_get(vps, src)
                    # recycled-page reset (see admit_fn): zero the SCALES
                    # of every freshly-owned page in the wave — including
                    # each row's decode-frontier pages and the COW dst —
                    # AFTER the src gathers above (a src page can double as
                    # another row's fresh page when an eviction inside this
                    # same wave recycled it) and BEFORE the dst scatter
                    # below re-lands the copied scale.
                    zf = zpages.reshape(-1)
                    kps = _pages_set(kps, zf, 0.0)
                    vps = _pages_set(vps, zf, 0.0)
                    kp = (_pages_set(kpc, dst, kcb),
                          _pages_set(kps, dst, ksb))
                    vp = (_pages_set(vpc, dst, vcb),
                          _pages_set(vps, dst, vsb))
                elif self.layered:
                    # main-table arrays only: a window-table page is
                    # never copied, the planner cuts such a match back
                    kp = jax.tree.map(lambda a: _pages_set(
                        a, dst, _pages_get(a, src)), kp)
                else:
                    kp = _pages_set(kp, dst, _pages_get(kp, src))
                    vp = _pages_set(vp, dst, _pages_get(vp, src))
            tgt = jnp.where(valid, slot, self.S)
            pos = pos.at[tgt].set(true_len - 1, mode="drop")
            tok = tok.at[tgt].set(last_tok, mode="drop")
            active = active.at[tgt].set(valid, mode="drop")
            stop = stop.at[tgt].set(stop_pos, mode="drop")
            keys = keys.at[tgt].set(keys_a, mode="drop")
            dl = dl.at[tgt].set(dls, mode="drop")
            spec = spec.at[tgt].set(spec_d, mode="drop")
            return (kp, vp, pos, tok, active, stop, keys, dl, spec)

        fn = telemetry.instrument_jit(
            jax.jit(hit, donate_argnums=schema.jit_donate("hit", hit)),
            "serve.admit_hit",
            key=(self.telemetry_label, self.S, A),
            fields={"server": self.telemetry_label, "pool": self.S,
                    "a_bucket": A})
        self._hits[A] = fn
        return fn

    def chunk_fn(self, c_bucket, key_pages=None):
        """The jitted CHUNKED-PREFILL program for one ``C``-token slice
        of a single prompt (cached per chunk bucket, and per
        ``key_pages_for`` bound where that gives one): ``chunk(
        param_vals, q8, sw, toks (C,) int32, meta (8,) int32 =
        [final, slot, true_len, stop_pos, seed, nlast, off,
        spec_depth], dls scalar f32, ptrow (MAXP,) int32, zrow (MAXP,)
        int32 pages to scale-reset before the RMW (the slot's freshly
        allocated pages on its FIRST chunk, sentinel afterward), kp,
        vp, pos, tok, active, stop, keys, dl, spec)`` → new state +
        ``(first_tok, done)`` scalars.

        The slice occupies absolute positions ``off .. off+C-1`` of the
        slot whose page-table row is ``ptrow`` (``off`` is TRACED — one
        compiled program per chunk length serves every landing offset,
        so a prompt of any length streams in over ``ceil(L/C)``
        dispatches with no retrace).  Intermediate chunks pass
        ``final = 0``: their state scatter targets slot ``S`` and
        DROPS, so the slot stays invisible to the step until the final
        chunk samples the first continuation token (at ``true_len - 1``
        with ``fold_in(PRNGKey(seed), true_len - 1)`` — the batched
        path's exact admission key) and activates it.  Also the
        prefix-cache PARTIAL-hit suffix path: with shared pages mapped
        for ``off`` tokens, the same program fills only the divergent
        tail."""
        C = int(c_bucket)
        fn = self._chunks.get((C, key_pages))
        if fn is not None:
            return fn
        if not 0 < C <= self.Tp:
            raise MXNetError(f"chunk bucket {C} outside cache "
                             f"length {self.Tp}")
        from ..gluon.parameter import params_swapped

        deng = self.eng
        page = self.page
        bound = {} if key_pages is None else {"key_pages": int(key_pages)}

        def chunk(param_vals, q8, sw, toks, meta, dls, ptrow, zrow, kp,
                  vp, pos, tok, active, stop, keys, dl, spec):
            final = meta[_CM["final"]]
            slot = meta[_CM["slot"]]
            true_len = meta[_CM["true_len"]]
            stop_pos = meta[_CM["stop_pos"]]
            seed = meta[_CM["seed"]]
            nlast = meta[_CM["nlast"]]
            off = meta[_CM["off"]]
            spec_d = meta[_CM["spec_depth"]]
            key1 = jax.random.PRNGKey(seed)                   # (2,)
            if self.quant_kv:
                # recycled-page reset (see admit_fn): the chunk RMW
                # gathers each window page's scale as its requant
                # FLOOR, so stale scales must be zeroed before the
                # first chunk touches the slot's pages.  The host sends
                # the freshly-allocated rows in ``zrow`` on the first
                # chunk only (all-sentinel afterward — later chunks
                # must keep the ratchet of earlier ones).
                with jax.named_scope("mx.page_write"):
                    (kpc, kps), (vpc, vps) = kp, vp
                    kp = (kpc, _pages_set(kps, zrow, 0.0))
                    vp = (vpc, _pages_set(vps, zrow, 0.0))
            # where state lives under the slot table, EVERY chunk lands
            # in the request's slot (the scalar columns below only on the
            # final one)
            where = {"slot": slot} if self.slot_kinds else {}
            with _TRACE_LOCK, params_swapped(deng.params, param_vals):
                logits, kp, vp, *counted = deng.chunk_tokens(
                    toks, off, nlast, ptrow, page, kp, vp, sw, q8, **bound,
                    **where)
                first = self._sample_slots(key1[None], logits,
                                           (true_len - 1)[None])[0]
            done = stop_pos <= true_len
            if self.eos_id is not None:
                done = done | (first == self.eos_id)
            # scalar masked scatter: intermediate chunks target slot S
            # and drop — only the final chunk activates the slot
            tgt = jnp.where(final != 0, slot, self.S)
            pos = pos.at[tgt].set(true_len, mode="drop")
            tok = tok.at[tgt].set(first, mode="drop")
            active = active.at[tgt].set((final != 0) & ~done,
                                        mode="drop")
            stop = stop.at[tgt].set(stop_pos, mode="drop")
            keys = keys.at[tgt].set(key1, mode="drop")
            dl = dl.at[tgt].set(dls, mode="drop")
            spec = spec.at[tgt].set(spec_d, mode="drop")
            new_state = (kp, vp, pos, tok, active, stop, keys, dl, spec)
            # an engine that counts what a chunk did (experts' load,
            # latent rows walked) hands it back beside the first token: a
            # third readback
            return new_state, (first, done) + tuple(counted)

        fn = telemetry.instrument_jit(
            jax.jit(chunk,
                    donate_argnums=schema.jit_donate("chunk", chunk)),
            "serve.chunk",
            key=(self.telemetry_label, self.S, C, key_pages),
            fields={"server": self.telemetry_label, "pool": self.S,
                    "c_bucket": C, "key_pages": key_pages,
                    # one slot's dense gather scratch per layer slice
                    "cache_bytes": self.eng.cache_bytes() // self.S})
        self._chunks[(C, key_pages)] = fn
        return fn

    def verify_fn(self, k_bucket):
        """The jitted DRAFT-AND-VERIFY program for up to ``k_bucket``
        drafted tokens per slot (cached per k bucket, the PR-8 ladder
        discipline — compile count is bounded by the pinned k ladder,
        and accept/reject churn only changes operand VALUES):
        ``verify(param_vals, q8, sw, now, pt, drafts (S, k) int32,
        nd (S,) int32 drafts-actually-proposed per slot, kp, vp, pos,
        tok, active, stop, keys, dl, spec)`` → new state +
        ``(out (S, K), adv (S,), done (S,))``.

        ONE pool-step-shaped dispatch scores ``K = k + 1`` positions
        per slot (column 0 is the slot's last emitted token, not yet
        attended — a slot with ``nd = 0`` drafts runs a plain step
        through it): ``out[s, j]`` is the greedy token the plain step
        path would emit after position ``pos[s] + j``, so the device
        accepts the longest prefix where ``out[:, :-1]`` matches the
        drafts (clamped by ``nd``, the slot-state ``spec`` cap, EOS,
        and the slot's remaining ``stop`` budget) and advances
        ``adv = accepted + 1`` positions.  The block's K/V columns are
        already in the paged pool; a REJECTED tail needs no undo — its
        columns sit past the advanced ``pos``, masked off causally and
        overwritten before the next attend, and pages were reserved
        for the full budget at admission, so rollback is the length
        update alone (never a copy, never a refcount).  Greedy only:
        acceptance compares argmax tokens, which is exact for
        ``temperature == 0`` — the server keeps sampled slots on the
        plain depth-1 step (rejection sampling is out of scope)."""
        k = int(k_bucket)
        fn = self._verifies.get(k)
        if fn is not None:
            return fn
        if k < 1:
            raise MXNetError(f"verify bucket {k} must be >= 1")
        if self.slot_kinds:
            raise MXNetError(
                "draft-and-verify is not implemented for a model with "
                f"state under the slot table ({', '.join(self.slot_kinds)}"
                "): a rejected draft would need that state rolled back; "
                "serve it with spec=False")
        if self.layered:
            raise MXNetError(
                "draft-and-verify is not implemented for models served "
                "from a per-layer description (latent / windowed / "
                "routed kinds): serve them with spec=False")
        if self.temperature != 0.0:
            raise MXNetError(
                "draft-and-verify acceptance is exact only for greedy "
                f"decoding; temperature={self.temperature} slots must "
                "run the plain step (rejection sampling is out of "
                "scope for v1)")
        from ..gluon.parameter import params_swapped

        eng = self
        deng = self.eng
        page = self.page
        S, K = self.S, k + 1

        def verify(param_vals, q8, sw, now, pt, drafts, nd, kp, vp,
                   pos, tok, active, stop, keys, dl, spec):
            toks = jnp.concatenate([tok[:, None], drafts], axis=1)
            with _TRACE_LOCK, params_swapped(deng.params, param_vals):
                logits, kp, vp = deng.pool_verify_paged(
                    toks, pos, pt, page, kp, vp, sw, q8)
            with jax.named_scope("mx.head"):
                out = jnp.argmax(logits,
                                 axis=-1).astype(jnp.int32)  # (S, K)
            # longest accepted prefix: draft j survives iff every
            # draft 0..j matched the model's own emission AND j is
            # inside both the proposed count and the slot's spec cap
            lim = jnp.minimum(nd, spec)
            ok = (out[:, :-1] == drafts) & \
                (jnp.arange(K - 1, dtype=jnp.int32)[None, :] <
                 lim[:, None])
            acc = jnp.sum(jnp.cumprod(ok.astype(jnp.int32), axis=1),
                          axis=1)
            adv = acc + 1
            if self.eos_id is not None:
                # an emitted EOS ends the stream: nothing past the
                # first one may be emitted, exactly like the step path
                iK = jnp.arange(K, dtype=jnp.int32)
                first_eos = jnp.min(
                    jnp.where(out == self.eos_id, iK[None, :], K),
                    axis=1)
                adv = jnp.minimum(adv, first_eos + 1)
            # never advance past the slot's stop position (its last
            # block columns were computed but are not emitted)
            adv = jnp.minimum(adv, jnp.maximum(stop - pos, 1))
            adv = jnp.where(active, adv, 0)
            nxt = jnp.where(
                active,
                out[jnp.arange(S), jnp.maximum(adv, 1) - 1], tok)
            newpos = pos + adv
            done = eng._retire_flags(active, nxt, newpos, stop, now,
                                     dl)
            new_state = (kp, vp, newpos, nxt, active & ~done, stop,
                         keys, dl, spec)
            return new_state, (out, adv, done)

        fn = telemetry.instrument_jit(
            jax.jit(verify,
                    donate_argnums=schema.jit_donate("verify", verify)),
            "serve.verify",
            key=(self.telemetry_label, self.S, K),
            fields={"server": self.telemetry_label, "pool": self.S,
                    "k_bucket": k,
                    # the verify block widens the step's dense gather
                    # scratch K-fold at the attention tail
                    "cache_bytes": self.eng.cache_bytes()})
        self._verifies[k] = fn
        return fn
