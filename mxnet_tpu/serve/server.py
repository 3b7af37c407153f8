"""Continuous-batching decode server.

``DecodeServer`` turns the one-shot ``kv_generate`` decode stack into a
request-serving loop: callers ``submit()`` ragged requests at any time
and new sequences JOIN THE RUNNING COMPILED STEP at step boundaries
instead of waiting for a static batch to drain (the Orca / vLLM
continuous-batching design, rebuilt on this repo's trace discipline).

Scheduler shape (one ``pump()`` = one step boundary):

1. **admit** — gather EVERY currently pending request the free slots
   can take into one wave and dispatch ONE bucketed ``(A, P)``
   admission executable for it (batched prefill + first tokens into
   all the admitted slots' cache columns): a burst of k arrivals at a
   step boundary costs 1 admit dispatch, not k.  Wave/bucket sizes are
   pinned to the ``MXNET_SERVE_ADMIT_SIZES`` /
   ``MXNET_SERVE_PREFILL_BUCKETS`` ladders (defaults derived from the
   pool sizes / cache length), so compile count is bounded by the
   ladder product; a wave larger than the biggest ``A`` bucket spills
   to a second dispatch in the same pump.  Pool sizes are pinned to
   the ``MXNET_SERVE_POOL_SIZES`` set; when the backlog outgrows the
   pool the state is padded up to the next pinned size (a handful of
   retraces per server lifetime, never per request).
2. **step** — if any slot is live, dispatch ONE decode-step executable
   (``serve.engine.PoolPrograms.step_fn``): every active slot advances
   one token, retired slots are masked.  The dispatch is async — the
   host never blocks here.
3. **drain** — read back the PREVIOUS dispatches' small
   ``(token, emitted, done)`` arrays (they are ready or nearly ready
   while the device runs the just-dispatched step), route tokens to the
   per-request ``TokenStream``s, free retired slots.  This is the ONE
   host readback per step, batched and off the hot path: the device
   queue already holds the next step when the host touches data.

EOS (``eos_id``) and per-request ``max_new_tokens`` retirement are
computed ON DEVICE by the step itself; the host only learns about them
in drain.  Backpressure: ``submit`` blocks (or raises with
``nowait=True``) once ``max_pending`` requests are queued.

``MXNET_SERVE_SYNC=1`` — or a model the slot-pool gate rejects — serves
each request through one ``kv_generate`` call instead (no continuous
batching, same token streams); the server API is unchanged.

Memory (ISSUE 10): the resident pool is registered with the process-
wide ``telemetry.memory.ACCOUNTANT`` (``device_bytes{subsystem=
"serve.kv_pool"}``), and ``MXNET_SERVE_HBM_BUDGET`` /
``DecodeServer(hbm_budget=)`` bounds the server's device-resident
serving state: an over-budget pool growth or admission-scratch
allocation raises a clean ``MXNetError`` naming requested vs available
bytes instead of an allocator OOM.  ``stats()`` reports
``pool_bytes`` next to occupancy.

Paged KV (ISSUE 16): the resident pool is PAGED — each sequence holds
only the fixed-size pages (``MXNET_SERVE_PAGE_SIZE`` tokens each) its
cached positions occupy, mapped through per-slot page tables passed as
traced operands (allocation churn never retraces).  Identical prompt
prefixes SHARE pages copy-on-write (``MXNET_SERVE_PREFIX_CACHE``): a
full prefix hit admits with ZERO prefill dispatches and a TTFT of one
decode step.  Prompts past the largest pinned prefill bucket stream in
over several CHUNKED-PREFILL dispatches instead of being rejected —
the only hard length limit is the pool cache length (docs/SERVING.md).

Fault tolerance (ISSUE 13): ``submit(deadline=)`` /
``MXNET_SERVE_DEADLINE`` give every request a wall-clock budget the
STEP EXECUTABLE enforces (a per-slot deadline rides the slot-state
vector next to the sampling keys; the step takes a ``now`` operand and
folds expiry into the same device-side ``done`` mask as EOS — zero
extra dispatches).  ``TokenStream.cancel()`` frees the slot at the
next step boundary without touching co-resident lanes.  A scheduler
watchdog fails every in-flight stream with the underlying error when
the pump thread dies or a dispatch wedges past
``MXNET_SERVE_STEP_TIMEOUT`` — no consumer ever blocks forever — and
pump/admit/step/verify are ``MXNET_FAULT_INJECT`` sites so all of it
is exercised deterministically in tier-1 (docs/SERVING.md).

Speculative decoding (ISSUE 17): on greedy servers a cheap host-side
drafter (``serve.draft.NGramDrafter`` by default; any
``serve.draft.Drafter`` plugs in) proposes up to
``MXNET_SERVE_SPEC_DEPTH`` continuation tokens per slot between
steps, and ONE bucketed ``(S, k)`` verify dispatch
(``PoolPrograms.verify_fn``, k pinned to the ``MXNET_SERVE_SPEC_SIZES``
ladder) scores every proposal and accepts each slot's longest
matching prefix device-side — several tokens per dispatch when the
drafts land, exactly one (the plain-step guarantee) when they don't.
Greedy streams stay token-for-token identical to ``kv_generate``;
sampled pools never draft (acceptance compares argmax tokens, exact
only at temperature 0).  ``MXNET_SERVE_SPEC=0`` is the escape hatch.
"""
from __future__ import annotations

import itertools
import os
import threading
import time
from collections import deque
from collections.abc import MutableMapping

import numpy as onp

from .. import telemetry
from ..base import MXNetError
from ..telemetry.faults import fault_point
from . import schema

__all__ = ["DecodeServer", "TokenStream", "serve_counters",
           "reset_serve_counters"]

# process-wide AGGREGATE dispatch accounting — every DecodeServer in
# the process increments it, so with several servers the numbers
# interleave.  Per-server truth lives in ``DecodeServer.counters``
# (tests/test_serve.py pins 1 step dispatch per decode step at steady
# state against it; benchmark/serve_bench.py reports it).  Mutations go
# through ``_bump`` / ``reset_serve_counters`` — both take
# ``_counters_lock``, so a reset racing a live scheduler thread's
# increments can't lose counts (read-modify-write vs. reassign).
serve_counters = {"step_dispatches": 0, "admit_dispatches": 0,
                  "sync_requests": 0, "pool_grows": 0,
                  "prefix_hits": 0, "cow_copies": 0,
                  "chunk_dispatches": 0, "verify_dispatches": 0,
                  "draft_proposed": 0, "draft_accepted": 0,
                  "draft_rejected": 0, "hit_dispatches": 0,
                  "admit_rows": 0, "admit_tokens": 0,
                  "chunk_expert_tokens": 0, "chunk_experts_touched": 0,
                  "latent_rows_walked": 0, "chunk_latent_rows_walked": 0,
                  "chunk_carried_tokens": 0}
_counters_lock = threading.Lock()
_server_seq = itertools.count()


def _bump(key, n=1):
    with _counters_lock:
        serve_counters[key] += n


def reset_serve_counters():
    with _counters_lock:
        for k in serve_counters:
            serve_counters[k] = 0


class _CounterView(MutableMapping):
    """The historical ``DecodeServer.counters`` dict API as a live view
    over per-server registry counters (``serve_<key>_total{server=}``),
    so benchmarks/tests keep reading ``srv.counters["step_dispatches"]``
    while exporters see the same numbers in ``telemetry.snapshot()`` /
    ``render_prometheus()``.  Assignment (the reset path) writes the
    backing counter; iteration order is the historical key order.

    Admission's own: ``hit_dispatches`` (prefix-hit admission dispatches;
    ``prefix_hits`` counts hit ROWS and partial hits), ``admit_rows``
    (token positions the admission dispatches compute: ``A x P`` a wave,
    ``C`` a chunk, none a hit) and ``admit_tokens`` (the real prompt
    tokens among them); ``compiles`` / ``compile_ms`` (this server's pool
    executables compiled, and their wall milliseconds: the compile watch
    counts them by the site's ``server`` field, whatever the event ring
    still holds).  A model with routed experts counts what its chunks
    routed: ``chunk_expert_tokens`` ((row, held expert) pairs over every
    row a chunk computes, padding included) and ``chunk_experts_touched``
    ((routed layer, held expert) cells that got a row: the experts'
    weights a chunk has to read).  A model with latent attention over every
    position counts what its page walks read, the decode steps' and the
    chunks' apart: ``latent_rows_walked`` (cached rows the steps' walks
    read, summed over the live slots and the latent layers: what
    ``latent_walk_roofline_pct.pangu``, ``step_hbm_roofline_pct.pangu`` and
    ``serve_mfu_pct.pangu`` divide by the step's time and dispatches) and
    ``chunk_latent_rows_walked`` (rows the chunks' walks reached, each
    chunk's last query's end summed over the latent layers, however many
    of its tiles re-read them; no reader divides it by a step).
    ``chunk_carried_tokens`` counts the prompt tokens of the chunks that
    continue a prompt (offset past 0): their queries read what the earlier
    chunks left — the prompt's own pages, or a slot-table state (a power
    retention layer's ``phi(Q) S``)."""

    _KEYS = ("step_dispatches", "admit_dispatches", "sync_requests",
             "pool_grows", "prefix_hits", "cow_copies",
             "chunk_dispatches", "verify_dispatches",
             "draft_proposed", "draft_accepted", "draft_rejected",
             "hit_dispatches", "admit_rows", "admit_tokens",
             "compiles", "compile_ms", "chunk_expert_tokens",
             "chunk_experts_touched", "latent_rows_walked",
             "chunk_latent_rows_walked", "chunk_carried_tokens")

    def __init__(self, server_label):
        self._c = {k: telemetry.counter(f"serve_{k}_total",
                                        server=server_label)
                   for k in self._KEYS}

    def inc(self, key, n=1):
        self._c[key].inc(n)

    def __getitem__(self, key):
        return self._c[key].value

    def __setitem__(self, key, value):
        self._c[key]._assign(int(value))

    def __delitem__(self, key):
        raise MXNetError("DecodeServer.counters keys are fixed")

    def __iter__(self):
        return iter(self._KEYS)

    def __len__(self):
        return len(self._KEYS)

    def __repr__(self):
        return repr(dict(self))


def _parse_sizes(var, raw, what):
    try:
        sizes = sorted({int(x) for x in raw.split(",") if x.strip()})
    except ValueError:
        raise MXNetError(f"{var}={raw!r}: expected a "
                         f"comma-separated list of {what}")
    if not sizes or sizes[0] < 1:
        raise MXNetError(f"{var}={raw!r}: {what} must be positive")
    return tuple(sizes)


def _pool_sizes_from_env():
    return _parse_sizes("MXNET_SERVE_POOL_SIZES",
                        os.environ.get("MXNET_SERVE_POOL_SIZES",
                                       "1,2,4,8"), "slot counts")


def _hbm_budget_from_env():
    """``MXNET_SERVE_HBM_BUDGET``: bytes (K/M/G suffixes accepted) the
    server's device-resident serving state may occupy; unset = no
    limit."""
    from ..telemetry.memory import parse_bytes

    raw = os.environ.get("MXNET_SERVE_HBM_BUDGET")
    if raw is None:
        return None
    return parse_bytes(raw, "MXNET_SERVE_HBM_BUDGET")


def _page_size_from_env():
    """``MXNET_SERVE_PAGE_SIZE``: tokens per KV page (the paged-pool
    allocation granule); default 16."""
    raw = os.environ.get("MXNET_SERVE_PAGE_SIZE", "16")
    try:
        page = int(raw)
    except ValueError:
        raise MXNetError(f"MXNET_SERVE_PAGE_SIZE={raw!r}: expected a "
                         "positive integer token count")
    if page < 1:
        raise MXNetError(f"MXNET_SERVE_PAGE_SIZE={raw!r}: page size "
                         "must be >= 1 tokens")
    return page


def _kv_dtype_from_env():
    """``MXNET_SERVE_KV_DTYPE``: storage dtype of the paged KV pool —
    ``int8`` stores pages as int8 codes with per-page-per-head float32
    scales (~4x smaller pages, lossy: PARITY.md pins the tolerance);
    default ``native`` keeps the model's cache dtype (lossless, the
    pre-int8 behavior).  ``DecodeServer(kv_dtype=)`` wins over the
    env."""
    raw = os.environ.get("MXNET_SERVE_KV_DTYPE", "native").lower()
    if raw in ("native", "f32", "float32", "bf16", "bfloat16", ""):
        return "native"
    if raw == "int8":
        return "int8"
    raise MXNetError(f"MXNET_SERVE_KV_DTYPE={raw!r}: expected 'native' "
                     "(model cache dtype) or 'int8'")


def _prefix_cache_from_env():
    """``MXNET_SERVE_PREFIX_CACHE``: 0 disables copy-on-write shared-
    prefix caching (default on)."""
    return os.environ.get("MXNET_SERVE_PREFIX_CACHE", "1") != "0"


def _spec_from_env():
    """``MXNET_SERVE_SPEC``: 0 disables speculative draft-and-verify
    decoding (default on; it only engages on greedy servers —
    sampled pools always run plain depth-1 steps)."""
    return os.environ.get("MXNET_SERVE_SPEC", "1") != "0"


def _spec_depth_from_env():
    """``MXNET_SERVE_SPEC_DEPTH``: max draft tokens proposed per slot
    per verify dispatch (default 4; 0 disables speculation, same as
    ``MXNET_SERVE_SPEC=0``)."""
    raw = os.environ.get("MXNET_SERVE_SPEC_DEPTH", "4")
    try:
        depth = int(raw)
    except ValueError:
        raise MXNetError(f"MXNET_SERVE_SPEC_DEPTH={raw!r}: expected "
                         "a non-negative integer draft depth")
    if depth < 0:
        raise MXNetError(f"MXNET_SERVE_SPEC_DEPTH={raw!r}: draft "
                         "depth must be >= 0")
    return depth


def _spec_sizes_from_env(depth):
    """``MXNET_SERVE_SPEC_SIZES``: the pinned k-bucket ladder for the
    verify executable — compile count is bounded by its length, the
    PR-8 admit-ladder discipline.  Default: powers of two up to the
    speculation depth."""
    raw = os.environ.get("MXNET_SERVE_SPEC_SIZES")
    if raw is None:
        return tuple(_pow2_ladder(1, max(depth, 1)))
    return _parse_sizes("MXNET_SERVE_SPEC_SIZES", raw, "draft depths")


def _parse_seconds(var, raw):
    """A positive float seconds knob; unset/0 = None, malformed = loud
    (the shared ``base.parse_seconds`` discipline)."""
    from ..base import parse_seconds

    val = parse_seconds(var, raw)
    return val if val is not None and val > 0 else None


def _default_deadline_from_env():
    """``MXNET_SERVE_DEADLINE``: default per-request wall-clock budget
    in seconds (submit(deadline=) wins); unset/0 = none."""
    return _parse_seconds("MXNET_SERVE_DEADLINE",
                          os.environ.get("MXNET_SERVE_DEADLINE"))


def _step_timeout_from_env():
    """``MXNET_SERVE_STEP_TIMEOUT``: seconds one scheduler pump
    (admission + step dispatch + drain) may run before the watchdog
    declares the dispatch wedged and fails all in-flight streams;
    unset/0 = disabled."""
    return _parse_seconds("MXNET_SERVE_STEP_TIMEOUT",
                          os.environ.get("MXNET_SERVE_STEP_TIMEOUT"))


def _pow2_ladder(start, top):
    """``start``, doubling, until ``top`` caps the ladder."""
    sizes, a = [], start
    while a < top:
        sizes.append(a)
        a *= 2
    sizes.append(top)
    return sizes


def _admit_sizes_default(pool_sizes):
    """Default admission-wave bucket ladder: powers of two up to the
    largest pinned pool size (a wave can never exceed the free slot
    count, so bigger buckets would only pad) — bounds a partially full
    wave's masked-row overcompute to < 2x while keeping single-request
    trickle admission at bucket 1."""
    return tuple(_pow2_ladder(1, max(pool_sizes)))


def _admit_sizes_from_env(pool_sizes):
    raw = os.environ.get("MXNET_SERVE_ADMIT_SIZES")
    if raw is None:
        return _admit_sizes_default(pool_sizes)
    return _parse_sizes("MXNET_SERVE_ADMIT_SIZES", raw, "wave sizes")


def _prefill_buckets_default(T):
    """Default prompt-length bucket ladder: powers of two from 8 up to
    the cache length ``T`` (each clamped to ``T``) — the same shape the
    per-request admission used, now pinned so compile count stays
    bounded by the ladder product."""
    return tuple(sorted({min(b, T) for b in _pow2_ladder(8, T)}))


def _prefill_buckets_from_env(T):
    raw = os.environ.get("MXNET_SERVE_PREFILL_BUCKETS")
    if raw is None:
        return _prefill_buckets_default(T)
    buckets = _parse_sizes("MXNET_SERVE_PREFILL_BUCKETS", raw,
                           "prompt bucket lengths")
    return tuple(sorted({min(b, T) for b in buckets}))


def _bucket_for(ladder, n):
    """Smallest ladder entry >= n (the caller guarantees one exists)."""
    for b in ladder:
        if b >= n:
            return b
    raise MXNetError(f"{n} exceeds the largest bucket {ladder[-1]}")


class _PrefixIndex:
    """Host-side copy-on-write shared-prefix page cache: a chained trie
    over FULL pages of prompt tokens, each node mapping one
    ``(parent, page-of-token-bytes)`` chunk to the pool page holding
    its K/V.  ``register`` pins a producer's prompt pages with one
    index-owned refcount each (so they outlive the producer's
    retirement); ``match`` walks the longest cached chain for a new
    prompt, and the admission path maps those pages READ-ONLY into the
    consumer's table row — zero prefill dispatches on a full hit.
    ``evict`` drops least-recently-touched LEAF nodes when the
    allocator runs dry, so the cache is exactly the pages nothing else
    wants yet.  Scheduler-thread-only, like the ``PagePool`` under
    it.

    With a WINDOW pool under it (``wpool``; a model some of whose layers
    keep only the last ``window`` positions), a cached prefix is only
    enterable where the index also holds those layers' rows for the
    positions just before its end.  ``register`` therefore keeps, on the
    node of the producer's last full page, the TAIL: the window pages of
    the ``tail_pages`` logical pages ending there, one index-owned
    refcount each.  ``match`` cuts a chain back to the deepest length a
    tail covers (or misses) — never a hit served from a wrong window.
    Tails are the first to go when the window pool runs dry
    (``evict_tails``, least recently touched first), so what every hit
    touches stays."""

    def __init__(self, page_size, pool, wpool=None, tail_pages=0):
        self.page = int(page_size)
        self.pool = pool
        self.wpool = wpool
        self.tail_pages = int(tail_pages)
        self._nodes = {}    # (parent_id, chunk_bytes) -> node dict
        self._by_id = {}    # node id -> node (parent chains, eviction)
        self._tails = {}    # node id -> node, for the nodes with a tail
        self._ids = itertools.count(1)
        self._tick = itertools.count(1)

    def __len__(self):
        return len(self._nodes)

    def _chunk_key(self, prompt, parent, c):
        return (parent,
                prompt[c * self.page:(c + 1) * self.page].tobytes())

    def match(self, prompt, limit=None):
        """Longest chain of cached FULL pages covering a prefix of
        ``prompt``: ``(num_matched_pages, [pool page ids])``; over a
        window pool ``(m, pages, {logical page: window page})``, the chain
        cut back to the deepest length ``m <= limit`` whose window a tail
        covers."""
        pages, chain, parent = [], [], 0
        for c in range(prompt.size // self.page):
            node = self._nodes.get(self._chunk_key(prompt, parent, c))
            if node is None:
                break
            node["last"] = next(self._tick)
            pages.append(node["page"])
            chain.append(node)
            parent = node["id"]
        if self.wpool is None:
            return len(pages), pages
        top = len(pages) if limit is None else min(limit, len(pages))
        for m in range(top, 0, -1):
            need = range(max(0, m - self.tail_pages + 1), m)
            # the tail kept at this length, or at the page behind it
            for node in chain[m - 1:m + 1]:
                tail = node.get("tail")
                if tail is not None and all(lp in tail for lp in need):
                    return m, pages[:m], {lp: tail[lp] for lp in need}
        return 0, [], {}

    def register(self, prompt, length, slot_pages, window_pages=None):
        """Index ``prompt[:length]``'s full pages, backed by the
        producer slot's ``slot_pages`` row.  Only NEWLY created nodes
        incref their page (existing nodes already own theirs); pages
        past the last FULL page are never indexed — their K/V columns
        get overwritten by the producer's own decode steps.
        ``window_pages`` ``{logical page: window page}`` is what the
        producer holds of the window pool: the last full page's node
        keeps the tail out of it."""
        parent, node = 0, None
        for c in range(min(length // self.page, len(slot_pages))):
            key = self._chunk_key(prompt, parent, c)
            node = self._nodes.get(key)
            if node is None:
                node = {"id": next(self._ids), "key": key,
                        "page": slot_pages[c], "parent": parent,
                        "children": 0, "last": next(self._tick)}
                self._nodes[key] = node
                self._by_id[node["id"]] = node
                if parent:
                    self._by_id[parent]["children"] += 1
                self.pool.incref(node["page"])
            else:
                node["last"] = next(self._tick)
            parent = node["id"]
        if node is not None and window_pages is not None \
                and "tail" not in node:
            c = length // self.page - 1
            lps = range(max(0, c - self.tail_pages + 1), c + 1)
            if all(lp in window_pages for lp in lps):
                node["tail"] = {lp: window_pages[lp] for lp in lps}
                for wp in node["tail"].values():
                    self.wpool.incref(wp)
                self._tails[node["id"]] = node

    def evict_tails(self, need):
        """Drop least-recently-touched tails until ``need`` window pages
        have come free (a page frees once no slot maps it either).
        Returns pages freed."""
        before = self.wpool.free_pages
        while self.wpool.free_pages - before < need and self._tails:
            self._drop_tail(min(self._tails.values(),
                                key=lambda nd: nd["last"]))
        return self.wpool.free_pages - before

    def _drop_tail(self, node):
        for wp in node.pop("tail").values():
            self.wpool.decref(wp)
        del self._tails[node["id"]]

    def evict(self, need, protect=()):
        """Drop LRU leaf nodes (never pages in ``protect``) until
        ``need`` pool pages have actually come free — a decref only
        frees a page once no slot still maps it.  Returns pages
        freed."""
        protect = set(protect)
        before = self.pool.free_pages
        while self.pool.free_pages - before < need:
            leaves = [nd for nd in self._nodes.values()
                      if nd["children"] == 0
                      and nd["page"] not in protect]
            if not leaves:
                break
            self._drop(min(leaves, key=lambda nd: nd["last"]))
        return self.pool.free_pages - before

    def _drop(self, node):
        if "tail" in node:
            self._drop_tail(node)
        del self._nodes[node["key"]]
        del self._by_id[node["id"]]
        if node["parent"]:
            self._by_id[node["parent"]]["children"] -= 1
        self.pool.decref(node["page"])

    def drop_all(self):
        """Release every index-owned page ref (server teardown)."""
        for node in list(self._tails.values()):
            self._drop_tail(node)
        for node in self._by_id.values():
            self.pool.decref(node["page"])
        self._nodes.clear()
        self._by_id.clear()


class TokenStream:
    """Streaming view of one request's continuation.

    Iterate it for token ids as they decode (blocking; ends at
    retirement), or call :meth:`tokens` to wait for completion.  Every
    iteration replays from the first token, so a finished stream can be
    re-iterated and concurrent consumers each see the full stream.
    Each token's host-arrival wall time is kept in :attr:`times` and
    the time-to-first-token (first arrival minus submit) separately in
    :attr:`ttft` — the latency sources for ``benchmark/serve_bench.py``
    (TTFT is the metric batched admission moves; inter-token gaps come
    from consecutive :attr:`times`).  ``detokenize`` (a ``token_id ->
    str`` callable) enables :meth:`text` / :meth:`text_iter` streaming
    detokenization."""

    def __init__(self, request_id, detokenize=None, on_token=None):
        self.request_id = request_id
        self.submit_time = time.perf_counter()
        self.times = []
        self._detok = detokenize
        self._on_token = on_token
        self._cv = threading.Condition()
        self._toks = []
        self._done = threading.Event()
        self._error = None
        self._cancel_hook = None   # wired by DecodeServer.submit
        self._cancelled = False
        # speculative-decoding ledger (scheduler-thread writes at
        # verify drains): draft tokens the verify dispatches accepted
        # into THIS stream vs proposed-but-rejected
        self.draft_accepted = 0
        self.draft_rejected = 0

    # -- producer side (server loop) ------------------------------------ #
    @property
    def ttft(self):
        """Time-to-first-token: first host arrival minus submit
        (``None`` until the first token lands) — the admission-latency
        metric, distinct from the inter-token gaps derivable from
        consecutive :attr:`times`."""
        return self.times[0] - self.submit_time if self.times else None

    def _push(self, tok):
        if self._done.is_set():
            # a late in-flight readback for a cancelled / deadline-
            # retired slot: the stream's token list is sealed
            return
        self.times.append(time.perf_counter())
        with self._cv:
            self._toks.append(tok)
            self._cv.notify_all()
        if self._on_token is not None:
            try:
                self._on_token(self.request_id, tok)
            except Exception as e:
                # a buggy per-request callback fails ITS stream only —
                # the scheduler thread (and every other client's
                # stream) must survive it
                self._on_token = None
                self._finish(e)

    def _finish(self, error=None):
        with self._cv:
            if self._error is None:   # first error wins (a callback
                self._error = error   # failure isn't erased by the
            self._done.set()          # slot's later clean retirement)
            self._cv.notify_all()

    # -- consumer side --------------------------------------------------- #
    def __iter__(self):
        i = 0
        while True:
            with self._cv:
                while i >= len(self._toks) and not self._done.is_set():
                    self._cv.wait()
                if i >= len(self._toks):
                    if self._error is not None:
                        raise self._error
                    return
                tok = self._toks[i]
            yield tok
            i += 1

    @property
    def done(self):
        return self._done.is_set()

    @property
    def cancelled(self):
        """True once :meth:`cancel` has taken effect (the stream is
        done with the tokens that arrived before cancellation)."""
        return self._cancelled

    @property
    def accept_rate(self):
        """Fraction of this request's proposed draft tokens the
        verify dispatches accepted (0.0 while nothing has been
        proposed; 1.0 means every draft matched the model's own
        greedy emission)."""
        total = self.draft_accepted + self.draft_rejected
        return self.draft_accepted / total if total else 0.0

    def cancel(self):
        """Cancel this request: a queued request is dropped
        immediately; an in-flight one has its pool slot freed at the
        NEXT STEP BOUNDARY by the scheduler — co-resident streams are
        untouched and no extra executable dispatch is spent (the lane
        is simply unmapped host-side, like any retired slot).  The
        stream finishes cleanly with the tokens received so far;
        idempotent, and a no-op once the request already retired.
        Returns True if the cancellation took effect."""
        hook = self._cancel_hook
        if hook is None:
            raise MXNetError(
                f"stream {self.request_id} is not cancellable "
                "(not attached to a server)")
        return hook()

    def tokens(self, timeout=None):
        """Block until the request retires; return the full token list.

        A timeout raises ``MXNetError`` but consumes nothing: the
        stream keeps filling, and the same consumer may call
        :meth:`tokens` (or iterate) again later and still drain the
        full stream."""
        if not self._done.wait(timeout):
            raise MXNetError(f"request {self.request_id} not finished "
                             f"within {timeout}s")
        if self._error is not None:
            raise self._error
        return list(self._toks)

    def text_iter(self):
        """Streaming detokenization: yield text piece per token."""
        if self._detok is None:
            raise MXNetError("TokenStream has no detokenize callable")
        for tok in self:
            yield self._detok(tok)

    def text(self, timeout=None):
        if self._detok is None:
            raise MXNetError("TokenStream has no detokenize callable")
        return "".join(self._detok(t) for t in self.tokens(timeout))


class _Request:
    __slots__ = ("prompt", "max_new", "seed", "stream", "span",
                 "deadline", "cancelled", "retired")

    def __init__(self, prompt, max_new, seed, stream, deadline=None):
        self.prompt = prompt
        self.max_new = max_new
        self.seed = seed
        self.stream = stream
        # absolute wall-clock retirement budget on the server's
        # monotonic clock (None = no deadline); rides the slot-state
        # vector device-side once admitted
        self.deadline = deadline
        self.cancelled = False
        self.retired = False    # span closed (guards double-observe on
        # the cancel-vs-drain and teardown-after-failure races)
        # request-span telemetry, filled in at admission and emitted as
        # one ``serve_request`` event at retirement (docs/TELEMETRY.md)
        self.span = {}


# ``stats()`` keys of the step's ``index_walk`` readback, in its order
# (``ops.index_scores``: pages walked, copies started, table width)
_INDEX_WALK_KEYS = ("index_pages_walked", "index_copies",
                    "index_pages_table")


class DecodeServer:
    """Continuous-batching decode server over a slot-pool KV cache.

    ``submit()`` never waits for other requests: a free slot is filled
    at the next step boundary and the request's tokens stream out as
    they decode.  ``temperature``/``top_k``/``eos_id`` are server-level
    (they shape the compiled sampler); ``seed`` is per-request — a
    served stream reproduces ``kv_generate(model, prompt[None],
    max_new_tokens, temperature, top_k, seed)`` token-for-token.

    ``autostart=True`` runs the scheduler on a background thread.  With
    ``autostart=False`` the owner calls :meth:`pump` — one admission +
    step + drain round per call — which the scheduler tests and the
    benchmark use to drive the loop deterministically.
    """

    def __init__(self, model, *, max_total_len=None, pool_sizes=None,
                 temperature=0.0, top_k=0, eos_id=None,
                 weights="native", max_pending=256, detokenize=None,
                 admit_sizes=None, prefill_buckets=None,
                 hbm_budget=None, default_deadline=None,
                 step_timeout=None, page_size=None, num_pages=None,
                 prefix_cache=None, spec=None, spec_depth=None,
                 spec_sizes=None, drafter=None, kv_dtype=None,
                 num_window_pages=None, autostart=True):
        from ..telemetry.memory import parse_bytes
        from .draft import NGramDrafter
        from .engine import PagePool, PoolPrograms, pool_state_init

        self.model = model
        # fault-tolerance knobs (ISSUE 13): the server's monotonic
        # clock (monkeypatchable in tests for deterministic deadline
        # expiry) and its epoch — per-slot deadlines ride the state
        # vector as float32 seconds RELATIVE to the epoch, so float32
        # precision is spent on the server's lifetime, not on host
        # uptime
        self._clock = time.monotonic
        self._epoch = self._clock()
        self.default_deadline = default_deadline \
            if default_deadline is not None \
            else _default_deadline_from_env()
        if self.default_deadline is not None \
                and self.default_deadline <= 0:
            raise MXNetError("default_deadline must be positive seconds")
        self.step_timeout = step_timeout if step_timeout is not None \
            else _step_timeout_from_env()
        if self.step_timeout is not None and self.step_timeout <= 0:
            self.step_timeout = None   # 0 = wedge detection off, same
            # as the env path (a 0 budget would hair-trigger on every
            # in-progress pump at the watchdog's next poll)
        self._fatal = None          # the error the scheduler died with
        self._torn = False          # _teardown ran: the pool was
        # released and unaccounted — a wedged dispatch completing late
        # must not re-pin it (see _dispatch_step/_dispatch_admit)
        self._watchdog = None
        self._pump_t0 = None        # monotonic start of the loop's
        # current pump (None between pumps); read by the watchdog
        self.T = int(max_total_len if max_total_len is not None
                     else model._cfg.max_length)
        self.pool_sizes = tuple(pool_sizes) if pool_sizes is not None \
            else _pool_sizes_from_env()
        if not self.pool_sizes \
                or list(self.pool_sizes) != sorted(set(self.pool_sizes)) \
                or self.pool_sizes[0] < 1:
            raise MXNetError(f"pool_sizes {self.pool_sizes} must be "
                             "strictly increasing positive slot counts")
        # bucketed batched-admission ladders: wave sizes (A) and prompt
        # bucket lengths (P) — compile count per pool size is bounded
        # by len(admit_sizes) * len(prefill_buckets), lazily filled
        self.admit_sizes = tuple(admit_sizes) \
            if admit_sizes is not None \
            else _admit_sizes_from_env(self.pool_sizes)
        if not self.admit_sizes \
                or list(self.admit_sizes) != sorted(set(self.admit_sizes)) \
                or self.admit_sizes[0] < 1:
            raise MXNetError(f"admit_sizes {self.admit_sizes} must be "
                             "strictly increasing positive wave sizes")
        self.prefill_buckets = tuple(prefill_buckets) \
            if prefill_buckets is not None \
            else _prefill_buckets_from_env(self.T)
        if not self.prefill_buckets \
                or list(self.prefill_buckets) != \
                sorted(set(self.prefill_buckets)) \
                or self.prefill_buckets[0] < 1 \
                or self.prefill_buckets[-1] > self.T:
            raise MXNetError(
                f"prefill_buckets {self.prefill_buckets} must be "
                "strictly increasing positive prompt lengths within "
                f"the cache length {self.T}")
        self.temperature, self.top_k = temperature, top_k
        self.eos_id = eos_id
        self.weights = weights
        self.max_pending = int(max_pending)
        self._detok = detokenize
        # HBM budget (bytes) for this server's device-resident serving
        # state: the resident slot-pool KV cache plus admission prefill
        # scratch.  Growth/admission that would exceed it raises a
        # clean MXNetError naming the shortfall instead of letting the
        # allocator OOM mid-dispatch; None = unlimited.
        self.hbm_budget = parse_bytes(hbm_budget, "hbm_budget") \
            if hbm_budget is not None else _hbm_budget_from_env()
        # paged-KV knobs: page granule, total page count (None = the
        # dense-equivalent S * MAXP allotment, rescaled on pool
        # growth; an explicit count is pinned for the server's life)
        # and the COW shared-prefix cache switch
        self.page_size = int(page_size) if page_size is not None \
            else _page_size_from_env()
        if self.page_size < 1:
            raise MXNetError(f"page_size must be >= 1, "
                             f"got {self.page_size}")
        self._num_pages_fixed = num_pages is not None
        # paged-pool storage dtype (ISSUE 18): "int8" quantizes pages
        # at write time inside the same executables and halves-again
        # the per-page bytes vs bf16 (4x vs f32) — the equal-HBM
        # residency lever; "native" is the lossless default
        self.kv_dtype = str(kv_dtype).lower() if kv_dtype is not None \
            else _kv_dtype_from_env()
        if self.kv_dtype in ("f32", "float32", "bf16", "bfloat16"):
            self.kv_dtype = "native"
        if self.kv_dtype not in ("native", "int8"):
            raise MXNetError(f"kv_dtype must be 'native' or 'int8', "
                             f"got {kv_dtype!r}")
        self.prefix_cache_enabled = bool(prefix_cache) \
            if prefix_cache is not None else _prefix_cache_from_env()
        # speculative decoding knobs (ISSUE 17): draft-and-verify is
        # GREEDY-ONLY (acceptance compares argmax tokens — exact at
        # temperature 0, wrong otherwise), gated HERE so a sampled
        # server never builds a verify program.  Depth is clamped to
        # the largest pinned k bucket; a 0 depth disables speculation
        # like MXNET_SERVE_SPEC=0 does.
        self.spec_depth = int(spec_depth) if spec_depth is not None \
            else _spec_depth_from_env()
        if self.spec_depth < 0:
            raise MXNetError(f"spec_depth must be >= 0, "
                             f"got {self.spec_depth}")
        self.spec_sizes = tuple(spec_sizes) \
            if spec_sizes is not None \
            else _spec_sizes_from_env(self.spec_depth)
        if not self.spec_sizes \
                or list(self.spec_sizes) != sorted(set(self.spec_sizes)) \
                or self.spec_sizes[0] < 1:
            raise MXNetError(f"spec_sizes {self.spec_sizes} must be "
                             "strictly increasing positive draft "
                             "depths")
        self.spec_depth = min(self.spec_depth, self.spec_sizes[-1])
        self.spec_enabled = ((bool(spec) if spec is not None
                              else _spec_from_env())
                             and self.spec_depth > 0
                             and temperature == 0.0)
        self._drafter = drafter if drafter is not None \
            else NGramDrafter()
        # per-server telemetry identity: labels this server's registry
        # counters/histograms and its compile / serve_* events
        self.telemetry_label = f"srv{next(_server_seq)}"
        self._tele = {
            "ttft": telemetry.histogram("serve_ttft_seconds",
                                        server=self.telemetry_label),
            "gap": telemetry.histogram("serve_token_gap_seconds",
                                       server=self.telemetry_label),
            "wait": telemetry.histogram("serve_queue_wait_seconds",
                                        server=self.telemetry_label),
            "occ": telemetry.gauge("serve_occupancy",
                                   server=self.telemetry_label),
            "pages": telemetry.gauge("serve_pages_in_use",
                                     server=self.telemetry_label),
        }

        self.sync_mode = os.environ.get("MXNET_SERVE_SYNC", "0") == "1"
        self.sync_reason = "MXNET_SERVE_SYNC=1" if self.sync_mode \
            else None
        self._progs = None
        self._pool_bytes = 0
        if not self.sync_mode:
            try:
                self._progs = PoolPrograms(
                    model, self.pool_sizes[0], self.T, temperature,
                    top_k, eos_id, weights,
                    telemetry_label=self.telemetry_label,
                    page_size=self.page_size, num_pages=num_pages,
                    kv_dtype=self.kv_dtype,
                    window_pages=num_window_pages,
                    max_chunk=self.prefill_buckets[-1])
            except MXNetError as e:
                # models the slot-pool gate rejects still serve, one
                # request at a time, through the kv_generate fallback
                self.sync_mode = True
                self.sync_reason = str(e)
        if self.sync_mode and self.hbm_budget is not None:
            # the kv_generate fallback holds no resident pool and
            # allocates per-request caches inside its own executables —
            # the budget machinery has nothing to meter there.  Say so
            # loudly: a silently inert limit is worse than none
            import warnings

            warnings.warn(
                f"DecodeServer hbm_budget={self.hbm_budget} is NOT "
                "enforced in sync mode (kv_generate fallback"
                f"{'' if self.sync_reason is None else ': ' + self.sync_reason}"
                ") — per-request decode caches are unmetered",
                stacklevel=2)
        if not self.sync_mode and self._progs.layered:
            # a model served from its per-layer description: one pool
            # size (its window ring and its per-slot state do not grow)
            # and no draft-and-verify
            kinds = ", ".join(self._progs.slot_kinds)
            if len(self.pool_sizes) > 1 and (
                    self._progs.window is not None or kinds):
                raise MXNetError(
                    "a model with windowed layers or per-slot state "
                    f"serves from one pool size, not {self.pool_sizes}")
            if spec and self.spec_enabled:
                raise MXNetError(
                    "draft-and-verify is not implemented for models "
                    "served from a per-layer description"
                    + (f" (a rejected draft would need the {kinds} state "
                       "rolled back)" if kinds else "")
                    + ": pass spec=False")
            self.spec_enabled = False
            if kinds:
                # a cached prefix is pages, and pages cannot enter a layer
                # whose memory is a state: no state is snapshot, so the
                # prefix index is off for such a model
                if prefix_cache:
                    raise MXNetError(
                        "prefix_cache=True is not implemented for a model "
                        f"with state under the slot table ({kinds}): a "
                        "cached prefix of pages holds no such state")
                self.prefix_cache_enabled = False
        if not self.sync_mode:
            # price the MINIMUM USABLE configuration before allocating
            # anything: the smallest pool plus the smallest admission
            # wave's prefill scratch (every request must pass through
            # one admission, so a budget that fits the pool alone would
            # construct a server that fails every submit) — a budget
            # the config can never fit is a constructor error, not a
            # first-request teardown
            from .engine import admit_scratch_bytes

            self._check_budget(
                self.pool_sizes[0],
                scratch=admit_scratch_bytes(self._progs,
                                            self.admit_sizes[0]),
                what=f"initial pool ({self.pool_sizes[0]} slots) plus "
                     f"the smallest admission wave's "
                     f"(A={self.admit_sizes[0]}) prefill scratch")
        self._state = None if self.sync_mode \
            else pool_state_init(self._progs)
        if self._state is not None:
            self._account_pool()
        # host-side page bookkeeping (scheduler-thread-only, like the
        # slot table): the free-list allocator, per-slot page-table
        # rows, the set of slots mid-chunked-prefill (their reserved
        # pages are masked OUT of the step's table until the final
        # chunk activates them), and the COW prefix index
        self._pages = None if self.sync_mode \
            else PagePool(self._progs.num_pages)
        self._slot_pages = [[] for _ in range(self.pool_sizes[0])]
        self._pt_rows = {}      # slot -> (row list, its int32 array)
        self._chunk_slots = set()
        self._chunking = deque()   # {"req", "slot", "off"} records
        # the WINDOW pool's bookkeeping (models with windowed layers):
        # its allocator, per slot the window pages it holds by logical
        # page, and the host's mirror of each live slot's next position,
        # by which pages are taken as a slot advances and let go behind it
        windowed = not self.sync_mode and self._progs.window is not None
        self._wpages = PagePool(self._progs.window_pages) if windowed \
            else None
        self._wback = self._progs.eng.window_back_pages(
            self._progs.page) if windowed else 0
        self._slot_wpages = [{} for _ in range(self.pool_sizes[0])]
        self._slot_pos = [0] * self.pool_sizes[0]
        # the request each slot last admitted (its entries under the slot
        # table hold that request's state until the next admission)
        self._tenant = [None] * self.pool_sizes[0]
        self._wheld_max = 0     # most window pages a stepping slot held
        self._prompt_tokens = self._prompt_cached = 0
        self._state_resets = 0  # slots started from zero per-slot state
        self._step_sums = {}    # the step's own counters, added up
        # a chunk's counters (its readback, in seq order) until a later
        # dispatch's readback says the device is past it; then added up
        self._chunk_pending, self._chunk_sums = [], {}
        self._chunk_lock = threading.Lock()
        self._prefix = _PrefixIndex(
            self._progs.page, self._pages, self._wpages,
            self._wback + 1 if windowed else 0) \
            if not self.sync_mode and self.prefix_cache_enabled \
            else None

        # scheduler bookkeeping (single scheduler thread; submit() is
        # the only cross-thread writer and it only touches _pending)
        self._lock = threading.Lock()
        self._work = threading.Condition(self._lock)
        self._pending = deque()
        self._stopping = False
        self._slots = [None] * self.pool_sizes[0]   # slot -> _Request
        # (kind, arrays, slot_snapshot/req, seq of the dispatch)
        self._inflight = deque()
        self._seq = 0            # dispatches so far: the id phase spans,
        self._phase_span = None  # and serve_request, name a dispatch by
        self._next_id = 0
        self._steps = 0
        # over the step dispatches and their stepping slots: the pages
        # that hold a slot's cached tokens, which is what a step that
        # walks its pages reads, and the width of its table row, which
        # is what the view reads (0 / 0 where the step builds the view)
        self._pages_walked = self._pages_table = 0
        self._occupied_lane_steps = 0
        self._capacity_lane_steps = 0   # sums len(_slots) per step, so
        # occupancy stays honest across pool growth (S changes mid-run)
        # per-server dispatch accounting: a dict-API view over the
        # telemetry registry (the module-level serve_counters aggregate
        # is also incremented, under its shared lock)
        self.counters = _CounterView(self.telemetry_label)
        self._stats_emitted = False
        self._thread = None
        telemetry.emit(
            "serve_config", server=self.telemetry_label,
            pool_sizes=list(self.pool_sizes),
            admit_sizes=list(self.admit_sizes),
            prefill_buckets=list(self.prefill_buckets),
            max_total_len=self.T, sync_mode=self.sync_mode,
            sync_reason=self.sync_reason,
            hbm_budget=self.hbm_budget, pool_bytes=self._pool_bytes,
            default_deadline=self.default_deadline,
            step_timeout=self.step_timeout,
            page_size=self.page_size,
            num_pages=None if self.sync_mode
            else self._progs.num_pages,
            kv_dtype=self.kv_dtype,
            # the priced per-page byte cost at kv_dtype — what
            # --check-serve's dtype-aware capacity check re-derives
            # pool_bytes from (None in sync mode: no resident pool)
            page_bytes=None if self.sync_mode
            else self._progs.page_bytes(),
            window_pages=0 if self.sync_mode
            else self._progs.window_pages,
            window_page_bytes=0 if self.sync_mode
            else self._progs.window_page_bytes(),
            slot_state_bytes=0 if self.sync_mode
            else self._progs.slot_state_bytes(),
            prefix_cache=self.prefix_cache_enabled,
            spec=self.spec_enabled, spec_depth=self.spec_depth,
            spec_sizes=list(self.spec_sizes))
        if autostart:
            self.start()

    # -- public API ------------------------------------------------------ #
    def start(self):
        """Start the background scheduler thread (no-op if one is
        already running), plus its watchdog: the watchdog fails every
        in-flight stream with the underlying error when the scheduler
        thread dies without cleanup, or when one pump wedges past
        ``step_timeout`` / ``MXNET_SERVE_STEP_TIMEOUT`` — no consumer
        ever blocks forever on a dead pump.  ``autostart=False`` + a
        later ``start()`` lets the owner warm the compiled programs
        pump-driven first, then hand the loop to the thread —
        ``benchmark/serve_bench.py`` uses this to keep compiles off
        the measured clock."""
        with self._work:
            if self._stopping:
                raise self._closed_error()
            if self._thread is not None and self._thread.is_alive():
                return
            self._thread = threading.Thread(
                target=self._loop, name="mxnet-serve", daemon=True)
            self._thread.start()
            if self._watchdog is None or not self._watchdog.is_alive():
                self._watchdog = threading.Thread(
                    target=self._watch, name="mxnet-serve-watchdog",
                    daemon=True)
                self._watchdog.start()

    def _closed_error(self):
        """The submit/start error after the server stopped: names the
        scheduler's fatal error when it died, plain "closed" after a
        clean close()."""
        if self._fatal is not None:
            return MXNetError(
                f"server failed and stopped serving: {self._fatal}")
        return MXNetError("server is closed")

    def submit(self, prompt_tokens, max_new_tokens=32, seed=0,
               nowait=False, on_token=None, deadline=None):
        """Queue one request; returns its :class:`TokenStream`.

        ``deadline`` (seconds, default ``default_deadline`` /
        ``MXNET_SERVE_DEADLINE``) is the request's wall-clock budget
        measured from submit: when it expires the sequence is retired
        DEVICE-SIDE at the next step boundary (the per-slot deadline
        rides the slot-state vector; no extra dispatch) with the
        tokens produced so far and reason ``deadline_exceeded``; a
        request whose deadline lapses while still queued is retired at
        the admission boundary without occupying a slot.

        Blocks while ``max_pending`` requests are already queued
        (``nowait=True`` raises instead — pool-full backpressure is a
        visible error, not an unbounded queue)."""
        prompt = onp.asarray(
            prompt_tokens.asnumpy() if hasattr(prompt_tokens, "asnumpy")
            else prompt_tokens, dtype=onp.int32).reshape(-1)
        if prompt.size == 0:
            raise MXNetError("empty prompt")
        if max_new_tokens < 1:
            raise MXNetError("max_new_tokens must be >= 1")
        # prompts past the largest pinned prefill bucket are NOT
        # rejected: chunked prefill streams them in over several
        # dispatches — the only hard limit is the pool cache length
        if prompt.size + max_new_tokens > self.T:
            raise MXNetError(
                f"prompt ({prompt.size}) + max_new_tokens "
                f"({max_new_tokens}) exceeds the pool cache length "
                f"{self.T}")
        if not self.sync_mode:
            # a request that can NEVER be paged in (more pages than
            # the pool will ever hold, reachable only with an explicit
            # small num_pages=) is a caller error here, not an
            # admission loop that spins forever
            need = self._progs.pages_for(prompt.size + max_new_tokens)
            cap = self._pages.num_pages if self._num_pages_fixed \
                else self.pool_sizes[-1] * self._progs.maxp
            if need > cap:
                raise MXNetError(
                    f"request needs {need} KV pages "
                    f"({prompt.size} prompt + {max_new_tokens} new "
                    f"tokens at page_size={self._progs.page}) but the "
                    f"page pool holds at most {cap} — raise "
                    "num_pages= or lower max_new_tokens")
        seed = int(seed)
        if not -2 ** 31 <= seed < 2 ** 31:
            # the slot pool carries the seed as a traced int32 operand;
            # rejecting it HERE keeps an oversized seed a caller error
            # instead of an OverflowError on the scheduler thread
            raise MXNetError(
                f"seed {seed} does not fit int32 — fold larger seeds "
                "on the host before submitting")
        if deadline is None:
            deadline = self.default_deadline
        if deadline is not None and deadline <= 0:
            raise MXNetError(
                f"deadline {deadline} must be positive seconds")
        abs_deadline = None if deadline is None \
            else self._clock() + deadline
        with self._work:
            if self._stopping:
                raise self._closed_error()
            while len(self._pending) >= self.max_pending:
                if nowait:
                    raise MXNetError(
                        f"backpressure: {len(self._pending)} requests "
                        f"pending (max_pending={self.max_pending})")
                if self._thread is None:
                    # no scheduler thread to drain the queue — blocking
                    # here would deadlock the pump()-driving thread
                    raise MXNetError(
                        f"backpressure: {len(self._pending)} requests "
                        f"pending (max_pending={self.max_pending}) and "
                        "no scheduler thread (autostart=False) — call "
                        "pump() to drain, or submit(nowait=True)")
                self._work.wait(0.05)
                if self._stopping:
                    raise self._closed_error()
            stream = TokenStream(self._next_id, self._detok, on_token)
            self._next_id += 1
            req = _Request(prompt, int(max_new_tokens), int(seed),
                           stream, deadline=abs_deadline)
            stream._cancel_hook = lambda: self._cancel(req)
            self._pending.append(req)
            self._work.notify_all()
        return stream

    def _count(self, key, n=1):
        self.counters.inc(key, n)
        _bump(key, n)

    def _slot_spec_depth(self, req):
        """The speculation-depth cap scattered into a slot's state row
        at admission (0 = never speculate; the device clamps accepted
        drafts to it even if a buggy drafter over-proposes)."""
        return self.spec_depth if self.spec_enabled else 0

    def reset_counters(self):
        """Zero the per-server dispatch counters AND the step/occupancy
        ledger, so a measurement window opened after a warm-up phase
        (``benchmark/serve_bench.py`` warms the whole admission-bucket
        ladder) reports the window's own occupancy, undiluted by the
        warm-up's idle lanes."""
        self._fold_chunk_counters()
        for k in self.counters:
            self.counters[k] = 0
        self._steps = 0
        self._pages_walked = self._pages_table = 0
        self._occupied_lane_steps = 0
        self._capacity_lane_steps = 0

    def stats(self):
        """Structured scheduler/occupancy/latency snapshot: the
        historical counters plus the per-server registry instruments
        (dispatch counters, TTFT / inter-token-gap / queue-wait
        histogram summaries) — the serving face of
        ``telemetry.snapshot()``."""
        S = len(self._slots)
        acc = self.counters["draft_accepted"]
        rej = self.counters["draft_rejected"]
        return {
            "server": self.telemetry_label,
            "num_slots": S,
            "steps": self._steps,
            # speculative-decoding face: the per-server draft ledger
            # plus the accept rate the benches report (accepted +
            # rejected == proposed is the --check-serve invariant)
            "spec": self.spec_enabled,
            "spec_depth": self.spec_depth,
            "draft_accepted": acc,
            "draft_rejected": rej,
            "draft_accept_rate": acc / (acc + rej)
            if (acc + rej) else 0.0,
            "occupancy": (self._occupied_lane_steps /
                          self._capacity_lane_steps
                          if self._capacity_lane_steps else 0.0),
            "pending": len(self._pending),
            "in_flight": sum(r is not None for r in self._slots),
            "sync_mode": self.sync_mode,
            # accountant-backed resident-pool bytes (0 in sync mode —
            # the kv_generate fallback holds no resident cache); never
            # read from self._state here, whose buffers may be donated
            # to an in-flight dispatch on the scheduler thread
            "pool_bytes": self._pool_bytes,
            "hbm_budget": self.hbm_budget,
            # pool storage dtype + the priced per-page cost: together
            # with pages_total they re-derive pool_bytes, the
            # --check-serve dtype-aware capacity identity
            "kv_dtype": self.kv_dtype,
            "page_bytes": None if self.sync_mode
            else self._progs.page_bytes(),
            # page-pool occupancy (0/None in sync mode: no pool)
            "page_size": None if self.sync_mode else self._progs.page,
            "pages_total": 0 if self._pages is None
            else self._pages.num_pages,
            "pages_in_use": 0 if self._pages is None
            else self._pages.in_use,
            "prefix_nodes": 0 if self._prefix is None
            else len(self._prefix),
            # off by the server's own rule for a model with state under
            # the slot table (``slot_kinds``), whose bytes a slot and
            # admissions started from zero state follow
            "prefix_cache": self.prefix_cache_enabled,
            "slot_kinds": [] if self.sync_mode
            else list(self._progs.slot_kinds),
            "state_bytes_per_slot": 0 if self.sync_mode
            else self._progs.slot_state_bytes(),
            "state_resets": self._state_resets,
            # how much of its table the step's page walk reads
            "step_pages_walked": self._pages_walked,
            "step_pages_table": self._pages_table,
            # the window pool (0 / None without windowed layers): a slot
            # holds pages for its window only, the prefix index the tails
            "window_pages_total": 0 if self._wpages is None
            else self._wpages.num_pages,
            "window_pages_in_use": 0 if self._wpages is None
            else self._wpages.in_use,
            "window_page_bytes": 0 if self.sync_mode
            else self._progs.window_page_bytes(),
            "window_pages_slot_max": self._wheld_max,
            "window_pages_slot_bound": self._wback + 2
            if self._wpages is not None else 0,
            "prefix_tails": 0 if self._prefix is None
            else len(self._prefix._tails),
            # prompt tokens admitted, and how many of them the prefix
            # cache served
            "prompt_tokens": self._prompt_tokens,
            "prompt_tokens_cached": self._prompt_cached,
            **self._step_stats(),
            **self._chunk_stats(),
            "counters": dict(self.counters),
            "ttft": self._tele["ttft"].summary(),
            "token_gap": self._tele["gap"].summary(),
            "queue_wait": self._tele["wait"].summary(),
        }

    def _step_stats(self):
        """What the step executable counted itself (an engine with routed
        experts or a selecting attention), summed over the steps routed so
        far: mean over routed layers and steps of the busiest held expert's
        tokens over the mean, share of (layer, expert) cells a step
        touched, tokens a held expert a step, keys selected a query, the
        pages the index scores walked, the rows the latent attention's
        walks read a step."""
        t = self._step_sums
        out = {}
        if t.get("cells"):
            out["moe_tokens_per_expert_step"] = t["tokens"] / t["cells"]
            out["moe_experts_touched_share"] = t["touched"] / t["cells"]
            out["moe_load_max_over_mean"] = \
                t["ratio_sum"] / t["ratio_n"] if t["ratio_n"] else None
        if t.get("queries"):
            out["selected_keys_per_query"] = t["selected"] / t["queries"]
        # the index-score kernel's own count over live slots and selecting
        # layers: pages its walks read, copies they took, the tables' width
        # (0 / 0 where no step ran it)
        for k in _INDEX_WALK_KEYS:
            out[k] = t.get(k, 0)
        # the latent attention kernel's: rows its walks read over live slots
        # and latent layers, and the copies they took, a step dispatched
        # (absent where no step walked)
        if t.get("latent_rows") and self._steps:
            out["latent_rows_walked_per_step"] = \
                t["latent_rows"] / self._steps
            out["latent_copies_per_step"] = t["latent_copies"] / self._steps
        return out

    def _chunk_stats(self):
        """What the chunks counted themselves (a model with routed
        experts), summed over every chunk dispatched so far: (row, held
        expert) pairs a (routed layer, held expert) cell, and the share of
        those cells a chunk touched.  Folds in the chunks still pending
        (their readback waits at most for the newest chunk)."""
        self._fold_chunk_counters()
        t = self._chunk_sums
        if not t.get("cells"):
            return {}
        return {"chunk_moe_tokens_per_expert": t["tokens"] / t["cells"],
                "chunk_moe_experts_touched_share": t["touched"] / t["cells"]}

    def _fold_chunk_counters(self, before=None):
        """Add up the pending chunk counters dispatched before the dispatch
        ``before`` (a seq; every one where ``None``): the device runs the
        dispatches in order, so once a later one's readback is in hand
        theirs is too."""
        with self._chunk_lock:
            due = [c for seq, c in self._chunk_pending
                   if before is None or seq < before]
            self._chunk_pending = [(seq, c) for seq, c in self._chunk_pending
                                   if before is not None and seq >= before]
        # read back outside the lock: a ``stats()`` from another thread may
        # wait here for the newest chunk, the scheduler's next append not
        due = [{k: onp.asarray(v) for k, v in c.items()} for c in due]
        with self._chunk_lock:
            t = self._chunk_sums
            for c in due:
                if "latent_walk" in c:
                    self._count("chunk_latent_rows_walked",
                                int(c["latent_walk"][0]))
                if "expert_load" not in c:
                    continue
                load = c["expert_load"]
                tokens, touched = int(load.sum()), int((load > 0).sum())
                self._count("chunk_expert_tokens", tokens)
                self._count("chunk_experts_touched", touched)
                for k, v in (("tokens", tokens), ("touched", touched),
                             ("cells", load.size)):
                    t[k] = t.get(k, 0) + v

    def _add_step_counters(self, c):
        t = self._step_sums
        if "expert_load" in c:
            load = onp.asarray(c["expert_load"], onp.float64)
            mean, top = load.mean(axis=1), load.max(axis=1)
            live = mean > 0
            for k, v in (("tokens", load.sum()), ("cells", load.size),
                         ("touched", (load > 0).sum()),
                         ("ratio_sum", (top[live] / mean[live]).sum()),
                         ("ratio_n", live.sum())):
                t[k] = t.get(k, 0) + float(v)
        if "selected" in c:
            t["selected"] = t.get("selected", 0) + int(c["selected"])
            t["queries"] = t.get("queries", 0) + int(c["queries"])
        if "index_walk" in c:
            for k, v in zip(_INDEX_WALK_KEYS, onp.asarray(c["index_walk"])):
                t[k] = t.get(k, 0) + int(v)
        if "latent_walk" in c:
            rows, copies = (int(v) for v in onp.asarray(c["latent_walk"]))
            self._count("latent_rows_walked", rows)
            for k, v in (("latent_rows", rows), ("latent_copies", copies)):
                t[k] = t.get(k, 0) + v

    def close(self, drain=True, timeout=60.0):
        """Stop the scheduler.  ``drain=True`` serves everything already
        submitted first; otherwise queued/in-flight requests fail with
        a server-closed error.  Deadline arithmetic is monotonic — a
        wall-clock (NTP) step during the drain cannot turn the budget
        into an instant or an infinite timeout."""
        deadline = time.monotonic() + timeout
        if drain:
            while (self._pending or
                   any(r is not None for r in self._slots) or
                   self._inflight):
                if self._thread is None or not self._thread.is_alive():
                    # no scheduler left to drain the backlog — either
                    # autostart=False, or a PRIOR close() timed out and
                    # the thread has since exited at its _stopping
                    # check with work outstanding; pump from here so
                    # "call close() again" actually finishes the drain
                    if not self.pump():
                        break
                elif time.monotonic() > deadline:
                    raise MXNetError("close(drain=True) timed out")
                else:
                    time.sleep(0.002)
        with self._work:
            self._stopping = True
            self._work.notify_all()
        if self._thread is not None:
            self._thread.join(
                timeout=max(deadline - time.monotonic(), 0.1))
            if self._thread.is_alive():
                # the scheduler is mid-pump (e.g. a pool-growth retrace
                # compiling) and owns _slots/_inflight — tearing them
                # down under it would double-route token waves.  It
                # exits at its next _stopping check; call close() again
                # to finish teardown.
                raise MXNetError(
                    "close() timed out waiting for the scheduler "
                    "thread (still inside a dispatch/retrace); it "
                    "stops at the next step boundary — call close() "
                    "again to finish teardown")
        if self._watchdog is not None:
            self._watchdog.join(timeout=1.0)   # exits on _stopping
        self._flush_drain(final=True)
        self._emit_stats()
        self._teardown(MXNetError("server closed"), reason="closed")

    def slot_state(self, slot):
        """``(request id, entries)``: slot ``slot``'s entries under the slot
        table — a host copy of every layer's, one array a row kind its model
        declares (``stats()["slot_kinds"]``), in the stored layout — and the
        request they last took tokens from (``None`` before any).  A check
        reads it: only while the server is idle (nothing queued, in a slot
        or in flight), so that no dispatch holds the arrays."""
        if self.sync_mode or not self._progs.slot_kinds:
            raise MXNetError("this server keeps no state under the slot "
                             "table")
        if self._state is None or self._pending or self._inflight \
                or self._chunking or any(r is not None for r in self._slots):
            raise MXNetError("slot_state reads only an idle server: wait "
                             "until every request has retired")
        return self._tenant[slot], tuple(
            onp.asarray(a[:, slot]) for a in self._state[1])

    def _emit_stats(self):
        """One ``serve_stats`` event per server lifetime (at close):
        the final counters + occupancy + latency summaries, so a
        recorded JSONL alone can re-check the one-dispatch-per-step
        discipline (``tools/telemetry_report.py --check-serve``)."""
        if self._stats_emitted:
            return
        self._stats_emitted = True
        telemetry.emit("serve_stats", **self.stats())

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close(drain=exc == (None, None, None))

    # -- scheduler ------------------------------------------------------- #
    def pump(self):
        """One scheduler round: cancellations, admissions, one step
        dispatch, drain.  Returns True if any work happened (False =
        fully idle: nothing pending, nothing in flight — the loop
        thread sleeps on that)."""
        fault_point("serve.pump", server=self.telemetry_label)
        try:
            return self._pump()
        finally:
            self._phase(None)

    def _phase(self, name, **ids):
        """Close the scheduler's open phase span and open ``name``
        (``None``: only close).  The phases are SIBLINGS that tile a
        pump — ``mx:serve:cancel``, ``admit_build``, the dispatches
        (``admit`` / ``admit_hit`` / ``chunk`` / ``verify`` / ``step``,
        each with its ``seq``), ``draft``, ``drain_wait`` and ``route``
        (each with ``cause`` = the ``seq`` whose readback it handles);
        ``mx:serve:idle`` is the loop thread's wait.  No span encloses
        them: a trace reduction labels an idle gap of the device by the
        span that covers most of it, and an outer span would take every
        label.  Free when no trace runs (``telemetry.span``)."""
        if self._phase_span is not None:
            self._phase_span.__exit__(None, None, None)
            self._phase_span = None
        if name is not None:
            self._phase_span = telemetry.span(name, **ids)
            self._phase_span.__enter__()

    def _next_seq(self):
        self._seq += 1
        return self._seq

    def _pump(self):
        # cancellations FIRST: a cancelled slot frees at this step
        # boundary, so the admission below can re-fill it in the same
        # pump — no wasted masked lane, no extra dispatch
        self._phase("mx:serve:cancel")
        worked = self._process_cancels()
        if self.sync_mode:
            return self._pump_sync() or worked
        worked |= self._admit_pending()
        stepped = False
        # slots mid-chunked-prefill don't step (their lanes activate at
        # the final chunk); only genuinely live lanes justify a dispatch
        if self._live_slots():
            drafts = None
            if self.spec_enabled:
                # drafts must chain off each slot's NEWEST device
                # token, which is still in flight until the previous
                # dispatch drains — speculation trades the one-dispatch
                # host/device overlap for multi-token dispatches
                # (docs/SERVING.md); draining here may retire slots, so
                # the liveness check repeats below
                worked |= self._flush_drain()
                if self._live_slots():
                    self._phase("mx:serve:draft")
                    drafts = self._build_drafts()
            if drafts:
                self._dispatch_verify(drafts)
                worked = stepped = True
            elif self._live_slots():
                self._dispatch_step()
                worked = stepped = True
        # drain PREVIOUS dispatches' readbacks: while stepping, the
        # newest dispatch stays in flight so the device computes it
        # while the host routes the older (S,)-sized arrays; once the
        # loop stops stepping, everything drains so streams finish
        worked |= self._flush_drain(keep=1 if stepped else 0)
        return worked

    def _live_slots(self):
        return any(r is not None and i not in self._chunk_slots
                   for i, r in enumerate(self._slots))

    def _loop(self):
        while True:
            with self._work:
                if self._stopping:
                    return
            self._pump_t0 = self._clock()   # the watchdog's wedge gauge
            try:
                worked = self.pump()
            except Exception as e:
                # a runtime dispatch failure (device OOM, XLA error, a
                # growth retrace) must not silently kill the scheduler
                # thread and hang every consumer: fail all outstanding
                # streams with the error and stop serving
                self._fail_all(e)
                return
            finally:
                self._pump_t0 = None
            if not worked:
                with self._work:
                    if self._stopping:
                        return
                    if not self._pending and not self._inflight:
                        with telemetry.span("mx:serve:idle"):
                            self._work.wait(0.05)

    def _watch_dispatch(self, fn):
        """Re-arm the wedge gauge for one dispatch — or SUSPEND it when
        ``fn`` has never compiled: a legitimate first-request /
        pool-growth jit compile can take far longer than any sane
        ``step_timeout``, and the watchdog must not kill a healthy
        server for it.  (Run on the scheduler thread only; _pump_t0 is
        cleared by _loop after the pump either way.)"""
        if self._pump_t0 is None:
            return   # pump-driven (no loop thread): nothing to gauge
        cache_size = getattr(fn, "_cache_size", None)
        if cache_size is not None and cache_size() == 0:
            self._pump_t0 = None     # cold program: compile, not wedge
        else:
            self._pump_t0 = self._clock()   # per-dispatch budget

    def _watch(self):
        """Scheduler watchdog (daemon, started next to the loop
        thread): fails all in-flight streams when the pump thread DIES
        without running its own failure path (a BaseException, a
        crashed C extension — an Exception inside pump() is already
        handled by ``_loop``), or when one pump WEDGES past
        ``step_timeout`` (a hung dispatch: the thread cannot be
        recovered, but every consumer gets the error instead of
        blocking forever).  Exits when the server stops."""
        while True:
            with self._work:
                if self._stopping:
                    return
            th = self._thread
            if th is not None and not th.is_alive():
                with self._work:
                    if self._stopping:
                        return   # clean close() raced the aliveness
                        # check: the thread exited BECAUSE we stopped
                self._watchdog_fire("scheduler thread died without "
                                    "running its failure path")
                return
            t0 = self._pump_t0
            if self.step_timeout is not None and t0 is not None \
                    and self._clock() - t0 > self.step_timeout:
                self._watchdog_fire(
                    f"scheduler pump wedged for more than "
                    f"step_timeout={self.step_timeout}s "
                    "(MXNET_SERVE_STEP_TIMEOUT) — a dispatch is hung")
                return
            time.sleep(0.05)

    def _watchdog_fire(self, why):
        telemetry.emit("watchdog_fired", server=self.telemetry_label,
                       reason=why)
        telemetry.counter("serve_watchdog_fired_total",
                          server=self.telemetry_label).inc()
        self._fail_all(MXNetError(
            f"serve watchdog fired: {why}; all in-flight streams "
            "failed"))

    def _fail_all(self, exc):
        err = exc if isinstance(exc, MXNetError) else \
            MXNetError(f"serving loop failed: {exc!r}")
        self._fatal = err   # submit()/start() raise this from now on
        with self._work:
            self._stopping = True
            self._work.notify_all()
        self._inflight.clear()   # readbacks are dropped, not routed
        self._teardown(err)

    # cancellation --------------------------------------------------------- #
    def _cancel(self, req):
        """Cross-thread cancellation entry (``TokenStream.cancel``).
        A queued request is dropped and finished HERE; an admitted one
        is only FLAGGED — its slot frees on the scheduler thread at
        the next step boundary (``_process_cancels``), so co-resident
        lanes never see a mid-step state edit.  Idempotent; False once
        the request already retired."""
        with self._work:
            if req.retired or req.stream.done:
                return False
            queued = req in self._pending
            if self.sync_mode and not queued:
                # sync fallback mid-kv_generate: there are no step
                # boundaries to retire at, so cancellation cannot take
                # effect — report failure rather than lie (the
                # slot-pool path is where cancel is real;
                # docs/SERVING.md)
                return False
            already = req.cancelled
            req.cancelled = True
            in_queue = False
            if not already and queued:
                self._pending.remove(req)
                in_queue = True
            self._work.notify_all()
        if in_queue:
            self._retire_aside(req, "cancelled")
        return True

    def _process_cancels(self):
        """Free cancelled requests' slots at the step boundary (the
        scheduler thread; also the pump-driven path).  The device lane
        itself is left alone — like any retired slot it keeps
        computing masked until re-admission overwrites it — so the
        retirement costs ZERO extra dispatches and cannot perturb
        co-resident streams."""
        with self._lock:
            hit = [(i, r) for i, r in enumerate(self._slots)
                   if r is not None and r.cancelled]
            for i, _r in hit:
                self._slots[i] = None
            if hit:
                self._work.notify_all()
        for i, r in hit:
            self._drop_chunk_record(i)
            self._free_slot_pages(i)
            self._retire_aside(r, "cancelled")
        # queued cancellations normally drop in _cancel; this sweeps
        # any that raced the pending-pop
        with self._lock:
            stale = [r for r in self._pending if r.cancelled]
            for r in stale:
                self._pending.remove(r)
        for r in stale:
            self._retire_aside(r, "cancelled")
        return bool(hit) or bool(stale)

    def _retire_aside(self, req, reason):
        """Finish a stream OUTSIDE the normal drain path (cancelled, or
        deadline-lapsed while queued): the stream seals with whatever
        tokens arrived, the span closes with ``reason``."""
        req.stream._cancelled = reason == "cancelled"
        req.stream._finish()
        self._observe_retire(req, reason)

    def _teardown(self, err, reason="error"):
        """Fail every queued and in-flight request with ``err``.  The
        snapshot-and-clear runs under the lock; streams are finished
        OUTSIDE it — _finish wakes consumer threads (and on_token
        callers) that may immediately re-enter submit()/stats()."""
        from ..telemetry.memory import ACCOUNTANT

        # ordering matters: flag FIRST, then release — a concurrent
        # wedged dispatch that assigns self._state after our None sees
        # the flag and releases its own result (no re-pin window)
        self._torn = True
        # the pool buffers die with the server: RELEASE them (drop the
        # state refs so the device memory is actually freed, not just
        # unaccounted) and retire the ledger entry + stats() mirror
        # together, so a closed server's stats()["pool_bytes"] agrees
        # with the zeroed device_bytes gauge AND with the allocator
        # (idempotent: close() after a failed scheduler lands here
        # twice)
        self._state = None
        ACCOUNTANT.drop("serve.kv_pool", self.telemetry_label)
        self._pool_bytes = 0
        # page bookkeeping dies with the pool buffers (idempotent):
        # slot rows, chunk records and the prefix index all release
        # their refs so a closed server reports pages_in_use == 0
        if self._pages is not None:
            self._chunking.clear()
            self._chunk_slots.clear()
            for i in range(len(self._slot_pages)):
                self._free_slot_pages(i)
            if self._prefix is not None:
                self._prefix.drop_all()
            self._tele["pages"].set(0)
        with self._lock:
            dropped = list(self._pending)
            self._pending.clear()
            leftover = [r for r in self._slots if r is not None]
            self._slots = [None] * len(self._slots)
            self._work.notify_all()
        for req in dropped + leftover:
            req.stream._finish(err)
            self._observe_retire(req, reason)

    # memory budget ------------------------------------------------------- #
    def _account_pool(self):
        """Register the pool state's exact bytes with the process-wide
        memory accountant (``device_bytes{subsystem="serve.kv_pool",
        device=}`` gauge + one ``device_memory`` event per change) —
        called at init and after each growth, never per step.  The
        ledger stores byte counts only, so the steady state's donated
        cache buffers (same shapes every step) stay correctly
        accounted without re-registration."""
        from ..telemetry.memory import ACCOUNTANT, nbytes_of

        self._pool_bytes = nbytes_of(self._state)
        ACCOUNTANT.set("serve.kv_pool", self.telemetry_label,
                       self._state)

    def _check_budget(self, num_slots, scratch=0, what="",
                      num_pages=None):
        """Refuse device allocations the HBM budget cannot hold, with a
        clean error naming requested vs available bytes (instead of an
        allocator OOM mid-dispatch).  ``num_slots`` prices the resident
        pool at that size (``num_pages`` overrides the dense-equivalent
        default page count); ``scratch`` adds transient bytes
        (admission prefill caches) on top of it."""
        if self.hbm_budget is None:
            return
        from ..telemetry.memory import format_bytes
        from .engine import pool_state_bytes

        projected = pool_state_bytes(self._progs, num_slots,
                                     num_pages=num_pages) \
            + scratch
        if projected <= self.hbm_budget:
            return
        requested = projected - self._pool_bytes
        available = max(self.hbm_budget - self._pool_bytes, 0)
        raise MXNetError(
            f"serve HBM budget exceeded: {what or 'allocation'} "
            f"requests {format_bytes(requested)} on top of the "
            f"{format_bytes(self._pool_bytes)} resident pool, but only "
            f"{format_bytes(available)} of the "
            f"{format_bytes(self.hbm_budget)} budget "
            f"(hbm_budget= / MXNET_SERVE_HBM_BUDGET) remains — raise "
            "the budget, pin smaller MXNET_SERVE_POOL_SIZES / "
            "MXNET_SERVE_ADMIT_SIZES, or lower max_total_len")

    # admissions --------------------------------------------------------- #
    def _take_pending(self):
        with self._lock:
            if not self._pending:
                return None
            req = self._pending.popleft()
            self._work.notify_all()
            return req

    def _maybe_grow(self):
        """Grow the pool to the next pinned size when the backlog wants
        more lanes than exist (retrace happens at most
        ``len(pool_sizes) - 1`` times, never per request)."""
        from .engine import PoolPrograms, pool_state_grow

        S = len(self._slots)
        busy = sum(r is not None for r in self._slots)
        want = busy + len(self._pending)
        bigger = [s for s in self.pool_sizes if s > S]
        if not bigger or want <= S:
            return
        new_s = S
        for s in bigger:
            new_s = s
            if s >= want:
                break
        # consult the memory accountant BEFORE compiling/allocating the
        # larger pool: an over-budget growth is a clean refusal naming
        # the shortfall, not an allocator OOM halfway through a retrace.
        # Priced as old + new pools RESIDENT TOGETHER: pool_state_grow
        # pads the old state into the new one, so both live until the
        # copy completes — the transient peak, not the settled size.
        # The refusal is deliberately LOUD (ISSUE 10 acceptance): a
        # budget the pinned pool ladder outgrows is a sizing error the
        # operator must see and fix (pin smaller pool sizes, or raise
        # the budget — tools/memory_report.py prices configs offline),
        # not a condition to silently serve degraded through
        # an explicitly pinned page count stays pinned across growth;
        # the dense-equivalent default rescales with the slot count
        new_pages = self._pages.num_pages if self._num_pages_fixed \
            else new_s * self._progs.maxp if self._progs.paged else 0
        self._check_budget(new_s, scratch=self._pool_bytes,
                           what=f"pool growth {S} -> {new_s} slots",
                           num_pages=new_pages)
        # growth compiles (eager state pad now, fresh step/admit
        # programs at their first dispatch): suspend the watchdog's
        # wedge gauge for the rest of this pump — a retrace is slow,
        # not wedged
        self._pump_t0 = None
        progs = PoolPrograms(self.model, new_s, self.T,
                             self.temperature, self.top_k, self.eos_id,
                             self.weights,
                             telemetry_label=self.telemetry_label,
                             page_size=self.page_size,
                             num_pages=new_pages,
                             kv_dtype=self.kv_dtype,
                             max_chunk=self.prefill_buckets[-1])
        # the old pool's in-flight readbacks refer to old slot indices
        # and page ids; they stay valid — slots and pages only ever grow
        self._progs = progs
        self._state = pool_state_grow(self._state, new_s,
                                      new_pages=new_pages)
        self._account_pool()
        if new_pages > self._pages.num_pages:
            self._pages.grow(new_pages)
        with self._lock:
            self._slots.extend([None] * (new_s - S))
        self._slot_pages.extend([] for _ in range(new_s - S))
        self._slot_wpages.extend({} for _ in range(new_s - S))
        self._slot_pos.extend([0] * (new_s - S))
        self._tenant.extend([None] * (new_s - S))
        self._count("pool_grows")

    def _admit_pending(self):
        """Wave-building batched admission: gather ALL currently
        pending requests the free slots can take (capped at the
        largest pinned ``A`` bucket), PLAN each one against the page
        pool / prefix cache, and dispatch each mode in bulk — prefill
        admissions as ONE bucketed ``(A, P)`` dispatch, prefix-cache
        hits as ONE no-forward hit dispatch, long prompts as chunked
        prefill records the pump streams in.  A burst of k arrivals at
        a step boundary costs 1-2 dispatches, not k.  The outer loop
        spills a backlog larger than the biggest ``A`` bucket (or than
        the free slots) into follow-up dispatches in the same pump."""
        admitted = may_retire = False
        self._phase("mx:serve:admit_build")
        self._maybe_grow()
        cap = self.admit_sizes[-1]
        while True:
            free = [i for i, r in enumerate(self._slots)
                    if r is None and i not in self._chunk_slots]
            if not free:
                break
            limit = min(len(free), cap)
            if self.hbm_budget is not None:
                # price the wave's admission scratch BEFORE popping it
                # into the slot table: a refusal here leaves the
                # requests pending and the slots free (a raise after
                # slot-recording would strand never-admitted lanes that
                # close(drain=True) then pumps forever).  The wave is
                # CLAMPED to the largest pinned A bucket the budget can
                # hold next to the current pool — a burst that would
                # only overflow at the big bucket admits in smaller
                # waves instead of failing; only a pool too large for
                # even the smallest bucket (reachable after growth)
                # raises.  The pop below is capped at the clamped size,
                # so a submit racing in can't inflate the priced A.
                from .engine import admit_scratch_bytes, \
                    pool_state_bytes

                with self._lock:
                    limit = min(limit, len(self._pending))
                if not limit:
                    break
                progs = self._progs
                resident = pool_state_bytes(
                    progs, len(self._slots),
                    num_pages=self._pages.num_pages)
                # the admit scratch is a DENSE native-dtype prefill
                # cache regardless of the pool's kv_dtype — priced as
                # such, so an int8 pool's smaller resident footprint
                # can't hide the full-size admission spike
                usable = [a for a in self.admit_sizes
                          if resident + admit_scratch_bytes(progs, a)
                          <= self.hbm_budget]
                if not usable:
                    A = self.admit_sizes[0]
                    self._check_budget(
                        len(self._slots),
                        scratch=admit_scratch_bytes(progs, A),
                        num_pages=self._pages.num_pages,
                        what=f"admission wave of {limit} "
                             f"(A={A} prefill scratch)")
                limit = min(limit, usable[-1])
            # pop + record into the slot table ATOMICALLY: a request
            # must never be invisible to close(drain=True)'s "anything
            # outstanding?" predicate (or to _fail_all) while its
            # admission dispatch is still being built.  Cancelled or
            # already-deadline-lapsed requests retire HERE, at the
            # admission boundary, without ever occupying a slot.
            wave, dropped = [], []
            now = self._clock()
            with self._lock:
                while self._pending and len(wave) < limit:
                    req = self._pending.popleft()
                    if req.cancelled or (req.deadline is not None
                                         and now >= req.deadline):
                        dropped.append(req)
                        continue
                    slot = free[len(wave)]
                    self._slots[slot] = req
                    wave.append((slot, req))
                if wave or dropped:
                    self._work.notify_all()
            for req in dropped:
                self._retire_aside(
                    req, "cancelled" if req.cancelled
                    else "deadline_exceeded")
            admitted |= bool(dropped)
            if not wave:
                if dropped:
                    continue   # the backlog behind the drops may fit
                break
            # reserve pages + classify each popped request (prefill
            # admit / prefix-cache hit / chunked prefill).  A pool that
            # can't cover a request right now unwinds IT and everything
            # behind it back to the queue front, in order — retiring
            # slots free pages and the next pump retries.
            plans, failed = [], None
            for k, (slot, req) in enumerate(wave):
                plan = self._plan_admission(req, slot)
                if plan is None:
                    failed = wave[k:]
                    break
                plans.append(plan)
            if failed is not None:
                with self._lock:
                    for slot, _req in failed:
                        self._slots[slot] = None
                    for _slot, req in reversed(failed):
                        self._pending.appendleft(req)
            admit_wave = [(p["slot"], p["req"]) for p in plans
                          if p["mode"] == "admit"]
            hit_wave = [p for p in plans if p["mode"] == "hit"]
            for p in plans:
                if p["mode"] == "chunk":
                    self._chunk_slots.add(p["slot"])
                    self._chunking.append(
                        {"req": p["req"], "slot": p["slot"],
                         "off": p["off"], "zero": p["zero"]})
            # hits dispatch FIRST: a COW source page another plan's
            # eviction freed and re-allocated this wave must be copied
            # before any admit/chunk dispatch can overwrite it (the
            # device stream is FIFO)
            if hit_wave:
                self._dispatch_hits(hit_wave)
            if admit_wave:
                self._dispatch_admit(admit_wave)
                may_retire |= any(r.max_new == 1
                                  for _, r in admit_wave)
            admitted |= bool(plans)
            if failed is not None:
                break
        chunked, chunk_retire = self._pump_chunks()
        if may_retire or chunk_retire:
            # a 1-token budget retires INSIDE the admission executable;
            # read the (first_tok, done) flags back now so its slot
            # frees before the step-dispatch decision — no wasted
            # dispatch.  Every other admission drains lazily with the
            # step readbacks, off the hot path (an EOS on the very
            # first token costs at most one masked-lane step).
            self._drain_admits()
        return admitted or chunked

    def _dispatch_admit(self, wave):
        """ONE bucketed (A, P) admission dispatch for a wave of
        ``(slot, request)`` pairs: A = smallest pinned wave bucket that
        fits the wave, P = smallest pinned prompt bucket that fits the
        wave's longest prompt (the admission planner routes longer
        prompts to chunked prefill, so one always exists).  Rows beyond
        the wave are masked no-ops on device; the prefill stream lands
        in the wave's reserved pages via the page-row operand."""
        fault_point("serve.admit", server=self.telemetry_label,
                    wave=len(wave))
        self._phase("mx:serve:admit_build")
        A = _bucket_for(self.admit_sizes, len(wave))
        P = _bucket_for(self.prefill_buckets,
                        max(req.prompt.size for _, req in wave))
        # the A-lane prefill scratch was budget-checked in
        # _admit_pending BEFORE the wave was popped into the slot
        # table (wave size <= the priced limit, so A here never
        # exceeds the checked bucket)
        fn = self._progs.admit_fn(A, P)
        self._watch_dispatch(fn)
        prompts = onp.zeros((A, P), onp.int32)
        # idle rows: valid=0 (their scatter drops on device); true_len
        # stays 1 so the per-row last-index gather reads a real column
        meta = onp.zeros((A, schema.meta_width("admit")), onp.int32)
        meta[:, schema.meta_col("admit", "true_len")] = 1
        # per-row wall-clock deadlines (server-epoch seconds; +inf =
        # none), scattered into the slot-state deadline vector the
        # step checks device-side
        dls = onp.full((A,), onp.inf, onp.float32)
        # reserved-page rows: idle rows and tail pages past a row's
        # reservation carry the sentinel, so their scatter drops
        npb = -(-P // self._progs.page)
        pages = onp.full((A, npb), self._progs.num_pages, onp.int32)
        # int8 recycled-page reset operand: EVERY page the wave
        # reserved (decode-frontier pages included — those are first
        # written by the step/verify RMWs, which floor at the page's
        # resident scale).  The executable zeroes their scales before
        # its own page writes; f32 pools ignore the operand.
        zpages = onp.full((A, self._progs.maxp), self._progs.num_pages,
                          onp.int32)
        if self._wpages is not None:
            # a windowed model's wave runs through each row's own table
            # rows: the pair (main rows, window rings)
            zpages = (zpages, self._window_table(
                [slot for slot, _ in wave], A))
        zmain = zpages[0] if self._wpages is not None else zpages
        for i, (slot, req) in enumerate(wave):
            n = req.prompt.size
            prompts[i, :n] = req.prompt
            meta[i] = schema.meta_row(
                "admit", valid=1, true_len=n, slot=slot,
                stop_pos=n + req.max_new - 1, seed=req.seed,
                spec_depth=self._slot_spec_depth(req))
            if req.deadline is not None:
                dls[i] = req.deadline - self._epoch
            row = self._slot_pages[slot]
            k = min(npb, len(row))
            pages[i, :k] = row[:k]
            zmain[i, :len(row)] = row
        # request-span admission fields (waves are step-boundary-rare,
        # not per-token)
        now = time.perf_counter()
        S = len(self._slots)
        busy = sum(r is not None for r in self._slots)
        occ = busy / S if S else 0.0
        seq = self._next_seq()
        for _slot, req in wave:
            wait = now - req.stream.submit_time
            req.span.update(queue_wait_s=wait, wave=len(wave),
                            a_bucket=A, p_bucket=P,
                            occupancy_at_admit=occ, admit_seq=seq)
            self._tele["wait"].observe(wait)
        ntok = sum(int(req.prompt.size) for _, req in wave)
        param_vals, q8, sw = self._progs.operands
        self._phase("mx:serve:admit", seq=seq, wave=len(wave),
                    a_bucket=A, p_bucket=P, rows=A * P, tokens=ntok,
                    requests=[r.stream.request_id for _, r in wave])
        new_state, (first, done) = fn(param_vals, prompts, meta, dls,
                                      pages, zpages, *self._state)
        self._state = new_state
        if self._torn:
            # the watchdog tore the server down while this dispatch was
            # wedged: the accountant already reported the pool freed —
            # drop the late result instead of re-pinning it
            self._state = None
            return
        self._count("admit_dispatches")
        self._count("admit_rows", A * P)
        self._count("admit_tokens", ntok)
        if self._progs.slot_kinds:
            self._state_resets += len(wave)
        self._inflight.append(("admit", (first, done), list(wave), seq))
        for slot, req in wave:
            self._prompt_landed(slot, req)

    # paged admission planning ------------------------------------------- #
    def _alloc_pages(self, n, protect=()):
        """All-or-nothing page reservation, evicting LRU prefix-cache
        entries (never ``protect``) when the free list runs dry."""
        got = self._pages.alloc(n)
        if got is None and self._prefix is not None:
            self._prefix.evict(n - self._pages.free_pages,
                               protect=protect)
            got = self._pages.alloc(n)
        return got

    def _free_slot_pages(self, slot):
        """Release one slot's page-table refs (idempotent: the row is
        cleared first).  Shared pages survive while the prefix index
        or another slot still holds them — that's the refcount."""
        row = self._slot_pages[slot]
        self._slot_pages[slot] = []
        for p in row:
            self._pages.decref(p)
        held = self._slot_wpages[slot]
        self._slot_wpages[slot] = {}
        for p in held.values():
            self._wpages.decref(p)

    # the window pool ------------------------------------------------------ #
    def _window_take(self, slot, first_pos, last_pos, tail=None):
        """Make ``slot`` hold a window page for every logical page from
        ``first_pos``'s to ``last_pos``'s: out of ``tail`` (the prefix
        index's pages for a cached prompt's end, shared read-only) where
        it has them, fresh ones otherwise; the index's least recently
        touched tails make room when the pool is dry.  False, with nothing
        taken, when even that is not enough."""
        PG = self._progs.page
        held = self._slot_wpages[slot]
        lps = [lp for lp in range(max(first_pos, 0) // PG,
                                  last_pos // PG + 1) if lp not in held]
        fresh = [lp for lp in lps if tail is None or lp not in tail]
        got = self._wpages.alloc(len(fresh))
        if got is None and self._prefix is not None:
            self._prefix.evict_tails(len(fresh) - self._wpages.free_pages)
            got = self._wpages.alloc(len(fresh))
        if got is None:
            return False
        held.update(zip(fresh, got))
        for lp in lps:
            if lp not in held:
                held[lp] = tail[lp]
                self._wpages.incref(tail[lp])
        return True

    def _window_need(self, slot, first_pos, last_pos, tail=None):
        if not self._window_take(slot, first_pos, last_pos, tail):
            raise MXNetError(
                f"serve window pool exhausted: {self._wpages.num_pages} "
                "pages cannot hold every live slot's window and the "
                "chunk in flight — raise num_window_pages or pin fewer "
                "slots / smaller prefill buckets")

    def _window_release(self, slot, next_pos):
        """Let go of the window pages behind what a query at ``next_pos``
        can still reach."""
        held = self._slot_wpages[slot]
        keep = next_pos // self._progs.page - self._wback
        for lp in [lp for lp in held if lp < keep]:
            self._wpages.decref(held.pop(lp))

    def _prompt_landed(self, slot, req):
        """The dispatch just queued writes the last of ``req``'s prompt
        into ``slot``'s pages: index its FULL pages for future hits (any
        consumer's read is a later dispatch on the same stream; a windowed
        model's tail with them) and let the slot step from its end."""
        L = int(req.prompt.size)
        if self._prefix is not None:
            self._prefix.register(
                req.prompt, L, self._slot_pages[slot],
                self._slot_wpages[slot]
                if self._wpages is not None else None)
        self._slot_entered(slot, L)

    def _slot_entered(self, slot, next_pos):
        """``slot`` steps from ``next_pos`` on (the host's mirror of the
        device's position; a windowed model's pages follow it)."""
        self._slot_pos[slot] = next_pos
        if self._wpages is not None:
            self._window_release(slot, next_pos)

    def _window_table(self, slots, rows=None):
        """The ``(rows, ring)`` window-table operand for ``slots`` in
        order: each slot's pages at ``logical page % ring``, sentinel
        elsewhere."""
        progs = self._progs
        ring = progs.ring
        out = onp.full((len(slots) if rows is None else rows, ring),
                       progs.window_pages, onp.int32)
        for i, slot in enumerate(slots):
            held = None if slot is None else self._slot_wpages[slot]
            if held:
                # one assignment a slot: a window of K/V rows is some
                # hundreds of pages, and this runs before every step
                lps = onp.fromiter(held.keys(), onp.int64, len(held))
                out[i, lps % ring] = onp.fromiter(held.values(), onp.int32,
                                                  len(held))
        return out

    def _drop_chunk_record(self, slot):
        """Forget a mid-chunked-prefill slot (cancel/teardown paths)."""
        if slot in self._chunk_slots:
            self._chunk_slots.discard(slot)
            for rec in list(self._chunking):
                if rec["slot"] == slot:
                    self._chunking.remove(rec)

    def _plan_admission(self, req, slot):
        """Decide how one popped request enters its slot, reserving its
        pool pages up front (ALL ``ceil((L+max_new)/page)`` of them —
        all-or-nothing, so a half-admitted pool can never deadlock):

        - ``admit``  — one bucketed prefill dispatch (no cached prefix,
          prompt fits the largest pinned bucket);
        - ``hit``    — the prefix cache covers every prompt token but
          (at most) the last: shared pages map READ-ONLY into the row,
          ZERO prefill dispatches, at most one COW page copy;
        - ``chunk``  — the prompt (or its uncached suffix) streams in
          over chunked-prefill dispatches.

        Returns ``None`` when the pool can't supply the pages right
        now (the caller re-queues the request and retries next pump,
        after retirements free pages)."""
        progs = self._progs
        PG = progs.page
        L = int(req.prompt.size)
        need = progs.pages_for(L + req.max_new)
        windowed, tail = self._wpages is not None, None
        if self._prefix is None:
            m, shared = 0, []
        elif windowed:
            # a window page is never copied, so a match stops short of
            # the whole prompt (the first write lands in a page of the
            # slot's own), and where the index kept no tail for a length
            # the chain is cut back to one it did, or missed
            m, shared, tail = self._prefix.match(req.prompt,
                                                 limit=(L - 1) // PG)
        else:
            m, shared = self._prefix.match(req.prompt)
        plan = self._plan_pages(req, slot, L, need, m, shared)
        if plan is None:
            return None
        self._tenant[slot] = req.stream.request_id
        if windowed:
            # the window pages the admission dispatch itself reads and
            # writes: an admit's whole prompt, a hit's tail; a chunk takes
            # its own as it goes
            first = 0 if plan["mode"] == "admit" else m * PG
            last = L - 1 if plan["mode"] == "admit" else m * PG - 1
            if not self._window_take(slot, first - self._wback * PG
                                     if m else first, last, tail):
                self._free_slot_pages(slot)
                return None
            if plan["mode"] == "hit":
                self._slot_entered(slot, L - 1)
        self._prompt_tokens += L
        if plan["mode"] != "admit":
            self._prompt_cached += m * PG
        return plan

    def _plan_pages(self, req, slot, L, need, m, shared):
        """``_plan_admission``'s main-pool half: reserve the pages, map the
        shared ones, name the mode."""
        progs = self._progs
        PG = progs.page
        if m and m * PG >= L - 1:
            # full hit.  The consumer enters at pos = L-1 and its first
            # step RE-WRITES that position's K/V — when the cached
            # pages cover all L tokens that write would land in the
            # last shared page, so it gets an eager COW copy; when they
            # cover L-1 the write lands in the first owned page.
            copy = m * PG == L
            keep = m - 1 if copy else m
            # protect the WHOLE matched chain (incl. the COW source):
            # evicting the source here could hand its page to a later
            # plan in the same wave before the copy dispatch reads it
            owned = self._alloc_pages(need - keep, shared[:m])
            if owned is None:
                return None
            for p in shared[:keep]:
                self._pages.incref(p)
            self._slot_pages[slot] = list(shared[:keep]) + owned
            return {"mode": "hit", "req": req, "slot": slot,
                    "shared": keep,
                    "src": shared[m - 1] if copy else -1,
                    "dst": owned[0] if copy else -1}
        if m == 0 and L <= self.prefill_buckets[-1]:
            owned = self._alloc_pages(need)
            if owned is None:
                return None
            self._slot_pages[slot] = owned
            return {"mode": "admit", "req": req, "slot": slot}
        # chunked prefill: a long prompt streams in over several
        # dispatches; a PARTIAL prefix hit maps its cached pages and
        # streams only the divergent suffix
        owned = self._alloc_pages(need - m, shared)
        if owned is None:
            return None
        for p in shared:
            self._pages.incref(p)
        self._slot_pages[slot] = list(shared) + owned
        if m:
            self._count("prefix_hits")
        return {"mode": "chunk", "req": req, "slot": slot,
                "off": m * PG, "zero": owned}

    def _page_table(self):
        """The step's ``(S, MAXP)`` int32 page-table operand, sentinel-
        padded.  Slots mid-chunked-prefill get ALL-SENTINEL rows: their
        reserved pages are being filled by chunk dispatches, and the
        step's masked zombie lane must not scribble on them — the real
        row appears once the final chunk activates the slot."""
        progs = self._progs
        pt = onp.full((len(self._slots), progs.maxp), progs.num_pages,
                      onp.int32)
        live = []
        for i, row in enumerate(self._slot_pages):
            ok = bool(row) and i not in self._chunk_slots
            live.append(i if ok else None)
            if ok:
                # a slot's row is fixed from admission to retirement: its
                # array is made once
                ent = self._pt_rows.get(i)
                if ent is None or ent[0] is not row:
                    ent = self._pt_rows[i] = (row, onp.asarray(row,
                                                               onp.int32))
                pt[i, :len(row)] = ent[1]
        if self._wpages is not None:
            return pt, self._window_table(live)
        return pt

    def _dispatch_hits(self, hits):
        """ONE masked dispatch admits a whole wave of prefix-cache
        HITS: the shared pages are already resident, so the executable
        only COW-copies each row's boundary page (if any) and scatters
        slot state — no model forward, zero prefill dispatches, and the
        request's first token arrives from the NEXT regular step
        (TTFT ≈ one decode step)."""
        self._phase("mx:serve:admit_build")
        A = _bucket_for(self.admit_sizes, len(hits))
        fn = self._progs.admit_hit_fn(A)
        self._watch_dispatch(fn)
        seq = self._next_seq()
        sentinel = self._progs.num_pages
        meta = onp.zeros((A, schema.meta_width("hit")), onp.int32)
        meta[:, schema.meta_col("hit", "true_len")] = 1
        dls = onp.full((A,), onp.inf, onp.float32)
        srcs = onp.full((A,), sentinel, onp.int32)
        dsts = onp.full((A,), sentinel, onp.int32)
        # int8 recycled-page reset operand: each hit row's freshly
        # OWNED pages (decode frontier + the COW dst) — the shared
        # prefix pages keep their resident scales.  The executable
        # zeroes these AFTER its src gathers, BEFORE its dst scatter.
        zpages = onp.full((A, self._progs.maxp), sentinel, onp.int32)
        now = time.perf_counter()
        S = len(self._slots)
        busy = sum(r is not None for r in self._slots)
        occ = busy / S if S else 0.0
        for i, plan in enumerate(hits):
            slot, req = plan["slot"], plan["req"]
            L = req.prompt.size
            meta[i] = schema.meta_row(
                "hit", valid=1, true_len=L, slot=slot,
                stop_pos=L + req.max_new - 1, seed=req.seed,
                last_tok=int(req.prompt[-1]),
                spec_depth=self._slot_spec_depth(req))
            if req.deadline is not None:
                dls[i] = req.deadline - self._epoch
            if plan["src"] >= 0:
                srcs[i] = plan["src"]
                dsts[i] = plan["dst"]
                self._count("cow_copies")
            fresh = self._slot_pages[slot][plan["shared"]:]
            zpages[i, :len(fresh)] = fresh
            self._count("prefix_hits")
            wait = now - req.stream.submit_time
            req.span.update(queue_wait_s=wait, wave=len(hits),
                            a_bucket=A, p_bucket=0,
                            occupancy_at_admit=occ, admit_seq=seq)
            self._tele["wait"].observe(wait)
        # no model forward: a hit computes no token positions
        self._phase("mx:serve:admit_hit", seq=seq, wave=len(hits),
                    a_bucket=A, p_bucket=0, rows=0, tokens=0,
                    requests=[p["req"].stream.request_id for p in hits])
        new_state = fn(meta, dls, srcs, dsts, zpages, *self._state)
        self._state = new_state
        if self._torn:
            self._state = None
            return
        self._count("hit_dispatches")

    def _pump_chunks(self):
        """Advance every mid-prefill request by ONE chunk dispatch per
        pump, interleaved with decode steps so resident sequences keep
        streaming while a long prompt fills in.  Returns ``(worked,
        may_retire)`` — the latter when a final chunk could retire its
        request inside the dispatch (1-token budget / EOS-at-admit)."""
        worked = may_retire = False
        for rec in list(self._chunking):
            req, slot = rec["req"], rec["slot"]
            if req.cancelled or (req.deadline is not None
                                 and self._clock() >= req.deadline):
                self._drop_chunk_record(slot)
                with self._lock:
                    if self._slots[slot] is req:
                        self._slots[slot] = None
                self._free_slot_pages(slot)
                self._retire_aside(
                    req, "cancelled" if req.cancelled
                    else "deadline_exceeded")
                worked = True
                continue
            final = self._dispatch_chunk(rec)
            worked = True
            if final:
                self._drop_chunk_record(slot)
                may_retire |= req.max_new == 1
        return worked, may_retire

    def _dispatch_chunk(self, rec):
        """ONE slice of a streaming prefill: up to the largest pinned
        prompt bucket of tokens runs through the slot's page-table row
        at the record's landing offset.  The FINAL chunk also samples
        the request's first token and activates the slot — its
        readback routes through the admit drain path.  Returns whether
        this was the final chunk."""
        req, slot, off = rec["req"], rec["slot"], rec["off"]
        fault_point("serve.chunk", server=self.telemetry_label)
        self._phase("mx:serve:admit_build")
        L = int(req.prompt.size)
        remaining = L - off
        top = self.prefill_buckets[-1]
        if remaining > top:
            C, final, ntok = top, False, top
        else:
            C = _bucket_for(self.prefill_buckets, remaining)
            final, ntok = True, remaining
        fn = self._progs.chunk_fn(
            C, self._progs.key_pages_for(C, off + ntok))
        self._watch_dispatch(fn)
        toks = onp.zeros((C,), onp.int32)
        toks[:ntok] = req.prompt[off:off + ntok]
        meta = onp.asarray(schema.meta_row(
            "chunk", final=1 if final else 0, slot=slot, true_len=L,
            stop_pos=L + req.max_new - 1, seed=req.seed,
            nlast=(L - 1 - off) if final else C - 1, off=off,
            spec_depth=self._slot_spec_depth(req)), onp.int32)
        dl = onp.float32(onp.inf if req.deadline is None
                         else req.deadline - self._epoch)
        ptrow = onp.full((self._progs.maxp,), self._progs.num_pages,
                         onp.int32)
        row = self._slot_pages[slot]
        ptrow[:len(row)] = row
        if self._wpages is not None:
            # the window pages this slice writes (those it reads behind
            # its offset the slot still holds, or took from a tail)
            self._window_need(slot, off, off + ntok - 1)
            ptrow = (ptrow, self._window_table([slot])[0])
        # int8 recycled-page reset operand: the slot's freshly
        # allocated pages ride the FIRST chunk dispatch only (their
        # stale scales must be zeroed before the first RMW floors on
        # them); later chunks send all-sentinel — they must keep the
        # scale ratchet of earlier chunks.  f32 pools ignore it.
        zrow = onp.full((self._progs.maxp,), self._progs.num_pages,
                        onp.int32)
        zero = rec.pop("zero", None)
        if zero:
            zrow[:len(zero)] = zero
        param_vals, q8, sw = self._progs.operands
        seq = self._next_seq()
        self._phase("mx:serve:chunk", seq=seq, c_bucket=C, rows=C,
                    tokens=ntok, requests=[req.stream.request_id])
        new_state, (first, done, *counted) = fn(
            param_vals, q8, sw, toks, meta, dl, ptrow, zrow, *self._state)
        self._state = new_state
        if counted:
            with self._chunk_lock:
                self._chunk_pending.append((seq, counted[0]))
        if self._torn:
            self._state = None
            return True
        self._count("chunk_dispatches")
        self._count("admit_rows", C)
        self._count("admit_tokens", ntok)
        if off:
            self._count("chunk_carried_tokens", ntok)
        if self._progs.slot_kinds and off == 0:
            self._state_resets += 1
        rec["off"] = off + ntok
        telemetry.emit("serve_chunk", server=self.telemetry_label,
                       request_id=req.stream.request_id, slot=slot,
                       c_bucket=C, offset=off, final=final)
        if final:
            wait = time.perf_counter() - req.stream.submit_time
            req.span.update(queue_wait_s=wait, wave=1, a_bucket=1,
                            p_bucket=C, admit_seq=seq)
            self._tele["wait"].observe(wait)
            self._prompt_landed(slot, req)
            self._inflight.append(("admit", (first, done),
                                   [(slot, req)], seq))
        elif self._wpages is not None:
            self._window_release(slot, rec["off"])
        return final

    # speculative decoding -------------------------------------------------- #
    def _build_drafts(self):
        """Host-side draft proposals for this pump, ``{slot: 1-D int32
        drafts}``; ``None`` when no slot proposed anything (the pump
        takes a plain step, costing exactly what it costs with
        speculation off).  Drafts chain off the last token ROUTED to
        each stream, so a just-admitted slot — including a prefix-
        cache hit, whose first step RECOMPUTES the final prompt
        position (ISSUE 16) — proposes nothing until its first step
        drains: the speculation ramp-in the COW semantics require
        falls out of the drain ordering for free."""
        drafts = {}
        for slot, req in enumerate(self._slots):
            if req is None or req.cancelled \
                    or slot in self._chunk_slots:
                continue
            toks = req.stream._toks
            if not toks:
                continue   # no routed token to chain from yet
            # the verify block emits up to k + 1 tokens; never draft
            # past the request's remaining budget (the device clamps
            # too — this just avoids wasted columns)
            k = min(self.spec_depth, req.max_new - len(toks) - 1)
            if k < 1:
                continue
            hist = onp.concatenate(
                [req.prompt, onp.asarray(toks, onp.int32)])
            prop = self._drafter.propose(hist, k)
            if prop is not None and len(prop):
                drafts[slot] = onp.asarray(
                    prop, onp.int32).reshape(-1)[:k]
        return drafts or None

    def _dispatch_verify(self, drafts):
        """ONE bucketed ``(S, k)`` draft-and-verify dispatch for this
        pump's proposals (k = smallest pinned spec bucket that fits
        the longest draft): column 0 replays each slot's device-held
        last token — a plain step for slots that proposed nothing —
        and the executable accepts each slot's longest matching
        prefix device-side.  Accepted K/V columns are already in the
        paged pool; rejected tails need no undo (pages were reserved
        all-or-nothing at admission, so rollback is the device-side
        position simply not advancing — never a copy, never a
        refcount; docs/SERVING.md)."""
        fault_point("serve.verify", server=self.telemetry_label)
        k = _bucket_for(self.spec_sizes,
                        max(d.size for d in drafts.values()))
        fn = self._progs.verify_fn(k)
        self._watch_dispatch(fn)
        S = len(self._slots)
        block = onp.zeros((S, k), onp.int32)
        nd = onp.zeros((S,), onp.int32)
        for slot, d in drafts.items():
            nd[slot] = d.size
            block[slot, :d.size] = d
        param_vals, q8, sw = self._progs.operands
        now = onp.float32(self._clock() - self._epoch)
        seq = self._next_seq()
        self._phase("mx:serve:verify", seq=seq, k_bucket=k)
        new_state, out = fn(param_vals, q8, sw, now, self._page_table(),
                            block, nd, *self._state)
        self._state = new_state
        if self._torn:
            self._state = None
            return
        self._count("verify_dispatches")
        busy = sum(r is not None for r in self._slots)
        self._occupied_lane_steps += busy
        self._capacity_lane_steps += S
        self._tele["occ"].set(busy / S)
        self._tele["pages"].set(self._pages.in_use)
        self._inflight.append(("verify", out,
                               (list(self._slots), nd, k), seq))

    # the step ------------------------------------------------------------ #
    def _dispatch_step(self):
        fault_point("serve.step", server=self.telemetry_label)
        self._watch_dispatch(self._progs.step_fn())
        param_vals, q8, sw = self._progs.operands
        # the step's wall clock: a float32 OPERAND (same aval every
        # call — never a retrace), against which the executable checks
        # every slot's deadline
        now = onp.float32(self._clock() - self._epoch)
        stepping = [i for i, r in enumerate(self._slots)
                    if r is not None and i not in self._chunk_slots]
        if self._wpages is not None:
            # each stepping slot writes at its position: take that page
            # if it is a new one, let go of what fell out of the window
            for i in stepping:
                p = self._slot_pos[i]
                self._window_need(i, p, p)
                self._window_release(i, p)
                self._wheld_max = max(self._wheld_max,
                                      len(self._slot_wpages[i]))
        seq = self._next_seq()
        self._phase("mx:serve:step", seq=seq)
        new_state, out = self._progs.step_fn()(
            param_vals, q8, sw, now, self._page_table(), *self._state)
        if self._progs.step_walks:
            page, maxp = self._progs.page, self._progs.maxp
            self._pages_walked += sum(
                min(-(-self._slot_pos[i] // page), maxp) for i in stepping)
            self._pages_table += len(stepping) * maxp
        for i in stepping:
            self._slot_pos[i] += 1
        self._state = new_state
        if self._torn:
            # late completion of a wedged dispatch after watchdog
            # teardown: don't re-pin the released pool (the gauge and
            # stats() already report 0 bytes)
            self._state = None
            return
        self._count("step_dispatches")
        self._steps += 1
        busy = sum(r is not None for r in self._slots)
        self._occupied_lane_steps += busy
        self._capacity_lane_steps += len(self._slots)
        self._tele["occ"].set(busy / len(self._slots))
        self._tele["pages"].set(self._pages.in_use)
        self._inflight.append(("step", out, list(self._slots), seq))

    # drain ---------------------------------------------------------------- #
    def _drain_admits(self):
        """Route every in-flight ADMIT readback (selective drain is
        stream-order-safe: an admit is always a request's first entry,
        and step entries only touch other, older requests)."""
        rest = deque()
        while self._inflight:
            entry = self._inflight.popleft()
            if entry[0] != "admit":
                rest.append(entry)
                continue
            self._route_admit(*entry[1:])
        self._inflight = rest

    def _route_admit(self, arrays, wave, seq):
        """Route one admission wave's ``(first_tok, done)`` readback to
        its requests' streams, in wave order — which IS submission
        order, so per-request stream order is preserved.  (A final
        CHUNK's scalar readback rides this path too, as a wave of
        one — hence the flatten.)"""
        self._phase("mx:serve:drain_wait", cause=seq)
        first = onp.asarray(arrays[0]).reshape(-1)
        done = onp.asarray(arrays[1]).reshape(-1)
        self._phase("mx:serve:route", cause=seq)
        for i, (slot, req) in enumerate(wave):
            if req.cancelled:
                continue   # retired aside; the lane's output is void
            tok = int(first[i])
            req.stream._push(tok)
            if done[i]:
                req.stream._finish()
                self._observe_retire(req,
                                     self._retire_reason(req, tok))
                freed = False
                with self._lock:
                    if self._slots[slot] is req:
                        self._slots[slot] = None
                        freed = True
                if freed:
                    self._free_slot_pages(slot)

    def _flush_drain(self, keep=0, final=False):
        """Route in-flight dispatches' readback arrays to their streams
        and free retired slots, oldest-first (the device stream is
        FIFO, so only the newest entries can still be computing).
        ``keep`` leaves that many newest entries in flight — the
        host/device overlap while the loop is actively stepping."""
        if final:
            keep = 0
        worked = False
        while len(self._inflight) > keep:
            kind, arrays, meta, seq = self._inflight.popleft()
            worked = True
            if kind == "admit":
                self._route_admit(arrays, meta, seq)
            elif kind == "verify":
                self._route_verify(arrays, meta, seq)
            else:
                self._phase("mx:serve:drain_wait", cause=seq)
                toks, emitted, done = (onp.asarray(a) for a in arrays[:3])
                self._phase("mx:serve:route", cause=seq)
                if len(arrays) > 3:
                    self._add_step_counters(arrays[3])
                snapshot = meta
                for slot, req in enumerate(snapshot):
                    if req is None or req.cancelled \
                            or not emitted[slot]:
                        continue
                    req.span.setdefault("first_step_seq", seq)
                    req.span["last_step_seq"] = seq
                    tok = int(toks[slot])
                    req.stream._push(tok)
                    if done[slot]:
                        req.stream._finish()
                        self._observe_retire(
                            req, self._retire_reason(req, tok))
                        freed = False
                        with self._lock:
                            if self._slots[slot] is req:
                                self._slots[slot] = None
                                freed = True
                        if freed:
                            self._free_slot_pages(slot)
            if self._chunk_pending:
                # this readback is in hand: every chunk before it is done
                self._fold_chunk_counters(seq)
        return worked

    def _route_verify(self, arrays, meta, seq):
        """Route one verify dispatch's ``(tokens (S, K), advance (S,),
        done (S,))`` readback: every live lane emits its accepted
        prefix plus the executable's own next token (``advance``
        tokens, >= 1 — a slot that proposed nothing gets its plain-
        step token through column 0), and the draft ledgers advance by
        exactly what each surviving stream's proposals resolved to, so
        accepted + rejected == proposed holds per stream, per server
        and in the recording (``telemetry_report --check-serve``
        re-derives it)."""
        self._phase("mx:serve:drain_wait", cause=seq)
        toks, adv, done = (onp.asarray(a) for a in arrays)
        self._phase("mx:serve:route", cause=seq)
        snapshot, nd, k_bucket = meta
        proposed_t = accepted_t = rejected_t = 0
        for slot, req in enumerate(snapshot):
            if req is None or req.cancelled:
                continue
            n = int(adv[slot])
            if n < 1:
                continue   # masked lane (inactive this dispatch)
            req.span.setdefault("first_step_seq", seq)
            req.span["last_step_seq"] = seq
            for t in toks[slot, :n]:
                req.stream._push(int(t))
            self._slot_pos[slot] += n    # the device advanced as far
            proposed = int(nd[slot])
            if proposed:
                accepted = n - 1
                rejected = proposed - accepted
                req.stream.draft_accepted += accepted
                req.stream.draft_rejected += rejected
                proposed_t += proposed
                accepted_t += accepted
                rejected_t += rejected
            if done[slot]:
                req.stream._finish()
                self._observe_retire(
                    req,
                    self._retire_reason(req, int(toks[slot, n - 1])))
                freed = False
                with self._lock:
                    if self._slots[slot] is req:
                        self._slots[slot] = None
                        freed = True
                if freed:
                    self._free_slot_pages(slot)
        if proposed_t:
            self._count("draft_proposed", proposed_t)
            self._count("draft_accepted", accepted_t)
            self._count("draft_rejected", rejected_t)
        telemetry.emit("serve_spec", server=self.telemetry_label,
                       k_bucket=k_bucket, proposed=proposed_t,
                       accepted=accepted_t, rejected=rejected_t)

    # request-span telemetry ------------------------------------------------ #
    def _retire_reason(self, req, last_tok):
        """The step/admit executables fold EOS, budget exhaustion and
        deadline expiry into one ``done`` flag; the host recovers
        which fired from the final token and the emitted count (EOS
        wins when several land on the same token; a full budget is
        ``max_len`` whether or not a deadline was also set)."""
        if self.eos_id is not None and last_tok == self.eos_id:
            return "eos"
        if len(req.stream._toks) >= req.max_new:
            return "max_len"
        if req.deadline is not None:
            return "deadline_exceeded"
        return "max_len"

    def _observe_retire(self, req, reason):
        """Close a request's span: registry observations (TTFT,
        inter-token gaps, requests-by-reason) + one ``serve_request``
        event, plus the dedicated failure-cause events
        (``deadline_exceeded`` / ``request_cancelled``) the failure
        report aggregates.  Runs at retirement only — never per token,
        never under ``_lock`` — and exactly once per request (the
        ``retired`` flag guards the cancel-vs-drain and
        teardown-after-failure races)."""
        if req.retired:
            return
        req.retired = True
        if reason == "deadline_exceeded":
            telemetry.emit("deadline_exceeded",
                           server=self.telemetry_label,
                           request_id=req.stream.request_id,
                           tokens=len(req.stream._toks),
                           max_new=req.max_new)
        elif reason == "cancelled":
            telemetry.emit("request_cancelled",
                           server=self.telemetry_label,
                           request_id=req.stream.request_id,
                           tokens=len(req.stream._toks))
        st = req.stream
        sp = req.span
        ttft = st.ttft
        if ttft is not None:
            self._tele["ttft"].observe(ttft)
        gap = self._tele["gap"]
        times = st.times
        for a, b in zip(times, times[1:]):
            gap.observe(b - a)
        telemetry.counter("serve_requests_total",
                          server=self.telemetry_label,
                          reason=reason).inc()
        telemetry.emit(
            "serve_request", server=self.telemetry_label,
            request_id=st.request_id, reason=reason,
            tokens=len(times),
            ttft_s=None if ttft is None else round(ttft, 6),
            queue_wait_s=None if "queue_wait_s" not in sp
            else round(sp["queue_wait_s"], 6),
            wave=sp.get("wave"), a_bucket=sp.get("a_bucket"),
            p_bucket=sp.get("p_bucket"),
            occupancy_at_admit=sp.get("occupancy_at_admit"),
            admit_seq=sp.get("admit_seq"),
            first_step_seq=sp.get("first_step_seq"),
            last_step_seq=sp.get("last_step_seq"),
            draft_accepted=st.draft_accepted,
            draft_rejected=st.draft_rejected)

    # sync fallback -------------------------------------------------------- #
    def _pump_sync(self):
        from ..models.decoding import kv_generate

        req = self._take_pending()
        if req is None:
            return False
        if req.cancelled:
            self._retire_aside(req, "cancelled")
            return True
        if req.deadline is not None and self._clock() >= req.deadline:
            # queue-lapsed deadline; the sync fallback cannot retire
            # MID-generation (no step boundaries), so this pre-check
            # is the whole deadline story here (docs/SERVING.md)
            self._retire_aside(req, "deadline_exceeded")
            return True
        self._count("sync_requests")
        wait = time.perf_counter() - req.stream.submit_time
        req.span["queue_wait_s"] = wait
        self._tele["wait"].observe(wait)
        try:
            out = kv_generate(self.model, req.prompt[None],
                              max_new_tokens=req.max_new,
                              temperature=self.temperature,
                              top_k=self.top_k, seed=req.seed,
                              weights=self.weights)
            new = out[0, req.prompt.size:]
            last = None
            if self.eos_id is not None:
                for t in new:
                    last = int(t)
                    req.stream._push(last)
                    if last == self.eos_id:
                        break
                req.stream._finish()
            else:
                for t in new:
                    last = int(t)
                    req.stream._push(last)
                req.stream._finish()
            self._observe_retire(
                req, "max_len" if last is None
                else self._retire_reason(req, last))
        except Exception as e:                 # surface, don't hang
            req.stream._finish(e)
            self._observe_retire(req, "error")
        return True
