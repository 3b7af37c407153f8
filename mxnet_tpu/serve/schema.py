"""Declarative operand schema for the serve pool executables.

This module is the SINGLE SOURCE OF TRUTH for the positional contracts
the paged slot-pool programs (``serve.engine.PoolPrograms``) live by:

* ``EXECUTABLES`` — each compiled program's operand list (name + order),
  which operands are donated to XLA, and the layout of its packed
  ``meta`` row.  ``jax.jit(..., donate_argnums=...)`` trusts these
  positions blindly: a new operand inserted without shifting the
  donation indices silently donates the WRONG buffer (the PR-18
  recycled-page bug rode exactly that hand-shifted pair), so the
  engine derives its ``donate_argnums`` from here instead of literals
  (:func:`jit_donate` also cross-checks the wrapped function's actual
  signature at program-build time).
* ``SLOT_STATE`` — the per-slot scalar state columns riding alongside
  the K/V page pools, in tuple order, with dtype and per-slot element
  count.  ``pool_state_bytes``/``admit_scratch_bytes`` price slots at
  :func:`slot_state_bytes` and ``tools/telemetry_report.py
  --check-serve`` re-derives the same figure from this file (loaded
  standalone, by path), so the byte ledger can never drift from the
  layout.

Both declarations are PURE LITERALS on purpose: ``tools/tracelint``'s
executable-contract rules (TL016–TL018) read them straight out of the
AST — no import, no execution — and hold every ``jax.jit`` donation
tuple, meta subscript and dispatch call-site in the lint target to the
same contract the runtime enforces.

This module imports nothing from the package (and no third-party
modules) so standalone tools can load it by file path.
"""
from __future__ import annotations

__all__ = ["EXECUTABLES", "SLOT_STATE", "KV_PAGE_INT8", "POOL_ROWS",
           "POOL_TABLES", "LANE_TILE", "row_lanes", "pool_rows",
           "executable_names", "operands",
           "arity", "donate_argnums", "donated_operands", "jit_donate",
           "state_operands", "state_arity", "slot_state_fields",
           "slot_state_bytes", "kv_page_int8_bytes", "meta_fields",
           "meta_width", "meta_col", "meta_cols", "meta_row"]

# -- the per-slot scalar state block ------------------------------------ #
# (name, dtype, elements-per-slot) in TUPLE ORDER: the state operand
# tuple every executable threads through is ``(kp, vp, *columns)``.
# ``keys`` is the 2-word per-slot PRNG key; ``dl`` the wall-clock
# retirement deadline (server-epoch seconds, +inf = none); ``spec`` the
# per-slot speculation-depth cap.
SLOT_STATE = (
    ("pos",    "int32",   1),   # next write index
    ("tok",    "int32",   1),   # last sampled token
    ("active", "bool",    1),   # slot live?
    ("stop",   "int32",   1),   # retire position
    ("keys",   "uint32",  2),   # per-slot PRNG key
    ("dl",     "float32", 1),   # per-slot deadline
    ("spec",   "int32",   1),   # speculation-depth cap
)

# -- the compiled programs ---------------------------------------------- #
# ``operands``: the wrapped function's positional parameters, in order.
# ``donated``: operand NAMES donated to XLA (the engine turns these
# into positions — always the page-pool pair today, but the indices
# differ per program because each has its own operand prefix).
# ``meta``: the packed int32 meta row's field order (() = no meta).
# ``getter``: the ``PoolPrograms`` method handing out the jitted fn —
# the linter resolves server-side dispatch call-sites through it.
# ``module``: dotted module (suffix-matched) defining the program.
EXECUTABLES = {
    "step": {
        "module": "mxnet_tpu.serve.engine",
        "getter": "step_fn",
        "telemetry": "serve.step",
        "operands": ("param_vals", "q8", "sw", "now", "pt",
                     "kp", "vp", "pos", "tok", "active", "stop",
                     "keys", "dl", "spec"),
        "donated": ("kp", "vp"),
        "meta": (),
    },
    "admit": {
        "module": "mxnet_tpu.serve.engine",
        "getter": "admit_fn",
        "telemetry": "serve.admit",
        "operands": ("param_vals", "prompts", "meta", "dls", "pages",
                     "zpages", "kp", "vp", "pos", "tok", "active",
                     "stop", "keys", "dl", "spec"),
        "donated": ("kp", "vp"),
        "meta": ("valid", "true_len", "slot", "stop_pos", "seed",
                 "spec_depth"),
    },
    "hit": {
        "module": "mxnet_tpu.serve.engine",
        "getter": "admit_hit_fn",
        "telemetry": "serve.admit_hit",
        "operands": ("meta", "dls", "src", "dst", "zpages",
                     "kp", "vp", "pos", "tok", "active", "stop",
                     "keys", "dl", "spec"),
        "donated": ("kp", "vp"),
        "meta": ("valid", "true_len", "slot", "stop_pos", "seed",
                 "last_tok", "spec_depth"),
    },
    "chunk": {
        "module": "mxnet_tpu.serve.engine",
        "getter": "chunk_fn",
        "telemetry": "serve.chunk",
        "operands": ("param_vals", "q8", "sw", "toks", "meta", "dls",
                     "ptrow", "zrow", "kp", "vp", "pos", "tok",
                     "active", "stop", "keys", "dl", "spec"),
        "donated": ("kp", "vp"),
        "meta": ("final", "slot", "true_len", "stop_pos", "seed",
                 "nlast", "off", "spec_depth"),
    },
    "verify": {
        "module": "mxnet_tpu.serve.engine",
        "getter": "verify_fn",
        "telemetry": "serve.verify",
        "operands": ("param_vals", "q8", "sw", "now", "pt", "drafts",
                     "nd", "kp", "vp", "pos", "tok", "active", "stop",
                     "keys", "dl", "spec"),
        "donated": ("kp", "vp"),
        "meta": (),
    },
}

# the int8-quantized K/V page representation (``kv_dtype="int8"``):
# each page stores codes at 1 byte/element plus ONE scale per
# (layer, KV head) for each of K and V.  ``models.decoding._kv_requant``
# produces exactly this pair (its ``_KV_CODE_DTYPE``/``_KV_SCALE_DTYPE``
# constants are test-pinned to these names) and ``PoolPrograms.
# page_bytes`` prices pages from it.
KV_PAGE_INT8 = {"codes": "int8", "scales": "float32"}

# -- the pools' row kinds ------------------------------------------------ #
# What a layer keeps of a cached token, by the CACHE KIND its model's
# per-layer description names (``models.decoding.layer_description``).
# ``table`` says which page table addresses the rows: ``main`` (a slot
# holds a page for every position it has cached; the prefix index shares
# them), ``window`` (a ring a slot: pages for its last ``window``
# positions only, released as it advances) or ``slot`` (no pages at all:
# ONE entry a slot, addressed by the slot's own index, rewritten in place
# at every token — a recurrent layer's state.  Never shared and never
# mapped from a cached prefix, so a model with such a kind serves with the
# prefix index off; an admission starts the entry from zero whatever the
# slot's last tenant left, and retiring a slot needs no device work).
# ``rows`` are the stored row kinds, each one array ``(layers of the kind,
# pages, page, lanes)`` — under the slot table ``(layers of the kind,
# slots, ...)``; the state tuple's ``kp`` holds the main-table arrays and
# ``vp`` the window-table ones (one latent array, or a K and a V array for
# ``kv_window``: a grouped-query layer with a window keeps its two rows
# under the ring, as ``kv`` keeps them under the main table) or, where a
# model has them, the slot-table ones (a uniform K/V model: ``kp`` = K,
# ``vp`` = V, both main), so that every executable donates them by the same
# two NAMES.  A row's lanes are
# its width rounded up to whole 128-lane tiles (``row_lanes``): a 64-wide
# minor dimension made the chip keep the pool page-minor and re-lay it out
# for every consumer (PERF.md, PR 27).
POOL_ROWS = {
    "kv": {"table": "main", "rows": ("k", "v")},
    "latent_index": {"table": "main", "rows": ("latent", "index_key")},
    "latent": {"table": "main", "rows": ("latent",)},
    "latent_window": {"table": "window", "rows": ("latent",)},
    "kv_window": {"table": "window", "rows": ("k", "v")},
    "ssm_state": {"table": "slot", "rows": ("state", "conv_tail")},
    "retention_state": {"table": "slot", "rows": ("state", "z")},
}
POOL_TABLES = ("main", "window", "slot")
LANE_TILE = 128

_ITEMSIZE = {"bool": 1, "int8": 1, "uint8": 1, "int16": 2, "uint16": 2,
             "int32": 4, "uint32": 4, "float32": 4, "int64": 8,
             "uint64": 8, "float64": 8}


def row_lanes(width):
    """Stored lanes of a row ``width`` wide: whole 128-lane tiles."""
    return -(-int(width) // LANE_TILE) * LANE_TILE


def pool_rows(cache_kind):
    """``(table, row kinds)`` of a declared cache kind."""
    try:
        entry = POOL_ROWS[cache_kind]
    except KeyError:
        raise ValueError(
            f"no cache kind {cache_kind!r} in the pool schema "
            f"(declared: {', '.join(POOL_ROWS)})") from None
    return entry["table"], entry["rows"]


def executable_names():
    """Declared program names, in declaration order."""
    return tuple(EXECUTABLES)


def _entry(name):
    try:
        return EXECUTABLES[name]
    except KeyError:
        raise ValueError(
            f"no serve executable named {name!r} in the operand schema "
            f"(declared: {', '.join(EXECUTABLES)})") from None


def operands(name):
    """The positional operand names of executable ``name``, in order."""
    return _entry(name)["operands"]


def arity(name):
    """Positional operand count of executable ``name``."""
    return len(operands(name))


def donated_operands(name):
    """The operand NAMES executable ``name`` donates."""
    return _entry(name)["donated"]


def donate_argnums(name):
    """The donation POSITIONS of executable ``name`` — derived from the
    declared operand order, never hand-counted."""
    ops = operands(name)
    donated = donated_operands(name)
    missing = [d for d in donated if d not in ops]
    if missing:
        raise ValueError(
            f"executable {name!r} declares donated operand(s) "
            f"{missing} absent from its operand list")
    return tuple(i for i, op in enumerate(ops) if op in donated)


def jit_donate(name, fn):
    """Validate ``fn``'s positional signature against the declaration
    and return the registry-derived ``donate_argnums`` for ``name``.

    This is the program-build-time enforcement point: the engine passes
    every pool executable through here, so an operand added to the
    function without updating the schema (or vice versa) raises before
    anything compiles — the same drift tracelint TL016/TL018 catches
    statically.
    """
    import inspect

    declared = operands(name)
    kinds = (inspect.Parameter.POSITIONAL_ONLY,
             inspect.Parameter.POSITIONAL_OR_KEYWORD)
    actual = tuple(p.name for p in
                   inspect.signature(fn).parameters.values()
                   if p.kind in kinds)
    if actual != declared:
        raise ValueError(
            f"executable {name!r} signature drifted from the operand "
            f"schema:\n  declared: {declared}\n  actual:   {actual}\n"
            "update mxnet_tpu/serve/schema.py and the function "
            "together — donation indices and call sites derive from "
            "the declaration")
    return donate_argnums(name)


# -- slot-state layout --------------------------------------------------- #

def slot_state_fields():
    """The per-slot scalar columns ``(name, dtype, elements)``."""
    return SLOT_STATE


def state_operands():
    """The full state operand block every executable's tail threads:
    the K/V page pools followed by the scalar columns, in tuple
    order."""
    return ("kp", "vp") + tuple(n for n, _, _ in SLOT_STATE)


def state_arity():
    """Element count of the pool state tuple."""
    return 2 + len(SLOT_STATE)


def slot_state_bytes():
    """Device bytes of ONE slot's scalar state — the pricing constant
    ``pool_state_bytes``/``admit_scratch_bytes`` scale and
    ``telemetry_report --check-serve`` re-derives."""
    return sum(_ITEMSIZE[dtype] * n for _, dtype, n in SLOT_STATE)


def kv_page_int8_bytes(nl, kv, page, d):
    """Device bytes of ONE int8-quantized page across all layers, K
    and V pools together, priced from the declared ``KV_PAGE_INT8``
    layout: ``page * d`` codes plus one scale per (layer, KV head)."""
    return 2 * nl * kv * (page * d * _ITEMSIZE[KV_PAGE_INT8["codes"]]
                          + _ITEMSIZE[KV_PAGE_INT8["scales"]])


# -- meta rows ----------------------------------------------------------- #

def meta_fields(name):
    """The packed int32 meta-row field order of executable ``name``."""
    return _entry(name)["meta"]


def meta_width(name):
    """Column count of executable ``name``'s meta row."""
    return len(meta_fields(name))


def meta_col(name, field):
    """Column index of ``field`` in executable ``name``'s meta row."""
    fields = meta_fields(name)
    try:
        return fields.index(field)
    except ValueError:
        raise ValueError(
            f"executable {name!r} has no meta field {field!r} "
            f"(declared: {fields})") from None


def meta_cols(name):
    """``{field: column}`` for executable ``name``'s meta row."""
    return {f: i for i, f in enumerate(meta_fields(name))}


def meta_row(name, **fields):
    """Assemble one meta row as a tuple in DECLARED column order.

    Every declared field must be supplied by keyword (and nothing
    else), so a new column added to the declaration immediately breaks
    every builder that has not been taught about it — the host-side
    mirror of :func:`jit_donate`.
    """
    layout = meta_fields(name)
    extra = sorted(set(fields) - set(layout))
    missing = [f for f in layout if f not in fields]
    if extra or missing:
        raise ValueError(
            f"meta_row({name!r}) fields disagree with the schema: "
            f"missing {missing}, unexpected {extra} "
            f"(declared order: {layout})")
    return tuple(fields[f] for f in layout)
