"""Tape-based autograd.

Reference surface: ``python/mxnet/autograd.py`` + ``src/imperative/``
(SURVEY.md §3.1 "Imperative runtime + autograd", anchors
``Imperative::Backward``, ``MXAutogradBackwardEx``): thread-local
recording/training flags; every invoked op appends a node to the tape (the
tape IS a graph); ``backward`` builds and runs the gradient graph.

TPU-native redesign (SURVEY.md §7 "Autograd"): we keep the explicit tape —
so ``record/pause``, ``attach_grad``/``grad_req``, ``mark_variables`` and
custom ``Function`` keep reference semantics — but each node's backward rule
is obtained by invoking the op through ``jax.vjp`` at record time.  The
returned ``vjp_fn`` closes over XLA-resident residuals, so backward is a walk
of the tape applying jax functions (which XLA fuses/dispatches async, playing
the role of the reference's engine-scheduled backward ops).
"""
from __future__ import annotations

import threading
import weakref
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as onp

from .base import MXNetError

__all__ = [
    "record", "pause", "train_mode", "predict_mode", "is_recording",
    "is_training", "set_recording", "set_training", "mark_variables",
    "backward", "grad", "Function", "get_symbol", "trace_value_and_grad",
]

_STATE = threading.local()


def _st():
    if not hasattr(_STATE, "recording"):
        _STATE.recording = False
        _STATE.training = False
    return _STATE


def is_recording() -> bool:
    return _st().recording


def is_training() -> bool:
    return _st().training


def set_recording(flag: bool) -> bool:
    st = _st()
    prev, st.recording = st.recording, bool(flag)
    return prev


def set_training(flag: bool) -> bool:
    st = _st()
    prev, st.training = st.training, bool(flag)
    return prev


class _ScopeCtx:
    def __init__(self, recording: Optional[bool], training: Optional[bool]):
        self._rec, self._train = recording, training

    def __enter__(self):
        st = _st()
        self._old = (st.recording, st.training)
        if self._rec is not None:
            st.recording = self._rec
        if self._train is not None:
            st.training = self._train
        return self

    def __exit__(self, *a):
        st = _st()
        st.recording, st.training = self._old


def record(train_mode: bool = True):
    """``with autograd.record():`` — turn on recording (+training mode)."""
    return _ScopeCtx(True, train_mode)


def pause(train_mode: bool = False):
    return _ScopeCtx(False, train_mode)


def train_mode():
    return _ScopeCtx(None, True)


def predict_mode():
    return _ScopeCtx(None, False)


# ---------------------------------------------------------------------------
# Tape graph
# ---------------------------------------------------------------------------

class TapeNode:
    """One recorded op invocation.  ``vjp_fn`` maps output cotangents to
    input cotangents (closing over XLA-resident residuals)."""

    __slots__ = ("name", "vjp_fn", "parents", "outputs", "out_avals",
                 "multi", "__weakref__")

    def __init__(self, name, vjp_fn, parents, out_avals, multi=None):
        self.name = name
        self.vjp_fn = vjp_fn
        # parents[i] corresponds to primal input i:
        #   ("node", TapeNode, out_idx) | ("leaf", weakref(NDArray)) | None
        self.parents = parents
        self.outputs = []  # weakrefs, set by invoke()
        self.out_avals = out_avals
        # whether vjp_fn expects a tuple cotangent (fn returned tuple/list)
        self.multi = len(out_avals) > 1 if multi is None else multi


class _FreedGraph:
    """Sentinel left on arrays whose producing node was consumed by a
    non-retaining backward: using them as *inputs* later treats them as
    constants; calling backward *on* them raises (reference: autograd
    graph-freed semantics)."""

    _inst = None

    def __new__(cls):
        if cls._inst is None:
            cls._inst = super().__new__(cls)
        return cls._inst


FREED = _FreedGraph()


def _record_invoke(opref, primals, kwargs, array_args):
    """Called from ops.registry.invoke while recording: run the op through
    jax.vjp and append a tape node.  (Reference: ``Imperative::RecordOp``.)
    """
    from .ndarray.ndarray import NDArray

    # optional tensor slots may be None — vjp only over present primals
    live_idx = [i for i, p in enumerate(primals) if p is not None]
    if len(live_idx) != len(primals):
        def fn(*xs):
            full = list(primals)
            for i, x in zip(live_idx, xs):
                full[i] = x
            return opref.fn(*full, **kwargs)
        live_primals = tuple(primals[i] for i in live_idx)
    elif kwargs:
        fn = lambda *xs: opref.fn(*xs, **kwargs)
        live_primals = primals
    else:
        fn = opref.fn
        live_primals = primals
    # pause so impls composed of other wrapped ops don't double-record
    with pause(train_mode=is_training()):
        results, vjp_fn = jax.vjp(fn, *live_primals)

    parents: list = []
    for i in live_idx:
        a = array_args[i]
        if isinstance(a, NDArray):
            if a._autograd_node is FREED:
                parents.append(None)
            elif a._autograd_node is not None:
                parents.append(("node", a._autograd_node, a._autograd_idx))
            elif a._grad is not None or a._grad_req != "null":
                parents.append(("leaf", weakref.ref(a)))
            else:
                parents.append(None)
        else:
            parents.append(None)

    multi = isinstance(results, (tuple, list))
    outs = list(results) if multi else [results]
    node = TapeNode(opref.name, vjp_fn, parents,
                    [jax.typeof(o) for o in outs], multi=multi)
    return results, node


def _zero_cotangent(aval):
    if jnp.issubdtype(aval.dtype, jnp.floating) or jnp.issubdtype(
            aval.dtype, jnp.complexfloating):
        return jnp.zeros(aval.shape, aval.dtype)
    return onp.zeros(aval.shape, dtype=jax.dtypes.float0)


def _is_float0(x):
    return getattr(x, "dtype", None) == jax.dtypes.float0


# ---------------------------------------------------------------------------
# Backward engine
# ---------------------------------------------------------------------------

def _backward_walk(heads, head_grads, targets=None, retain_graph=False):
    """Reverse-mode walk of the tape from ``heads``.

    If ``targets`` is None: accumulate into leaf ``.grad`` per ``grad_req``
    (reference ``Imperative::Backward``).  Otherwise return cotangents for
    exactly those NDArrays (reference ``MXAutogradBackwardEx`` with
    ``var_handles`` — the ``autograd.grad`` path).
    """
    from .ndarray.ndarray import NDArray, _wrap_like

    heads = [heads] if isinstance(heads, NDArray) else list(heads)
    if head_grads is None:
        head_grads = [None] * len(heads)
    elif isinstance(head_grads, NDArray) or head_grads is None:
        head_grads = [head_grads]
    else:
        head_grads = list(head_grads)
    if len(head_grads) != len(heads):
        raise MXNetError("heads and head_grads length mismatch")

    # --- seed cotangents -------------------------------------------------
    node_cots: dict[int, list] = {}   # id(node) -> per-output cotangent
    node_by_id: dict[int, TapeNode] = {}
    leaf_cots: dict[int, Any] = {}    # id(ndarray) -> cotangent
    leaf_by_id: dict[int, NDArray] = {}

    def add_node_cot(node, idx, val):
        nid = id(node)
        node_by_id[nid] = node
        lst = node_cots.setdefault(nid, [None] * len(node.out_avals))
        lst[idx] = val if lst[idx] is None else lst[idx] + val

    def add_leaf_cot(arr, val):
        if _is_float0(val):
            return
        aid = id(arr)
        leaf_by_id[aid] = arr
        leaf_cots[aid] = val if aid not in leaf_cots else leaf_cots[aid] + val

    target_ids = None
    if targets is not None:
        target_ids = {id(t) for t in targets}

    for h, hg in zip(heads, head_grads):
        g = hg._data if isinstance(hg, NDArray) else hg
        if g is None:
            aval = jax.typeof(h._data)
            g = jnp.ones(aval.shape, aval.dtype) if jnp.issubdtype(
                aval.dtype, jnp.floating) else _zero_cotangent(aval)
        if h._autograd_node is FREED:
            raise MXNetError(
                "graph already freed: call backward(retain_graph=True) to "
                "backprop through the same graph twice")
        if h._autograd_node is not None:
            add_node_cot(h._autograd_node, h._autograd_idx, g)
        else:
            add_leaf_cot(h, g)

    # --- topo order: consumers before producers --------------------------
    order: list[TapeNode] = []
    seen: set[int] = set()
    root_nodes = [h._autograd_node for h in heads if h._autograd_node]
    stack = [(n, False) for n in root_nodes]
    while stack:
        node, done = stack.pop()
        nid = id(node)
        if done:
            order.append(node)
            continue
        if nid in seen:
            continue
        seen.add(nid)
        stack.append((node, True))
        for p in node.parents:
            if p is not None and p[0] == "node" and id(p[1]) not in seen:
                stack.append((p[1], False))
    order.reverse()  # consumers first

    # cotangents captured for explicit targets that are intermediates
    target_node_cots: dict[int, Any] = {}

    # --- walk ------------------------------------------------------------
    for node in order:
        nid = id(node)
        cots = node_cots.get(nid)
        if cots is None:
            continue
        filled = [c if c is not None else _zero_cotangent(a)
                  for c, a in zip(cots, node.out_avals)]
        if node.vjp_fn is None:
            raise MXNetError(
                "graph already freed: call backward(retain_graph=True) to "
                "backprop through the same graph twice")
        arg = tuple(filled) if node.multi else filled[0]
        in_cots = node.vjp_fn(arg)
        if not retain_graph:
            node.vjp_fn = None  # free residuals
            for outref in node.outputs:
                o = outref() if outref else None
                if o is not None and o._autograd_node is node:
                    o._autograd_node = FREED
        # record cotangents for explicit intermediate targets
        if target_ids:
            for outref in node.outputs:
                o = outref() if outref else None
                if o is not None and id(o) in target_ids:
                    c = filled[o._autograd_idx]
                    tid = id(o)
                    target_node_cots[tid] = (
                        c if tid not in target_node_cots
                        else target_node_cots[tid] + c)
        for p, c in zip(node.parents, in_cots):
            if p is None or _is_float0(c):
                continue
            if p[0] == "node":
                add_node_cot(p[1], p[2], c)
            else:
                arr = p[1]()
                if arr is not None:
                    add_leaf_cot(arr, c)

    # --- commit ----------------------------------------------------------
    if targets is not None:
        out = []
        for t in targets:
            tid = id(t)
            c = target_node_cots.get(tid, leaf_cots.get(tid))
            if c is None:
                c = jnp.zeros(t.shape, t.dtype)
            out.append(_wrap_like(c, t))
        return out

    for aid, c in leaf_cots.items():
        arr = leaf_by_id[aid]
        if arr._grad_req == "null" or arr._grad is None:
            continue
        if arr._grad_req == "add":
            arr._grad._rebind(arr._grad._data + c)
        else:  # write
            arr._grad._rebind(jnp.asarray(c, arr._grad._data.dtype)
                              if c.dtype != arr._grad._data.dtype else c)
    return None


def backward(heads, head_grads=None, retain_graph=False, train_mode=True):
    """``mx.autograd.backward`` — grads land in ``x.grad``."""
    with pause(train_mode=train_mode):
        _backward_walk(heads, head_grads, None, retain_graph)


def grad(heads, variables, head_grads=None, retain_graph=None,
         create_graph=False, train_mode=True):
    """``mx.autograd.grad`` — return grads w.r.t. ``variables`` without
    touching ``.grad``.  ``create_graph`` (higher-order) is not yet
    supported and raises (documented descope for now)."""
    from .ndarray.ndarray import NDArray

    if create_graph:
        raise MXNetError("create_graph=True (higher-order grad) not yet "
                         "supported; use jax.grad via block.apply for "
                         "higher-order derivatives")
    single = isinstance(variables, NDArray)
    targets = [variables] if single else list(variables)
    if retain_graph is None:
        retain_graph = create_graph
    with pause(train_mode=train_mode):
        outs = _backward_walk(heads, head_grads, targets, retain_graph)
    return outs[0] if single else outs


def trace_value_and_grad(fn, params, frozen_params=(), train_mode=True):
    """Grad-and-value capture for the fused train step — the tape is never
    materialized.

    Where ``record()``/``backward()`` append one TapeNode per op and walk
    it afterwards, this functionalizes the whole ``fn`` call (forward +
    loss) and differentiates it with ``jax.value_and_grad``, so a single
    XLA program carries forward AND backward (the reference's
    whole-step-behind-CachedOp amalgamation, SURVEY.md §4.2).  Returns a
    PURE function, intended to be traced inside ``jax.jit``::

        pure(key, train_vals, frozen_vals, *args)
            -> (outs, grads, new_frozen_vals)

    - ``fn`` is NDArray-level user code (e.g. ``lambda x, y:
      loss(net(x), y)``); it may return a single loss or a tuple whose
      FIRST element is the loss (extra outputs — predictions — ride along
      undifferentiated).
    - ``params``/``frozen_params`` are the Parameters whose values ride
      in as ``train_vals``/``frozen_vals`` operands (CachedOp's
      weights-as-arguments discipline, via ``params_swapped``).
    - The backward is seeded with the gradient of ``sum(loss)`` — the
      identical seeding to ``loss.backward()`` on the tape path.
    - ``new_frozen_vals`` are the frozen params' values with staged aux
      updates (BN moving stats) applied, aligned with ``frozen_params``.
    - ``pure.out_struct['is_seq']`` records (at first trace) whether
      ``fn`` returned a sequence.
    """
    from .gluon.block import trace_scope
    from .gluon.parameter import params_swapped
    from .ndarray.ndarray import NDArray

    params = list(params)
    frozen = list(frozen_params)
    all_params = params + frozen
    struct: dict = {}

    def run(key, train_vals, frozen_vals, args):
        all_vals = list(train_vals) + list(frozen_vals)
        with trace_scope(key, train_mode) as aux:
            with params_swapped(all_params, all_vals):
                nd_args = [a if isinstance(a, NDArray) else NDArray(a)
                           for a in args]
                out = fn(*nd_args)
        is_seq = isinstance(out, (tuple, list))
        struct["is_seq"] = is_seq
        outs = [o._data if isinstance(o, NDArray) else o
                for o in (out if is_seq else [out])]
        aux_by_id = {id(p): jax.lax.stop_gradient(v)
                     for (p, v) in aux.values()}
        new_frozen = [aux_by_id.get(id(p), v)
                      for p, v in zip(frozen, frozen_vals)]
        return outs, new_frozen

    def pure(key, train_vals, frozen_vals, *args):
        def loss_of(tv):
            outs, new_frozen = run(key, tv, frozen_vals, args)
            return jnp.sum(outs[0]), (outs, new_frozen)

        (_, (outs, new_frozen)), grads = jax.value_and_grad(
            loss_of, has_aux=True)(tuple(train_vals))
        return tuple(outs), grads, new_frozen

    pure.out_struct = struct
    return pure


def mark_variables(variables, gradients, grad_reqs="write"):
    """Attach gradient buffers to arrays (reference
    ``MXAutogradMarkVariables``)."""
    from .ndarray.ndarray import NDArray

    if isinstance(variables, NDArray):
        variables, gradients = [variables], [gradients]
    if isinstance(grad_reqs, str):
        grad_reqs = [grad_reqs] * len(variables)
    for v, g, r in zip(variables, gradients, grad_reqs):
        v._grad = g
        v._grad_req = r


def get_symbol(x):
    """Reference returns the recorded Symbol; here the tape has no separate
    symbolic IR — use ``HybridBlock.export`` for graph capture."""
    raise MXNetError("get_symbol: tape-to-symbol export not supported; "
                     "hybridize + export() instead")


# ---------------------------------------------------------------------------
# Custom Function (reference: mx.autograd.Function -> CustomOp thread pool;
# here backward is just a python callback wired as the node's vjp)
# ---------------------------------------------------------------------------

class Function:
    """User-defined differentiable function with explicit backward."""

    def __init__(self):
        self._saved = ()

    def save_for_backward(self, *arrays):
        self._saved = arrays

    @property
    def saved_tensors(self):
        return self._saved

    def forward(self, *inputs):
        raise NotImplementedError

    def backward(self, *output_grads):
        raise NotImplementedError

    def __call__(self, *inputs):
        from .ndarray.ndarray import NDArray, _wrap_like

        with pause(train_mode=is_training()):
            outputs = self.forward(*inputs)
        multi = isinstance(outputs, (tuple, list))
        outs = list(outputs) if multi else [outputs]
        if not is_recording():
            return outputs

        func = self

        def vjp_fn(cots):
            cots = cots if isinstance(cots, tuple) else (cots,)
            nd_cots = [_wrap_like(c, None) for c in cots]
            with pause():
                grads = func.backward(*nd_cots)
            grads = grads if isinstance(grads, (tuple, list)) else (grads,)
            return tuple(g._data if isinstance(g, NDArray) else g
                         for g in grads)

        parents = []
        for a in inputs:
            if isinstance(a, NDArray):
                if a._autograd_node is FREED:
                    parents.append(None)
                elif a._autograd_node is not None:
                    parents.append(("node", a._autograd_node, a._autograd_idx))
                else:
                    parents.append(("leaf", weakref.ref(a)))
            else:
                parents.append(None)
        node = TapeNode(type(self).__name__, vjp_fn, parents,
                        [jax.typeof(o._data) for o in outs], multi=multi)
        for i, o in enumerate(outs):
            o._autograd_node = node
            o._autograd_idx = i
        node.outputs = [o._weak() for o in outs]
        return outputs
