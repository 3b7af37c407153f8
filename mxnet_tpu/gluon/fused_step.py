"""Fused train step: ONE donated-buffer XLA executable per step, with
on-device gradient accumulation.

Reference counterpart (SURVEY.md §4.2, §7): the reference amortizes
per-op overhead by amalgamating the training step behind CachedOp + the
dependency engine.  Our imperative port still ran the step as a
Python-sequenced phase chain — jitted CachedOp forward, tape-driven
backward, kvstore allreduce, fused ``Optimizer.multi_update`` — with
host round-trips between each phase.  ``FusedStep`` collapses the chain:
forward + loss + backward (``autograd.trace_value_and_grad`` — no tape)
+ grad rescale + cross-replica reduction (GSPMD, from input shardings) +
the optimizer apply (``Optimizer.fused_step_apply``) trace into one
``jax.jit`` executable with DONATED weight / optimizer-state /
grad-accumulator buffers, keyed by (batch shape/dtype signature, phase,
training flag, optimizer hyperparameters).

Gradient accumulation folds into the same executable:
``Trainer(update_interval=N)`` compiles TWO executables — a *micro* step
(forward+backward+accumulate into a device-resident accumulator ring)
and an *apply* step (accumulate + optimizer apply + accumulator reset) —
and fires the apply only every Nth call, with the 1/(N·batch) rescale
riding the apply's existing rescale operand.  A large effective batch
pays ONE optimizer apply and ONE replica sync per window instead of N.

``MXNET_FUSED_STEP=0`` (or an unsupported configuration: kvstore-backed
reduction, per-ctx replicas, sparse params, non-fusable optimizers like
SGLD) restores today's phase-by-phase path — record → tape backward →
``Trainer.step`` — bit-for-bit.
"""
from __future__ import annotations

import os
import time

import jax
import jax.numpy as jnp

from .. import telemetry
from ..base import MXNetError

__all__ = ["FusedStep", "fused_step_enabled", "step_counters",
           "reset_step_counters"]

# Dispatch accounting (read by the dispatch-count regression test and the
# fused-step benchmark rows):
#   dispatches        — fused-step executable invocations (exactly one per
#                       fused_step() call on the fused path)
#   micro_dispatches  — accumulate-only invocations (mid-window)
#   apply_dispatches  — invocations that ran the optimizer apply (one per
#                       update interval)
#   legacy_steps      — calls that took the phase-by-phase fallback
#   compiles          — executable cache misses (traces)
step_counters = {"dispatches": 0, "micro_dispatches": 0,
                 "apply_dispatches": 0, "legacy_steps": 0, "compiles": 0}


def reset_step_counters():
    for k in step_counters:
        step_counters[k] = 0


# registry instruments mirroring the dict above (plus step latency /
# accumulation-window phase), created on first use — module import must
# not touch the registry
_tele = None


def _instruments():
    global _tele
    if _tele is None:
        _tele = {
            "lat_micro": telemetry.histogram("fused_step_seconds",
                                             phase="micro"),
            "lat_apply": telemetry.histogram("fused_step_seconds",
                                             phase="apply"),
            "d_micro": telemetry.counter("fused_step_dispatches_total",
                                         phase="micro"),
            "d_apply": telemetry.counter("fused_step_dispatches_total",
                                         phase="apply"),
            "d_legacy": telemetry.counter("fused_step_dispatches_total",
                                          phase="legacy"),
            "window": telemetry.gauge("fused_step_window_pos"),
        }
    return _tele


def fused_step_enabled() -> bool:
    """Escape hatch: ``MXNET_FUSED_STEP=0`` restores the phase-by-phase
    step (read per call so tests can toggle it)."""
    return os.environ.get("MXNET_FUSED_STEP", "1") != "0"


class FusedStep:
    """Step compiler for one ``(Trainer, loss_fn)`` pair.

    ``loss_fn(*batch)`` is NDArray-level user code returning the
    per-sample loss (or a ``(loss, *extras)`` tuple — extras such as
    predictions ride through the executable undifferentiated).  Created
    and cached by ``Trainer.fused_step``; define the loss_fn ONCE outside
    the training loop so the cache key (``id(loss_fn)``) is stable.
    """

    def __init__(self, trainer, loss_fn, data_sharding=None,
                 train_mode=True):
        self._trainer = trainer
        self._loss_fn = loss_fn
        # optional NamedSharding for the batch operands (see
        # parallel.collectives.dp_sharding): placing the batch over the
        # data axis makes GSPMD insert the cross-replica grad all-reduce
        # INSIDE this executable — the kvstore phase folded into the step
        self._data_sharding = data_sharding
        self._train_mode = bool(train_mode)
        self._built = False
        self._train_idx: list = []     # trainer._params indices, live only
        self._train_params: list = []
        self._frozen_params: list = []
        self._mp_flags: list = []
        self._pure = None              # trace_value_and_grad closure
        self._cache: dict = {}         # (phase, sig, ...) -> jitted fn
        self._accum = None             # device grad accumulators (N > 1)
        self._accum_key = None         # train.grad_accum ledger key
        self._legacy_accum = None      # host-path accumulators (fallback)
        self._static_supported = None  # cached config verdict

    # ------------------------------------------------------------------ #
    def _supported(self) -> bool:
        # only the env hatch is re-read per call; the kvstore/replica/
        # sparse/optimizer facts are fixed once training starts, and an
        # O(n_params) scan per step would re-create exactly the per-param
        # host overhead the one-dispatch design removes
        if not fused_step_enabled():
            return False
        if self._static_supported is None:
            tr = self._trainer
            tr._init_kvstore()
            ok = not (tr._kvstore is not None or tr._update_on_kvstore)
            # SGLD: host RNG in the rule — not traceable once
            ok = ok and tr._optimizer._fusable
            # per-ctx replicas / sparse params: kvstore + per-param paths
            ok = ok and all(
                p._replicas is None and p._stype == "default"
                and p._grad_stype == "default" for p in tr._params)
            self._static_supported = ok
        return self._static_supported

    # ------------------------------------------------------------------ #
    def _build(self, nd_batch):
        from .. import autograd
        from .block import _no_hybrid

        tr = self._trainer
        if any(p._data is None for p in tr._params):
            # materialize deferred shapes with one imperative forward
            # (the _CachedOp._ensure_params discipline)
            with autograd.pause(train_mode=False), _no_hybrid():
                self._loss_fn(*nd_batch)
        for i, p in enumerate(tr._params):
            if p._data is None:
                raise MXNetError(
                    f"fused_step: parameter {p.name} is not initialized "
                    "after one forward; initialize() the block first")
            if p.grad_req == "null":
                self._frozen_params.append(p)
            else:
                tr._ensure_state(i)
                self._train_idx.append(i)
                self._train_params.append(p)
        opt = tr._optimizer
        self._mp_flags = [
            opt._use_mp(tr._params[i]._data._data, tr._states[i])
            for i in self._train_idx]
        self._pure = autograd.trace_value_and_grad(
            self._loss_fn, self._train_params, self._frozen_params,
            train_mode=self._train_mode)
        self._place_params()
        self._built = True
        self._trainer._account_params()

    def _place_params(self):
        """With a data-sharded batch (``data_sharding=``), weights /
        states must live on the SAME mesh or jit refuses the mixed
        committed placements: replicate them over the batch's mesh
        (params with their own ``set_sharding`` keep it).  GSPMD then
        compiles the cross-replica grad reduction into the step — this
        is the fused path's allreduce."""
        sh = self._data_sharding
        if sh is None or not hasattr(sh, "mesh"):
            return
        from jax.sharding import NamedSharding, PartitionSpec
        from ..parallel.mesh import global_put

        tr = self._trainer
        repl = NamedSharding(sh.mesh, PartitionSpec())
        for p in self._train_params + self._frozen_params:
            tgt = p._sharding if p._sharding is not None else repl
            p._data._data = global_put(p._data._data, tgt)
        for i in self._train_idx:
            tr._states[i] = jax.tree.map(
                lambda a: global_put(a, repl)
                if hasattr(a, "shape") else a, tr._states[i])

    # ------------------------------------------------------------------ #
    def _get_fn(self, phase, sig):
        opt = self._trainer._optimizer
        key = (phase, sig, self._train_mode,
               self._trainer._update_interval > 1, opt._hyper_key(),
               opt.clip_gradient is not None)
        fn = self._cache.get(key)
        if fn is None:
            fn = telemetry.instrument_jit(
                self._compile(phase), "gluon.fused_step",
                key=(phase, sig), fields={"phase": phase})
            self._cache[key] = fn
            step_counters["compiles"] += 1
        return fn

    def _compile(self, phase):
        pure = self._pure
        opt = self._trainer._optimizer
        mp_flags = list(self._mp_flags)
        has_accum = self._trainer._update_interval > 1

        if phase == "micro":
            def micro(train_vals, frozen_vals, accum, key, *args):
                outs, grads, new_frozen = pure(key, train_vals,
                                               frozen_vals, *args)
                new_accum = [a + g.astype(a.dtype)
                             for a, g in zip(accum, grads)]
                return outs, new_accum, new_frozen

            # the accumulator ring is donated: accumulate is in-place at
            # the XLA level, weights/states pass through untouched
            return jax.jit(micro, donate_argnums=(2,))

        def apply(train_vals, opt_states, frozen_vals, accum, key, lrs,
                  wds, ts, rescale, *args):
            outs, grads, new_frozen = pure(key, train_vals, frozen_vals,
                                           *args)
            if has_accum:
                totals = [a + g.astype(a.dtype)
                          for a, g in zip(accum, grads)]
            else:
                totals = list(grads)
            new_ws, new_ss = opt.fused_step_apply(
                list(train_vals), totals, list(opt_states), mp_flags,
                lrs, wds, ts, rescale)
            new_accum = [jnp.zeros_like(a) for a in accum] if has_accum \
                else []
            return outs, new_ws, new_ss, new_frozen, new_accum

        donate = (0, 1, 3) if has_accum else (0, 1)
        return jax.jit(apply, donate_argnums=donate)

    # ------------------------------------------------------------------ #
    def __call__(self, batch, batch_size=None):
        from ..ndarray.ndarray import NDArray
        from .. import random as mxrandom
        from ..ndarray.ndarray import _grad_dtype

        tr = self._trainer
        nd_batch = [b if isinstance(b, NDArray) else NDArray(jnp.asarray(b))
                    for b in batch]
        if batch_size is None:
            batch_size = nd_batch[0].shape[0] if nd_batch[0].shape else 1
        if not self._supported():
            return self._legacy(nd_batch, batch_size)
        if not self._built:
            self._build(nd_batch)

        args = []
        if self._data_sharding is not None:
            # on a multi-process mesh each rank passes ITS batch slice
            # and global_put assembles the pod-global batch; the jitted
            # step then spans process boundaries (grad allreduce over
            # DCN) while staying one executable dispatch per rank
            from ..parallel.mesh import global_put

            for b in nd_batch:
                args.append(global_put(b._data, self._data_sharding))
        else:
            args = [b._data for b in nd_batch]
        sig = tuple((tuple(a.shape), str(a.dtype)) for a in args)
        N = tr._update_interval
        train_vals = [p._data._data for p in self._train_params]
        frozen_vals = [p._data._data for p in self._frozen_params]
        if self._data_sharding is not None \
                and hasattr(self._data_sharding, "mesh") \
                and jax.process_count() > 1:
            # pod discipline: EVERY operand of the global-mesh jit must
            # be a global array (keys, hypers, the accumulator ring) —
            # a process-local leftover turns the one-executable step
            # into a placement error
            from jax.sharding import NamedSharding, PartitionSpec
            from ..parallel.mesh import global_put

            _repl = NamedSharding(self._data_sharding.mesh,
                                  PartitionSpec())

            def _g(a):
                return global_put(a, _repl)
        else:
            def _g(a):
                return a
        key = _g(mxrandom.next_key())
        if N > 1 and self._accum is None:
            adopted = self._adopt_pending_accum(tr, train_vals)
            self._accum = [_g(a) for a in adopted] if adopted else [
                _g(jnp.zeros(v.shape, _grad_dtype(v.dtype)))
                for v in train_vals]
            # the accumulator ring is a real device-resident cost of
            # update_interval>1 — one ledger entry PER FusedStep (a
            # trainer driving several loss_fns owns several rings, so
            # keying by trainer alone would overwrite), sized once
            # (the donated ring keeps these shapes every window)
            from ..telemetry.memory import ACCOUNTANT

            self._accum_key = \
                f"{self._trainer._mem_key()}:fs{id(self):x}"
            ACCOUNTANT.set("train.grad_accum", self._accum_key,
                           self._accum)

        tele = _instruments()
        tr._window_pos += 1
        if tr._window_pos < N:
            fn = self._get_fn("micro", sig)
            t0 = time.perf_counter()
            with telemetry.span("mx:fused_step:micro"):
                outs, self._accum, new_frozen = fn(
                    train_vals, frozen_vals, self._accum, key, *args)
            tele["lat_micro"].observe(time.perf_counter() - t0)
            tele["d_micro"].inc()
            tele["window"].set(tr._window_pos)
            step_counters["dispatches"] += 1
            step_counters["micro_dispatches"] += 1
            for p, v in zip(self._frozen_params, new_frozen):
                p._data._data = v
            return self._wrap_outs(outs)

        # window boundary: ONE executable runs fwd+bwd+accumulate+apply
        tr._window_pos = 0
        opt = tr._optimizer
        lrs, wds, ts = [], [], []
        for i in self._train_idx:
            opt._update_count(i)
            lrs.append(opt._get_lr(i))
            wds.append(opt._get_wd(i))
            ts.append(opt._index_update_count[i])
        rescale = jnp.float32(tr._scale / (float(batch_size) * N))
        states = [tr._states[i] for i in self._train_idx]
        fn = self._get_fn("apply", sig)
        t0 = time.perf_counter()
        with telemetry.span("mx:fused_step:apply"):
            outs, new_ws, new_ss, new_frozen, new_accum = fn(
                train_vals, states, frozen_vals,
                self._accum if N > 1 else [], key,
                _g(jnp.asarray(lrs, jnp.float32)),
                _g(jnp.asarray(wds, jnp.float32)),
                _g(jnp.asarray(ts, jnp.int32)), _g(rescale), *args)
        tele["lat_apply"].observe(time.perf_counter() - t0)
        tele["d_apply"].inc()
        tele["window"].set(tr._window_pos)
        step_counters["dispatches"] += 1
        step_counters["apply_dispatches"] += 1
        for p, w in zip(self._train_params, new_ws):
            p._data._data = w
        for i, s in zip(self._train_idx, new_ss):
            tr._states[i] = s
        for p, v in zip(self._frozen_params, new_frozen):
            p._data._data = v
        self._accum = new_accum if N > 1 else None
        return self._wrap_outs(outs)

    def _adopt_pending_accum(self, tr, train_vals):
        """Adopt a checkpoint-restored accumulator ring
        (``mx.checkpoint`` stages them on ``trainer._pending_accum``
        when a mid-window save is restored): the first staged ring
        whose shapes match this step's training params resumes the
        window exactly where the save left it.  A restored mid-window
        position with NO matching ring cannot resume bit-exact — that
        is a loud error, not a silent zero ring."""
        pending = getattr(tr, "_pending_accum", None)
        if pending is None:
            return None   # no checkpoint restore in this trainer's life
        if not pending:
            if tr._window_pos != 0:
                raise MXNetError(
                    "fused_step: trainer was restored mid-accumulation-"
                    f"window (micro-batch {tr._window_pos}/"
                    f"{tr._update_interval}) but its saved accumulator "
                    "ring was already adopted by another fused step — "
                    "one checkpointed ring cannot resume two windows")
            return None
        for ridx, ring in enumerate(pending):
            if len(ring) == len(train_vals) and all(
                    tuple(r.shape) == tuple(v.shape)
                    for r, v in zip(ring, train_vals)):
                return pending.pop(ridx)
        if tr._window_pos != 0:
            raise MXNetError(
                "fused_step: trainer was restored mid-accumulation-"
                f"window (micro-batch {tr._window_pos}/"
                f"{tr._update_interval}) but none of the "
                f"{len(pending)} checkpointed accumulator ring(s) "
                "match this step's parameter shapes — the checkpoint "
                "was taken with a different loss_fn/model geometry")
        return None

    def release_accounting(self):
        """Retire this step's ``train.grad_accum`` ledger entry —
        called when the trainer's FusedStep cache evicts it (its
        accumulator ring is freed with it; an un-dropped entry would
        read as a ``reconcile()`` delta<0 leak forever).  Deferred
        drop: this is also reachable from ``Trainer.__del__``, which
        may run via GC inside a thread holding the accountant lock."""
        if self._accum_key is not None:
            from ..telemetry.memory import ACCOUNTANT

            ACCOUNTANT.drop_deferred("train.grad_accum",
                                     self._accum_key)
            self._accum_key = None

    def _wrap_outs(self, outs):
        from ..ndarray.ndarray import NDArray

        nd = [NDArray(o) for o in outs]
        if self._pure is not None and self._pure.out_struct.get("is_seq"):
            return tuple(nd)
        return nd[0]

    # ------------------------------------------------------------------ #
    def _legacy(self, nd_batch, batch_size):
        """Phase-by-phase fallback: record → tape backward →
        ``Trainer.step`` — the exact pre-fusion sequence (bit-for-bit at
        ``update_interval=1``).  For N > 1, ``grad_req='write'`` params
        accumulate host-side across the window (``'add'`` params already
        accumulate in their grad buffer); ``Trainer.step`` fires the
        apply at the boundary with the effective-batch rescale."""
        from .. import autograd

        tr = self._trainer
        step_counters["legacy_steps"] += 1
        _instruments()["d_legacy"].inc()
        with autograd.record(train_mode=self._train_mode):
            out = self._loss_fn(*nd_batch)
        loss = out[0] if isinstance(out, (tuple, list)) else out
        autograd.backward([loss])
        N = tr._update_interval
        if N > 1:
            write_live = [p for p in tr._params
                          if p.grad_req == "write" and p._data is not None
                          and p._data._grad is not None]
            grads_now = [p.grad()._data for p in write_live]
            if tr._window_pos == 0 or self._legacy_accum is None:
                self._legacy_accum = grads_now
            else:
                self._legacy_accum = [a + g for a, g in
                                      zip(self._legacy_accum, grads_now)]
            if tr._window_pos + 1 >= N:
                for p, a in zip(write_live, self._legacy_accum):
                    p.grad()._rebind(a)
                self._legacy_accum = None
        tr._accum_managed = True  # this fallback accumulates 'write'
        try:                      # grads itself (above)
            tr.step(batch_size)
        finally:
            tr._accum_managed = False
        return out
