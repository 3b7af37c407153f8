"""GSPMD sharding rules and the fused SPMD train step.

Reference counterpart (SURVEY.md §4.2): the training step is
``record → forward → backward → Trainer.step`` with the KVStore doing the
cross-device reduction as separate engine ops.  TPU-native, that whole loop
is ONE jitted function over the mesh: forward+backward+optimizer with
donated buffers; GSPMD inserts the grad all-reduce (data axis) and the
tensor-parallel collectives (model axis) from sharding annotations — the
explicit KVStore machinery disappears into the compiler
(SURVEY.md §7 "KVStore").

``ShardingRules`` plays the role of the reference's per-device replica
lists / `group2ctx` placement (§3.3): a regex over parameter names maps
each param to a ``PartitionSpec`` on the mesh.
"""
from __future__ import annotations

import re
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp

from .. import telemetry
from ..base import MXNetError
from .mesh import Mesh, P, default_mesh, global_put
from jax.sharding import NamedSharding

__all__ = ["ShardingRules", "shard_block", "SPMDTrainer"]


class ShardingRules:
    """Ordered (regex → PartitionSpec) rules for parameter sharding.

    Example (tensor parallel Dense layers on axis 'tp', everything else
    replicated)::

        rules = ShardingRules([
            (r".*dense\\d*\\.weight", P("tp", None)),
            (r".*\\.bias",            P("tp")),
        ])
        shard_block(net, mesh, rules)
    """

    def __init__(self, rules: Sequence, default=P()):
        self.rules = [(re.compile(pat), spec) for pat, spec in rules]
        self.default = default

    def spec_for(self, name: str, shape=None, mesh: Optional[Mesh] = None):
        spec = self.default
        for pat, s in self.rules:
            if pat.match(name):
                spec = s
                break
        if shape is None or mesh is None:
            return spec
        return _fit_spec(spec, shape, mesh)


def _fit_spec(spec, shape, mesh: Mesh):
    """Drop spec axes that don't divide the corresponding dim, and truncate
    the spec to the array rank (so tiny test shapes and rank-mismatched
    rules still compile instead of erroring inside GSPMD)."""
    from .mesh import local_mesh_axes
    sizes = local_mesh_axes(mesh)
    out = []
    for i, s in enumerate(tuple(spec)[:len(shape)]):
        if s is None:
            out.append(None)
            continue
        ax_size = sizes.get(s) if isinstance(s, str) else None
        if isinstance(s, (tuple, list)):
            ax_size = 1
            for name in s:
                ax_size *= sizes[name]
        if ax_size is None or (shape[i] and shape[i] % ax_size == 0):
            out.append(s)
        else:
            out.append(None)
    return P(*out)


def shard_block(block, mesh: Optional[Mesh] = None,
                rules: Optional[ShardingRules] = None):
    """Annotate every initialized parameter of ``block`` with a
    ``NamedSharding`` from ``rules`` (device_put happens immediately;
    uninitialized params pick the sharding up at init)."""
    mesh = mesh or default_mesh()
    rules = rules or ShardingRules([])
    for name, p in block.collect_params().items():
        spec = rules.spec_for(name, p.shape if p.shape else None, mesh)
        p.set_sharding(NamedSharding(mesh, spec))
    return block


class SPMDTrainer:
    """One-jit training: ``step(data, label)`` runs forward, backward, and
    the optimizer update as a single compiled SPMD program over the mesh.

    - ``dp_axis`` shards the batch (data parallel); grads are reduced by
      GSPMD automatically because params are replicated (or sharded) over
      that axis.
    - param shardings come from ``rules`` (tensor/sequence parallel) or
      previously applied ``Parameter.set_sharding``.
    - param + optimizer-state buffers are donated: the update is in-place
      at the XLA level (the reference's ``static_alloc`` memory reuse).

    The imperative ``gluon.Trainer`` remains the API-parity path; this is
    the performance path (SURVEY.md §7 build plan, Phase 2).
    """

    def __init__(self, block, loss_fn: Callable, optimizer,
                 optimizer_params: Optional[dict] = None,
                 mesh: Optional[Mesh] = None,
                 rules: Optional[ShardingRules] = None,
                 dp_axis: str = "dp", donate: bool = True):
        from .. import optimizer as opt_mod

        self._block = block
        self._loss_fn = loss_fn
        self._mesh = mesh or default_mesh()
        self._rules = rules
        self._dp_axis = dp_axis
        self._donate = donate
        optimizer_params = dict(optimizer_params or {})
        if isinstance(optimizer, opt_mod.Optimizer):
            self._opt = optimizer
            self._rescale = float(optimizer_params.pop(
                "rescale_grad", optimizer.rescale_grad))
        else:
            self._rescale = float(optimizer_params.pop("rescale_grad", 1.0))
            self._opt = opt_mod.create(optimizer, **optimizer_params)
        self._built = False
        self._step_fn = None
        self._multi_step_fn = None
        self._t = 0
        self._param_names: list = []
        self._train_params: list = []   # Parameter objs with grad_req != null
        self._frozen_params: list = []  # grad_req == null (e.g. running stats)
        self._train_vals: list = []
        self._frozen_vals: list = []
        self._opt_states: list = []

    # ------------------------------------------------------------------ #
    @property
    def optimizer(self):
        return self._opt

    @property
    def learning_rate(self):
        return self._opt.learning_rate

    def set_learning_rate(self, lr):
        self._opt.set_learning_rate(lr)

    # ------------------------------------------------------------------ #
    def _ensure_built(self, data, label):
        if self._built:
            return
        from ..ndarray.ndarray import NDArray
        from ..gluon.block import _no_hybrid
        from .. import autograd

        block = self._block
        params = block.collect_params()
        if any(p._data is None for p in params.values()):
            # materialize deferred shapes with one imperative forward
            with autograd.pause(train_mode=False), _no_hybrid():
                block(data if isinstance(data, NDArray) else
                      NDArray(jnp.asarray(data)))
            params = block.collect_params()
        if self._rules is not None:
            shard_block(block, self._mesh, self._rules)
        for name, p in params.items():
            if p._data is None:
                continue
            self._param_names.append(name)
            if p.grad_req != "null":
                self._train_params.append(p)
            else:
                self._frozen_params.append(p)
        self._train_vals = [p._data._data for p in self._train_params]
        self._frozen_vals = [p._data._data for p in self._frozen_params]
        self._opt_states = [
            self._opt.create_state_multi_precision(i, p.data())
            for i, p in enumerate(self._train_params)]
        # place params/states in the step's own layout up front: the
        # first call then has the signature of every later one (whose
        # operands are the step's outputs) — one compile per program,
        # not a second one on step 2 — and on a pod, where the
        # in_shardings span processes, jit could not auto-place
        # host/local-committed values at all
        repl, shard_of, state_shardings = self._shardings()
        self._train_vals = [global_put(v, shard_of(p)) for v, p in
                            zip(self._train_vals, self._train_params)]
        self._frozen_vals = [global_put(v, shard_of(p)) for v, p in
                             zip(self._frozen_vals, self._frozen_params)]
        self._opt_states = [
            jax.tree.map(lambda a, sh: global_put(a, sh)
                         if hasattr(a, "shape") else a, s,
                         state_shardings(s, p))
            for s, p in zip(self._opt_states, self._train_params)]
        self._step_fn = self._compile()
        self._built = True

    # ------------------------------------------------------------------ #
    def _forward_loss(self, key, train_vals, frozen_vals, data, label,
                      aux_out):
        """Pure loss: swap param values into the block, run block + loss
        imperatively (ops dispatch straight to jnp on tracers), collect aux
        (running-stat) updates."""
        from ..ndarray.ndarray import NDArray
        from ..gluon.block import trace_scope
        from ..gluon.parameter import params_swapped

        all_params = self._train_params + self._frozen_params
        all_vals = list(train_vals) + list(frozen_vals)
        with trace_scope(key, training=True) as aux:
            with params_swapped(all_params, all_vals):
                out = self._block(NDArray(data))
                out0 = out[0] if isinstance(out, (list, tuple)) else out
                with jax.named_scope("mx.head"):
                    loss = self._loss_fn(out0, NDArray(label))
                    loss_val = jnp.mean(
                        loss._data if isinstance(loss, NDArray) else loss)
        aux_out.append([(p, jax.lax.stop_gradient(v))
                        for (p, v) in aux.values()])
        return loss_val

    def _make_step_fn(self):
        """The pure one-step body shared by the single-step jit and the
        multi-step scan."""
        opt = self._opt
        mp_flags = []
        for s, p in zip(self._opt_states, self._train_params):
            w = p._data._data
            mp_flags.append(
                opt.multi_precision and w.dtype in (jnp.float16, jnp.bfloat16)
                and isinstance(s, tuple) and len(s) == 2
                and getattr(s[0], "dtype", None) == jnp.float32)
        lr_mults = [float(p.lr_mult) for p in self._train_params]
        wd_mults = [float(p.wd_mult) for p in self._train_params]

        def step_fn(train_vals, opt_states, frozen_vals, key, lr, rescale,
                    t, data, label):
            aux_box: list = []

            def loss_of(tv):
                return self._forward_loss(key, tv, frozen_vals, data,
                                          label, aux_box)

            loss, grads = jax.value_and_grad(loss_of)(tuple(train_vals))
            aux_pairs = aux_box[-1] if aux_box else []

            new_vals, new_states = [], []
            with jax.named_scope("mx.optimizer"):
                for i, (w, g, s, mp) in enumerate(
                        zip(train_vals, grads, opt_states, mp_flags)):
                    lr_i = lr * lr_mults[i]
                    wd_i = opt.wd * wd_mults[i]
                    if mp:
                        master, inner = s
                        g32 = g.astype(jnp.float32) * rescale
                        if opt.clip_gradient is not None:
                            g32 = jnp.clip(g32, -opt.clip_gradient,
                                           opt.clip_gradient)
                        nm, ni = opt._update_rule(master, g32, inner, lr_i,
                                                  wd_i, t)
                        new_vals.append(nm.astype(w.dtype))
                        new_states.append((nm, jax.tree.map(
                            lambda a, b: b.astype(a.dtype) if hasattr(
                                a, "dtype") else b, inner, ni)))
                    else:
                        # CRITICAL dtype discipline: the traced f32 scalars
                        # (rescale/lr) promote bf16 math to f32; without the
                        # casts below one step() silently turns the whole
                        # model f32 and the MXU runs at 1/2-1/4 rate
                        g = (g * rescale).astype(w.dtype)
                        if opt.clip_gradient is not None:
                            g = jnp.clip(g, -opt.clip_gradient,
                                         opt.clip_gradient)
                        nw, ns = opt._update_rule(w, g, s, lr_i, wd_i, t)
                        new_vals.append(nw.astype(w.dtype))
                        new_states.append(jax.tree.map(
                            lambda a, b: b.astype(a.dtype) if hasattr(
                                a, "dtype") else b, s, ns))

            # map aux updates back to frozen-param slots
            aux_by_id = {id(p): v for p, v in aux_pairs}
            new_frozen = [aux_by_id.get(id(p), v)
                          for p, v in zip(self._frozen_params, frozen_vals)]
            return loss, list(new_vals), new_states, new_frozen

        return step_fn

    def _shardings(self):
        mesh = self._mesh
        repl = NamedSharding(mesh, P())

        def shard_of(p):
            return p._sharding if p._sharding is not None else repl

        def state_shardings(s, p):
            psh = shard_of(p)
            return jax.tree.map(
                lambda leaf: psh if getattr(leaf, "shape", None)
                == p._data._data.shape else repl, s)

        return repl, shard_of, state_shardings

    def _compile(self):
        step_fn = self._make_step_fn()
        mesh = self._mesh
        repl, shard_of, state_shardings = self._shardings()

        in_shardings = (
            [shard_of(p) for p in self._train_params],
            [state_shardings(s, p)
             for s, p in zip(self._opt_states, self._train_params)],
            [shard_of(p) for p in self._frozen_params],
            repl, repl, repl, repl,
            NamedSharding(mesh, P(self._dp_axis)),
            NamedSharding(mesh, P(self._dp_axis)),
        )
        out_shardings = (
            repl,               # loss
            in_shardings[0],    # new param values keep their layout
            in_shardings[1],    # optimizer states likewise
            in_shardings[2],    # frozen/aux values likewise
        )
        donate = (0, 1) if self._donate else ()
        return telemetry.instrument_jit(
            jax.jit(step_fn, in_shardings=in_shardings,
                    out_shardings=out_shardings, donate_argnums=donate),
            "spmd.step")

    def _compile_multi(self):
        """N steps inside one compiled program via ``lax.scan`` —
        amortizes host dispatch over N steps; the latency-hiding answer to
        the reference's engine pipelining."""
        step_fn = self._make_step_fn()
        mesh = self._mesh
        repl, shard_of, state_shardings = self._shardings()

        def multi_fn(train_vals, opt_states, frozen_vals, keys, lr, rescale,
                     t0, datas, labels):
            def body(carry, xs):
                tv, os_, fv, t = carry
                key, d, l = xs
                loss, ntv, nos, nfv = step_fn(tv, os_, fv, key, lr,
                                              rescale, t, d, l)
                return (tuple(ntv), nos, nfv, t + 1), loss

            (tv, os_, fv, _), losses = jax.lax.scan(
                body, (tuple(train_vals), opt_states, frozen_vals, t0),
                (keys, datas, labels))
            return losses, list(tv), os_, fv

        data_sh = NamedSharding(mesh, P(None, self._dp_axis))
        in_shardings = (
            [shard_of(p) for p in self._train_params],
            [state_shardings(s, p)
             for s, p in zip(self._opt_states, self._train_params)],
            [shard_of(p) for p in self._frozen_params],
            repl, repl, repl, repl,
            data_sh, data_sh,
        )
        out_shardings = (repl, in_shardings[0], in_shardings[1],
                         in_shardings[2])
        donate = (0, 1) if self._donate else ()
        return telemetry.instrument_jit(
            jax.jit(multi_fn, in_shardings=in_shardings,
                    out_shardings=out_shardings, donate_argnums=donate),
            "spmd.run_steps")

    # ------------------------------------------------------------------ #
    def run_steps(self, data, label, batch_size: Optional[int] = None):
        """Run N fused steps in ONE dispatch.  ``data``/``label`` carry a
        leading steps axis: (N, batch, ...).  Returns the (N,) loss
        array as an NDArray."""
        from ..ndarray.ndarray import NDArray
        from .. import random as mxrandom

        d = data._data if isinstance(data, NDArray) else jnp.asarray(data)
        l = label._data if isinstance(label, NDArray) else jnp.asarray(label)
        n = d.shape[0]
        self._ensure_built(NDArray(d[0]), NDArray(l[0]))
        if self._multi_step_fn is None:
            self._multi_step_fn = self._compile_multi()
        with telemetry.span("mx:train:feed", seq=self._t + 1, steps=n):
            keys = jax.random.split(mxrandom.next_key(), n)
            lr = jnp.asarray(self._opt.learning_rate, jnp.float32)
            rescale = jnp.asarray(
                self._rescale / (batch_size if batch_size else 1.0),
                jnp.float32)
            t0 = jnp.asarray(self._t + 1, jnp.int32)
            sh = NamedSharding(self._mesh, P(None, self._dp_axis))
            if jax.process_count() > 1:
                repl = NamedSharding(self._mesh, P())
                keys, lr, rescale, t0 = (global_put(a, repl) for a in
                                         (keys, lr, rescale, t0))
            d = global_put(d, sh)
            l = global_put(l, sh)
        with telemetry.span("mx:train:step", seq=self._t + 1, steps=n):
            losses, self._train_vals, self._opt_states, \
                self._frozen_vals = self._multi_step_fn(
                    self._train_vals, self._opt_states,
                    self._frozen_vals, keys, lr, rescale, t0, d, l)
        self._t += n
        self._opt.num_update = self._t
        for p, v in zip(self._train_params, self._train_vals):
            p._data._data = v
        for p, v in zip(self._frozen_params, self._frozen_vals):
            p._data._data = v
        return NDArray(losses)

    def step_hlo_op_count(self, data, label):
        """Optimized-HLO instruction count of the compiled one-step
        program (``profiler_xla.hlo_op_count`` convention: fusion bodies
        collapse to one op, while bodies count once) — the static
        sequencer-overhead metric behind the round-3 anatomy
        (the BERT step's wall-vs-device MFU gap is ~5,300 ops x ~1 us of
        fixed per-op cost).  Compiles but does not execute; donation is
        irrelevant at lowering time."""
        from ..ndarray.ndarray import NDArray
        from .. import profiler_xla

        d = data._data if isinstance(data, NDArray) else jnp.asarray(data)
        l = label._data if isinstance(label, NDArray) \
            else jnp.asarray(label)
        self._ensure_built(NDArray(d), NDArray(l))
        lr = jnp.asarray(self._opt.learning_rate, jnp.float32)
        rescale = jnp.asarray(self._rescale, jnp.float32)
        t = jnp.asarray(max(self._t, 1), jnp.int32)
        # a CONSTANT key, not random.next_key(): only shapes matter for
        # lowering, and a diagnostic must not advance the global PRNG
        # stream (it would silently change dropout/sampling streams of
        # the surrounding training run)
        key = jax.random.PRNGKey(0)
        return profiler_xla.hlo_op_count(
            self._step_fn, self._train_vals, self._opt_states,
            self._frozen_vals, key, lr, rescale, t, d, l)

    def step(self, data, label, batch_size: Optional[int] = None):
        """Run one fused train step; returns the (device-async) loss as an
        NDArray.  ``batch_size`` defaults to the global batch dim (grad is
        the mean loss's grad, so rescale defaults to 1)."""
        from ..ndarray.ndarray import NDArray
        from .. import random as mxrandom

        d = data._data if isinstance(data, NDArray) else jnp.asarray(data)
        l = label._data if isinstance(label, NDArray) else jnp.asarray(label)
        self._ensure_built(NDArray(d), NDArray(l))
        self._t += 1
        self._opt.num_update = self._t
        # the host's two phases of a step, on the device timeline while
        # a trace runs (docs/TELEMETRY.md): feed, then the dispatch
        with telemetry.span("mx:train:feed", seq=self._t):
            lr = jnp.asarray(self._opt.learning_rate, jnp.float32)
            rescale = jnp.asarray(
                self._rescale / (batch_size if batch_size else 1.0),
                jnp.float32)
            t = jnp.asarray(self._t, jnp.int32)
            key = mxrandom.next_key()
            if jax.process_count() > 1:
                repl = NamedSharding(self._mesh, P())
                key, lr, rescale, t = (global_put(a, repl) for a in
                                       (key, lr, rescale, t))
            d = global_put(d, NamedSharding(self._mesh, P(self._dp_axis)))
            l = global_put(l, NamedSharding(self._mesh, P(self._dp_axis)))
        with telemetry.span("mx:train:step", seq=self._t):
            loss, self._train_vals, self._opt_states, \
                self._frozen_vals = self._step_fn(
                    self._train_vals, self._opt_states,
                    self._frozen_vals, key, lr, rescale, t, d, l)
        # sync new values back into the block's Parameters (rebind is
        # async — no host transfer)
        for p, v in zip(self._train_params, self._train_vals):
            p._data._data = v
        for p, v in zip(self._frozen_params, self._frozen_vals):
            p._data._data = v
        return NDArray(loss)
