"""Pipeline parallelism (GPipe schedule over a mesh axis).

SURVEY.md §3.3 marks PP "optional later phase" for the reference (which has
none — only manual ``group2ctx`` placement).  TPU-native implementation:
stages live on a ``pp`` mesh axis, activations flow stage-to-stage with
``ppermute`` (ICI-neighbor traffic), and microbatches fill the pipeline on
a GPipe schedule — M microbatches over S stages cost M+S-1 ticks, all
inside ONE jitted ``shard_map`` (XLA overlaps the permute with compute).

The schedule is differentiable end-to-end: ``jax.grad`` through
``gpipe_apply`` backpropagates the reverse schedule automatically, so a
pipelined train step is just ``jax.value_and_grad(loss ∘ gpipe_apply)``.

Constraint (by design): activations circulate a ring, so the stage input
and output shapes must match — run embeddings/heads outside the pipelined
trunk (the standard GPipe decomposition).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding

from .mesh import Mesh, P, default_mesh, local_mesh_axes

__all__ = ["gpipe_apply", "stack_stage_params"]


def stack_stage_params(stage_params_list):
    """Stack per-stage parameter pytrees on a new leading axis (the ``pp``
    sharding axis): [tree_0, ..., tree_{S-1}] → tree of (S, ...) arrays."""
    return jax.tree.map(lambda *leaves: jnp.stack(leaves),
                        *stage_params_list)


def gpipe_apply(stage_fn: Callable, stage_params, x, mesh: Mesh = None,
                axis: str = "pp", microbatches: int = None,
                param_specs=None, batch_axis: str = None):
    """Run ``x`` through S pipeline stages with a GPipe schedule.

    - ``stage_fn(params_i, h) -> h`` — one stage (same structure every
      stage, per-stage weights; h-shape invariant).
    - ``stage_params`` — pytree with leading axis S (see
      :func:`stack_stage_params`), sharded over ``axis``.
    - ``x`` — (batch, ...) input, split into ``microbatches`` chunks along
      axis 0 (default S, the minimum that fills the pipeline).
    - ``param_specs`` — optional pytree of ``PartitionSpec`` for the
      stacked stage params (leading axis must be ``axis``), enabling
      pp×tp composition: shard stage weights over a tensor axis too and
      do the tp collectives (``lax.psum``/``lax.all_gather``) inside
      ``stage_fn`` itself.  With ``param_specs`` the stage must also
      preserve the activation DTYPE (not just shape): in-shard
      collectives cannot be eval_shape'd up front, so a dtype-changing
      stage surfaces as a scan-carry mismatch instead of the pure-pp
      path's ring-invariance error.
    - ``batch_axis`` — optional mesh axis to shard the microbatch dim
      over (dp×pp composition); the output stays sharded over it.

    Returns the final stage's (batch, ...) output, replicated over
    ``axis`` (and sharded over ``batch_axis`` if given).
    """
    from ..ndarray.ndarray import NDArray

    mesh = mesh or default_mesh()
    S = local_mesh_axes(mesh)[axis]
    M = microbatches or S
    xv = x._data if isinstance(x, NDArray) else jnp.asarray(x)
    params = jax.tree.map(
        lambda a: a._data if isinstance(a, NDArray) else jnp.asarray(a),
        stage_params)
    B = xv.shape[0]
    if B % M:
        raise ValueError(f"batch {B} must divide into {M} microbatches")
    mb = B // M
    xs = xv.reshape((M, mb) + xv.shape[1:])

    out_dtype = xv.dtype
    if param_specs is None:
        # pure-pp path: stage_fn sees global microbatch shapes, so the
        # ring-invariance precondition is checkable up front.  (With
        # param_specs the stage may use in-shard collectives, which
        # cannot be eval_shape'd outside shard_map.)
        p0 = jax.tree.map(lambda a: a[0], params)
        out_aval = jax.eval_shape(stage_fn, p0, jax.ShapeDtypeStruct(
            (mb,) + xv.shape[1:], xv.dtype))
        if tuple(out_aval.shape) != (mb,) + tuple(xv.shape[1:]):
            raise ValueError(
                "gpipe_apply requires ring-invariant activations: stage "
                f"output {tuple(out_aval.shape)} != input "
                f"{(mb,) + tuple(xv.shape[1:])}; keep embeddings/heads "
                "outside the pipelined trunk")
        out_dtype = out_aval.dtype

    def shard_fn(local_params, xs_local):
        my = lax.axis_index(axis)
        lp = jax.tree.map(lambda a: a[0], local_params)  # drop local S=1
        fwd = [(i, (i + 1) % S) for i in range(S)]

        def tick(state, t):
            prev = lax.ppermute(state, axis, fwd)
            x_t = xs_local[jnp.minimum(t, M - 1)].astype(state.dtype)
            inp = jnp.where(my == 0, x_t, prev)
            out = stage_fn(lp, inp)
            return out, out

        state0 = jnp.zeros(xs_local.shape[1:], out_dtype)
        # the carry varies per pp shard; mark the init accordingly
        state0 = lax.pcast(state0, (axis,), to="varying")
        _, hist = lax.scan(tick, state0, jnp.arange(M + S - 1))
        # the final stage emits microbatch m at tick m + S - 1
        outs = lax.dynamic_slice_in_dim(hist, S - 1, M, axis=0)
        mine = jnp.where(my == S - 1, outs, jnp.zeros_like(outs))
        return lax.psum(mine, axis)  # replicate the true outputs

    pspec = (param_specs if param_specs is not None
             else jax.tree.map(lambda a: P(axis), params))
    params = jax.device_put(params, jax.tree.map(
        lambda s: NamedSharding(mesh, s), pspec))
    x_spec = P(None, batch_axis) if batch_axis else P()
    # in-stage collectives (tp) defeat the static replication checker
    check_vma = param_specs is None and not batch_axis
    fn = jax.shard_map(shard_fn, mesh=mesh, in_specs=(pspec, x_spec),
                       out_specs=x_spec, check_vma=check_vma)
    out = fn(params, xs)
    result = out.reshape((B,) + out.shape[2:])
    return NDArray(result) if isinstance(x, NDArray) else result
