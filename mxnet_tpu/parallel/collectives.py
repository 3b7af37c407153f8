"""Explicit collectives over the mesh.

Reference counterpart (SURVEY.md §5.8): ``CommDevice``/``CommDeviceTree``
P2P reduction trees, ``KVStoreNCCL`` ring allreduce, ps-lite cross-node
push/pull.  TPU-native: every collective is a ``shard_map``-wrapped XLA
collective (psum / all_gather / psum_scatter / ppermute) compiled onto
ICI/DCN; there is no engine scheduling — overlap comes from XLA's
latency-hiding scheduler.

These helpers take and return ``NDArray``/jax arrays whose leading axis is
sharded over ``axis`` (or replicated inputs for broadcast).  They are the
building blocks of ``KVStore('tpu')`` and of the multi-host `dist_sync`
path; inside a jitted SPMD step you normally never call them — GSPMD
inserts the equivalent ops from sharding annotations.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from .mesh import Mesh, P, default_mesh, local_mesh_axes

__all__ = ["all_reduce", "all_gather", "reduce_scatter", "broadcast",
           "ring_pass", "dp_sharding"]


def dp_sharding(mesh: Optional[Mesh] = None, axis: str = "dp"):
    """``NamedSharding`` laying a batch out over the data-parallel axis
    (delegates to :func:`..mesh.data_sharding` — one definition).

    The fused train step (``Trainer.fused_step(...,
    data_sharding=dp_sharding(mesh))``) places its batch operands with
    this sharding; with the parameters replicated (or GSPMD-sharded) over
    the same mesh, the compiled step then CONTAINS the cross-replica
    gradient all-reduce — the reference's per-step KVStore pushpull phase
    folded into the one traced executable, inserted by GSPMD instead of
    engine-scheduled ops (SURVEY.md §7 "KVStore")."""
    from .mesh import data_sharding
    return data_sharding(mesh, axis)


def _unwrap(x):
    from ..ndarray.ndarray import NDArray
    return x._data if isinstance(x, NDArray) else jnp.asarray(x)


def _wrap_like(val, ref):
    from ..ndarray.ndarray import NDArray
    if isinstance(ref, NDArray):
        return NDArray(val)
    return val


_OPS = {
    "sum": jax.lax.psum,
    "mean": jax.lax.pmean,
    "max": jax.lax.pmax,
    "min": jax.lax.pmin,
}


def all_reduce(x, mesh: Optional[Mesh] = None, axis: str = "dp",
               op: str = "sum"):
    """All-reduce ``x`` (sharded on its leading dim over ``axis``) — the
    result is the reduced value, replicated over ``axis``, with the same
    per-shard shape.  Equivalent of one NCCL ring all-reduce
    (``KVStoreNCCL``)."""
    if op not in _OPS:
        raise ValueError(f"unknown reduce op {op}")
    mesh = mesh or default_mesh()
    red = _OPS[op]
    data = _unwrap(x)

    fn = jax.shard_map(lambda v: red(v, axis), mesh=mesh,
                       in_specs=P(axis), out_specs=P())
    # input must be laid out sharded over axis; put it there if it isn't
    data = jax.device_put(data, NamedSharding(mesh, P(axis)))
    return _wrap_like(fn(data), x)


def all_gather(x, mesh: Optional[Mesh] = None, axis: str = "dp",
               tiled: bool = True):
    """Gather shards along the leading dim: per-shard (s, ...) → full
    (s*n, ...) on every device."""
    mesh = mesh or default_mesh()
    data = jax.device_put(_unwrap(x), NamedSharding(mesh, P(axis)))
    fn = jax.shard_map(
        lambda v: jax.lax.all_gather(v, axis, tiled=tiled),
        mesh=mesh, in_specs=P(axis), out_specs=P(), check_vma=False)
    return _wrap_like(fn(data), x)


def reduce_scatter(x, mesh: Optional[Mesh] = None, axis: str = "dp",
                   op: str = "sum"):
    """Reduce-scatter: every shard holds the (full-size) addend; the result
    is the reduced value scattered over ``axis`` along the leading dim.
    Equivalent of the reference's tree reduce-scatter phase
    (``comm_tree.h``)."""
    mesh = mesh or default_mesh()
    n = local_mesh_axes(mesh)[axis]
    data = _unwrap(x)
    if data.shape[0] % n:
        raise ValueError(
            f"leading dim {data.shape[0]} not divisible by axis size {n}")
    # replicate input, psum_scatter inside shard_map
    data = jax.device_put(data, NamedSharding(mesh, P()))
    fn = jax.shard_map(
        lambda v: jax.lax.psum_scatter(v, axis, scatter_dimension=0,
                                       tiled=True),
        mesh=mesh, in_specs=P(), out_specs=P(axis))
    return _wrap_like(fn(data), x)


def broadcast(x, mesh: Optional[Mesh] = None, axis: str = "dp",
              root: int = 0):
    """Broadcast shard ``root``'s value to all devices on ``axis`` (the
    reference's CommDevice broadcast phase)."""
    mesh = mesh or default_mesh()
    n = local_mesh_axes(mesh)[axis]
    if not 0 <= root < n:
        raise ValueError(f"broadcast root {root} out of range for axis "
                         f"{axis!r} of size {n}")
    data = jax.device_put(_unwrap(x), NamedSharding(mesh, P(axis)))

    def _bcast(v):
        idx = jax.lax.axis_index(axis)
        # where (not multiply): inf/NaN on non-root shards must not leak
        # through the psum
        contrib = jnp.where(idx == root, v, jnp.zeros_like(v))
        return jax.lax.psum(contrib, axis)

    fn = jax.shard_map(_bcast, mesh=mesh, in_specs=P(axis), out_specs=P())
    return _wrap_like(fn(data), x)


def ring_pass(x, mesh: Optional[Mesh] = None, axis: str = "sp",
              shift: int = 1):
    """Rotate shards around the ``axis`` ring by ``shift`` steps
    (collective-permute over ICI) — the primitive under ring attention
    (SURVEY.md §5.7, new capability vs the reference)."""
    mesh = mesh or default_mesh()
    n = local_mesh_axes(mesh)[axis]
    perm = [(i, (i + shift) % n) for i in range(n)]
    data = jax.device_put(_unwrap(x), NamedSharding(mesh, P(axis)))
    fn = jax.shard_map(
        partial(jax.lax.ppermute, axis_name=axis, perm=perm),
        mesh=mesh, in_specs=P(axis), out_specs=P(axis))
    return _wrap_like(fn(data), x)
