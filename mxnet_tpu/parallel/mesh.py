"""Device meshes and the multi-host bootstrap.

Reference counterpart: context groups + kvstore device lists
(``mx.gpu(i)`` lists sliced by ``DataParallelExecutorGroup``) and the
ps-lite/ZMQ node bootstrap driven by ``tools/launch.py`` env vars
(``DMLC_PS_ROOT_URI``/``DMLC_ROLE``/..., SURVEY.md §4.4).  TPU-native:
one ``jax.sharding.Mesh`` names the axes (``dp``/``tp``/``sp``/``pp``)
and XLA emits the collectives; multi-host membership comes from
``jax.distributed.initialize`` instead of a ZMQ Van.
"""
from __future__ import annotations

import math
import os
import threading
import time
from typing import Optional, Sequence

import jax
import numpy as onp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..base import MXNetError

__all__ = ["Mesh", "P", "make_mesh", "current_mesh", "default_mesh",
           "use_mesh", "named_sharding", "data_sharding",
           "replicated_sharding", "init_distributed", "local_mesh_axes",
           "barrier", "global_put"]

_state = threading.local()


def make_mesh(axes=None, devices: Optional[Sequence] = None) -> Mesh:
    """Build a named device mesh.

    ``axes``: dict ``{name: size}`` in major→minor order; at most one size
    may be ``-1`` ("fill with the remaining devices").  Defaults to a pure
    data-parallel mesh ``{'dp': n_devices}``.  For multi-host topologies put
    the cross-host axis first (major) so its collectives ride DCN while the
    minor axes stay on ICI (SURVEY.md §5.8).
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if axes is None:
        axes = {"dp": n}
    if isinstance(axes, (list, tuple)):
        axes = dict(axes)
    names = list(axes.keys())
    sizes = list(axes.values())
    if sizes.count(-1) > 1:
        raise MXNetError("at most one mesh axis may be -1")
    fixed = 1
    for s in sizes:
        if s != -1:
            fixed *= s
    if n % fixed:
        raise MXNetError(
            f"mesh axes {axes} do not divide {n} devices")
    if -1 in sizes:
        sizes[sizes.index(-1)] = n // fixed
    total = 1
    for s in sizes:
        total *= s
    if total != n:
        raise MXNetError(
            f"mesh axes {dict(zip(names, sizes))} use {total} devices, "
            f"have {n}")
    arr = onp.array(devices).reshape(sizes)
    return Mesh(arr, tuple(names))


def default_mesh() -> Mesh:
    """The ambient mesh: the active ``use_mesh`` if any, else a cached pure-DP
    mesh over all devices."""
    cur = current_mesh()
    if cur is not None:
        return cur
    if getattr(_state, "default", None) is None or \
            _state.default.devices.size != len(jax.devices()):
        _state.default = make_mesh()
    return _state.default


def current_mesh() -> Optional[Mesh]:
    stack = getattr(_state, "stack", None)
    return stack[-1] if stack else None


class use_mesh:
    """Context manager making ``mesh`` the ambient mesh for sharding-aware
    APIs (Parameter.set_sharding defaults, SPMDTrainer, kvstore 'tpu')."""

    def __init__(self, mesh: Mesh):
        self.mesh = mesh

    def __enter__(self):
        if not hasattr(_state, "stack"):
            _state.stack = []
        _state.stack.append(self.mesh)
        return self.mesh

    def __exit__(self, *a):
        _state.stack.pop()


def named_sharding(mesh: Mesh, *spec) -> NamedSharding:
    return NamedSharding(mesh, P(*spec))


def data_sharding(mesh: Optional[Mesh] = None, axis: str = "dp",
                  ) -> NamedSharding:
    """Batch-dim sharding for input batches (the reference's batch slicing
    across the ctx list, SURVEY.md §3.3 row 'Data parallel')."""
    mesh = mesh or default_mesh()
    return NamedSharding(mesh, P(axis))


def replicated_sharding(mesh: Optional[Mesh] = None) -> NamedSharding:
    mesh = mesh or default_mesh()
    return NamedSharding(mesh, P())


def local_mesh_axes(mesh: Mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def global_put(x, sharding):
    """``jax.device_put`` that also works when ``sharding`` spans
    processes.  Single-process (the virtual-mesh CI shape) this IS
    ``device_put``; on a multi-process mesh ``device_put`` cannot
    target non-addressable devices, so the global array is assembled
    from each process's local data instead
    (``jax.make_array_from_process_local_data``): a batch-sharded spec
    treats ``x`` as this rank's batch slice, a replicated spec expects
    every rank to pass the same full value."""
    if jax.process_count() == 1 or not hasattr(sharding, "mesh"):
        return jax.device_put(x, sharding)
    if isinstance(x, jax.Array) and not x.is_fully_addressable:
        # already pod-global: device_put reshards globals fine — it is
        # only HOST data it cannot scatter to non-addressable devices
        return jax.device_put(x, sharding)
    local = onp.asarray(x)
    return jax.make_array_from_process_local_data(sharding, local)


def _configure_cpu_collectives():
    """Point the CPU client at a real cross-process collectives backend
    BEFORE the backend initializes.  Without this the CPU platform has
    no multi-process collectives at all — every psum across ranks
    hangs/fails — which is exactly the backend limit the pre-gloo
    ``test_kvstore_dist`` multi-process tests died on.  Only applied
    when the job is explicitly pinned to CPU (``JAX_PLATFORMS=cpu``,
    the CI stand-in for a pod); TPU pods bring their own ICI/DCN
    transport.  ``MXNET_CPU_COLLECTIVES`` overrides the implementation
    name (default ``gloo``; ``none`` disables)."""
    plats = (os.environ.get("JAX_PLATFORMS") or "").lower()
    if "cpu" not in [p.strip() for p in plats.split(",")]:
        return
    impl = os.environ.get("MXNET_CPU_COLLECTIVES", "gloo")
    if impl.lower() in ("", "0", "none"):
        return
    jax.config.update("jax_cpu_collectives_implementation", impl)


def _init_timeout_from_env():
    from ..base import parse_seconds

    t = parse_seconds("MXNET_INIT_TIMEOUT",
                      os.environ.get("MXNET_INIT_TIMEOUT", "300"))
    return t if t > 0 else None


def _init_retries_from_env():
    raw = os.environ.get("MXNET_INIT_RETRIES", "2")
    try:
        return max(int(raw), 0)
    except ValueError:
        # same loud-knob discipline as base.parse_seconds
        raise MXNetError(f"MXNET_INIT_RETRIES={raw!r}: expected an "
                         "integer")


def init_distributed(coordinator_address: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None,
                     local_device_ids=None,
                     initialization_timeout: Optional[float] = None,
                     retries: Optional[int] = None) -> None:
    """Multi-host bootstrap (replaces the reference's ps-lite scheduler
    rendezvous, SURVEY.md §4.4).

    Falls back to env vars so ``tools/launch.py``-style launchers work:
    ``MXNET_COORDINATOR`` (or the reference-compatible pair
    ``DMLC_PS_ROOT_URI``/``DMLC_PS_ROOT_PORT``), ``MXNET_NUM_WORKERS`` (or
    ``DMLC_NUM_WORKER``), ``MXNET_WORKER_ID`` (or ``DMLC_WORKER_ID``).
    No-ops when single-process and no coordinator is configured.

    Fault tolerance (ISSUE 13): when supervised by ``tools/launch.py``
    the rank starts its heartbeat BEFORE the rendezvous, so a rank
    stuck dialing a dead coordinator still reads as alive-but-waiting.
    The rendezvous itself is bounded — ``initialization_timeout``
    seconds (``MXNET_INIT_TIMEOUT``, default 300; passed through to
    ``jax.distributed`` where supported) per attempt, ``retries``
    (``MXNET_INIT_RETRIES``, default 2) extra attempts with doubling
    backoff — and a rendezvous that still cannot complete raises a
    clean ``MXNetError`` naming the coordinator and rank instead of
    blocking forever.
    """
    from .heartbeat import start_heartbeat

    start_heartbeat()
    if coordinator_address is None:
        coordinator_address = os.environ.get("MXNET_COORDINATOR")
        if coordinator_address is None:
            uri = os.environ.get("DMLC_PS_ROOT_URI")
            port = os.environ.get("DMLC_PS_ROOT_PORT")
            if uri and port:
                coordinator_address = f"{uri}:{port}"
    if num_processes is None:
        num_processes = int(os.environ.get(
            "MXNET_NUM_WORKERS", os.environ.get("DMLC_NUM_WORKER", "1")))
    if process_id is None:
        process_id = int(os.environ.get(
            "MXNET_WORKER_ID", os.environ.get("DMLC_WORKER_ID", "0")))
    if coordinator_address is None and num_processes == 1:
        return
    if initialization_timeout is None:
        initialization_timeout = _init_timeout_from_env()
    if retries is None:
        retries = _init_retries_from_env()
    _configure_cpu_collectives()
    kwargs = dict(coordinator_address=coordinator_address,
                  num_processes=num_processes,
                  process_id=process_id,
                  local_device_ids=local_device_ids)
    if initialization_timeout is not None:
        # jax takes whole seconds: round UP so a sub-second budget
        # becomes 1s, never a truncated 0 (= immediate deadline)
        kwargs["initialization_timeout"] = max(
            math.ceil(float(initialization_timeout)), 1)
    from ..telemetry.faults import fault_point

    backoff, last = 1.0, None
    for attempt in range(retries + 1):
        try:
            # chaos hook: a `raise` fault here exercises the bounded
            # retry/backoff path deterministically on CPU; a `kill`
            # fault exercises the supervisor's dead-rank handling
            # mid-rendezvous
            fault_point("dist.init", coordinator=coordinator_address,
                        rank=process_id, attempt=attempt)
            jax.distributed.initialize(**kwargs)
            from ..telemetry.events import emit

            emit("dist_init", rank=process_id,
                 processes=num_processes, attempts=attempt + 1,
                 coordinator=coordinator_address,
                 devices=len(jax.devices()))
            return
        except Exception as e:  # rendezvous/transport failure
            # genuine double-init is a programming error to surface
            # verbatim, not a rendezvous failure to retry (jax's
            # actual message is "...should only be called once.";
            # older/other versions say "already initialized")
            if "should only be called once" in str(e) \
                    or "already initialized" in str(e):
                raise
            last = e
            # a failed connect leaves jax's global distributed state
            # assigned (verified against jax 0.4.x) — tear it down or
            # every retry (including a CALLER-level one after the
            # final attempt) dies on the double-init check instead of
            # re-dialing the coordinator
            try:
                jax.distributed.shutdown()
            except Exception:
                pass
            if attempt < retries:
                time.sleep(backoff)
                backoff *= 2
    raise MXNetError(
        f"distributed init failed: rank {process_id}/{num_processes} "
        f"could not rendezvous with coordinator {coordinator_address} "
        f"after {retries + 1} attempt(s) of "
        f"{initialization_timeout or 'unbounded'}s each "
        f"(last error: {last!r}) — check that rank 0 is alive and the "
        "address is reachable; MXNET_INIT_TIMEOUT / MXNET_INIT_RETRIES "
        "tune the budget")


def _barrier_timeout_from_env():
    from ..base import parse_seconds

    t = parse_seconds("MXNET_BARRIER_TIMEOUT",
                      os.environ.get("MXNET_BARRIER_TIMEOUT", "0"))
    return t if t > 0 else None


def barrier(tag: str = "mxnet_barrier",
            timeout: Optional[float] = None) -> None:
    """Cross-process barrier with a bounded wait.

    ``timeout`` seconds (default ``MXNET_BARRIER_TIMEOUT``; unset/0 =
    wait forever, the pre-ISSUE-13 behavior) after which a clean
    ``MXNetError`` names the coordinator instead of the process
    blocking in the collective until an operator kills the job.  The
    kvstore ``dist_sync`` barrier routes through this, so a dead peer
    rank turns every survivor's next barrier into an error the
    supervisor can act on.

    On timeout the underlying collective cannot be cancelled — its
    daemon thread is abandoned (it dies with the process; the process
    group is unusable after a lost peer anyway).
    """
    if jax.process_count() == 1:
        return
    if timeout is None:
        timeout = _barrier_timeout_from_env()
    from jax.experimental import multihost_utils

    if not timeout:
        multihost_utils.sync_global_devices(tag)
        return
    done = threading.Event()
    err = []

    def _run():
        try:
            multihost_utils.sync_global_devices(tag)
        except Exception as e:
            err.append(e)
        finally:
            done.set()

    th = threading.Thread(target=_run, name="mxnet-barrier",
                          daemon=True)
    th.start()
    if not done.wait(timeout):
        raise MXNetError(
            f"barrier {tag!r} timed out after {timeout}s waiting on "
            f"the process group (rank {jax.process_index()} of "
            f"{jax.process_count()}, coordinator "
            f"{os.environ.get('MXNET_COORDINATOR', '?')}) — a peer "
            "rank is dead or wedged; the collective thread is "
            "abandoned")
    if err:
        raise MXNetError(f"barrier {tag!r} failed: {err[0]!r}")
