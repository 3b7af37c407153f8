"""Foundation: errors, env-var config, dtype tables.

TPU-native rebuild of the reference's dmlc-core facilities (SURVEY.md §3.1
"dmlc-core": logging/CHECK, `dmlc::GetEnv`, `dmlc::Parameter`) as one typed
Python config module (SURVEY.md §5.6).  `MXNET_*` environment variables keep
their reference names so existing user scripts and tests carry over.
"""
from __future__ import annotations

import os
import threading
from typing import Any, Callable, Optional

import numpy as onp

__all__ = [
    "MXNetError",
    "get_env",
    "env_truthy",
    "string_types",
    "numeric_types",
    "integer_types",
    "mx_real_t",
    "_Null",
]


class MXNetError(RuntimeError):
    """Error raised by the framework (reference anchor: ``MXGetLastError`` /
    python ``MXNetError``)."""


# float32 matmuls run at full f32 precision (like the reference's fp32 cuBLAS
# gemm); bf16 speed comes from actual bf16 dtypes (AMP), not a hidden
# precision downgrade.  Override with MXNET_TPU_MATMUL_PRECISION=default for
# raw-speed f32 experiments.
import jax as _jax

_jax.config.update(
    "jax_default_matmul_precision",
    os.environ.get("MXNET_TPU_MATMUL_PRECISION", "highest"))

# Persistent compile cache.  JAX reads JAX_COMPILATION_CACHE_DIR itself;
# when it is unset the cache lives at a FIXED path inside the checkout — a
# path that moves (tempfile, pid, timestamp) is never found again by the
# next process.  A process pinned to the CPU gets none: XLA:CPU logs two
# multi-KB "machine type doesn't match" errors per cache hit, and the CPU
# runs are the tests, whose compiles are small.
if not os.environ.get("JAX_COMPILATION_CACHE_DIR") and \
        os.environ.get("JAX_PLATFORMS", "").strip().lower() != "cpu":
    _jax.config.update(
        "jax_compilation_cache_dir",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), ".jax_cache"))


string_types = (str,)
numeric_types = (float, int, onp.generic)
integer_types = (int, onp.integer)

mx_real_t = onp.float32


class _NullType:
    """Placeholder for unset keyword arguments (reference anchor: ``_Null``
    in generated op wrappers)."""

    _inst = None

    def __new__(cls):
        if cls._inst is None:
            cls._inst = super().__new__(cls)
        return cls._inst

    def __repr__(self):
        return "_Null"

    def __bool__(self):
        return False


_Null = _NullType()


# ---------------------------------------------------------------------------
# Environment-variable config (reference: dmlc::GetEnv at point of use;
# ~100 MXNET_* vars documented in docs/.../env_var.md).  We read lazily so
# tests can monkeypatch os.environ (mirrors mx.util.environment()).
# ---------------------------------------------------------------------------

_ENV_REGISTRY: dict[str, tuple[Any, str]] = {}
_env_lock = threading.Lock()


def register_env(name: str, default: Any, doc: str = "") -> None:
    with _env_lock:
        _ENV_REGISTRY[name] = (default, doc)


def get_env(name: str, default: Any = None, typ: Optional[Callable] = None):
    """Read an ``MXNET_*`` (or any) environment variable with typed parsing."""
    if default is None and name in _ENV_REGISTRY:
        default = _ENV_REGISTRY[name][0]
    raw = os.environ.get(name)
    if raw is None:
        return default
    if typ is None and default is not None:
        typ = type(default)
    if typ is bool:
        return raw.strip().lower() in ("1", "true", "yes", "on")
    if typ is not None:
        try:
            return typ(raw)
        except (TypeError, ValueError):
            return default
    return raw


def env_truthy(name: str, default: bool = False) -> bool:
    return bool(get_env(name, default, bool))


def parse_seconds(var: str, raw) -> Optional[float]:
    """LOUD seconds-knob parsing shared by the fault-tolerance timeout
    hatches (ISSUE 13: serve deadlines/step timeout, init/barrier
    timeouts, heartbeat interval): a malformed value raises a clean
    ``MXNetError`` naming the variable — never a silent fallback to a
    default or to wait-forever, which is exactly the hang/misconfig
    these knobs exist to prevent.  Returns ``None`` for an unset
    value; zero-vs-None semantics stay at the call site."""
    if raw is None:
        return None
    try:
        return float(raw)
    except (TypeError, ValueError):
        raise MXNetError(f"{var}={raw!r}: expected seconds (a number)")


# Engine-type compat: MXNET_ENGINE_TYPE=NaiveEngine selects fully synchronous
# dispatch (reference anchor: NaiveEngine debug mode, SURVEY.md §5.2).  On
# TPU this means block_until_ready after every op.
register_env("MXNET_ENGINE_TYPE", "ThreadedEnginePerDevice",
             "NaiveEngine = synchronous dispatch for debugging")
register_env("MXNET_EXEC_BULK_EXEC_TRAIN", 1, "no-op on TPU; XLA fuses")
register_env("MXNET_GPU_MEM_POOL_TYPE", "Naive", "no-op; XLA manages HBM")
# accepted-and-ignored CUDA/engine-era vars (docs/ENV_VARS.md "Data /
# misc"): registering them keeps ported scripts working AND keeps the
# tracelint TL005 docs<->reads reconciliation honest — every documented
# hatch has exactly one read/registration site.
register_env("MXNET_CUDNN_AUTOTUNE_DEFAULT", 1,
             "no-op; XLA autotunes convolutions itself")
register_env("MXNET_KVSTORE_BIGARRAY_BOUND", 1000000,
             "no-op; collectives replace the kvstore server batching")
register_env("MXNET_USE_FUSION", 1, "no-op; XLA fusion is always on")
register_env("MXNET_GPU_WORKER_NTHREADS", 2,
             "no-op; XLA manages device streams")


def is_naive_engine() -> bool:
    return get_env("MXNET_ENGINE_TYPE") == "NaiveEngine"


# ---------------------------------------------------------------------------
# dtype tables (reference: mshadow type enum used across the C ABI)
# ---------------------------------------------------------------------------

_DTYPE_NP_TO_MX = {
    None: -1,
    onp.float32: 0,
    onp.float64: 1,
    onp.float16: 2,
    onp.uint8: 3,
    onp.int32: 4,
    onp.int8: 5,
    onp.int64: 6,
    onp.bool_: 7,
    onp.int16: 8,
    onp.uint16: 9,
    onp.uint32: 10,
    onp.uint64: 11,
}
_DTYPE_MX_TO_NP = {v: k for k, v in _DTYPE_NP_TO_MX.items()}
# bfloat16 is TPU-native; give it the id the reference reserves for bf16.
try:  # ml_dtypes ships with jax
    import ml_dtypes

    bfloat16 = ml_dtypes.bfloat16
    _DTYPE_NP_TO_MX[bfloat16] = 12
    _DTYPE_MX_TO_NP[12] = bfloat16
except ImportError:  # pragma: no cover
    bfloat16 = None


def dtype_np_to_mx(dtype) -> int:
    key = onp.dtype(dtype).type if dtype is not None else None
    if key not in _DTYPE_NP_TO_MX:
        raise MXNetError(f"unsupported dtype {dtype}")
    return _DTYPE_NP_TO_MX[key]


def dtype_mx_to_np(code: int):
    return _DTYPE_MX_TO_NP[code]
