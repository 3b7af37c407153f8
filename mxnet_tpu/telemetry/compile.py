"""Compile/retrace watch: turn every ``jax.jit`` trace into a
queryable ``compile`` event.

``instrument_jit(fn, site, ...)`` wraps a freshly created jitted
callable.  Each call compares the executable-cache size before and
after the dispatch; growth means THIS call traced+compiled a new
signature, so one ``compile`` event is emitted carrying the site, the
producer's cache key, the wall time of the triggering call (trace +
compile + first dispatch) and the cache size after (``cache_size > 1``
is a RETRACE — the regression class tests/test_fused_step.py and
tests/test_serve.py pin, now visible in production streams).  The
registry mirrors the stream: ``compiles_total{site=}`` and
``retraces_total{site=}``.  A site whose ``fields`` name a ``server`` (the
serve pool programs) also counts into that server's
``serve_compiles_total{server=}`` and ``serve_compile_ms_total{server=}``
(``DecodeServer.counters["compiles"]`` / ``["compile_ms"]``): exact however
many events the ring has let go since.

Steady-state cost per dispatch: two ``_cache_size()`` calls (a C++
attribute read) + one ``perf_counter`` pair — noise against even a
CPU-smoke decode step.  ``MXNET_TELEMETRY=0`` returns ``fn`` unwrapped,
restoring the exact pre-telemetry dispatch path.

``MXNET_TELEMETRY_HLO=1`` additionally records the optimized-HLO
instruction count (``profiler_xla.count_hlo_ops``) on each compile
event, and ``MXNET_TELEMETRY_MEM=1`` the executable's
``memory_analysis()`` bytes (argument / output / temp / generated-code
/ peak — ``mem_*`` fields, see ``telemetry.memory``).  Either flag
lowers+compiles the signature a SECOND time through the AOT path
(shape structs only — donated buffers are never touched; both flags on
share the one recompile), so they are debugging/CI modes, not
production defaults.
"""
from __future__ import annotations

import os
import time

from . import events
from . import memory
from .registry import REGISTRY

__all__ = ["instrument_jit"]


def _hlo_wanted():
    return os.environ.get("MXNET_TELEMETRY_HLO", "0") == "1"


def instrument_jit(fn, site, key=None, fields=None):
    """Wrap jitted ``fn`` so new traces emit ``compile`` events.

    ``site`` names the producer (e.g. ``"serve.step"``); ``key`` is the
    producer's own cache key (stringified into the event); ``fields``
    are extra structured fields merged into every event from this
    wrapper (e.g. bucket sizes).  Returns ``fn`` unchanged when
    telemetry is off or ``fn`` has no executable cache to watch —
    callers never need to special-case."""
    if not events.telemetry_enabled():
        return fn
    if not hasattr(fn, "_cache_size"):
        return fn
    return _CompileWatch(fn, site, key, fields)


class _CompileWatch:
    # __weakref__ matters: jax.eval_shape (the CachedOp structure-priming
    # path) takes a weak reference to the callable it traces
    __slots__ = ("_fn", "_site", "_key", "_fields", "__weakref__")

    def __init__(self, fn, site, key, fields):
        self._fn = fn
        self._site = site
        self._key = key
        self._fields = dict(fields) if fields else {}

    def __call__(self, *args, **kwargs):
        fn = self._fn
        try:
            n0 = fn._cache_size()
        except Exception:
            return fn(*args, **kwargs)
        t0 = time.perf_counter()
        out = fn(*args, **kwargs)
        try:
            n1 = fn._cache_size()
        except Exception:
            return out
        if n1 > n0:
            self._record(time.perf_counter() - t0, n1, args, kwargs)
        return out

    # -- event side ------------------------------------------------------ #
    def _record(self, wall, cache_size, args, kwargs):
        ev = dict(self._fields)
        ev["site"] = self._site
        if self._key is not None:
            ev["key"] = str(self._key)
        ev["wall_s"] = round(wall, 6)
        ev["cache_size"] = int(cache_size)
        retrace = cache_size > 1
        if retrace:
            ev["retrace"] = True
        want_hlo, want_mem = _hlo_wanted(), memory.mem_enabled()
        if want_hlo or want_mem:
            compiled = self._aot_compile(args, kwargs)
            if compiled is not None:
                if want_hlo:
                    n = self._hlo_ops(compiled)
                    if n is not None:
                        ev["hlo_ops"] = n
                if want_mem:
                    ma = memory.memory_analysis(compiled)
                    if ma is not None:
                        ev.update((f"mem_{k}", v) for k, v in ma.items())
        REGISTRY.counter("compiles_total", site=self._site).inc()
        server = self._fields.get("server")
        if server is not None:
            REGISTRY.counter("serve_compiles_total", server=server).inc()
            REGISTRY.counter("serve_compile_ms_total",
                             server=server).inc(wall * 1e3)
        if retrace:
            REGISTRY.counter("retraces_total", site=self._site).inc()
        events.emit("compile", **ev)

    def _aot_compile(self, args, kwargs):
        """Lower+compile this signature a second time from shape
        structs (already-donated input buffers are never dereferenced)
        — the one recompile both the HLO op count and the memory
        analysis read from."""
        import jax

        def struct(x):
            if hasattr(x, "shape") and hasattr(x, "dtype"):
                return jax.ShapeDtypeStruct(x.shape, x.dtype)
            return x

        try:
            s_args, s_kwargs = jax.tree_util.tree_map(struct,
                                                      (args, kwargs))
            return self._fn.lower(*s_args, **s_kwargs).compile()
        except Exception:
            return None

    @staticmethod
    def _hlo_ops(compiled):
        """Optimized-HLO instruction count of the AOT-compiled
        signature."""
        from .. import profiler_xla

        try:
            return profiler_xla.count_hlo_ops(compiled.as_text())
        except Exception:
            return None

    # the wrapper must be a drop-in for the jitted fn: tests and callers
    # reach for ``_cache_size()`` / ``lower()`` on the returned object
    def __getattr__(self, name):
        return getattr(self._fn, name)

    def __repr__(self):
        return f"instrumented[{self._site}]({self._fn!r})"
