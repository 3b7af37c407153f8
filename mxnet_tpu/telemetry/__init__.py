"""``mx.telemetry`` — unified runtime telemetry (ISSUE 9).

One process-wide layer replaces the per-benchmark instruments the perf
claims used to rest on (module-global counter dicts, ad-hoc stopwatch
code, hand-called ``profiler_xla.hlo_op_count``):

- **metrics registry** (:mod:`.registry`): thread-safe counters /
  gauges / fixed-bucket histograms, near-zero cost to record, exported
  on demand via :func:`snapshot` or :func:`render_prometheus`.
- **event log** (:mod:`.events`): structured ``compile`` / serve-span /
  bench events in a bounded ring, fanned out to JSONL sinks
  (``MXNET_TELEMETRY_JSONL=path`` or :func:`add_jsonl_sink`);
  ``tools/telemetry_report.py`` summarizes a recorded file and
  re-checks the dispatch/retrace invariants from it alone.
- **compile watch** (:func:`instrument_jit`): every ``jax.jit`` trace
  in the hot subsystems (fused train step, CachedOp, serve pool
  programs, offline decode) emits a ``compile`` event — retrace
  regressions become a queryable stream instead of a test-only
  assertion.
- **device-timeline bridge** (:func:`span` / :func:`spans`): while a
  device trace is being captured (``mx.profiler.start()``) serve/train
  phases appear as ``jax.profiler.TraceAnnotation`` ranges carrying
  their ids, and are kept in memory on the ``time.perf_counter()``
  clock; otherwise a span is a no-op context.
- **memory axis** (:mod:`.memory`, ISSUE 10): per-executable
  ``memory_analysis()`` bytes on compile events under
  ``MXNET_TELEMETRY_MEM=1``, the process-wide :data:`ACCOUNTANT`
  ledger of device-resident allocations by subsystem
  (``device_bytes{subsystem,device}`` gauges + ``device_memory``
  events, reconcilable against ``jax.live_arrays()``), and the byte
  arithmetic behind ``MXNET_SERVE_HBM_BUDGET`` / ``tools/
  memory_report.py``.

- **fault injection** (:mod:`.faults`, ISSUE 13): deterministic
  env-armed failures (``MXNET_FAULT_INJECT=site:kind:after_n``) at
  named sites in the serve scheduler, kvstore, and launch heartbeats,
  so every recovery path is exercisable in tier-1 on CPU; each firing
  emits a ``fault_injected`` event.  Free when unset.

``MXNET_TELEMETRY=0`` disables event emission and un-wraps the compile
watch (the registry itself stays live — ``DecodeServer.counters`` and
friends are views over it).  See docs/TELEMETRY.md.
"""
from __future__ import annotations

import collections
import contextlib
import time

from . import memory
from .compile import instrument_jit
from .events import (JsonlSink, add_jsonl_sink, add_sink, clear_events,
                     emit, events, remove_sink, telemetry_enabled)
from .faults import fault_point, parse_fault_spec, reset_faults
from .memory import (ACCOUNTANT, MemoryAccountant, format_bytes,
                     live_device_bytes, mem_enabled, memory_analysis,
                     nbytes_of, parse_bytes, per_device_bytes, reconcile)
from .registry import (DEFAULT_LATENCY_BUCKETS, REGISTRY, Counter, Gauge,
                       Histogram, Registry, counter, gauge, histogram,
                       render_prometheus, reset_metrics, snapshot)

__all__ = [
    "Counter", "Gauge", "Histogram", "Registry", "REGISTRY",
    "counter", "gauge", "histogram", "snapshot", "render_prometheus",
    "reset_metrics", "DEFAULT_LATENCY_BUCKETS",
    "emit", "events", "clear_events", "add_sink", "remove_sink",
    "add_jsonl_sink", "JsonlSink", "telemetry_enabled",
    "fault_point", "parse_fault_spec", "reset_faults",
    "instrument_jit", "span", "spans", "clear_spans",
    "memory", "ACCOUNTANT", "MemoryAccountant", "memory_analysis",
    "mem_enabled", "nbytes_of", "per_device_bytes", "live_device_bytes",
    "parse_bytes", "format_bytes", "reconcile",
]


# the phase spans of the last profiled stretch: ``(name, t0, t1, seq,
# cause, fields)`` with ``time.perf_counter()`` stamps, oldest first.  A
# bounded ring (a span is a few per scheduler step; an operator's trace of
# minutes fits), cleared by ``mx.profiler.start()``.
SPAN_RING = 1 << 16
_SPANS = collections.deque(maxlen=SPAN_RING)
_NULL = contextlib.nullcontext()


class _Span:
    """One phase span while a trace runs: a ``TraceAnnotation`` whose
    stats are the span's ids and fields, and a row of the ring."""

    __slots__ = ("name", "seq", "cause", "fields", "_hist", "_traced",
                 "_ann", "_t0")

    def __init__(self, name, seq, cause, fields, hist, traced):
        self.name, self.seq, self.cause = name, seq, cause
        self.fields, self._hist, self._traced = fields, hist, traced

    def __enter__(self):
        if self._traced:
            import jax

            stats = {k: v if isinstance(v, (int, float, str)) else
                     " ".join(map(str, v))     # ids of a wave; no commas
                     for k, v in self.fields.items()}
            if self.seq is not None:
                stats["seq"] = self.seq
            if self.cause is not None:
                stats["cause"] = self.cause
            self._ann = jax.profiler.TraceAnnotation(self.name, **stats)
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        if self._traced:
            self._ann.__exit__(*exc)
            _SPANS.append((self.name, self._t0, t1, self.seq, self.cause,
                           self.fields))
        if self._hist is not None:
            REGISTRY.histogram(self._hist).observe(t1 - self._t0)
        return False


def span(name, seq=None, cause=None, hist=None, **fields):
    """A phase of a host loop (``mx:serve:step``, ``mx:train:feed``).

    While a device trace is being captured (``mx.profiler.start()``) the
    phase is (a) a ``jax.profiler.TraceAnnotation`` on the timeline with
    ``seq`` (the id of what it dispatches), ``cause`` (the ``seq`` whose
    result it handles) and ``fields`` as the event's stats, and (b) a row
    ``(name, t0, t1, seq, cause, fields)`` of :func:`spans`, stamped with
    ``time.perf_counter()`` — so phases land in the timeline, and in
    memory, exactly when someone is looking.  Otherwise it is a free
    no-op context.  ``hist`` names a histogram that times the phase
    whether or not a trace runs."""
    from .. import profiler

    traced = profiler._state["running"]
    if not traced and hist is None:
        return _NULL
    return _Span(name, seq, cause, fields, hist, traced)


def spans(name=None):
    """The spans recorded since the last ``mx.profiler.start()`` (all, or
    those called ``name``), oldest first."""
    rows = list(_SPANS)
    return rows if name is None else [r for r in rows if r[0] == name]


def clear_spans():
    _SPANS.clear()
