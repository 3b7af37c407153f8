"""``mx.profiler`` — profiling facade.

Reference surface: ``python/mxnet/profiler.py`` + ``src/profiler/``
(SURVEY.md §5.1): ``set_config(profile_all=..., filename=...)``,
``start/stop/pause/resume/dump``, per-op aggregate stats
(``dumps(reset)``), and user domains ``Task``/``Counter``/``Marker``/
``Scope``.

TPU-native: device-side tracing is ``jax.profiler`` (TensorBoard /
Perfetto trace of XLA ops on the TPU) — ``start/stop`` wrap
``jax.profiler.start_trace/stop_trace``; ``Task``/``Scope`` map onto
``jax.profiler.TraceAnnotation`` so user ranges appear in the device
timeline.  Host-side per-op aggregate timing (the reference's
``MXAggregateProfileStatsPrint`` table) is kept by a lightweight hook in
the op-dispatch path, enabled while profiling is on."""
from __future__ import annotations

import atexit
import json
import os
import threading
import time
from collections import defaultdict

import jax

__all__ = ["set_config", "profiler_set_config", "start", "stop", "pause",
           "resume", "dump", "dumps", "device_dumps", "device_regions",
           "set_state", "state",
           "Task", "Frame", "Counter", "Marker", "Scope", "TraceAnnotation"]

_lock = threading.Lock()
_config = {
    "filename": "profile.json",
    "profile_all": False,
    "profile_symbolic": True,
    "profile_imperative": True,
    "profile_memory": False,
    "profile_api": False,
    "aggregate_stats": True,
    "continuous_dump": False,
    # ``jax.profiler.ProfileOptions`` of the trace the facade starts
    # itself (None = JAX's default).  The Python tracer slows host code
    # severalfold: a live server is profiled with python_tracer_level=0
    "python_tracer_level": None,
    "host_tracer_level": None,
}
# ``xplane``: the bytes of the trace the last stop() ended (replaced, never
# accumulated); ``parsed``: profiler_xla.parse_xplane of them, on first use
_state = {"running": False, "trace_dir": None, "op_stats": None,
          "paused": False, "xplane": None, "parsed": None}


def set_config(**kwargs):
    """``mx.profiler.set_config(profile_all=True, filename='prof')`` —
    ``filename`` names the trace output directory (TensorBoard/Perfetto
    format rather than the reference's single chrome-tracing JSON).

    Unknown keys raise ``MXNetError`` naming the offender — a typoed
    ``profile_imperativ=`` must not silently configure nothing."""
    from .base import MXNetError

    unknown = sorted(set(kwargs) - set(_config))
    if unknown:
        raise MXNetError(
            f"profiler.set_config: unknown config key(s) {unknown}; "
            f"known keys: {sorted(_config)}")
    _config.update(kwargs)


profiler_set_config = set_config


class _OpStats:
    """Aggregate per-op host-dispatch stats (reference aggregate table)."""

    def __init__(self):
        self.times = defaultdict(lambda: [0, 0.0, float("inf"), 0.0])

    def record(self, name, dt):
        t = self.times[name]
        t[0] += 1
        t[1] += dt
        t[2] = min(t[2], dt)
        t[3] = max(t[3], dt)

    def table(self):
        lines = [f"{'Name':<40}{'Calls':>8}{'Total(ms)':>12}"
                 f"{'Min(ms)':>10}{'Max(ms)':>10}", "-" * 80]
        for name, (n, tot, mn, mx) in sorted(
                self.times.items(), key=lambda kv: -kv[1][1]):
            lines.append(f"{name:<40}{n:>8}{tot * 1e3:>12.3f}"
                         f"{mn * 1e3:>10.3f}{mx * 1e3:>10.3f}")
        return "\n".join(lines)


def _hook(name, dt):
    # under _lock: ``dumps(reset=True)`` swaps op_stats while dispatch
    # threads record — an unlocked read-then-record here could land a
    # row in the already-rendered stats object (a lost count)
    with _lock:
        st = _state["op_stats"]
        if st is not None:
            st.record(name, dt)


def _running_trace_dir():
    """The log directory of a ``jax.profiler`` trace that already runs,
    else ``None``.  JAX 0.9.0 keeps it in the private
    ``jax._src.profiler._profile_state`` (``profile_session``,
    ``log_dir``); ``tests/test_tracing_regions.py`` pins both names, and a
    JAX without them reads as "no trace runs"."""
    try:
        from jax._src import profiler as _jax_profiler
        st = _jax_profiler._profile_state
        return st.log_dir if st.profile_session is not None else None
    except Exception:
        return None


def _profile_options():
    levels = {k: _config[k] for k in ("python_tracer_level",
                                      "host_tracer_level")
              if _config[k] is not None}
    if not levels:
        return None
    options = jax.profiler.ProfileOptions()
    for k, v in levels.items():
        setattr(options, k, int(v))
    return options


def start():
    """Start profiling: device trace + host op stats + the phase spans of
    ``telemetry.span``.  Inside a ``jax.profiler`` trace that already runs
    ("nested") the facade keeps to that trace: it records the running
    trace's directory, and its ``stop()`` is what ends the trace."""
    # wire the per-op hook into the dispatch path (ops/registry.invoke)
    import sys
    from . import telemetry
    from .ops import registry as _registry
    _registry._profiler = sys.modules[__name__]
    with _lock:
        if _state["running"]:
            return
        trace_dir = _running_trace_dir()
        if trace_dir is None:
            trace_dir = _config["filename"]
            if trace_dir.endswith(".json"):
                trace_dir = trace_dir[:-5] + "_trace"
            os.makedirs(trace_dir, exist_ok=True)
            try:
                jax.profiler.start_trace(
                    trace_dir, profiler_options=_profile_options())
            except Exception:
                pass  # unsupported backends: keep host stats only
        telemetry.clear_spans()
        _state["running"] = True
        _state["trace_dir"] = trace_dir
        _state["xplane"] = _state["parsed"] = None
        if _state["op_stats"] is None or not _state["paused"]:
            _state["op_stats"] = _OpStats()
        _state["paused"] = False


def stop():
    """End the trace and keep its ``.xplane.pb`` as bytes (a file read, no
    parse: a caller may stop the trace in the middle of serving, and a
    parse under the GIL would slow the scheduler thread;
    ``device_regions()`` / ``device_dumps()`` parse on first use)."""
    from . import profiler_xla
    with _lock:
        if not _state["running"]:
            return
        # spans stop with the trace, not with its export (seconds for a
        # trace of tens of MB, during which a server keeps stepping)
        _state["running"] = False
        try:
            jax.profiler.stop_trace()
        except Exception:
            pass
        try:
            _state["xplane"] = profiler_xla.read_xplane(_state["trace_dir"])
        except OSError:
            _state["xplane"] = None
        _state["parsed"] = None


def pause(profile_process="worker"):
    """Suspend collection WITHOUT resetting accumulated stats (reference
    pause/resume semantics)."""
    with _lock:
        if not _state["running"]:
            return
        _state["paused"] = True
    stop()


def resume(profile_process="worker"):
    start()


def dump(finished=True, profile_process="worker"):
    """Write the aggregate table next to the trace dir (the device trace
    itself is already on disk in TensorBoard format)."""
    st = _state["op_stats"]
    if st is None:
        return
    out = {"traceEvents": [
        {"name": name, "ph": "X", "ts": 0, "dur": v[1] * 1e6,
         "pid": 0, "tid": 0, "args": {"calls": v[0]}}
        for name, v in st.times.items()]}
    fname = _config["filename"]
    if not fname.endswith(".json"):
        fname += ".json"
    with open(fname, "w") as f:
        json.dump(out, f)


def dumps(reset=False):
    """Return the aggregate stats table as a string (reference
    ``MXAggregateProfileStatsPrint``)."""
    with _lock:
        st = _state["op_stats"]
        s = st.table() if st else ""
        if reset and st:
            _state["op_stats"] = _OpStats()
    return s


def _parsed_trace():
    """``profiler_xla.parse_xplane`` of the trace the last ``stop()``
    ended (parsed once), or ``None``: no trace, no device plane."""
    from . import profiler_xla
    with _lock:
        raw, parsed = _state["xplane"], _state["parsed"]
    if parsed is not None or not raw:
        return parsed
    try:        # outside the lock: the dispatch path's _hook takes it
        parsed = profiler_xla.parse_xplane(raw)
    except Exception:
        parsed = None                   # truncated trace: best effort
    with _lock:
        if _state["xplane"] is raw:     # no newer trace since
            _state["parsed"] = parsed
    return parsed


def device_dumps(by="tf_op", peak_tflops=None, limit=30):
    """Per-XLA-op device-time table for the last ``start()``/``stop()``
    window — the reference's per-op aggregate, recovered *inside* fused
    jit steps by parsing the device trace (see ``profiler_xla``).

    ``by``: "tf_op" (jaxpr-level provenance), "region" (its innermost
    ``mx.*`` scope), "name" (HLO op), "category"
    (convolution/fusion/copy/all-reduce...), or "source"."""
    from . import profiler_xla
    if by not in ("tf_op", "region", "name", "category", "source"):
        raise ValueError(f"by={by!r}: expected one of 'tf_op', "
                         "'region', 'name', 'category', 'source'")
    parsed = _parsed_trace()
    if parsed is None:
        return ""
    rows = profiler_xla.aggregate(parsed["ops"], by=by)
    return profiler_xla.format_table(rows, peak_tflops=peak_tflops,
                                     limit=limit)


def device_regions():
    """Where the device time of the last ``start()``/``stop()`` window went
    inside each executable: ``{"jit_step": {"runs": n, "run_seconds": s,
    "regions": {"mx.attn": s, ..., "unscoped": s}}}`` over the runs that
    lie whole in the trace (``profiler_xla.device_regions``; the region
    vocabulary is docs/TELEMETRY.md's).  ``None`` where the trace has no
    device plane (a CPU run) or no trace was taken."""
    from . import profiler_xla
    parsed = _parsed_trace()
    return None if parsed is None else profiler_xla.device_regions(parsed)


def set_state(state="stop", profile_process="worker"):
    if state in ("run", "start"):
        start()
    else:
        stop()


def state():
    return "run" if _state["running"] else "stop"


# --------------------------------------------------------------------------- #
# user annotation domains
# --------------------------------------------------------------------------- #

TraceAnnotation = jax.profiler.TraceAnnotation


class Scope:
    """``with mx.profiler.Scope('name'):`` — device-timeline annotation."""

    def __init__(self, name="<unk>"):
        self._ann = jax.profiler.TraceAnnotation(name)

    def __enter__(self):
        self._ann.__enter__()
        return self

    def __exit__(self, *a):
        self._ann.__exit__(*a)


class Task:
    """Named task with explicit start/stop (reference ``ProfileTask``)."""

    def __init__(self, domain=None, name="task"):
        self.name = getattr(domain, "name", "") + name \
            if domain is not None else name
        self._ann = None

    def start(self):
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()

    def stop(self):
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None


Frame = Task


class Counter:
    """Numeric counter (reference ``ProfileCounter``), delegated to the
    process-wide telemetry registry: the value lives in a
    ``profiler_counter{counter=}`` gauge (counters may decrement, so the
    backing instrument is a gauge), visible in ``mx.telemetry.
    snapshot()`` / ``render_prometheus()`` next to the runtime's own
    metrics.  The reference API (``set_value``/``increment``/
    ``decrement``/``+=``) is unchanged."""

    def __init__(self, domain=None, name="counter", value=None):
        from . import telemetry
        self.name = getattr(domain, "name", "") + name \
            if domain is not None else name
        # same (domain+)name = same backing gauge, so two Counter
        # objects over one name share a value (registry identity); a
        # fresh gauge starts at 0 and an existing one is NOT reset here
        self._gauge = telemetry.gauge("profiler_counter",
                                      counter=self.name)
        if value is not None:
            self.set_value(value)

    @property
    def value(self):
        return self._gauge.value

    @value.setter
    def value(self, v):
        # the reference API allowed plain ``c.value = n`` assignment
        self._gauge.set(v)

    def set_value(self, value):
        self._gauge.set(value)

    def increment(self, delta=1):
        self._gauge.add(delta)

    def decrement(self, delta=1):
        self._gauge.add(-delta)

    def __iadd__(self, v):
        self.increment(v)
        return self

    def __isub__(self, v):
        self.decrement(v)
        return self


class Marker:
    """Instant event (reference ``ProfileMarker``), delegated to the
    telemetry event log (kind ``marker``) AND the device timeline."""

    def __init__(self, domain=None, name="marker"):
        self.name = getattr(domain, "name", "") + name \
            if domain is not None else name

    def mark(self, scope="process"):
        from . import telemetry
        telemetry.emit("marker", name=self.name, scope=scope)
        with jax.profiler.TraceAnnotation(f"marker:{self.name}"):
            pass


class Domain:
    def __init__(self, name):
        self.name = name


atexit.register(stop)
