"""From a profiler trace to the few numbers the metrics read.

``load_xplane`` turns the ``.xplane.pb`` the JAX profiler writes into a
neutral form — ``[{"name", "lines": [{"name", "events": [(name, start_ns,
dur_ns), ...]}]}]`` — and ``reduce`` turns that form into:

- ``busy_s`` / ``window_s`` / ``idle_pct``: the union of the intervals in
  which an operation ran on a device ("XLA Ops" lane of each
  ``/device:TPU:n`` plane), averaged over the device planes, against the
  traced window;
- ``modules``: per executable (the "XLA Modules" lane; ``jit_step(123)`` is
  keyed ``jit_step``) its device seconds and its runs inside the window,
  and the same over the runs the window's ends do not cut (``whole_*``);
- ``device_ops``: the operations with most SELF time (an enclosing
  ``while`` does not count its body twice), keyed ``module/op``;
- ``idle_gaps``: the idle intervals summed by the host span that covered
  most of each (``mx:*`` annotations of the program and the benchmark's own
  ``cb:*`` ones), ``inside:<module>`` for a pause between two operations
  of one run of an executable, ``none`` where nothing covered it.

Lane names were read off a v5e trace (PERF.md section 3); a trace without
a device plane reduces to ``None`` — a metric then has nothing to read.
"""
import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIXES = ("mx:", "cb:")
WINDOW_SPAN = "cb:window"       # the bracket of the traced window; no label


def load_xplane(trace_dir):
    """The neutral form of the newest ``*.xplane.pb`` under ``trace_dir``,
    keeping only the lanes ``reduce`` reads."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        return []
    planes = []
    for plane in ProfileData.from_file(files[-1]).planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        if not device and plane.name != HOST_PLANE:
            continue
        lines = []
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            events = [(e.name, float(e.start_ns), float(e.duration_ns))
                      for e in line.events
                      if device or e.name.startswith(SPAN_PREFIXES)]
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def union(intervals):
    """Merged, sorted ``[(start, end), ...]`` of possibly nested or
    overlapping intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(intervals, t0, t1):
    return [(max(s, t0), min(e, t1)) for s, e in intervals
            if e > t0 and s < t1]


def _self_times(events):
    """``[(name, start, self_ns)]``: an event's time less its children's."""
    order = sorted(events, key=lambda e: (e[1], -e[2]))
    out, stack = [], []          # stack of [name, start, end, self]
    for name, start, dur in order:
        while stack and start >= stack[-1][2]:
            top = stack.pop()
            out.append((top[0], top[1], top[3]))
        if stack:
            stack[-1][3] -= dur
        stack.append([name, start, start + dur, dur])
    out.extend((s[0], s[1], s[3]) for s in stack)
    return out


def _short_op(name):
    """``%fusion.13 = bf16[..] fusion(..)`` -> ``fusion.13``."""
    return name.split(" = ", 1)[0].lstrip("%").strip()[:80]


def _module_key(name):
    return name.split("(", 1)[0]


def reduce(planes, window=None, top=10):
    """See the module docstring.  ``window`` is ``(t0_ns, t1_ns)`` on the
    trace's clock; by default the span from the first to the last device
    event."""
    devices = [p for p in planes if DEVICE_PLANE.match(p["name"])]
    lanes = []
    for p in devices:
        by = {l["name"]: l["events"] for l in p["lines"]}
        if by.get(OPS_LINE):
            lanes.append((by[OPS_LINE], by.get(MODULES_LINE, [])))
    if not lanes:
        return None
    if window is None:
        every = [e for ops, _ in lanes for e in ops]
        window = (min(e[1] for e in every), max(e[1] + e[2] for e in every))
    t0, t1 = window
    spans = [e for p in planes if p["name"] == HOST_PLANE
             for l in p["lines"] for e in l["events"]
             if e[0].startswith(SPAN_PREFIXES) and e[0] != WINDOW_SPAN]

    busy_ns, modules, op_self, gap_by = 0.0, {}, {}, {}
    for ops, mods in lanes:
        merged = _clip(union((s, s + d) for _, s, d in ops), t0, t1)
        busy_ns += sum(e - s for s, e in merged)
        mods = sorted((s, s + d, _module_key(n)) for n, s, d in mods)
        for s, e, key in mods:
            if e > t0 and s < t1:
                row = modules.setdefault(key, {"seconds": 0.0, "runs": 0,
                                               "whole_seconds": 0.0,
                                               "whole_runs": 0})
                row["seconds"] += (min(e, t1) - max(s, t0)) / 1e9
                row["runs"] += 1
                if s >= t0 and e <= t1:     # not cut by the window's ends
                    row["whole_seconds"] += (e - s) / 1e9
                    row["whole_runs"] += 1
        starts = [m[0] for m in mods]

        def module_at(t):
            i = bisect.bisect_right(starts, t) - 1
            return mods[i][2] if i >= 0 and t < mods[i][1] else None

        for name, start, self_ns in _self_times(ops):
            if not t0 <= start < t1:
                continue
            key = f"{module_at(start) or '?'}/{_short_op(name)}"
            op_self[key] = op_self.get(key, 0.0) + self_ns / 1e9
        edges = [t0] + [x for iv in merged for x in iv] + [t1]
        for gs, ge in zip(edges[0::2], edges[1::2]):
            if ge <= gs:
                continue
            inside = module_at(gs)
            label, cover = "none", 0.0
            if inside is not None and module_at(ge - 1.0) == inside:
                label = f"inside:{inside}"
            else:
                for n, s, d in spans:
                    c = min(ge, s + d) - max(gs, s)
                    if c > cover:
                        label, cover = n, c
            gap_by[label] = gap_by.get(label, 0.0) + (ge - gs) / 1e9
    n = len(lanes)
    busy_s, window_s = busy_ns / 1e9 / n, (t1 - t0) / 1e9

    def ranked(d):
        return [[k, v / n] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {
        "busy_s": busy_s, "window_s": window_s,
        "idle_pct": 100.0 * (1.0 - busy_s / window_s),
        "modules": {k: {f: x / n for f, x in v.items()}
                    for k, v in modules.items()},
        "device_ops": ranked(op_self), "idle_gaps": ranked(gap_by),
    }


def step_device_s(run):
    """Device seconds of one run of the configuration's step executable,
    over its runs that lie whole in the traced window (the "XLA Modules"
    lane; the executable's name is the configuration file's
    ``executables.step``), or ``None``."""
    trace = run.get("trace")
    name = run["config"].get("executables", {}).get("step")
    if not trace or name not in trace["modules"]:
        return None
    row = trace["modules"][name]
    return row["whole_seconds"] / row["whole_runs"] if row["whole_runs"] \
        else None
