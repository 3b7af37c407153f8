"""Per-XLA-op device profiling — the aggregate table *inside* a fused step.

Reference parity (SURVEY.md §5.1): the reference profiler wraps every
engine ``OprBlock`` execution, so ``MXAggregateProfileStatsPrint`` shows a
per-op totals table.  Under XLA the entire train step is ONE fused program
and host-side hooks see nothing — this module recovers the reference's
visibility by parsing the ``jax.profiler`` device trace: every executed
HLO op's device duration, bytes accessed, and model FLOPs, grouped by op
name / HLO category / source tf_op.

Usage::

    rows = profile_fn(step_fn, args)        # trace + parse in one call
    print(format_table(rows))

or through the ``mx.profiler`` facade: ``start()``/``stop()`` around any
device work, then ``device_dumps()`` renders this table.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re
import tempfile
from collections import defaultdict

__all__ = ["parse_trace", "aggregate", "format_table", "profile_fn",
           "latest_session", "count_hlo_ops", "hlo_op_count"]


def latest_session(trace_dir):
    """Return the newest profile-session directory under *trace_dir*."""
    sessions = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*")))
    if not sessions:
        raise FileNotFoundError(f"no profile sessions under {trace_dir}")
    return sessions[-1]


def parse_trace(trace_dir):
    """Parse a ``jax.profiler`` trace directory into device-op records.

    Returns a list of dicts with keys: ``name``, ``category``, ``tf_op``,
    ``dur_us`` (device duration), ``flops``, ``bytes``, ``occurrences`` =1.
    Only events on the device "XLA Ops" lanes are returned (host python /
    runtime events are skipped) — these are the per-HLO-op executions.
    """
    session = latest_session(trace_dir)
    records = []
    for tj in sorted(glob.glob(os.path.join(session, "*.trace.json.gz"))):
        with gzip.open(tj, "rt") as f:
            trace = json.load(f)
        events = trace.get("traceEvents", [])
        # identify device pids and their "XLA Ops" / "Async XLA Ops" lanes
        device_pids = set()
        op_lanes = set()
        for e in events:
            if e.get("ph") != "M":
                continue
            if e.get("name") == "process_name" and \
                    "/device:" in e["args"].get("name", ""):
                device_pids.add(e["pid"])
            if e.get("name") == "thread_name" and \
                    "XLA Ops" in e["args"].get("name", ""):
                op_lanes.add((e["pid"], e["tid"]))
        for e in events:
            if e.get("ph") != "X" or e.get("pid") not in device_pids:
                continue
            if (e["pid"], e.get("tid")) not in op_lanes:
                continue
            args = e.get("args", {})
            dur_us = float(args.get("device_duration_ps", 0)) / 1e6 \
                or float(e.get("dur", 0.0))
            records.append({
                "name": e.get("name", "?"),
                "category": args.get("hlo_category", "?"),
                "tf_op": args.get("tf_op", ""),
                "source": args.get("source", ""),
                "long_name": args.get("long_name", ""),
                "dur_us": dur_us,
                "flops": int(args.get("model_flops", 0)),
                "bytes": int(args.get("raw_bytes_accessed",
                                      args.get("bytes_accessed", 0))),
            })
    return records


def aggregate(records, by="category"):
    """Group records by ``category`` | ``name`` | ``tf_op`` | ``source``.

    Returns rows sorted by total time desc: dicts with ``key``, ``calls``,
    ``dur_us``, ``flops``, ``bytes``, ``tflops`` (achieved), ``gbps``
    (achieved HBM bandwidth), ``pct`` of total device time.
    """
    groups = defaultdict(lambda: [0, 0.0, 0, 0])
    for r in records:
        k = r[by] or "<none>"
        g = groups[k]
        g[0] += 1
        g[1] += r["dur_us"]
        g[2] += r["flops"]
        g[3] += r["bytes"]
    total = sum(g[1] for g in groups.values()) or 1.0
    rows = []
    for k, (n, dur, fl, by_) in groups.items():
        rows.append({
            "key": k, "calls": n, "dur_us": dur, "flops": fl, "bytes": by_,
            "tflops": fl / dur / 1e6 if dur else 0.0,
            "gbps": by_ / dur / 1e3 if dur else 0.0,
            "pct": 100.0 * dur / total,
        })
    rows.sort(key=lambda r: -r["dur_us"])
    return rows


def format_table(rows, peak_tflops=None, limit=30):
    """Render aggregate rows as the reference-style per-op stats table."""
    lines = [f"{'Op':<44}{'Calls':>6}{'Time(us)':>11}{'%':>6}"
             f"{'TFLOP/s':>9}{'GB/s':>8}" +
             ("{:>6}".format("MFU%") if peak_tflops else ""),
             "-" * (84 + (6 if peak_tflops else 0))]
    for r in rows[:limit]:
        line = (f"{r['key'][:43]:<44}{r['calls']:>6}{r['dur_us']:>11.1f}"
                f"{r['pct']:>6.1f}{r['tflops']:>9.1f}{r['gbps']:>8.0f}")
        if peak_tflops:
            line += f"{100 * r['tflops'] / peak_tflops:>6.1f}"
        lines.append(line)
    tot = sum(r["dur_us"] for r in rows)
    lines.append(f"{'TOTAL':<44}{sum(r['calls'] for r in rows):>6}"
                 f"{tot:>11.1f}{100.0:>6.1f}")
    return "\n".join(lines)


def profile_fn(fn, *args, trace_dir=None, iters=2, warmup=True):
    """Trace ``fn(*args)`` on device and return per-op records.

    ``fn`` should be jit-compiled; it is run once for warmup (compile),
    then ``iters`` times inside the trace window with a device->host
    readback as the sync point.
    Durations are divided by ``iters`` so rows read as per-invocation.
    """
    import numpy as onp

    import jax

    if trace_dir is None:
        trace_dir = tempfile.mkdtemp(prefix="mxtpu_prof_")
    if warmup:
        jax.block_until_ready(fn(*args))
    jax.profiler.start_trace(trace_dir)
    try:
        out = None
        for _ in range(iters):
            out = fn(*args)
        leaves = [x for x in jax.tree_util.tree_leaves(out)
                  if hasattr(x, "dtype")]
        if leaves:
            onp.asarray(jax.device_get(leaves[0]))  # readback sync
    finally:
        jax.profiler.stop_trace()
    records = parse_trace(trace_dir)
    for r in records:
        r["dur_us"] /= iters
    return records


# ----------------------------------------------------------------------- #
# static HLO op counting — the sequencer-overhead metric
# ----------------------------------------------------------------------- #
# r4 decode profile: the per-token cost floor is ~230 device
# ops x ~2.5 us of fixed sequencer cost each, and the BERT train step
# carries the same ~5,300-op gap.  The trace profiler above measures the
# overhead after the fact; these helpers measure the CAUSE — how many
# instructions the compiled program issues per invocation — so a fix
# (e.g. the stacked-layer scan decode) is assertable in CI on any
# backend, CPU included.

# instructions that exist in the HLO text but are not dispatched ops:
# parameters/constants are materialized buffers, tuple plumbing is free,
# bitcast is a layout annotation
_NON_EXEC_OPS = frozenset(
    ("parameter", "constant", "tuple", "get-tuple-element", "bitcast"))
# computation params and instruction result types may be tuples with
# internal spaces/parens — "(s32[], f32[2,4]{1,0})" — hence the loose
# ".*) ->" header match and the explicit tuple-type alternative
_COMP_HEADER = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s*->")
_CALLED_COMP = re.compile(r"(?:calls=|to_apply=)%?([\w.\-]+)")
_INSTR = re.compile(
    r"^\s+(?:ROOT\s+)?%?[\w.\-]+\s+=\s+(?:\([^)]*\)|\S+)\s+([\w\-]+)\(")


def count_hlo_ops(hlo_text):
    """Count the sequencer-visible instructions in optimized HLO text.

    Convention (matches how the device trace counts executed ops):

    - fusion bodies (``calls=``) and reduce/scatter/sort combinators
      (``to_apply=``) execute as part of ONE instruction in their caller
      — their inner instructions are not counted;
    - ``while`` bodies/conditions ARE counted, ONCE — a body that runs NL
      times still costs one body's worth of *distinct* program ops, which
      is exactly the collapse a stacked-layer ``lax.scan`` buys over an
      unrolled layer stack;
    - parameters, constants, and tuple/get-tuple-element/bitcast plumbing
      are free (no dispatched kernel).
    """
    excluded = set(_CALLED_COMP.findall(hlo_text))
    n = 0
    current = None
    for line in hlo_text.splitlines():
        m = _COMP_HEADER.match(line)
        if m:
            current = m.group(2)
            continue
        if line.startswith("}"):
            current = None
            continue
        if current is None or current in excluded:
            continue
        m = _INSTR.match(line)
        if m and m.group(1) not in _NON_EXEC_OPS:
            n += 1
    return n


def hlo_op_count(fn, *args, **kwargs):
    """Compile ``fn(*args, **kwargs)`` and return its optimized-HLO
    instruction count (see ``count_hlo_ops`` for the convention).

    ``fn`` may be a ``jax.jit`` object or a plain python callable (jitted
    here); args may be concrete arrays or ``jax.ShapeDtypeStruct``s — only
    shapes/dtypes matter, nothing is executed."""
    import jax

    if not hasattr(fn, "lower"):
        fn = jax.jit(fn)
    compiled = fn.lower(*args, **kwargs).compile()
    return count_hlo_ops(compiled.as_text())
