"""Per-XLA-op device profiling — the aggregate table *inside* a fused step.

Reference parity (SURVEY.md §5.1): the reference profiler wraps every
engine ``OprBlock`` execution, so ``MXAggregateProfileStatsPrint`` shows a
per-op totals table.  Under XLA the entire train step is ONE fused program
and host-side hooks see nothing — this module recovers the reference's
visibility by parsing the ``jax.profiler`` device trace: every executed
HLO op's device duration, bytes accessed, and model FLOPs, grouped by op
name / HLO category / provenance (``tf_op``) / ``mx.*`` region, read from
the ``.xplane.pb`` the profiler writes (``parse_xplane``).

Usage::

    ops = profile_fn(step_fn, args)         # trace + parse in one call
    print(format_table(aggregate(ops, by="tf_op")))

or through the ``mx.profiler`` facade: ``start()``/``stop()`` around any
device work, then ``device_dumps()`` renders this table.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import struct
import tempfile
from collections import defaultdict

__all__ = ["parse_xplane", "read_xplane", "device_regions", "region_of",
           "aggregate", "format_table", "profile_fn", "count_hlo_ops",
           "hlo_op_count"]

# lane names of a v5e trace (PERF.md section 3); chipbench/reduce.py reads
# the same two
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
UNSCOPED = "unscoped"
_EDGE_PS = 1_000_000     # 1 us: a run this close to the trace's first or
                         # last device event is the trace's first or last
_REGION = re.compile(r"mx\.[a-z_]+")


def read_xplane(trace_dir):
    """The bytes of the newest ``*.xplane.pb`` under *trace_dir* (what
    ``jax.profiler.stop_trace`` leaves in ``plugins/profile/<time>/``),
    or ``None`` when there is none."""
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        return None
    with open(files[-1], "rb") as f:
        return f.read()


# ----------------------------------------------------------------------- #
# the xspace wire format — tsl/profiler/protobuf/xplane.proto
# ----------------------------------------------------------------------- #
# ``jax.profiler.ProfileData`` hands out an event's name, start, duration
# and its OWN stats; the provenance of a device operation (``tf_op``, the
# jaxpr name stack a ``jax.named_scope`` writes into), its category, FLOPs
# and bytes are stats of its XEventMetadata, which ProfileData does not
# expose (read off a v5e trace, PERF.md section 3).  So the few messages
# below are decoded here, from the bytes, with the standard library only:
#   XSpace{planes=1}  XPlane{name=2, lines=3, event_metadata=4 (map),
#   stat_metadata=5 (map), stats=6}  XLine{name=2, timestamp_ns=3,
#   events=4}  XEvent{metadata_id=1, offset_ps=2, duration_ps=3, stats=4}
#   XEventMetadata{id=1, name=2, display_name=4, stats=5}
#   XStatMetadata{id=1, name=2}  XStat{metadata_id=1, double=2, uint64=3,
#   int64=4, str=5, bytes=6, ref=7}

def _varint(buf, pos):
    b = buf[pos]
    pos += 1
    if b < 0x80:
        return b, pos
    out, shift = b & 0x7F, 7
    while True:
        b = buf[pos]
        pos += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, pos
        shift += 7


def _fields(buf, pos, end):
    """``(field number, value)`` of one message: an int for a varint, a
    ``(start, end)`` slice for a length-delimited field, raw bytes for a
    fixed one."""
    while pos < end:
        key, pos = _varint(buf, pos)
        kind = key & 7
        if kind == 0:
            val, pos = _varint(buf, pos)
        elif kind == 2:
            n, pos = _varint(buf, pos)
            val = (pos, pos + n)
            pos += n
        elif kind in (1, 5):
            n = 8 if kind == 1 else 4
            val = buf[pos:pos + n]
            pos += n
        else:
            raise ValueError(f"xspace: wire type {kind} at byte {pos}")
        yield key >> 3, val


def _text(buf, span):
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _stat(buf, span, stat_names):
    """``(name, value)`` of one XStat; a ``ref`` names its string."""
    name = value = None
    for f, v in _fields(buf, *span):
        if f == 1:
            name = stat_names.get(v, str(v))
        elif f == 2:
            value = struct.unpack("<d", v)[0]
        elif f == 3:
            value = v
        elif f == 4:
            value = v - (1 << 64) if v >> 63 else v
        elif f == 5:
            value = _text(buf, v)
        elif f == 6:
            value = bytes(buf[v[0]:v[1]])
        elif f == 7:
            value = stat_names.get(v, "")
    return name, value


def _map_entries(buf, spans):
    """``{key: value span}`` of a ``map<int64, Message>`` field."""
    out = {}
    for span in spans:
        key = val = None
        for f, v in _fields(buf, *span):
            if f == 1:
                key = v
            elif f == 2:
                val = v
        if key is not None and val is not None:
            out[key] = val
    return out


def _device_planes(data):
    """The device planes of a serialized xspace, decoded as far as
    ``parse_xplane`` reads them: ``[{"name", "lines": {lane: [(metadata
    id, start_ps, dur_ps), ...]}, "meta": {id: {"name", "display",
    stats...}}}]``.  Host planes are skipped unread."""
    buf = memoryview(data)
    planes = []
    for f, span in _fields(buf, 0, len(buf)):
        if f != 1:
            continue
        name, lines, metas, stat_meta = "", [], [], []
        for pf, v in _fields(buf, *span):
            if pf == 2:
                name = _text(buf, v)
            elif pf == 3:
                lines.append(v)
            elif pf == 4:
                metas.append(v)
            elif pf == 5:
                stat_meta.append(v)
        if not DEVICE_PLANE.match(name):
            continue
        stat_names = {}
        for key, v in _map_entries(buf, stat_meta).items():
            stat_names[key] = next(
                (_text(buf, x) for sf, x in _fields(buf, *v) if sf == 2),
                "")
        meta = {}
        for key, v in _map_entries(buf, metas).items():
            row = {"name": "", "display": ""}
            for mf, x in _fields(buf, *v):
                if mf == 2:
                    row["name"] = _text(buf, x)
                elif mf == 4:
                    row["display"] = _text(buf, x)
                elif mf == 5:
                    k, val = _stat(buf, x, stat_names)
                    row[k] = val
            meta[key] = row
        lanes = {}
        for v in lines:
            lane, base_ps, events = "", 0, []
            for lf, x in _fields(buf, *v):
                if lf == 2:
                    lane = _text(buf, x)
                elif lf == 3:
                    base_ps = x * 1000
                elif lf == 4:
                    events.append(x)
            if lane not in (OPS_LINE, MODULES_LINE):
                continue
            rows = []
            for ev in events:
                mid = off = dur = 0
                for ef, x in _fields(buf, *ev):
                    if ef == 1:
                        mid = x
                    elif ef == 2:
                        off = x
                    elif ef == 3:
                        dur = x
                rows.append((mid, base_ps + off, dur))
            lanes[lane] = rows
        planes.append({"name": name, "lines": lanes, "meta": meta})
    return planes


# device kernels whose events carry no provenance at all, by a part of
# their operation's name: the grouped product (``lax.ragged_dot``, emitted
# by ``ops/moe.routed_experts`` alone) runs as a custom kernel named
# ``ragged-dot-none[.n]`` with an empty ``tf_op`` (read off a v5e trace,
# PR 29), so it would read ``unscoped`` whatever scope it was traced under.
# The paged-attention kernel (``ops/paged_attention.py``) is a
# ``pallas_call`` named for this table: its custom call keeps its provenance
# in the HLO, and is known by name should an event of it come without; so
# is the index-score kernel of ``ops/index_scores.py`` (``mx_index_scores``,
# the page walk of a selecting layer's decode step, under ``mx.index``), and
# so are the flash kernels of ``ops/attention.py`` (``mx_flash_fwd``,
# ``mx_flash_bwd_dq``, ``mx_flash_bwd_dkv``), whose operations a
# differentiated program names ``jvp_mx_flash_fwd_[.n]`` and
# ``transpose_jvp_mx_flash_bwd_dq__[.n]`` — hence a part, not the start.
# Since PR 39 a TPU lowering of the routed experts' two products is ONE
# ``pallas_call``, ``mx_moe_gmm`` (``ops/grouped_matmul.py``), under
# ``mx.moe_experts`` too; shapes it takes no row tile for keep ragged-dot.
# A power-retention layer's step kernel, ``mx_retention_update``
# (``ops/power_retention.py``), updates a slot-table state in place as
# ``mx_ssm_update`` does, under the same region; its prefill's two
# products with the expansion, ``mx_retention_read`` and
# ``mx_retention_write``, are prefill's scan as ``ops.ssd.chunk_scan`` is.
_KERNEL_REGIONS = (("mx_moe_gmm", "mx.moe_experts"),
                   ("ragged-dot", "mx.moe_experts"),
                   # the same kernel walking a window layer's ring: its
                   # name holds the shorter one, so it comes first
                   ("mx_paged_attention_window", "mx.window_attn"),
                   ("mx_paged_attention", "mx.attn"),
                   ("mx_flash", "mx.attn"),
                   ("mx_ssm_update", "mx.ssm_state"),
                   ("mx_retention_update", "mx.ssm_state"),
                   ("mx_retention_read", "mx.ssm_scan"),
                   ("mx_retention_write", "mx.ssm_scan"),
                   ("mx_index_scores", "mx.index"),
                   ("mx_latent_paged_attention", "mx.latent_attn"))


def region_of(provenance, name=None):
    """The region of a device operation: the INNERMOST ``mx.*`` component
    of its provenance path (``jit(step)/mx.dense/while/body/mx.attn/mul``
    and ``transpose(jvp(mx.attn))/dot_general`` are both ``mx.attn``),
    ``"unscoped"`` where the path has none — but for the few kernels that
    never carry one, which are known by ``name`` (``_KERNEL_REGIONS``).
    The vocabulary is docs/TELEMETRY.md's."""
    found = _REGION.findall(provenance or "")
    if found:
        return found[-1]
    for part, region in _KERNEL_REGIONS:
        if name and part in name:
            return region
    return UNSCOPED


def _self_ps(events):
    """``[(index, self_ps)]`` of ``[(start, dur), ...]``: an event's time
    less that of the events it encloses (a ``while`` and its body)."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][0], -events[i][1]))
    out, stack = [], []          # stack of [index, end, self]
    for i in order:
        start, dur = events[i]
        while stack and start >= stack[-1][1]:
            top = stack.pop()
            out.append((top[0], top[2]))
        if stack:
            stack[-1][2] -= dur
        stack.append([i, start + dur, dur])
    out.extend((top[0], top[2]) for top in stack)
    return out


def parse_xplane(source):
    """Device operations and executable runs of a profiler trace.

    *source* is a serialized xspace (``bytes``, what the ``mx.profiler``
    facade keeps of the trace it stops) or a trace directory, whose newest
    ``*.xplane.pb`` is read.  Returns ``None`` when there is no trace or
    it has no ``/device:TPU:n`` plane (a CPU run), else ``{"ops": [...],
    "runs": [...]}``:

    - one ``runs`` row per event of a device's "XLA Modules" lane —
      ``module`` (``jit_step(123)`` is keyed ``jit_step``), ``device``,
      ``start_us``, ``dur_us`` and ``whole``: True when the device plane
      also holds work before the run's start and after its end.  A run
      the trace's start or end cut is recorded from where the trace began,
      or up to where it ended (read off a v5e trace), so a run at either
      end of the trace cannot be told from a cut one and is not counted;
    - one ``ops`` row per event of the "XLA Ops" lane — ``name``
      (``fusion.9``), ``long_name`` (the HLO text), ``category``,
      ``tf_op`` (the provenance: the jaxpr name stack, ``jax.named_scope``
      included), ``source``, ``start_us``, ``dur_us``, ``self_us`` (the
      duration less the enclosed operations' — a ``while`` does not count
      its body twice), ``flops``, ``bytes``, ``device``, and ``module`` /
      ``run`` (index into ``runs``) of the run it started in, ``None``
      outside any.
    """
    data = read_xplane(source) if isinstance(source, (str, os.PathLike)) \
        else source
    planes = _device_planes(data) if data else []
    if not planes:
        return None
    ops, runs = [], []
    for plane in planes:
        meta = plane["meta"]
        op_events = plane["lines"].get(OPS_LINE, [])
        mod_events = sorted(plane["lines"].get(MODULES_LINE, []),
                            key=lambda e: e[1])
        every = op_events + mod_events
        if not every:
            continue
        t_first = min(e[1] for e in every)
        t_last = max(e[1] + e[2] for e in every)
        base = len(runs)
        for mid, start, dur in mod_events:
            runs.append({
                "module": meta.get(mid, {}).get("name", "?")
                .split("(", 1)[0],
                "device": plane["name"], "start_us": start / 1e6,
                "dur_us": dur / 1e6,
                "whole": start - t_first > _EDGE_PS
                and t_last - (start + dur) > _EDGE_PS})
        starts = [e[1] for e in mod_events]
        selfs = dict(_self_ps([(s, d) for _, s, d in op_events]))
        for i, (mid, start, dur) in enumerate(op_events):
            m = meta.get(mid, {})
            k = bisect.bisect_right(starts, start) - 1
            inside = k >= 0 and start < starts[k] + mod_events[k][2]
            ops.append({
                "name": m.get("display") or _short_op(m.get("name", "?")),
                "long_name": m.get("name", ""),
                "category": m.get("hlo_category", "?"),
                "tf_op": m.get("tf_op", ""),
                "source": m.get("source", ""),
                "start_us": start / 1e6, "dur_us": dur / 1e6,
                "self_us": selfs[i] / 1e6,
                "flops": int(m.get("model_flops", 0) or 0),
                "bytes": int(m.get("raw_bytes_accessed",
                                   m.get("bytes_accessed", 0)) or 0),
                "device": plane["name"],
                "module": runs[base + k]["module"] if inside else None,
                "run": base + k if inside else None,
            })
    return {"ops": ops, "runs": runs}


def _short_op(name):
    """``%fusion.13 = bf16[..] fusion(..)`` -> ``fusion.13``."""
    return name.split(" = ", 1)[0].lstrip("%").strip()


def device_regions(parsed):
    """Per executable of ``parse_xplane``'s result: ``{"jit_step":
    {"runs": whole runs, "run_seconds": their device seconds,
    "regions": {region: self seconds over those runs}}}``.  Operations of
    a run the trace cut are left out, so the regions of an executable sum
    to the busy time of its whole runs."""
    out = {}
    for run in parsed["runs"]:
        if run["whole"]:
            row = out.setdefault(run["module"], {
                "runs": 0, "run_seconds": 0.0, "regions": {}})
            row["runs"] += 1
            row["run_seconds"] += run["dur_us"] / 1e6
    for op in parsed["ops"]:
        if op["run"] is None or not parsed["runs"][op["run"]]["whole"]:
            continue
        regions = out[op["module"]]["regions"]
        key = region_of(op["tf_op"], op["name"])
        regions[key] = regions.get(key, 0.0) + op["self_us"] / 1e6
    return out


def aggregate(records, by="category"):
    """Group ``parse_xplane``'s ``ops`` by ``category`` | ``name`` |
    ``tf_op`` | ``source`` | ``region`` (``region_of`` of ``tf_op``).

    Returns rows sorted by total time desc: dicts with ``key``, ``calls``,
    ``dur_us`` (SELF time, so an enclosing ``while`` does not count its
    body twice and the rows sum to the busy time), ``flops``, ``bytes``,
    ``tflops`` (achieved), ``gbps`` (achieved HBM bandwidth), ``pct`` of
    total device time.
    """
    groups = defaultdict(lambda: [0, 0.0, 0, 0])
    for r in records:
        k = region_of(r["tf_op"], r["name"]) if by == "region" else \
            r[by] or "<none>"
        g = groups[k]
        g[0] += 1
        g[1] += r["self_us"]
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
    """Trace ``fn(*args)`` on device and return per-op records
    (``parse_xplane``'s ``ops``; empty without a device plane).

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
    records = (parse_xplane(trace_dir) or {"ops": []})["ops"]
    for r in records:
        r["dur_us"] /= iters
        r["self_us"] /= iters
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
