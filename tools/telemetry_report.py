#!/usr/bin/env python3
"""Summarize a telemetry JSONL (``MXNET_TELEMETRY_JSONL`` /
``mx.telemetry.add_jsonl_sink``) into the BASELINE.md-style tables, and
re-check the dispatch/retrace invariants from the recorded stream alone.

    python tools/telemetry_report.py run.jsonl
    python tools/telemetry_report.py run.jsonl --check-serve
    python tools/telemetry_report.py run.jsonl --json

Sections (each skipped when the file has no events of that kind):

- **compile events** — per site: count, retraces, total/max wall time,
  HLO op count range (when recorded under ``MXNET_TELEMETRY_HLO=1``).
- **serve requests** — per server: request count by retirement reason,
  token totals, p50/p99 TTFT and queue wait, admission wave stats.
- **serve stats** — the per-server close() snapshot: steps, dispatch
  counters, occupancy.
- **failure causes** — the fault-tolerance events (ISSUE 13):
  ``worker_dead`` / ``deadline_exceeded`` / ``request_cancelled`` /
  ``fault_injected`` / ``watchdog_fired`` / ``kvstore_error`` /
  ``checkpoint_corrupt``, counted per kind with a
  per-site/server/reason breakdown.
- **checkpoints** — ``checkpoint_saved`` / ``checkpoint_restored``
  rollup per directory: saves, bytes, snapshot/write seconds (the
  async-save stall truth), restores and corrupt skips.
- **restarts** — ``pod_restart`` events from the
  ``tools/launch.py --restarts`` supervisor: per (rank, why) counts,
  attempts, backoff (ISSUE 15 recovery loop).
- **bench rows** — ``kind=bench`` events (serve_bench / step_profile
  measured rows) passed through as a table.

``--check-serve`` re-derives the test-pinned serving invariants from
the stream (no process state needed):

1. compile count per server ≤ the pinned ladder product
   (``len(admit_sizes) × len(prefill_buckets) × len(pool_sizes)`` from
   its ``serve_config`` event) and ≤ 1 step compile per pool size;
2. zero RETRACES: every serve compile event is a distinct program
   (first-trace), never a second signature of one;
3. one step-executable dispatch per decode step
   (``serve_stats.counters.step_dispatches == serve_stats.steps``);
4. pool bytes ≤ the configured HBM budget across the whole recording:
   for servers whose ``serve_config`` carries a non-null
   ``hbm_budget``, every ``serve.kv_pool`` accountant sample
   (``device_memory`` events) and the close-time
   ``serve_stats.pool_bytes`` must stay within it;
5. pages ≤ pool capacity (ISSUE 16): any ``serve_stats`` carrying the
   paged-pool fields must report ``pages_in_use <= pages_total``
   (streams recorded before paging simply lack the fields and skip
   the check);
6. speculative-decoding ledger (ISSUE 17): verify compiles per server
   ≤ ``len(spec_sizes) × len(pool_sizes)`` and retrace-free like the
   other serve sites, and every proposed draft token resolves —
   ``accepted + rejected == proposed`` re-derived both from the
   per-dispatch ``serve_spec`` events and from the close-time
   ``serve_stats`` draft counters (pre-speculation recordings lack
   the fields and skip the check).

Exit status 1 when a check fails (the tier-1 serve smoke shells this
against the JSONL ``benchmark/serve_bench.py --smoke`` records).
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from collections import defaultdict


def load_pod(path):
    """Merge a pod's telemetry: ``path`` is either one merged JSONL
    (events already rank-tagged by ``mxnet_tpu.telemetry.emit``) or a
    directory of per-rank recordings (``tools/launch.py
    --telemetry-dir``: ``rank<r>.jsonl``).  Returns the union sorted
    by timestamp — the rank field on each event, not the source file,
    is the attribution."""
    if os.path.isdir(path):
        files = sorted(glob.glob(os.path.join(path, "*.jsonl")))
        if not files:
            print(f"# {path}: no *.jsonl recordings in directory",
                  file=sys.stderr)
        events = []
        for f in files:
            events.extend(load(f))
        events.sort(key=lambda e: e.get("ts", 0.0))
        return events
    return load(path)


def load(path):
    events = []
    with open(path, "r", encoding="utf-8") as fh:
        for i, line in enumerate(fh, start=1):
            line = line.strip()
            if not line:
                continue
            try:
                ev = json.loads(line)
            except json.JSONDecodeError as e:
                print(f"# {path}:{i}: skipping unparseable line ({e})",
                      file=sys.stderr)
                continue
            if isinstance(ev, dict):
                events.append(ev)
    return events


def _pct(xs, q):
    xs = sorted(xs)
    if not xs:
        return None
    return xs[min(len(xs) - 1, int(q * len(xs)))]


def _ms(v):
    """Render an already-milliseconds value (None = no samples)."""
    return "-" if v is None else f"{v:.3f}"


def _to_ms(v):
    return None if v is None else round(v * 1e3, 3)


# --------------------------------------------------------------------- #
# sections
# --------------------------------------------------------------------- #

def compile_summary(events):
    """Per-site compile rows: count/retraces/wall/hlo."""
    rows = []
    by_site = defaultdict(list)
    for e in events:
        if e.get("kind") == "compile":
            by_site[e.get("site", "?")].append(e)
    for site in sorted(by_site):
        evs = by_site[site]
        walls = [e.get("wall_s", 0.0) for e in evs]
        hlo = [e["hlo_ops"] for e in evs if "hlo_ops" in e]
        rows.append({
            "site": site,
            "compiles": len(evs),
            "retraces": sum(1 for e in evs if e.get("retrace")),
            "wall_s_total": round(sum(walls), 3),
            "wall_s_max": round(max(walls), 3) if walls else 0.0,
            "hlo_ops_min": min(hlo) if hlo else None,
            "hlo_ops_max": max(hlo) if hlo else None,
        })
    return rows


def serve_summary(events):
    """Per-server request-span rows."""
    by_srv = defaultdict(list)
    for e in events:
        if e.get("kind") == "serve_request":
            by_srv[e.get("server", "?")].append(e)
    rows = []
    for srv in sorted(by_srv):
        evs = by_srv[srv]
        reasons = defaultdict(int)
        for e in evs:
            reasons[e.get("reason", "?")] += 1
        ttfts = [e["ttft_s"] for e in evs if e.get("ttft_s") is not None]
        waits = [e["queue_wait_s"] for e in evs
                 if e.get("queue_wait_s") is not None]
        waves = [e["wave"] for e in evs if e.get("wave") is not None]
        rows.append({
            "server": srv,
            "requests": len(evs),
            "reasons": dict(sorted(reasons.items())),
            "tokens": sum(e.get("tokens", 0) for e in evs),
            "p50_ttft_ms": _to_ms(_pct(ttfts, 0.5)),
            "p99_ttft_ms": _to_ms(_pct(ttfts, 0.99)),
            "p50_queue_wait_ms": _to_ms(_pct(waits, 0.5)),
            "p99_queue_wait_ms": _to_ms(_pct(waits, 0.99)),
            "mean_admit_wave": (round(sum(waves) / len(waves), 2)
                                if waves else None),
        })
    return rows


FAILURE_KINDS = ("worker_dead", "deadline_exceeded", "request_cancelled",
                 "fault_injected", "watchdog_fired", "kvstore_error",
                 "checkpoint_corrupt")


def failure_summary(events):
    """Aggregate the failure-cause events (ISSUE 13) per kind: count +
    the per-site/server/reason breakdown, so one recording answers
    "what failed, where, how often" next to the perf tables."""
    rows = []
    by_kind = defaultdict(list)
    for e in events:
        if e.get("kind") in FAILURE_KINDS:
            by_kind[e["kind"]].append(e)
    for kind in FAILURE_KINDS:
        evs = by_kind.get(kind)
        if not evs:
            continue
        detail = defaultdict(int)
        for e in evs:
            where = e.get("site") or e.get("server") or \
                (f"rank {e['rank']}" if "rank" in e else None) or \
                e.get("dir") or "?"
            what = e.get("fault_kind") or e.get("reason") or \
                e.get("why") or e.get("command") or e.get("error")
            detail[f"{where}" + (f": {what}" if what else "")] += 1
        rows.append({"kind": kind, "count": len(evs),
                     "detail": dict(sorted(detail.items()))})
    return rows


def checkpoint_summary(events):
    """Per-directory checkpoint rollup: saves (bytes + the measured
    snapshot/write stalls — the async-save acceptance truth), restores,
    and corrupt skips."""
    by_dir = defaultdict(lambda: {"saves": 0, "restores": 0,
                                  "corrupt": 0, "bytes": 0,
                                  "snapshot_s": [], "write_s": [],
                                  "last_step": None})
    saw = False
    for e in events:
        kind = e.get("kind")
        if kind not in ("checkpoint_saved", "checkpoint_restored",
                        "checkpoint_corrupt"):
            continue
        saw = True
        d = by_dir[e.get("dir", "?")]
        if kind == "checkpoint_saved":
            d["saves"] += 1
            d["bytes"] += e.get("bytes", 0)
            if e.get("snapshot_s") is not None:
                d["snapshot_s"].append(e["snapshot_s"])
            if e.get("write_s") is not None:
                d["write_s"].append(e["write_s"])
            d["last_step"] = e.get("step")
        elif kind == "checkpoint_restored":
            d["restores"] += 1
        else:
            d["corrupt"] += 1
    if not saw:
        return []
    rows = []
    for path in sorted(by_dir):
        d = by_dir[path]
        snaps, writes = d["snapshot_s"], d["write_s"]
        rows.append({
            "dir": path, "saves": d["saves"], "restores": d["restores"],
            "corrupt": d["corrupt"], "bytes": d["bytes"],
            "last_step": d["last_step"],
            "snapshot_ms_mean": _to_ms(sum(snaps) / len(snaps))
            if snaps else None,
            "snapshot_ms_max": _to_ms(max(snaps)) if snaps else None,
            "write_ms_mean": _to_ms(sum(writes) / len(writes))
            if writes else None,
        })
    return rows


def restart_summary(events):
    """``pod_restart`` rows from the launch supervisor: one recording
    answers how often the pod restarted, for which failures, and how
    much backoff it paid."""
    evs = [e for e in events if e.get("kind") == "pod_restart"]
    if not evs:
        return []
    detail = defaultdict(int)
    for e in evs:
        detail[f"rank {e.get('rank', '?')}: {e.get('why', '?')}"] += 1
    return [{"restarts": len(evs),
             "backoff_s_total": round(sum(e.get("backoff_s", 0.0)
                                          for e in evs), 3),
             "max_attempt": max(e.get("attempt", 1) for e in evs),
             "detail": dict(sorted(detail.items()))}]


def _parse_bytes(raw):
    """``14G``-style byte sizes for ``--hbm-budget``."""
    raw = str(raw).strip()
    mult = 1
    suffixes = {"K": 1 << 10, "M": 1 << 20, "G": 1 << 30, "T": 1 << 40}
    if raw and raw[-1].upper() in suffixes:
        mult = suffixes[raw[-1].upper()]
        raw = raw[:-1]
    return int(float(raw) * mult)


def pod_summary(events, hbm_budget=None):
    """Per-rank rollup of a merged pod recording — the two operator
    questions first: WHICH HOST RETRACED (rank-tagged ``compile``
    events with ``retrace``) and WHICH HOST IS OVER ITS HBM BUDGET
    (peak concurrent total of the rank's ``device_memory`` /
    ``device_bytes`` accountant gauges vs ``hbm_budget``).  Events
    without a rank tag (the launch supervisor's own ``worker_dead`` /
    ``pod_restart``) roll up under rank ``"pod"``."""
    by_rank = defaultdict(lambda: {
        "events": 0, "compiles": 0, "retraces": 0,
        "retrace_sites": set(), "compile_wall_s": 0.0,
        "peak_device_bytes": 0, "_gauges": {}, "faults": 0,
        "dist_inits": 0, "last_step": None, "saves": 0})
    for e in events:
        rank = e.get("rank", "pod")
        d = by_rank[rank]
        d["events"] += 1
        kind = e.get("kind")
        if kind == "compile":
            d["compiles"] += 1
            d["compile_wall_s"] += e.get("wall_s", 0.0)
            if e.get("retrace"):
                d["retraces"] += 1
                d["retrace_sites"].add(str(e.get("site", "?")))
        elif kind == "device_memory":
            # replay the accountant gauges in ts order: the rank's HBM
            # truth is the peak CONCURRENT total, not the max sample
            key = (e.get("subsystem", "?"), e.get("key", "?"))
            d["_gauges"][key] = e.get("bytes", 0)
            d["peak_device_bytes"] = max(
                d["peak_device_bytes"], sum(d["_gauges"].values()))
        elif kind == "fault_injected":
            d["faults"] += 1
        elif kind == "dist_init":
            d["dist_inits"] += 1
        elif kind == "checkpoint_saved":
            d["saves"] += 1
            d["last_step"] = e.get("step")
    rows = []
    for rank in sorted(by_rank, key=lambda r: (isinstance(r, str), r)):
        d = by_rank[rank]
        row = {"rank": rank, "events": d["events"],
               "compiles": d["compiles"], "retraces": d["retraces"],
               "retrace_sites": sorted(d["retrace_sites"]),
               "compile_wall_s": round(d["compile_wall_s"], 3),
               "peak_device_bytes": d["peak_device_bytes"],
               "faults": d["faults"], "dist_inits": d["dist_inits"],
               "saves": d["saves"], "last_step": d["last_step"]}
        if hbm_budget is not None and rank != "pod":
            row["over_hbm_budget"] = \
                d["peak_device_bytes"] > hbm_budget
        rows.append(row)
    return rows


def render_pod(events, hbm_budget=None):
    rows = pod_summary(events, hbm_budget)
    lines = ["pod (per rank)",
             f"  {'rank':<6}{'events':>8}{'compiles':>9}"
             f"{'retraces':>9}{'wall(s)':>9}{'peak bytes':>12}"
             f"{'saves':>7}{'last step':>10}"]
    for r in rows:
        lines.append(
            f"  {str(r['rank']):<6}{r['events']:>8}{r['compiles']:>9}"
            f"{r['retraces']:>9}{r['compile_wall_s']:>9.2f}"
            f"{r['peak_device_bytes']:>12}{r['saves']:>7}"
            f"{str(r['last_step'] if r['last_step'] is not None else '-'):>10}")
    retraced = [r for r in rows if r["retraces"]]
    if retraced:
        lines.append("  retraced hosts: " + ", ".join(
            f"rank {r['rank']} ({', '.join(r['retrace_sites'])})"
            for r in retraced))
    else:
        lines.append("  retraced hosts: none")
    if hbm_budget is not None:
        over = [r for r in rows if r.get("over_hbm_budget")]
        if over:
            lines.append(
                f"  over hbm budget ({hbm_budget} bytes): " + ", ".join(
                    f"rank {r['rank']} "
                    f"(peak {r['peak_device_bytes']})" for r in over))
        else:
            lines.append(f"  over hbm budget ({hbm_budget} bytes): "
                         "none")
    return "\n".join(lines)


def _serve_schema():
    """Load ``mxnet_tpu/serve/schema.py`` standalone, by file path —
    the operand/slot-state declarations import nothing, so this tool
    can price slot state EXACTLY without importing the package (which
    would pull jax).  Returns None when the tree isn't alongside the
    tool (e.g. the report script copied into a recording dir)."""
    import importlib.util

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        os.pardir, "mxnet_tpu", "serve", "schema.py")
    if not os.path.exists(path):
        return None
    try:
        spec = importlib.util.spec_from_file_location(
            "_serve_operand_schema", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod
    except Exception:
        return None


def check_serve(events):
    """Re-derive the serving invariants from the stream; returns a list
    of failure strings (empty = all good)."""
    failures = []
    configs = {e["server"]: e for e in events
               if e.get("kind") == "serve_config" and "server" in e}
    compiles = defaultdict(list)
    for e in events:
        if e.get("kind") == "compile" and \
                e.get("site") in ("serve.step", "serve.admit",
                                  "serve.verify"):
            compiles[e.get("server")].append(e)
    stats = [e for e in events if e.get("kind") == "serve_stats"]

    for srv, cfg in sorted(configs.items()):
        if cfg.get("sync_mode"):
            continue
        evs = compiles.get(srv, [])
        admits = [e for e in evs if e["site"] == "serve.admit"]
        steps = [e for e in evs if e["site"] == "serve.step"]
        verifies = [e for e in evs if e["site"] == "serve.verify"]
        ladder = (len(cfg.get("admit_sizes", [])) *
                  len(cfg.get("prefill_buckets", [])) *
                  len(cfg.get("pool_sizes", [])) or None)
        if ladder is not None and len(admits) > ladder:
            failures.append(
                f"{srv}: {len(admits)} admit compiles exceed the "
                f"pinned ladder product {ladder}")
        if len(steps) > len(cfg.get("pool_sizes", [1])):
            failures.append(
                f"{srv}: {len(steps)} step compiles for "
                f"{len(cfg['pool_sizes'])} pinned pool sizes")
        # verify programs are pinned to the spec k ladder x pool sizes
        # (accept/reject churn is operand values, never shapes) —
        # pre-speculation recordings lack spec_sizes and skip this
        spec_ladder = (len(cfg.get("spec_sizes") or []) *
                       len(cfg.get("pool_sizes", [])))
        if verifies and spec_ladder and len(verifies) > spec_ladder:
            failures.append(
                f"{srv}: {len(verifies)} verify compiles exceed the "
                f"pinned k ladder product {spec_ladder}")
        # distinct-program check: a repeated (pool, A, P, k) or a
        # cache_size > 1 event is a RETRACE of an existing program
        seen = set()
        for e in admits + steps + verifies:
            key = (e["site"], e.get("pool"), e.get("a_bucket"),
                   e.get("p_bucket"), e.get("k_bucket"))
            if key in seen or e.get("retrace"):
                failures.append(f"{srv}: retrace of {key}")
            seen.add(key)

    # speculative-decoding ledger (ISSUE 17): every proposed draft
    # token resolves to exactly one of accepted/rejected — re-derived
    # BOTH from the per-dispatch serve_spec events and from the
    # close-time serve_stats counters
    spec_evs = defaultdict(lambda: {"proposed": 0, "accepted": 0,
                                    "rejected": 0})
    for e in events:
        if e.get("kind") == "serve_spec":
            led = spec_evs[e.get("server", "?")]
            for f in ("proposed", "accepted", "rejected"):
                led[f] += e.get(f, 0)
    for srv, led in sorted(spec_evs.items()):
        if led["accepted"] + led["rejected"] != led["proposed"]:
            failures.append(
                f"{srv}: serve_spec events: accepted "
                f"{led['accepted']} + rejected {led['rejected']} != "
                f"proposed {led['proposed']}")
    for st in stats:
        counters = st.get("counters", {})
        prop = counters.get("draft_proposed")
        if prop is None:
            continue   # pre-speculation recording
        acc = counters.get("draft_accepted", 0)
        rej = counters.get("draft_rejected", 0)
        if acc + rej != prop:
            failures.append(
                f"{st.get('server', '?')}: serve_stats counters: "
                f"draft_accepted {acc} + draft_rejected {rej} != "
                f"draft_proposed {prop}")

    for st in stats:
        counters = st.get("counters", {})
        n_steps = st.get("steps")
        disp = counters.get("step_dispatches")
        if n_steps is not None and disp is not None and disp != n_steps:
            failures.append(
                f"{st.get('server', '?')}: {disp} step dispatches for "
                f"{n_steps} decode steps (expected exactly 1/step)")

    # pool bytes vs the configured HBM budget, across the recording:
    # the accountant timeline (device_memory events keyed by the server
    # label) plus the close-time serve_stats snapshot
    pool_peak = defaultdict(int)
    for e in events:
        if e.get("kind") == "device_memory" and \
                e.get("subsystem") == "serve.kv_pool":
            srv = e.get("key", "?")
            pool_peak[srv] = max(pool_peak[srv], e.get("bytes", 0))
    for srv, cfg in sorted(configs.items()):
        budget = cfg.get("hbm_budget")
        if budget is None:
            continue
        peak = pool_peak.get(srv, 0)
        if peak > budget:
            failures.append(
                f"{srv}: pool bytes {peak} exceed the configured "
                f"hbm_budget {budget}")
    for st in stats:
        budget = configs.get(st.get("server"), {}).get("hbm_budget")
        pb = st.get("pool_bytes")
        if budget is not None and pb is not None and pb > budget:
            failures.append(
                f"{st.get('server', '?')}: serve_stats pool_bytes "
                f"{pb} exceed the configured hbm_budget {budget}")

    # paged-pool capacity (ISSUE 16): pages in use can never exceed
    # the pool's page count — pre-paging recordings lack the fields
    # and skip the check
    for st in stats:
        total = st.get("pages_total")
        used = st.get("pages_in_use")
        if total is not None and used is not None and used > total:
            failures.append(
                f"{st.get('server', '?')}: {used} pages in use exceed "
                f"the pool capacity {total}")

    # dtype-aware page pricing (ISSUE 18): the reported pool bytes must
    # equal pages_total * the PRICED page size (codes + scales under
    # kv_dtype=int8) plus the per-slot scalar state — an int8 pool
    # billed at f32 page bytes (or vice versa) fails here.  Pre-int8
    # recordings lack page_bytes and skip the check; the retrace key
    # above is deliberately dtype-free (kv_dtype never shapes a trace
    # signature beyond the operand dtypes it already keys).
    schema = _serve_schema()
    for st in stats:
        pb = st.get("pool_bytes")
        page_bytes = st.get("page_bytes")
        total = st.get("pages_total")
        slots = st.get("num_slots")
        if None in (pb, page_bytes, total, slots) or pb == 0:
            continue   # sync mode / torn-down pool: nothing resident
        # a model with windowed layers keeps a second pool, priced by
        # its own page size (absent from older recordings: 0)
        # and one with state under the slot table its bytes a slot
        priced = total * page_bytes + (st.get("window_pages_total") or 0) \
            * (st.get("window_page_bytes") or 0) \
            + slots * (st.get("state_bytes_per_slot") or 0)
        if schema is not None:
            # the slot-state layout declaration is on hand: the scalar
            # state must price to EXACTLY slots * slot_state_bytes()
            # (the same figure pool_state_bytes charges) — any gap is
            # a column added to one side of the ledger only
            expect = slots * schema.slot_state_bytes()
            ok = pb - priced == expect
        else:
            # standalone fallback: the per-slot scalars are a few
            # dozen bytes; 64 bounds them without re-pinning a layout
            # this copy of the tool can't see
            ok = 0 <= pb - priced < slots * 64
        if not ok:
            failures.append(
                f"{st.get('server', '?')}: serve_stats pool_bytes {pb} "
                f"inconsistent with {total} pages * {page_bytes} "
                f"priced page bytes (kv_dtype="
                f"{st.get('kv_dtype', 'native')})")
    if not configs and not stats:
        failures.append("no serve_config/serve_stats events in the "
                        "stream — nothing to check")
    return failures


# --------------------------------------------------------------------- #
# rendering
# --------------------------------------------------------------------- #

def render(events):
    lines = []
    comp = compile_summary(events)
    if comp:
        lines.append("compile events")
        lines.append(f"  {'site':<24}{'compiles':>9}{'retraces':>9}"
                     f"{'wall(s)':>9}{'max(s)':>8}  hlo ops")
        for r in comp:
            hlo = "-" if r["hlo_ops_min"] is None else (
                f"{r['hlo_ops_min']}"
                if r["hlo_ops_min"] == r["hlo_ops_max"]
                else f"{r['hlo_ops_min']}..{r['hlo_ops_max']}")
            lines.append(
                f"  {r['site']:<24}{r['compiles']:>9}{r['retraces']:>9}"
                f"{r['wall_s_total']:>9.2f}{r['wall_s_max']:>8.2f}  "
                f"{hlo}")
    srv = serve_summary(events)
    if srv:
        lines.append("")
        lines.append("serve requests")
        lines.append(f"  {'server':<8}{'requests':>9}{'tokens':>8}"
                     f"{'p50 ttft(ms)':>13}{'p99 ttft(ms)':>13}"
                     f"{'p50 wait(ms)':>13}{'wave':>6}  reasons")
        for r in srv:
            wave = "-" if r["mean_admit_wave"] is None \
                else f"{r['mean_admit_wave']:.1f}"
            lines.append(
                f"  {r['server']:<8}{r['requests']:>9}{r['tokens']:>8}"
                f"{_ms(r['p50_ttft_ms']):>13}{_ms(r['p99_ttft_ms']):>13}"
                f"{_ms(r['p50_queue_wait_ms']):>13}{wave:>6}  "
                f"{r['reasons']}")
    stats = [e for e in events if e.get("kind") == "serve_stats"]
    if stats:
        lines.append("")
        lines.append("serve stats (at close)")
        for st in stats:
            c = st.get("counters", {})
            lines.append(
                f"  {st.get('server', '?'):<8}steps={st.get('steps')} "
                f"occupancy={st.get('occupancy', 0):.3f} "
                f"step_dispatches={c.get('step_dispatches')} "
                f"admit_dispatches={c.get('admit_dispatches')} "
                f"pool_grows={c.get('pool_grows')} "
                f"sync_requests={c.get('sync_requests')}")
    fails = failure_summary(events)
    if fails:
        lines.append("")
        lines.append("failure causes")
        for r in fails:
            lines.append(f"  {r['kind']:<20}{r['count']:>6}")
            for where, n in r["detail"].items():
                lines.append(f"    {n:>4}x {where}")
    ckpts = checkpoint_summary(events)
    if ckpts:
        lines.append("")
        lines.append("checkpoints")
        for r in ckpts:
            lines.append(
                f"  {r['dir']}: {r['saves']} saves "
                f"({r['bytes']} bytes, last step {r['last_step']}), "
                f"{r['restores']} restores, {r['corrupt']} corrupt; "
                f"snapshot stall mean {_ms(r['snapshot_ms_mean'])} ms "
                f"max {_ms(r['snapshot_ms_max'])} ms, "
                f"write mean {_ms(r['write_ms_mean'])} ms")
    restarts = restart_summary(events)
    if restarts:
        r = restarts[0]
        lines.append("")
        lines.append("pod restarts")
        lines.append(f"  {r['restarts']} restarts, "
                     f"{r['backoff_s_total']}s total backoff, "
                     f"deepest attempt {r['max_attempt']}")
        for where, n in r["detail"].items():
            lines.append(f"    {n:>4}x {where}")
    bench = [e for e in events if e.get("kind") == "bench"]
    if bench:
        lines.append("")
        lines.append("bench rows")
        for e in bench:
            row = {k: v for k, v in e.items() if k not in ("ts", "kind")}
            lines.append("  " + json.dumps(row, sort_keys=True))
    markers = [e for e in events if e.get("kind") in ("marker", "phase")]
    if markers:
        lines.append("")
        lines.append("markers/phases: " + ", ".join(
            str(e.get("name", "?")) for e in markers))
    if not lines:
        lines.append("(no recognized telemetry events)")
    return "\n".join(lines)


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="Summarize a telemetry JSONL and re-check the "
                    "serving dispatch/retrace invariants from it.")
    ap.add_argument("path", help="JSONL file recorded via "
                                 "MXNET_TELEMETRY_JSONL or "
                                 "mx.telemetry.add_jsonl_sink; with "
                                 "--pod, alternatively a directory of "
                                 "per-rank recordings "
                                 "(tools/launch.py --telemetry-dir)")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable summary instead of tables")
    ap.add_argument("--pod", action="store_true",
                    help="merge per-rank recordings and add the "
                         "per-rank rollup: which host retraced, which "
                         "host is over its HBM budget, per-rank "
                         "compile/memory/checkpoint truth")
    ap.add_argument("--hbm-budget", default=None,
                    help="per-rank device-memory budget for the --pod "
                         "over-budget verdict (bytes; K/M/G/T "
                         "suffixes accepted)")
    ap.add_argument("--check-serve", action="store_true",
                    help="verify serving invariants (ladder-bounded "
                         "compiles, zero retraces, 1 dispatch/step); "
                         "exit 1 on violation")
    args = ap.parse_args(argv)

    budget = _parse_bytes(args.hbm_budget) \
        if args.hbm_budget is not None else None
    events = load_pod(args.path) if args.pod else load(args.path)
    if args.json:
        out = {
            "events": len(events),
            "compile": compile_summary(events),
            "serve": serve_summary(events),
            "failures": failure_summary(events),
            "checkpoints": checkpoint_summary(events),
            "restarts": restart_summary(events),
            "bench": [e for e in events if e.get("kind") == "bench"],
        }
        if args.pod:
            out["pod"] = pod_summary(events, budget)
        print(json.dumps(out, indent=2, sort_keys=True))
    else:
        print(f"# {args.path}: {len(events)} events")
        if args.pod:
            print(render_pod(events, budget))
            print()
        print(render(events))

    if args.check_serve:
        failures = check_serve(events)
        if failures:
            for f in failures:
                print(f"CHECK FAILED: {f}", file=sys.stderr)
            return 1
        print("serve checks OK: ladder-bounded compiles, zero "
              "retraces, 1 dispatch/step, pool bytes within budget, "
              "draft ledger balanced")
    return 0


if __name__ == "__main__":
    sys.exit(main())
