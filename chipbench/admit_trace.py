"""What the admission metrics of every serve cell share: the admission
executables' device seconds by region GROUP, and their device time set
against the real prompt tokens the server counted.

The admission executables are every ``jit_admit*`` (a bucketed wave),
``jit_hit*`` (a prefix hit) and ``jit_chunk*`` (a chunk of a prompt) run
that lies whole in the traced stretch (``profiler.device_regions()``).
Every region of the vocabulary (docs/TELEMETRY.md's table and
``profiler_xla._KERNEL_REGIONS``) falls in exactly one group, so the groups
of a cell sum to 100.  ``granite_trace.admit_region_pct`` leaves out
``jit_hit``; this module is its successor for every cell.

Every function returns ``None`` where there is nothing to read (a CPU run,
an untraced run, a program without the counter): the metric is then left
out, never 0.
"""

GROUPS = {
    # everything that reads or writes cached context
    "attention": ("mx.attn", "mx.window_attn", "mx.latent_attn",
                  "mx.latent_gather", "mx.index", "mx.paged_view",
                  "mx.kv_write", "mx.latent_write", "mx.page_write"),
    "experts": ("mx.moe_experts", "mx.moe_route"),
    # ``mx.optimizer`` (a train step's per-leaf update) is in no admission
    # executable; it is here so that the vocabulary has no region left over
    "dense": ("mx.dense", "mx.head", "mx.moe_shared", "mx.qk_norm_rope",
              "mx.optimizer"),
    "state": ("mx.ssm_scan", "mx.ssm_state", "mx.ssm_conv", "mx.ssm_gate"),
    "unscoped": ("unscoped",),
}
GROUP_OF = {r: g for g, regions in GROUPS.items() for r in regions}

# an admission executable's module name -> the server's counter of its
# dispatches (``DecodeServer.counters``)
DISPATCHES = {"jit_admit": "admit_dispatches", "jit_hit": "hit_dispatches",
              "jit_chunk": "chunk_dispatches"}


def _admission_rows():
    """``{module: device_regions row}`` of the admission executables with a
    whole run in the traced stretch, or ``None``."""
    try:
        from mxnet_tpu import profiler
        table = profiler.device_regions()
    except Exception:       # no such reader in this program: nothing read
        return None
    rows = {name: row for name, row in (table or {}).items()
            if name.startswith(tuple(DISPATCHES)) and row["runs"]}
    return rows or None


def group_pct(run, group):
    """Share (%) of the admission executables' device time under the
    regions of ``group`` (a region newer than ``GROUPS`` reads as
    ``unscoped`` here; a test holds ``GROUPS`` to the vocabulary)."""
    rows = _admission_rows()
    if rows is None:
        return None
    total = part = 0.0
    for row in rows.values():
        for region, s in row["regions"].items():
            total += s
            if GROUP_OF.get(region, "unscoped") == group:
                part += s
    return 100.0 * part / total if total else None


def _dispatch(run):
    """The server's counters differenced over the window, or ``None`` where
    the run was not traced on a chip (the metric is a traced run's)."""
    if not run.get("trace"):
        return None
    return run.get("counters", {}).get("dispatch")


def pad_token_pct(run):
    """Share (%) of the token positions admission computed in the window
    that were padding to a bucket: ``admit_rows`` less ``admit_tokens``
    over ``admit_rows``."""
    d = _dispatch(run)
    if not d or not d.get("admit_rows"):
        return None
    return 100.0 * (d["admit_rows"] - d["admit_tokens"]) / d["admit_rows"]


def device_us_per_token(run):
    """Admission's device microseconds per real prompt token in the window:
    over the admission executables, the mean device time of a whole run in
    the traced stretch times that executable's dispatches in the window,
    summed, over ``admit_tokens``.  ``None`` where an executable the window
    dispatched has no whole run to read."""
    d, rows = _dispatch(run), _admission_rows()
    if not d or not d.get("admit_tokens") or rows is None:
        return None
    seconds = 0.0
    for prefix, key in DISPATCHES.items():
        n = d.get(key, 0)
        if not n:
            continue
        mine = [row for name, row in rows.items() if name.startswith(prefix)]
        runs = sum(row["runs"] for row in mine)
        if not runs:
            return None
        seconds += n * sum(row["run_seconds"] for row in mine) / runs
    return 1e6 * seconds / d["admit_tokens"]


def window_compile_ms(run):
    """Wall milliseconds this server spent compiling its pool executables
    inside the window (the server's own ``compile_ms`` counter)."""
    return (_dispatch(run) or {}).get("compile_ms")
