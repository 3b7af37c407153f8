"""What the dots3 cell's per-layer metrics share: the step executable's
device seconds by region for ONE run, and the step's work as the window's
counters give it.  Every function returns ``None`` where there is nothing
to read (a CPU run, an untraced run, a program without the region or the
counter): the metric is then left out, never 0."""
from chipbench import program_trace, shapes_dots3


def region_seconds(run, *regions):
    """Device self seconds one run of the step executable spends under
    ``regions``."""
    try:
        from mxnet_tpu import profiler
        table = profiler.device_regions()
    except Exception:
        return None
    row = (table or {}).get(
        run["config"].get("executables", {}).get("step"))
    if not row or not row["runs"]:
        return None
    s = sum(row["regions"].get(r, 0.0) for r in regions)
    return s / row["runs"] if s > 0 else None


def region_pct(run, *regions):
    """``program_trace.region_pct``, ``None`` where the share is 0: the
    region is then not in this program."""
    pct = program_trace.region_pct(run, *regions)
    return pct if pct else None


def step_work(run):
    """One mean step of the window from the server's counters: ``slots``
    stepping, (layer, expert) cells ``touched``, (token, held expert) pairs
    ``expert_tokens``, ``live_tokens`` cached in front of the queries,
    ``selected`` (query, key) pairs of the sparse attention and
    ``window_pairs`` of the sliding one."""
    st, c = run.get("server_stats") or {}, run["counters"]
    steps = c.get("steps")
    if not steps or st.get("moe_experts_touched_share") is None \
            or st.get("selected_keys_per_query") is None:
        return None
    cfg = run["geometry"]
    routed = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    cells = routed * cfg["held_experts"][1]
    slots = c["occupied_lane_steps"] / steps
    context = c.get("context_tokens_mean") or 0.0
    return {"slots": slots,
            "touched": st["moe_experts_touched_share"] * cells,
            "expert_tokens": st["moe_tokens_per_expert_step"] * cells,
            "live_tokens": slots * context,
            "selected": slots * st["selected_keys_per_query"],
            "window_pairs": slots * min(cfg["sliding_window_size"],
                                        context)}


def roofline_pct(run, least, *regions):
    """The least seconds one step needs at the chip's peaks for ``least(cfg,
    work)``'s ``(bytes, flops)`` (a ``shapes_dots3`` function of the
    configuration and ``step_work``), over the device seconds one step spends
    under ``regions``."""
    work, peaks = step_work(run), run.get("peaks")
    spent = region_seconds(run, *regions)
    if work is None or not peaks or spent is None:
        return None
    floor = shapes_dots3.floor_seconds(least(run["geometry"], work), peaks)
    return 100.0 * floor / spent
