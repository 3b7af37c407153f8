"""What the trinity cell's per-layer metrics share: the step's work as the
window's clock readings and the server's counters give it, and the accepted
reader of another cell where this cell reads the same keys the same way.  The
step executable's own regions are ``dots3_trace``'s readers (nothing in them
is particular to a model).  Every function returns ``None`` where there is
nothing to read (a CPU run, an untraced run, a program without the region or
the counter): the metric is then left out, never 0."""
import os

import numpy as np

from chipbench import dots3_trace, harness, shapes_trinity


def reader_of(metric):
    """``metrics/<metric>.py``'s ``read``, for a reader of this cell that is
    that one under this cell's name."""
    return harness.load_by_path(
        os.path.join(os.path.dirname(os.path.abspath(__file__)), "metrics",
                     metric + ".py"), "m_" + metric.replace(".", "_")).read


def step_work(run):
    """One mean step of the window: ``slots`` stepping; (layer, expert)
    cells ``touched`` and (token, held expert) pairs ``expert_tokens`` from
    the step's own counters; ``live_tokens`` cached in front of the queries
    and ``window_pairs`` of them inside each query's window (over every
    token a step emitted in the window — a stream's second token on — its
    context length, summed and divided by the window's step dispatches)."""
    st, c, w = run.get("server_stats") or {}, run["counters"], run["window"]
    steps = c.get("steps")
    if not steps or st.get("moe_experts_touched_share") is None:
        return None
    cfg = run["geometry"]
    cells = (cfg["num_hidden_layers"] - cfg["num_dense_layers"]) \
        * cfg["held_experts"][1]
    live = pairs = 0
    for r in run["records"]:
        t = np.asarray(r["times"][1:])
        k = np.nonzero((t >= w["t_open"]) & (t < w["t_close"]))[0] + 1
        context = r["prompt_len"] + k
        live += int(np.sum(context))
        pairs += int(np.sum(np.minimum(context, cfg["sliding_window"] - 1)))
    return {"slots": c["occupied_lane_steps"] / steps,
            "touched": st["moe_experts_touched_share"] * cells,
            "expert_tokens": st["moe_tokens_per_expert_step"] * cells,
            "live_tokens": live / steps, "window_pairs": pairs / steps}


def roofline_pct(run, least, *regions):
    """The least seconds one step needs at the chip's peaks for ``least(cfg,
    work)``'s ``(bytes, flops)`` (a ``shapes_trinity`` function of the
    configuration and ``step_work``), over the device seconds one step spends
    under ``regions``."""
    work, peaks = step_work(run), run.get("peaks")
    spent = dots3_trace.region_seconds(run, *regions)
    if work is None or not peaks or spent is None:
        return None
    floor = shapes_trinity.floor_seconds(least(run["geometry"], work), peaks)
    return 100.0 * floor / spent
