"""The least time one decode step could take — the fixed weights, the experts
a step touched and every latent row the walks read, each read once at the
HBM peak (``shapes_pangu.decode_step_min_bytes``) — over the step
executable's device time: the same work whatever implements it."""
from chipbench import pangu_trace, reduce, shapes_pangu


def read(run):
    step_s, peaks = reduce.step_device_s(run), run.get("peaks")
    rows = pangu_trace.rows_per_step(run)
    share = (run.get("server_stats") or {}).get("moe_experts_touched_share")
    if step_s is None or not peaks or rows is None or share is None:
        return None
    cfg = run["geometry"]
    cells = (cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]) \
        * cfg["held_experts"][1]
    least = shapes_pangu.decode_step_min_bytes(cfg, share * cells, rows)
    return 100.0 * least / peaks["hbm_bytes_per_s"] / step_s
