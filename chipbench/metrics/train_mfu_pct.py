"""Model FLOP/s utilisation of the whole train step: the operations forward
and backward need a token (``shapes.train_flops_per_token``: 6 x parameters
plus causal attention's half) x tokens a second over the run's window, over
the chip's bf16 peak.  Recomputation is not counted."""
from chipbench import shapes


def read(run):
    peaks, c, w = run.get("peaks"), run["counters"], run["window"]
    if not peaks or not c.get("steps"):
        return None
    rate = c["steps"] * c["rows"] * c["seq"] / (w["t_end"] - w["t_open"])
    return 100.0 * shapes.train_flops_per_token(run["geometry"], c["seq"]) \
        * rate / peaks["bf16_flops_per_s"]
