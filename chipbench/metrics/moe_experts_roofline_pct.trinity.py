"""The least time the routed experts of one step need (the touched experts'
weights read once, 2 x an expert's parameters a routed pair) over the device
time under ``mx.moe_experts``."""
from chipbench import shapes_trinity, trinity_trace


def read(run):
    return trinity_trace.roofline_pct(
        run, lambda cfg, w: shapes_trinity.moe_experts_min(
            cfg, w["touched"], w["expert_tokens"]), "mx.moe_experts")
