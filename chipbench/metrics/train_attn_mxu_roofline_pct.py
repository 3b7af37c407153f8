"""The least time the train step's attention needs — the operations
``shapes.train_flops_per_token`` counts for it (6 x layers x units x seq a
token: the causal half of QK^T and PV, forward and backward, recomputation
not counted) x the step's tokens at the chip's bf16 peak — over the step's
device seconds under ``mx.attn``.  The same work whatever implements the
region; it cannot pass 100, since the region does at least that work."""
from chipbench import dots3_trace, shapes


def read(run):
    attn_s, peaks = dots3_trace.region_seconds(run, "mx.attn"), \
        run.get("peaks")
    c = run["counters"]
    if attn_s is None or not peaks or not c.get("rows") or not c.get("seq"):
        return None
    g = run["geometry"]
    per_token = shapes.train_flops_per_token(g, c["seq"]) \
        - 6 * shapes.gpt2_params(g)
    least_s = per_token * c["rows"] * c["seq"] / peaks["bf16_flops_per_s"]
    return 100.0 * least_s / attn_s
