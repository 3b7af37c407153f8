"""Share of the train step's device time under ``mx.dense``: embeddings, qkv /
proj / fc1 / fc2, norms and residuals, forward and backward."""
from chipbench import program_trace


def read(run):
    return program_trace.region_pct(run, "mx.dense")
