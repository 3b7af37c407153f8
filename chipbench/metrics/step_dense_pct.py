"""Share of the decode step's device time under ``mx.dense`` and ``mx.head``:
the weight products, norms and residuals, ``ln_f``, the head and sampling
- the work a decode step exists for."""
from chipbench import program_trace


def read(run):
    return program_trace.region_pct(run, "mx.dense", "mx.head")
