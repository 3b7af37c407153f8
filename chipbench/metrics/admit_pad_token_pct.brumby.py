"""Share of the token positions the admission dispatches computed in the
window that were padding to a bucket: the server's ``admit_rows`` less
``admit_tokens``, over ``admit_rows`` (``admit_trace.pad_token_pct``)."""
from chipbench import admit_trace


def read(run):
    return admit_trace.pad_token_pct(run)
