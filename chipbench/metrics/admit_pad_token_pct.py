"""Share of the token positions the admission dispatches computed in the
window that were padding to a bucket: the server's ``admit_rows`` (``A x P``
a wave, ``C`` a chunk) less ``admit_tokens`` (real prompt tokens), over
``admit_rows``."""
from chipbench import admit_trace


def read(run):
    return admit_trace.pad_token_pct(run)
