"""The least time prefill's operations in a mean admission dispatch need —
its prompt tokens' causal pairs within the dispatch, their writes into the
state and the carried state's reads by the tokens of chunks that continue a
prompt (``shapes_brumby.retention_chunk_flops``) at the chip's bf16 peak —
over the device seconds a mean admission run spends under the prefill region
(``mx.ssm_scan``).  The tokens are the server's counters ``admit_tokens``
(true prompt tokens, padding left out) and ``chunk_carried_tokens`` over the
window's dispatches, and the pairs those of dispatches of the mean length: a
lower bound of the work, so the share cannot pass 100."""
from chipbench import brumby_trace, shapes_brumby


def read(run):
    mean, peaks = brumby_trace.prefill_per_dispatch(run), run.get("peaks")
    if mean is None or not peaks:
        return None
    tokens, carried, spent = mean
    return 100.0 * shapes_brumby.retention_chunk_flops(
        run["geometry"], tokens, 1, carried) / peaks["bf16_flops_per_s"] \
        / spent
