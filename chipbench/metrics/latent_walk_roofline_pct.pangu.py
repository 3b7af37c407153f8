"""The least time the latent attention's page walks of one step need — every
cached row a walk read (the counter ``latent_rows_walked`` a step) read once
and scored and summed by every head in the absorbed form
(``shapes_pangu.latent_walk_min``), the longer of its bytes and its
operations at the chip's peaks — over the step's device time under
``mx.latent_attn``: the kernel ``mx_latent_paged_attention``, whatever
implements it."""
from chipbench import dots3_trace, pangu_trace, shapes_pangu


def read(run):
    rows, peaks = pangu_trace.rows_per_step(run), run.get("peaks")
    spent = dots3_trace.region_seconds(run, "mx.latent_attn")
    if rows is None or not peaks or spent is None:
        return None
    floor = shapes_pangu.floor_seconds(
        shapes_pangu.latent_walk_min(run["geometry"], rows), peaks)
    return 100.0 * floor / spent
