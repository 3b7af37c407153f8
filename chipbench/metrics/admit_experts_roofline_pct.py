"""The least time a question's chunk needs for its routed experts — the
(routed layer, held expert) cells its rows touched, each expert's weights
read once, and 2 x an expert's parameters a (row, held expert) pair — over
the device time one whole ``jit_chunk*`` run spends under ``mx.moe_experts``.

Both sides are per chunk: the touched cells and pairs are the server's own
counters over the window (``chunk_experts_touched``,
``chunk_expert_tokens``, over ``chunk_dispatches``), the device seconds the
mean over the chunk runs that lie whole in the traced stretch, so the
stretch's share of chunks (a sample of 3 s) cancels.  The counters count
every row a chunk computes, the padding past the prompt too: those rows
route and run through the experts as well.

``None`` where there is nothing to read: an untraced or CPU run, a program
whose chunks count no experts (the parent of PR 39), a model without routed
experts, a window without a chunk.
"""
from chipbench import admit_trace, shapes_dots3, shapes_trinity


def read(run):
    d, peaks = admit_trace._dispatch(run), run.get("peaks")
    rows = admit_trace._admission_rows()
    if not d or not peaks or rows is None or not d.get("chunk_dispatches") \
            or not d.get("chunk_experts_touched"):
        return None
    chunks = [row for name, row in rows.items()
              if name.startswith("jit_chunk")]
    runs = sum(row["runs"] for row in chunks)
    spent = sum(row["regions"].get("mx.moe_experts", 0.0) for row in chunks)
    if not runs or not spent:
        return None
    cfg = run["geometry"]
    shapes = shapes_trinity if "num_dense_layers" in cfg else shapes_dots3
    n = d["chunk_dispatches"]
    least = shapes.floor_seconds(shapes.moe_experts_min(
        cfg, d["chunk_experts_touched"] / n,
        d.get("chunk_expert_tokens", 0) / n), peaks)
    return 100.0 * least / (spent / runs)
