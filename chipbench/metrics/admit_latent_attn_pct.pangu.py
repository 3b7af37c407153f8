"""Share of the question chunks' device time (every ``jit_chunk*`` run whole
in the traced stretch) under ``mx.latent_gather`` and ``mx.latent_attn``:
the rows a chunk's queries reach read back through the table and the masked
dense attention over them, the compute-bound prefill form."""
from chipbench import admit_trace


def read(run):
    rows = {name: row for name, row in
            (admit_trace._admission_rows() or {}).items()
            if name.startswith("jit_chunk")}
    total = sum(s for row in rows.values() for s in row["regions"].values())
    if not total:
        return None
    part = sum(row["regions"].get(r, 0.0) for row in rows.values()
               for r in ("mx.latent_gather", "mx.latent_attn"))
    return 100.0 * part / total
