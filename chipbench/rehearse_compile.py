"""``JAX_PLATFORMS=cpu python3 chipbench/rehearse_compile.py --workload <train cell> [--rows 8,4,2]``

Rehearsal 3 of the ``on-chip-measurement`` guide, for a training cell:
compile the real-size train step for a v5e that is described and not
attached, and print ``memory_analysis()`` for each candidate number of rows —
what the chip's compiler refuses, it refuses here at no chip time.  The
trainer is built on the CPU exactly as the entry builds it; its pure step
function is then lowered with every argument placed on the described chip.
Nothing runs, so this says nothing about time, and it counts one program,
not what else the process keeps on the device.
"""
import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None, root=ROOT):
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from chipbench import gpt, harness

    ap = argparse.ArgumentParser(prog="chipbench/rehearse_compile.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rows", default="8,4,2")
    args = ap.parse_args(argv)
    bench = harness.read_json(os.path.join(root, "BENCHMARK.json"))
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    ctx = harness.Context(root, bench, cell, 0, 1.0, 0, time.time())
    entry = ctx.entry()
    geom = gpt.geometry(ctx.config)
    net, trainer = entry.build(ctx, geom)
    seq = int(ctx.traffic["seq"])

    import mxnet_tpu as mx
    tiny = mx.nd.array(jnp.zeros((1, 8), jnp.int32), dtype="int32")
    trainer._ensure_built(tiny, tiny)
    step_fn = trainer._make_step_fn()
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def struct(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip)

    state = jax.tree.map(struct, (trainer._train_vals, trainer._opt_states,
                                  trainer._frozen_vals))
    scalars = (struct(jax.random.PRNGKey(0)), struct(jnp.float32(0)),
               struct(jnp.float32(0)), struct(jnp.int32(0)))
    for rows in (int(r) for r in args.rows.split(",")):
        batch = jax.ShapeDtypeStruct((rows, seq), jnp.int32, sharding=chip)
        t0 = time.time()
        try:
            compiled = jax.jit(step_fn, donate_argnums=(0, 1)).lower(
                *state, *scalars, batch, batch).compile()
            ma = compiled.memory_analysis()
            row = {"rows": rows, "fits": True,
                   "argument_bytes": ma.argument_size_in_bytes,
                   "output_bytes": ma.output_size_in_bytes,
                   "alias_bytes": ma.alias_size_in_bytes,
                   "temp_bytes": ma.temp_size_in_bytes,
                   "peak_bytes": getattr(ma, "peak_memory_in_bytes", None)}
        except Exception as e:
            row = {"rows": rows, "fits": False, "error": str(e)[:300]}
        row["compile_s"] = round(time.time() - t0, 1)
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
