"""``python3 chipbench/readings.py --workload <cell> --seeds 1,2,3 --seconds <s>``

The readings a limit is set from (PERF.md section 2): for each seed, in one
process, a short window of the cell at its own size and load, the numbers
``correct`` compares, and beside them the same numbers for the CONTROL — the
plain reference put in the program's place and computed in int8, the
precision below bfloat16 — and, for a training cell, for the planted fault
"half of the batch left out".  One JSON line a seed.  The benchmark's own
runs never run this.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None, root=ROOT):
    from chipbench import harness

    ap = argparse.ArgumentParser(prog="chipbench/readings.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=1)
    ap.add_argument("--init", default=None, metavar="LAYER_STD,QKV_STD",
                    help="read with these widths of the seeded weights in "
                         "place of the configuration's `init` (how a "
                         "candidate is tried before it is written there)")
    args = ap.parse_args(argv)
    bench = harness.read_json(os.path.join(root, "BENCHMARK.json"))
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx = harness.Context(root, bench, cell, seed, args.seconds, 0,
                              time.time())
        ctx.control = bool(args.control)
        if args.init:
            layer_std, qkv_std = (float(x) for x in args.init.split(","))
            ctx.config["init"] = {"layer_std": layer_std, "qkv_std": qkv_std}
        harness.claim_device(ctx)
        out = ctx.entry().run(ctx)
        print(json.dumps({
            "seed": seed, "device": ctx.device["kind"],
            "init": ctx.config.get("init"),
            "program": {k: c["value"] for k, c in out["compared"].items()},
            "uncompared": out.get("numbers"),
            "control": out.get("control"),
            "end_to_end": out["end_to_end"],
            "memory_peak_bytes": out["memory_peak_bytes"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
