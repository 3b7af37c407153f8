"""The harness: finds a cell's files by the names in ``BENCHMARK.json``,
runs its entry, reads its metrics and prints the result line.

A cell is data: ``configs/<config>.json`` names an entry
(``entries/<entry>.py``), ``traffic/<traffic>.json`` names a generator
(``generators/<generator>.py``), and every per-layer metric is a reader of
its own (``metrics/<metric>.py`` with ``read(run)``).  All of them are
loaded by path from ``<root>/chipbench``, so a later PR adds files and
``BENCHMARK.json`` entries and edits nothing here.
"""
import argparse
import importlib.util
import json
import os
import sys
import time


class BenchError(Exception):
    """The run cannot be made (no chip, unknown cell, bad file)."""


def load_by_path(path, name):
    if not os.path.isfile(path):
        raise BenchError(f"no such file: {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path):
    try:
        with open(path) as fh:
            return json.load(fh)
    except OSError as e:
        raise BenchError(f"cannot read {path}: {e}") from e


class Context:
    """What an entry is given: the cell's files, the run's arguments and
    the loaders it needs."""

    def __init__(self, root, bench, cell, seed, seconds, trace, t_start):
        self.root, self.bench, self.cell = root, bench, cell
        self.dir = os.path.join(root, bench["paths"][0])
        self.seed, self.seconds, self.trace = seed, seconds, bool(trace)
        self.t_start = t_start
        cfg = next((c for c in bench["configs"]
                    if c["name"] == cell["config"]), None)
        if cfg is None:
            raise BenchError(f"cell {cell['name']} names config "
                             f"{cell['config']!r}, which is not listed")
        self.config = read_json(os.path.join(root, cfg["file"]))
        self.traffic = read_json(os.path.join(
            self.dir, "traffic", cell["traffic"] + ".json"))
        self.device = None      # filled by ``claim_device``
        self.peaks = None
        self.control = False    # ``readings.py`` sets it: read the control
                                # and the planted faults beside the program

    def generator(self):
        name = self.traffic["generator"]
        return load_by_path(os.path.join(self.dir, "generators",
                                         name + ".py"), f"cb_gen_{name}")

    def entry(self):
        name = self.config["entry"]
        return load_by_path(os.path.join(self.dir, "entries", name + ".py"),
                            f"cb_entry_{name}")

    def metric_reader(self, name):
        return load_by_path(os.path.join(self.dir, "metrics", name + ".py"),
                            "cb_metric_" + name.replace(".", "_"))


def claim_device(ctx):
    """The chip, or an error.  A CPU run happens only under an explicit
    ``JAX_PLATFORMS=cpu`` (the rehearsal); its line says ``cpu`` and none
    of its numbers is a device number."""
    import jax

    from chipbench import shapes

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR") and \
            os.environ.get("JAX_PLATFORMS", "").strip().lower() != "cpu":
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ctx.root, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)
    devs = jax.devices()
    platform, chips = devs[0].platform, int(ctx.cell["chips"])
    rehearsal = os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
    if platform != "tpu" and not rehearsal:
        raise BenchError(f"jax found platform {platform!r}, not a TPU (set "
                         "JAX_PLATFORMS=cpu for the rehearsal)")
    if platform == "tpu" and len(devs) < chips:
        raise BenchError(f"cell {ctx.cell['name']} needs {chips} chips, "
                         f"jax found {len(devs)}")
    ctx.device = {"platform": platform, "kind": devs[0].device_kind,
                  "count": len(devs)}
    ctx.peaks = shapes.peaks_for(
        devs[0].device_kind, os.path.join(ctx.dir, "peaks.json")) \
        if platform == "tpu" else None


def memory_peak_bytes():
    """Peak bytes on the fullest device, as the allocator reports it (0
    where the backend reports nothing, as the CPU)."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks))


def percentile(values, q):
    """The q-th percentile by linear interpolation (numpy's default)."""
    import numpy as np
    return float(np.percentile(np.asarray(values, dtype=np.float64), q))


class Tracer:
    """The profiler around a stretch of the window, on a thread of its
    own so that starting and stopping it does not stall the driver.  The
    stretch is bracketed by a ``cb:window`` annotation, which is the
    reduction's window.  JAX's trace is started here with the Python
    tracer off (it slows the scheduler's host code severalfold); the
    program's profiler facade is started inside it, which its ``start()``
    allows ("nested": it keeps to the trace that runs), so that the
    program's own ``mx:*`` annotations are switched on."""

    def __init__(self, delay_s, seconds):
        import tempfile
        import threading
        self.delay_s, self.seconds = delay_s, seconds
        self._tmp = tempfile.TemporaryDirectory(prefix="chipbench_trace_")
        self._thread = threading.Thread(target=self._run, name="cb-tracer")
        self.error = None

    def start(self):
        self._thread.start()

    def _run(self):
        import jax
        from chipbench import reduce
        from mxnet_tpu import profiler
        try:
            time.sleep(self.delay_s)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(self._tmp.name,
                                     profiler_options=options)
            profiler.set_config(filename=os.path.join(self._tmp.name,
                                                      "facade"))
            profiler.start()
            try:
                with jax.profiler.TraceAnnotation(reduce.WINDOW_SPAN):
                    time.sleep(self.seconds)
            finally:
                profiler.stop()
        except Exception as e:      # reported by ``finish``
            self.error = e

    def finish(self):
        """Wait for the trace, reduce it, delete it."""
        from chipbench import reduce
        self._thread.join(300)
        try:
            if self.error is not None:
                raise BenchError(f"tracing failed: {self.error!r}")
            planes = reduce.load_xplane(self._tmp.name)
            window = None
            for p in planes:
                for line in p["lines"]:
                    for name, s, d in line["events"]:
                        if name == reduce.WINDOW_SPAN:
                            window = (s, s + d)
            return reduce.reduce(planes, window)
        finally:
            self._tmp.cleanup()


def applies(metric, cell_name):
    return "workloads" not in metric or cell_name in metric["workloads"]


def result_line(ctx, out):
    """The contract's object from an entry's result ``out``."""
    name = ctx.cell["name"]
    metrics = {}
    if not ctx.trace:
        for m in ctx.bench["end_to_end"]:
            if applies(m, name) and m["name"] in out["end_to_end"]:
                metrics[m["name"]] = {"value": out["end_to_end"][m["name"]],
                                      "unit": m["unit"]}
    else:
        run = dict(out, config=ctx.config, traffic=ctx.traffic,
                   peaks=ctx.peaks, cell=name, seconds=ctx.seconds)
        for m in ctx.bench["per_layer"]:
            if not applies(m, name):
                continue
            value = ctx.metric_reader(m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    compared = out["compared"]
    correct = bool(compared) and all(
        c["value"] is not None and c["value"] <= c["limit"]
        for c in compared.values())
    device = dict(ctx.device, memory_peak_bytes=out["memory_peak_bytes"])
    line = {"correct": correct, "attempted": out["attempted"],
            "failed": out["failed"], "metrics": metrics, "device": device}
    trace = out.get("trace")
    if ctx.trace and trace:
        device["busy_s"], device["window_s"] = trace["busy_s"], \
            trace["window_s"]
        line["breakdown"] = {"device_ops": trace["device_ops"],
                             "idle_gaps": trace["idle_gaps"]}
    line["compared"] = compared
    return line


def main(argv, root, t_start):
    ap = argparse.ArgumentParser(prog="chipbench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        bench = read_json(os.path.join(root, "BENCHMARK.json"))
        cell = next((w for w in bench["workloads"]
                     if w["name"] == args.workload), None)
        if cell is None:
            raise BenchError(f"no workload {args.workload!r} in "
                             "BENCHMARK.json")
        ctx = Context(root, bench, cell, args.seed, args.seconds,
                      args.trace, t_start)
        claim_device(ctx)
        out = ctx.entry().run(ctx)
        line = result_line(ctx, out)
    except (BenchError, ImportError, KeyError) as e:
        print(f"chipbench: {type(e).__name__}: {e}", file=sys.stderr)
        return 1
    sys.stdout.flush()
    print("chipbench: detail " + json.dumps(
        {"window": out.get("window"), "counters": out.get("counters"),
         "end_to_end": out.get("end_to_end"),
         "uncompared": out.get("numbers"),
         "modules": (out.get("trace") or {}).get("modules")}),
        file=sys.stderr)
    for k, c in line["compared"].items():
        print(f"chipbench: compared {k} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(f"chipbench: correct = {line['correct']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0
