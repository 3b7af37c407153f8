"""CPU tests of the chip benchmark (``chipbench/``): the harness end to end
at a toy width, the contract of ``BENCHMARK.json``, the trace reduction, the
shape functions, the plain reference, the control and the planted faults.

Every end-to-end test runs from a temporary copy of ``chipbench/`` to which a
configuration, a traffic mix and a per-layer metric are ADDED as new files
plus ``BENCHMARK.json`` entries — no file that is there is edited — so each
of them is also the drop-in check.  No TPU topology is described here.
"""
import json
import os
import re
import shutil

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TOY_GEOM = dict(n_layer=2, n_embd=64, n_head=4, n_inner=256, vocab_size=512)
DROP_IN_METRIC = '''"""A metric a later PR drops in: steps the window counted."""


def read(run):
    return float(run["counters"]["steps"]) or None
'''


def _toy_root(tmp):
    """``tmp`` as a checkout holding a copy of ``chipbench/`` plus toy files
    dropped in beside what is there."""
    bench_dir = os.path.join(tmp, "chipbench")
    shutil.copytree(os.path.join(REPO, "chipbench"), bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)

    def cfg_from(src, name, **over):
        with open(os.path.join(bench_dir, "configs", src + ".json")) as fh:
            cfg = json.load(fh)
        cfg.update(TOY_GEOM, name=name, preset="gpt2_small", **over)
        with open(os.path.join(bench_dir, "configs", name + ".json"),
                  "w") as fh:
            json.dump(cfg, fh)
        bench["configs"].append({
            "name": name, "source": cfg["source"], "reduced": ["n_layer"],
            "file": f"chipbench/configs/{name}.json", "why": "toy"})
        return cfg

    def traffic_from(src, name, **over):
        with open(os.path.join(bench_dir, "traffic", src + ".json")) as fh:
            tr = json.load(fh)
        for k, v in over.items():
            if isinstance(v, dict):
                tr[k] = dict(tr[k], **v)
            else:
                tr[k] = v
        with open(os.path.join(bench_dir, "traffic", name + ".json"),
                  "w") as fh:
            json.dump(tr, fh)

    # two narrow layers need a sharper init than 36 wide ones before the
    # int8 control parts from float32 often enough to read
    serve = cfg_from("gpt2_large_serve", "toy_serve", n_positions=128,
                     limits={"served_gap_mean": 0.004},
                     init={"layer_std": 0.08, "qkv_std": 0.64})
    serve["server"].update(max_total_len=128, pool_sizes=[4], num_pages=32,
                           admit_sizes=[1, 2], prefill_buckets=[32, 64])
    with open(os.path.join(bench_dir, "configs", "toy_serve.json"),
              "w") as fh:
        json.dump(serve, fh)
    cfg_from("gpt2_medium_train", "toy_train", n_positions=64,
             limits={"loss_gap": 0.01, "grad_norm_gap": 0.05,
                     "update_norm_gap": 0.05})
    traffic_from("closed32", "toy_closed4", clients=4, block=4,
                 requests=4000, max_total=128, trace_delay_s=0.1,
                 trace_seconds=0.3,
                 prompt_len={"median": 24, "min": 4, "max": 64},
                 output_len={"median": 12, "min": 4, "max": 24})
    traffic_from("seq1024", "toy_seq64", rows=4, seq=64, trace_delay_s=0.1,
                 trace_seconds=0.3)
    with open(os.path.join(bench_dir, "metrics", "toy_steps.py"), "w") as fh:
        fh.write(DROP_IN_METRIC)
    cells = {"toy_serve_closed4": ("toy_serve", "toy_closed4"),
             "toy_train_seq64": ("toy_train", "toy_seq64")}
    for name, (cfg, tr) in cells.items():
        bench["workloads"].append({"name": name, "config": cfg,
                                   "traffic": tr, "chips": 1, "why": "toy"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            kind = "serve" if "serve" in m["workloads"][0] else "train"
            m["workloads"] = m["workloads"] + [
                c for c in cells if kind in c]
    bench["per_layer"].append({
        "name": "toy_steps", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "toy", "moves": "setup_s",
        "workloads": list(cells)})
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as fh:
        json.dump(bench, fh)
    return tmp


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    return _toy_root(str(tmp_path_factory.mktemp("chipbench_toy")))


def _run(root, capfd, *argv):
    from chipbench import run
    rc = run.main(list(argv), root=root)
    out = capfd.readouterr()
    lines = [ln for ln in out.out.splitlines() if ln.strip()]
    return rc, (json.loads(lines[-1]) if rc == 0 and lines else None), out


def _well_formed(line, metric_names):
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "compared"
    assert set(metric_names) <= set(line["metrics"])
    for m in line["metrics"].values():
        assert isinstance(m["value"], float) and m["unit"]
    assert line["device"]["platform"] == "cpu"      # never a device number
    assert {"kind", "count", "memory_peak_bytes"} <= set(line["device"])
    for c in line["compared"].values():
        assert set(c) == {"value", "limit"}


# --------------------------------------------------------------------------- #
# the harness end to end at a toy width (and the drop-in check)
# --------------------------------------------------------------------------- #

def test_serve_toy_end_to_end(toy_root, capfd):
    rc, line, out = _run(toy_root, capfd, "--workload", "toy_serve_closed4",
                         "--seed", "3000000019", "--seconds", "1.5",
                         "--trace", "0")
    assert rc == 0, out.err
    _well_formed(line, ["serve_tok_s", "tpot_p50_ms",
                        "setup_s"])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 4
    assert line["compared"]["compiles_in_window"]["value"] == 0
    assert "compared served_gap_mean" in out.err


def test_train_toy_traced_reads_the_dropped_in_metric(toy_root, capfd):
    rc, line, out = _run(toy_root, capfd, "--workload", "toy_train_seq64",
                         "--seed", "7", "--seconds", "1.0", "--trace", "1")
    assert rc == 0, out.err
    # the CPU trace has no device plane: trace metrics have nothing to read
    # and are left out, never reported as 0
    _well_formed(line, ["toy_steps"])
    assert "device_idle_pct.train" not in line["metrics"]
    assert "train_mfu_pct" not in line["metrics"]      # no peaks on a CPU
    assert line["correct"] is True
    assert line["metrics"]["toy_steps"]["value"] == line["attempted"]


def test_same_seed_same_inputs(toy_root):
    from chipbench import harness, weights
    gen = harness.load_by_path(os.path.join(
        toy_root, "chipbench", "generators", "closed_loop.py"), "g")
    with open(os.path.join(toy_root, "chipbench", "traffic",
                           "toy_closed4.json")) as fh:
        tr = json.load(fh)
    a, b, c = (gen.make(tr, s, 512)["requests"] for s in (5, 5, 6))
    assert all((x["prompt"] == y["prompt"]).all()
               and x["max_new"] == y["max_new"] for x, y in zip(a, b))
    # another seed: the same multiset of lengths, in another order
    assert sorted(x["prompt"].size for x in a[4:]) == \
        sorted(x["prompt"].size for x in c[4:])
    assert any(x["prompt"].size != y["prompt"].size for x, y in zip(a, c))
    geom = dict(num_layers=1, units=8, num_heads=2, hidden_size=16,
                vocab_size=32, max_length=16)
    big = 2 ** 31 + 12345
    w1, w2 = weights.make(geom, big, "float32"), \
        weights.make(geom, big, "float32")
    assert all((np.asarray(w1[k]) == np.asarray(w2[k])).all() for k in w1)
    w3 = weights.make(geom, 12345, "float32")
    assert not (np.asarray(w1["wte"]) == np.asarray(w3["wte"])).all()


def test_refuses_without_chip_or_cell(toy_root, capfd, monkeypatch):
    rc, line, out = _run(toy_root, capfd, "--workload", "no_such_cell",
                         "--seed", "1", "--seconds", "1")
    assert rc != 0 and line is None and "no workload" in out.err
    # a CPU without the explicit pin is no chip: refuse, print no result
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    rc, line, out = _run(toy_root, capfd, "--workload", "toy_train_seq64",
                         "--seed", "1", "--seconds", "1")
    assert rc != 0 and out.out.strip() == "" and "not a TPU" in out.err


# --------------------------------------------------------------------------- #
# BENCHMARK.json against the contract
# --------------------------------------------------------------------------- #

def test_benchmark_json_contract():
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        raw = fh.read()
    b = json.loads(raw)
    assert len(raw) <= 64 * 1024
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
    assert 1 <= b["run_seconds"] <= 51
    budget = (2 + 14 * 24) * (b["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert budget <= 43200
    cells = {w["name"]: w for w in b["workloads"]}
    configs = {c["name"]: c for c in b["configs"]}
    assert len(cells) == len(b["workloads"])
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert name.match(c["name"]) and len(c["why"]) <= 200
        assert any(c["file"].startswith(p + "/") for p in b["paths"])
        with open(os.path.join(REPO, c["file"])) as fh:
            cfg = json.load(fh)
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
        assert any(w["config"] == c["name"] for w in b["workloads"])
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert name.match(w["name"]) and name.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        assert os.path.isfile(os.path.join(
            REPO, "chipbench", "traffic", w["traffic"] + ".json"))
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert name.match(m["name"]) and unit.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
        assert set(m.get("workloads", cells)) <= set(cells)

    def reports(cell, metric):
        return "workloads" not in metric or cell in metric["workloads"]

    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert name.match(m["name"]) and unit.match(m["unit"])
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["moves"] in e2e and "\n" not in m["layer"]
        for cell in m.get("workloads", cells):
            assert reports(cell, e2e[m["moves"]]), (m["name"], cell)
        assert os.path.isfile(os.path.join(
            REPO, "chipbench", "metrics", m["name"] + ".py"))
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(names) == len(set(names))
    for cell in cells:
        mine = [m for m in b["end_to_end"] if reports(cell, m)]
        assert len(mine) >= 2 and any(m["name"] == "setup_s" for m in mine)
        assert any(reports(cell, m) for m in b["per_layer"])
        assert any("mfu" in m["name"] and reports(cell, m)
                   for m in b["per_layer"])
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= max(
        1, len(cells) // 4)


# --------------------------------------------------------------------------- #
# the trace reduction, on a small hand-built trace
# --------------------------------------------------------------------------- #

def test_reduce_hand_built_trace():
    from chipbench import reduce
    ms = 1e6    # ns
    ops = [("%while = (s32[]) while(...)", 0 * ms, 4 * ms),   # holds 2 children
           ("%fusion.1 = bf16[8] fusion(...)", 0 * ms, 1 * ms),
           ("%fusion.2 = bf16[8] fusion(...)", 2 * ms, 2 * ms),
           ("%copy.3 = bf16[8] copy(...)", 7 * ms, 1 * ms)]
    planes = [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": [
                ("jit_step(123)", 0 * ms, 4 * ms),
                ("jit_admit(456)", 7 * ms, 1 * ms)]}]},
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            ("cb:window", 0 * ms, 10 * ms),
            ("mx:serve:admit", 4.5 * ms, 2 * ms),
            ("mx:serve:step", 8 * ms, 0.5 * ms)]}]},
    ]
    r = reduce.reduce(planes, window=(0.0, 10 * ms))
    assert r["window_s"] == pytest.approx(0.010)
    assert r["busy_s"] == pytest.approx(0.005)       # union, not the sum
    assert r["idle_pct"] == pytest.approx(50.0)
    assert r["modules"]["jit_step"] == {
        "seconds": pytest.approx(0.004), "runs": 1,
        "whole_seconds": pytest.approx(0.004), "whole_runs": 1}
    # a run the window's end cuts counts towards busy time, not towards
    # the time of one run
    cut = reduce.reduce(planes, window=(0.0, 7.5 * ms))["modules"]
    assert cut["jit_admit"] == {
        "seconds": pytest.approx(0.0005), "runs": 1,
        "whole_seconds": 0.0, "whole_runs": 0}
    assert reduce.step_device_s({
        "trace": {"modules": cut},
        "config": {"executables": {"step": "jit_admit"}}}) is None
    ops_by = dict(r["device_ops"])
    assert ops_by["jit_step/fusion.2"] == pytest.approx(0.002)
    assert ops_by["jit_step/while"] == pytest.approx(0.001)   # self time
    assert ops_by["jit_admit/copy.3"] == pytest.approx(0.001)
    gaps = dict(r["idle_gaps"])
    # 4..7 ms lies mostly under the admit span, 8..10 ms under the step
    # span less than under nothing else: the window bracket never labels
    assert gaps["mx:serve:admit"] == pytest.approx(0.003)
    assert gaps["mx:serve:step"] == pytest.approx(0.002)
    assert "cb:window" not in gaps
    # nothing on a device: nothing to read
    assert reduce.reduce([planes[1]]) is None
    assert reduce.union([(0, 2), (1, 3), (5, 6), (5.5, 5.7)]) == \
        [(0, 3), (5, 6)]


# --------------------------------------------------------------------------- #
# shape functions against hand-worked numbers; the peaks table
# --------------------------------------------------------------------------- #

LARGE = dict(num_layers=36, units=1280, num_heads=20, hidden_size=5120,
             vocab_size=50257, max_length=1024)
MEDIUM = dict(num_layers=24, units=1024, num_heads=16, hidden_size=4096,
              vocab_size=50257, max_length=1024)


def test_shapes_hand_worked():
    from chipbench import shapes
    # per layer: 12 u^2 + 13 u; embeddings (V + T) u; final norm 2 u
    assert shapes.gpt2_params(LARGE) == 36 * (12 * 1280 ** 2 + 13 * 1280) \
        + (50257 + 1024) * 1280 + 2 * 1280 == 774_030_080
    assert shapes.gpt2_params(MEDIUM) == 354_823_168
    assert shapes.kv_bytes_per_token(LARGE, 2) == 184_320
    # weights once + 10,000 live tokens of K and V
    assert shapes.decode_step_min_bytes(LARGE, 10_000, 2) == \
        774_030_080 * 2 + 10_000 * 184_320
    assert shapes.served_flops(LARGE, 1000) == 2 * 774_030_080 * 1000
    # 6 N + 6 layers units seq: causal attention counted as its half
    assert shapes.train_flops_per_token(MEDIUM, 1024) == \
        6 * 354_823_168 + 6 * 24 * 1024 * 1024
    # the roofline arithmetic of step_hbm_roofline_pct at 819 GB/s
    least_ms = shapes.decode_step_min_bytes(LARGE, 10_000, 2) / 819e9 * 1e3
    assert least_ms == pytest.approx(4.1407, rel=1e-4)


def test_peaks_keyed_by_device_kind():
    from chipbench import shapes
    v5e = shapes.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9 and "source" in v5e
    with pytest.raises(KeyError, match="TPU v9"):
        shapes.peaks_for("TPU v9")


# --------------------------------------------------------------------------- #
# the plain reference against the program, at a toy size
# --------------------------------------------------------------------------- #

def _toy_program(dtype="float32"):
    import mxnet_tpu as mx
    from mxnet_tpu import models
    from chipbench import gpt, weights
    geom = dict(num_layers=2, units=64, num_heads=4, hidden_size=256,
                vocab_size=512, max_length=64)
    net, _ = models.gpt2_small(dtype=dtype, **geom)
    net.initialize(mx.init.Zero())
    w = weights.make(geom, 11, dtype)
    gpt.load_into(net, geom, w)
    return net, geom, w


def test_reference_matches_models_gpt():
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from chipbench import reference
    net, geom, w = _toy_program()
    ids = np.random.default_rng(0).integers(0, 512, (2, 33), dtype=np.int32)
    data, label = ids[:, :-1], ids[:, 1:]
    got = net(mx.nd.array(data, dtype="int32")).asnumpy()
    ref = np.asarray(reference.logits(w, jnp.asarray(data), 4))
    assert np.abs(got - ref).max() < 1e-5 * np.abs(ref).max() + 1e-6
    loss = gluon.loss.SoftmaxCrossEntropyLoss()(
        net(mx.nd.array(data, dtype="int32")),
        mx.nd.array(label, dtype="int32")).asnumpy().mean()
    ref_loss = float(reference.loss(w, jnp.asarray(data),
                                    jnp.asarray(label), 4))
    assert loss == pytest.approx(ref_loss, rel=1e-5)


# --------------------------------------------------------------------------- #
# the control and the planted faults come out as not correct
# --------------------------------------------------------------------------- #

def _ctx(root, cell_name, seed):
    import time
    from chipbench import harness
    bench = harness.read_json(os.path.join(root, "BENCHMARK.json"))
    cell = next(w for w in bench["workloads"] if w["name"] == cell_name)
    return harness.Context(root, bench, cell, seed, 1.0, 0, time.time())


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_train_control_and_half_batch_fail_a_limit(toy_root, seed):
    """The reference put in the program's place and computed in int8 — and
    the same with half of the batch left out — fail at least one of the
    cell's numbers at the toy cell's limits."""
    from chipbench import gpt
    ctx = _ctx(toy_root, "toy_train_seq64", seed)
    entry, geom = ctx.entry(), gpt.geometry(ctx.config)
    batches = ctx.generator().make(ctx.traffic, seed, geom["vocab_size"])
    import mxnet_tpu as mx
    from mxnet_tpu import models
    net, _ = models.gpt2_small(dtype="float32", **geom)
    net.initialize(mx.init.Zero())
    leaves = gpt.leaf_names(net, geom)
    ref = entry.reference_readings(ctx, geom, batches, leaves)
    limits = ctx.config["limits"]
    assert all(v == 0 for v in entry.compare(ref, ref).values())
    for variant in ("control", "half_batch"):
        got = entry.compare(entry.reference_readings(
            ctx, geom, batches, leaves, **{variant: True}), ref)
        assert any(got[k] > limits[k] for k in limits), (variant, got)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_serve_control_reads_over_the_limit(toy_root, seed):
    """The reference put in the program's place and computed in int8: the
    token it puts first lies, in the mean, further below the float32
    reference's best than the toy cell's limit allows (it need not decode:
    same contexts, position by position)."""
    import jax.numpy as jnp
    from chipbench import gpt, reference, weights
    ctx = _ctx(toy_root, "toy_serve_closed4", seed)
    geom = gpt.geometry(ctx.config)
    w = weights.make(geom, seed, ctx.config["dtype"], ctx.config["init"])
    gaps = []
    for j in range(4):
        context = np.random.default_rng([seed, j]).integers(
            0, geom["vocab_size"], geom["max_length"], dtype=np.int32)
        own, control = reference.served_gaps(
            w, jnp.asarray(context), jnp.asarray(np.roll(context, -1)),
            geom["num_heads"], control=True)
        gaps.append(np.asarray(control))
        assert float(np.min(np.asarray(own))) >= 0.0
    assert np.mean(gaps) > ctx.config["limits"]["served_gap_mean"]


def _state_unchanged(monkeypatch):
    from mxnet_tpu.optimizer import optimizer
    monkeypatch.setattr(optimizer.AdamW, "_update_rule",
                        lambda self, w, g, state, lr, wd, t: (w, state))


def _half_batch(monkeypatch):
    import mxnet_tpu as mx
    from mxnet_tpu.parallel import spmd
    whole = spmd.SPMDTrainer.step

    def half(self, data, label, batch_size=None):
        h = data.shape[0] // 2
        return whole(self, mx.nd.array(data.asnumpy()[:h], dtype="int32"),
                     mx.nd.array(label.asnumpy()[:h], dtype="int32"))
    monkeypatch.setattr(spmd.SPMDTrainer, "step", half)


def _token_altered(monkeypatch):
    from mxnet_tpu.serve import server
    push = server.TokenStream._push

    def altered(self, tok):
        push(self, (tok + 1) % 512 if len(self._toks) == 2 else tok)
    monkeypatch.setattr(server.TokenStream, "_push", altered)


@pytest.mark.parametrize("cell, fault", [
    ("toy_train_seq64", _state_unchanged),
    ("toy_train_seq64", _half_batch),
    ("toy_serve_closed4", _token_altered),
], ids=["state_unchanged", "half_batch", "token_altered"])
def test_fault_under_the_timed_path_reads_not_correct(
        toy_root, capfd, monkeypatch, cell, fault):
    """The whole of a run (device look aside) with the timed path broken
    underneath: ``correct`` comes out false, the run still reports."""
    fault(monkeypatch)
    rc, line, out = _run(toy_root, capfd, "--workload", cell, "--seed", "9",
                         "--seconds", "1.0", "--trace", "0")
    assert rc == 0, out.err
    assert line["correct"] is False
    assert any(c["value"] is None or c["value"] > c["limit"]
               for c in line["compared"].values())
    assert "correct = False" in out.err
