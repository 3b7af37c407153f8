"""CPU tests of the granite cell's benchmark files (``chipbench/``): the
entry end to end at a toy width, dropped into a temporary copy of
``chipbench/`` as NEW files plus ``BENCHMARK.json`` entries (the drop-in
pattern of ``test_chipbench.py``), the shape functions at the published
sizes, the seeded weights, the reference's controls, and every new reader on
a planted run — ``None`` where there is nothing to read.
"""
import json
import os
import shutil

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "granite4h_micro_serve_chat64"

TOY = dict(
    hidden_size=32, num_hidden_layers=6,
    layer_types=["mamba", "mamba", "attention", "mamba", "mamba", "mamba"],
    shared_intermediate_size=48, intermediate_size=48,
    num_attention_heads=4, num_key_value_heads=2, mamba_n_heads=4,
    mamba_d_head=16, mamba_d_state=8, mamba_chunk_size=8, vocab_size=96,
    dtype="float32")


def _real_cfg():
    from chipbench import granite, harness
    return granite.reference_config(harness.read_json(os.path.join(
        REPO, "chipbench", "configs", "granite4h_micro_serve.json")))


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("chipbench_granite_toy"))
    bench_dir = os.path.join(tmp, "chipbench")
    shutil.copytree(os.path.join(REPO, "chipbench"), bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    with open(os.path.join(bench_dir, "configs",
                           "granite4h_micro_serve.json")) as fh:
        cfg = json.load(fh)
    cfg.update(TOY, name="toy_granite")
    cfg["server"] = {"max_total_len": 64, "pool_sizes": [4],
                     "admit_sizes": [1, 2], "prefill_buckets": [8, 16],
                     "spec": False, "eos_id": None, "num_pages": 64,
                     "page_size": 4}
    cfg["check"] = {"sample": 3}
    cfg["limits"] = {"served_gap_mean": 1e-3}
    with open(os.path.join(bench_dir, "configs", "toy_granite.json"),
              "w") as fh:
        json.dump(cfg, fh)
    with open(os.path.join(bench_dir, "traffic",
                           "closed64_chat.json")) as fh:
        tr = json.load(fh)
    tr.update(clients=4, block=4, requests=4000, max_total=64,
              prompt_len={"dist": "lognormal", "median": 8, "sigma": 0.6,
                          "min": 3, "max": 16},
              output_len={"dist": "lognormal", "median": 8, "sigma": 0.5,
                          "min": 4, "max": 16},
              trace_delay_s=0.1, trace_seconds=0.3)
    with open(os.path.join(bench_dir, "traffic", "toy_chat4.json"),
              "w") as fh:
        json.dump(tr, fh)
    bench["configs"].append({
        "name": "toy_granite", "source": cfg["source"], "reduced": [],
        "file": "chipbench/configs/toy_granite.json", "why": "toy"})
    bench["workloads"].append({
        "name": "toy_granite_chat4", "config": "toy_granite",
        "traffic": "toy_chat4", "chips": 1, "why": "toy"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"] = m["workloads"] + ["toy_granite_chat4"]
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as fh:
        json.dump(bench, fh)
    return tmp


def _run(root, capfd, *argv):
    from chipbench import run
    rc = run.main(list(argv), root=root)
    out = capfd.readouterr()
    lines = [ln for ln in out.out.splitlines() if ln.strip()]
    return rc, (json.loads(lines[-1]) if rc == 0 and lines else None), out


@pytest.mark.parametrize("trace", [0, 1])
def test_toy_chat_end_to_end(toy_root, capfd, trace):
    rc, line, out = _run(toy_root, capfd, "--workload", "toy_granite_chat4",
                         "--seed", "3000000019", "--seconds", "2.0",
                         "--trace", str(trace))
    assert rc == 0, out.err
    assert line["failed"] == 0, out.err
    assert line["attempted"] > 4
    c = line["compared"]
    assert c["malformed_streams"]["value"] == 0
    # float32 program against the float32 reference: rounding only
    assert c["served_gap_mean"]["value"] <= 1e-3
    if not trace:
        # (``compiles_in_window`` differences a bounded ring of events: in
        # one pytest process with others it can read low — PERF.md section 7)
        assert set(line["metrics"]) == {"serve_tok_s", "setup_s"}
        return
    got = line["metrics"]
    assert got["sched_occupancy_pct.granite"]["value"] > 50.0
    assert got["tpot_p50_ms.granite"]["value"] > 0.0
    assert got["ttft_p95_ms.granite"]["value"] > 0.0
    # a device number never reads on the CPU
    for name in ("step_device_ms.granite", "step_hbm_roofline_pct.granite",
                 "ssm_state_roofline_pct", "step_ssm_state_pct",
                 "device_idle_pct.granite", "serve_mfu_pct.granite",
                 "admit_scan_pct"):
        assert name not in got


def test_traffic_is_the_issues():
    from chipbench import harness
    gen = harness.load_by_path(os.path.join(
        REPO, "chipbench", "generators", "closed_loop.py"), "g")
    spec = harness.read_json(os.path.join(
        REPO, "chipbench", "traffic", "closed64_chat.json"))
    assert spec["clients"] == spec["block"] == 64
    a, b = (gen.make(spec, s, 100352) for s in (1, 3000000019))
    # the same multiset of prompt lengths and of output lengths a block
    # (768 + 256 = max_total: no pair is cut)
    for key in (lambda r: r["prompt"].size, lambda r: r["max_new"]):
        lens = lambda t: sorted(key(r) for r in t["requests"][64:128])
        assert lens(a) == lens(b)
    sizes = [r["prompt"].size for r in a["requests"]]
    assert min(sizes) >= 16 and max(sizes) <= 768
    assert max(int(r["prompt"].max()) for r in a["requests"][:64]) > 90000
    assert all(r["prompt"].size + r["max_new"] <= 1024
               for r in a["requests"])


def test_configuration_is_the_published_one():
    """Every number of the catalog row's ``config`` under the same key,
    nothing reduced, the float32 state among the assumptions."""
    from chipbench import harness
    cfg = harness.read_json(os.path.join(
        REPO, "chipbench", "configs", "granite4h_micro_serve.json"))
    assert cfg["reduced"] == [] and cfg["state_dtype"] == "float32"
    assert "state_dtype" in cfg["assumed"]
    want = dict(hidden_size=2048, num_hidden_layers=40, vocab_size=100352,
                num_attention_heads=32, num_key_value_heads=8,
                shared_intermediate_size=8192, mamba_n_heads=64,
                mamba_d_head=64, mamba_d_state=128, mamba_d_conv=4,
                mamba_expand=2, mamba_chunk_size=256, mamba_n_groups=1,
                attention_multiplier=0.015625, embedding_multiplier=12,
                residual_multiplier=0.22, logits_scaling=8,
                num_local_experts=0, rms_norm_eps=1e-05,
                position_embedding_type="nope", tie_word_embeddings=True)
    for k, v in want.items():
        assert cfg[k] == v, k
    assert cfg["layer_types"].count("attention") == 4
    assert [i for i, t in enumerate(cfg["layer_types"])
            if t == "attention"] == [5, 15, 25, 35]
    srv = cfg["server"]
    assert srv["pool_sizes"] == [64] and srv["num_pages"] * 16 == 64 * 1024


def test_shape_functions_at_the_published_sizes():
    from chipbench import shapes_granite as sh
    cfg = _real_cfg()
    assert sh.mamba_params(cfg) == 76182976
    assert sh.attention_params(cfg) == 60821504
    assert sh.total_params(cfg) == 3191396096
    assert sh.state_bytes_per_slot(cfg) == 36 * 64 * 64 * 128 * 4
    assert sh.tail_bytes_per_slot(cfg) == 36 * 3 * 4352 * 2
    assert sh.kv_bytes_per_token(cfg) == 8192
    # ISSUE 33's reckoning: 16.4 GB a step of 64 live slots, 59% the state
    step = sh.decode_step_min_bytes(cfg, 64, 64 * 400)
    assert round(step / 1e9, 1) == 16.4
    b, f = sh.ssm_state_min(cfg, 64)
    assert 0.58 < b / step < 0.60
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    assert sh.floor_seconds((b, f), peaks) == b / 819e9     # bytes-bound


def test_program_declares_what_the_shapes_count():
    """The program's own parameter shapes and per-slot state bytes are the
    benchmark's counts (``shapes_granite`` knows nothing of the program)."""
    from chipbench import granite, harness
    from chipbench import shapes_granite as sh
    config = harness.read_json(os.path.join(
        REPO, "chipbench", "configs", "granite4h_micro_serve.json"))
    _, model_cfg = granite.build(config)
    shapes = granite.shapes(model_cfg)
    assert sum(int(np.prod(s)) for s, _ in shapes.values()) \
        == sh.total_params(_real_cfg())
    assert shapes["r0_in_weight"] == ((5, 2048, 8512), "bfloat16")
    assert shapes["r0_a_log"][1] == "float32"


def test_seeded_weights_follow_the_recurrences_own_init():
    from chipbench import weights_granite
    shapes = {"r0_a_log": ((3, 64), "float32"),
              "r0_dt_bias": ((3, 64), "float32"),
              "r0_d_skip": ((3, 64), "float32"),
              "r0_in_weight": ((3, 64, 40), "float32"),
              "r0_norm1_gamma": ((3, 64), "float32"),
              "wte_weight": ((96, 64), "float32")}
    w = {k: np.asarray(v) for k, v in weights_granite.make(
        shapes, 3000000019, {"embed_gain": 2.0}).items()}
    again = weights_granite.make(shapes, 3000000019, {"embed_gain": 2.0})
    assert all(np.array_equal(w[k], again[k]) for k in w)
    a = np.exp(w["r0_a_log"])
    assert a.min() >= 1.0 and a.max() <= 16.0
    dt = np.log1p(np.exp(w["r0_dt_bias"]))
    assert 0.00099 <= dt.min() and dt.max() <= 0.1001
    assert np.all(w["r0_d_skip"] == 1.0)
    assert abs(w["r0_in_weight"].std() - 1 / 8) < 0.01
    assert abs(w["wte_weight"].std() - 2.0 / 8) < 0.02
    assert 0.9 < w["r0_norm1_gamma"].min() < 1.0 < w["r0_norm1_gamma"].max()


@pytest.mark.parametrize("control", ["int8", "bf16_state"])
def test_controls_part_from_the_reference(control):
    """Each control is the reference with one argument changed, and reads
    differently: int8 far, a bfloat16 state a little."""
    import jax.numpy as jnp
    from chipbench import reference_granite as ref
    from chipbench import weights_granite
    from mxnet_tpu.models import granite_hybrid as gh
    _, cfg = gh.granite_hybrid_tiny()
    w = weights_granite.make(gh.parameter_shapes(cfg), 5,
                             {"embed_gain": 0.25, "final_norm_gain": 14.0})
    rcfg = {k: getattr(cfg, k) for k in cfg.__dataclass_fields__}
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 96, 40),
                       jnp.int32)
    z = np.asarray(ref.full_logits(w, rcfg, toks))
    zc = np.asarray(ref.full_logits(w, rcfg, toks, control=control))
    err = np.abs(z - zc).max()
    assert err > (1e-2 if control == "int8" else 1e-5)
    gap, gap_c = ref.served_gaps(w, rcfg, toks, jnp.asarray(z.argmax(-1)),
                                 control=control)
    assert float(np.abs(np.asarray(gap)).max()) == 0.0
    assert float(np.asarray(gap_c).min()) >= 0.0


def test_bf16_state_control_rounds_with_an_op_the_chip_keeps():
    """``astype`` to bfloat16 and back is a pair the chip's compiler drops
    (it is allowed excess precision): the control then serves the float32
    reference's own tokens and reads exactly 0.0, as PR 33's first readings
    did.  ``lax.reduce_precision`` is an operation of its own and stays."""
    import jax
    import jax.numpy as jnp
    from chipbench import reference_granite as ref
    from chipbench import weights_granite
    from mxnet_tpu.models import granite_hybrid as gh
    _, cfg = gh.granite_hybrid_tiny()
    w = weights_granite.make(gh.parameter_shapes(cfg), 5, None)
    rcfg = {k: getattr(cfg, k) for k in cfg.__dataclass_fields__}
    lw = {k[3:]: v[0] for k, v in w.items() if k.startswith("r0_")}
    h = jnp.ones((6, cfg.hidden_size), jnp.float32)
    text = {c: str(jax.make_jaxpr(lambda lw, h: ref.mamba_mixer(
        rcfg, lw, h, ref.mm_f32, c, ()))(lw, h)) for c in (None,
                                                          "bf16_state")}
    assert "reduce_precision" in text["bf16_state"]
    assert "reduce_precision" not in text[None]
    assert "bf16" not in text["bf16_state"]


def _planted(**over):
    run = {"config": {"executables": {"step": "jit_step"}, "dtype":
                      "bfloat16"},
           "geometry": _real_cfg(),
           "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
           "window": {"t_open": 10.0, "t_close": 40.0, "t_end": 41.0},
           "end_to_end": {"tpot_p50_ms": 36.5, "ttft_p95_ms": 410.0},
           "records": [{"submit": 11.0, "times": [11.2, 11.3, 11.4],
                        "error": None, "prompt_len": 300, "max_new": 3,
                        "queue_wait_s": 0.05}],
           "server_stats": {"state_bytes_per_slot": 76437504},
           "counters": {"steps": 800, "occupied_lane_steps": 51200.0,
                        "num_slots": 64, "prompt_tokens": 90000,
                        "tokens_in_window": 51000},
           "trace": {"busy_s": 2.9, "idle_pct": 3.0, "modules": {
               "jit_step": {"seconds": 2.4, "runs": 80,
                            "whole_seconds": 2.4, "whole_runs": 80},
               "jit_admit": {"seconds": 0.58, "runs": 4,
                             "whole_seconds": 0.58, "whole_runs": 4}}}}
    run.update(over)
    return run


def _reader(name):
    from chipbench import harness
    return harness.load_by_path(os.path.join(
        REPO, "chipbench", "metrics", name + ".py"),
        "m_" + name.replace(".", "_")).read


NEW = [m["name"] for m in json.load(open(os.path.join(
    REPO, "BENCHMARK.json")))["per_layer"] if m.get("workloads") == [CELL]]


def test_every_new_metric_has_a_reader_and_the_cell_lists_it():
    assert len(NEW) == 19
    for name in NEW:
        assert callable(_reader(name))
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    tok = next(m for m in bench["end_to_end"] if m["name"] == "serve_tok_s")
    assert tok["workloads"][-1] == CELL
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "closed64_chat"
    assert len(cell["why"]) <= 200


@pytest.mark.parametrize("name", NEW)
def test_reader_has_nothing_to_read_of_a_bare_run(name):
    """No trace, no peaks, no counters (an older program, a CPU run):
    ``None``, never 0 and never a raise."""
    bare = _planted(trace=None, peaks=None, server_stats={},
                    end_to_end={}, records=[],
                    counters={"steps": 0, "occupied_lane_steps": 0.0})
    assert _reader(name)(bare) is None


@pytest.mark.parametrize("name,want", [
    ("sched_occupancy_pct.granite", 100.0), ("tpot_p50_ms.granite", 36.5),
    ("ttft_p95_ms.granite", 410.0), ("device_idle_pct.granite", 3.0),
    ("admit_device_pct.granite", 20.0), ("step_device_ms.granite", 30.0),
    ("queue_wait_p95_ms.granite", 50.0)])
def test_reader_on_a_planted_run(name, want):
    assert _reader(name)(_planted()) == pytest.approx(want)


def test_admit_stall_on_planted_spans(monkeypatch):
    """Steps 1, 2, 3 arrive 40 ms apart; step 5, with an admission's ``seq``
    before it, 63 ms after step 3: the admission cost the stream 23 ms."""
    from mxnet_tpu import telemetry
    rows, t = [], 11.0
    for seq, gap in ((1, 0.0), (2, 0.04), (3, 0.04), (5, 0.063)):
        t += gap
        rows.append(("mx:serve:step", t - 0.03, t - 0.029, seq, None, {}))
        rows.append(("mx:serve:route", t, t + 0.001, None, seq, {}))
    monkeypatch.setattr(telemetry, "spans", lambda name=None: rows)
    assert _reader("admit_stall_ms.granite")(_planted()) \
        == pytest.approx(23.0)


def test_shares_on_planted_regions(monkeypatch):
    """With planted region seconds the shares come out of the shape
    functions: under 100, above 0; a region the program does not have is
    left out, not 0."""
    from mxnet_tpu import profiler
    step = {"mx.ssm_state": 15.0e-3, "mx.ssm_conv": 1.5e-3,
            "mx.ssm_gate": 1.5e-3, "mx.attn": 0.6e-3, "mx.kv_write": 0.3e-3,
            "mx.dense": 9.0e-3, "mx.head": 0.6e-3, "unscoped": 1.5e-3}
    table = {"jit_step": {"runs": 80, "run_seconds": 2.4, "regions": {
                 k: v * 80 for k, v in step.items()}},
             "jit_admit": {"runs": 4, "run_seconds": 0.58, "regions": {
                 "mx.ssm_scan": 0.058, "mx.dense": 0.522}}}
    monkeypatch.setattr(profiler, "device_regions", lambda: table)
    run = _planted()
    for name in ("ssm_state_roofline_pct", "step_hbm_roofline_pct.granite",
                 "serve_mfu_pct.granite"):
        v = _reader(name)(run)
        assert v is not None and 0.0 < v < 100.0, (name, v)
    # 64 live slots' states read and written once: 9.66 GB at 819 GB/s =
    # 11.8 ms of the planted 15
    assert _reader("ssm_state_roofline_pct")(run) == pytest.approx(
        100 * 2 * 64 * 75497472 / 819e9 / 15.0e-3)
    assert _reader("step_ssm_state_pct")(run) == pytest.approx(50.0)
    assert _reader("step_ssm_conv_pct")(run) == pytest.approx(5.0)
    assert _reader("step_ssm_gate_pct")(run) == pytest.approx(5.0)
    assert _reader("step_attention_pct.granite")(run) == pytest.approx(3.0)
    assert _reader("step_dense_pct.granite")(run) == pytest.approx(32.0)
    assert _reader("step_unscoped_pct.granite")(run) == pytest.approx(5.0)
    assert _reader("admit_scan_pct")(run) == pytest.approx(10.0)
    del table["jit_admit"], table["jit_step"]["regions"]["mx.ssm_gate"]
    assert _reader("admit_scan_pct")(run) is None
    assert _reader("step_ssm_gate_pct")(run) is None
