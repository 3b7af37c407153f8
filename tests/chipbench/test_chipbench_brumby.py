"""CPU tests of the brumby cell's benchmark files (``chipbench/``): the entry
end to end at a toy width, dropped into a temporary copy of ``chipbench/`` as
NEW files plus ``BENCHMARK.json`` entries (the drop-in pattern of
``test_chipbench.py``), the configuration against the catalog's published
keys, the shape functions against the configuration's own sums, every new
reader on a planted run — ``None`` where there is nothing to read — and the
two controls in the program's place, which the comparison must refuse.
"""
import json
import os
import shutil
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "brumby14b_serve_long24"
TOY_CELL = "toy_brumby_long4"

TOY = dict(hidden_size=64, intermediate_size=96, num_attention_heads=4,
           num_key_value_heads=2, head_dim=32, num_hidden_layers=2,
           vocab_size=96, rope_theta=10000.0, dtype="float32")


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("chipbench_brumby_toy"))
    bench_dir = os.path.join(tmp, "chipbench")
    shutil.copytree(os.path.join(REPO, "chipbench"), bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    with open(os.path.join(bench_dir, "configs",
                           "brumby_14b_serve.json")) as fh:
        cfg = json.load(fh)
    cfg.update(TOY, name="toy_brumby")
    cfg["init"] = {"sink": 32.0, "gate_logits": [3.0, 6.0],
                   "gate_noise": 1.0}
    cfg["server"] = {"max_total_len": 128, "pool_sizes": [4],
                     "admit_sizes": [1], "prefill_buckets": [8, 32],
                     "spec": False, "eos_id": None}
    cfg["check"] = {"sample": 2, "probe_prompt": 40, "probe_new": 48,
                    "probes": 3}
    cfg["limits"] = {"served_gap_mean": 1e-3, "state_rel_err": 1e-3}
    with open(os.path.join(bench_dir, "configs", "toy_brumby.json"),
              "w") as fh:
        json.dump(cfg, fh)
    with open(os.path.join(bench_dir, "traffic",
                           "closed24_long.json")) as fh:
        tr = json.load(fh)
    tr.update(clients=4, block=8, requests=2000, max_total=128,
              prompt_len={"dist": "lognormal", "median": 16, "sigma": 0.5,
                          "min": 8, "max": 40},
              output_len={"dist": "lognormal", "median": 8, "sigma": 0.5,
                          "min": 4, "max": 16},
              trace_delay_s=0.1, trace_seconds=0.3)
    with open(os.path.join(bench_dir, "traffic", "toy_long4.json"),
              "w") as fh:
        json.dump(tr, fh)
    bench["configs"].append({
        "name": "toy_brumby", "source": cfg["source"],
        "reduced": cfg["reduced"],
        "file": "chipbench/configs/toy_brumby.json", "why": "toy"})
    bench["workloads"].append({
        "name": TOY_CELL, "config": "toy_brumby", "traffic": "toy_long4",
        "chips": 1, "why": "toy"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"] = m["workloads"] + [TOY_CELL]
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
def test_toy_long_end_to_end(toy_root, capfd, trace):
    rc, line, out = _run(toy_root, capfd, "--workload", TOY_CELL,
                         "--seed", "3000000019", "--seconds", "2.0",
                         "--trace", str(trace))
    assert rc == 0, out.err
    assert line["correct"] is True and line["failed"] == 0, out.err
    assert line["attempted"] > 4
    c = line["compared"]
    assert c["compiles_in_window"]["value"] == 0
    assert c["served_gap_mean"]["value"] is not None
    assert c["state_rel_err"]["value"] is not None
    if not trace:
        assert set(line["metrics"]) == {"serve_tok_s", "setup_s"}
        return
    # a device number never reads on the CPU: every new metric but the
    # host clock's time per output token is left out
    assert set(line["metrics"]) & set(NEW) == {"tpot_p50_ms.brumby"}


@pytest.fixture(scope="module")
def controlled(toy_root):
    """One toy run with the controls read beside the program (as
    ``readings.py`` runs it)."""
    from chipbench import harness
    bench = harness.read_json(os.path.join(toy_root, "BENCHMARK.json"))
    cell = next(w for w in bench["workloads"] if w["name"] == TOY_CELL)
    ctx = harness.Context(toy_root, bench, cell, 2654435761, 2.0, 0,
                          time.time())
    ctx.control = True
    harness.claim_device(ctx)
    return ctx, ctx.entry().run(ctx)


@pytest.mark.parametrize("control,limit", [
    ("bf16_state", "state_rel_err"), ("bf16_step", "state_rel_err"),
    ("int8", "state_rel_err")])
def test_a_control_in_the_programs_place_is_not_correct(controlled, control,
                                                        limit):
    """Through the harness's own comparison: with a control's reading put
    where the program's was — the state rounded to bfloat16 after every
    token, or only at each decode step's write (the prompt in float32), or
    everything in int8 — the run comes out not ``correct``, by that limit
    alone.  (At this width no served token parts the controls from float32:
    a vocabulary of 96 leaves the best logit far ahead.)"""
    from chipbench import harness
    ctx, out = controlled
    assert harness.result_line(ctx, out)["correct"] is True
    out = dict(out, compared={k: dict(v) for k, v in out["compared"].items()})
    out["compared"][limit]["value"] = out["control"][control][limit]
    line = harness.result_line(ctx, out)
    assert line["correct"] is False
    over = [k for k, c in line["compared"].items()
            if c["value"] is None or c["value"] > c["limit"]]
    assert over == [limit]


def _config():
    from chipbench import harness
    return harness.read_json(os.path.join(
        REPO, "chipbench", "configs", "brumby_14b_serve.json"))


def _real_cfg():
    from chipbench import brumby
    return brumby.reference_config(_config())


def test_configuration_holds_the_published_widths():
    """Every number of the catalog's row under the same key but
    ``num_hidden_layers``, the one cut; what the equations assume and where
    the cut lies, written down."""
    cfg = _config()
    published = dict(
        attention_bias=False, head_dim=128, hidden_act="silu",
        hidden_size=5120, intermediate_size=17408,
        max_position_embeddings=32768, max_window_layers=40,
        model_type="brumby", num_attention_heads=40,
        num_key_value_heads=8, rms_norm_eps=1e-06, rope_scaling=None,
        rope_theta=1000000, sliding_window=None, tie_word_embeddings=False,
        use_sliding_window=False, vocab_size=151936)
    assert {k: cfg[k] for k in published} == published
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["num_hidden_layers"] == 8 \
        and cfg["published"]["num_hidden_layers"] == 40
    assert {"degree", "gate", "eps", "state_dtype",
            "gate_init"} <= set(cfg["assumed"])
    assert "5 chips" in cfg["deployment"] and "pipeline" in cfg["deployment"]
    assert cfg["server"]["pool_sizes"] == [24] \
        and cfg["server"]["spec"] is False
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"]
                 if c["name"] == "brumby_14b_serve")
    assert entry["source"] == cfg["source"] \
        and entry["reduced"] == cfg["reduced"]


def test_shapes_count_the_configuration():
    """34.08 MB of state a slot and layer (8 KV heads x 8,256 rows x (128 +
    1) x 4 B), 330.35M parameters a layer, 4,198M resident = 8.40 GB; a
    step's floor of 6.84 GB of weights and 13.08 GB of state at 24 slots is
    24.3 ms at 819 GB/s; the program declares the same count."""
    import numpy as np
    from chipbench import brumby
    from chipbench import shapes_brumby as sh
    cfg = _real_cfg()
    assert sh.state_bytes_per_slot_layer(cfg) == 34080768
    assert round(sh.layer_params(cfg) / 1e6, 2) == 330.35
    assert int(sh.total_params(cfg) / 1e6) == 4198
    assert round(sh.total_params(cfg) * sh.BYTES / 1e9, 2) == 8.40
    assert round(sh.step_params(cfg) * sh.BYTES / 1e9, 2) == 6.84
    assert round(2 * 24 * sh.state_bytes_per_slot(cfg) / 1e9, 2) == 13.09
    assert round(sh.decode_step_min_bytes(cfg, 24) / 819e9 * 1e3, 1) == 24.3
    _, model_cfg = brumby.build(_config())
    declared = sum(int(np.prod(s)) for s, _ in
                   brumby.shapes(model_cfg).values())
    assert declared == sh.total_params(cfg)
    # prefill in the attention form within a dispatch: the pairs' scores in
    # two dispatches of 64, the state's writes for all 128 tokens and its
    # reads for the 64 of a chunk that continues a prompt
    f = sh.retention_chunk_flops(cfg, 128, 2, 64)
    assert f == 8 * (2 * 64 * 65 / 2 * 40 * 512 + 128 * 8 * 8256 * 256
                     + 64 * 40 * 8256 * 256)


def _planted(**over):
    run = {"config": {"executables": {"step": "jit_step"}},
           "geometry": _real_cfg(),
           "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
           "window": {"t_open": 10.0, "t_close": 40.0, "t_end": 41.0},
           "end_to_end": {"tpot_p50_ms": 45.0}, "records": [],
           "server_stats": {},
           "counters": {"steps": 1000, "occupied_lane_steps": 23000.0,
                        "num_slots": 24, "tokens_in_window": 23000,
                        "prompt_tokens": 50000,
                        "dispatch": {"step_dispatches": 1000,
                                     "admit_dispatches": 30,
                                     "chunk_dispatches": 30,
                                     "admit_rows": 61440,
                                     "admit_tokens": 50000,
                                     "chunk_carried_tokens": 18000}},
           "trace": {"busy_s": 2.9, "idle_pct": 3.0, "modules": {
               "jit_step": {"seconds": 3.0, "runs": 100,
                            "whole_seconds": 3.0, "whole_runs": 100},
               "jit_chunk": {"seconds": 0.5, "runs": 5,
                             "whole_seconds": 0.5, "whole_runs": 5}}}}
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
    assert sorted(NEW) == sorted([
        "step_device_ms.brumby", "step_retention_pct",
        "retention_state_roofline_pct", "admit_retention_pct",
        "retention_chunk_mxu_roofline_pct", "step_hbm_roofline_pct.brumby",
        "serve_mfu_pct.brumby", "admit_device_pct.brumby",
        "admit_pad_token_pct.brumby", "tpot_p50_ms.brumby"])
    for name in NEW:
        assert callable(_reader(name))
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    tok = next(m for m in bench["end_to_end"] if m["name"] == "serve_tok_s")
    assert CELL in tok["workloads"]
    assert tok["workloads"][-1] == "granite4h_micro_serve_chat64"
    assert all(m["moves"] == "serve_tok_s" for m in bench["per_layer"]
               if m["name"] in NEW)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200 \
        and cell["traffic"] == "closed24_long"


@pytest.mark.parametrize("name", NEW)
def test_reader_has_nothing_to_read_of_a_bare_run(name):
    """No trace, no peaks, no counters (an older program, a CPU run):
    ``None``, never 0 and never a raise."""
    bare = _planted(trace=None, peaks=None, end_to_end={},
                    counters={"steps": 0, "occupied_lane_steps": 0.0})
    assert _reader(name)(bare) is None


def test_readers_on_planted_regions(monkeypatch):
    """With planted region seconds the shares come out of the shape
    functions and the server's admission counters: under 100, above 0; a
    run whose counters hold no prompt tokens reads no chunk roofline."""
    from chipbench import shapes_brumby as sh
    from mxnet_tpu import profiler
    step = {"mx.ssm_state": 20.0e-3, "mx.dense": 8.0e-3,
            "mx.qk_norm_rope": 0.5e-3, "mx.head": 1.5e-3}
    chunk = {"mx.ssm_scan": 40.0e-3, "mx.dense": 50.0e-3}
    admit = {"mx.ssm_scan": 10.0e-3, "mx.dense": 30.0e-3}
    monkeypatch.setattr(profiler, "device_regions", lambda: {
        "jit_step": {"runs": 100, "run_seconds": 3.0,
                     "regions": {k: v * 100 for k, v in step.items()}},
        "jit_admit": {"runs": 5, "run_seconds": 0.2,
                      "regions": {k: v * 5 for k, v in admit.items()}},
        "jit_chunk": {"runs": 5, "run_seconds": 0.5,
                      "regions": {k: v * 5 for k, v in chunk.items()}}})
    run = _planted()
    assert _reader("step_device_ms.brumby")(run) == pytest.approx(30.0)
    assert _reader("step_retention_pct")(run) == pytest.approx(
        100 * 20 / 30)
    assert _reader("admit_retention_pct")(run) == pytest.approx(
        100 * 50 / 130)
    # the traced stretch: 0.5 s of chunks in 2.9 s busy
    assert _reader("admit_device_pct.brumby")(run) == pytest.approx(
        100 * 0.5 / 2.9)
    assert _reader("admit_pad_token_pct.brumby")(run) == pytest.approx(
        100 * (61440 - 50000) / 61440)
    assert _reader("tpot_p50_ms.brumby")(run) == 45.0
    units = {m["name"]: m["unit"] for m in json.load(open(os.path.join(
        REPO, "BENCHMARK.json")))["per_layer"]}
    for name in NEW:
        v = _reader(name)(run)
        assert v is not None and v > 0.0, (name, v)
        assert units[name] != "%" or v < 100.0, (name, v)
    # a mean dispatch of 50,000 / 60 prompt tokens, 18,000 / 60 of them
    # carried, against 25 ms of the prefill region a run
    assert _reader("retention_chunk_mxu_roofline_pct")(run) == pytest.approx(
        100 * sh.retention_chunk_flops(
            _real_cfg(), 50000 / 60, 1, 18000 / 60) / 197e12 / 25e-3)
    # 23 live slots' states read and written over 20 ms
    assert _reader("retention_state_roofline_pct")(run) == pytest.approx(
        100 * 2 * 23 * 8 * 34080768 / 819e9 / 20e-3)
    bare = _planted(counters=dict(_planted()["counters"], dispatch={
        "step_dispatches": 1000, "admit_dispatches": 30,
        "chunk_dispatches": 30}))
    assert _reader("retention_chunk_mxu_roofline_pct")(bare) is None
