"""CPU tests of the pangu cell's benchmark files (``chipbench/``): the entry
end to end at a toy width, dropped into a temporary copy of ``chipbench/`` as
NEW files plus ``BENCHMARK.json`` entries (the drop-in pattern of
``test_chipbench.py``), the traffic, the configuration against the catalog's
published keys, the shape functions against the configuration's own sums,
and every new reader on a planted run — ``None`` where there is nothing to
read.
"""
import json
import os
import shutil

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "pangu_ultra_serve_sessions24"

TOY = dict(
    hidden_size=32, intermediate_size=64, num_attention_heads=4,
    num_key_value_heads=4, q_lora_rank=16, kv_lora_rank=16,
    qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8, n_routed_experts=4,
    num_experts_per_tok=4, moe_intermediate_size=16, vocab_size=96,
    dtype="float32")


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("chipbench_pangu_toy"))
    bench_dir = os.path.join(tmp, "chipbench")
    shutil.copytree(os.path.join(REPO, "chipbench"), bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    with open(os.path.join(bench_dir, "configs",
                           "pangu_ultra_moe_serve.json")) as fh:
        cfg = json.load(fh)
    cfg.update(TOY, name="toy_pangu")
    cfg["held"].update(router_experts=16, first_expert=4)
    cfg["server"] = {"max_total_len": 192, "pool_sizes": [4],
                     "admit_sizes": [1], "prefill_buckets": [8, 32],
                     "spec": False, "eos_id": None, "num_pages": 256,
                     "page_size": 4}
    cfg["check"] = {"sample": 2, "rows": 16}
    cfg["limits"] = {"served_gap_mean": 1e-3}
    with open(os.path.join(bench_dir, "configs", "toy_pangu.json"),
              "w") as fh:
        json.dump(cfg, fh)
    with open(os.path.join(bench_dir, "traffic",
                           "doc_sessions24_ctx32k.json")) as fh:
        tr = json.load(fh)
    tr.update(clients=4, block=4, requests=2000, max_total=192,
              doc_len={"dist": "uniform", "min": 64, "max": 128,
                       "round_to": 4},
              question_len={"dist": "lognormal", "median": 6, "sigma": 0.4,
                            "min": 4, "max": 8},
              answer_len={"dist": "lognormal", "median": 8, "sigma": 0.5,
                          "min": 4, "max": 12},
              trace_delay_s=0.1, trace_seconds=0.3)
    with open(os.path.join(bench_dir, "traffic", "toy_sessions4p.json"),
              "w") as fh:
        json.dump(tr, fh)
    bench["configs"].append({
        "name": "toy_pangu", "source": cfg["source"],
        "reduced": cfg["reduced"],
        "file": "chipbench/configs/toy_pangu.json", "why": "toy"})
    bench["workloads"].append({
        "name": "toy_pangu_sessions4", "config": "toy_pangu",
        "traffic": "toy_sessions4p", "chips": 1, "why": "toy"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"] = m["workloads"] + ["toy_pangu_sessions4"]
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
def test_toy_sessions_end_to_end(toy_root, capfd, trace):
    rc, line, out = _run(toy_root, capfd, "--workload",
                         "toy_pangu_sessions4", "--seed", "3000000019",
                         "--seconds", "2.0", "--trace", str(trace))
    assert rc == 0, out.err
    assert line["correct"] is True and line["failed"] == 0, out.err
    assert line["attempted"] > 4
    c = line["compared"]
    assert c["compiles_in_window"]["value"] == 0
    # no prefill longer than one question chunk an admission
    assert c["window_chunk_tokens"]["value"] == 8
    assert c["served_gap_mean"]["value"] is not None
    if not trace:
        assert set(line["metrics"]) == {"serve_tok_s", "setup_s"}
        return
    # a device number never reads on the CPU: every new metric is left out
    assert not any(name.endswith(".pangu") for name in line["metrics"])


def test_traffic_holds_the_cells_documents():
    """24 documents, the stratified quantiles of uniform 16,384-32,768 in
    whole pages, 589,824 tokens; the same multiset every seed; questions of
    32-128 and answers of 64-256 tokens; ids over the held 19,200."""
    from chipbench import harness
    gen = harness.load_by_path(os.path.join(
        REPO, "chipbench", "generators", "doc_sessions.py"), "g")
    spec = harness.read_json(os.path.join(
        REPO, "chipbench", "traffic", "doc_sessions24_ctx32k.json"))
    spec = dict(spec, requests=48)
    a, b = gen.make(spec, 1, 19200), gen.make(spec, 3000000019, 19200)
    lens = lambda t: sorted(d.size for d in t["documents"])
    assert lens(a) == lens(b) and len(a["documents"]) == 24
    assert all(n % 16 == 0 and 16384 <= n <= 32768 for n in lens(a))
    assert sum(lens(a)) == 589824
    assert all(32 <= r["question"].size <= 128 and 64 <= r["max_new"] <= 256
               for r in a["requests"][24:])
    assert max(d.max() for d in a["documents"]) < 19200
    assert spec["max_total"] == 33152 and spec["block"] == 24


def _config():
    from chipbench import harness
    return harness.read_json(os.path.join(
        REPO, "chipbench", "configs", "pangu_ultra_moe_serve.json"))


def _real_cfg():
    from chipbench import pangu
    return pangu.reference_config(_config())


def test_configuration_holds_the_published_widths():
    """Every number of the catalog's row under the same key but the keys in
    ``reduced``; no width among those; the cut as the file states it."""
    cfg = _config()
    published = dict(
        attention_bias=False, hidden_act="silu", hidden_size=7680,
        intermediate_size=18432, kv_lora_rank=512,
        max_position_embeddings=131072, model_type="pangu_ultra_moe",
        moe_intermediate_size=2048, n_shared_experts=1, norm_topk_prob=True,
        num_attention_heads=128, num_experts_per_tok=8,
        num_key_value_heads=128, num_nextn_predict_layers=1,
        q_lora_rank=1536, qk_nope_head_dim=128, qk_rope_head_dim=64,
        rms_norm_eps=1e-05, rope_theta=25600000, routed_scaling_factor=2.5,
        sandwich_norm=True, tie_word_embeddings=False, v_head_dim=128)
    assert {k: cfg[k] for k in published} == published
    assert sorted(cfg["reduced"]) == sorted([
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size"])
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"],
            cfg["n_routed_experts"], cfg["vocab_size"]) == (5, 1, 16, 19200)
    assert cfg["published"]["n_routed_experts"] == 256 \
        and cfg["held"]["router_experts"] == 256
    assert "mtp" in cfg["left_out"] and cfg["server"]["spec"] is False
    assert cfg["init"]["router_pairs"] is True \
        and cfg["held"]["first_expert"] % 2 == 0
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"]
                 if c["name"] == "pangu_ultra_moe_serve")
    assert entry["source"] == cfg["source"] \
        and sorted(entry["reduced"]) == sorted(cfg["reduced"])


def test_shapes_count_the_configuration():
    """The configuration's own sums: 196.58M of attention a layer, 47.19M an
    expert, 4,919M parameters resident = 9.84 GB at two bytes; a latent row
    of 1,152 B carries 278.5k operations a head set; the program declares
    the same count."""
    from chipbench import pangu
    from chipbench import shapes_pangu as sh
    cfg = _real_cfg()
    assert round(sh.attention_params(cfg) / 1e6, 2) == 196.58
    assert round(sh.expert_params(cfg) / 1e6, 2) == 47.19
    assert round(sh.total_params(cfg) / 1e6) == 4919
    assert round(sh.total_params(cfg) * sh.BYTES / 1e9, 2) == 9.84
    assert sh.latent_row_bytes(cfg) == 1152
    b, f = sh.latent_walk_min(cfg, 1)
    assert (b, f) == (1152, 2 * 128 * (576 + 512))
    _, model_cfg = pangu.build(_config())
    declared = sum(int(np.prod(s)) for s, _ in
                   pangu.shapes(model_cfg).values())
    assert 0 < declared - sh.total_params(cfg) < 2e5
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    # a row's operations take longer than its bytes at the peaks: 242 an
    # byte of 1,152 useful against the ridge of 240
    assert sh.floor_seconds(sh.latent_walk_min(cfg, 600000), peaks) \
        == sh.latent_walk_min(cfg, 600000)[1] / 197e12
    lo = sh.decode_step_min_bytes(cfg, 20, 5 * 24 * 20000)
    hi = sh.decode_step_min_bytes(cfg, 40, 5 * 24 * 30000)
    assert sh.fixed_params(cfg) * 2 < lo < hi


def _planted(**over):
    cfg = _real_cfg()
    run = {"config": {"executables": {"step": "jit_step"}, "dtype":
                      "bfloat16"},
           "geometry": cfg, "peaks": {"hbm_bytes_per_s": 819e9,
                                      "bf16_flops_per_s": 197e12},
           "window": {"t_open": 10.0, "t_close": 40.0, "t_end": 41.0},
           "end_to_end": {}, "records": [],
           "server_stats": {"moe_experts_touched_share": 0.5,
                            "moe_tokens_per_expert_step": 0.75},
           "counters": {"steps": 2000, "occupied_lane_steps": 47520.0,
                        "num_slots": 24, "tokens_in_window": 48000,
                        "prompt_tokens": 1000000,
                        "prompt_tokens_cached": 990000,
                        "dispatch": {"step_dispatches": 2000,
                                     "latent_rows_walked": 2000 * 3000000,
                                     "chunk_dispatches": 100}},
           "trace": {"busy_s": 2.9, "idle_pct": 3.0, "modules": {
               "jit_step": {"seconds": 2.0, "runs": 100,
                            "whole_seconds": 2.0, "whole_runs": 100},
               "jit_chunk": {"seconds": 0.5, "runs": 10,
                             "whole_seconds": 0.5, "whole_runs": 10}}}}
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
        "step_device_ms.pangu", "step_latent_attn_pct.pangu",
        "latent_walk_roofline_pct.pangu", "step_hbm_roofline_pct.pangu",
        "admit_latent_attn_pct.pangu", "serve_mfu_pct.pangu"])
    for name in NEW:
        assert callable(_reader(name))
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    tok = next(m for m in bench["end_to_end"] if m["name"] == "serve_tok_s")
    assert CELL in tok["workloads"]
    assert all(m["moves"] == "serve_tok_s" for m in bench["per_layer"]
               if m["name"] in NEW)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200 \
        and cell["traffic"] == "doc_sessions24_ctx32k"


def test_the_int8_control_in_the_programs_place_is_not_correct(toy_root):
    """The reading the limit is set against, through the harness's own
    comparison: with the plain reference computed in int8 put where the
    program's tokens were, the run comes out not ``correct``, by the
    served-token gap and by no other of the cell's limits."""
    import time
    from chipbench import harness
    bench = harness.read_json(os.path.join(toy_root, "BENCHMARK.json"))
    cell = next(w for w in bench["workloads"]
                if w["name"] == "toy_pangu_sessions4")
    ctx = harness.Context(toy_root, bench, cell, 2654435761, 2.0, 0,
                          time.time())
    ctx.control = True      # as ``readings.py`` sets it
    harness.claim_device(ctx)
    out = ctx.entry().run(ctx)
    assert harness.result_line(ctx, out)["correct"] is True
    out["compared"]["served_gap_mean"]["value"] = \
        out["control"]["served_gap_mean"]
    line = harness.result_line(ctx, out)
    assert line["correct"] is False
    over = [k for k, c in line["compared"].items()
            if c["value"] is None or c["value"] > c["limit"]]
    assert over == ["served_gap_mean"]


@pytest.mark.parametrize("name", NEW)
def test_reader_has_nothing_to_read_of_a_bare_run(name):
    """No trace, no peaks, no counters (an older program, a CPU run):
    ``None``, never 0 and never a raise."""
    bare = _planted(trace=None, peaks=None, server_stats={},
                    counters={"steps": 0, "occupied_lane_steps": 0.0})
    assert _reader(name)(bare) is None


def test_readers_on_planted_regions(monkeypatch):
    """With planted region seconds the shares come out of the shape
    functions and the walk counter: under 100, above 0; a program without
    the walk's counter (the parent) reads nothing."""
    from mxnet_tpu import profiler
    step = {"mx.latent_attn": 6.0e-3, "mx.latent_write": 0.1e-3,
            "mx.moe_experts": 5.0e-3, "mx.dense": 7.9e-3}
    chunk = {"mx.latent_attn": 30.0e-3, "mx.latent_gather": 2.0e-3,
             "mx.moe_experts": 10.0e-3, "mx.dense": 8.0e-3}
    monkeypatch.setattr(profiler, "device_regions", lambda: {
        "jit_step": {"runs": 100, "run_seconds": 1.9,
                     "regions": {k: v * 100 for k, v in step.items()}},
        "jit_chunk": {"runs": 10, "run_seconds": 0.5,
                      "regions": {k: v * 10 for k, v in chunk.items()}}})
    run = _planted()
    assert _reader("step_device_ms.pangu")(run) == pytest.approx(20.0)
    assert _reader("step_latent_attn_pct.pangu")(run) == pytest.approx(
        100 * 6.1 / 19.0)
    assert _reader("admit_latent_attn_pct.pangu")(run) == pytest.approx(64.0)
    for name in ("latent_walk_roofline_pct.pangu",
                 "step_hbm_roofline_pct.pangu", "serve_mfu_pct.pangu"):
        v = _reader(name)(run)
        assert v is not None and 0.0 < v < 100.0, (name, v)
    # the walk's floor: 3M rows of 1,152 B and 278.5k operations each,
    # the operations longer, over 6 ms
    assert _reader("latent_walk_roofline_pct.pangu")(run) == pytest.approx(
        100 * 3e6 * 2 * 128 * 1088 / 197e12 / 6e-3)
    parent = _planted(counters={"steps": 2000, "occupied_lane_steps": 1.0,
                                "dispatch": {"step_dispatches": 2000}})
    for name in ("latent_walk_roofline_pct.pangu",
                 "step_hbm_roofline_pct.pangu", "serve_mfu_pct.pangu"):
        assert _reader(name)(parent) is None
