"""CPU tests of the dots3 cell's benchmark files (``chipbench/``): the entry
end to end at a toy width, dropped into a temporary copy of ``chipbench/`` as
NEW files plus ``BENCHMARK.json`` entries (the drop-in pattern of
``test_chipbench.py``), the generator, the shape functions, and every new
reader on a planted run — ``None`` where there is nothing to read.
"""
import json
import os
import shutil

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "dots3_note_serve_sessions32"

TOY = dict(
    hidden_size=32, intermediate_size=64, num_attention_heads=4,
    q_lora_rank=16, kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
    v_head_dim=8, index_n_heads=4, index_head_dim=8, index_topk=8,
    swa_num_attention_heads=2, swa_q_lora_rank=16, swa_kv_lora_rank=24,
    swa_qk_nope_head_dim=12, swa_qk_rope_head_dim=4, swa_v_head_dim=8,
    sliding_window_size=9, n_routed_experts=4, num_experts_per_tok=4,
    moe_intermediate_size=16, vocab_size=96, dtype="float32")


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("chipbench_dots3_toy"))
    bench_dir = os.path.join(tmp, "chipbench")
    shutil.copytree(os.path.join(REPO, "chipbench"), bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    with open(os.path.join(bench_dir, "configs",
                           "dots3_note_serve.json")) as fh:
        cfg = json.load(fh)
    cfg.update(TOY, name="toy_dots3")
    cfg["held"].update(router_experts=16, first_expert=4)
    cfg["server"] = {"max_total_len": 192, "pool_sizes": [4],
                     "admit_sizes": [1], "prefill_buckets": [8, 32],
                     "spec": False, "eos_id": None, "num_pages": 256,
                     "num_window_pages": 96, "page_size": 4}
    cfg["check"] = {"sample": 2, "rows": 16}
    cfg["limits"] = {"served_gap_mean": 1e-3}
    with open(os.path.join(bench_dir, "configs", "toy_dots3.json"),
              "w") as fh:
        json.dump(cfg, fh)
    with open(os.path.join(bench_dir, "traffic",
                           "doc_sessions32_ctx32k.json")) as fh:
        tr = json.load(fh)
    tr.update(clients=4, block=4, requests=2000, max_total=192,
              doc_len={"dist": "uniform", "min": 64, "max": 128,
                       "round_to": 4},
              question_len={"dist": "lognormal", "median": 6, "sigma": 0.4,
                            "min": 4, "max": 8},
              answer_len={"dist": "lognormal", "median": 8, "sigma": 0.5,
                          "min": 4, "max": 12},
              trace_delay_s=0.1, trace_seconds=0.3)
    with open(os.path.join(bench_dir, "traffic", "toy_sessions4.json"),
              "w") as fh:
        json.dump(tr, fh)
    bench["configs"].append({
        "name": "toy_dots3", "source": cfg["source"],
        "reduced": cfg["reduced"],
        "file": "chipbench/configs/toy_dots3.json", "why": "toy"})
    bench["workloads"].append({
        "name": "toy_dots3_sessions4", "config": "toy_dots3",
        "traffic": "toy_sessions4", "chips": 1, "why": "toy"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"] = m["workloads"] + ["toy_dots3_sessions4"]
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
                         "toy_dots3_sessions4", "--seed", "3000000019",
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
    # counters read on the CPU too; a device number never does
    got = line["metrics"]
    assert got["prefix_hit_token_pct"]["value"] > 80.0
    assert 0.0 < got["moe_experts_touched_pct"]["value"] <= 100.0
    assert got["moe_load_max_over_mean"]["value"] >= 1.0
    assert got["tpot_p50_ms.dots3"]["value"] > 0.0
    for name in ("step_device_ms.dots3", "step_hbm_roofline_pct.dots3",
                 "moe_experts_roofline_pct", "step_index_pct",
                 "device_idle_pct.dots3", "serve_mfu_pct.dots3"):
        assert name not in got


def test_generator_same_multiset_every_seed():
    from chipbench import harness
    gen = harness.load_by_path(os.path.join(
        REPO, "chipbench", "generators", "doc_sessions.py"), "g")
    spec = harness.read_json(os.path.join(
        REPO, "chipbench", "traffic", "doc_sessions32_ctx32k.json"))
    spec = dict(spec, requests=64)
    a, b = gen.make(spec, 1, 19008), gen.make(spec, 3000000019, 19008)
    lens = lambda t: sorted(d.size for d in t["documents"])
    assert lens(a) == lens(b) and len(a["documents"]) == 32
    assert sum(lens(a)) == 786432 and all(n % 16 == 0 for n in lens(a))
    assert 16384 <= min(lens(a)) and max(lens(a)) <= 32768
    pairs = lambda t: sorted((r["question"].size, r["max_new"])
                             for r in t["requests"][32:64])
    assert sorted(p[0] for p in pairs(a)) == sorted(p[0] for p in pairs(b))
    assert sorted(p[1] for p in pairs(a)) == sorted(p[1] for p in pairs(b))
    assert all(32 <= q <= 128 and 64 <= o <= 256 for q, o in pairs(a))
    assert any(not np.array_equal(x["question"], y["question"])
               for x, y in zip(a["requests"], b["requests"]))
    again = gen.make(spec, 1, 19008)
    assert all(np.array_equal(x, y) for x, y in
               zip(a["documents"], again["documents"]))


def _real_cfg():
    from chipbench import dots3, harness
    return dots3.reference_config(harness.read_json(os.path.join(
        REPO, "chipbench", "configs", "dots3_note_serve.json")))


def test_shapes_count_the_configuration():
    """ISSUE 29's own sums: 144.0M / 90.8M of attention, 23.6M an expert,
    4,087M parameters resident."""
    from chipbench import shapes_dots3 as sh
    cfg = _real_cfg()
    assert round(sh._attention_params(cfg, True) / 1e6, 1) == 144.0
    assert round(sh._attention_params(cfg, False) / 1e6, 1) == 90.8
    assert round(sh.expert_params(cfg) / 1e6, 1) == 23.6
    assert round(sh.total_params(cfg) / 1e6) == 4087
    # a step's floor grows with what it touches, and is bytes-bound
    lo = sh.decode_step_min_bytes(cfg, 64, 32 * 16384, 32 * 2048, 32 * 513)
    hi = sh.decode_step_min_bytes(cfg, 128, 32 * 32768, 32 * 2048, 32 * 513)
    assert sh.fixed_params(cfg) * 2 < lo < hi
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    b, f = sh.moe_experts_min(cfg, 82, 128)
    assert sh.floor_seconds((b, f), peaks) == b / 819e9


def _planted(**over):
    cfg = _real_cfg()
    run = {"config": {"executables": {"step": "jit_step"}, "dtype":
                      "bfloat16"},
           "geometry": cfg, "peaks": {"hbm_bytes_per_s": 819e9,
                                      "bf16_flops_per_s": 197e12},
           "window": {"t_open": 10.0, "t_close": 40.0, "t_end": 41.0},
           "end_to_end": {"tpot_p50_ms": 21.5},
           "records": [{"submit": 11.0, "times": [11.2, 11.3], "error": None,
                        "prompt_len": 20000, "max_new": 2}],
           "server_stats": {"moe_experts_touched_share": 0.64,
                            "moe_tokens_per_expert_step": 1.0,
                            "moe_load_max_over_mean": 3.5,
                            "selected_keys_per_query": 2048.0},
           "counters": {"steps": 1500, "occupied_lane_steps": 48000.0,
                        "prompt_tokens": 1000000, "tokens_in_window": 48000,
                        "prompt_tokens_cached": 990000,
                        "context_tokens_mean": 24000.0},
           "trace": {"busy_s": 2.9, "idle_pct": 3.0, "modules": {
               "jit_step": {"seconds": 2.5, "runs": 125,
                            "whole_seconds": 2.5, "whole_runs": 125},
               "jit_chunk": {"seconds": 0.29, "runs": 29,
                             "whole_seconds": 0.29, "whole_runs": 29}}}}
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
    assert len(NEW) == 21
    for name in NEW:
        assert callable(_reader(name))


@pytest.mark.parametrize("name", NEW)
def test_reader_has_nothing_to_read_of_a_bare_run(name):
    """No trace, no peaks, no counters (an older program, a CPU run):
    ``None``, never 0 and never a raise."""
    bare = _planted(trace=None, peaks=None, server_stats={},
                    end_to_end={}, records=[],
                    counters={"steps": 0, "occupied_lane_steps": 0.0})
    assert _reader(name)(bare) is None


@pytest.mark.parametrize("name,want", [
    ("moe_experts_touched_pct", 64.0), ("moe_load_max_over_mean", 3.5),
    ("prefix_hit_token_pct", 99.0), ("tpot_p50_ms.dots3", 21.5),
    ("device_idle_pct.dots3", 3.0), ("admit_device_pct.dots3", 10.0),
    ("step_device_ms.dots3", 20.0), ("ttft_p95_ms.dots3", 200.0)])
def test_reader_on_a_planted_run(name, want):
    assert _reader(name)(_planted()) == pytest.approx(want)


def test_rooflines_on_planted_regions(monkeypatch):
    """With planted region seconds the shares come out of the shape
    functions: under 100, above 0, and the whole-step share from the
    counters alone."""
    from mxnet_tpu import profiler
    regions = {"mx.moe_experts": 6.0e-3, "mx.index": 5.0e-3,
               "mx.latent_gather": 1.0e-3, "mx.latent_attn": 1.0e-3,
               "mx.window_attn": 1.0e-3, "mx.dense": 5.0e-3,
               "unscoped": 1.0e-3}
    monkeypatch.setattr(profiler, "device_regions", lambda: {
        "jit_step": {"runs": 125, "run_seconds": 2.5,
                     "regions": {k: v * 125 for k, v in regions.items()}}})
    run = _planted()
    for name in ("moe_experts_roofline_pct", "index_roofline_pct",
                 "latent_attn_roofline_pct", "window_attn_roofline_pct",
                 "step_hbm_roofline_pct.dots3", "serve_mfu_pct.dots3"):
        v = _reader(name)(run)
        assert v is not None and 0.0 < v < 100.0, (name, v)
    assert _reader("step_moe_experts_pct")(run) == pytest.approx(30.0)
    assert _reader("step_latent_attn_pct")(run) == pytest.approx(10.0)
    assert _reader("step_unscoped_pct.dots3")(run) == pytest.approx(5.0)
    # a region the program does not have: left out, not 0
    assert _reader("step_moe_route_pct")(run) is None
