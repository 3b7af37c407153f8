"""CPU tests of the trinity cell's benchmark files (``chipbench/``): the entry
end to end at a toy width, dropped into a temporary copy of ``chipbench/`` as
NEW files plus ``BENCHMARK.json`` entries (the drop-in pattern of
``test_chipbench.py``), the traffic, the configuration against the catalog's
published keys, the shape functions against ISSUE 35's own sums, and every
new reader on a planted run — ``None`` where there is nothing to read.
"""
import json
import os
import shutil

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "trinity_large_serve_sessions24"

TOY = dict(
    hidden_size=32, intermediate_size=64, num_attention_heads=4,
    num_key_value_heads=2, head_dim=8, sliding_window=9, num_experts=4,
    num_experts_per_tok=4, moe_intermediate_size=16, vocab_size=96,
    dtype="float32")


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("chipbench_trinity_toy"))
    bench_dir = os.path.join(tmp, "chipbench")
    shutil.copytree(os.path.join(REPO, "chipbench"), bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    with open(os.path.join(bench_dir, "configs",
                           "trinity_large_serve.json")) as fh:
        cfg = json.load(fh)
    cfg.update(TOY, name="toy_trinity")
    cfg["held"].update(router_experts=16, first_expert=4)
    cfg["server"] = {"max_total_len": 192, "pool_sizes": [4],
                     "admit_sizes": [1], "prefill_buckets": [8, 32],
                     "spec": False, "eos_id": None, "num_pages": 256,
                     "num_window_pages": 128, "page_size": 4}
    cfg["check"] = {"sample": 2, "rows": 16}
    cfg["limits"] = {"served_gap_mean": 1e-3}
    with open(os.path.join(bench_dir, "configs", "toy_trinity.json"),
              "w") as fh:
        json.dump(cfg, fh)
    with open(os.path.join(bench_dir, "traffic",
                           "doc_sessions24_ctx16k.json")) as fh:
        tr = json.load(fh)
    # documents on both sides of the window of 9
    tr.update(clients=4, block=4, requests=2000, max_total=192,
              doc_len={"dist": "uniform", "min": 4, "max": 128,
                       "round_to": 4},
              question_len={"dist": "lognormal", "median": 6, "sigma": 0.4,
                            "min": 4, "max": 8},
              answer_len={"dist": "lognormal", "median": 8, "sigma": 0.5,
                          "min": 4, "max": 12},
              trace_delay_s=0.1, trace_seconds=0.3)
    with open(os.path.join(bench_dir, "traffic", "toy_sessions4t.json"),
              "w") as fh:
        json.dump(tr, fh)
    bench["configs"].append({
        "name": "toy_trinity", "source": cfg["source"],
        "reduced": cfg["reduced"],
        "file": "chipbench/configs/toy_trinity.json", "why": "toy"})
    bench["workloads"].append({
        "name": "toy_trinity_sessions4", "config": "toy_trinity",
        "traffic": "toy_sessions4t", "chips": 1, "why": "toy"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"] = m["workloads"] + ["toy_trinity_sessions4"]
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
                         "toy_trinity_sessions4", "--seed", "3000000019",
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
    assert got["prefix_hit_token_pct.trinity"]["value"] > 50.0
    assert 0.0 < got["moe_experts_touched_pct.trinity"]["value"] <= 100.0
    assert got["moe_load_max_over_mean.trinity"]["value"] >= 1.0
    assert got["tpot_p50_ms.trinity"]["value"] > 0.0
    assert got["ttft_p95_ms.trinity"]["value"] > 0.0
    for name in ("step_device_ms.trinity", "step_hbm_roofline_pct.trinity",
                 "moe_experts_roofline_pct.trinity",
                 "full_attn_roofline_pct.trinity",
                 "window_attn_roofline_pct.trinity",
                 "step_window_attn_pct.trinity", "device_idle_pct.trinity",
                 "serve_mfu_pct.trinity"):
        assert name not in got


def test_traffic_is_the_issues():
    """24 documents, the stratified quantiles of uniform 1,024-16,384 in
    whole pages: a fifth inside the 4,096-token window, four fifths past
    it; the same multiset every seed."""
    from chipbench import harness
    gen = harness.load_by_path(os.path.join(
        REPO, "chipbench", "generators", "doc_sessions.py"), "g")
    spec = harness.read_json(os.path.join(
        REPO, "chipbench", "traffic", "doc_sessions24_ctx16k.json"))
    spec = dict(spec, requests=48)
    a, b = gen.make(spec, 1, 25024), gen.make(spec, 3000000019, 25024)
    lens = lambda t: sorted(d.size for d in t["documents"])
    assert lens(a) == lens(b) and len(a["documents"]) == 24
    assert all(n % 16 == 0 and 1024 <= n <= 16384 for n in lens(a))
    assert sum(n <= 4096 for n in lens(a)) == 5
    assert 8600 < np.mean(lens(a)) < 8800
    assert all(32 <= r["question"].size <= 128 and 64 <= r["max_new"] <= 256
               for r in a["requests"][24:])
    assert max(d.max() for d in a["documents"]) < 25024


def _config():
    from chipbench import harness
    return harness.read_json(os.path.join(
        REPO, "chipbench", "configs", "trinity_large_serve.json"))


def _real_cfg():
    from chipbench import trinity
    return trinity.reference_config(_config())


def test_configuration_holds_the_published_widths():
    """Every number of the catalog's row under the same key but the keys in
    ``reduced``; no width among those."""
    cfg = _config()
    published = dict(
        global_attn_every_n_layers=4, head_dim=128, hidden_act="silu",
        hidden_size=3072, intermediate_size=12288, load_balance_coeff=5e-05,
        max_position_embeddings=262144, model_type="afmoe",
        moe_intermediate_size=3072, mup_enabled=True, n_group=1,
        num_attention_heads=48, num_expert_groups=1, num_experts_per_tok=4,
        num_key_value_heads=8, num_limited_groups=1, num_shared_experts=1,
        rms_norm_eps=1e-05, rope_scaling=None, rope_theta=10000,
        route_norm=True, route_scale=2.448, score_func="sigmoid",
        sliding_window=4096, tie_word_embeddings=False, topk_group=1,
        use_grouped_mm=True)
    assert {k: cfg[k] for k in published} == published
    assert sorted(cfg["reduced"]) == sorted([
        "num_hidden_layers", "num_dense_layers", "num_experts",
        "vocab_size", "layer_types"])
    assert (cfg["num_hidden_layers"], cfg["num_dense_layers"],
            cfg["num_experts"], cfg["vocab_size"]) == (5, 1, 32, 25024)
    assert cfg["layer_types"] == ["sliding_attention"] * 2 \
        + ["full_attention"] + ["sliding_attention"] * 2
    assert cfg["published"]["num_experts"] == 256 \
        and cfg["held"]["router_experts"] == 256
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"]
                 if c["name"] == "trinity_large_serve")
    assert entry["source"] == cfg["source"] \
        and sorted(entry["reduced"]) == sorted(cfg["reduced"])


def test_router_pairs_are_opposed_and_held_whole():
    """``init.router_pairs``: expert ``2k + 1``'s router column and bias are
    expert ``2k``'s with the sign turned, no other leaf moves, and the
    configuration's rank holds whole pairs."""
    from chipbench import weights_trinity as wt
    shapes = {"r1_router_weight": ((2, 16, 8), "float32"),
              "r1_router_bias": ((2, 8), "float32"),
              "r1_egate_weight": ((2, 4, 16, 8), "float32")}
    paired = wt.make(shapes, 3000000019, {"router_pairs": True})
    plain = wt.make(shapes, 3000000019, {})
    for name in ("r1_router_weight", "r1_router_bias"):
        w = np.asarray(paired[name])
        assert np.array_equal(w[..., 1::2], -w[..., 0::2]) and w.any()
        assert np.array_equal(w[..., 0::2], np.asarray(plain[name])[..., 0::2])
    assert np.array_equal(paired["r1_egate_weight"], plain["r1_egate_weight"])
    cfg = _config()
    assert cfg["init"]["router_pairs"] is True
    assert cfg["held"]["first_expert"] % 2 == 0 and cfg["num_experts"] % 2 == 0


def test_shapes_count_the_configuration():
    """ISSUE 35's own sums: 62.9M of attention a layer, 28.3M an expert,
    4,322M parameters resident = 8.64 GB; a 4 KB K + V row a layer."""
    from chipbench import shapes_trinity as sh
    cfg = _real_cfg()
    assert round(sh.attention_params(cfg) / 1e6, 1) == 62.9
    assert round(sh.expert_params(cfg) / 1e6, 1) == 28.3
    assert round(sh.total_params(cfg) / 1e6) == 4322
    assert round(sh.total_params(cfg) * sh.BYTES / 1e9, 2) == 8.64
    assert sh.kv_row_bytes(cfg) == 4096
    # the program declares the same count (norm gains and the router's bias
    # beside the matrices)
    from chipbench import trinity
    _, model_cfg = trinity.build(_config())
    declared = sum(int(np.prod(s)) for s, _ in
                   trinity.shapes(model_cfg).values())
    assert 0 < declared - sh.total_params(cfg) < 1e5
    # a step's floor grows with what it touches, and is bytes-bound
    lo = sh.decode_step_min_bytes(cfg, 40, 24 * 4000, 24 * 3000)
    hi = sh.decode_step_min_bytes(cfg, 80, 24 * 9000, 24 * 4000)
    assert sh.fixed_params(cfg) * 2 < lo < hi
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    for b, f in (sh.moe_experts_min(cfg, 43, 48),
                 sh.full_attn_min(cfg, 24 * 8900),
                 sh.window_attn_min(cfg, 24 * 4000)):
        assert sh.floor_seconds((b, f), peaks) == b / 819e9


def _planted(**over):
    cfg = _real_cfg()
    run = {"config": {"executables": {"step": "jit_step"}, "dtype":
                      "bfloat16"},
           "geometry": cfg, "peaks": {"hbm_bytes_per_s": 819e9,
                                      "bf16_flops_per_s": 197e12},
           "window": {"t_open": 10.0, "t_close": 40.0, "t_end": 41.0},
           "end_to_end": {"tpot_p50_ms": 14.5, "ttft_p95_ms": 200.0},
           "records": [{"submit": 11.0, "times": [11.2, 11.3, 11.4],
                        "error": None, "prompt_len": 9000, "max_new": 3,
                        "queue_wait_s": 0.05},
                       {"submit": 11.0, "times": [11.2, 11.3],
                        "error": None, "prompt_len": 2000, "max_new": 2,
                        "queue_wait_s": 0.03}],
           "server_stats": {"moe_experts_touched_share": 0.33,
                            "moe_tokens_per_expert_step": 0.375,
                            "moe_load_max_over_mean": 3.5},
           "counters": {"steps": 2000, "occupied_lane_steps": 47520.0,
                        "num_slots": 24,
                        "prompt_tokens": 3000000, "tokens_in_window": 48000,
                        "prompt_tokens_cached": 2970000,
                        "context_tokens_mean": 8900.0},
           "trace": {"busy_s": 2.9, "idle_pct": 3.0, "modules": {
               "jit_step": {"seconds": 2.5, "runs": 200,
                            "whole_seconds": 2.5, "whole_runs": 200},
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
    assert len(NEW) == 23 and all(n.endswith(".trinity") for n in NEW)
    for name in NEW:
        assert callable(_reader(name))
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    assert CELL in next(m for m in bench["end_to_end"]
                        if m["name"] == "serve_tok_s")["workloads"]
    assert all(m["moves"] == "serve_tok_s" for m in bench["per_layer"]
               if m["name"] in NEW)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200 \
        and cell["traffic"] == "doc_sessions24_ctx16k"


def test_the_int8_control_in_the_programs_place_is_not_correct(toy_root):
    """The reading the limit is set against, through the harness's own
    comparison: with the plain reference computed in int8 put where the
    program's tokens were, the run comes out not ``correct``, by the
    served-token gap and by no other of the cell's limits."""
    import time
    from chipbench import harness
    bench = harness.read_json(os.path.join(toy_root, "BENCHMARK.json"))
    cell = next(w for w in bench["workloads"]
                if w["name"] == "toy_trinity_sessions4")
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
                    end_to_end={}, records=[],
                    counters={"steps": 0, "occupied_lane_steps": 0.0})
    assert _reader(name)(bare) is None


@pytest.mark.parametrize("name,want", [
    ("moe_experts_touched_pct.trinity", 33.0),
    ("moe_load_max_over_mean.trinity", 3.5),
    ("prefix_hit_token_pct.trinity", 99.0),
    ("tpot_p50_ms.trinity", 14.5), ("ttft_p95_ms.trinity", 200.0),
    ("device_idle_pct.trinity", 3.0), ("admit_device_pct.trinity", 10.0),
    ("step_device_ms.trinity", 12.5),
    ("sched_occupancy_pct.trinity", 99.0),
    ("queue_wait_p95_ms.trinity", 49.0)])
def test_reader_on_a_planted_run(name, want):
    assert _reader(name)(_planted()) == pytest.approx(want)


def test_admit_stall_on_planted_spans(monkeypatch):
    """Steps 1, 2, 3 arrive 9 ms apart; step 5, with a chunk's ``seq`` before
    it, 31 ms after step 3: the admission cost the stream 22 ms."""
    from mxnet_tpu import telemetry
    rows, t = [], 11.0
    for seq, gap in ((1, 0.0), (2, 0.009), (3, 0.009), (5, 0.031)):
        t += gap
        rows.append(("mx:serve:step", t - 0.008, t - 0.007, seq, None, {}))
        rows.append(("mx:serve:route", t, t + 0.001, None, seq, {}))
    monkeypatch.setattr(telemetry, "spans", lambda name=None: rows)
    assert _reader("admit_stall_ms.trinity")(_planted()) \
        == pytest.approx(22.0)


def test_step_work_counts_context_and_window():
    """Three tokens emitted by steps in the window: contexts 9001, 9002 and
    2001; inside the window of 4096 at most 4095 cached keys each."""
    from chipbench import trinity_trace
    work = trinity_trace.step_work(_planted())
    assert work["live_tokens"] * 2000 == 9001 + 9002 + 2001
    assert work["window_pairs"] * 2000 == 4095 + 4095 + 2001
    assert work["slots"] == pytest.approx(23.76)
    assert work["touched"] == pytest.approx(0.33 * 4 * 32)
    assert trinity_trace.step_work(_planted(server_stats={})) is None


def test_rooflines_on_planted_regions(monkeypatch):
    """With planted region seconds the shares come out of the shape
    functions: under 100, above 0, and the whole-step share from the
    counters alone."""
    from mxnet_tpu import profiler
    regions = {"mx.moe_experts": 4.0e-3, "mx.attn": 1.5e-3,
               "mx.window_attn": 3.0e-3, "mx.dense": 2.5e-3,
               "mx.moe_route": 0.5e-3, "mx.head": 0.25e-3,
               "mx.moe_shared": 0.25e-3, "unscoped": 0.5e-3}
    monkeypatch.setattr(profiler, "device_regions", lambda: {
        "jit_step": {"runs": 200, "run_seconds": 2.5,
                     "regions": {k: v * 200 for k, v in regions.items()}}})
    # a window's worth of steps: 24 slots, 8,900 tokens of context each
    recs = [{"submit": 11.0, "error": None, "prompt_len": 8900,
             "max_new": 2001,
             "times": list(np.linspace(11.0, 39.0, 2001))}] * 24
    run = _planted(records=recs)
    for name in ("moe_experts_roofline_pct.trinity",
                 "full_attn_roofline_pct.trinity",
                 "window_attn_roofline_pct.trinity",
                 "step_hbm_roofline_pct.trinity", "serve_mfu_pct.trinity"):
        v = _reader(name)(run)
        assert v is not None and 0.0 < v < 100.0, (name, v)
    assert _reader("step_moe_experts_pct.trinity")(run) \
        == pytest.approx(32.0)
    assert _reader("step_attention_pct.trinity")(run) == pytest.approx(12.0)
    assert _reader("step_window_attn_pct.trinity")(run) \
        == pytest.approx(24.0)
    assert _reader("step_moe_route_pct.trinity")(run) == pytest.approx(4.0)
    assert _reader("step_dense_pct.trinity")(run) == pytest.approx(24.0)
    assert _reader("step_unscoped_pct.trinity")(run) == pytest.approx(4.0)
    # a region the program does not have: left out, not 0
    monkeypatch.setattr(profiler, "device_regions", lambda: {
        "jit_step": {"runs": 200, "run_seconds": 2.5,
                     "regions": {"mx.dense": 2.5}}})
    assert _reader("step_window_attn_pct.trinity")(run) is None
    assert _reader("window_attn_roofline_pct.trinity")(run) is None
