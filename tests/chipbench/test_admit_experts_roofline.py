"""``admit_experts_roofline_pct`` (ISSUE 39) and the counters it reads: the
reader on a CPU run, on a traced run of a program whose chunks count no
experts (the parent's), and on a synthetic traced run against a hand
reckoning; the chunks' expert counters in ``stats()`` of a CPU serve of the
tiny ``dots3`` model."""
import json
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from chipbench import harness, weights_dots3
from mxnet_tpu import profiler, serve
from mxnet_tpu.models import dots3

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = "admit_experts_roofline_pct"
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
# the two cells' geometry as far as the reader reads it
DOTS3 = {"hidden_size": 5120, "moe_intermediate_size": 1536,
         "first_k_dense_replace": 1}
TRINITY = {"hidden_size": 3072, "moe_intermediate_size": 3072,
           "num_dense_layers": 1}
# four whole chunk runs of 20 ms under the experts, and the step's, which
# must not count
TABLE = {
    "jit_chunk": {"runs": 4, "run_seconds": 0.2, "regions": {
        "mx.moe_experts": 0.08, "mx.moe_route": 0.002, "mx.dense": 0.01}},
    "jit_step": {"runs": 100, "run_seconds": 2.0, "regions": {
        "mx.moe_experts": 1.0, "mx.dense": 1.0}},
}


def _read(run):
    return harness.load_by_path(
        os.path.join(ROOT, "chipbench", "metrics", NAME + ".py"),
        "t_" + NAME).read(run)


def _run(geometry=DOTS3, trace=True, **dispatch):
    return {"trace": {"busy_s": 3.0, "window_s": 3.0, "modules": {}}
            if trace else None, "peaks": PEAKS if trace else None,
            "geometry": geometry, "counters": {"dispatch": dispatch}}


@pytest.fixture
def traced(monkeypatch):
    monkeypatch.setattr(profiler, "device_regions", lambda: TABLE)


def test_metric_lists_the_routed_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        m, = [m for m in json.load(fh)["per_layer"] if m["name"] == NAME]
    assert m["workloads"] == ["dots3_note_serve_sessions32",
                              "trinity_large_serve_sessions24"]
    assert (m["moves"], m["layer"], m["unit"], m["better"]) == (
        "serve_tok_s", "routed experts ops/moe.py", "%", "higher")


def test_reads_none_without_a_chip_trace():
    """A CPU run: no device plane, no reduced trace, no peaks."""
    assert profiler.device_regions() is None
    assert _read(_run(trace=False, chunk_dispatches=10,
                      chunk_experts_touched=1000,
                      chunk_expert_tokens=2000)) is None


@pytest.mark.parametrize("dispatch", [
    # the parent's server: its chunks count no experts
    {"chunk_dispatches": 10, "admit_rows": 1280},
    # a model without routed experts counts 0
    {"chunk_dispatches": 10, "chunk_experts_touched": 0,
     "chunk_expert_tokens": 0},
    # no chunk in the window
    {"chunk_dispatches": 0, "chunk_experts_touched": 0},
])
def test_reads_none_without_the_chunk_counters(traced, dispatch):
    assert _read(_run(**dispatch)) is None


@pytest.mark.parametrize("geometry, expert", [(DOTS3, 3 * 5120 * 1536),
                                              (TRINITY, 3 * 3072 * 3072)])
def test_reads_the_hand_reckoned_share(traced, geometry, expert):
    """100 (layer, expert) cells a chunk, each expert's weights read once at
    819 GB/s, over 20 ms a chunk under ``mx.moe_experts`` (the step's time
    left out); the pairs' operations take far less than the bytes."""
    run = _run(geometry, chunk_dispatches=10, chunk_experts_touched=1000,
               chunk_expert_tokens=10 * 1024)
    least = 100 * expert * 2 / 819e9
    assert 2 * expert * 1024 / 197e12 < least
    assert _read(run) == pytest.approx(100 * least / 0.02)


def _tiny_dots3():
    net, cfg = dots3.dots3_tiny(held_experts=(4, 8))
    net.collect_params().setattr("grad_req", "null")
    net.initialize(mx.init.Zero())
    w = weights_dots3.make(dots3.parameter_shapes(cfg), 7,
                           {"score_gain": 0.7, "expert_out_gain": 3.0})
    for n, p in net.collect_params().items():
        p.set_data(w[n[len(net.prefix):]])
    return net, cfg


def test_stats_count_what_the_chunks_routed():
    """A 50-token prompt over buckets (8, 32) is two chunks of 32 rows; each
    row takes 4 of 16 experts, of which this server holds 8, in every routed
    layer.  ``stats()`` gives the share of (layer, held expert) cells the
    chunks touched and their pairs a cell; the counters the sums."""
    net, cfg = _tiny_dots3()
    srv = serve.DecodeServer(
        net, max_total_len=128, pool_sizes=(4,), admit_sizes=(1, 2),
        prefill_buckets=(8, 32), page_size=4, num_pages=96,
        num_window_pages=64, spec=False, autostart=False)
    s = srv.submit(np.random.default_rng(3).integers(0, 96, 50).astype(
        np.int32), max_new_tokens=4)
    for _ in range(100):
        if s.done:
            break
        srv.pump()
    st = srv.stats()
    srv.close()
    c = st["counters"]
    assert c["chunk_dispatches"] == 2
    routed = cfg.num_hidden_layers - cfg.first_k_dense_replace
    cells = 2 * routed * 8
    pairs = c["chunk_expert_tokens"]
    # every pair of a row is one of its 4 choices, each held or not
    assert 0 < pairs <= 2 * 32 * 4 * routed
    assert 0 < c["chunk_experts_touched"] <= cells
    assert st["chunk_moe_experts_touched_share"] == pytest.approx(
        c["chunk_experts_touched"] / cells)
    assert st["chunk_moe_tokens_per_expert"] == pytest.approx(pairs / cells)
