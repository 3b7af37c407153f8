"""``train_attn_mxu_roofline_pct`` (ISSUE 32) on a hand-built run: the
causal attention operations of one train step at the bf16 peak over the
device seconds a step spends under ``mx.attn``, ``None`` where there is
nothing to read, and declared in ``BENCHMARK.json`` after the accepted
entries."""
import json
import os

import pytest

from chipbench import harness

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MEDIUM = dict(num_layers=24, units=1024, num_heads=16, hidden_size=4096,
              vocab_size=50257, max_length=1024)
NAME = "train_attn_mxu_roofline_pct"


def _read(run):
    return harness.load_by_path(
        os.path.join(REPO, "chipbench", "metrics", NAME + ".py"),
        "ta_metric").read(run)


def _run(**over):
    run = {"config": {"executables": {"step": "jit_step_fn"}},
           "geometry": MEDIUM, "peaks": {"bf16_flops_per_s": 197e12},
           "window": {"t_open": 100.0, "t_close": 130.0, "t_end": 130.2},
           "counters": {"steps": 96, "rows": 8, "seq": 1024},
           "records": []}
    run.update(over)
    return run


@pytest.fixture
def regions(monkeypatch):
    from mxnet_tpu import profiler
    state = {"table": None}
    monkeypatch.setattr(profiler, "device_regions", lambda: state["table"])
    return state


@pytest.mark.parametrize("attn_s, want", [
    (0.1885, 3.33),         # the blockwise scans (PERF.md, PR 26's trace)
    (0.0300, 20.9),
], ids=["parent", "a_sixth_of_it"])
def test_by_hand(regions, attn_s, want):
    """6 x 24 x 1024 x 1024 operations a token x 8,192 tokens = 1.237
    TFLOP a step, whatever ran them."""
    regions["table"] = {"jit_step_fn": {
        "runs": 9, "run_seconds": 0.3,
        "regions": {"mx.attn": 9 * attn_s, "mx.dense": 0.85,
                    "unscoped": 0.08}}}
    flops = 6 * 24 * 1024 * 1024 * 8 * 1024
    got = _read(_run())
    assert got == pytest.approx(100.0 * flops / 197e12 / attn_s)
    assert got == pytest.approx(want, rel=2e-3)


def test_none_without_a_source(regions, monkeypatch):
    assert _read(_run()) is None                    # a CPU run: no table
    regions["table"] = {"jit_step_fn": {"runs": 0, "run_seconds": 0.0,
                                        "regions": {}}}
    assert _read(_run()) is None                    # no whole run
    regions["table"] = {"jit_step_fn": {"runs": 3, "run_seconds": 0.3,
                                        "regions": {"mx.dense": 0.3}}}
    assert _read(_run()) is None                    # no attention region
    regions["table"] = {"jit_step_fn": {"runs": 3, "run_seconds": 0.3,
                                        "regions": {"mx.attn": 0.3}}}
    assert _read(_run(peaks=None)) is None          # no chip, no peak
    assert _read(_run(counters={"steps": 3})) is None
    from mxnet_tpu import profiler
    monkeypatch.delattr(profiler, "device_regions")
    assert _read(_run()) is None                    # an older program


def test_declared_after_the_accepted_metrics_with_every_key():
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    at = [x["name"] for x in bench["per_layer"]].index(NAME)
    assert at >= 48         # appended: PR 30 left 48 entries
    m = bench["per_layer"][at]
    assert m == {"name": NAME, "unit": "%", "better": "higher",
                 "source": "device_trace", "layer": "whole train step",
                 "moves": "train_tok_s",
                 "workloads": ["gpt2m_train_seq1024"]}
    mfu = next(x for x in bench["per_layer"]
               if x["name"] == "train_mfu_pct")
    assert (m["layer"], m["moves"]) == (mfu["layer"], mfu["moves"])
