"""``paged_attn_roofline_pct`` (ISSUE 30) on a hand-built run: the K and V
bytes of the step's live tokens at the HBM peak over the device seconds
under the cache-to-output leg's regions, ``None`` where there is nothing to
read, and declared in ``BENCHMARK.json`` after the accepted entries."""
import json
import os

import pytest

from chipbench import harness

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
LARGE = dict(num_layers=36, units=1280, num_heads=20, hidden_size=5120,
             vocab_size=50257, max_length=1024)
NAME = "paged_attn_roofline_pct"


def _read(run):
    return harness.load_by_path(
        os.path.join(REPO, "chipbench", "metrics", NAME + ".py"),
        "pa_metric").read(run)


def _run(**over):
    """Two steps in the window; the first stream's tokens 2 and 3 and the
    second's token 2 are emitted in it: contexts 101, 102 and 301."""
    run = {"config": {"executables": {"step": "jit_step"},
                      "dtype": "bfloat16"},
           "geometry": LARGE, "peaks": {"hbm_bytes_per_s": 819e9},
           "window": {"t_open": 100.0, "t_close": 130.0},
           "counters": {"steps": 2},
           "records": [
               {"prompt_len": 100, "times": [99.0, 100.5, 101.0, 131.0]},
               {"prompt_len": 300, "times": [100.2, 100.9]}]}
    run.update(over)
    return run


@pytest.fixture
def regions(monkeypatch):
    from mxnet_tpu import profiler
    state = {"table": None}
    monkeypatch.setattr(profiler, "device_regions", lambda: state["table"])
    return state


@pytest.mark.parametrize("leg", [
    {"mx.paged_view": 0.030, "mx.kv_write": 0.004, "mx.attn": 0.006},
    {"mx.attn": 0.040},
], ids=["view_path", "page_walk"])
def test_by_hand(regions, leg):
    """The same reading whichever regions hold the leg's 4 ms a run."""
    regions["table"] = {"jit_step": {
        "runs": 10, "run_seconds": 0.1,
        "regions": dict(leg, **{"mx.dense": 0.05, "unscoped": 0.01})}}
    live_a_step = (101 + 102 + 301) / 2
    least_s = live_a_step * 2 * 36 * 1280 * 2 / 819e9
    assert _read(_run()) == pytest.approx(100.0 * least_s / 0.004)


def test_none_without_a_source(regions, monkeypatch):
    assert _read(_run()) is None                    # a CPU run: no table
    regions["table"] = {"jit_step": {"runs": 0, "run_seconds": 0.0,
                                     "regions": {}}}
    assert _read(_run()) is None                    # no whole run
    regions["table"] = {"jit_step": {"runs": 3, "run_seconds": 0.1,
                                     "regions": {"mx.dense": 0.1}}}
    assert _read(_run()) is None                    # the leg is not there
    regions["table"] = {"jit_step": {"runs": 3, "run_seconds": 0.1,
                                     "regions": {"mx.attn": 0.1}}}
    assert _read(_run(peaks=None)) is None          # no chip, no peak
    assert _read(_run(counters={})) is None
    from mxnet_tpu import profiler
    monkeypatch.delattr(profiler, "device_regions")
    assert _read(_run()) is None                    # an older program


def test_declared_after_the_accepted_metrics_with_every_key():
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    at = [x["name"] for x in bench["per_layer"]].index(NAME)
    assert at >= 47         # appended: PR 29 left 47 entries
    m = bench["per_layer"][at]
    assert m == {"name": NAME, "unit": "%", "better": "higher",
                 "source": "device_trace",
                 "layer": "decode body and kernels models/decoding.py",
                 "moves": "serve_tok_s",
                 "workloads": ["gpt2l_serve_closed32"]}
    step_hbm = next(x for x in bench["per_layer"]
                    if x["name"] == "step_hbm_roofline_pct")
    assert m["layer"] == step_hbm["layer"]
