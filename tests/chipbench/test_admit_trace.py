"""The admission metrics of every serve cell (ISSUE 38): the region groups
of ``chipbench/admit_trace.py`` against the program's vocabulary, and each
new reader on a CPU run and on a synthetic traced run."""
import json
import os
import re

import pytest

from chipbench import admit_trace, harness
from mxnet_tpu import profiler, profiler_xla

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SERVE = ["gpt2l_serve_closed32", "dots3_note_serve_sessions32",
         "granite4h_micro_serve_chat64", "trinity_large_serve_sessions24"]
CELLS = {
    "admit_attention_pct": SERVE,
    "admit_dense_pct": SERVE,
    "admit_experts_pct": ["dots3_note_serve_sessions32",
                          "trinity_large_serve_sessions24"],
    # every serve cell, reading 0 where no state-space layer runs: a list of
    # the granite cell alone would be counted by that cell's own test
    # (``tests/chipbench/test_chipbench_granite.py`` pins 19 such metrics)
    "admit_state_pct": SERVE,
    "admit_unscoped_pct": SERVE,
    "admit_pad_token_pct": SERVE,
    "admit_device_us_per_token": SERVE,
    "window_compile_ms": SERVE,
}
GROUP_METRIC = {"attention": "admit_attention_pct",
                "dense": "admit_dense_pct",
                "experts": "admit_experts_pct", "state": "admit_state_pct",
                "unscoped": "admit_unscoped_pct"}


def _vocabulary():
    """docs/TELEMETRY.md's region table, the kernels known by name and
    ``unscoped``."""
    with open(os.path.join(ROOT, "docs", "TELEMETRY.md")) as fh:
        documented = re.findall(r"^\| `(mx\.[a-z_]+)` \|", fh.read(), re.M)
    assert len(documented) >= 20
    return set(documented) | {r for _, r in profiler_xla._KERNEL_REGIONS} \
        | {profiler_xla.UNSCOPED}


def test_every_region_falls_in_exactly_one_group():
    listed = [r for regions in admit_trace.GROUPS.values() for r in regions]
    assert len(listed) == len(set(listed))          # no region in two
    assert set(listed) == _vocabulary()             # none left over, none
    assert set(admit_trace.GROUPS) == set(GROUP_METRIC)   # made up


def _reader(name):
    return harness.load_by_path(
        os.path.join(ROOT, "chipbench", "metrics", name + ".py"),
        "t_admit_" + name).read


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


@pytest.mark.parametrize("name", sorted(CELLS))
def test_metric_lists_exactly_its_cells(name):
    m, = [m for m in _bench()["per_layer"] if m["name"] == name]
    assert sorted(m["workloads"]) == sorted(CELLS[name])
    assert m["moves"] == "serve_tok_s"


# a traced run's table: two admission executables and the step, whose
# regions must not count
TABLE = {
    "jit_chunk": {"runs": 4, "run_seconds": 0.2, "regions": {
        "mx.moe_experts": 0.08, "mx.moe_route": 0.002,
        "mx.latent_gather": 0.05, "mx.latent_attn": 0.02, "mx.index": 0.018,
        "mx.dense": 0.01, "mx.head": 0.002, "unscoped": 0.008,
        "mx.page_write": 0.01}},
    "jit_admit": {"runs": 2, "run_seconds": 0.02, "regions": {
        "mx.dense": 0.012, "mx.attn": 0.004, "mx.ssm_scan": 0.003,
        "unscoped": 0.001}},
    "jit_step": {"runs": 100, "run_seconds": 2.0, "regions": {
        "mx.dense": 1.0, "mx.attn": 1.0}},
}


def _run(**dispatch):
    return {"trace": {"busy_s": 3.0, "window_s": 3.0, "modules": {}},
            "counters": {"dispatch": dispatch}}


@pytest.fixture
def traced(monkeypatch):
    monkeypatch.setattr(profiler, "device_regions", lambda: TABLE)


def test_group_shares_of_a_traced_run(traced):
    run = _run()
    got = {g: _reader(m)(run) for g, m in GROUP_METRIC.items()}
    total = 0.22
    assert got["experts"] == pytest.approx(100 * 0.082 / total)
    assert got["attention"] == pytest.approx(100 * 0.102 / total)
    assert got["dense"] == pytest.approx(100 * 0.024 / total)
    assert got["state"] == pytest.approx(100 * 0.003 / total)
    assert got["unscoped"] == pytest.approx(100 * 0.009 / total)
    assert sum(got.values()) == pytest.approx(100.0)


def test_counter_metrics_of_a_traced_run(traced):
    run = _run(admit_dispatches=3, chunk_dispatches=10, hit_dispatches=0,
               admit_rows=3 * 256 + 10 * 128, admit_tokens=500 + 700,
               compile_ms=0.0)
    assert _reader("admit_pad_token_pct")(run) == pytest.approx(
        100 * (2048 - 1200) / 2048)
    # a chunk 50 ms, a wave 10 ms on average in the trace
    assert _reader("admit_device_us_per_token")(run) == pytest.approx(
        1e6 * (10 * 0.05 + 3 * 0.01) / 1200)
    assert _reader("window_compile_ms")(run) == 0.0


def test_executable_dispatched_but_never_traced_whole_reads_none(traced):
    """A hit dispatched in the window with no whole run in the trace: its
    time cannot be read, so the metric is left out."""
    run = _run(admit_dispatches=1, hit_dispatches=2, admit_rows=8,
               admit_tokens=5)
    assert _reader("admit_device_us_per_token")(run) is None


@pytest.mark.parametrize("name", sorted(CELLS))
def test_reader_reads_none_without_a_chip_trace(name):
    """A CPU run: no device plane, no reduced trace."""
    run = {"trace": None, "counters": {"dispatch": {
        "admit_rows": 64, "admit_tokens": 18, "admit_dispatches": 1,
        "compile_ms": 0.0}}}
    assert profiler.device_regions() is None
    assert _reader(name)(run) is None


@pytest.mark.parametrize("name", ["admit_pad_token_pct",
                                  "admit_device_us_per_token",
                                  "window_compile_ms"])
def test_reader_of_a_program_without_the_counters_reads_none(traced, name):
    """The parent program's server counts no rows, tokens or compile
    milliseconds: its traced run leaves these metrics out, and raises
    nothing."""
    run = _run(admit_dispatches=3, chunk_dispatches=10, prefix_hits=4)
    assert _reader(name)(run) is None
