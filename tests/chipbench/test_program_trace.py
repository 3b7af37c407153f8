"""The per-layer metrics that read the program's own tracing
(``chipbench/program_trace.py`` and its readers under ``chipbench/metrics``),
each on a hand-built ``run`` with hand-built regions and spans, and ``None``
on empty sources.  CPU only; nothing here runs a cell."""
import json
import os

import pytest

from chipbench import harness

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

SERVE_REGIONS = {"mx.paged_view": 0.40, "mx.kv_write": 0.25, "mx.attn": 0.12,
                 "mx.dense": 0.02, "mx.head": 0.01, "mx.page_write": 0.05,
                 "unscoped": 0.15}
TRAIN_REGIONS = {"mx.attn": 0.2, "mx.dense": 0.5, "mx.head": 0.15,
                 "mx.optimizer": 0.05, "unscoped": 0.1}
REGION_METRICS = {
    "step_paged_view_pct": ("jit_step", 40.0),
    "step_kv_write_pct": ("jit_step", 25.0),
    "step_attention_pct": ("jit_step", 12.0),
    "step_dense_pct": ("jit_step", 3.0),
    "step_page_write_pct": ("jit_step", 5.0),
    "step_unscoped_pct.serve": ("jit_step", 15.0),
    "train_attention_pct": ("jit_step_fn", 20.0),
    "train_dense_pct": ("jit_step_fn", 50.0),
    "train_head_loss_pct": ("jit_step_fn", 15.0),
    "train_optimizer_pct": ("jit_step_fn", 5.0),
    "step_unscoped_pct.train": ("jit_step_fn", 10.0),
}
SPAN_METRICS = ("admit_stall_ms", "sched_host_ms_per_step",
                "train_host_ms_per_step")


def _reader(name):
    return harness.load_by_path(
        os.path.join(REPO, "chipbench", "metrics", name + ".py"),
        "pt_metric_" + name.replace(".", "_"))


def _run(step, **over):
    run = {"config": {"executables": {"step": step}},
           "window": {"t_open": 100.0, "t_close": 130.0, "t_end": 131.0},
           "trace": None, "records": [], "counters": {}}
    run.update(over)
    return run


@pytest.fixture
def program(monkeypatch):
    """Stand-ins for what the program keeps in memory."""
    from mxnet_tpu import profiler, telemetry
    state = {"regions": None, "spans": []}
    monkeypatch.setattr(profiler, "device_regions",
                        lambda: state["regions"])
    monkeypatch.setattr(telemetry, "spans", lambda name=None: state["spans"])
    return state


def _span(name, t0, ms, seq=None, cause=None, **fields):
    return (name, t0, t0 + ms / 1e3, seq, cause, fields)


@pytest.mark.parametrize("name", sorted(REGION_METRICS))
def test_region_metric_by_hand(name, program):
    step, expect = REGION_METRICS[name]
    program["regions"] = {
        "jit_step": {"runs": 17, "run_seconds": 1.0,
                     "regions": SERVE_REGIONS},
        "jit_step_fn": {"runs": 8, "run_seconds": 1.0,
                        "regions": TRAIN_REGIONS},
        "jit_admit": {"runs": 2, "run_seconds": 0.1,
                      "regions": {"mx.dense": 0.1}}}
    assert _reader(name).read(_run(step)) == pytest.approx(expect)


@pytest.mark.parametrize("name", sorted(REGION_METRICS))
def test_region_metric_is_none_without_a_source(name, program):
    step, _ = REGION_METRICS[name]
    read = _reader(name).read
    assert read(_run(step)) is None                 # a CPU run: no table
    program["regions"] = {"jit_other": {"runs": 3, "run_seconds": 1.0,
                                        "regions": {"unscoped": 1.0}}}
    assert read(_run(step)) is None                 # the step never ran
    program["regions"] = {step: {"runs": 0, "run_seconds": 0.0,
                                 "regions": {}}}
    assert read(_run(step)) is None                 # no whole run of it


def test_region_reader_survives_a_program_without_the_facade(monkeypatch):
    """Laid over an older checkout the reader finds no
    ``device_regions`` and no ``spans``: nothing read, nothing raised."""
    from mxnet_tpu import profiler, telemetry
    monkeypatch.delattr(profiler, "device_regions")
    monkeypatch.delattr(telemetry, "spans")
    assert _reader("step_attention_pct").read(_run("jit_step")) is None
    assert _reader("sched_host_ms_per_step").read(_run("jit_step")) is None
    assert _reader("admit_stall_ms").read(_run("jit_step")) is None
    assert _reader("train_host_ms_per_step").read(_run("jit_step_fn")) \
        is None


def test_admit_device_pct_reads_the_reduced_trace():
    read = _reader("admit_device_pct").read
    trace = {"busy_s": 3.0, "window_s": 3.0, "modules": {
        "jit_step": {"seconds": 2.7, "runs": 19},
        "jit_admit": {"seconds": 0.18, "runs": 3},
        "jit_hit": {"seconds": 0.03, "runs": 1},
        "jit_chunk": {"seconds": 0.015, "runs": 1}}}
    assert read(_run("jit_step", trace=trace)) == pytest.approx(7.5)
    assert read(_run("jit_step")) is None
    assert read(_run("jit_step", trace=dict(trace, busy_s=0.0))) is None


def _serve_spans():
    """Seven dispatches: steps 1-4, an admission wave (seq 5), steps 6
    and 7.  The device takes 160 ms a step and 50 ms for the wave, and the
    scheduler runs one step ahead of it: a step's readback is routed
    (``cause``) in the pump that dispatched the next one.  Each step costs
    the host 1 + 2 ms (build, dispatch) and 0.5 ms of routing; the wave
    3 + 4 ms.  One step and one idle span lie outside the window."""
    rows, t, done = [], 101.0, {}
    for seq in (1, 2, 3, 4, 5, 6, 7):
        done[seq] = (done.get(seq - 1, t) + (0.050 if seq == 5 else 0.160))
    for seq in (1, 2, 3, 4, 6, 7):
        prev = seq - 1 if seq != 6 else 4
        if seq == 6:
            rows += [_span("mx:serve:admit_build", t - 0.010, 3.0),
                     _span("mx:serve:admit", t - 0.007, 4.0, seq=5,
                           wave=2, requests=[7, 8])]
        else:
            rows.append(_span("mx:serve:admit_build", t - 0.001, 1.0))
        rows.append(_span("mx:serve:step", t, 2.0, seq=seq))
        if prev in done and prev >= 1:
            wait = max(done[prev] - (t + 0.002), 0.0)
            rows += [_span("mx:serve:drain_wait", t + 0.002, wait * 1e3,
                           cause=prev),
                     _span("mx:serve:route", t + 0.002 + wait, 0.5,
                           cause=prev)]
            t = t + 0.002 + wait + 0.0015
        else:
            t += 0.003
    rows += [_span("mx:serve:idle", t + 0.2, 50.0),
             _span("mx:serve:step", 99.0, 2.0, seq=0),
             _span("mx:serve:idle", 131.0, 50.0)]
    return rows


def test_admit_stall_ms_by_hand(program):
    program["spans"] = _serve_spans()
    # readbacks of steps 1, 2, 3, 4, 6 arrive 160 ms apart but for step 6,
    # which waits for the wave too: 210 ms after step 4
    assert _reader("admit_stall_ms").read(_run("jit_step")) \
        == pytest.approx(50.0)
    program["spans"] = [r for r in _serve_spans() if r[3] != 6
                        and r[4] != 6]
    assert _reader("admit_stall_ms").read(_run("jit_step")) is None


def test_sched_host_ms_per_step_by_hand(program):
    program["spans"] = _serve_spans()
    # six steps in the window: 5 x (1 + 2) + (3 + 4 + 2) of building and
    # dispatching, 5 readbacks routed at 0.5; waits and idle left out
    assert _reader("sched_host_ms_per_step").read(_run("jit_step")) \
        == pytest.approx((5 * 3.0 + 9.0 + 5 * 0.5) / 6)


def test_train_host_ms_per_step_by_hand(program):
    rows, t = [], 100.5
    for seq in range(1, 4):
        rows += [_span("mx:train:feed", t, 1.5, seq=seq),
                 _span("mx:train:step", t + 0.0015, 2.5, seq=seq)]
        t += 0.3
    rows.append(_span("mx:train:step", 129.9999, 2.5, seq=9))   # cut
    program["spans"] = rows
    assert _reader("train_host_ms_per_step").read(_run("jit_step_fn")) \
        == pytest.approx(4.0)


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_span_metric_is_none_on_an_empty_stretch(name, program):
    assert _reader(name).read(_run("jit_step")) is None


def test_new_metrics_are_declared_with_every_key():
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    new = sorted(REGION_METRICS) + list(SPAN_METRICS) + ["admit_device_pct"]
    cells = {w["name"] for w in bench["workloads"]}
    moves = {m["name"] for m in bench["end_to_end"]}
    for name in new:
        m = by_name[name]
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert set(m["workloads"]) <= cells and m["moves"] in moves
        assert m["source"] == ("program_span" if name in SPAN_METRICS
                               else "device_trace")
        assert os.path.isfile(os.path.join(
            REPO, "chipbench", "metrics", name + ".py"))
    # appended, never put in the middle: the PR 24 entries come first
    assert [m["name"] for m in bench["per_layer"]][:11] == [
        "sched_occupancy_pct", "queue_wait_p95_ms", "ttft_p95_ms",
        "step_device_ms.serve", "token_gap_p95_ms",
        "step_hbm_roofline_pct", "serve_mfu_pct", "device_idle_pct.serve",
        "step_device_ms.train", "train_mfu_pct", "device_idle_pct.train"]
