"""The tracing of ISSUE 26 on the CPU at a toy width: the ``mx.*`` named
scopes in the lowered executables, the region of a provenance path, the
xspace reader and ``device_regions`` on a hand-built trace, the facade
inside a running ``jax.profiler`` trace, and the phase spans of the serve
scheduler and of ``SPMDTrainer.step``."""
import re

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import profiler, profiler_xla, telemetry

TOY = dict(num_layers=2, units=64, num_heads=4, hidden_size=128,
           vocab_size=97, max_length=64, dtype="float32")


def _toy_net():
    from mxnet_tpu import models
    net, cfg = models.gpt2_small(**TOY)
    net.initialize(mx.init.Normal(0.02))
    return net, cfg


def _toy_server(**kw):
    from mxnet_tpu import serve
    net, _ = _toy_net()
    return serve.DecodeServer(
        net, autostart=False, pool_sizes=(4,), admit_sizes=(1, 2),
        prefill_buckets=(16, 32), spec=False, max_total_len=64, **kw)


def _lowered_serve_step():
    srv = _toy_server()
    try:
        pv, q8, sw = srv._progs.operands
        return srv._progs.step_fn().lower(
            pv, q8, sw, np.float32(0), srv._page_table(), *srv._state)
    finally:
        srv.close()     # or its pool stays accounted for the whole process


def _lowered_serve_admit():
    from mxnet_tpu.serve import schema
    srv = _toy_server()
    try:
        progs, (A, P) = srv._progs, (2, 16)
        pv, _, _ = progs.operands
        npb = -(-P // progs.page)
        return progs.admit_fn(A, P).lower(
            pv, np.zeros((A, P), np.int32),
            np.zeros((A, schema.meta_width("admit")), np.int32),
            np.full((A,), np.inf, np.float32), np.zeros((A, npb), np.int32),
            np.zeros((A, progs.maxp), np.int32), *srv._state)
    finally:
        srv.close()


def _toy_trainer():
    import jax
    from mxnet_tpu import gluon, parallel
    net, cfg = _toy_net()
    mesh = parallel.make_mesh({"dp": 1}, jax.devices()[:1])
    trainer = parallel.SPMDTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "adamw",
        {"learning_rate": 1e-3}, mesh=mesh)
    batch = mx.nd.array(np.zeros((2, cfg.max_length)), dtype="int32")
    return trainer, batch


def _lowered_train_step():
    import jax
    import jax.numpy as jnp
    tr, batch = _toy_trainer()
    tr._ensure_built(batch, batch)
    lr = jnp.float32(1e-3)
    return tr._step_fn.lower(
        tr._train_vals, tr._opt_states, tr._frozen_vals,
        jax.random.PRNGKey(0), lr, lr, jnp.int32(1), batch._data,
        batch._data)


@pytest.mark.parametrize("lower, regions", [
    (_lowered_serve_step, ("mx.paged_view", "mx.kv_write", "mx.attn",
                           "mx.dense", "mx.page_write", "mx.head")),
    (_lowered_serve_admit, ("mx.kv_write", "mx.attn", "mx.dense",
                            "mx.page_write", "mx.head")),
    (_lowered_train_step, ("mx.attn", "mx.dense", "mx.head",
                           "mx.optimizer")),
], ids=["serve_step", "serve_admit", "train_step"])
def test_region_names_in_lowered_text(lower, regions):
    """Every region of docs/TELEMETRY.md's table that the executable has
    is a component of some operation's location in its lowered text."""
    names = set(re.findall(r'loc\("([^"]+)"',
                           lower().as_text(debug_info=True)))
    found = {profiler_xla.region_of(n) for n in names}
    assert set(regions) <= found


@pytest.mark.parametrize("path, region", [
    ("jit(step)/mx.dense/dot_general:", "mx.dense"),
    ("jit(step_fn)/transpose(jvp(mx.attn))/dot_general:", "mx.attn"),
    ("jit(step)/mx.dense/while/body/closed_call/mx.kv_write/scatter:",
     "mx.kv_write"),
    ("jit(step_fn)/transpose(jvp(mx.dense))/mx.attn/while/body/mul:",
     "mx.attn"),
    ("jit(step)/jit(_where)/select_n:", "unscoped"),
    ("", "unscoped"),
], ids=["forward", "transpose_jvp", "while_body", "nested_innermost",
        "none", "empty"])
def test_region_is_the_innermost_mx_component(path, region):
    assert profiler_xla.region_of(path) == region


@pytest.mark.parametrize("name, provenance, region", [
    ("ragged-dot-none.3", "", "mx.moe_experts"),
    ("mx_paged_attention.8", "", "mx.attn"),
    ("mx_flash_fwd.3", "", "mx.attn"),
    ("mx_flash_bwd_dq", "", "mx.attn"),
    ("mx_flash_bwd_dkv.24", "", "mx.attn"),
    ("jvp_mx_flash_fwd_.1", "", "mx.attn"),
    ("transpose_jvp_mx_flash_bwd_dq__.7", "", "mx.attn"),
    ("mx_flash_fwd_qkv.2", "", "mx.attn"),
    ("mx_flash_bwd_dq_qkv", "", "mx.attn"),
    ("mx_flash_bwd_dkv_qkv.23", "", "mx.attn"),
    ("jvp_mx_flash_fwd_qkv_.1", "", "mx.attn"),
    ("transpose_jvp_mx_flash_bwd_dkv_qkv__.5", "", "mx.attn"),
    ("mx_paged_attention.8",
     "jit(step)/mx.dense/while/body/mx.attn/mx_paged_attention/pallas_call",
     "mx.attn"),
    ("mx_index_scores.3", "", "mx.index"),
    ("mx_index_scores",
     "jit(step)/mx.dense/mx.index/cond/branch_0_fun/mx_index_scores/"
     "pallas_call", "mx.index"),
    ("mx_paged_attention_window.2", "", "mx.window_attn"),
    ("mx_ssm_update.4", "", "mx.ssm_state"),
    ("mx_retention_update.2", "", "mx.ssm_state"),
    ("mx_retention_read.1", "", "mx.ssm_scan"),
    ("mx_retention_write.8", "", "mx.ssm_scan"),
    ("fusion.170", "", "unscoped"),
], ids=["grouped_product", "paged_attention", "flash_fwd", "flash_bwd_dq",
        "flash_bwd_dkv", "flash_fwd_differentiated",
        "flash_bwd_dq_differentiated", "packed_fwd", "packed_bwd_dq",
        "packed_bwd_dkv", "packed_fwd_differentiated",
        "packed_bwd_dkv_differentiated", "provenance_wins", "index_scores",
        "index_scores_provenance", "ring_walk", "ssm_update",
        "retention_update", "retention_read", "retention_write", "other"])
def test_region_of_a_kernel_known_by_name(name, provenance, region):
    """A custom kernel whose device events carry no provenance is known by
    a part of its operation's name (a differentiated program wraps a
    ``pallas_call``'s name: ``jvp_mx_flash_fwd_``)."""
    assert profiler_xla.region_of(provenance, name) == region


def test_no_kernel_name_hides_a_later_one():
    """``region_of`` takes the FIRST listed part a name holds: a part that
    another listed part holds must come after it."""
    parts = [part for part, _ in profiler_xla._KERNEL_REGIONS]
    for i, early in enumerate(parts):
        for late in parts[i + 1:]:
            assert early not in late, (early, late)


def _two_executables(make_xspace):
    """A device plane by hand, times in ps.  ``jit_step`` runs three times
    (the first and the last touch the ends of the trace), ``jit_admit``
    once between them.  In the whole run of ``jit_step`` a ``while`` of
    40 us encloses two body operations of 10 and 25 us."""
    us = 1_000_000
    dense = {"tf_op": "jit(step)/mx.dense/dot_general:"}
    attn = {"tf_op": "jit(step)/mx.dense/while/body/closed_call/mx.attn/"
                     "mul:"}
    view = {"tf_op": "jit(step)/mx.dense/while/body/closed_call/"
                     "mx.paged_view/gather:"}
    return make_xspace([{"name": "/device:TPU:0", "lines": {
        "XLA Modules": [("jit_step(7)", 0, 20 * us),
                        ("jit_step(7)", 100 * us, 60 * us),
                        ("jit_admit(9)", 200 * us, 30 * us),
                        ("jit_step(7)", 300 * us, 20 * us)],
        "XLA Ops": [
            ("%fusion.1 = f32[8] fusion()", 1 * us, 15 * us, dense),
            ("%fusion.1 = f32[8] fusion()", 101 * us, 15 * us),
            ("%while.2 = (s32[]) while()", 117 * us, 40 * us, {}),
            ("%fusion.3 = f32[8] fusion()", 118 * us, 10 * us, view),
            ("%fusion.4 = f32[8] fusion()", 130 * us, 25 * us, attn),
            ("%copy.5 = f32[8] copy()", 158 * us, 2 * us, {}),
            ("%fusion.6 = f32[8] fusion()", 201 * us, 29 * us,
             {"tf_op": "jit(admit)/mx.page_write/scatter:"}),
            ("%fusion.1 = f32[8] fusion()", 301 * us, 15 * us),
        ]}}])


def test_parse_xplane_self_time_and_runs(make_xspace):
    parsed = profiler_xla.parse_xplane(_two_executables(make_xspace))
    assert [(r["module"], r["whole"]) for r in parsed["runs"]] == [
        ("jit_step", False), ("jit_step", True), ("jit_admit", True),
        ("jit_step", False)]
    by_name = {(o["name"], o["run"]): o for o in parsed["ops"]}
    loop = by_name[("while.2", 1)]
    assert loop["dur_us"] == pytest.approx(40.0)
    assert loop["self_us"] == pytest.approx(40.0 - 10.0 - 25.0)
    assert by_name[("fusion.4", 1)]["self_us"] == pytest.approx(25.0)
    assert by_name[("fusion.6", 2)]["module"] == "jit_admit"


def test_device_regions_by_hand(make_xspace):
    table = profiler_xla.device_regions(
        profiler_xla.parse_xplane(_two_executables(make_xspace)))
    step = table["jit_step"]
    assert step["runs"] == 1                # the two at the ends are cut
    assert step["run_seconds"] == pytest.approx(60e-6)
    assert step["regions"] == pytest.approx({
        "mx.dense": 15e-6, "mx.paged_view": 10e-6, "mx.attn": 25e-6,
        "unscoped": 5e-6 + 2e-6})           # the while's own 5, the copy
    assert table["jit_admit"]["regions"] == pytest.approx(
        {"mx.page_write": 29e-6})


def test_facade_device_regions_reads_kept_bytes(make_xspace, monkeypatch):
    monkeypatch.setitem(profiler._state, "xplane",
                        _two_executables(make_xspace))
    monkeypatch.setitem(profiler._state, "parsed", None)
    assert set(profiler.device_regions()) == {"jit_step", "jit_admit"}
    monkeypatch.setitem(profiler._state, "xplane", None)
    monkeypatch.setitem(profiler._state, "parsed", None)
    assert profiler.device_regions() is None
    assert profiler.device_dumps() == ""


def test_nested_start_keeps_the_running_trace(tmp_path):
    """Inside a running ``jax.profiler`` trace the facade records THAT
    trace's directory (JAX 0.9.0: ``_profile_state.profile_session`` /
    ``.log_dir``, pinned here), and its ``stop()`` ends the trace and
    keeps the ``.xplane.pb`` as bytes."""
    import jax
    import jax.numpy as jnp
    from jax._src import profiler as jax_profiler

    assert jax_profiler._profile_state.profile_session is None
    jax.profiler.start_trace(str(tmp_path / "outer"))
    try:
        assert jax_profiler._profile_state.log_dir == str(tmp_path / "outer")
        profiler.set_config(filename=str(tmp_path / "facade"))
        profiler.start()
        assert profiler._state["trace_dir"] == str(tmp_path / "outer")
        jnp.ones(8).sum().block_until_ready()
    finally:
        profiler.stop()
        profiler.set_config(filename="profile.json")
    assert jax_profiler._profile_state.profile_session is None
    assert not (tmp_path / "facade").exists()
    raw = profiler._state["xplane"]
    assert raw and raw == profiler_xla.read_xplane(str(tmp_path / "outer"))
    planes = jax.profiler.ProfileData.from_serialized_xspace(raw).planes
    assert any(p.name == "/host:CPU" for p in planes)
    assert profiler.device_regions() is None    # no device plane on a CPU


def test_set_config_passes_profile_options(tmp_path, monkeypatch):
    import jax

    seen = {}

    def fake_start(log_dir, profiler_options=None):
        seen["options"] = profiler_options

    monkeypatch.setattr(jax.profiler, "start_trace", fake_start)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    profiler.set_config(filename=str(tmp_path / "p"),
                        python_tracer_level=0, host_tracer_level=1)
    try:
        profiler.start()
        profiler.stop()
        assert seen["options"].python_tracer_level == 0
        assert seen["options"].host_tracer_level == 1
        profiler.set_config(python_tracer_level=None,
                            host_tracer_level=None)
        profiler.start()
        profiler.stop()
        assert seen["options"] is None      # JAX's own default
    finally:
        profiler.set_config(filename="profile.json",
                            python_tracer_level=None,
                            host_tracer_level=None)


@pytest.fixture
def facade(tmp_path):
    """The facade running over a real (CPU) trace, Python tracer off."""
    profiler.set_config(filename=str(tmp_path / "trace"),
                        python_tracer_level=0)
    profiler.start()
    try:
        yield
    finally:
        profiler.stop()
        profiler.set_config(filename="profile.json",
                            python_tracer_level=None)


def test_span_records_only_while_a_trace_runs(facade):
    with telemetry.span("t:phase", seq=812, cause=811, wave=2,
                        requests=[4, 5]) as sp:
        pass
    assert sp is not None
    (name, t0, t1, seq, cause, fields), = telemetry.spans("t:phase")
    assert (name, seq, cause) == ("t:phase", 812, 811) and t0 <= t1
    assert fields == {"wave": 2, "requests": [4, 5]}
    profiler.stop()
    with telemetry.span("t:phase", seq=813) as sp:
        pass
    assert sp is None and len(telemetry.spans("t:phase")) == 1
    profiler.start()                        # a new trace clears the ring
    assert telemetry.spans() == []


def test_span_fields_reach_the_trace(tmp_path):
    """``seq`` and the fields arrive as stats of the annotation's event."""
    import jax

    profiler.set_config(filename=str(tmp_path / "trace"),
                        python_tracer_level=0)
    profiler.start()
    try:
        with telemetry.span("mx:serve:step", seq=812):
            pass
    finally:
        profiler.stop()
        profiler.set_config(filename="profile.json",
                            python_tracer_level=None)
    space = jax.profiler.ProfileData.from_serialized_xspace(
        profiler._state["xplane"])
    events = [e for p in space.planes for line in p.lines
              for e in line.events if e.name == "mx:serve:step"]
    assert events and dict(events[0].stats)["seq"] in (812, "812")


def test_serve_phase_spans_tile_the_pump(facade):
    srv = _toy_server()
    rng = np.random.default_rng(0)
    streams = [srv.submit(rng.integers(1, 97, n, dtype=np.int32),
                          max_new_tokens=4) for n in (5, 9, 12)]
    for _ in range(64):
        if all(s.done for s in streams):
            break
        srv.pump()
    srv.close()
    assert all(s.done for s in streams)
    rows = sorted((r for r in telemetry.spans()
                   if r[0].startswith("mx:serve:")), key=lambda r: r[1])
    names = {r[0] for r in rows}
    assert {"mx:serve:cancel", "mx:serve:admit_build", "mx:serve:admit",
            "mx:serve:step", "mx:serve:drain_wait",
            "mx:serve:route"} <= names
    # siblings on one thread: each ends before the next begins
    assert all(a[2] <= b[1] for a, b in zip(rows, rows[1:]))
    # every dispatch has its own seq; a readback names the seq it handles
    dispatched = [r[3] for r in rows if r[3] is not None]
    assert dispatched == sorted(set(dispatched))
    assert {r[4] for r in rows if r[0] == "mx:serve:route"} \
        <= set(dispatched)
    # a request id leads to its wave's dispatch and the steps it rode
    admits = {r[3]: r[5] for r in rows if r[0] == "mx:serve:admit"}
    steps = {r[3] for r in rows if r[0] == "mx:serve:step"}
    events = [e for e in telemetry.events("serve_request")
              if e["server"] == srv.telemetry_label]
    assert len(events) == 3
    for e in events:
        wave = admits[e["admit_seq"]]
        assert e["request_id"] in wave["requests"]
        assert wave["wave"] == e["wave"] and wave["a_bucket"] == \
            e["a_bucket"] and wave["p_bucket"] == e["p_bucket"]
        assert e["admit_seq"] < e["first_step_seq"] <= e["last_step_seq"]
        assert {e["first_step_seq"], e["last_step_seq"]} <= steps


def test_serve_spans_cost_nothing_without_a_trace():
    srv = _toy_server()
    telemetry.clear_spans()
    s = srv.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=3)
    for _ in range(32):
        if s.done:
            break
        srv.pump()
    srv.close()
    assert s.done and telemetry.spans() == []
    assert srv._phase_span is None


def test_train_step_spans(facade):
    tr, batch = _toy_trainer()
    for _ in range(2):
        tr.step(batch, batch)
    rows = [r for r in telemetry.spans() if r[0].startswith("mx:train:")]
    assert [(r[0], r[3]) for r in rows] == [
        ("mx:train:feed", 1), ("mx:train:step", 1),
        ("mx:train:feed", 2), ("mx:train:step", 2)]
    assert all(a[2] <= b[1] for a, b in zip(rows, rows[1:]))
