"""Trace-parsing device profiler (SURVEY.md §5.1 — per-op aggregate table
recovered inside fused jit steps)."""
import pytest

from mxnet_tpu import profiler_xla


def _fusion(n, **stats):
    return f"%fusion.{n} = f32[8]{{0}} fusion(f32[8]{{0}} %p), kind=kLoop", \
        stats


def _trace(make_xspace, ops):
    """One ``/device:TPU:0`` plane whose run of ``jit_step`` holds ``ops``
    (``(name, start_ps, dur_ps, metadata stats)``), between two short runs
    so that it lies whole in the trace, and a host plane beside it."""
    return make_xspace([
        {"name": "/device:TPU:0", "lines": {
            "XLA Modules": [("jit_step(123)", 0, 5_000_000),
                            ("jit_step(123)", 10_000_000, 60_000_000),
                            ("jit_step(123)", 80_000_000, 5_000_000)],
            "XLA Ops": ops}},
        {"name": "/host:CPU", "lines": {
            "python": [("PjitFunction(step)", 0, 99_000_000)]}}])


def test_parse_xplane_device_lane_only(make_xspace):
    name, stats = _fusion(
        1, hlo_category="convolution fusion", model_flops=2147483648,
        raw_bytes_accessed=6291456, tf_op="jit(step)/dot_general:")
    # the host plane's event and the device's "XLA Modules" lane are not
    # operations: only the "XLA Ops" event comes back
    parsed = profiler_xla.parse_xplane(_trace(
        make_xspace, [(name, 12_000_000, 12_600_000, stats)]))
    assert len(parsed["ops"]) == 1
    r = parsed["ops"][0]
    assert r["name"] == "fusion.1"
    assert r["long_name"] == name
    assert r["category"] == "convolution fusion"
    assert abs(r["dur_us"] - 12.6) < 1e-6       # picoseconds in the trace
    assert abs(r["start_us"] - 12.0) < 1e-6
    assert r["flops"] == 2147483648
    assert r["bytes"] == 6291456
    assert r["tf_op"].startswith("jit(step)")
    assert r["module"] == "jit_step" and parsed["runs"][r["run"]]["whole"]


def test_aggregate_and_format(make_xspace):
    n1, s1 = _fusion(1, hlo_category="fusion", model_flops=1000000000,
                     raw_bytes_accessed=1000, tf_op="jit(f)/dot_general:")
    n2, s2 = _fusion(2, hlo_category="fusion", model_flops=0,
                     raw_bytes_accessed=4000, tf_op="jit(f)/add:")
    recs = profiler_xla.parse_xplane(_trace(make_xspace, [
        (n1, 12_000_000, 10_000_000, s1),
        (n2, 30_000_000, 30_000_000, s2)]))["ops"]
    by_cat = profiler_xla.aggregate(recs, by="category")
    assert len(by_cat) == 1 and by_cat[0]["calls"] == 2
    assert abs(by_cat[0]["dur_us"] - 40.0) < 1e-6
    assert abs(by_cat[0]["pct"] - 100.0) < 1e-6

    by_op = profiler_xla.aggregate(recs, by="tf_op")
    assert [r["key"] for r in by_op] == ["jit(f)/add:", "jit(f)/dot_general:"]
    # achieved TFLOP/s: 1e9 flops / 10 us = 1e14 flops/s = 100 TFLOP/s
    assert abs(by_op[1]["tflops"] - 100.0) < 1e-6

    table = profiler_xla.format_table(by_op, peak_tflops=197.0)
    assert "jit(f)/add:" in table and "TOTAL" in table and "MFU%" in table


def test_no_trace_or_no_device_plane_is_none(tmp_path, make_xspace):
    assert profiler_xla.read_xplane(str(tmp_path)) is None
    assert profiler_xla.parse_xplane(str(tmp_path)) is None
    host_only = make_xspace([{"name": "/host:CPU", "lines": {
        "python": [("PjitFunction(step)", 0, 99)]}}])
    assert profiler_xla.parse_xplane(host_only) is None


def test_profile_fn_cpu_no_crash():
    """On CPU the trace has no TPU device lane — profile_fn must still
    run the function and return a (possibly empty) record list."""
    import jax.numpy as jnp
    import jax

    f = jax.jit(lambda x: (x * 2).sum())
    recs = profiler_xla.profile_fn(f, jnp.ones((8, 8)), iters=1)
    assert isinstance(recs, list)


def test_profiler_facade_device_dumps(make_xspace, monkeypatch):
    """mx.profiler.device_dumps() renders the table for the last window
    from the bytes ``stop()`` kept."""
    from mxnet_tpu import profiler

    name, stats = _fusion(1, hlo_category="fusion", raw_bytes_accessed=128,
                          tf_op="jit(f)/mx.attn/mul:")
    raw = _trace(make_xspace, [(name, 12_000_000, 5_000_000, stats)])
    monkeypatch.setitem(profiler._state, "xplane", raw)
    monkeypatch.setitem(profiler._state, "parsed", None)
    assert "jit(f)/mx.attn/mul:" in profiler.device_dumps(by="tf_op")
    assert "mx.attn" in profiler.device_dumps(by="region")


# --------------------------------------------------------------------- #
# static HLO op counting (count_hlo_ops / hlo_op_count)
# --------------------------------------------------------------------- #

_HLO_SAMPLE = """\
HloModule jit_f, is_scheduled=true

%region_0.1 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.1 = f32[] add(f32[] %a, f32[] %b)
}

%fused_computation (p0: f32[2,4]) -> f32[2,4] {
  %p0 = f32[2,4]{1,0} parameter(0)
  %c = f32[] constant(2)
  %bc = f32[2,4]{1,0} broadcast(f32[] %c), dimensions={}
  ROOT %mul.0 = f32[2,4]{1,0} multiply(f32[2,4]{1,0} %p0, f32[2,4]{1,0} %bc)
}

%body.2 (t: (s32[], f32[2,4])) -> (s32[], f32[2,4]) {
  %t = (s32[], f32[2,4]{1,0}) parameter(0)
  %i = s32[] get-tuple-element((s32[], f32[2,4]{1,0}) %t), index=0
  %x = f32[2,4]{1,0} get-tuple-element((s32[], f32[2,4]{1,0}) %t), index=1
  %one = s32[] constant(1)
  %ip = s32[] add(s32[] %i, s32[] %one)
  %fus = f32[2,4]{1,0} fusion(f32[2,4]{1,0} %x), kind=kLoop, calls=%fused_computation
  %z = f32[] constant(0)
  %red = f32[2]{0} reduce(f32[2,4]{1,0} %fus, f32[] %z), dimensions={1}, to_apply=%region_0.1
  %bcast.0 = f32[2,4]{1,0} broadcast(f32[2]{0} %red), dimensions={0}
  ROOT %tup = (s32[], f32[2,4]{1,0}) tuple(s32[] %ip, f32[2,4]{1,0} %bcast.0)
}

%cond.3 (t: (s32[], f32[2,4])) -> pred[] {
  %t = (s32[], f32[2,4]{1,0}) parameter(0)
  %i = s32[] get-tuple-element((s32[], f32[2,4]{1,0}) %t), index=0
  %n = s32[] constant(4)
  ROOT %lt = pred[] compare(s32[] %i, s32[] %n), direction=LT
}

ENTRY %main.4 (arg: f32[2,4]) -> f32[2,4] {
  %arg = f32[2,4]{1,0} parameter(0)
  %zero = s32[] constant(0)
  %tup.0 = (s32[], f32[2,4]{1,0}) tuple(s32[] %zero, f32[2,4]{1,0} %arg)
  %wh = (s32[], f32[2,4]{1,0}) while((s32[], f32[2,4]{1,0}) %tup.0), condition=%cond.3, body=%body.2
  ROOT %out = f32[2,4]{1,0} get-tuple-element((s32[], f32[2,4]{1,0}) %wh), index=1
}
"""


def test_count_hlo_ops_convention():
    """Fusion bodies and reduce combinators are excluded (they execute
    as ONE op in their caller), while bodies/conds count once, and
    parameter/constant/tuple plumbing is free.  Sample counts: body.2
    has add+fusion+reduce+broadcast = 4, cond.3 has compare = 1, entry
    has while = 1."""
    assert profiler_xla.count_hlo_ops(_HLO_SAMPLE) == 6


def test_hlo_op_count_scan_collapses_unrolled_loop():
    """The API motivation in miniature: a scanned body compiles to one
    body's worth of instructions regardless of trip count; the unrolled
    loop grows with it."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def unrolled(x, w):
        for i in range(8):
            x = jnp.tanh(x @ w[i])
        return x

    def scanned(x, w):
        return lax.scan(lambda x, wi: (jnp.tanh(x @ wi), None), x, w)[0]

    x = jax.ShapeDtypeStruct((2, 16), jnp.float32)
    w = jax.ShapeDtypeStruct((8, 16, 16), jnp.float32)
    n_unrolled = profiler_xla.hlo_op_count(unrolled, x, w)
    n_scanned = profiler_xla.hlo_op_count(jax.jit(scanned), x, w)
    assert n_scanned < n_unrolled
    assert n_unrolled >= 8  # at least one dot per unrolled layer
