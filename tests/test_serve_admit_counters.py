"""Admission measured from inside the server (ISSUE 38).

``DecodeServer.counters`` gains ``admit_rows`` (token positions the
admission dispatches compute: ``A x P`` a wave, ``C`` a chunk, none a prefix
hit), ``admit_tokens`` (the real prompt tokens among them), ``hit_dispatches``
(``prefix_hits`` counts hit rows and partial hits, not dispatches) and
``compiles`` / ``compile_ms`` (this server's pool executables, counted by the
compile watch whatever the event ring still holds).  The admission phase
spans carry ``rows`` and ``tokens``.  Held exact on a tiny GPT-2 server and
a tiny layered engine (trinity: windowed layers, routed experts).
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import profiler, serve, telemetry


def _gpt():
    from mxnet_tpu.models import GPT, GPTConfig
    mx.random.seed(0)
    net = GPT(GPTConfig(vocab_size=97, max_length=64, num_layers=2,
                        units=32, num_heads=4, hidden_size=64))
    net.initialize(mx.init.Normal(0.02))
    return net


def _trinity():
    from chipbench import weights_trinity
    from mxnet_tpu.models import trinity
    net, cfg = trinity.trinity_tiny(held_experts=(4, 8))
    net.collect_params().setattr("grad_req", "null")
    net.initialize(mx.init.Zero())
    w = weights_trinity.make(trinity.parameter_shapes(cfg), 5,
                             {"qk_gain": 1.7, "expert_out_gain": 3.0})
    for n, p in net.collect_params().items():
        p.set_data(w[n[len(net.prefix):]])
    return net


# per engine: the server's options, and for each case the prompts' lengths
# (``doc`` first: served and drained before the counters are zeroed) with
# the expected (dispatches, rows, tokens).  GPT-2: pages of 16, buckets 8 /
# 16.  trinity: pages of 4, buckets 8 / 32, a match stops a page short of
# the whole prompt (a window page is never copied).
ENGINES = {
    "gpt2": (_gpt, dict(max_total_len=64, pool_sizes=(4,),
                        admit_sizes=(1, 4), prefill_buckets=(8, 16))),
    "trinity": (_trinity, dict(max_total_len=128, pool_sizes=(4,),
                               admit_sizes=(1, 4), prefill_buckets=(8, 32),
                               page_size=4, num_pages=96,
                               num_window_pages=96)),
}
CASES = {
    # a wave of 1: one row of its bucket
    ("gpt2", "wave_of_1"): ((), (5,), dict(admit_dispatches=1), 8, 5),
    ("trinity", "wave_of_1"): ((), (5,), dict(admit_dispatches=1), 8, 5),
    # two prompts in one pump: a wave of 4 rows (the A bucket) at the
    # longest prompt's bucket, two of them idle
    ("gpt2", "wave_of_4_two_real"): ((), (5, 13), dict(admit_dispatches=1),
                                     4 * 16, 18),
    ("trinity", "wave_of_4_two_real"): ((), (5, 13),
                                        dict(admit_dispatches=1),
                                        4 * 32, 18),
    # past the largest bucket: chunks of the top bucket, then the rest's
    ("gpt2", "chunked"): ((), (21,), dict(chunk_dispatches=2), 16 + 8, 21),
    ("trinity", "chunked"): ((), (50,), dict(chunk_dispatches=2),
                             32 + 32, 50),
    # the whole prompt cached: no model forward, no rows
    ("gpt2", "prefix_hit"): ((32,), None, dict(hit_dispatches=1,
                                               prefix_hits=1), 0, 0),
    ("trinity", "prefix_hit"): ((52,), 1, dict(hit_dispatches=1,
                                               prefix_hits=1), 0, 0),
    # a cached prefix and a new suffix: one chunk of the suffix's bucket
    ("gpt2", "partial_hit"): ((32,), 5, dict(chunk_dispatches=1,
                                             prefix_hits=1,
                                             hit_dispatches=0), 8, 5),
    ("trinity", "partial_hit"): ((50,), 7, dict(chunk_dispatches=1,
                                                prefix_hits=1,
                                                hit_dispatches=0), 32, 9),
}


@pytest.fixture(scope="module", params=sorted(ENGINES))
def engine(request):
    build, kw = ENGINES[request.param]
    srv = serve.DecodeServer(build(), spec=False, autostart=False, **kw)
    yield request.param, srv
    srv.close(drain=False)


def _tokens(n, seed):
    return np.random.default_rng(seed).integers(0, 96, n).astype(np.int32)


def _drain(srv, streams):
    for _ in range(400):
        if all(s.done for s in streams):
            break
        srv.pump()
    assert all(s.done for s in streams)


def _run_case(srv, case, seed):
    """Serve ``case``'s document (if any), zero the counters, then submit
    its prompts in ONE pump's wave and drain."""
    doc_lens, rest, _, _, _ = case
    prompts = []
    if doc_lens:
        doc = _tokens(doc_lens[0], seed)
        _drain(srv, [srv.submit(doc, max_new_tokens=1)])
        prompts = [doc if rest is None
                   else np.concatenate([doc, _tokens(rest, seed + 1)])]
    else:
        prompts = [_tokens(n, seed + i) for i, n in enumerate(rest)]
    srv.reset_counters()
    _drain(srv, [srv.submit(p, max_new_tokens=2) for p in prompts])
    return srv.stats()["counters"]


@pytest.mark.parametrize("case", ["wave_of_1", "wave_of_4_two_real",
                                  "chunked", "prefix_hit", "partial_hit"])
def test_admit_rows_and_tokens_are_exact(engine, case):
    name, srv = engine
    want = CASES[(name, case)]
    c = _run_case(srv, want, seed=100 + len(case))
    for key, n in want[2].items():
        assert c[key] == n, (key, c)
    assert (c["admit_rows"], c["admit_tokens"]) == (want[3], want[4]), c
    # every counter of a prompt's admission sits beside the ones it had
    assert c["admit_tokens"] <= c["admit_rows"]


def test_admission_spans_carry_rows_and_tokens(engine, tmp_path):
    """While a trace runs each admission phase span says how many token
    positions its dispatch computes and how many are real; summed over the
    spans they are the counters."""
    name, srv = engine
    profiler.set_config(filename=str(tmp_path / "trace"),
                        python_tracer_level=0)
    profiler.start()
    try:
        c = _run_case(srv, CASES[(name, "wave_of_4_two_real")], seed=7)
    finally:
        profiler.stop()
        profiler.set_config(filename="profile.json",
                            python_tracer_level=None)
    rows = [r for r in telemetry.spans()
            if r[0] in ("mx:serve:admit", "mx:serve:admit_hit",
                        "mx:serve:chunk")]
    last = [r for r in rows if r[0] == "mx:serve:admit"][-1][5]
    assert (last["rows"], last["tokens"]) == (c["admit_rows"],
                                             c["admit_tokens"])
    assert last["rows"] == last["a_bucket"] * last["p_bucket"]


def test_compiles_are_counted_past_a_wrapped_event_ring(monkeypatch):
    """A bucket first met after ``reset_counters()`` counts one compile and
    its wall milliseconds; with an event ring of 8 events flooded between
    two such buckets, the counters still count both, where differencing the
    ring's compile events cannot."""
    monkeypatch.setenv("MXNET_TELEMETRY_EVENTS", "8")
    telemetry.clear_events()
    try:
        srv = serve.DecodeServer(_gpt(), max_total_len=64, pool_sizes=(4,),
                                 admit_sizes=(1, 4),
                                 prefill_buckets=(8, 16), spec=False,
                                 autostart=False)
        _drain(srv, [srv.submit(_tokens(5, 1), max_new_tokens=2)])
        srv.reset_counters()
        assert (srv.counters["compiles"], srv.counters["compile_ms"]) == (0, 0)
        ring0 = len(telemetry.events("compile"))
        _drain(srv, [srv.submit(_tokens(13, 2), max_new_tokens=2)])
        assert srv.counters["compiles"] == 1       # the (1, 16) wave
        assert srv.counters["compile_ms"] > 0
        for i in range(20):
            telemetry.emit("phase", name=f"filler{i}")
        assert not telemetry.events("compile")      # the ring let it go
        _drain(srv, [srv.submit(_tokens(21, 3), max_new_tokens=2)])
        c = srv.stats()["counters"]
        assert c["compiles"] == 3        # the chunks of 16 and of 8
        assert len(telemetry.events("compile")) - ring0 < c["compiles"]
        # one server's compiles are its own
        assert telemetry.counter("serve_compiles_total",
                                 server=srv.telemetry_label).value == 3
        srv.close()
    finally:
        monkeypatch.delenv("MXNET_TELEMETRY_EVENTS")
        telemetry.clear_events()
