"""The K/V page pool stays lane-dense and the pool executables stay in
place on it (ISSUE 27): a small engine's ``serve.step`` and one
``serve.admit`` are compiled for a TPU v5e that is described and not
attached (``tools/rehearse_serve.py``; nothing runs), and the optimized
HLO is held to what the chip measured as the difference between a 159 ms
and a 15 ms decode step — no pass over the whole pool, the pool kept in
the layout the program declares, no second pool in the program's scratch.

Every test here uses the ``chip`` fixture, which describes the topology
inside the test's own process and skips where the TPU compiler cannot.
"""
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import models
from mxnet_tpu.serve.engine import PoolPrograms

SLOTS, TOTAL, PAGE, NPAGES = 4, 128, 16, 256
LAYERS, UNITS, HEADS = 2, 256, 4            # D = 64, KV·D = 256 lanes


@pytest.fixture(scope="module")
def chip():
    from tools import rehearse_serve
    try:
        return rehearse_serve.v5e_chip()
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module", params=["native", "int8"])
def progs(request):
    net = models.GPT(models.GPTConfig(
        vocab_size=512, num_layers=LAYERS, units=UNITS, num_heads=HEADS,
        hidden_size=4 * UNITS, max_length=TOTAL, dtype="bfloat16"))
    net.collect_params().setattr("grad_req", "null")
    net.initialize(mx.init.Zero())
    return PoolPrograms(net, SLOTS, TOTAL, page_size=PAGE,
                        num_pages=NPAGES, kv_dtype=request.param)


@pytest.fixture(scope="module")
def reports(chip, progs):
    """``pool_report`` of the step and of one admit wave, compiled once
    without JAX's persistent cache (a TPU-target compile is written to it
    but cannot be read back without a chip)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from tools import rehearse_serve as rs

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return {
            "step": rs.pool_report(rs.compile_step(progs, chip), progs),
            "admit": rs.pool_report(rs.compile_admit(progs, chip, 2, 32),
                                    progs)}
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _pool_bytes(progs):
    """Bytes of one pool array (an int8 pool's codes)."""
    e = progs.eng
    item = 1 if progs.quant_kv else onp.dtype(e.cdtype).itemsize
    return e.NL * NPAGES * PAGE * e.KV * e.D * item


@pytest.mark.parametrize("which", ["step", "admit"])
def test_no_pass_over_the_whole_pool(reports, which):
    """No ``copy`` of the pool and no fusion that writes a pool-sized
    result, but for the scatters that update the donated pool in place."""
    sized = reports[which]["pool_sized"]
    assert set(sized) <= {"fusion:scatter", "scatter"}, sized
    # K and V (and nothing else) are scattered into: one update each
    assert sum(len(v) for v in sized.values()) == 2, sized


@pytest.mark.parametrize("which", ["step", "admit"])
def test_pool_keeps_the_declared_layout(reports, which):
    """The compiler keeps the pool major-to-minor as declared, (layer,
    page, row, KV·D): a re-ordered entry layout is what made every
    consumer of the old (…, page, D = 64) pool convert it."""
    layouts = reports[which]["pool_entry_layouts"]
    assert layouts, reports[which]
    for lay in layouts:
        assert lay.split("{")[1].startswith("3,2,1,0"), lay


def test_step_scratch_is_a_few_views(reports, progs):
    """``serve.step`` reserves no second pool: its scratch is under four
    of one layer's (S, T, KV·D) views (K and V, gathered and updated)."""
    e = progs.eng
    view = SLOTS * progs.Tp * e.KV * e.D * onp.dtype(e.cdtype).itemsize
    assert reports["step"]["temp_bytes"] < 4 * view
    assert reports["step"]["temp_bytes"] < _pool_bytes(progs) // 4


def test_admit_scratch_is_the_prefill_not_the_pool(reports, progs):
    """``serve.admit``'s scratch is its own dense prefill, not a copy of
    the pool (which is sized here to dwarf a 2 x 32 wave)."""
    assert reports["admit"]["temp_bytes"] < _pool_bytes(progs) // 4
