"""The K/V page pool stays lane-dense and the pool executables stay in
place on it (ISSUE 27): a small engine's ``serve.step`` and one
``serve.admit`` are compiled for a TPU v5e that is described and not
attached (``tools/rehearse_serve.py``; nothing runs), and the optimized
HLO is held to what the chip measured as the difference between a 159 ms
and a 15 ms decode step — no pass over the whole pool, the pool kept in
the layout the program declares, no second pool in the program's scratch.
Since ISSUE 30 the native pool's step WALKS its pages: the compile for the
chip must hold the paged-attention kernel (a Mosaic custom call, so the test
cannot pass on the view path the CPU lowers) and no ``(S, T, KV·D)`` view;
the int8 pool keeps the view path.

Every test here uses the ``chip`` fixture, which describes the topology
inside the test's own process and skips where the TPU compiler cannot.
"""
import math

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


def test_native_step_walks_its_pages_in_the_kernel(reports, progs):
    """The TPU lowering of a native pool's step holds the paged-attention
    kernel and builds no view of it; an int8 pool's step gathers the view
    (which it dequantizes on the way) and holds no kernel."""
    step = reports["step"]
    if progs.quant_kv:
        assert not progs.step_walks
        assert step["kernels"] == [] and step["view_sized"]
        return
    assert progs.step_walks
    assert len(step["kernels"]) == 1, step["kernels"]
    assert step["kernels"][0].startswith("mx_paged_attention")
    assert step["view_sized"] == []


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


# --------------------------------------------------------------------------- #
# pools of declared row kinds (a model served from its per-layer description:
# latent rows and index keys under the main table, window rows under a ring)
# --------------------------------------------------------------------------- #

# pools too large for the chip to stage whole in its vector memory (a pool
# of a few MB is prefetched there in one async copy, which no real pool is)
L_SLOTS, L_TOTAL, L_PAGES, L_WPAGES = 4, 2048, 32768, 8192


@pytest.fixture(scope="module")
def layered_progs():
    """Lane widths as the published model has them in kind — a 192 + 64
    latent row, a 128-lane index key, a 320 + 64 window row, all padded to
    whole tiles — at a small depth and hidden size."""
    from mxnet_tpu.models import dots3
    net, _ = dots3.dots3_tiny(
        dtype="bfloat16", hidden_size=256, intermediate_size=384,
        num_attention_heads=4, q_lora_rank=128, kv_lora_rank=192,
        qk_nope_head_dim=64, qk_rope_head_dim=64, v_head_dim=64,
        index_n_heads=4, index_head_dim=128, index_topk=64,
        swa_num_attention_heads=4, swa_q_lora_rank=128, swa_kv_lora_rank=320,
        swa_qk_nope_head_dim=64, swa_qk_rope_head_dim=64, swa_v_head_dim=64,
        sliding_window_size=65, moe_intermediate_size=128, vocab_size=512,
        vocab_slice=(0, 512), max_length=L_TOTAL)
    net.collect_params().setattr("grad_req", "null")
    net.initialize(mx.init.Zero())
    return PoolPrograms(net, L_SLOTS, L_TOTAL, page_size=PAGE,
                        num_pages=L_PAGES, window_pages=L_WPAGES,
                        max_chunk=64)


@pytest.fixture(scope="module")
def layered_reports(chip, layered_progs):
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from tools import rehearse_serve as rs

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        p = layered_progs
        return {
            "step": rs.pool_report(rs.compile_step(p, chip), p),
            "chunk": rs.pool_report(rs.compile_chunk(p, chip, 64), p),
            "admit_hit": rs.pool_report(rs.compile_hit(p, chip, 2), p)}
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def test_layered_pools_are_whole_lane_tiles(layered_progs):
    import jax

    from mxnet_tpu.serve.engine import pool_state_init
    kp, vp = jax.eval_shape(lambda: pool_state_init(layered_progs))[:2]
    shapes = [a.shape for a in jax.tree.leaves((kp, vp))]
    assert shapes == [(2, L_PAGES, PAGE, 256), (2, L_PAGES, PAGE, 128),
                      (3, L_WPAGES, PAGE, 384)]
    assert all(s[-1] % 128 == 0 for s in shapes)
    # priced as allocated: main pages, window pages, slot state
    from mxnet_tpu.serve.engine import pool_state_bytes
    want = sum(onp.prod(s) * 2 for s in shapes) + L_SLOTS * 29
    assert pool_state_bytes(layered_progs, num_pages=L_PAGES) == want


@pytest.mark.parametrize("which", ["step", "chunk", "admit_hit"])
def test_layered_no_pass_over_a_whole_pool(layered_reports, which):
    """Every pool-sized result is an in-place scatter into a donated pool:
    no ``copy``, no fusion that rewrites one."""
    sized = layered_reports[which]["pool_sized"]
    assert set(sized) <= {"fusion:scatter", "scatter"}, sized
    # the step and a chunk write a latent row and an index key a full layer
    # and a window row a sliding layer; a hit copies main-table pages only
    want = 2 if which == "admit_hit" else 2 + 2 + 3
    assert sum(len(v) for v in sized.values()) == want, sized


@pytest.mark.parametrize("which", ["step", "chunk", "admit_hit"])
def test_layered_pools_keep_the_declared_layout(layered_reports, which):
    layouts = layered_reports[which]["pool_entry_layouts"]
    assert len(layouts) == 3, layered_reports[which]
    for lay in layouts:
        assert lay.split("{")[1].startswith("3,2,1,0"), lay


def test_layered_step_scores_its_keys_where_they_lie(layered_reports,
                                                     layered_progs):
    """The TPU lowering of the step holds the index-score kernel, one
    custom call a selecting layer named ``mx_index_scores`` (a Mosaic call:
    the view form the CPU lowers cannot pass), and neither the gathered
    ``(S, T, 128)`` key view nor the ``(S, J, T)`` float32 score block; a
    chunk of ``C`` queries keeps the view form and holds no such kernel."""
    step, chunk = layered_reports["step"], layered_reports["chunk"]
    scoring = [k for k in step["kernels"] if k.startswith("mx_index_scores")]
    assert len(scoring) == len(layered_progs.eng.full) == 2, step["kernels"]
    assert step["view_sized"] == []
    assert not [k for k in chunk["kernels"] if k.startswith("mx_index")]


@pytest.mark.parametrize("which,rows", [("step", "slots"), ("chunk", 64)])
def test_layered_selection_gathers_nothing(layered_reports, layered_progs,
                                           which, rows):
    """The selection gathers nothing.  Before PR 37 ``mask_positions`` and
    its caller fetched, index by index, the chosen blocks' mask rows out of
    ``(N, blocks, 128)``, the blocks' counts out of ``(N, blocks)`` and
    ``seen`` ``(N, T)`` at the positions (the chip's compiler rewrites such
    a gather and drops its provenance, so they are told by what they
    gather FROM, in any region): none of them is left, for the step's one
    query a slot or a chunk's 64.  Nor is the lookup of the
    selected positions' page ids in the page table (``block_pages``: a
    one-hot product): the only gathers from the table, in any region, are
    the new rows' writes, one id a query, never one a selected position.
    Under ``mx.index`` the step's TPU lowering holds no ``gather`` at all —
    the rotation's even and odd lanes are strided slices — and a chunk's
    only one is the key view's, out of the index-key pool.  Under
    ``mx.latent_gather`` each selecting layer holds one gather, of the
    selected latent rows out of the latent pool."""
    report = layered_reports[which]
    N = L_SLOTS if rows == "slots" else rows
    maxp = layered_progs.maxp
    T = maxp * PAGE
    gone = {f"pred[{N},{T}]", f"pred[{N},1,{T}]", f"pred[1,{N},{T}]",
            f"pred[{N},{T // 128},128]", f"s32[{N},{T // 128}]"}
    sources = {t for found in report["gathers"].values() for t in found}
    assert not gone & sources, report["gathers"]
    # the step's rows of the table, or a chunk's one slot's row
    table = {f"s32[{L_SLOTS},{maxp}]", f"s32[1,{maxp}]", f"s32[{maxp}]"}
    for found in report["gather_results"].values():
        for source, result in found:
            if source in table:
                assert math.prod(int(d) for d in result.split("[")[1]
                                 .rstrip("]").split(",") if d) <= N, found
    latent = f"bf16[2,{L_PAGES},{PAGE},256]"
    assert report["gathers"]["mx.latent_gather"] == [latent] * len(
        layered_progs.eng.idx), report["gathers"]
    pool = f"bf16[2,{L_PAGES},{PAGE},128]"
    assert set(report["gathers"].get("mx.index", [])) == (
        set() if which == "step" else {pool}), report["gathers"]


def test_index_key_pool_enters_the_kernel_as_declared(chip, layered_progs):
    """The index-key pool is an operand of the custom call whole, in the
    layout the program declares (minor to major ``{3,2,1,0}``): what the
    in-place scatter of the new keys hands on, no copy of it between."""
    import re

    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from tools import rehearse_serve as rs

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        text = rs.compile_step(layered_progs, chip).as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    pool = f"bf16[2,{L_PAGES},{PAGE},128]"
    calls = [line for line in text.splitlines()
             if " custom-call(" in line and "mx_index_scores" in
             line.split(" = ")[0]]
    assert len(calls) == 2
    by_name = {m.group(1): m.group(2) for m in re.finditer(
        r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = \S+ ([a-z\-]+)\(", text, re.M)}
    for line in calls:
        constraints = line.split("operand_layout_constraints=")[1].split(
            "backend_config")[0]
        assert pool + "{3,2,1,0}" in constraints, constraints
        operands = re.search(r"custom-call\((.*?)\), custom_call_target",
                             line).group(1)
        last = operands.split(",")[-1].strip().lstrip("%")
        # the pool operand is the scatter that wrote the new keys (or the
        # parameter itself), never a copy
        assert by_name.get(last) in ("fusion", "scatter", "parameter",
                                     "get-tuple-element"), (last, by_name.get(last))


@pytest.mark.parametrize("which", ["step", "chunk", "admit_hit"])
def test_layered_scratch_is_no_second_pool(layered_reports, which):
    """The scratch stays under a quarter of the SMALLEST pool (the index
    keys, 268 MB here): no pool-sized copy can hide in it."""
    smallest = 2 * L_PAGES * PAGE * 128 * 2
    assert layered_reports[which]["temp_bytes"] < smallest // 4, \
        layered_reports[which]


# --------------------------------------------------------------------------- #
# the slot table: a recurrent state beside the pages (ISSUE 33)
# --------------------------------------------------------------------------- #

H_SLOTS, H_TOTAL, H_PAGES = 8, 128, 64


@pytest.fixture(scope="module")
def hybrid_progs():
    """Lane widths as the published model has them in kind — state rows of
    two 64-wide heads a 128-lane tile, K and V rows of whole tiles — at a small
    depth and hidden size."""
    from mxnet_tpu.models import granite_hybrid as gh
    net, _ = gh.granite_hybrid_tiny(
        dtype="bfloat16", hidden_size=256, shared_intermediate_size=512,
        num_attention_heads=8, num_key_value_heads=8, mamba_n_heads=8,
        mamba_d_head=64, mamba_d_state=128, mamba_chunk_size=64,
        vocab_size=512, max_length=H_TOTAL, attention_multiplier=0.125)
    net.collect_params().setattr("grad_req", "null")
    net.initialize(mx.init.Zero())
    return PoolPrograms(net, H_SLOTS, H_TOTAL, page_size=PAGE,
                        num_pages=H_PAGES)


@pytest.fixture(scope="module")
def hybrid_reports(chip, hybrid_progs):
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from tools import rehearse_serve as rs

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        p = hybrid_progs
        out = {"step": rs.pool_report(rs.compile_step(p, chip), p),
               "admit": rs.pool_report(rs.compile_admit(p, chip, 2, 64), p),
               "chunk": rs.pool_report(rs.compile_chunk(p, chip, 64), p)}
        for name, row in out.items():
            row["faults"] = rs.slot_state_faults("serve." + name, row, p)
        return out
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def test_slot_table_is_priced_as_allocated(hybrid_progs):
    import jax

    from mxnet_tpu.serve.engine import pool_state_bytes, pool_state_init
    p = hybrid_progs
    assert p.slot_kinds == ("ssm_state",) and p.window is None
    state = jax.eval_shape(lambda: pool_state_init(p))
    shapes = [(a.shape, a.dtype.name) for a in jax.tree.leaves(state[:2])]
    assert shapes == [
        ((1, H_PAGES, PAGE, 256), "bfloat16"),      # K rows, main table
        ((1, H_PAGES, PAGE, 256), "bfloat16"),      # V rows
        ((5, H_SLOTS, 4, 128, 128), "float32"),     # state: (H/2, N, 2P)
        ((5, H_SLOTS, 3 * 768), "bfloat16")]        # tail: 3 rows of 6 tiles
    allocated = sum(int(onp.prod(a.shape)) * a.dtype.itemsize
                    for a in jax.tree.leaves(state))
    assert pool_state_bytes(p, num_pages=H_PAGES) == allocated
    assert p.slot_state_bytes() == 5 * (4 * 128 * 128 * 4 + 3 * 768 * 2)


@pytest.mark.parametrize("which", ["step", "admit", "chunk"])
def test_slot_state_is_updated_in_place(hybrid_reports, which):
    """No ``copy`` as large as a pool array, the step's scratch far under
    one layer's state, and on the step the update is the Pallas kernel (a
    Mosaic custom call: the CPU's lowering cannot pass this)."""
    row = hybrid_reports[which]
    assert row["faults"] == [], row
    one_layer = H_SLOTS * 4 * 128 * 128 * 4
    assert all(n < one_layer for n in row["copy_bytes"].values()), row
    if which == "step":
        assert any("mx_ssm_update" in k for k in row["kernels"]), row
        assert row["temp_bytes"] < one_layer // 2, row


@pytest.mark.parametrize("which", ["step", "admit", "chunk"])
def test_slot_pools_keep_the_declared_layout(hybrid_reports, which):
    for lay in hybrid_reports[which]["pool_entry_layouts"]:
        dims = lay.split("{")[1]
        assert dims.startswith(("4,3,2,1,0", "3,2,1,0", "2,1,0")), lay


# --------------------------------------------------------------------------- #
# K and V rows under the window table's ring (ISSUE 35)
# --------------------------------------------------------------------------- #

W_SLOTS, W_TOTAL, W_PAGES, W_WPAGES = 4, 2048, 16384, 4096


@pytest.fixture(scope="module")
def window_progs():
    """Gated grouped-query attention, three sliding layers (a window of 65)
    in four, K/V rows of one whole lane tile (2 heads of 64), routed layers
    in a run of three: the stacked-runs body with both tables."""
    from mxnet_tpu.models import trinity
    net, _ = trinity.trinity_tiny(
        dtype="bfloat16", hidden_size=256, intermediate_size=384,
        num_attention_heads=8, num_key_value_heads=2, head_dim=64,
        sliding_window=65, moe_intermediate_size=512, vocab_size=512,
        vocab_slice=(0, 512), num_hidden_layers=5,
        layer_types=("sliding_attention",) * 4 + ("full_attention",),
        max_length=W_TOTAL)
    net.collect_params().setattr("grad_req", "null")
    net.initialize(mx.init.Zero())
    return PoolPrograms(net, W_SLOTS, W_TOTAL, page_size=PAGE,
                        num_pages=W_PAGES, window_pages=W_WPAGES,
                        max_chunk=64)


@pytest.fixture(scope="module")
def window_reports(chip, window_progs):
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from tools import rehearse_serve as rs

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        p = window_progs
        return {
            "step": rs.pool_report(rs.compile_step(p, chip), p),
            "chunk": rs.pool_report(rs.compile_chunk(p, chip, 64), p),
            "admit_hit": rs.pool_report(rs.compile_hit(p, chip, 2), p)}
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def test_window_pools_are_priced_as_allocated(window_progs):
    import jax

    from mxnet_tpu.serve.engine import pool_state_bytes, pool_state_init
    p = window_progs
    kp, vp = jax.eval_shape(lambda: pool_state_init(p))[:2]
    shapes = [a.shape for a in jax.tree.leaves((kp, vp))]
    assert shapes == [(1, W_PAGES, PAGE, 128)] * 2 \
        + [(4, W_WPAGES, PAGE, 128)] * 2
    # a ring: the window's pages back, the one written, a chunk's, a spare
    assert p.window == 65 and p.ring == 4 + 1 + 4 + 1
    want = sum(onp.prod(s) * 2 for s in shapes) + W_SLOTS * 29
    assert pool_state_bytes(p, num_pages=W_PAGES) == want


@pytest.mark.parametrize("which", ["step", "chunk", "admit_hit"])
def test_window_no_pass_over_a_whole_pool(window_reports, which):
    """Every pool-sized result is an in-place scatter into a donated pool:
    a K and a V row a layer, four of them through the ring; a hit copies
    main-table pages only."""
    sized = window_reports[which]["pool_sized"]
    assert not window_reports[which]["copy_bytes"]
    allowed = {"fusion:scatter", "scatter", "fusion:dynamic-update-slice"}
    assert set(sized) <= allowed, sized
    layouts = window_reports[which]["pool_entry_layouts"]
    assert len(layouts) == 2, window_reports[which]
    for lay in layouts:
        assert lay.split("{")[1].startswith("3,2,1,0"), lay


def test_window_step_walks_both_tables_in_the_kernel(window_reports):
    """One kernel body, two names: the run of three sliding layers and the
    dense one walk their rings (``mx_paged_attention_window``), the full
    layer its table row from 0; the runs' grouped products beside them."""
    kernels = window_reports["step"]["kernels"]
    walks = [k for k in kernels if k.startswith("mx_paged_attention")]
    ring = [k for k in walks if k.startswith("mx_paged_attention_window")]
    # three runs: dense sliding, routed sliding x 3 (one scan), full
    assert len(walks) == 3 and len(ring) == 2, kernels
    # the routed experts' two products: one kernel a run (PR 39)
    assert any(k.startswith("mx_moe_gmm") for k in kernels)
    assert not any(k.startswith("mx_paged_attention")
                   for k in window_reports["chunk"]["kernels"])


@pytest.mark.parametrize("which", ["step", "chunk", "admit_hit"])
def test_window_scratch_is_no_second_pool(window_reports, window_progs,
                                          which):
    """The scratch stays under a quarter of the smaller pool, and the
    step's under ONE layer's gate-and-up experts (16 x 256 x 1,024): a scan
    over the routed run slices no layer's experts out for the grouped
    product (``ops.moe.routed_experts``)."""
    smaller = W_PAGES * PAGE * 128 * 2
    temp = window_reports[which]["temp_bytes"]
    assert temp < smaller // 4, window_reports[which]
    if which == "step":
        assert temp < 16 * 256 * 1024 * 2 // 2, window_reports[which]
