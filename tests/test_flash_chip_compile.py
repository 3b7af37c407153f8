"""The flash-attention kernels lowered and compiled for a DESCRIBED TPU
v5e — no chip attached, nothing runs (the ``on-chip-measurement`` guide,
section 2).  This is what catches what interpret mode lets through: a block
the chip's tiling refuses, a transpose Mosaic cannot do, a product whose
operand types it rejects ("Bad lhs type": bfloat16 operands at the
framework's ``highest`` default precision), more VMEM than a kernel may
take.  A compile that passes is not a chip run and says nothing of speed.

The topology is described inside a fixture, after a test of this file has
started: only one process may load the TPU's library, so nothing here
touches it at import, and every test of it lives in this one file.  Where
the topology cannot be described the tests skip.
"""
import os

import pytest

SHAPES = {
    # the train cell's attention: B 8 x H 16, L 1,024, D 64, bfloat16
    "gpt2m_train": (128, 1024, 64, "bfloat16"),
    "L2048_D128": (16, 2048, 128, "bfloat16"),
    # a head width that is no multiple of 128 lanes nor of a bfloat16
    # sublane tile still goes unpadded: its block spans the dimension
    "D80_float32": (4, 256, 80, "float32"),
}


@pytest.fixture(scope="module")
def one_chip():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no libtpu here, or its lock is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(one_chip, fn, *shapes):
    import jax
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("kernel", ["fwd", "bwd_dq", "bwd_dkv"])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_kernel_compiles_for_v5e(one_chip, shape, kernel):
    import jax.numpy as jnp
    from mxnet_tpu.ops import attention as attn
    BH, L, D, dtype = SHAPES[shape]
    scale = 1.0 / D ** 0.5
    qkv = ((1, BH, L, D), jnp.dtype(dtype))
    row = ((BH, 1, L), jnp.float32)
    if kernel == "fwd":
        _compile(one_chip,
                 lambda q, k, v: attn._pallas_fwd(q, k, v, scale, True),
                 qkv, qkv, qkv)
    else:
        fn = {"bwd_dq": attn._pallas_bwd_dq,
              "bwd_dkv": attn._pallas_bwd_dkv}[kernel]
        _compile(one_chip,
                 lambda q, k, v, g, lse, delta: fn(
                     q, k, v, g, lse, delta, scale, True),
                 qkv, qkv, qkv, qkv, row, row)


PACKED = {
    # the train cell's projection: B 8, L 1,024, 3 x (H 16 x D 64): heads
    # in pairs, a 128-lane block of the (B, L, 3U) array a grid step
    "gpt2m_train": (8, 16, 64, 1024),
    # one head a lane block
    "L2048_D128": (2, 8, 128, 2048),
}


@pytest.mark.parametrize("kernel", ["fwd", "bwd_dq", "bwd_dkv"])
@pytest.mark.parametrize("shape", list(PACKED))
def test_packed_kernel_compiles_for_v5e(one_chip, shape, kernel):
    """The same three kernels reading q, k and v as lane blocks of the
    packed projection and writing (B, L, U) and one (B, L, 3U) gradient:
    the lane masks that take a head out of a pair, two heads' score-sized
    temporaries in the 16 MiB a kernel may take, the dq kernel's own delta,
    the dk/dv kernel's copies into the buffer it shares with dq."""
    import jax.numpy as jnp
    from mxnet_tpu.ops import attention as attn
    B, H, D, L = PACKED[shape]
    scale = 1.0 / D ** 0.5
    qkv = ((B, L, 3 * H * D), jnp.bfloat16)
    g = ((B, L, H * D), jnp.bfloat16)
    row = ((B * H, 1, L), jnp.float32)
    if kernel == "fwd":
        compiled = _compile(
            one_chip, lambda qkv: attn._pallas_fwd(
                qkv, None, None, scale, True, heads=H), qkv)
    elif kernel == "bwd_dq":        # makes delta itself, of out and do
        compiled = _compile(
            one_chip, lambda qkv, g, lse, out: attn._pallas_bwd_dq(
                qkv, None, None, g, lse, None, scale, True, heads=H,
                out=out), qkv, g, row, g)
    else:       # copies dk and dv into dq's buffer itself
        compiled = _compile(
            one_chip, lambda qkv, g, lse, delta, into: attn._pallas_bwd_dkv(
                qkv, None, None, g, lse, delta, scale, True, heads=H,
                into=into), qkv, g, row, row, qkv)
    assert f"mx_flash_{kernel}_qkv" in compiled.as_text()


# the routed experts' grouped-product kernel (ISSUE 39) at the serve cells'
# four shapes: (rows M = N x top_k, H, I, groups): a question chunk's and a
# decode step's, ``trinity``'s over a stacked run of two layers
MOE = {
    "trinity_step": (96, 3072, 3072, 64),
    "dots3_step": (256, 5120, 1536, 32),
    "trinity_chunk": (512, 3072, 3072, 64),
    "dots3_chunk": (1024, 5120, 1536, 32),
}


@pytest.mark.parametrize("shape", list(MOE))
def test_moe_kernel_compiles_for_v5e(one_chip, shape):
    """``mx_moe_gmm``: weight blocks of about 4 MB double-buffered beside a
    row tile, its float32 output tile and three scratches, under the VMEM
    limit the call asks for; bfloat16 products at ``DEFAULT`` precision
    (the framework's ``highest`` default would fail with "Bad lhs
    type")."""
    import jax.numpy as jnp
    from mxnet_tpu.ops import grouped_matmul as gm
    M, H, I, G = MOE[shape]
    compiled = _compile(
        one_chip, lambda xs, wgu, wd, sizes, base: gm.grouped_swiglu(
            xs, wgu, wd, sizes, base),
        ((M, H), jnp.bfloat16), ((G, H, 2 * I), jnp.bfloat16),
        ((G, I, H), jnp.bfloat16), ((32,), jnp.int32), ((), jnp.int32))
    assert gm._NAME in compiled.as_text()


# the page walks of a latent layer's decode step at the cells' shapes: the
# latent attention kernel at ``pangu_ultra_serve_sessions24``'s (24 slots,
# 128 heads of 640 lanes, five layers of 40,960 pages of 16, a table of
# 2,072 entries) and the index-score kernel at ``dots3``'s, which fetches its
# groups by the same copies
@pytest.mark.parametrize("kernel", ["latent_walk", "index_scores"])
def test_page_walk_kernel_compiles_for_v5e(one_chip, kernel):
    """Two row buffers of a group, the scores and the float32 accumulator
    under the VMEM a kernel may take; run copies of a whole group and of a
    block, page copies; bfloat16 products at ``DEFAULT`` precision."""
    import jax.numpy as jnp
    if kernel == "latent_walk":
        from mxnet_tpu.ops import latent_attention as la
        compiled = _compile(
            one_chip, lambda q, pool, pt, ends: la._kernel_call(
                q, pool, 2, pt, ends, 192 ** -0.5, 512, False),
            ((24, 128, 640), jnp.bfloat16),
            ((5, 40960, 16, 640), jnp.bfloat16), ((24, 2072), jnp.int32),
            ((24,), jnp.int32))
        assert la._NAME in compiled.as_text()
    else:
        from mxnet_tpu.ops import index_scores as ix
        compiled = _compile(
            one_chip, lambda q, w, pool, pt, ends: ix._kernel_call(
                q, w, pool, 1, pt, ends, False),
            ((32, 64, 128), jnp.bfloat16), ((32, 64), jnp.float32),
            ((2, 65536, 16, 128), jnp.bfloat16), ((32, 2072), jnp.int32),
            ((32,), jnp.int32))
        assert ix._NAME in compiled.as_text()


# the page walk of a latent layer's CHUNK at ``pangu_ultra_serve_sessions24``'s
# shapes: a 128-token question and a 512-token document chunk of one slot,
# 128 heads of 640 lanes, five layers of 40,960 pages of 16, a table of 2,072
@pytest.mark.parametrize("C", [128, 512])
def test_latent_chunk_kernel_compiles_for_v5e(one_chip, C):
    """Tiles of 2,048 query rows and groups of 512 rows: the scores and
    weights of a group beside the float32 accumulator and two row buffers
    under the VMEM limit the call asks for; the masked and the unmasked
    update; bfloat16 products at ``DEFAULT`` precision."""
    import jax.numpy as jnp
    from mxnet_tpu.ops import latent_attention as la
    compiled = _compile(
        one_chip, lambda q, pool, pt, ends: la._chunk_call(
            q, pool, 2, pt, ends, 192 ** -0.5, 512, False),
        ((1, C, 128, 640), jnp.bfloat16),
        ((5, 40960, 16, 640), jnp.bfloat16), ((1, 2072), jnp.int32),
        ((1, C), jnp.int32))
    assert la._CHUNK_NAME in compiled.as_text()


def test_retention_update_kernel_compiles_for_v5e(one_chip):
    """The cell's step: 24 slots, 8 KV heads of 128 lanes, the expansion's
    8,704 rows of a 128-wide head (the floor counts 8,256), five query heads
    a group, ``q``, ``k`` and ``v`` in bfloat16 as the projections give
    them; the state donated, its blocks of 4.5 MB in and out under the VMEM
    limit the call asks for, the slot map and the group's row picked by
    masks.  The kernel builds ``phi(q)``, ``phi(k)`` and ``v``'s lane
    spread in VMEM: ``S`` and ``z`` are its only operands of the
    expansion's width, both left in HBM (no memory space ``S(1)``), and no
    buffer of the compiled update but they holds as many floats as one
    layer's ``phi(k)``."""
    import math
    import re

    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import power_retention as pr
    E = pr.expanded_rows(128)
    s_shape, z_shape = (8, 24, 8, 128, E), (8, 24, 8, E)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        (s_shape, jnp.float32), (z_shape, jnp.float32),
        ((24, 40, 128), jnp.bfloat16), ((24, 8, 128), jnp.bfloat16),
        ((24, 8, 128), jnp.bfloat16), ((24, 8), jnp.float32),
        ((24,), jnp.bool_))]
    step = jax.jit(lambda s, z, q, k, v, la, lv: pr.state_update(
        s, z, jnp.int32(3), q, k, v, la, lv, 1e-6), donate_argnums=(0, 1))
    text = step.lower(*args).compile().as_text()
    call = next(line for line in text.splitlines()
                if "tpu_custom_call" in line and pr._NAME in line)
    f32 = lambda txt: [tuple(int(x) for x in m.split(","))
                       for m in re.findall(r"f32\[([\d,]+)\]", txt)]
    operands = re.search(r"operand_layout_constraints=(.*?)"
                         r"output_to_operand_aliasing", call).group(1)
    assert [sh for sh in f32(operands) if sh[-1] == E] == [s_shape, z_shape]
    results = call.split(" custom-call(")[0]
    stored = re.findall(r"f32\[[\d,]+,%d\]\{([^}]*)\}" % E, results)
    assert len(stored) == 2 and not any("S(" in lay for lay in stored)
    limit = re.search(r'"scoped_memory_configs":\[\{[^}]*"size":"(\d+)"',
                      call)
    assert int(limit.group(1)) <= 100 << 20
    wide = {sh for sh in f32(text) if math.prod(sh) >= 24 * 8 * E}
    assert wide == {s_shape, z_shape}


@pytest.mark.parametrize("T", [128, 1024])
def test_retention_prefill_kernels_compile_for_v5e(one_chip, T):
    """The cell's narrowest and widest admission buckets: ``T`` tokens of 40 query
    heads read the carried state of its 8 KV heads (``S`` with ``z`` below
    it, 144 rows a group), and its 8 KV heads write into the state — ``phi``
    built in VMEM a group of 512 rows at a time."""
    import jax.numpy as jnp
    from mxnet_tpu.ops import power_retention as pr
    E = pr.expanded_rows(128)
    read = _compile(
        one_chip, lambda x, s: pr._read_call(x, s, False),
        ((8, 5 * T, 128), jnp.bfloat16), ((8, 144, E), jnp.bfloat16))
    assert pr._READ_NAME in read.as_text()
    write = _compile(
        one_chip, lambda x, c, v: pr._write_call(x, c, v, E, False),
        ((8, T, 128), jnp.bfloat16), ((8, T), jnp.float32),
        ((8, T, 128), jnp.bfloat16))
    assert pr._WRITE_NAME in write.as_text()
