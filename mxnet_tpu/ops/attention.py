"""Attention kernels: flash attention (Pallas TPU) + ring attention (SP).

Reference counterpart: the BERT-era fused attention matmuls
(``_contrib_interleaved_matmul_selfatt_qk/valatt``, SURVEY.md §3.1
"Operator corpus" contrib family) which materialize the O(L²) score matrix.
The TPU-native answer (SURVEY.md §5.7 — NEW capability, not parity) is:

- ``flash_attention``: blockwise online-softmax attention, O(L) memory.
  On TPU both the forward AND backward run as Pallas kernels (MXU-tiled
  128-blocks, fp32 accumulation); everywhere else a ``lax.scan`` blockwise
  implementation that XLA fuses.  Padding masks (additive bias of layout
  ``(B|1, 1, 1, Lk)``) and attention dropout run INSIDE the kernels;
  general dense biases (e.g. ALiBi tables) take the XLA blockwise path.
  Backward recomputes blockwise from the saved log-sum-exp (the
  flash-attention-2 scheme) — no O(L²) residuals on any path.
- ``ring_attention``: sequence-parallel attention over a mesh axis; K/V
  shards rotate around the ICI ring via ``ppermute`` while each device
  accumulates online-softmax partials for its local Q shard.  This is the
  scale-out long-context path (SURVEY.md §3.3 "SP/CP" row).

Dropout determinism: the keep-mask is a pure position hash of
``(seed, batch·head, q_pos, k_pos)`` computed identically by the Pallas
kernels and the XLA paths, so a forward on one path and a backward
recompute on another still see the same mask.

Shapes follow (batch, heads, seq, head_dim) throughout.
"""
from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from .registry import op

__all__ = ["flash_attention", "ring_attention", "rope"]

_NEG_INF = -1e30
_BLOCK = 128  # MXU-native q/k tile


def _interpret() -> bool:
    # run the Pallas kernels in interpreter mode (CPU numerics testing)
    # backend hatch read at trace time; the pod launcher exports MXNET_*
    # to every rank, so the read is host-uniform by deployment contract:
    # tracelint: disable=TL007 -- tools/launch.py propagates MXNET_* env to all ranks
    return os.environ.get("MXNET_FLASH_INTERPRET", "") == "1"


def _pallas_backend_ok() -> bool:
    """Shared Pallas backend gate (flash, q8_matvec): interpret mode or a
    real TPU backend."""
    if _interpret():
        return True
    return jax.default_backend() == "tpu"


def _use_pallas() -> bool:
    # backend hatch read at trace time; the pod launcher exports MXNET_*
    # to every rank, so the read is host-uniform by deployment contract:
    # tracelint: disable=TL007 -- tools/launch.py propagates MXNET_* env to all ranks
    env = os.environ.get("MXNET_USE_FLASH_ATTENTION", "").lower()
    if env in ("0", "false", "off"):
        return False
    return _pallas_backend_ok()


def _is_kmask(bias) -> bool:
    """Additive bias of layout (B|1, 1, 1, Lk) — a key padding mask."""
    return bias is not None and bias.ndim == 4 and \
        bias.shape[1] == 1 and bias.shape[2] == 1


def _pallas_eligible(q, k, bias, dtype_ok=True) -> bool:
    if not _use_pallas():
        return False
    if q.shape[2] % _BLOCK or k.shape[2] % _BLOCK:
        return False
    if bias is not None and not (_is_kmask(bias) and
                                 bias.shape[3] == k.shape[2]):
        return False
    return dtype_ok


# --------------------------------------------------------------------------- #
# dropout keep-mask: pure position hash, identical on every path
# --------------------------------------------------------------------------- #

def _hash_bits(seed, bh, qpos, kpos):
    """murmur3-style avalanche over (seed, batch·head, q, k) -> uint32.
    ``bh``/``qpos``/``kpos`` broadcast against each other; pure uint32
    elementwise ops so the Pallas TPU lowering computes bit-identical
    values to XLA."""
    u = jnp.uint32
    h = u(seed) ^ (jnp.asarray(bh).astype(jnp.uint32) * u(0x9E3779B1))
    h = h ^ (jnp.asarray(qpos).astype(jnp.uint32) * u(0x85EBCA77))
    h = h ^ (jnp.asarray(kpos).astype(jnp.uint32) * u(0xC2B2AE3D))
    h = h ^ (h >> 16)
    h = h * u(0x7FEB352D)
    h = h ^ (h >> 15)
    h = h * u(0x846CA68B)
    h = h ^ (h >> 16)
    return h


def _keep_threshold(rate: float):
    # drop iff bits < rate * 2^32  (P = rate, a python float hyperparam)
    # tracelint: disable=TL001 -- scalar cast folds at trace time
    return jnp.uint32(min(int(rate * 4294967296.0), 4294967295))


def _keep(seed, bh, qpos, kpos, rate):
    return _hash_bits(seed, bh, qpos, kpos) >= _keep_threshold(rate)


# --------------------------------------------------------------------------- #
# blockwise XLA path (runs everywhere; O(L) memory via scan over q blocks)
# --------------------------------------------------------------------------- #

def _blockwise_attn(q, k, v, bias, seed, scale, causal, dropout, q_block):
    """Online-softmax attention, scanning over q blocks.  Returns
    (out, lse) with lse = logsumexp of scores per query row (fp32).
    ``bias`` is an optional additive score bias broadcastable to
    (B, H, Lq, Lk) — the padding-mask channel."""
    B, H, Lq, D = q.shape
    Lk = k.shape[2]
    nq = -(-Lq // q_block)
    pad_q = nq * q_block - Lq
    qf = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0))) if pad_q else q
    qf = qf.reshape(B, H, nq, q_block, D)
    if bias is not None:
        bias = jnp.broadcast_to(
            bias.astype(jnp.float32),
            (bias.shape[0], bias.shape[1], Lq, Lk))
        bias = jnp.pad(bias, ((0, 0), (0, 0), (0, pad_q), (0, 0))) \
            if pad_q else bias
    v32 = v.astype(jnp.float32)
    kpos = lax.broadcasted_iota(jnp.int32, (1, Lk), 1)
    bh = (lax.broadcasted_iota(jnp.int32, (B, H), 0) * H +
          lax.broadcasted_iota(jnp.int32, (B, H), 1))[..., None, None]

    def one_block(i, qb):
        s = jnp.einsum("bhqd,bhkd->bhqk", qb, k,
                       preferred_element_type=jnp.float32)
        s = s * scale
        if bias is not None:
            s = s + lax.dynamic_slice_in_dim(bias, i * q_block, q_block,
                                             axis=2)
        qpos = i * q_block + lax.broadcasted_iota(
            jnp.int32, (q_block, 1), 0)
        if causal:
            s = jnp.where(qpos >= kpos, s, _NEG_INF)
        m = jnp.max(s, axis=-1, keepdims=True)
        m = jnp.maximum(m, -1e30)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        if dropout > 0.0:
            keep = _keep(seed, bh, qpos[None, None], kpos[None, None],
                         dropout)
            p = jnp.where(keep, p, 0.0) / (1.0 - dropout)
        o = jnp.einsum("bhqk,bhkd->bhqd", p, v32) / jnp.maximum(l, 1e-30)
        lse = (m + jnp.log(jnp.maximum(l, 1e-30)))[..., 0]
        return o, lse

    def scan_fn(_, xs):
        i, qb = xs
        return None, one_block(i, qb)

    _, (o, lse) = lax.scan(
        scan_fn, None, (jnp.arange(nq), jnp.moveaxis(qf, 2, 0)))
    o = jnp.moveaxis(o, 0, 2).reshape(B, H, nq * q_block, D)
    lse = jnp.moveaxis(lse, 0, 2).reshape(B, H, nq * q_block)
    if pad_q:
        o, lse = o[:, :, :Lq], lse[:, :, :Lq]
    return o.astype(q.dtype), lse


# --------------------------------------------------------------------------- #
# Pallas TPU forward kernel
# --------------------------------------------------------------------------- #

def _kmask_arrays(bias, B):
    """(B|1, 1, 1, Lk) additive mask -> (Nb, 1, Lk) fp32 view for the
    kernels (middle singleton keeps the Pallas block 3D/tile-legal)."""
    return bias.astype(jnp.float32).reshape(
        bias.shape[0], 1, bias.shape[3])


def _pad_heads(x, D):
    if x.shape[-1] == D:
        return x
    return jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, D - x.shape[-1]),))


# residual layout: lse/delta are stored lane-replicated at width 128
# ((BH, L, 128)) — the same scheme as jax.experimental.pallas.ops.tpu.
# flash_attention — so the backward kernels can read (block_q, 1) columns
# without any in-kernel transpose.
_LANES = 128


def _rep(x):
    """(BH, L) -> (BH, L, 128) lane-replicated."""
    return jnp.broadcast_to(x[..., None], x.shape + (_LANES,))


def _block_q_for(L):
    """Larger q blocks at length cut k/v HBM re-streaming (traffic scales
    with L/block_q) while staying within VMEM."""
    for bq in (512, 256, 128):
        if L % bq == 0:
            return bq
    return _BLOCK


def _pallas_fwd(q, k, v, scale, causal, kmask=None, seed=None, dropout=0.0,
                block_q=None, block_k=_BLOCK):
    """Flash forward on TPU.  Grid (batch·heads, q_blocks, k_blocks) with
    the k axis innermost: VMEM holds one q/k/v block at a time (O(block·D)
    VMEM — long sequences stream from HBM) while running max / sum / output
    accumulators live in VMEM scratch across the k sweep.  head_dim is
    padded to the 128-lane width so every model head size hits the MXU.
    ``kmask`` is an optional (Nb, 1, Lk) additive bias (key padding mask);
    ``dropout``/``seed`` apply in-kernel attention dropout via the shared
    position hash."""
    if block_q is None:
        block_q = _block_q_for(q.shape[2])
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, L, D0 = q.shape
    Lk = k.shape[2]
    D = max(128, -(-D0 // 128) * 128)
    q, k, v = (_pad_heads(x, D) for x in (q, k, v))
    nq = L // block_q
    nk = Lk // block_k
    inv_keep = 1.0 / (1.0 - dropout) if dropout > 0.0 else 1.0

    def kernel(seed_ref, *refs):
        if kmask is not None:
            km_ref = refs[0]
            refs = refs[1:]
        q_ref, k_ref, v_ref, o_ref, lse_ref, m_s, l_s, acc_s = refs
        bhi = pl.program_id(0)
        qi = pl.program_id(1)
        kj = pl.program_id(2)

        @pl.when(kj == 0)
        def _init():
            m_s[:] = jnp.full_like(m_s, _NEG_INF)
            l_s[:] = jnp.zeros_like(l_s)
            acc_s[:] = jnp.zeros_like(acc_s)

        run = True
        if causal:
            # skip fully-masked blocks above the diagonal
            run = (qi + 1) * block_q > kj * block_k

        @pl.when(run if causal else True)
        def _compute():
            qb = q_ref[0].astype(jnp.float32)
            kb = k_ref[0].astype(jnp.float32)
            vb = v_ref[0].astype(jnp.float32)
            s = jax.lax.dot_general(
                qb, kb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if kmask is not None:
                s = s + km_ref[0]                       # (1, bk) broadcast
            qpos = qi * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, 1), 0)
            kpos = kj * block_k + lax.broadcasted_iota(
                jnp.int32, (1, block_k), 1)
            if causal:
                s = jnp.where(qpos >= kpos, s, _NEG_INF)
            m_prev = m_s[:]
            m_new = jnp.maximum(
                m_prev, jnp.broadcast_to(
                    jnp.max(s, axis=-1, keepdims=True), (block_q, _LANES)))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new[:, :1])
            # fully-masked rows/blocks: exp(-1e30 - (-1e30)) == 1 poison
            p = jnp.where(s <= _NEG_INF * 0.5, 0.0, p)
            m_s[:] = m_new
            l_s[:] = l_s[:] * alpha + jnp.broadcast_to(
                jnp.sum(p, axis=-1, keepdims=True), (block_q, _LANES))
            if dropout > 0.0:
                keep = _keep(seed_ref[0, 0], bhi, qpos, kpos, dropout)
                p = jnp.where(keep, p, 0.0) * inv_keep
            acc_s[:] = acc_s[:] * alpha[:, :1] + jax.lax.dot_general(
                p, vb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        @pl.when(kj == nk - 1)
        def _finalize():
            l = jnp.maximum(l_s[:], 1e-30)
            o_ref[0] = (acc_s[:] / l[:, :1]).astype(o_ref.dtype)
            lse_ref[0] = m_s[:] + jnp.log(l)

    grid = (B * H, nq, nk)
    qr = q.reshape(B * H, L, D)
    kr = k.reshape(B * H, Lk, D)
    vr = v.reshape(B * H, Lk, D)
    in_specs = [
        pl.BlockSpec((1, 1), lambda b, i, j: (0, 0),
                     memory_space=pltpu.SMEM),
    ]
    args = [jnp.full((1, 1), 0 if seed is None else seed, jnp.uint32)]
    if kmask is not None:
        Nb = kmask.shape[0]
        if Nb == 1:
            km_idx = lambda b, i, j: (0, 0, j)
        else:
            km_idx = lambda b, i, j: (b // H, 0, j)
        in_specs.append(pl.BlockSpec((1, 1, block_k), km_idx,
                                     memory_space=pltpu.VMEM))
        args.append(kmask)
    in_specs += [
        pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0),
                     memory_space=pltpu.VMEM),
    ]
    args += [qr, kr, vr]
    out, lse_rep = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, _LANES), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, L, D), q.dtype),
            jax.ShapeDtypeStruct((B * H, L, _LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
    )(*args)
    out = out.reshape(B, H, L, D)
    if D != D0:
        out = out[..., :D0]
    return out, lse_rep[..., 0].reshape(B, H, L)


# --------------------------------------------------------------------------- #
# Pallas TPU backward kernels (flash-attention-2: recompute from lse)
# --------------------------------------------------------------------------- #

def _pallas_bwd_dq(q, k, v, g, lse_rep, dlt_rep, scale, causal, kmask=None,
                   seed=None, dropout=0.0, block_q=None, block_k=_BLOCK):
    """dq kernel: grid (BH, nq, nk), k innermost; dq accumulates in VMEM.
    ``lse_rep``/``dlt_rep`` are the lane-replicated (BH, L, 128) residuals."""
    if block_q is None:
        block_q = _block_q_for(q.shape[2])
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, L, D0 = q.shape
    Lk = k.shape[2]
    D = max(128, -(-D0 // 128) * 128)
    q, k, v, g = (_pad_heads(x, D) for x in (q, k, v, g))
    nq, nk = L // block_q, Lk // block_k
    inv_keep = 1.0 / (1.0 - dropout) if dropout > 0.0 else 1.0

    def kernel(seed_ref, *refs):
        if kmask is not None:
            km_ref = refs[0]
            refs = refs[1:]
        q_ref, k_ref, v_ref, g_ref, lse_ref, dlt_ref, dq_ref, dq_s = refs
        bhi = pl.program_id(0)
        qi = pl.program_id(1)
        kj = pl.program_id(2)

        @pl.when(kj == 0)
        def _init():
            dq_s[:] = jnp.zeros_like(dq_s)

        run = True
        if causal:
            run = (qi + 1) * block_q > kj * block_k

        @pl.when(run if causal else True)
        def _compute():
            qb = q_ref[0].astype(jnp.float32)
            kb = k_ref[0].astype(jnp.float32)
            vb = v_ref[0].astype(jnp.float32)
            gb = g_ref[0].astype(jnp.float32)
            s = jax.lax.dot_general(
                qb, kb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if kmask is not None:
                s = s + km_ref[0]
            qpos = qi * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, 1), 0)
            kpos = kj * block_k + lax.broadcasted_iota(
                jnp.int32, (1, block_k), 1)
            if causal:
                s = jnp.where(qpos >= kpos, s, _NEG_INF)
            p = jnp.exp(s - lse_ref[0][:, :1])          # (bq, bk)
            p = jnp.where(s <= _NEG_INF * 0.5, 0.0, p)
            dp = jax.lax.dot_general(
                gb, vb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            if dropout > 0.0:
                keep = _keep(seed_ref[0, 0], bhi, qpos, kpos, dropout)
                dp = jnp.where(keep, dp, 0.0) * inv_keep
            ds = p * (dp - dlt_ref[0][:, :1])
            dq_s[:] = dq_s[:] + scale * jax.lax.dot_general(
                ds, kb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        @pl.when(kj == nk - 1)
        def _finalize():
            dq_ref[0] = dq_s[:].astype(dq_ref.dtype)

    grid = (B * H, nq, nk)
    in_specs = [pl.BlockSpec((1, 1), lambda b, i, j: (0, 0),
                             memory_space=pltpu.SMEM)]
    args = [jnp.full((1, 1), 0 if seed is None else seed, jnp.uint32)]
    if kmask is not None:
        Nb = kmask.shape[0]
        km_idx = (lambda b, i, j: (0, 0, j)) if Nb == 1 else \
            (lambda b, i, j: (b // H, 0, j))
        in_specs.append(pl.BlockSpec((1, 1, block_k), km_idx,
                                     memory_space=pltpu.VMEM))
        args.append(kmask)
    in_specs += [
        pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_q, _LANES), lambda b, i, j: (b, i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_q, _LANES), lambda b, i, j: (b, i, 0),
                     memory_space=pltpu.VMEM),
    ]
    args += [q.reshape(B * H, L, D), k.reshape(B * H, Lk, D),
             v.reshape(B * H, Lk, D), g.reshape(B * H, L, D),
             lse_rep, dlt_rep]
    dq = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((B * H, L, D), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
    )(*args)
    return dq.reshape(B, H, L, D)[..., :D0]


def _pallas_bwd_dkv(q, k, v, g, lse_rep, dlt_rep, scale, causal, kmask=None,
                    seed=None, dropout=0.0, need_dbias=False,
                    block_q=_BLOCK, block_k=None):
    """dk/dv kernel: grid (BH, nk, nq), q innermost.  Computation stays in
    q-row orientation ((block_q, block_k) scores); dk/dv fall out of
    contractions over the q dim, so no in-kernel transposes are needed.
    Optionally also emits the q-and-lane-summed dbias for the k-mask
    layout as (BH, 1, Lk)."""
    if block_k is None:
        block_k = _block_q_for(k.shape[2])
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, L, D0 = q.shape
    Lk = k.shape[2]
    D = max(128, -(-D0 // 128) * 128)
    q, k, v, g = (_pad_heads(x, D) for x in (q, k, v, g))
    nq, nk = L // block_q, Lk // block_k
    inv_keep = 1.0 / (1.0 - dropout) if dropout > 0.0 else 1.0

    def kernel(seed_ref, *refs):
        if kmask is not None:
            km_ref = refs[0]
            refs = refs[1:]
        (q_ref, k_ref, v_ref, g_ref, lse_ref, dlt_ref) = refs[:6]
        refs = refs[6:]
        if need_dbias:
            dk_ref, dv_ref, db_ref, dk_s, dv_s, db_s = refs
        else:
            dk_ref, dv_ref, dk_s, dv_s = refs
        bhi = pl.program_id(0)
        kj = pl.program_id(1)
        qi = pl.program_id(2)

        @pl.when(qi == 0)
        def _init():
            dk_s[:] = jnp.zeros_like(dk_s)
            dv_s[:] = jnp.zeros_like(dv_s)
            if need_dbias:
                db_s[:] = jnp.zeros_like(db_s)

        run = True
        if causal:
            run = (qi + 1) * block_q > kj * block_k

        @pl.when(run if causal else True)
        def _compute():
            qb = q_ref[0].astype(jnp.float32)
            kb = k_ref[0].astype(jnp.float32)
            vb = v_ref[0].astype(jnp.float32)
            gb = g_ref[0].astype(jnp.float32)
            s = jax.lax.dot_general(
                qb, kb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if kmask is not None:
                s = s + km_ref[0]
            qpos = qi * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, 1), 0)
            kpos = kj * block_k + lax.broadcasted_iota(
                jnp.int32, (1, block_k), 1)
            if causal:
                s = jnp.where(qpos >= kpos, s, _NEG_INF)
            p = jnp.exp(s - lse_ref[0][:, :1])          # (bq, bk)
            p = jnp.where(s <= _NEG_INF * 0.5, 0.0, p)
            dp = jax.lax.dot_general(
                gb, vb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            p_drop = p
            if dropout > 0.0:
                keep = _keep(seed_ref[0, 0], bhi, qpos, kpos, dropout)
                dp = jnp.where(keep, dp, 0.0) * inv_keep
                p_drop = jnp.where(keep, p, 0.0) * inv_keep
            ds = p * (dp - dlt_ref[0][:, :1])
            # contract over the q dim — outputs land k-major, no transpose
            dv_s[:] = dv_s[:] + jax.lax.dot_general(
                p_drop, gb, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dk_s[:] = dk_s[:] + scale * jax.lax.dot_general(
                ds, qb, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            if need_dbias:
                db_s[:] = db_s[:] + jnp.broadcast_to(
                    jnp.sum(ds, axis=0, keepdims=True), db_s.shape)

        @pl.when(qi == nq - 1)
        def _finalize():
            dk_ref[0] = dk_s[:].astype(dk_ref.dtype)
            dv_ref[0] = dv_s[:].astype(dv_ref.dtype)
            if need_dbias:
                db_ref[0] = db_s[:1]

    grid = (B * H, nk, nq)
    in_specs = [pl.BlockSpec((1, 1), lambda b, j, i: (0, 0),
                             memory_space=pltpu.SMEM)]
    args = [jnp.full((1, 1), 0 if seed is None else seed, jnp.uint32)]
    if kmask is not None:
        Nb = kmask.shape[0]
        km_idx = (lambda b, j, i: (0, 0, j)) if Nb == 1 else \
            (lambda b, j, i: (b // H, 0, j))
        in_specs.append(pl.BlockSpec((1, 1, block_k), km_idx,
                                     memory_space=pltpu.VMEM))
        args.append(kmask)
    in_specs += [
        pl.BlockSpec((1, block_q, D), lambda b, j, i: (b, i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_q, D), lambda b, j, i: (b, i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_q, _LANES), lambda b, j, i: (b, i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_q, _LANES), lambda b, j, i: (b, i, 0),
                     memory_space=pltpu.VMEM),
    ]
    args += [q.reshape(B * H, L, D), k.reshape(B * H, Lk, D),
             v.reshape(B * H, Lk, D), g.reshape(B * H, L, D),
             lse_rep, dlt_rep]
    out_specs = [
        pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0),
                     memory_space=pltpu.VMEM),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((B * H, Lk, D), jnp.float32),
        jax.ShapeDtypeStruct((B * H, Lk, D), jnp.float32),
    ]
    scratch = [pltpu.VMEM((block_k, D), jnp.float32),
               pltpu.VMEM((block_k, D), jnp.float32)]
    if need_dbias:
        out_specs.append(
            pl.BlockSpec((1, 1, block_k), lambda b, j, i: (b, 0, j),
                         memory_space=pltpu.VMEM))
        out_shape.append(
            jax.ShapeDtypeStruct((B * H, 1, Lk), jnp.float32))
        scratch.append(pltpu.VMEM((8, block_k), jnp.float32))
    res = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
    )(*args)
    dk = res[0].reshape(B, H, Lk, D)[..., :D0]
    dv = res[1].reshape(B, H, Lk, D)[..., :D0]
    dbias = res[2].reshape(B, H, Lk) if need_dbias else None
    return dk, dv, dbias



# --------------------------------------------------------------------------- #
# custom VJP: blockwise recompute backward (flash-attention-2 scheme)
# --------------------------------------------------------------------------- #

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _flash(q, k, v, bias, seed, scale, causal, dropout=0.0, impl="auto"):
    out, _ = _flash_fwd_impl(q, k, v, bias, seed, scale, causal, dropout,
                             impl)
    return out


def _flash_fwd_impl(q, k, v, bias, seed, scale, causal, dropout,
                    impl="auto"):
    L = q.shape[2]
    if impl != "xla" and _pallas_eligible(q, k, bias):
        kmask = _kmask_arrays(bias, q.shape[0]) if bias is not None \
            else None
        return _pallas_fwd(q, k, v, scale, causal, kmask=kmask, seed=seed,
                           dropout=dropout)
    return _blockwise_attn(q, k, v, bias, seed, scale, causal, dropout,
                           q_block=min(128, max(16, L)))


def _flash_fwd(q, k, v, bias, seed, scale, causal, dropout=0.0,
               impl="auto"):
    out, lse = _flash_fwd_impl(q, k, v, bias, seed, scale, causal, dropout,
                               impl)
    return out, (q, k, v, bias, seed, out, lse)


def _flash_bwd(scale, causal, dropout, impl, res, g):
    q, k, v, bias, seed, out, lse = res
    B, H, Lq, D = q.shape
    Lk = k.shape[2]
    g32, o32 = g.astype(jnp.float32), out.astype(jnp.float32)
    # delta_i = sum_d o_i * do_i  (row-wise), standard flash backward
    delta = jnp.sum(o32 * g32, axis=-1)                 # (B,H,Lq)

    if impl != "xla" and _pallas_eligible(q, k, bias):
        kmask = _kmask_arrays(bias, B) if bias is not None else None
        lse_rep = _rep(lse.reshape(B * H, Lq))
        dlt_rep = _rep(delta.reshape(B * H, Lq))
        dq = _pallas_bwd_dq(q, k, v, g, lse_rep, dlt_rep, scale, causal,
                            kmask=kmask, seed=seed, dropout=dropout)
        dk, dv, dbias_bh = _pallas_bwd_dkv(
            q, k, v, g, lse_rep, dlt_rep, scale, causal, kmask=kmask,
            seed=seed, dropout=dropout, need_dbias=bias is not None)
        if bias is None:
            dbias = None
        else:
            db = dbias_bh.sum(axis=1)                   # (B, Lk): sum heads
            if bias.shape[0] == 1:
                db = db.sum(axis=0, keepdims=True)
            dbias = db.reshape(bias.shape).astype(bias.dtype)
        return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
                dbias, None)

    q32, k32, v32 = (x.astype(jnp.float32) for x in (q, k, v))
    block = min(512, Lk)
    nkb = -(-Lk // block)
    padk = nkb * block - Lk
    if padk:
        k32 = jnp.pad(k32, ((0, 0), (0, 0), (0, padk), (0, 0)))
        v32 = jnp.pad(v32, ((0, 0), (0, 0), (0, padk), (0, 0)))
    qpos = lax.broadcasted_iota(jnp.int32, (Lq, 1), 0)
    bh = (lax.broadcasted_iota(jnp.int32, (B, H), 0) * H +
          lax.broadcasted_iota(jnp.int32, (B, H), 1))[..., None, None]

    bias32 = None
    if bias is not None:
        bias32 = jnp.broadcast_to(
            bias.astype(jnp.float32),
            (bias.shape[0], bias.shape[1], Lq, Lk))
        if padk:
            bias32 = jnp.pad(bias32, ((0, 0), (0, 0), (0, 0), (0, padk)))

    def body(carry, j):
        dq_acc = carry
        ks = lax.dynamic_slice_in_dim(k32, j * block, block, axis=2)
        vs = lax.dynamic_slice_in_dim(v32, j * block, block, axis=2)
        s = jnp.einsum("bhqd,bhkd->bhqk", q32, ks) * scale
        if bias32 is not None:
            s = s + lax.dynamic_slice_in_dim(bias32, j * block, block,
                                             axis=3)
        kpos = j * block + lax.broadcasted_iota(jnp.int32, (1, block), 1)
        valid = kpos < Lk
        if causal:
            valid = jnp.logical_and(valid, qpos >= kpos)
        s = jnp.where(valid, s, _NEG_INF)
        p = jnp.exp(s - lse[..., None])                 # (B,H,Lq,block)
        p = jnp.where(s <= _NEG_INF * 0.5, 0.0, p)
        dp = jnp.einsum("bhqd,bhkd->bhqk", g32, vs)
        p_drop = p
        if dropout > 0.0:
            keep = _keep(seed, bh, qpos[None, None], kpos[None, None],
                         dropout)
            dp = jnp.where(keep, dp, 0.0) / (1.0 - dropout)
            p_drop = jnp.where(keep, p, 0.0) / (1.0 - dropout)
        dv = jnp.einsum("bhqk,bhqd->bhkd", p_drop, g32)
        ds = p * (dp - delta[..., None]) * scale
        dq_acc = dq_acc + jnp.einsum("bhqk,bhkd->bhqd", ds, ks)
        dk = jnp.einsum("bhqk,bhqd->bhkd", ds, q32)
        if bias is None:
            dbias_blk = jnp.zeros((), jnp.float32)
        else:
            # d(bias) = ds / scale, summed over dims bias broadcasts on
            db = ds / scale
            for ax in range(3):
                if bias.shape[ax] == 1:
                    db = jnp.sum(db, axis=ax, keepdims=True)
            if bias.shape[3] == 1:
                db = jnp.sum(db, axis=3, keepdims=True)
            dbias_blk = db
        return dq_acc, (dk, dv, dbias_blk)

    dq0 = jnp.zeros_like(q32)
    dq, (dks, dvs, dbs) = lax.scan(body, dq0, jnp.arange(nkb))
    D_ = q.shape[3]
    dk = jnp.moveaxis(dks, 0, 2).reshape(B, H, nkb * block, D_)[:, :, :Lk]
    dv = jnp.moveaxis(dvs, 0, 2).reshape(B, H, nkb * block, D_)[:, :, :Lk]
    if bias is None:
        dbias = None
    elif bias.shape[3] == 1:
        dbias = jnp.sum(dbs, axis=0).astype(bias.dtype)
    else:
        # stacked k-blocks → (b0, b1, b2, nkb*block) → trim pad
        dbias = jnp.moveaxis(dbs, 0, 3)
        dbias = dbias.reshape(*dbias.shape[:3], nkb * block)[..., :Lk]
        dbias = dbias.astype(bias.dtype)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            dbias, None)


_flash.defvjp(_flash_fwd, _flash_bwd)


# below this many score elements per head, materializing the full (Lq, Lk)
# attention matrix is cheap and XLA's fused softmax beats the blockwise
# kernel's scan overhead (measured on v5e: 12 layers of L=128 attention run
# ~25% faster unblocked); the flash path takes over where O(L^2) memory
# actually matters
_PLAIN_ATTN_MAX_SCORES = 512 * 512

# --------------------------------------------------------------------------- #
# measured dispatch (VERDICT r2 item 4: "chosen path == fastest measured
# path").  Constants are the crossover sequence lengths from
# ``benchmark/attention_bench.py`` on v5e (causal, B4 H8 D64, bf16).
# Entries are (max_seq, impl); the first row whose bound covers
# max(Lq, Lk) wins.  "plain" materializes O(L²)
# scores (fused-softmax), "xla" is the blockwise lax.scan path, "pallas"
# the Pallas kernels (fwd + bwd).
# --------------------------------------------------------------------------- #
_PATH_TABLE = {
    # measured 2026-07-30 on v5e (builders' round-3 sweep):
    #   fwd:   512 plain 0.80ms | 1k-4k xla (1.17/2.02/5.92ms, pallas
    #          1.58/3.43/10.63) | 8k pallas 38.8ms (xla 39.0)
    #   train: 512 plain 0.79ms | 1k xla 1.74ms (plain 2.12, pallas 2.27)
    #          | 2k+ pallas 6.41/22.1/78.2ms (xla 6.88/25.1/122.5)
    # (sequences <= 512 already took the plain path via
    # _PLAIN_ATTN_MAX_SCORES before the table is consulted)
    "fwd": ((4096, "xla"), (None, "pallas")),
    "train": ((1024, "xla"), (None, "pallas")),
}


def _choose_path(Lq, Lk, bias, training):
    """Pick the implementation per the measured table.  Dense biases
    (anything that is not a full-width key-padding mask) never run the
    Pallas kernels, so their long-seq rows degrade to the XLA blockwise
    path."""
    L = max(Lq, Lk)
    if Lq * Lk <= _PLAIN_ATTN_MAX_SCORES:
        return "plain"
    # pallas needs the kmask's key dim to be exactly Lk — a broadcast
    # (..., 1) bias cannot be padded into a valid kernel mask
    pallas_bias_ok = bias is None or (_is_kmask(bias) and
                                      bias.shape[3] == Lk)
    for bound, impl in _PATH_TABLE["train" if training else "fwd"]:
        if bound is None or L <= bound:
            if impl == "pallas" and (not pallas_bias_ok or
                                     not _use_pallas()):
                return "xla"
            return impl
    return "xla"


def _pad_to_block(q, k, v, bias):
    """Pad seq dims to the 128 multiple the Pallas kernels need and merge
    the padding into a key-mask bias, so real tokenized batches (e.g.
    seq 1000) still hit the kernel (VERDICT r2 item 4).  Returns
    (q, k, v, bias, orig_Lq)."""
    Lq, Lk = q.shape[2], k.shape[2]
    pq = (-Lq) % _BLOCK
    pk = (-Lk) % _BLOCK
    if not pq and not pk:
        return q, k, v, bias, Lq
    if pq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))
    if pk or bias is not None:
        if bias is None:
            bias = jnp.zeros((1, 1, 1, Lk), q.dtype)
        bias = jnp.pad(bias, ((0, 0), (0, 0), (0, 0), (0, pk)),
                       constant_values=_NEG_INF)
    return q, k, v, bias, Lq


def _plain_attn(q, k, v, bias, scale, causal, dropout=0.0, seed=None):
    B, H = q.shape[0], q.shape[1]
    # bf16 inputs stay bf16 into the MXU; accumulation is f32 via
    # preferred_element_type (an f32 upcast first would halve MXU rate)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    Lq, Lk = q.shape[2], k.shape[2]
    if causal:
        qpos = lax.broadcasted_iota(jnp.int32, (Lq, Lk), 0)
        kpos = lax.broadcasted_iota(jnp.int32, (Lq, Lk), 1)
        s = jnp.where(qpos >= kpos, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    if dropout > 0.0:
        bh = (lax.broadcasted_iota(jnp.int32, (B, H), 0) * H +
              lax.broadcasted_iota(jnp.int32, (B, H), 1))[..., None, None]
        qpos = lax.broadcasted_iota(jnp.int32, (1, 1, Lq, 1), 2)
        kpos = lax.broadcasted_iota(jnp.int32, (1, 1, 1, Lk), 3)
        keep = _keep(seed, bh, qpos, kpos, dropout)
        p = jnp.where(keep, p, 0.0) / (1.0 - dropout)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)


@op("flash_attention")
@jax.named_scope("mx.attn")
def flash_attention(q, k, v, bias=None, *, scale: Optional[float] = None,
                    causal: bool = False, dropout: float = 0.0,
                    training: Optional[bool] = None):
    """Memory-efficient attention over (B, H, L, D) tensors.  ``bias`` is an
    optional additive score bias broadcastable to (B, H, Lq, Lk) — use
    large negative values as a padding mask.  Gradients propagate through
    ``bias`` on every path (summed over broadcast dims).

    ``dropout`` applies attention-probability dropout (reference: the
    Dropout inside ``MultiheadAttention``) when training — in training
    mode (``autograd.is_training()``) unless ``training`` overrides.

    The implementation is chosen from the MEASURED dispatch table
    ``_PATH_TABLE`` (benchmark/attention_bench.py sweep): short sequences
    take the unblocked fused-softmax path, the mid range the XLA blockwise
    kernel, long sequences the Pallas kernels (fwd AND bwd).  ``training``
    selects the train-tuned (fwd+bwd) vs inference-tuned column.  On the
    Pallas path 128-unaligned lengths are padded inside the op (the pad
    keys are masked via the key-mask bias channel); general dense biases
    (not a ``(B|1,1,1,Lk)`` key mask) always use the XLA paths."""
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    if training is None:
        from .. import autograd
        training = autograd.is_training()
    rate = float(dropout) if training else 0.0
    if rate > 0.0:
        from .. import random as mxrandom
        seed = jax.random.bits(mxrandom.next_key(), dtype=jnp.uint32)
    else:
        seed = jnp.uint32(0)
    path = _choose_path(q.shape[2], k.shape[2], bias, bool(training))
    if path == "plain":
        return _plain_attn(q, k, v, bias, float(scale), bool(causal),
                           dropout=rate, seed=seed)
    if path == "pallas":
        q2, k2, v2, bias2, Lq = _pad_to_block(q, k, v, bias)
        out = _flash(q2, k2, v2, bias2, seed, float(scale), bool(causal),
                     rate, "pallas")
        return out[:, :, :Lq] if out.shape[2] != Lq else out
    return _flash(q, k, v, bias, seed, float(scale), bool(causal), rate,
                  "xla")


# ---------------------------------------------------------------------------
# rotary position embeddings (RoPE) — Llama-family models
# ---------------------------------------------------------------------------

@op("rope")
def rope(x, *, base=10000.0, position_offset=0):
    """Apply rotary position embeddings to (B, H, L, D) q/k tensors
    (TPU-native addition, no reference analog — the positional mechanism
    of the Llama family, BASELINE config 5).

    Rotates consecutive (even, odd) feature pairs by position-dependent
    angles: theta_i = pos / base^(2i/D).  ``position_offset`` supports
    KV-cache decode: a scalar offsets every row uniformly (queries at
    absolute positions offset..offset+L); a (B,) vector gives each
    batch row its own absolute depth (the slot-pool serving step, where
    every row is an independent sequence at its own position)."""
    B, H, L, D = x.shape
    half = D // 2
    inv_freq = 1.0 / (base ** (
        jnp.arange(0, half, dtype=jnp.float32) * 2.0 / D))
    off = jnp.asarray(position_offset, dtype=jnp.float32)
    pos = jnp.arange(L, dtype=jnp.float32) + off[..., None]  # (L,)|(B,L)
    angles = pos[..., None] * inv_freq              # (L,half)|(B,L,half)
    cos = jnp.expand_dims(jnp.cos(angles), -3)      # (1,L,h)|(B,1,L,h)
    sin = jnp.expand_dims(jnp.sin(angles), -3)
    x32 = x.astype(jnp.float32)
    x1 = x32[..., 0::2]
    x2 = x32[..., 1::2]
    r1 = x1 * cos - x2 * sin
    r2 = x1 * sin + x2 * cos
    out = jnp.stack([r1, r2], axis=-1).reshape(B, H, L, D)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# ring attention: sequence parallelism over a mesh axis
# ---------------------------------------------------------------------------

def _ring_attn_local(q, k, v, scale, causal, axis, n_shards):
    """Runs inside shard_map: q/k/v are the LOCAL sequence shards
    (B, H, L/n, D).  K/V rotate around the ring; each step folds one
    remote block into the online softmax."""
    my = lax.axis_index(axis)
    Lloc = q.shape[2]
    q32 = q.astype(jnp.float32)
    qpos = (my * Lloc + lax.broadcasted_iota(
        jnp.int32, (Lloc, 1), 0))[None, None]       # (1,1,Lloc,1)

    def step(carry, i):
        kcur, vcur, m, l, acc = carry
        src = (my - i) % n_shards                   # whose shard we hold
        s = jnp.einsum("bhqd,bhkd->bhqk", q32,
                       kcur.astype(jnp.float32)) * scale
        if causal:
            kpos = (src * Lloc + lax.broadcasted_iota(
                jnp.int32, (1, Lloc), 1))[None, None]
            s = jnp.where(qpos >= kpos, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jnp.einsum(
            "bhqk,bhkd->bhqd", p, vcur.astype(jnp.float32))
        perm = [(j, (j + 1) % n_shards) for j in range(n_shards)]
        k_next = lax.ppermute(kcur, axis, perm)
        v_next = lax.ppermute(vcur, axis, perm)
        return (k_next, v_next, m_new, l_new, acc_new), None

    B, H, _, D = q.shape
    m0 = jnp.full((B, H, Lloc, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Lloc, 1), jnp.float32)
    a0 = jnp.zeros((B, H, Lloc, D), jnp.float32)
    (kf, vf, m, l, acc), _ = lax.scan(
        step, (k, v, m0, l0, a0), jnp.arange(n_shards))
    out = acc / jnp.maximum(l, 1e-30)
    return out.astype(q.dtype)


@op("ring_attention", differentiable=True)
def ring_attention(q, k, v, *, scale: Optional[float] = None,
                   causal: bool = False, axis: str = "sp",
                   mesh=None):
    """Sequence-parallel attention: inputs sharded over ``axis`` on the seq
    dim; communication is ``ppermute`` around the ring (ICI-neighbor
    traffic only, the canonical long-context pattern)."""
    from ..parallel.mesh import default_mesh, local_mesh_axes, P
    from jax.sharding import NamedSharding

    mesh = mesh or default_mesh()
    n = local_mesh_axes(mesh)[axis]
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    seq_sharding = NamedSharding(mesh, P(None, None, axis, None))
    q = jax.device_put(q, seq_sharding)
    k = jax.device_put(k, seq_sharding)
    v = jax.device_put(v, seq_sharding)
    fn = jax.shard_map(
        functools.partial(_ring_attn_local, scale=float(scale),
                          causal=bool(causal), axis=axis, n_shards=n),
        mesh=mesh,
        in_specs=(P(None, None, axis, None),) * 3,
        out_specs=P(None, None, axis, None),
        check_vma=False)
    return fn(q, k, v)
