"""Attention kernels: flash attention (Pallas TPU) + ring attention (SP).

Reference counterpart: the BERT-era fused attention matmuls
(``_contrib_interleaved_matmul_selfatt_qk/valatt``, SURVEY.md §3.1
"Operator corpus" contrib family) which materialize the O(L²) score matrix.
The TPU-native answer (SURVEY.md §5.7 — NEW capability, not parity) is:

- ``flash_attention``: blockwise online-softmax attention, O(L) memory.
  On TPU both the forward AND backward run as Pallas kernels (blocks of
  up to 1,024 rows at the head's own width); everywhere else a
  ``lax.scan`` blockwise implementation that XLA fuses.  On every path the
  matrix products take their operands in the dtype they arrive in
  (bfloat16 q, k, v, do; the probabilities and ``ds`` cast to match) and
  accumulate in float32; scores, softmax statistics, ``lse`` and ``delta``
  are float32.  Padding masks (additive bias of layout
  ``(B|1, 1, 1, Lk)``) and attention dropout run INSIDE the kernels;
  general dense biases (e.g. ALiBi tables) take the XLA blockwise path.
  Backward recomputes blockwise from the saved log-sum-exp (the
  flash-attention-2 scheme) — no O(L²) residuals on any path.
- ``flash_attention_qkv``: the same attention straight off a model's fused
  (B, L, 3U) projection, giving (B, L, U).  Where ``flash_attention``
  would take the Pallas kernels and the heads fill whole lane tiles, the
  same three kernel bodies address q, k, v, the output and the gradients
  as 128-lane blocks of that array where it lies (two 64-wide heads a
  block), so no transpose, slice or concatenate stands between the
  projections and the kernels; everywhere else it splits the heads and
  calls ``flash_attention``.
- ``ring_attention``: sequence-parallel attention over a mesh axis; K/V
  shards rotate around the ICI ring via ``ppermute`` while each device
  accumulates online-softmax partials for its local Q shard.  This is the
  scale-out long-context path (SURVEY.md §3.3 "SP/CP" row).

Dropout determinism: the keep-mask is a pure position hash of
``(seed, batch·head, q_pos, k_pos)`` computed identically by the Pallas
kernels and the XLA paths, so a forward on one path and a backward
recompute on another still see the same mask.

Shapes follow (batch, heads, seq, head_dim) but for the packed projection.
"""
from __future__ import annotations

import functools
import inspect
import os
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from .registry import get_op, op

__all__ = ["flash_attention", "flash_attention_qkv", "ring_attention",
           "rope"]

_NEG_INF = -1e30
_BLOCK = 128  # MXU-native q/k tile


def _interpret() -> bool:
    # run the Pallas kernels in interpreter mode (CPU numerics testing)
    # backend hatch read at trace time; the pod launcher exports MXNET_*
    # to every rank, so the read is host-uniform by deployment contract:
    # tracelint: disable=TL007 -- tools/launch.py propagates MXNET_* env to all ranks
    return os.environ.get("MXNET_FLASH_INTERPRET", "") == "1"


def on_tpu(kernel, fallback, *operands):
    """``kernel(*operands)`` where the program lowers for a TPU,
    ``fallback(*operands)`` on every other platform; with
    ``MXNET_FLASH_INTERPRET=1``, ``kernel(*operands, interpret=True)``
    wherever it is (CPU numerics).  How every Pallas kernel with an XLA
    form beside it is chosen: ``kernel`` takes ``interpret=False`` by
    default."""
    if _interpret():
        return kernel(*operands, interpret=True)
    return lax.platform_dependent(*operands, tpu=kernel, default=fallback)


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
        # p goes to the MXU in v's dtype, as q and k did (_plain_attn)
        o = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v,
                       preferred_element_type=jnp.float32) \
            / jnp.maximum(l, 1e-30)
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


# per-row statistics (lse, delta, the key mask's gradient) live in HBM as
# lane-dense (BH, 1, L) rows — L x 4 bytes a head, where a lane-replicated
# (BH, L, 128) copy is 128 times that and outweighs q, k, v and g together.
# A kernel that wants one as a column turns a block of it once per q (or
# k) block; in VMEM the running statistics stay lane-replicated.
_LANES = 128
# what a kernel over the packed projection adds to its name
_PACKED_NAME = "_qkv"


def _col_to_row(x):
    """(n, 128) lane-replicated column -> (1, n) row."""
    return x.T[:1]


def _row_to_col(row):
    """(1, n) row -> (n, 128) lane-replicated column."""
    return jnp.broadcast_to(row, (_LANES, row.shape[1])).T


def _block_for(L, dtype):
    """Rows of a block of the forward and dq kernels, q and k side alike:
    the whole sequence up to 1,024 rows, the largest multiple of 128 that
    divides a longer one.  The cost is a block's, not a row's: on the
    v5e sweep (2026-10-03, B8 H16 D64 bfloat16 causal, ms a kernel at
    L 1,024: q/k blocks 128/128 3.10 fwd 2.65 dq, 512/512 0.80 / 0.66,
    1,024/1,024 0.50 / 0.60; at L 2,048: 512/512 2.78 / 2.34, 1,024/1,024
    1.80 / 2.12) a whole-sequence block that masks its upper triangle beats
    four blocks that skip one of them.  Inputs wider than two bytes stop
    at 512: the dq kernel's float32 blocks of 1,024 x 128 beside its four
    score-sized temporaries do not fit the 16 MiB of VMEM a kernel may
    take (refused by the compile for a described v5e, not swept)."""
    cap = 1024 if jnp.dtype(dtype).itemsize <= 2 else 512
    return max(b for b in range(_BLOCK, cap + 1, _BLOCK) if L % b == 0)


def _block_dkv_for(L, dtype):
    """Rows of a block of the dk/dv kernel, k and q side alike: half of
    ``_block_for`` where that is still whole 128-lane tiles.  Its steps are
    cheaper (no running statistics to carry), so skipping a quarter of the
    scores pays (same sweep, ms at L 768 / 1,024 / 2,048: 0.46 / 0.71 /
    2.40 at half, 0.49 / 0.84 / 2.48 at the full block; at 4,096 the full
    block leads, 4.00 against 4.22.  With the diagonal block cut in two,
    ``_causal_tiles``: 0.60 at half against 0.66 at L 1,024, 2.33 against
    2.21 at 2,048)."""
    b = _block_for(L, dtype)
    return b // 2 if b % 256 == 0 else b


_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_NN = (((1,), (0,)), ((), ()))      # a @ b


def _mxu(a, b, dims):
    """A product on the MXU in its operands' own dtype (bfloat16 stays
    bfloat16: one pass, where a float32 product takes several), float32
    accumulation.  The framework's default precision, ``highest``
    (base.py), is a float32 product's to keep; Mosaic refuses it on
    narrower operands ("Bad lhs type")."""
    return lax.dot_general(
        a, b, dims, preferred_element_type=jnp.float32,
        precision=None if a.dtype == jnp.float32 else lax.Precision.DEFAULT)


def _block_scores(rows, cols, scale, qpos, kpos, causal, add=None):
    """float32 scores of one block, ``rows @ cols.T``: q rows by k columns
    in the forward and dq kernels, k rows by q columns in dk/dv —
    ``qpos`` / ``kpos`` are a column and a row of positions, or a row and
    a column, to match."""
    s = _mxu(rows, cols, _NT) * scale
    if add is not None:
        s = s + add
    if causal:
        s = jnp.where(qpos >= kpos, s, _NEG_INF)
    return s


def _causal_tiles(qi, kj, block_q, block_k, causal, split_along, tile):
    """Run ``tile(q_rows, k_rows, masked)`` — static slices of the
    resident q and k blocks — over what the causal mask leaves of block
    ``(qi, kj)``: nothing above the diagonal, the whole block unmasked
    below it, one masked tile on it.  With ``split_along`` a square block
    on the diagonal is cut in two along the side whose rows the kernel
    accumulates over ("q" in dq, "k" in dk/dv) and its upper-right quarter
    skipped: on the v5e sweep (2026-10-03, B8 H16 D64 bfloat16, L 1,024)
    dq went 0.594 -> 0.464 ms and dk/dv 0.687 -> 0.598; the forward, whose
    two halves each carry the running statistics, went 0.496 -> 0.561 and
    passes ``None``."""
    from jax.experimental import pallas as pl

    whole = slice(0, block_q), slice(0, block_k)
    if not causal:
        tile(*whole, False)
    elif split_along and block_q == block_k and block_q % (2 * _BLOCK) == 0:
        lo, hi = slice(0, block_q // 2), slice(block_q // 2, block_q)
        halves = ((lo, lo), (hi, whole[1])) if split_along == "q" else \
            ((whole[0], lo), (hi, hi))
        pl.when(kj < qi)(lambda: tile(*whole, False))

        @pl.when(kj == qi)
        def _on_the_diagonal():
            for q_rows, k_rows in halves:
                tile(q_rows, k_rows, True)
    else:
        pl.when((qi + 1) * block_q > kj * block_k)(
            lambda: tile(*whole, True))


def _streamed_k_block(block_q, block_k, causal):
    """``(i, j) ->`` the k (and v) block that step ``j`` of q block ``i``
    reads: a causal block the mask removes re-names the last one its q
    block reads, so a skipped step fetches nothing."""
    if not causal:
        return lambda i, j: j
    return lambda i, j: jnp.minimum(j, ((i + 1) * block_q - 1) // block_k)


class _HeadBlocks(NamedTuple):
    """How a kernel's grid ``(n0, pairs, row blocks, streamed blocks)``
    finds its heads, in either layout of the operands.

    Apart, ``(B, H, L, D)``: q, k, v (and ``do``, the output, every
    gradient) are ``(B*H, L, D)`` arrays, a block is one head at its own
    width, ``n0 = B*H`` and ``pairs = 1``.

    Packed, the ``(B, L, 3U)`` projection as it lies: ONE array handed in
    once a role, a block is ``width`` = 128 lanes of it holding
    ``per_block`` = two 64-wide heads side by side (or one head's D where
    that is whole lane tiles: ``_packed_heads``) — q at lane block ``p``, k at ``U/width + p``, v at
    ``2U/width + p`` — and ``do``, the output and the gradients are
    ``(B, L, U)`` with a head pair at lane block ``p``; ``n0 = B`` and
    ``pairs = H / per_block``.

    Per-row statistics are ``(B*H, 1, L)`` rows in both, ``per_block`` rows
    a grid step."""
    B: int
    H: int
    D: int
    n0: int
    pairs: int
    per_block: int
    width: int
    k_at: int
    v_at: int

    def spec(self, rows, row_block, at=0):
        """BlockSpec of ``rows`` rows by ``width`` lanes; ``row_block``
        maps the grid's last two indices to the row block, ``at`` is the
        role's first lane block."""
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu
        return pl.BlockSpec(
            (1, rows, self.width),
            lambda n, p, a, b: (n, row_block(a, b), at + p),
            memory_space=pltpu.VMEM)

    def row_spec(self, cols, col_block):
        """BlockSpec of the heads' ``(1, cols)`` statistics rows."""
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu
        return pl.BlockSpec(
            (self.per_block, 1, cols),
            lambda n, p, a, b: (n * self.pairs + p, 0, col_block(a, b)),
            memory_space=pltpu.VMEM)

    def first_head(self):
        """``batch * H + head`` of a grid step's first head: what the
        dropout hash is keyed on, the same in both layouts."""
        from jax.experimental import pallas as pl
        return (pl.program_id(0) * self.pairs + pl.program_id(1)) \
            * self.per_block

    def array(self, L):
        """Shape of ``do``, the output or a gradient of ``L`` rows."""
        if self.n0 == self.B:
            return (self.B, L, self.H * self.D)
        return (self.n0, L, self.D)


def _packed_heads(U, heads):
    """(heads a lane block, its width) of a packed projection of ``heads``
    heads: one head of whole lane tiles, or an even count of 64-wide heads
    in pairs.  None for every other width — no whole lane tiles, or four
    and more heads a block, whose score-sized temporaries (a set a head)
    overran the 16 MiB of VMEM a kernel may take in the compile for a
    described v5e (D 32 at L 2,048, D 16 at 1,024: the forward)."""
    D = U // heads
    if D % _LANES == 0:
        return 1, D
    if 2 * D == _LANES and heads % 2 == 0:
        return 2, _LANES
    return None


def _head_blocks(q, k, v, heads):
    """(the kernel's q, k, v operands, their ``_HeadBlocks``): ``heads`` is
    None for q, k, v apart and the head count where ``q`` is the packed
    projection (``k`` and ``v`` are then None)."""
    if heads is None:
        B, H, L, D = q.shape
        return [x.reshape(B * H, x.shape[2], D) for x in (q, k, v)], \
            _HeadBlocks(B, H, D, B * H, 1, 1, D, 0, 0)
    B, L, U3 = q.shape
    U = U3 // 3
    per_block, width = _packed_heads(U, heads)
    return [q, q, q], _HeadBlocks(
        B, heads, U // heads, B, heads // per_block, per_block, width,
        U // width, 2 * U // width)


# A lane block of the packed layout holds ``per_block`` heads side by side
# and each is an attention of its own.  A 64-deep contraction half-fills
# the v5e's 128 x 128 MXU already, so head ``h`` is taken with lane masks
# and no lane slicing at no extra MXU pass: ``s_h = (q * m_h) @ k.T`` over
# all 128 lanes, and a product that ends in the head's lanes is made 128
# wide and its other lanes dropped.

def _lanes_of(h, hb, shape):
    lane = lax.broadcasted_iota(jnp.int32, shape, 1)
    return (lane >= h * hb.D) & (lane < (h + 1) * hb.D)


def _head_rows(x, h, hb):
    """``x`` with every lane but head ``h``'s zeroed: against it a
    contraction over all the lanes of the other operand is head ``h``'s
    alone."""
    if hb.per_block == 1:
        return x
    return jnp.where(_lanes_of(h, hb, x.shape), x, jnp.zeros_like(x))


def _by_head(parts, hb):
    """One ``(n, width)`` array whose lanes of head ``h`` are
    ``parts[h]``'s: of products made 128 lanes wide, or of lane-replicated
    ``(n, 128)`` statistics (a head apart takes its column as it is
    broadcast)."""
    if hb.per_block == 1:
        return parts[0] if parts[0].shape[1] == hb.width else parts[0][:, :1]
    first, second = parts                   # ``_packed_heads``: pairs only
    return jnp.where(_lanes_of(0, hb, first.shape), first, second)


def _lead_operands(seed, kmask, hb, block_k, k_axis):
    """What every kernel takes first: the dropout seed in SMEM and, where
    there is one, the (Nb, 1, Lk) key mask by k block (``k_axis`` is the
    grid axis that walks k)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    specs = [pl.BlockSpec((1, 1), lambda *g: (0, 0),
                          memory_space=pltpu.SMEM)]
    args = [jnp.full((1, 1), 0 if seed is None else seed, jnp.uint32)]
    if kmask is not None:
        if kmask.shape[0] == 1:
            km_idx = lambda *g: (0, 0, g[k_axis])
        else:
            km_idx = lambda *g: (g[0] // (hb.n0 // hb.B), 0, g[k_axis])
        specs.append(pl.BlockSpec((1, 1, block_k), km_idx,
                                  memory_space=pltpu.VMEM))
        args.append(kmask)
    return specs, args


def _kernel_jit(fn):
    """``jax.jit`` around a kernel's launcher, everything but the arrays
    static: the layers of a model then trace the kernel and lower it to
    Mosaic once, not once a layer — at 24 layers 1.4 s of every process
    start in place of 5.7 s, compile cache warm or not (lowered for a
    described v5e).  ``interpret`` is read here, outside the cache, and is
    part of its key."""
    static = {"scale", "causal", "dropout", "need_dbias", "block_q",
              "block_k", "heads", "interpret"}
    jitted = jax.jit(fn, static_argnames=sorted(
        static & set(inspect.signature(fn).parameters)))

    @functools.wraps(fn)
    def launch(*args, **kwargs):
        return jitted(*args, interpret=_interpret(), **kwargs)
    return launch


@_kernel_jit
def _pallas_fwd(q, k, v, scale, causal, kmask=None, seed=None, dropout=0.0,
                block_q=None, block_k=None, heads=None, interpret=False):
    """Flash forward on TPU.  Grid (batch·heads, 1, q_blocks, k_blocks) —
    packed, (batch, head pairs, q_blocks, k_blocks): ``_HeadBlocks`` — with
    the k axis innermost: VMEM holds one q/k/v block at a time (O(block·D)
    VMEM — long sequences stream from HBM) while running max / sum / output
    accumulators live in VMEM scratch across the k sweep.  The products
    take q, k, v as they arrive and ``p`` cast to match; everything else is
    float32.  A causal block above the diagonal is neither computed nor
    fetched.  ``kmask`` is an optional (Nb, 1, Lk) additive bias (key
    padding mask); ``dropout``/``seed`` apply in-kernel attention dropout
    via the shared position hash.  Returns (out, lse): (B, H, L, D) and
    (B, H, L) for q, k, v apart; with ``heads``, ``q`` the packed
    (B, L, 3U) projection, (B, L, U) and the (B·H, 1, L) rows."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (q3, k3, v3), hb = _head_blocks(q, k, v, heads)
    L, Lk = q3.shape[1], k3.shape[1]
    if block_q is None:
        block_q = _block_for(L, q.dtype)
    if block_k is None:
        block_k = _block_for(Lk, q.dtype)
    nq = L // block_q
    nk = Lk // block_k
    inv_keep = 1.0 / (1.0 - dropout) if dropout > 0.0 else 1.0
    heads_here = range(hb.per_block)

    def kernel(seed_ref, *refs):
        if kmask is not None:
            km_ref = refs[0]
            refs = refs[1:]
        q_ref, k_ref, v_ref, o_ref, lse_ref, m_s, l_s, acc_s = refs
        head0 = hb.first_head()
        qi = pl.program_id(2)
        kj = pl.program_id(3)

        @pl.when(kj == 0)
        def _init():
            m_s[:] = jnp.full_like(m_s, _NEG_INF)
            l_s[:] = jnp.zeros_like(l_s)
            acc_s[:] = jnp.zeros_like(acc_s)

        def tile(qs, ks, masked):
            nq_, nk_ = qs.stop - qs.start, ks.stop - ks.start
            qpos = qi * block_q + qs.start + lax.broadcasted_iota(
                jnp.int32, (nq_, 1), 0)
            kpos = kj * block_k + ks.start + lax.broadcasted_iota(
                jnp.int32, (1, nk_), 1)
            alphas, pvs = [], []
            for h in heads_here:
                s = _block_scores(
                    _head_rows(q_ref[0, qs], h, hb),
                    k_ref[0, ks], scale,
                    qpos, kpos, masked,
                    km_ref[0, :, ks] if kmask is not None else None)
                m_prev = m_s[h, qs]
                m_new = jnp.maximum(
                    m_prev, jnp.broadcast_to(
                        jnp.max(s, axis=-1, keepdims=True), (nq_, _LANES)))
                alpha = jnp.exp(m_prev - m_new)
                p = jnp.exp(s - m_new[:, :1])
                if kmask is not None:
                    # a row whose keys so far are all masked:
                    # exp(-1e30 - (-1e30)) == 1 poison.  (Causal alone
                    # never has one: key 0 is open to every row in the
                    # first block.)
                    p = jnp.where(s <= _NEG_INF * 0.5, 0.0, p)
                m_s[h, qs] = m_new
                l_s[h, qs] = l_s[h, qs] * alpha + jnp.broadcast_to(
                    jnp.sum(p, axis=-1, keepdims=True), (nq_, _LANES))
                if dropout > 0.0:
                    keep = _keep(seed_ref[0, 0], head0 + h, qpos, kpos,
                                 dropout)
                    p = jnp.where(keep, p, 0.0) * inv_keep
                alphas.append(alpha)
                pvs.append(_mxu(p.astype(v_ref.dtype),
                                v_ref[0, ks], _NN))
            acc_s[qs] = acc_s[qs] * _by_head(alphas, hb) + _by_head(pvs, hb)

        _causal_tiles(qi, kj, block_q, block_k, causal, None, tile)

        @pl.when(kj == nk - 1)
        def _finalize():
            l = [jnp.maximum(l_s[h], 1e-30) for h in heads_here]
            o_ref[0] = (acc_s[:] / _by_head(l, hb)).astype(o_ref.dtype)
            for h in heads_here:
                lse_ref[h] = _col_to_row(m_s[h] + jnp.log(l[h]))

    k_block = _streamed_k_block(block_q, block_k, causal)
    q_block = lambda i, j: i
    in_specs, args = _lead_operands(seed, kmask, hb, block_k, 3)
    in_specs += [hb.spec(block_q, q_block),
                 hb.spec(block_k, k_block, hb.k_at),
                 hb.spec(block_k, k_block, hb.v_at)]
    out, lse = pl.pallas_call(
        kernel,
        grid=(hb.n0, hb.pairs, nq, nk),
        in_specs=in_specs,
        out_specs=[hb.spec(block_q, q_block),
                   hb.row_spec(block_q, q_block)],
        out_shape=[
            jax.ShapeDtypeStruct(hb.array(L), q.dtype),
            jax.ShapeDtypeStruct((hb.B * hb.H, 1, L), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((hb.per_block, block_q, _LANES), jnp.float32),
            pltpu.VMEM((hb.per_block, block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, hb.width), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3 + ("arbitrary",)),
        interpret=interpret,
        name="mx_flash_fwd" + _PACKED_NAME * (heads is not None),
    )(*args, q3, k3, v3)
    if heads is not None:
        return out, lse
    return out.reshape(q.shape), lse.reshape(hb.B, hb.H, L)


# --------------------------------------------------------------------------- #
# Pallas TPU backward kernels (flash-attention-2: recompute from lse)
# --------------------------------------------------------------------------- #

@_kernel_jit
def _pallas_bwd_dq(q, k, v, g, lse, delta, scale, causal, kmask=None,
                   seed=None, dropout=0.0, block_q=None, block_k=None,
                   heads=None, out=None, interpret=False):
    """dq kernel: the forward's grid, k innermost; dq accumulates in VMEM.
    ``lse``/``delta`` are the (BH, 1, L) rows, turned to columns once per
    q block.  Operands as in the forward: ``ds`` is cast to k's dtype.

    With ``heads``, ``q`` is the packed projection and ``g`` (B, L, U);
    ``delta`` is None and the forward's ``out`` (B, L, U) comes instead:
    the kernel makes ``delta = rowsum(do * o)`` of its q block itself, a
    head at a time (``do`` is resident anyway, and over (B, L, H·D) XLA
    re-lays the float32 product out before it can sum 64 lanes of 128).
    Returns (the (B, L, 3U) gradient of the projection with dq's lane
    blocks written and the rest left to ``_pallas_bwd_dkv``, the delta
    rows)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (q3, k3, v3), hb = _head_blocks(q, k, v, heads)
    L, Lk = q3.shape[1], k3.shape[1]
    if block_q is None:
        block_q = _block_for(L, q.dtype)
    if block_k is None:
        block_k = _block_for(Lk, q.dtype)
    nq, nk = L // block_q, Lk // block_k
    inv_keep = 1.0 / (1.0 - dropout) if dropout > 0.0 else 1.0
    heads_here = range(hb.per_block)
    packed = heads is not None

    def kernel(seed_ref, *refs):
        if kmask is not None:
            km_ref = refs[0]
            refs = refs[1:]
        # the sixth operand: the delta rows or, packed, the forward's
        # output, with the delta rows made here a second output
        q_ref, k_ref, v_ref, g_ref, lse_ref, dlt_ref, dq_ref, *refs = refs
        if packed:
            o_ref = dlt_ref
            dlt_out_ref, *refs = refs
        dq_s, lse_s, dlt_s = refs
        head0 = hb.first_head()
        qi = pl.program_id(2)
        kj = pl.program_id(3)

        @pl.when(kj == 0)
        def _init():
            dq_s[:] = jnp.zeros_like(dq_s)
            if packed:
                og = o_ref[0].astype(jnp.float32) * \
                    g_ref[0].astype(jnp.float32)
            for h in heads_here:
                lse_s[h] = _row_to_col(lse_ref[h])
                if not packed:
                    dlt_s[h] = _row_to_col(dlt_ref[h])
                    continue
                dlt_s[h] = jnp.broadcast_to(
                    jnp.sum(_head_rows(og, h, hb), axis=1, keepdims=True),
                    (block_q, _LANES))
                dlt_out_ref[h] = _col_to_row(dlt_s[h])

        def tile(qs, ks, masked):
            qpos = qi * block_q + qs.start + lax.broadcasted_iota(
                jnp.int32, (qs.stop - qs.start, 1), 0)
            kpos = kj * block_k + ks.start + lax.broadcasted_iota(
                jnp.int32, (1, ks.stop - ks.start), 1)
            dqs = []
            for h in heads_here:
                s = _block_scores(
                    _head_rows(q_ref[0, qs], h, hb),
                    k_ref[0, ks], scale,
                    qpos, kpos, masked,
                    km_ref[0, :, ks] if kmask is not None else None)
                p = jnp.exp(s - lse_s[h, qs, :1])       # (q rows, k rows)
                if kmask is not None:
                    p = jnp.where(s <= _NEG_INF * 0.5, 0.0, p)
                dp = _mxu(_head_rows(g_ref[0, qs], h, hb),
                          v_ref[0, ks], _NT)
                if dropout > 0.0:
                    keep = _keep(seed_ref[0, 0], head0 + h, qpos, kpos,
                                 dropout)
                    dp = jnp.where(keep, dp, 0.0) * inv_keep
                ds = p * (dp - dlt_s[h, qs, :1])
                dqs.append(_mxu(ds.astype(k_ref.dtype),
                                k_ref[0, ks], _NN))
            dq_s[qs] = dq_s[qs] + _by_head(dqs, hb)

        _causal_tiles(qi, kj, block_q, block_k, causal, "q", tile)

        @pl.when(kj == nk - 1)
        def _finalize():
            dq_ref[0] = (dq_s[:] * scale).astype(dq_ref.dtype)

    k_block = _streamed_k_block(block_q, block_k, causal)
    q_block = lambda i, j: i
    in_specs, args = _lead_operands(seed, kmask, hb, block_k, 3)
    in_specs += [hb.spec(block_q, q_block),
                 hb.spec(block_k, k_block, hb.k_at),
                 hb.spec(block_k, k_block, hb.v_at),
                 hb.spec(block_q, q_block),
                 hb.row_spec(block_q, q_block),
                 hb.row_spec(block_q, q_block) if not packed
                 else hb.spec(block_q, q_block)]
    out_specs = [hb.spec(block_q, q_block)]
    out_shape = [jax.ShapeDtypeStruct(
        q.shape if packed else hb.array(L), q.dtype)]
    if packed:
        out_specs.append(hb.row_spec(block_q, q_block))
        out_shape.append(jax.ShapeDtypeStruct(lse.shape, jnp.float32))
    dq, *made = pl.pallas_call(
        kernel,
        grid=(hb.n0, hb.pairs, nq, nk),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, hb.width), jnp.float32),
            pltpu.VMEM((hb.per_block, block_q, _LANES), jnp.float32),
            pltpu.VMEM((hb.per_block, block_q, _LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3 + ("arbitrary",)),
        interpret=interpret,
        name="mx_flash_bwd_dq" + _PACKED_NAME * packed,
    )(*args, q3, k3, v3, g.reshape(hb.array(L)), lse,
      out if packed else delta)
    return (dq, made[0]) if packed else dq.reshape(q.shape)


@_kernel_jit
def _pallas_bwd_dkv(q, k, v, g, lse, delta, scale, causal, kmask=None,
                    seed=None, dropout=0.0, need_dbias=False,
                    block_q=None, block_k=None, heads=None, into=None,
                    interpret=False):
    """dk/dv kernel: grid (BH, 1, nk, nq) — packed, (B, head pairs, nk,
    nq) — q innermost.  Scores are computed k-row by q-column ((block_k,
    block_q)), so the (BH, 1, L) ``lse`` / ``delta`` rows broadcast as they
    are and dk/dv are plain products ``p.T @ g`` / ``ds.T @ q`` with
    nothing transposed.  A causal q block before the diagonal is neither
    computed nor fetched.  Optionally also emits the q-summed dbias for
    the k-mask layout as (BH, 1, Lk).

    With ``heads``, ``q`` is the packed projection, ``g`` (B, L, U) and
    ``into`` the (B, L, 3U) gradient that ``_pallas_bwd_dq`` began: it is
    this call's output too (aliased), and the kernel copies each finished
    dk and dv block into its lane block of it itself — a ``pallas_call``
    writes one block an output a grid step, and here two go to one array.
    The copies of a grid step are waited for when the next ones are due,
    so the grid runs in order (every axis ``arbitrary``: the v5e has one
    core to run it on).  Returns (that gradient, the dbias rows)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (q3, k3, v3), hb = _head_blocks(q, k, v, heads)
    L, Lk = q3.shape[1], k3.shape[1]
    if block_k is None:
        block_k = _block_dkv_for(Lk, q.dtype)
    if block_q is None:
        block_q = _block_dkv_for(L, q.dtype)
    nq, nk = L // block_q, Lk // block_k
    inv_keep = 1.0 / (1.0 - dropout) if dropout > 0.0 else 1.0
    heads_here = range(hb.per_block)
    packed = heads is not None

    def kernel(seed_ref, *refs):
        if kmask is not None:
            km_ref = refs[0]
            refs = refs[1:]
        (q_ref, k_ref, v_ref, g_ref, lse_ref, dlt_ref) = refs[:6]
        refs = refs[6:]
        km_s = db_ref = db_s = None
        if packed:
            # (the aliased input), the buffer, ..., staging and semaphores
            _, buf_ref, *refs, stage, sems = refs
            dk_ref = dv_ref = None
        else:
            dk_ref, dv_ref, *refs = refs
        if need_dbias:
            db_ref, dk_s, dv_s, db_s, *refs = refs
        else:
            dk_s, dv_s, *refs = refs
        if kmask is not None:
            km_s, = refs
        head0 = hb.first_head()
        n, p = pl.program_id(0), pl.program_id(1)
        kj = pl.program_id(2)
        qi = pl.program_id(3)

        @pl.when(qi == 0)
        def _init():
            dk_s[:] = jnp.zeros_like(dk_s)
            dv_s[:] = jnp.zeros_like(dv_s)
            if need_dbias:
                db_s[:] = jnp.zeros_like(db_s)
            if kmask is not None:
                km_s[:] = _row_to_col(km_ref[0])

        def tile(qs, ks, masked):
            nk_ = ks.stop - ks.start
            qpos = qi * block_q + qs.start + lax.broadcasted_iota(
                jnp.int32, (1, qs.stop - qs.start), 1)
            kpos = kj * block_k + ks.start + lax.broadcasted_iota(
                jnp.int32, (nk_, 1), 0)
            dks, dvs = [], []
            for h in heads_here:
                s = _block_scores(
                    _head_rows(k_ref[0, ks], h, hb),
                    q_ref[0, qs], scale,
                    qpos, kpos, masked,
                    km_s[ks, :1] if kmask is not None else None)
                p = jnp.exp(s - lse_ref[h, :, qs])      # (k rows, q rows)
                if kmask is not None:
                    p = jnp.where(s <= _NEG_INF * 0.5, 0.0, p)
                dp = _mxu(_head_rows(v_ref[0, ks], h, hb),
                          g_ref[0, qs], _NT)
                p_drop = p
                if dropout > 0.0:
                    keep = _keep(seed_ref[0, 0], head0 + h, qpos, kpos,
                                 dropout)
                    dp = jnp.where(keep, dp, 0.0) * inv_keep
                    p_drop = jnp.where(keep, p, 0.0) * inv_keep
                ds = p * (dp - dlt_ref[h, :, qs])
                dvs.append(_mxu(p_drop.astype(g_ref.dtype),
                                g_ref[0, qs], _NN))
                dks.append(_mxu(ds.astype(q_ref.dtype),
                                q_ref[0, qs], _NN))
                if need_dbias:
                    db_s[h, ks] = db_s[h, ks] + jnp.broadcast_to(
                        jnp.sum(ds, axis=1, keepdims=True), (nk_, _LANES))
            dv_s[ks] = dv_s[ks] + _by_head(dvs, hb)
            dk_s[ks] = dk_s[ks] + _by_head(dks, hb)

        _causal_tiles(qi, kj, block_q, block_k, causal, "k", tile)

        def copies():
            """dk and dv of this grid step: staging -> their lane blocks
            of the one (B, L, 3U) gradient."""
            rows = pl.ds(pl.multiple_of(kj * block_k, block_k), block_k)
            return [pltpu.make_async_copy(
                stage.at[r],
                buf_ref.at[n, rows, pl.ds(pl.multiple_of(
                    (at + p) * hb.width, hb.width), hb.width)],
                sems.at[r]) for r, at in enumerate((hb.k_at, hb.v_at))]

        @pl.when(qi == nq - 1)
        def _finalize():
            if packed:
                first = (n == 0) & (p == 0) & (kj == 0)
                last = (n == hb.n0 - 1) & (p == hb.pairs - 1) & \
                    (kj == nk - 1)

                @pl.when(jnp.logical_not(first))
                def _previous_landed():
                    for c in copies():
                        c.wait()
                stage[0] = (dk_s[:] * scale).astype(stage.dtype)
                stage[1] = dv_s[:].astype(stage.dtype)
                for c in copies():
                    c.start()

                @pl.when(last)
                def _all_landed():
                    for c in copies():
                        c.wait()
            else:
                dk_ref[0] = (dk_s[:] * scale).astype(dk_ref.dtype)
                dv_ref[0] = dv_s[:].astype(dv_ref.dtype)
            if need_dbias:
                for h in heads_here:
                    db_ref[h] = _col_to_row(db_s[h])

    if causal:
        # q blocks before the diagonal re-name the first one read
        q_block = lambda j, i: jnp.minimum(
            jnp.maximum(i, j * block_k // block_q), nq - 1)
    else:
        q_block = lambda j, i: i
    k_block = lambda j, i: j
    in_specs, args = _lead_operands(seed, kmask, hb, block_k, 2)
    in_specs += [hb.spec(block_q, q_block),
                 hb.spec(block_k, k_block, hb.k_at),
                 hb.spec(block_k, k_block, hb.v_at),
                 hb.spec(block_q, q_block),
                 hb.row_spec(block_q, q_block),
                 hb.row_spec(block_q, q_block)]
    out_specs = [hb.spec(block_k, k_block), hb.spec(block_k, k_block)]
    out_shape = [jax.ShapeDtypeStruct(hb.array(Lk), q.dtype)] * 2
    operands = [q3, k3, v3, g.reshape(hb.array(L)), lse, delta]
    aliases = {}
    if packed:
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        aliases = {len(args) + len(operands): 0}
        operands.append(into)
        out_specs = [pl.BlockSpec(memory_space=pl.ANY)]
        out_shape = [jax.ShapeDtypeStruct(into.shape, into.dtype)]
    scratch = [pltpu.VMEM((block_k, hb.width), jnp.float32),
               pltpu.VMEM((block_k, hb.width), jnp.float32)]
    if need_dbias:
        out_specs.append(hb.row_spec(block_k, k_block))
        out_shape.append(
            jax.ShapeDtypeStruct((hb.B * hb.H, 1, Lk), jnp.float32))
        scratch.append(
            pltpu.VMEM((hb.per_block, block_k, _LANES), jnp.float32))
    if kmask is not None:
        scratch.append(pltpu.VMEM((block_k, _LANES), jnp.float32))
    if packed:
        scratch += [pltpu.VMEM((2, block_k, hb.width), into.dtype),
                    pltpu.SemaphoreType.DMA((2,))]
    res = pl.pallas_call(
        kernel,
        grid=(hb.n0, hb.pairs, nk, nq),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 4 if packed
            else ("parallel",) * 3 + ("arbitrary",)),
        interpret=interpret,
        name="mx_flash_bwd_dkv" + _PACKED_NAME * packed,
    )(*args, *operands)
    if packed:
        return res[0], res[1] if need_dbias else None
    dk, dv, *db = res
    return dk.reshape(k.shape), dv.reshape(v.shape), \
        db[0].reshape(hb.B, hb.H, Lk) if need_dbias else None


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


def _kmask_grad(dbias_bh, bias, B, H):
    """The dk/dv kernel's per-head (B·H, 1, Lk) sums -> the key mask's
    gradient, in its (B|1, 1, 1, Lk) layout."""
    if bias is None:
        return None
    db = dbias_bh.reshape(B, H, -1).sum(axis=1)         # (B, Lk): sum heads
    if bias.shape[0] == 1:
        db = db.sum(axis=0, keepdims=True)
    return db.reshape(bias.shape).astype(bias.dtype)


def _flash_bwd(scale, causal, dropout, impl, res, g):
    q, k, v, bias, seed, out, lse = res
    B, H, Lq, D = q.shape
    Lk = k.shape[2]
    g32, o32 = g.astype(jnp.float32), out.astype(jnp.float32)
    # delta_i = sum_d o_i * do_i  (row-wise), standard flash backward
    delta = jnp.sum(o32 * g32, axis=-1)                 # (B,H,Lq)

    if impl != "xla" and _pallas_eligible(q, k, bias):
        kmask = _kmask_arrays(bias, B) if bias is not None else None
        lse_row = lse.reshape(B * H, 1, Lq)
        dlt_row = delta.reshape(B * H, 1, Lq)
        dq = _pallas_bwd_dq(q, k, v, g, lse_row, dlt_row, scale, causal,
                            kmask=kmask, seed=seed, dropout=dropout)
        dk, dv, dbias_bh = _pallas_bwd_dkv(
            q, k, v, g, lse_row, dlt_row, scale, causal, kmask=kmask,
            seed=seed, dropout=dropout, need_dbias=bias is not None)
        return dq, dk, dv, _kmask_grad(dbias_bh, bias, B, H), None

    # the five products below take q, k, v, g as they arrive and p / ds
    # cast to match (float32 inputs: float32 products, as before);
    # delta, lse, the scores and every accumulator are float32
    f32 = jnp.float32
    block = min(512, Lk)
    nkb = -(-Lk // block)
    padk = nkb * block - Lk
    if padk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, padk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, padk), (0, 0)))
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
        ks = lax.dynamic_slice_in_dim(k, j * block, block, axis=2)
        vs = lax.dynamic_slice_in_dim(v, j * block, block, axis=2)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, ks,
                       preferred_element_type=f32) * scale
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
        dp = jnp.einsum("bhqd,bhkd->bhqk", g, vs,
                        preferred_element_type=f32)
        p_drop = p
        if dropout > 0.0:
            keep = _keep(seed, bh, qpos[None, None], kpos[None, None],
                         dropout)
            dp = jnp.where(keep, dp, 0.0) / (1.0 - dropout)
            p_drop = jnp.where(keep, p, 0.0) / (1.0 - dropout)
        dv = jnp.einsum("bhqk,bhqd->bhkd", p_drop.astype(g.dtype), g,
                        preferred_element_type=f32)
        ds = p * (dp - delta[..., None]) * scale
        dq_acc = dq_acc + jnp.einsum(
            "bhqk,bhkd->bhqd", ds.astype(ks.dtype), ks,
            preferred_element_type=f32)
        dk = jnp.einsum("bhqk,bhqd->bhkd", ds.astype(q.dtype), q,
                        preferred_element_type=f32)
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

    dq0 = jnp.zeros(q.shape, f32)
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


# --------------------------------------------------------------------------- #
# the same kernels over the packed (B, L, 3U) projection
# --------------------------------------------------------------------------- #

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_qkv(qkv, bias, seed, heads, scale, causal, dropout):
    """Self-attention straight off the fused projection: ``qkv`` (B, L, 3U)
    in, (B, L, U) out, one (B, L, 3U) gradient back, and no transpose,
    slice or copy of q, k, v, ``do`` or the output between the projections
    and the kernels.  ``bias`` is None or a (B|1, 1, 1, L) key mask.
    Pallas path only (``_packed_heads`` and ``L % 128 == 0`` are the
    caller's to check)."""
    return _flash_qkv_fwd(qkv, bias, seed, heads, scale, causal, dropout)[0]


def _flash_qkv_fwd(qkv, bias, seed, heads, scale, causal, dropout):
    kmask = _kmask_arrays(bias, qkv.shape[0]) if bias is not None else None
    out, lse = _pallas_fwd(qkv, None, None, scale, causal, kmask=kmask,
                           seed=seed, dropout=dropout, heads=heads)
    return out, (qkv, bias, seed, out, lse)


def _flash_qkv_bwd(heads, scale, causal, dropout, res, g):
    qkv, bias, seed, out, lse = res
    B = out.shape[0]
    kmask = _kmask_arrays(bias, B) if bias is not None else None
    # one (B, L, 3U) buffer: the dq kernel writes its third and makes
    # delta, the dk/dv kernel fills in the rest — no concatenate
    dq, delta = _pallas_bwd_dq(
        qkv, None, None, g, lse, None, scale, causal, kmask=kmask, seed=seed,
        dropout=dropout, heads=heads, out=out)
    dqkv, dbias_bh = _pallas_bwd_dkv(
        qkv, None, None, g, lse, delta, scale, causal, kmask=kmask,
        seed=seed, dropout=dropout, need_dbias=bias is not None,
        heads=heads, into=dq)
    return dqkv, _kmask_grad(dbias_bh, bias, B, heads), None


_flash_qkv.defvjp(_flash_qkv_fwd, _flash_qkv_bwd)


# below this many score elements per head, materializing the full (Lq, Lk)
# attention matrix is cheap and XLA's fused softmax beats the blockwise
# kernel's scan overhead (measured on v5e: 12 layers of L=128 attention run
# ~25% faster unblocked); the flash path takes over where O(L^2) memory
# actually matters
_PLAIN_ATTN_MAX_SCORES = 512 * 512

# --------------------------------------------------------------------------- #
# measured dispatch (VERDICT r2 item 4: "chosen path == fastest measured
# path").  Constants are the crossover sequence lengths from
# ``benchmark/attention_bench.py`` on v5e (causal, D64, bf16, at the
# table's own shape B4 H8 and at the train cell's B8 H16).
# Entries are (max_seq, impl); the first row whose bound covers
# max(Lq, Lk) wins.  "plain" materializes O(L²)
# scores (fused-softmax), "xla" is the blockwise lax.scan path, "pallas"
# the Pallas kernels (fwd + bwd).
# --------------------------------------------------------------------------- #
_PATH_TABLE = {
    # measured 2026-10-03 on v5e (PR 32: every path feeds the MXU bf16),
    # ms at B4 H8 | B8 H16, pallas / xla / plain:
    #   fwd:   768   0.098 / 0.088 / 0.243 | 0.391 / 0.324 / 2.227
    #          1,024 0.123 / 0.143 / 0.989 | 0.494 / 0.573 / 3.983
    #          2,048 0.417 / 0.503 / 3.971 | 1.787 / 9.417 / 15.80
    #          4,096 1.335 / 1.969 / 15.49 | 6.064 / 38.77 / 49.46
    #          8,192 4.899 / 36.23 / -     |
    #   train: 768   0.282 / 0.299 / 0.516 | 1.191 / 2.760 / 4.159
    #          1,024 0.383 / 0.493 / 1.567 | 1.677 / 5.553 / 7.702
    #          2,048 1.319 / 3.906 / 7.347 | 6.004 / 28.82 / 29.46
    #          4,096 4.924 / 20.18 / 28.95 | 20.99 / 111.8 / out of memory
    #          8,192 18.17 / 109.8 / -     |
    # (train = forward, dq AND dk/dv: the bench now feeds all three
    # gradients on, where the sweep of 2026-07-30 fed dq alone and XLA
    # dropped the dk/dv kernel as dead code.  That sweep, float32 products
    # and heads padded to 128 lanes, had pallas behind xla up to 4,096 fwd:
    # 1.58 against 1.17 ms at 1,024, B4 H8.)
    # Sequences <= 512 already took the plain path via
    # _PLAIN_ATTN_MAX_SCORES before the table is consulted; there the
    # same sweep reads, fwd, 0.077 / 0.047 / 0.046 | 0.306 / 0.182 / 1.027
    # and, train, 0.184 / 0.108 / 0.122 | 0.756 / 0.788 / 1.618: plain
    # holds at the small batch and trails at the large one, and the choice
    # does not see the batch.
    # From the packed (B, L, 3U) projection (``--packed``, 2026-10-04, PR 34;
    # both arms the Pallas kernels, "apart" pays the split into heads and
    # the transposes back), ms at B4 H8 | B8 H16, packed / apart:
    #   fwd:   1,024 0.110 / 0.150 | 0.440 / 0.759    2,048 - | 1.667 / 2.520
    #   train: 1,024 0.359 / 0.421 | 1.438 / 1.893    2,048 - | 5.361 / 7.031
    # (a head out of a 128-lane pair by lane masks; by 64-lane slices the
    # packed arm read 0.497 and 1.565 at 1,024, B8 H16; with three gradient
    # arrays and a concatenate in place of the one buffer, 1.446 and 5.398.)
    # The rows below hold for it as they stand: it asks them the same way.
    "fwd": ((768, "xla"), (None, "pallas")),
    "train": ((None, "pallas"),),
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


def _dropout_seed(rate):
    """The call's seed of the position hash: fresh bits off the framework's
    key where anything is dropped, a constant where not."""
    if rate > 0.0:
        from .. import random as mxrandom
        return jax.random.bits(mxrandom.next_key(), dtype=jnp.uint32)
    return jnp.uint32(0)


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
    ``_PATH_TABLE`` (benchmark/attention_bench.py sweep) by
    ``(Lq, Lk, bias, training)``: up to 512 x 512 scores the unblocked
    fused-softmax path; above that, in training, the Pallas kernels
    (forward, dq, dk/dv) at every length, and in inference the XLA
    blockwise path up to 768 and the Pallas forward beyond.  Every path
    feeds the MXU the inputs' own dtype and accumulates in float32.  On
    the Pallas path 128-unaligned lengths are padded inside the op (the
    pad keys are masked via the key-mask bias channel); general dense
    biases (not a ``(B|1,1,1,Lk)`` key mask), and every backend but the
    TPU, always use the XLA paths."""
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    if training is None:
        from .. import autograd
        training = autograd.is_training()
    rate = float(dropout) if training else 0.0
    seed = _dropout_seed(rate)
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


def _split_heads(qkv, heads):
    """(B, L, 3U) -> q, k, v as (B, H, L, D): the reshape / transpose /
    slice ``MultiHeadAttention`` did itself before the packed kernels."""
    B, L, U3 = qkv.shape
    qkv = qkv.reshape(B, L, 3, heads, U3 // 3 // heads)
    qkv = qkv.transpose(2, 0, 3, 1, 4)                   # (3, B, H, L, D)
    return qkv[0], qkv[1], qkv[2]


@op("flash_attention_qkv")
def flash_attention_qkv(qkv, bias=None, *, num_heads: int,
                        causal: bool = False, dropout: float = 0.0,
                        training: Optional[bool] = None):
    """Self-attention over the fused projection ``qkv`` (B, L, 3U) — q, k
    and v side by side, heads within each — giving (B, L, U): what
    ``MultiHeadAttention`` puts between its two projections.  ``bias``,
    ``causal``, ``dropout`` and ``training`` are ``flash_attention``'s.

    It asks the same measured table as ``flash_attention``.  Where the
    answer is the Pallas kernels, L is whole 128-row blocks and the heads
    fill whole lane tiles (``_packed_heads``: D 64 with an even H, or
    ``D % 128 == 0``), the kernels read q, k and v as
    128-lane blocks of ``qkv`` where it lies and write (B, L, U) — no
    transpose or slice around them, forward or backward, and the same
    dropout positions as the call apart.  Everywhere else (plain and XLA
    paths, a dense bias, other widths, no TPU) it splits the heads as the
    model did and calls ``flash_attention``, where XLA folds the transposes
    into its einsums.  A ``telemetry`` event ``attention_path`` a traced
    call names the path taken."""
    from .. import telemetry
    B, L, U3 = qkv.shape
    U = U3 // 3
    if training is None:
        from .. import autograd
        training = autograd.is_training()
    path = _choose_path(L, L, bias, bool(training))
    if path == "pallas" and L % _BLOCK == 0 and _packed_heads(U, num_heads):
        path = "packed"
    telemetry.emit("attention_path", op="flash_attention_qkv", path=path,
                   seq=L, heads=num_heads, head_dim=U // num_heads)
    if path != "packed":
        # outside ``mx.attn``: the split is the enclosing region's, as it
        # was the model's
        q, k, v = _split_heads(qkv, num_heads)
        out = get_op("flash_attention").fn(
            q, k, v, bias, causal=causal, dropout=dropout, training=training)
        return out.transpose(0, 2, 1, 3).reshape(B, L, U)
    with jax.named_scope("mx.attn"):
        rate = float(dropout) if training else 0.0
        return _flash_qkv(qkv, bias, _dropout_seed(rate), num_heads,
                          1.0 / (U // num_heads) ** 0.5, bool(causal), rate)


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
