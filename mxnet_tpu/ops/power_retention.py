"""Degree-2 power retention (Gelada, Buckman, Zhang et al., arXiv:2507.04239):
a layer whose memory of the stream is one state a KV head group, read by the
group's query heads.

A group ``g`` keeps ``S[g]`` ``(D, dv)`` and ``z[g]`` ``(D,)`` and sees, a
token, ``k[g]`` ``(d,)``, ``v[g]`` ``(dv,)`` and a decay ``a[g] = exp(log
a[g])`` in ``(0, 1)``; each query head ``h`` of the group reads it::

    S_t[g] = a_t S_{t-1}[g] + phi(k_t[g]) v_t[g]^T
    z_t[g] = a_t z_{t-1}[g] + phi(k_t[g])
    y_t[h] = phi(q_t[h])^T S_t[g] / (phi(q_t[h]) . z_t[g] + eps)

``phi`` is the degree-2 symmetric power: ``phi(q) . phi(k) = (q . k)^2``.

- ``expand``: ``phi`` in the STORED LAYOUT.  The ``d`` coordinates fall
  into ``t = d / 8`` tiles of 8; for every tile OFFSET ``o`` from 0 to ``t /
  2`` and every tile ``a`` the 64 rows ``(r, c)`` hold ``x[8a + r] x[8b +
  c]``, ``b = (a + o) mod t``, times ``sqrt(2)`` where ``o > 0``; at ``o = t
  / 2`` (``t`` even) a pair of tiles comes up twice, and only ``a < t / 2``
  is kept.  So every unordered pair of tiles is there once.  The pair ``a =
  b`` holds both ``(r, c)`` and ``(c, r)``, whose two rows add up to the
  cross term's factor 2 with no weight, and the sum over every row is
  ``sum_a (q_a . k_a)^2 + 2 sum_{a < b} (q_a . k_a)(q_b . k_b) = (q .
  k)^2``.  For ``d = 128`` that is ``E = 136 x 64 = 8,704`` rows, of which
  ``d (d + 1) / 2 = 8,256`` are distinct (``exact_rows``): the 448 repeated
  ones buy a layout that is one broadcast product of the tiles and their
  rolls, with no gather, in 128-lane chunks.
- ``state_update``: ONE token a slot against the stored state of every slot
  (the serving step).  Each live slot's state is read once and written once,
  in place: on a TPU by the Pallas kernel ``mx_retention_update`` (the decay,
  the rank-one update, ``z`` and the group's readouts in one pass over a
  group's block), whose only operand besides ``S`` and ``z`` is a slot's
  128-wide ``q``, ``k``, ``v`` and decay packed in one ``(128, 128)`` block:
  at the slot's first group it builds ``phi`` of the slot's query heads and
  keys in VMEM (``expand``'s rows to 2 ulp), and each group takes ``v``'s
  column from the block transposed; elsewhere by the same arithmetic in
  ``jax.numpy`` over ``expand``, which is the kernel's reference.  A slot
  that is not live moves no bytes.
- ``chunk_scan``: ``T`` tokens a row from a given state (prefill), in the
  chunked form: within a chunk the gated scores ``(q . k)^2 exp(G_t -
  G_s)``, across chunks ``phi(Q) S`` and ``phi(K)^T V``.  A right-padded
  position decays by 1 and adds nothing, so a row leaves the state at its
  true length.

STORED LAYOUT of a state: ``(G, dv, E)`` a slot — ``v``'s coordinates on the
sublanes, the expansion's rows on the lanes — and ``z`` ``(G, E)``.  So the
step's update is a column of ``v`` times a lane row of ``phi(k)``, and each
readout a lane row of ``phi(q)`` times the state, summed along the lanes: no
transpose in the loop.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import _interpret

__all__ = ["STATE_DTYPE", "expand", "expanded_rows", "exact_rows",
           "state_update", "chunk_scan", "supports", "prefill_kernels"]

# a stored state's dtype: a decay near 1 multiplies it at every token
STATE_DTYPE = jnp.dtype("float32")

# profiler_xla._KERNEL_REGIONS knows them: the step's kernel, and prefill's
# two products with the expansion
_NAME = "mx_retention_update"
_READ_NAME, _WRITE_NAME = "mx_retention_read", "mx_retention_write"
_TILE = 8                       # coordinates a tile of the expansion
_LANES = 128                    # the kernel walks a state in such chunks
_HIGHEST = lax.Precision.HIGHEST
# a kernel's products in the operands' own dtype, whatever the caller's
# default precision
_ONE_PASS = lax.Precision.DEFAULT


def expanded_rows(d):
    """Stored rows of ``phi`` of a ``d``-wide vector (the tiled layout)."""
    t = d // _TILE
    return t * (t + 1) // 2 * _TILE * _TILE


def exact_rows(d):
    """Distinct rows of the degree-2 symmetric power: ``d (d + 1) / 2``."""
    return d * (d + 1) // 2


def supports(d, dv):
    """Whether the step's kernel takes a state of these widths: ``phi``
    built as prefill's kernels build it (``prefill_kernels``; the expansion
    then whole 128-lane chunks too), whole sublane tiles of ``v``."""
    return prefill_kernels(d) and dv % 8 == 0


def expand(x):
    """``(..., d)`` -> ``phi(x)`` ``(..., E)`` float32 in the stored layout
    (module docstring): ONE broadcast product of each tile and its partner
    with the vectors on the minor axis, then one transpose — no gather, and
    no slice or concatenation of the products, which the chip's compiler
    would write out whole."""
    *lead, d = x.shape
    if d % _TILE:
        raise ValueError(f"power retention needs a width of whole tiles of "
                         f"{_TILE}, not {d}")
    t, N = d // _TILE, math.prod(lead)
    tiles = jnp.moveaxis(x.astype(jnp.float32).reshape(N, t, _TILE), 0, -1)
    offsets = t // 2 + 1
    rolled = jnp.stack([jnp.roll(tiles, -o, axis=0)
                        for o in range(offsets)])
    mine = jnp.broadcast_to(tiles[None], rolled.shape)    # (o, t, 8, N)
    # rows in groups of ``n`` tiles: an offset a group, or (t even) two,
    # the last offset's second half (its pairs again) left out
    n, groups = (t // 2, t + 1) if t % 2 == 0 else (t, offsets)
    part = lambda y: y.reshape(-1, n, _TILE, N)[:groups]
    w = np.full((groups, 1, 1, 1, 1), math.sqrt(2.0), np.float32)
    w[:t // n] = 1
    rows = part(mine)[:, :, :, None] * part(rolled)[:, :, None] * w
    return rows.reshape(-1, N).T.reshape(*lead, -1)


# -- the step ---------------------------------------------------------- #
def _rows_per_group(hpg):
    """Rows of a KV head group in the step's packed operand: its ``hpg``
    query heads, ``k``, ``v`` and the decay, padded to a sublane tile."""
    return -(-(hpg + 3) // 8) * 8


def _packed(q, k, v, log_a):
    """The step kernel's one operand besides the state: ``(S, R, W)``
    float32, group ``g``'s rows ``P g`` on (``P = _rows_per_group``) its
    query heads, ``k``, ``v`` and ``exp(log a)`` along the whole row, the
    rest zero; ``R`` and ``W`` whole 128-lane blocks so that the kernel can
    transpose a slot's block."""
    S, H, d = q.shape
    G, dv = k.shape[1], v.shape[-1]
    hpg = H // G
    P = _rows_per_group(hpg)
    W = -(-max(d, dv) // _LANES) * _LANES
    R = -(-G * P // _LANES) * _LANES
    lanes = lambda x: jnp.pad(x.astype(jnp.float32), [(0, 0)] * (x.ndim - 1)
                              + [(0, W - x.shape[-1])])
    a = jnp.broadcast_to(jnp.exp(log_a)[..., None, None], (S, G, 1, W))
    rows = jnp.concatenate([lanes(q.reshape(S, G, hpg, d)),
                            lanes(k)[:, :, None], lanes(v)[:, :, None], a],
                           axis=2)
    rows = jnp.pad(rows, [(0, 0), (0, 0), (0, P - hpg - 3), (0, 0)])
    return jnp.pad(rows.reshape(S, G * P, W), [(0, 0), (0, R - G * P),
                                               (0, 0)])


def _phi_block(x_ref, xt_ref, rows_ref, phi_ref, *, t):
    """``phi`` of every vector of a slot's packed block ``x_ref`` ``(1, R,
    W)`` (vectors of ``8 t`` coordinates) into ``phi_ref`` ``(G, P, E)``,
    row ``P g + j`` of the block at ``[g, j]``: the block transposed once
    into ``xt_ref`` (coordinates on the sublanes, vectors on the lanes),
    ``_phi_rows`` a group of tile pairs at a time into ``rows_ref``, each
    128-row block of that transposed back (the vectors on the sublanes, the
    expansion's rows on the lanes).  The rows are ``expand``'s to 2 ulp:
    ``sqrt(2)`` multiplies the partner tile, not the product."""
    G, P = phi_ref.shape[:2]
    n = t // 2
    xt_ref[0] = x_ref[0].T.reshape(xt_ref.shape[1:])
    span = 64 * n

    def group(m, carry):
        _phi_rows(xt_ref, rows_ref, m, t=t, n=n)
        for b in range(span // _LANES):
            blk = rows_ref[pl.ds(b * _LANES, _LANES), :].T          # (R, L)
            lo = pl.multiple_of(m * span + b * _LANES, _LANES)
            for g in range(G):
                phi_ref[g, :, pl.ds(lo, _LANES)] = blk[g * P:(g + 1) * P]
        return carry

    lax.fori_loop(0, t + 1, group, 0)


def _kernel(layer_ref, live_ref, map_ref, grp_ref, first_ref, s_ref, z_ref,
            x_ref, s_out, z_out, y_out, acc_ref, xt_ref, rows_ref, phi_ref,
            *, hpg, dv, h8, t):
    del layer_ref, map_ref, grp_ref
    s, g = pl.program_id(0), pl.program_id(1)
    live = live_ref[s] != 0
    P = phi_ref.shape[1]

    @pl.when(jnp.logical_and(jnp.logical_not(live), first_ref[s] != 0))
    def _keep():
        # a step with no live slot before it reads the first live slot's
        # block (or slot 0's), whose output buffer is not yet written
        s_out[...] = s_ref[...]
        z_out[...] = z_ref[...]

    @pl.when(jnp.logical_and(live, g == 0))
    def _build():
        # the slot's q and k do not change across its groups: every
        # group's phi once, at its first
        _phi_block(x_ref, xt_ref, rows_ref, phi_ref, t=t)

    @pl.when(live)
    def _step():
        # the group's rows of the block: a mask, not a dynamic sublane
        # index (which the chip's compiler refuses)
        x = x_ref[0]
        at_row = lambda r: lax.broadcasted_iota(jnp.int32, x.shape, 0) == r
        a = jnp.sum(jnp.where(at_row(g * P + hpg + 2), x, 0.0), axis=0,
                    keepdims=True)[:, :_LANES]                    # (1, 128)
        xt = x.T
        at_v = lax.broadcasted_iota(jnp.int32, xt.shape, 1) == g * P + hpg + 1
        vcol = jnp.broadcast_to(jnp.sum(jnp.where(at_v, xt, 0.0), axis=1,
                                        keepdims=True)[:dv], (dv, _LANES))
        acc_ref[...] = jnp.zeros_like(acc_ref)

        def chunk(c, dacc):
            lo = pl.multiple_of(c * _LANES, _LANES)
            blk = phi_ref[g, :, pl.ds(lo, _LANES)]                  # (P, L)
            pk, pqc = blk[hpg:hpg + 1], blk[:hpg]
            new = a * s_ref[0, 0, 0, :, pl.ds(lo, _LANES)] + vcol * pk
            s_out[0, 0, 0, :, pl.ds(lo, _LANES)] = new
            for h in range(hpg):
                acc_ref[h] += new * pqc[h:h + 1]
            # z: this group's row updated; the rows before it are the
            # earlier steps' (in the output buffer), the rows after it as
            # read
            zin = z_ref[0, 0, :, pl.ds(lo, _LANES)]
            rows = lax.broadcasted_iota(jnp.int32, zin.shape, 0)
            zall = jnp.where(rows < g, z_out[0, 0, :, pl.ds(lo, _LANES)], zin)
            zall = jnp.where(rows == g, a * zin + pk, zall)
            z_out[0, 0, :, pl.ds(lo, _LANES)] = zall
            zn = jnp.sum(jnp.where(rows == g, zall, 0.0), axis=0,
                         keepdims=True)
            return dacc + zn * pqc

        dacc = lax.fori_loop(0, s_ref.shape[-1] // _LANES, chunk,
                             jnp.zeros((hpg, _LANES), jnp.float32))
        den = jnp.sum(dacc, axis=-1, keepdims=True)                # (hpg, 1)
        y_out[0, 0, pl.ds(h8, hpg), :] = jnp.broadcast_to(den, (hpg, dv))
        ones = jnp.ones((8, _LANES), jnp.float32)
        for h in range(hpg):
            # the lane sum of the head's products, as a row of v
            row = lax.dot_general(ones, acc_ref[h], (((1,), (1,)), ((), ())),
                                  precision=_HIGHEST,
                                  preferred_element_type=jnp.float32)
            y_out[0, 0, pl.ds(h, 1), :] = row[:1]


def _routes(live):
    """Which block each slot's grid steps read: a live slot its own; a slot
    that is not live the LAST block of the live slot before it (what the
    pipeline holds already: nothing moves), or, with none before, the first
    block of the first live one (of slot 0 where none is live), which it
    keeps as it is.  ``(slot map, no live slot before)``."""
    S = live.shape[0]
    idx = jnp.arange(S, dtype=jnp.int32)
    before = lax.cummax(jnp.where(live, idx, -1))
    first_live = jnp.argmax(live).astype(jnp.int32)
    lead = before < 0
    slot = jnp.where(live, idx, jnp.where(lead, first_live, before))
    return slot, lead


def _kernel_call(state, z, layer, live, q, k, v, log_a, interpret):
    _, S, G, dv, E = state.shape
    d = k.shape[-1]
    hpg = q.shape[1] // G
    h8 = -(-hpg // 8) * 8
    x = _packed(q, k, v, log_a)
    R, W = x.shape[1:]
    P = _rows_per_group(hpg)
    t = d // _TILE
    slot, lead = _routes(live)
    grp = jnp.where(live, -1, jnp.where(lead, 0, G - 1)).astype(jnp.int32)

    def own(g, grp_ref, s):
        return jnp.where(grp_ref[s] < 0, g, grp_ref[s])

    st = pl.BlockSpec((1, 1, 1, dv, E), lambda s, g, lr, lv, m, gr, f: (
        lr[0], m[s], own(g, gr, s), 0, 0))
    zs = pl.BlockSpec((1, 1, G, E),
                      lambda s, g, lr, lv, m, gr, f: (lr[0], m[s], 0, 0))
    xs = pl.BlockSpec((1, R, W), lambda s, g, lr, lv, m, gr, f: (m[s], 0, 0))
    ys = pl.BlockSpec((1, 1, 2 * h8, dv), lambda s, g, lr, lv, m, gr, f: (
        m[s], own(g, gr, s), 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5, grid=(S, G),
        in_specs=[st, zs, xs], out_specs=[st, zs, ys],
        scratch_shapes=[pltpu.VMEM((hpg, dv, _LANES), jnp.float32),
                        pltpu.VMEM((1, W // _TILE, _TILE, R), jnp.float32),
                        pltpu.VMEM((32 * t, R), jnp.float32),
                        pltpu.VMEM((G, P, E), jnp.float32)])
    # two buffers of each block in and out, the state's dominating; the
    # scratch, phi of the slot's vectors the largest of it
    need = 4 * (4 * dv * E + 4 * G * E + 2 * R * W + 4 * h8 * dv
                + hpg * dv * _LANES + W * R + 32 * t * R + G * P * E)
    new, zn, y = pl.pallas_call(
        functools.partial(_kernel, hpg=hpg, dv=dv, h8=h8, t=t),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct(z.shape, z.dtype),
                   jax.ShapeDtypeStruct((S, G, 2 * h8, dv), jnp.float32)],
        # operands 5 and 6 (after the five prefetched scalars) are the state
        # and z: the blocks of layer ``layer`` are rewritten where they lie
        input_output_aliases={5: 0, 6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=min(100 << 20, need + (8 << 20))),
        name=_NAME,
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), live.astype(jnp.int32),
      slot, grp, lead.astype(jnp.int32), state, z, x)
    return y[:, :, :hpg], y[:, :, h8:h8 + hpg, 0], new, zn


def _plain_call(state, z, layer, live, q, k, v, log_a):
    S, H, _ = q.shape
    G = k.shape[1]
    old = lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)
    zo = lax.dynamic_index_in_dim(z, layer, 0, keepdims=False)
    pk = expand(k)                                        # (S, G, E)
    pq = expand(q.reshape(S, G, H // G, -1))              # (S, G, hpg, E)
    a = jnp.exp(log_a)[..., None]                         # (S, G, 1)
    new = a[..., None] * old \
        + v.astype(jnp.float32)[..., None] * pk[:, :, None, :]
    zn = a * zo + pk
    # the sums over E in the kernel's order: the 128-lane chunks added up
    # lane by lane, then the lanes
    lanes = lambda x: x.reshape(*x.shape[:-1], -1, _LANES)
    num = jnp.sum(jnp.einsum("sgvcl,sghcl->sghvl", lanes(new), lanes(pq),
                             precision=_HIGHEST), axis=-1)
    den = jnp.sum(jnp.einsum("sgcl,sghcl->sghl", lanes(zn), lanes(pq),
                             precision=_HIGHEST), axis=-1)
    keep = live[:, None, None]
    new = jnp.where(keep[..., None], new, old)
    zn = jnp.where(keep, zn, zo)
    return num, den, lax.dynamic_update_index_in_dim(state, new, layer, 0), \
        lax.dynamic_update_index_in_dim(z, zn, layer, 0)


def state_update(state, z, layer, q, k, v, log_a, live, eps):
    """One token a slot.  ``state`` ``(layers, S, G, dv, E)`` and ``z``
    ``(layers, S, G, E)`` float32, the whole stored arrays (donated by the
    caller: updated in place); ``layer`` a traced scalar; ``q`` ``(S, H,
    d)``, query head ``h`` of group ``h // (H / G)``; ``k`` ``(S, G, d)``;
    ``v`` ``(S, G, dv)``; ``log_a`` ``(S, G)`` float32; ``live`` ``(S,)``
    bool — a slot that is not live keeps its state.  Returns ``(y (S, H, dv)
    float32, state, z)``; ``y`` of a slot that is not live is 0."""
    S, H, d = q.shape
    dv = v.shape[-1]
    args = (state, z, layer, live, q, k, v, log_a)
    if not supports(d, dv):
        num, den, state, z = _plain_call(*args)
    elif _interpret():
        num, den, state, z = _kernel_call(*args, interpret=True)
    else:
        num, den, state, z = lax.platform_dependent(
            *args, tpu=functools.partial(_kernel_call, interpret=False),
            default=_plain_call)
    y = num / (den + eps)[..., None]
    y = jnp.where(live[:, None, None, None], y, 0.0)
    return y.reshape(S, H, dv), state, z


# -- prefill ----------------------------------------------------------- #
def _layout(d):
    """The stored layout's rows as index arrays: ``(i, j, w)`` with ``phi(x)
    [e] = w[e] x[i[e]] x[j[e]]`` (what ``expand`` builds)."""
    t = d // _TILE
    offsets = t // 2 + 1
    n, groups = (t // 2, t + 1) if t % 2 == 0 else (t, offsets)
    m, a2, r, c = np.meshgrid(np.arange(groups), np.arange(n),
                              np.arange(_TILE), np.arange(_TILE),
                              indexing="ij")
    o, a = m // (t // n), (m % (t // n)) * n + a2
    i, j = _TILE * a + r, _TILE * ((a + o) % t) + c
    w = np.where(o == 0, 1.0, math.sqrt(2.0))
    return i.ravel(), j.ravel(), w.ravel().astype(np.float32)


def _expand_gram(gram):
    """``sum_s c_s phi(k_s)`` from ``gram = sum_s c_s k_s k_s^T`` ``(...,
    d, d)``: the layout's entries of the matrix, weighted."""
    d = gram.shape[-1]
    i, j, w = _layout(d)
    return gram.reshape(*gram.shape[:-2], d * d)[..., i * d + j] * w


def prefill_kernels(d):
    """Whether prefill's two products with the expansion take the kernels
    that build ``phi`` in VMEM: whole tiles, and groups of tile pairs a
    multiple of 128 rows (``d`` a multiple of 32)."""
    return d % (4 * _TILE) == 0


def _phi_rows(xt_ref, phi_ref, m, c=None, *, t, n):
    """Rows ``m * 64 n`` on of ``phi`` TRANSPOSED — one group of ``n`` tile
    pairs, ``(64 n, R)`` float32 into ``phi_ref`` — from ``xt_ref`` ``(1, t,
    8, R)``: the coordinates of ``R`` vectors in tiles of 8 on the
    sublanes.  Row ``(a', r, c)`` of the group is ``x[8a + r] x[8b + c]``:
    coordinate ``8a + r`` broadcast down eight sublanes times the tile
    ``b``, with no gather; ``c`` ``(1, R)`` scales each vector."""
    per = t // n
    o, h = m // per, m % per
    wgt = jnp.where(o == 0, 1.0, math.sqrt(2.0)).astype(jnp.float32)
    for a2 in range(n):
        a = h * n + a2
        xa = xt_ref[0, a]                                     # (8, R)
        xb = xt_ref[0, (a + o) % t] * wgt
        if c is not None:
            xb = xb * c
        for r in range(_TILE):
            phi_ref[pl.ds(a2 * 64 + r * _TILE, _TILE), :] = \
                xa[r:r + 1, :] * xb


def _read_kernel(xt_ref, s_ref, o_ref, phi_ref, *, t, n, groups):
    """``o = S phi(X)^T``: a group's state ``(rows, E)`` read by ``R``
    expanded vectors, ``phi`` built a group of tile pairs at a time."""
    o_ref[...] = jnp.zeros_like(o_ref)
    span = 64 * n

    def group(m, carry):
        _phi_rows(xt_ref, phi_ref, m, t=t, n=n)
        lo = pl.multiple_of(m * span, span)
        o_ref[0] += jnp.dot(s_ref[0, :, pl.ds(lo, span)],
                            phi_ref[...].astype(s_ref.dtype),
                            precision=_ONE_PASS,
                            preferred_element_type=jnp.float32)
        return carry

    lax.fori_loop(0, groups, group, 0)


def _write_kernel(xt_ref, c_ref, v_ref, o_ref, phi_ref, *, t, n, groups):
    """``o += V phi(c K)``: ``R`` tokens' values ``(dv, R)`` times their
    expanded keys, each scaled by its ``c``, summed into ``(dv, E)`` over
    the token blocks of the grid's second axis."""
    @pl.when(pl.program_id(1) == 0)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    span = 64 * n
    c = c_ref[0]                                              # (1, R)

    def group(m, carry):
        _phi_rows(xt_ref, phi_ref, m, c, t=t, n=n)
        lo = pl.multiple_of(m * span, span)
        o_ref[0, :, pl.ds(lo, span)] += lax.dot_general(
            v_ref[0], phi_ref[...].astype(v_ref.dtype),
            (((1,), (1,)), ((), ())), precision=_ONE_PASS,
            preferred_element_type=jnp.float32)
        return carry

    lax.fori_loop(0, groups, group, 0)


def _tiles_t(x, rows):
    """``(N, R0, d)`` -> ``(N, d / 8, 8, rows)`` float32: the coordinates on
    the sublanes in tiles of 8, the vectors on the lanes, padded with zero
    vectors to ``rows``."""
    N, R0, d = x.shape
    xt = jnp.swapaxes(x.astype(jnp.float32), 1, 2)
    xt = jnp.pad(xt, [(0, 0), (0, 0), (0, rows - R0)])
    return xt.reshape(N, d // _TILE, _TILE, rows)


def _lane_block(n):
    """The widest of 1,024 / 512 / 256 / 128 lanes that ``n`` (a multiple of
    128) splits into."""
    return next(b for b in (1024, 512, 256, 128) if n % b == 0)


def _read_call(x, s, interpret):
    """``S phi(x)^T`` for every group: ``x`` ``(N, R0, d)``, ``s`` ``(N,
    rows, E)``; returns ``(N, rows, R0)`` float32."""
    N, R0, d = x.shape
    rows, E = s.shape[1:]
    t = d // _TILE
    n, groups = t // 2, t + 1
    Rp = -(-R0 // 128) * 128
    R = _lane_block(Rp)
    xt = _tiles_t(x, Rp)
    out = pl.pallas_call(
        functools.partial(_read_kernel, t=t, n=n, groups=groups),
        grid=(N, Rp // R),
        in_specs=[pl.BlockSpec((1, t, _TILE, R), lambda i, j: (i, 0, 0, j)),
                  pl.BlockSpec((1, rows, E), lambda i, j: (i, 0, 0))],
        out_specs=pl.BlockSpec((1, rows, R), lambda i, j: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((N, rows, Rp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((64 * n, R), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=64 << 20),
        name=_READ_NAME, interpret=interpret,
    )(xt, s)
    return out[..., :R0]


def _write_call(x, c, v, E, interpret):
    """``sum_s v_s phi(c_s x_s)^T`` for every group: ``x`` ``(N, T, d)``,
    ``c`` ``(N, T)``, ``v`` ``(N, T, dv)``; returns ``(N, dv, E)``
    float32."""
    N, T, d = x.shape
    dv = v.shape[-1]
    t = d // _TILE
    n, groups = t // 2, t + 1
    Tp = -(-T // 128) * 128
    R = _lane_block(Tp)
    xt = _tiles_t(x, Tp)
    cp = jnp.pad(c.astype(jnp.float32), [(0, 0), (0, Tp - T)])[:, None]
    vt = jnp.pad(jnp.swapaxes(v, 1, 2), [(0, 0), (0, 0), (0, Tp - T)])
    return pl.pallas_call(
        functools.partial(_write_kernel, t=t, n=n, groups=groups),
        grid=(N, Tp // R),
        in_specs=[pl.BlockSpec((1, t, _TILE, R), lambda i, j: (i, 0, 0, j)),
                  pl.BlockSpec((1, 1, R), lambda i, j: (i, 0, j)),
                  pl.BlockSpec((1, dv, R), lambda i, j: (i, 0, j))],
        out_specs=pl.BlockSpec((1, dv, E), lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((N, dv, E), jnp.float32),
        scratch_shapes=[pltpu.VMEM((64 * n, R), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=64 << 20),
        name=_WRITE_NAME, interpret=interpret,
    )(xt, cp, vt)


def _kernels_or(kernel, plain, *args):
    """``kernel(*args, interpret)`` where prefill's kernels run (a TPU, or
    ``MXNET_FLASH_INTERPRET=1``), else ``plain(*args)``."""
    if _interpret():
        return kernel(*args, True)
    return lax.platform_dependent(
        *args, tpu=functools.partial(kernel, interpret=False), default=plain)


def chunk_scan(q, k, v, log_a, init, init_z, count, chunk, eps):
    """``T`` tokens a row from the state ``(init, init_z)``, in the ATTENTION
    form within the dispatch and the state form only at its two ends.  ``q``
    ``(B, T, H, d)``; ``k`` ``(B, T, G, d)``; ``v`` ``(B, T, G, dv)``;
    ``log_a`` ``(B, T, G)`` float32; ``init`` ``(B, G, dv, E)``, ``init_z``
    ``(B, G, E)`` float32; ``count`` ``(B,)`` how many of a row's tokens are
    true (the rest is right padding: it decays by 1 and adds nothing).
    Returns ``(y (B, T, H, dv) float32, final state, final z)``.

    With ``G_t`` the decays' running log sum from the dispatch's start::

        y_t = (sum_{s<=t} (q_t . k_s)^2 e^(G_t - G_s) v_s + e^G_t phi(q_t)^T S_0)
            / (sum_{s<=t} (q_t . k_s)^2 e^(G_t - G_s) + e^G_t phi(q_t) . z_0
               + eps)
        S_T = e^G_T S_0 + sum_s e^(G_T - G_s) phi(k_s) v_s^T
        z_T = e^G_T z_0 + (the layout's entries of sum_s e^(G_T - G_s) k_s k_s^T)

    Queries go in blocks of ``chunk``, each against every key of the
    dispatch, masked.  The carried state's term is taken only where some
    row's ``z_0`` is not zero: a dispatch that starts its streams builds no
    ``phi(Q)``.  On a TPU (``prefill_kernels``) the two products with the
    expansion, ``S_0 phi(Q)^T`` and ``V phi(K)``, are the Pallas kernels
    ``mx_retention_read`` and ``mx_retention_write``, which build ``phi`` a
    group of tile pairs at a time in VMEM from the transposed vectors; elsewhere
    ``expand`` and ``einsum``.  The scores' operands, the weighted values'
    and those two products take ``q``'s dtype, one pass of the MXU each; the
    state, ``z`` (its update a float32 Gram matrix) and every sum are
    float32."""
    Bn, T, H, d = q.shape
    G, dv = k.shape[2], v.shape[-1]
    E = init.shape[-1]
    hpg = H // G
    f32, mdt = jnp.float32, q.dtype
    true = jnp.arange(T)[None] < count[:, None]
    log_a = jnp.where(true[..., None], log_a, 0.0)
    k = jnp.where(true[..., None, None], k, 0)
    cum = jnp.cumsum(log_a, axis=1)                           # (B, T, G)
    Q = min(int(chunk), T)
    pad = -T % Q
    nb = (T + pad) // Q
    blocks = lambda x: jnp.moveaxis(jnp.pad(
        x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2)).reshape(
            (Bn, nb, Q) + x.shape[2:]), 1, 0)
    unblock = lambda x: jnp.moveaxis(x, 0, 1).reshape(
        (Bn, nb * Q) + x.shape[3:])[:, :T]
    qg = q.reshape(Bn, T, G, hpg, d)
    cg = jnp.moveaxis(cum, 1, 2)                              # (B, G, T)
    kpos = jnp.arange(T, dtype=jnp.int32)
    vm = v.astype(mdt)

    def block(xs):
        q_b, c_b, t_b = xs
        qk = jnp.einsum("btgjd,bsgd->bgjts", q_b, k,
                        preferred_element_type=f32)          # (B,G,hpg,Q,T)
        # exp(G_t - G_s) for s <= t; the masked part would overflow, so it
        # is masked before the exp
        diff = jnp.moveaxis(c_b, 1, 2)[..., :, None] - cg[..., None, :]
        seg = jnp.exp(jnp.where(t_b[:, None] >= kpos[None], diff, -jnp.inf))
        w = qk * qk * seg[:, :, None]
        num = jnp.einsum("bgjts,bsgv->btgjv", w.astype(mdt), vm,
                         preferred_element_type=f32)
        return num, jnp.moveaxis(jnp.sum(w, axis=-1), 3, 1)  # (B,Q,G,hpg)

    num, den = lax.map(block, (blocks(qg), blocks(cum), jnp.arange(
        nb * Q, dtype=jnp.int32).reshape(nb, Q)))
    num, den = unblock(num), unblock(den)

    def carried():
        """What the state adds, decayed to each position."""
        dec = jnp.exp(cum)[..., None]                         # (B, T, G, 1)

        def read_plain(q_all):
            pq = expand(q_all).astype(mdt)
            return (jnp.einsum("btgjE,bgvE->btgjv", pq, init.astype(mdt),
                               preferred_element_type=f32),
                    jnp.einsum("btgjE,bgE->btgj", pq, init_z.astype(mdt),
                               preferred_element_type=f32))

        def read_kernel(q_all, interpret):
            # z as a row below the state's, padded to a bf16 tile
            sa = jnp.concatenate(
                [init.astype(mdt), jnp.pad(init_z[:, :, None].astype(mdt),
                                           [(0, 0), (0, 0), (0, 15), (0, 0)])],
                axis=2)
            xs = jnp.moveaxis(q_all, 1, 3).reshape(Bn * G, hpg * T, d)
            out = _read_call(xs, sa.reshape(Bn * G, dv + 16, E), interpret)
            out = out.reshape(Bn, G, dv + 16, hpg, T)
            return (jnp.transpose(out[:, :, :dv], (0, 4, 1, 3, 2)),
                    jnp.transpose(out[:, :, dv], (0, 3, 1, 2)))

        nx, dx = _kernels_or(read_kernel, read_plain, qg) \
            if prefill_kernels(d) else read_plain(qg)
        return nx * dec[..., None], dx * dec

    nx, dx = lax.cond(jnp.any(init_z != 0), carried,
                      lambda: (jnp.zeros_like(num), jnp.zeros_like(den)))
    y = (num + nx) / (den + dx + eps)[..., None]
    to_end = jnp.exp(cum[:, -1:] - cum)                       # (B, T, G)
    last = jnp.exp(cum[:, -1])                                # (B, G)

    def write_plain(kk, c, vv):
        pk = (expand(kk) * c[..., None]).astype(mdt)
        return jnp.einsum("bsgE,bsgv->bgvE", pk, vv,
                          preferred_element_type=f32)

    def write_kernel(kk, c, vv, interpret):
        flat = lambda x: jnp.moveaxis(x, 2, 1).reshape(
            (Bn * G, T) + x.shape[3:])
        out = _write_call(flat(kk), flat(c[..., None])[..., 0], flat(vv), E,
                          interpret)
        return out.reshape(Bn, G, dv, E)

    added = _kernels_or(write_kernel, write_plain, k, to_end, vm) \
        if prefill_kernels(d) else write_plain(k, to_end, vm)
    gram = jnp.einsum("bsgi,bsgj->bgij", k.astype(f32) * to_end[..., None],
                      k.astype(f32), precision=_HIGHEST)
    s = last[..., None, None] * init.astype(f32) + added
    zz = last[..., None] * init_z.astype(f32) + _expand_gram(gram)
    return (y.reshape(Bn, T, H, dv), s, zz)
