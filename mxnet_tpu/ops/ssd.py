"""The state-space (Mamba-2 / SSD, arXiv:2405.21060) operations of a layer
whose memory of the stream is a fixed-size state a slot.

One head ``h`` of width ``P`` keeps ``S[h]`` ``(P, N)`` and sees, a token,
``x[h]`` ``(P,)``, a step ``dt[h] > 0``, and the shared ``B``, ``C`` ``(N,)``::

    S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] x_t[h] (outer) B_t
    y_t[h] = S_t[h] C_t                         (the caller adds D[h] x_t[h])

- ``state_update``: ONE token a slot against the stored state of every slot
  (the serving step).  The state is read once and written once, in place:
  on a TPU by the Pallas kernel ``mx_ssm_update`` (decay, rank-one update
  and the readout ``S C`` in one pass over a slot's block); elsewhere by the
  same arithmetic in ``jax.numpy``, which is the kernel's reference.  (XLA
  alone makes three passes: its readout is a second fusion that reads the
  state again.)
- ``chunk_scan``: ``T`` tokens a row from a given state (prefill), in the
  chunked form: within a chunk ``Y = ((C B^T) * L)(dt x)`` with ``L[t, s] =
  exp(sum_{s < r <= t} dt_r A)``, between chunks the state carried with its
  decay.  A position with ``dt = 0`` decays by 1 and adds nothing, which is
  how a right-padded row leaves the state at its true length.
- ``conv_step`` / ``conv_seq``: the causal depthwise convolution in front,
  over the stored tail of the last ``K - 1`` inputs.

STORED LAYOUT of a state: ``(H / g, N, P * g)`` — ``g = 128 // P`` heads share
a row's lanes, lane ``p * g + j`` holding element ``p`` of the group's head
``j`` (``pack`` / ``unpack``), the state width ``N`` on the sublanes.  So the
minor dimension is a whole 128-lane tile for ``P = 64``, the readout's sum
over ``N`` runs down the sublanes (vector adds, no cross-lane reduction a
row), ``B`` and ``C`` enter as columns spread over the lanes, and ``x``,
``dt``, the decay and ``y`` as lane rows.  The heads are INTERLEAVED in the
lanes, not side by side, so that no layout of the stored array is the
prefill's ``(H, P, N)``: with the heads side by side the chip's compiler
re-laid the whole array out to fold the transpose, and back, every chunk.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import _interpret

__all__ = ["STATE_DTYPE", "heads_per_row", "pack", "unpack", "state_update",
           "chunk_scan", "conv_step", "conv_seq", "supports"]

# a stored state's dtype: a decay near 1 multiplies it at every token, so it
# is not rounded to the model's dtype
STATE_DTYPE = jnp.dtype("float32")

_NAME = "mx_ssm_update"     # profiler_xla._KERNEL_REGIONS knows it
_BLOCK_BYTES = 1 << 20      # a slot's state is moved in blocks of this size


def heads_per_row(heads, head_dim):
    """How many heads share a stored row's lanes: as many as fill a
    128-lane tile, where they divide the heads; else one."""
    g = max(1, 128 // head_dim)
    return g if heads % g == 0 else 1


def pack(s):
    """``(..., H, P, N)`` -> the stored ``(..., H / g, N, P * g)``."""
    *lead, H, P, N = s.shape
    g = heads_per_row(H, P)
    s = s.reshape(*lead, H // g, g, P, N)               # (.., H2, g, P, N)
    return jnp.transpose(s, (*range(len(lead)), *(len(lead) + i for i in
                             (0, 3, 2, 1)))).reshape(*lead, H // g, N, P * g)


def unpack(s, head_dim):
    """The stored ``(..., H / g, N, P * g)`` -> ``(..., H, P, N)``."""
    *lead, H2, N, L = s.shape
    g = L // head_dim
    s = s.reshape(*lead, H2, N, head_dim, g)            # (.., H2, N, P, g)
    return jnp.transpose(s, (*range(len(lead)), *(len(lead) + i for i in
                             (0, 3, 2, 1)))).reshape(*lead, H2 * g, head_dim,
                                                     N)


def _rows(v, g):
    """``(S, H, P)`` per-head rows as lane rows ``(S, H / g, P * g)`` of the
    stored layout."""
    S, H, P = v.shape
    return jnp.swapaxes(v.reshape(S, H // g, g, P), -1, -2).reshape(
        S, H // g, P * g)


def _unrows(v, head_dim):
    """The inverse: lane rows ``(S, H / g, P * g)`` -> ``(S, H, P)``."""
    S, H2, L = v.shape
    g = L // head_dim
    return jnp.swapaxes(v.reshape(S, H2, head_dim, g), -1, -2).reshape(
        S, H2 * g, head_dim)


def supports(state_shape):
    """Whether the kernel takes a stored state of this static structure:
    whole (8, 128) tiles a head row."""
    _, _, H2, N, L = state_shape
    return L % 128 == 0 and N % 8 == 0


def _update_math(old, da, dtx, bcol, ccol):
    """``old`` ``(..., N, L)``; ``da``, ``dtx`` ``(..., 1, L)``; ``bcol``,
    ``ccol`` ``(N, L)``-broadcastable columns.  Returns ``(new, y (..., 1,
    L))``."""
    new = da * old + bcol * dtx
    return new, jnp.sum(new * ccol, axis=-2, keepdims=True)


def _kernel(layer_ref, live_ref, s_ref, da_ref, dtx_ref, b_ref, c_ref,
            out_ref, y_ref, *, rows):
    del layer_ref
    live = live_ref[pl.program_id(0)] != 0
    bcol, ccol = b_ref[0], c_ref[0]                  # (N, L)

    def row(j, carry):
        old = s_ref[0, 0, j]                         # (N, L)
        new, y = _update_math(old, da_ref[0, pl.ds(j, 1), :],
                              dtx_ref[0, pl.ds(j, 1), :], bcol, ccol)
        # a slot that is not stepping keeps its state: a chunked prefill
        # may be filling it between steps
        out_ref[0, 0, j] = jnp.where(live, new, old)
        y_ref[0, pl.ds(j, 1), :] = y
        return carry

    lax.fori_loop(0, rows, row, 0)


def _kernel_call(state, layer, live, da, dtx, bcol, ccol, interpret):
    _, S, H2, N, L = state.shape
    hb = max(1, min(H2, _BLOCK_BYTES // (N * L * 4)))
    while H2 % hb:
        hb -= 1
    row = pl.BlockSpec((1, hb, L), lambda s, j, *_: (s, j, 0))
    col = pl.BlockSpec((1, N, L), lambda s, j, *_: (s, 0, 0))
    block = pl.BlockSpec((1, 1, hb, N, L),
                         lambda s, j, layer, live: (layer[0], s, j, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(S, H2 // hb),
        in_specs=[block, row, row, col, col],
        out_specs=[block, row])
    new, y = pl.pallas_call(
        functools.partial(_kernel, rows=hb),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((S, H2, L), jnp.float32)],
        # operand 2 (after the two prefetched scalars) is the state: the
        # blocks of layer ``layer`` are rewritten where they lie
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        name=_NAME,
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), live.astype(jnp.int32),
      state, da, dtx, bcol, ccol)
    return new, y


def _plain_call(state, layer, live, da, dtx, bcol, ccol):
    old = lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)
    new, y = _update_math(old, da[:, :, None, :], dtx[:, :, None, :],
                          bcol[:, None], ccol[:, None])
    new = jnp.where(live[:, None, None, None], new, old)
    return lax.dynamic_update_index_in_dim(state, new, layer, 0), y[:, :, 0]


def state_update(state, layer, x, dt, a, b, c, live):
    """One token a slot.  ``state`` ``(layers, S, H / g, N, P * g)`` float32,
    the whole stored array (donated by the caller: updated in place);
    ``layer`` a traced scalar; ``x`` ``(S, H, P)``; ``dt`` ``(S, H)`` float32
    (after its softplus); ``a`` ``(H,)`` float32, negative; ``b``, ``c``
    ``(S, N)``; ``live`` ``(S,)`` bool — a slot that is not live keeps its
    state.  Returns ``(y (S, H, P) float32 = S_t C_t, state)``."""
    S, H, P = x.shape
    N, L = state.shape[-2:]
    g = L // P
    f32 = jnp.float32
    da = _rows(jnp.broadcast_to(jnp.exp(dt * a)[..., None], (S, H, P)), g)
    dtx = _rows(dt[..., None] * x.astype(f32), g)
    spread = lambda v: jnp.broadcast_to(v.astype(f32)[..., None], (S, N, L))
    args = (state, layer, live, da, dtx, spread(b), spread(c))
    if _interpret():
        state, y = _kernel_call(*args, interpret=True)
    elif supports(state.shape):
        state, y = lax.platform_dependent(
            *args, tpu=functools.partial(_kernel_call, interpret=False),
            default=_plain_call)
    else:
        state, y = _plain_call(*args)
    return _unrows(y, P), state


def chunk_scan(x, dt, a, b, c, init, chunk):
    """``T`` tokens a row from the state ``init``.  ``x`` ``(B, T, H, P)``;
    ``dt`` ``(B, T, H)`` float32, 0 where a row's token is padding; ``a``
    ``(H,)``; ``b``, ``c`` ``(B, T, N)``; ``init`` ``(B, H, P, N)`` float32.
    Returns ``(y (B, T, H, P) float32, final state (B, H, P, N) float32)``.
    Matrix operands take ``x``'s dtype, sums and the state float32."""
    Bn, T, H, P = x.shape
    N = b.shape[-1]
    f32, mdt = jnp.float32, x.dtype
    Q = min(int(chunk), T)
    pad = -T % Q
    if pad:
        widen = lambda v: jnp.pad(v, [(0, 0), (0, pad)]
                                  + [(0, 0)] * (v.ndim - 2))
        x, dt, b, c = widen(x), widen(dt), widen(b), widen(c)
    nc = (T + pad) // Q
    chunks = lambda v: jnp.moveaxis(
        v.reshape((Bn, nc, Q) + v.shape[2:]), 1, 0)
    tri = jnp.tril(jnp.ones((Q, Q), jnp.bool_))

    def body(s, xs):
        x_c, dt_c, b_c, c_c = xs
        cum = jnp.cumsum(jnp.moveaxis(dt_c * a, 1, 2), axis=-1)  # (B, H, Q)
        # L[t, s] = exp(cum_t - cum_s) for s <= t; the masked half would
        # overflow, so it is masked before the exp
        seg = jnp.where(tri, cum[..., :, None] - cum[..., None, :], -jnp.inf)
        gram = jnp.einsum("btn,bsn->bts", c_c, b_c,
                          preferred_element_type=f32)
        m = (gram[:, None] * jnp.exp(seg)).astype(mdt)        # (B, H, Q, Q)
        dtx = dt_c[..., None] * x_c.astype(f32)               # (B, Q, H, P)
        y = jnp.einsum("bhts,bshp->bthp", m, dtx.astype(mdt),
                       preferred_element_type=f32)
        # what the carried state adds, decayed to each position
        y = y + jnp.einsum("btn,bhpn->bthp", c_c.astype(f32), s) \
            * jnp.moveaxis(jnp.exp(cum), 1, 2)[..., None]
        to_end = jnp.exp(cum[..., -1:] - cum)                 # (B, H, Q)
        s = jnp.exp(cum[..., -1])[..., None, None] * s + jnp.einsum(
            "bshp,bsn->bhpn",
            (dtx * jnp.moveaxis(to_end, 1, 2)[..., None]).astype(mdt), b_c,
            preferred_element_type=f32)
        return s, y

    final, ys = lax.scan(body, init.astype(f32),
                         (chunks(x), chunks(dt), chunks(b), chunks(c)))
    y = jnp.moveaxis(ys, 0, 1).reshape(Bn, nc * Q, H, P)
    return y[:, :T], final


def _conv(win, w, bias, T):
    """``silu(bias + sum_j w[j] * win[:, j:j + T])`` in float32."""
    K = w.shape[0]
    acc = bias.astype(jnp.float32)
    for j in range(K):
        acc = acc + w[j].astype(jnp.float32) \
            * win[:, j:j + T].astype(jnp.float32)
    return jax.nn.silu(acc)


def conv_step(tail, u, w, bias):
    """One token a slot.  ``tail`` ``(S, K - 1, W)`` the slot's last inputs,
    ``u`` ``(S, W)`` the new one, ``w`` ``(K, W)``, ``bias`` ``(W,)``.
    Returns ``(activated output (S, W) in u's dtype, the new tail)``."""
    win = jnp.concatenate([tail, u[:, None]], axis=1)
    return _conv(win, w, bias, 1)[:, 0].astype(u.dtype), win[:, 1:]


def conv_seq(tail, u, w, bias, count):
    """``T`` tokens a row.  ``tail`` ``(B, K - 1, W)`` (zeros at a stream's
    start), ``u`` ``(B, T, W)``, ``count`` ``(B,)`` how many of a row's
    tokens are true (the rest is right padding).  Returns ``(activated
    output (B, T, W), the tail after the row's last TRUE token)``."""
    K = w.shape[0]
    win = jnp.concatenate([tail.astype(u.dtype), u], axis=1)
    # the last K - 1 true inputs are rows count .. count + K - 2 of ``win``
    idx = count[:, None] + jnp.arange(K - 1, dtype=jnp.int32)[None]
    new_tail = jnp.take_along_axis(win, idx[..., None], axis=1)
    return _conv(win, w, bias, u.shape[1]).astype(u.dtype), new_tail
