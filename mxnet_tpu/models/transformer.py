"""Transformer building blocks (hybridizable, MXU-shaped).

Reference counterpart: GluonNLP's BERT/Transformer blocks built on the
contrib interleaved self-attention ops
(``_contrib_interleaved_matmul_selfatt_qk``, SURVEY.md §3.1) which fuse the
QKV projections into one matmul.  Here the same fusion holds (one
Dense(3·units) projection — one big MXU GEMM), the O(L²) score
materialization is replaced by the flash kernel (O(L) memory,
SURVEY.md §5.7), and the kernel reads the heads out of that projection
where it lies (``ops.attention.flash_attention_qkv``).
"""
from __future__ import annotations

import jax

from ..base import MXNetError
from ..gluon.block import HybridBlock
from ..gluon.nn.basic_layers import Dense, Dropout, LayerNorm

__all__ = ["MultiHeadAttention", "PositionwiseFFN",
           "TransformerEncoderCell", "TransformerDecoderCell"]


class MultiHeadAttention(HybridBlock):
    """Fused-QKV multi-head self-attention over (batch, seq, units).

    ``causal=True`` gives decoder (GPT) masking inside the flash kernel;
    an optional additive ``mask`` input (broadcastable to (B, 1, L, L),
    −inf at masked positions) carries encoder padding masks.
    """

    def __init__(self, units, num_heads, dropout=0.0, causal=False,
                 use_bias=True, dtype="float32", prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        if units % num_heads:
            raise MXNetError(f"units {units} not divisible by "
                             f"num_heads {num_heads}")
        self._units = units
        self._heads = num_heads
        self._causal = causal
        self._attn_dropout = dropout
        with self.name_scope():
            self.qkv = Dense(3 * units, flatten=False, use_bias=use_bias,
                             in_units=units, dtype=dtype, prefix="qkv_")
            self.proj = Dense(units, flatten=False, use_bias=use_bias,
                              in_units=units, dtype=dtype, prefix="out_")
            self.drop = Dropout(dropout) if dropout else None

    def hybrid_forward(self, F, x, mask=None):
        # q, k and v stay where the projection put them: the op reads the
        # (B, L, 3U) array by head and hands (B, L, U) back
        out = F.flash_attention_qkv(self.qkv(x), mask,
                                    num_heads=self._heads,
                                    causal=self._causal,
                                    dropout=self._attn_dropout)
        out = self.proj(out)
        if self.drop is not None:
            out = self.drop(out)
        return out


class PositionwiseFFN(HybridBlock):
    """units → hidden (GELU) → units; both matmuls MXU-large."""

    def __init__(self, units, hidden_size, dropout=0.0, activation="gelu",
                 dtype="float32", prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        with self.name_scope():
            self.fc1 = Dense(hidden_size, flatten=False, in_units=units,
                             activation=activation, dtype=dtype,
                             prefix="fc1_")
            self.fc2 = Dense(units, flatten=False, in_units=hidden_size,
                             dtype=dtype, prefix="fc2_")
            self.drop = Dropout(dropout) if dropout else None

    def hybrid_forward(self, F, x):
        out = self.fc2(self.fc1(x))
        if self.drop is not None:
            out = self.drop(out)
        return out


class _TransformerCell(HybridBlock):
    """Pre-norm transformer layer: x + attn(ln(x)); x + ffn(ln(x))."""

    def __init__(self, units, hidden_size, num_heads, dropout=0.0,
                 causal=False, dtype="float32", prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        with self.name_scope():
            self.ln1 = LayerNorm(in_channels=units, prefix="ln1_")
            self.attn = MultiHeadAttention(units, num_heads, dropout,
                                           causal=causal, dtype=dtype,
                                           prefix="attn_")
            self.ln2 = LayerNorm(in_channels=units, prefix="ln2_")
            self.ffn = PositionwiseFFN(units, hidden_size, dropout,
                                       dtype=dtype, prefix="ffn_")

    # the device-timeline region of the layer (docs/TELEMETRY.md) but
    # for the attention core, which ``ops.attention`` names ``mx.attn``
    @jax.named_scope("mx.dense")
    def hybrid_forward(self, F, x, mask=None):
        x = x + self.attn(self.ln1(x), mask) if mask is not None else \
            x + self.attn(self.ln1(x))
        return x + self.ffn(self.ln2(x))

    def decode_layer_arrays(self):
        """This layer's decode weights as a flat dict of device arrays —
        one slot per projection/bias/norm row, uniform across the GPT
        family so ``models.decoding.stack_decode_weights`` can stack the
        whole block list into (NL, ...) arrays for the stacked-layer scan
        decode (``models.kv_generate``).  Missing biases are exported as
        zeros so every layer stacks to the same pytree."""
        import jax.numpy as jnp

        def wb(lyr, tag):
            w = lyr.weight.data()._data
            b = lyr.bias.data()._data if getattr(lyr, "bias", None) \
                is not None else jnp.zeros((w.shape[0],), w.dtype)
            return {f"{tag}_w": w, f"{tag}_b": b}

        out = {}
        out.update(wb(self.attn.qkv, "qkv"))
        out.update(wb(self.attn.proj, "proj"))
        out.update(wb(self.ffn.fc1, "fc1"))
        out.update(wb(self.ffn.fc2, "fc2"))
        out.update({
            "ln1_g": self.ln1.gamma.data()._data,
            "ln1_b": self.ln1.beta.data()._data,
            "ln2_g": self.ln2.gamma.data()._data,
            "ln2_b": self.ln2.beta.data()._data,
        })
        return out


class TransformerEncoderCell(_TransformerCell):
    def __init__(self, units, hidden_size, num_heads, dropout=0.0,
                 dtype="float32", prefix=None, params=None):
        super().__init__(units, hidden_size, num_heads, dropout,
                         causal=False, dtype=dtype, prefix=prefix,
                         params=params)


class TransformerDecoderCell(_TransformerCell):
    def __init__(self, units, hidden_size, num_heads, dropout=0.0,
                 dtype="float32", prefix=None, params=None):
        super().__init__(units, hidden_size, num_heads, dropout,
                         causal=True, dtype=dtype, prefix=prefix,
                         params=params)
