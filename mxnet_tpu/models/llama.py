"""Llama-family decoder models (BASELINE config 5: "GPT-2 774M /
Llama-7B TP×DP"; SURVEY.md §7 Phase 4).

TPU-first architecture choices, matching the public Llama design:
pre-RMSNorm blocks, rotary position embeddings (no learned positional
table), grouped-query attention (kv_heads ≤ heads), SwiGLU FFN, untied
LM head — all over the same flash-attention + GSPMD machinery as GPT.
No reference analog (the reference's NLP stack is GluonNLP-era BERT);
this is capability the rebuild adds, like flash/ring attention.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax

from ..gluon.block import HybridBlock
from ..gluon.nn.basic_layers import Dense, Embedding, RMSNorm

__all__ = ["LlamaConfig", "Llama", "llama_tp_rules", "llama_tiny",
           "llama_7b"]


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    max_length: int = 2048
    num_layers: int = 8
    units: int = 512
    num_heads: int = 8
    num_kv_heads: int = 8          # < num_heads => grouped-query attention
    hidden_size: int = 1376        # SwiGLU inner dim
    rope_base: float = 10000.0
    dtype: str = "float32"

    @property
    def num_params(self) -> int:
        u, h = self.units, self.hidden_size
        d = u // self.num_heads
        per_layer = (u * u + 2 * u * self.num_kv_heads * d + u * u  # qkvo
                     + 3 * u * h                                    # swiglu
                     + 2 * u)                                       # 2 rms
        return (self.vocab_size * u * 2    # embed + untied head
                + self.num_layers * per_layer + self.units)


class LlamaAttention(HybridBlock):
    """RoPE + grouped-query causal self-attention over (B, L, U)."""

    def __init__(self, units, num_heads, num_kv_heads, rope_base=10000.0,
                 dtype="float32", prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        if units % num_heads or num_heads % num_kv_heads:
            raise ValueError(f"units {units} / heads {num_heads} / "
                             f"kv_heads {num_kv_heads} incompatible")
        self._units = units
        self._heads = num_heads
        self._kv_heads = num_kv_heads
        self._rope_base = float(rope_base)
        d = units // num_heads
        with self.name_scope():
            self.q_proj = Dense(units, flatten=False, use_bias=False,
                                in_units=units, dtype=dtype, prefix="q_")
            self.k_proj = Dense(num_kv_heads * d, flatten=False,
                                use_bias=False, in_units=units,
                                dtype=dtype, prefix="k_")
            self.v_proj = Dense(num_kv_heads * d, flatten=False,
                                use_bias=False, in_units=units,
                                dtype=dtype, prefix="v_")
            self.o_proj = Dense(units, flatten=False, use_bias=False,
                                in_units=units, dtype=dtype, prefix="o_")

    def hybrid_forward(self, F, x):
        B, L, U = x.shape
        H, KV = self._heads, self._kv_heads
        D = U // H
        q = F.transpose(F.reshape(self.q_proj(x), shape=(B, L, H, D)),
                        axes=(0, 2, 1, 3))
        k = F.transpose(F.reshape(self.k_proj(x), shape=(B, L, KV, D)),
                        axes=(0, 2, 1, 3))
        v = F.transpose(F.reshape(self.v_proj(x), shape=(B, L, KV, D)),
                        axes=(0, 2, 1, 3))
        q = F.rope(q, base=self._rope_base)
        k = F.rope(k, base=self._rope_base)
        if KV != H:  # grouped-query: repeat kv heads across query groups
            rep = H // KV
            k = F.repeat(k, repeats=rep, axis=1)
            v = F.repeat(v, repeats=rep, axis=1)
        out = F.flash_attention(q, k, v, causal=True)
        out = F.reshape(F.transpose(out, axes=(0, 2, 1, 3)),
                        shape=(B, L, U))
        return self.o_proj(out)


class LlamaMLP(HybridBlock):
    """SwiGLU: down( silu(gate(x)) * up(x) )."""

    def __init__(self, units, hidden_size, dtype="float32", prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        with self.name_scope():
            self.gate = Dense(hidden_size, flatten=False, use_bias=False,
                              in_units=units, dtype=dtype, prefix="gate_")
            self.up = Dense(hidden_size, flatten=False, use_bias=False,
                            in_units=units, dtype=dtype, prefix="up_")
            self.down = Dense(units, flatten=False, use_bias=False,
                              in_units=hidden_size, dtype=dtype,
                              prefix="down_")

    def hybrid_forward(self, F, x):
        g = self.gate(x)
        return self.down(g * F.sigmoid(g) * self.up(x))  # silu(gate)*up


class LlamaCell(HybridBlock):
    def __init__(self, cfg: LlamaConfig, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        with self.name_scope():
            self.rms1 = RMSNorm(in_channels=cfg.units, prefix="rms1_")
            self.attn = LlamaAttention(cfg.units, cfg.num_heads,
                                       cfg.num_kv_heads, cfg.rope_base,
                                       dtype=cfg.dtype, prefix="attn_")
            self.rms2 = RMSNorm(in_channels=cfg.units, prefix="rms2_")
            self.mlp = LlamaMLP(cfg.units, cfg.hidden_size,
                                dtype=cfg.dtype, prefix="mlp_")

    @jax.named_scope("mx.dense")    # region: see _TransformerCell
    def hybrid_forward(self, F, x):
        x = x + self.attn(self.rms1(x))
        return x + self.mlp(self.rms2(x))

    def decode_layer_arrays(self):
        """This layer's decode weights as a flat dict of device arrays
        (the Llama-family counterpart of
        ``_TransformerCell.decode_layer_arrays``): split q/k/v/o
        projections (GQA — k/v rows are KV·D wide), SwiGLU gate/up/down,
        and the two RMSNorm gammas.  The family contract is bias-free
        projections, so no bias slots are exported."""
        return {
            "q_w": self.attn.q_proj.weight.data()._data,
            "k_w": self.attn.k_proj.weight.data()._data,
            "v_w": self.attn.v_proj.weight.data()._data,
            "o_w": self.attn.o_proj.weight.data()._data,
            "gate_w": self.mlp.gate.weight.data()._data,
            "up_w": self.mlp.up.weight.data()._data,
            "down_w": self.mlp.down.weight.data()._data,
            "rms1_g": self.rms1.gamma.data()._data,
            "rms2_g": self.rms2.gamma.data()._data,
        }


class Llama(HybridBlock):
    """tokens (B, L) → logits (B, L, vocab)."""

    def __init__(self, config: LlamaConfig, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._cfg = config
        c = config
        with self.name_scope():
            self.wte = Embedding(c.vocab_size, c.units, dtype=c.dtype,
                                 prefix="wte_")
            self.blocks = []
            for i in range(c.num_layers):
                cell = LlamaCell(c, prefix=f"h{i}_")
                self.register_child(cell, f"h{i}")
                self.blocks.append(cell)
            self.ln_f = RMSNorm(in_channels=c.units, prefix="rmsf_")
            self.head = Dense(c.vocab_size, flatten=False, use_bias=False,
                              in_units=c.units, dtype=c.dtype,
                              prefix="head_")

    def forward(self, tokens, *args, **kwargs):
        with jax.named_scope("mx.dense"):
            x = self.wte(tokens)
        for blk in self.blocks:
            x = blk(x)
        with jax.named_scope("mx.head"):
            return self.head(self.ln_f(x))

    def stacked_decode_weights(self):
        """Every layer's decode weights stacked into (num_layers, ...)
        arrays — the Llama/GQA operand set of the stacked-layer
        ``lax.scan`` decode path (``models.kv_generate``).  See
        ``GPT.stacked_decode_weights`` and
        ``decoding.stack_decode_weights``."""
        from .decoding import stack_decode_weights
        return stack_decode_weights(self.blocks)

    def generate(self, prompt_tokens, max_new_tokens=32, temperature=1.0,
                 top_k=0, seed=None):
        """Full-recompute autoregressive sampling (same loop as
        ``GPT.generate``).  For O(L)-per-token decode use
        ``models.kv_generate`` — it recognizes Llama blocks (RoPE via
        ``position_offset``, grouped-query KV cache)."""
        from .gpt import GPT
        return GPT.generate(self, prompt_tokens, max_new_tokens,
                            temperature, top_k, seed)


def llama_tp_rules(tp_axis: str = "tp"):
    """Megatron-style TP: q/k/v/gate/up split on the output dim,
    o/down on the input dim (one all-reduce per block pair via GSPMD);
    embedding + head sharded on vocab."""
    from ..parallel import P, ShardingRules
    return ShardingRules([
        (r".*(q|k|v|gate|up)_weight", P(tp_axis, None)),
        (r".*(o|down)_weight", P(None, tp_axis)),
        (r".*wte_weight", P(tp_axis, None)),
        (r".*head_weight", P(tp_axis, None)),
    ])


def _preset(**kw):
    def make(dtype="float32", **overrides):
        cfg = LlamaConfig(**{**kw, "dtype": dtype, **overrides})
        return Llama(cfg), cfg
    return make


llama_tiny = _preset(vocab_size=512, max_length=128, num_layers=2,
                     units=64, num_heads=4, num_kv_heads=2,
                     hidden_size=128)
llama_7b = _preset(vocab_size=32000, max_length=4096, num_layers=32,
                   units=4096, num_heads=32, num_kv_heads=32,
                   hidden_size=11008)
