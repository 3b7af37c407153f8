"""GPT-family decoder-only language models (the flagship perf model).

Reference counterpart: none in-tree (the reference's NLP stack is GluonNLP);
this corresponds to BASELINE config 5 ("GPT-2 774M TP×DP").  Design is
TPU-first: pre-norm blocks over flash attention, fused QKV, bf16-friendly,
and a Megatron-style tensor-parallel sharding rule set (``gpt_tp_rules``)
that GSPMD turns into ICI collectives.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax

from ..gluon.block import HybridBlock
from ..gluon.nn.basic_layers import Dense, Dropout, Embedding, LayerNorm
from .transformer import TransformerDecoderCell

__all__ = ["GPTConfig", "GPT", "gpt2_small", "gpt2_medium", "gpt2_large",
           "gpt2_774m", "gpt_tp_rules"]


@dataclass
class GPTConfig:
    vocab_size: int = 50257
    max_length: int = 1024
    num_layers: int = 12
    units: int = 768
    num_heads: int = 12
    hidden_size: int = 3072
    dropout: float = 0.0
    dtype: str = "float32"

    @property
    def num_params(self) -> int:
        wpe = self.max_length * self.units
        wte = self.vocab_size * self.units
        per_layer = (3 * self.units * self.units + 3 * self.units  # qkv
                     + self.units * self.units + self.units        # proj
                     + 2 * self.units * self.hidden_size           # ffn
                     + self.hidden_size + self.units
                     + 4 * self.units)                             # 2×LN
        return wte + wpe + self.num_layers * per_layer + 2 * self.units


class GPT(HybridBlock):
    """Decoder-only transformer LM: tokens (B, L) → logits (B, L, vocab).

    The LM head reuses the token embedding (weight tying) — one big
    (B·L, units) × (units, vocab) MXU GEMM.
    """

    def __init__(self, config: GPTConfig, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._cfg = config
        c = config
        with self.name_scope():
            self.wte = Embedding(c.vocab_size, c.units, dtype=c.dtype,
                                 prefix="wte_")
            self.wpe = Embedding(c.max_length, c.units, dtype=c.dtype,
                                 prefix="wpe_")
            self.drop = Dropout(c.dropout) if c.dropout else None
            self.blocks = []
            for i in range(c.num_layers):
                cell = TransformerDecoderCell(
                    c.units, c.hidden_size, c.num_heads, c.dropout,
                    dtype=c.dtype,
                    prefix=f"h{i}_")
                self.register_child(cell, f"h{i}")
                self.blocks.append(cell)
            self.ln_f = LayerNorm(in_channels=c.units, prefix="lnf_")

    # weight tying (LM head = wte.T) reads a child's parameter directly, so
    # the whole model defines ``forward`` instead of ``hybrid_forward``;
    # hybridize still jits it (the CachedOp traces ``forward``).
    def forward(self, tokens, *args, **kwargs):
        from .. import ndarray as F
        B, L = tokens.shape
        # device-timeline regions (docs/TELEMETRY.md): the embeddings
        # count with the layers, ``ln_f`` with the head
        with jax.named_scope("mx.dense"):
            x = self.wte(tokens)
            pos_ids = F.broadcast_to(
                F.reshape(F.arange(L, dtype="int32"), shape=(1, L)),
                shape=(B, L))
            x = x + self.wpe(pos_ids)
            if self.drop is not None:
                x = self.drop(x)
        for blk in self.blocks:
            x = blk(x)
        with jax.named_scope("mx.head"):
            x = self.ln_f(x)
            w = self.wte.weight.data()                   # (vocab, units)
            logits = F.dot(F.reshape(x, shape=(B * L, self._cfg.units)),
                           w, transpose_b=True)
            return F.reshape(logits,
                             shape=(B, L, self._cfg.vocab_size))

    def stacked_decode_weights(self):
        """Every layer's decode weights stacked into (num_layers, ...)
        arrays (one array per slot: qkv/proj/fc1/fc2 weight+bias, the
        four LayerNorm rows) — the operand set of the stacked-layer
        ``lax.scan`` decode path in ``models.kv_generate``, which runs
        ONE layer-body's worth of HLO instead of ``num_layers`` unrolled
        copies.  See ``decoding.stack_decode_weights``."""
        from .decoding import stack_decode_weights
        return stack_decode_weights(self.blocks)

    def generate(self, prompt_tokens, max_new_tokens=32, temperature=1.0,
                 top_k=0, seed=None):
        """Autoregressive sampling (greedy when ``temperature==0``;
        ``top_k>0`` restricts the sample space).  Host-driven loop over the
        growing prefix — jit caches one program per length like the
        reference's BucketingModule caches per-bucket graphs."""
        import numpy as np
        from .. import ndarray as nd

        rng = np.random.RandomState(seed if seed is not None else 0)
        out = np.asarray(
            prompt_tokens.asnumpy() if hasattr(prompt_tokens, "asnumpy")
            else prompt_tokens, dtype=np.int32)
        for _ in range(max_new_tokens):
            window = out[:, -self._cfg.max_length:]
            logits = self(nd.array(window, dtype="int32"))
            last = logits.asnumpy()[:, -1].astype(np.float64)   # (B, V)
            if temperature == 0.0:
                nxt = last.argmax(-1).astype(np.int32)
            else:
                last = last / max(temperature, 1e-6)
                if top_k and top_k < last.shape[-1]:
                    kth = np.partition(last, -top_k, axis=-1)[:, -top_k]
                    last = np.where(last < kth[:, None], -np.inf, last)
                p = np.exp(last - last.max(-1, keepdims=True))
                p /= p.sum(-1, keepdims=True)
                nxt = np.asarray([rng.choice(p.shape[-1], p=row)
                                  for row in p], dtype=np.int32)
            out = np.concatenate([out, nxt[:, None]], axis=1)
        return out


def gpt_tp_rules(tp_axis: str = "tp"):
    """Megatron-style TP sharding: QKV/fc1 split on the output dim, proj/fc2
    on the input dim (one all-reduce per block pair, inserted by GSPMD);
    embeddings sharded on vocab."""
    from ..parallel import ShardingRules, P
    return ShardingRules([
        (r".*attn_qkv_weight", P(tp_axis, None)),
        (r".*attn_qkv_bias", P(tp_axis)),
        (r".*attn_out_weight", P(None, tp_axis)),
        (r".*ffn_fc1_weight", P(tp_axis, None)),
        (r".*ffn_fc1_bias", P(tp_axis)),
        (r".*ffn_fc2_weight", P(None, tp_axis)),
        (r".*wte_weight", P(tp_axis, None)),
    ])


def _preset(**kw):
    def make(**overrides):
        cfg = GPTConfig(**{**kw, **overrides})
        return GPT(cfg), cfg
    return make


gpt2_small = _preset(num_layers=12, units=768, num_heads=12,
                     hidden_size=3072)
gpt2_medium = _preset(num_layers=24, units=1024, num_heads=16,
                      hidden_size=4096)
gpt2_large = _preset(num_layers=36, units=1280, num_heads=20,
                     hidden_size=5120)
gpt2_774m = gpt2_large  # BASELINE config 5 naming
