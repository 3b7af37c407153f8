"""Model families (transformers).

The reference's transformer-era surface lives in GluonNLP (external) plus the
contrib fused-attention ops (SURVEY.md §3.1 contrib family,
``_contrib_interleaved_matmul_selfatt_*``).  Here the transformer family is
first-class: hybridizable Gluon blocks whose attention runs the flash
kernel (ops/attention.py) and whose layouts are MXU-shaped (fused QKV
matmul, big batched GEMMs).  Vision models live in
``gluon.model_zoo.vision``.
"""
from .transformer import (MultiHeadAttention, PositionwiseFFN,
                          TransformerEncoderCell, TransformerDecoderCell)
from .decoding import kv_generate, decode_mode, decode_step_program
from .gpt import GPT, GPTConfig, gpt2_small, gpt2_medium, gpt2_large, \
    gpt2_774m, gpt_tp_rules
from .bert import BERTModel, BERTConfig, bert_base, bert_large
from .llama import (Llama, LlamaConfig, llama_tp_rules, llama_tiny,
                    llama_7b)
from .dots3 import Dots3, Dots3Config, dots3_tiny
from .trinity import Trinity, TrinityConfig, trinity_tiny
from .granite_hybrid import (GraniteHybrid, GraniteHybridConfig,
                             granite_hybrid_tiny)
from .pangu_moe import PanguUltraMoE, PanguUltraMoEConfig, pangu_tiny
from .brumby import Brumby, BrumbyConfig, brumby_tiny
from .seq2seq import (CrossAttention, Seq2SeqEncoder, Seq2SeqDecoder,
                      Seq2SeqDecoderCell, TransformerSeq2Seq)

__all__ = [
    "MultiHeadAttention", "PositionwiseFFN", "TransformerEncoderCell",
    "TransformerDecoderCell", "GPT", "GPTConfig", "gpt2_small",
    "gpt2_medium", "gpt2_large", "gpt2_774m", "gpt_tp_rules",
    "BERTModel", "BERTConfig", "bert_base", "bert_large",
    "CrossAttention", "Seq2SeqEncoder", "Seq2SeqDecoder",
    "Seq2SeqDecoderCell", "TransformerSeq2Seq",
    "Llama", "LlamaConfig", "llama_tp_rules", "llama_tiny", "llama_7b",
    "Dots3", "Dots3Config", "dots3_tiny",
    "Trinity", "TrinityConfig", "trinity_tiny",
    "GraniteHybrid", "GraniteHybridConfig", "granite_hybrid_tiny",
    "PanguUltraMoE", "PanguUltraMoEConfig", "pangu_tiny",
    "Brumby", "BrumbyConfig", "brumby_tiny",
    "kv_generate", "decode_mode", "decode_step_program",
]
