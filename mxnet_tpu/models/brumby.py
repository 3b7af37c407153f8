"""The ``brumby`` language model (manifestai/Brumby-14B-Base, ``config.json``):
Qwen3-14B's decoder with every attention layer replaced by degree-2 power
retention (arXiv:2507.04239; ``ops.power_retention``).  A layer, RMSNorm eps
``rms_norm_eps``, no biases:

- retention: ``q = rope(RMSNorm_q(h W_q))`` per query head, ``k =
  rope(RMSNorm_k(h W_k))`` and ``v = h W_v`` per KV head (query head ``j``
  reads KV head ``j // (heads / kv heads)``), RoPE over the halves of a head
  at ``rope_theta``; a decay ``log a = logsigmoid(h W_a)`` a token a KV head
  (``W_a`` ``(hidden, kv heads)``); each KV head keeps the state ``S_t = a_t
  S_{t-1} + phi(k_t) v_t^T``, ``z_t = a_t z_{t-1} + phi(k_t)``, ``phi`` the
  degree-2 symmetric power, and each of its query heads reads ``y =
  phi(q)^T S / (phi(q) . z + eps)``; out ``W_o [y]``;
- a SwiGLU feed-forward of ``intermediate_size``.

The embedding and the head are untied.  The configuration gives no key for
the degree (2), the gate's projection or ``eps`` (``retention_eps``): the
defaults are what the serving configuration states under ``assumed``.
Prefill's query block (``retention_chunk``: 128 queries at a time against
every key of a dispatch) is the program's own choice; the model's equations
do not depend on it.

Every layer is of one kind, so the parameters are ONE stacked run,
``(layers, ...)``, which the serving engine scans
(``models.layered.LayeredEngine``).  ``decode_description()`` names the
attention kind ``retention`` and the cache kind ``retention_state`` under the
SLOT table: the model holds no pages.  ``forward`` is the full causal pass
through that engine's dense (fresh pools) form.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp

from ..gluon.block import HybridBlock

__all__ = ["BrumbyConfig", "Brumby", "brumby_tiny", "parameter_shapes"]


@dataclass
class BrumbyConfig:
    vocab_size: int = 151936
    hidden_size: int = 5120
    num_hidden_layers: int = 40
    intermediate_size: int = 17408
    num_attention_heads: int = 40
    num_key_value_heads: int = 8
    head_dim: int = 128
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    retention_eps: float = 1e-6
    retention_chunk: int = 128
    max_length: int = 32768
    dtype: str = "float32"

    @classmethod
    def from_hf(cls, hf, max_length=None, dtype="float32"):
        """From the published ``config.json`` keys; refuses what the model
        does not implement."""
        for k, want in (("use_sliding_window", False),
                        ("sliding_window", None),
                        ("tie_word_embeddings", False),
                        ("attention_bias", False), ("hidden_act", "silu"),
                        ("rope_scaling", None)):
            if hf.get(k, want) != want:
                raise ValueError(f"brumby: {k}={hf[k]!r} is not implemented "
                                 f"(only {want!r})")
        names = set(cls.__dataclass_fields__)
        kw = {k: v for k, v in hf.items() if k in names}
        kw["max_length"] = int(max_length or hf["max_position_embeddings"])
        kw["dtype"] = dtype
        cfg = cls(**kw)
        if cfg.num_attention_heads % cfg.num_key_value_heads:
            raise ValueError("brumby: num_attention_heads is not a multiple "
                             "of num_key_value_heads")
        return cfg

    def attention(self):
        """The sizes of a layer's mixer."""
        return {"kind": "retention", "heads": self.num_attention_heads,
                "kv_heads": self.num_key_value_heads,
                "head_dim": self.head_dim, "theta": float(self.rope_theta),
                "rope": "halves", "qk_norm": True, "degree": 2,
                "eps": float(self.retention_eps),
                "chunk": int(self.retention_chunk)}


def _layer_shapes(cfg):
    """``{parameter suffix: shape of ONE layer}``; matrices are stored
    ``(in, out)``, so a product is ``x @ W``."""
    H, F, d = cfg.hidden_size, cfg.intermediate_size, cfg.head_dim
    q, kv = cfg.num_attention_heads * d, cfg.num_key_value_heads * d
    return {"norm1_gamma": (H,), "norm2_gamma": (H,),
            "qnorm_gamma": (d,), "knorm_gamma": (d,),
            "q_weight": (H, q), "k_weight": (H, kv), "v_weight": (H, kv),
            "gate_weight": (H, cfg.num_key_value_heads),
            "o_weight": (q, H),
            "gu_weight": (H, 2 * F), "down_weight": (F, H)}


def parameter_shapes(cfg):
    """``{parameter name (no prefix): (shape, dtype)}`` of the whole model,
    in declaration order: the layers stacked along a leading axis (``r0_``,
    the one run); norm gains float32."""
    out = {"wte_weight": (cfg.vocab_size, cfg.hidden_size),
           "normf_gamma": (cfg.hidden_size,),
           "head_weight": (cfg.hidden_size, cfg.vocab_size)}
    for k, s in _layer_shapes(cfg).items():
        out["r0_" + k] = (cfg.num_hidden_layers,) + s
    return {k: (s, "float32" if k.endswith("_gamma") else cfg.dtype)
            for k, s in out.items()}


class Brumby(HybridBlock):
    """tokens ``(B, L)`` -> logits ``(B, L, vocabulary)``."""

    def __init__(self, config: BrumbyConfig, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._cfg = config
        self._names = []
        with self.name_scope():
            for name, (shape, dtype) in parameter_shapes(config).items():
                init = "ones" if name.endswith("_gamma") else None
                setattr(self, "p_" + name, self.params.get(
                    name, shape=shape, dtype=dtype, init=init))
                self._names.append(name)

    def weights(self):
        """``{"wte", "normf", "head", "runs": [{suffix: stacked array}]}`` of
        the parameters' current (possibly traced) values."""
        val = {n: getattr(self, "p_" + n).data()._data for n in self._names}
        return {"wte": val["wte_weight"], "normf": val["normf_gamma"],
                "head": val["head_weight"],
                "runs": [{n[3:]: v for n, v in val.items()
                          if n.startswith("r0_")}]}

    def decode_description(self):
        """Per layer: ``{"attn": {...kind and sizes}, "ffn": {...},
        "cache": kind}``."""
        c = self._cfg
        return [{"attn": c.attention(),
                 "ffn": {"kind": "swiglu", "width": c.intermediate_size},
                 "cache": "retention_state"}
                for _ in range(c.num_hidden_layers)]

    def forward(self, tokens, *args, **kwargs):
        from ..ndarray.ndarray import NDArray
        from .layered import LayeredEngine

        toks = tokens._data if isinstance(tokens, NDArray) else tokens
        eng = LayeredEngine(self, toks.shape[0], toks.shape[1],
                            toks.shape[1])
        out = eng.forward_dense(self.weights(), jnp.asarray(toks))
        return NDArray(out) if isinstance(tokens, NDArray) else out


def brumby_tiny(dtype="float32", **overrides):
    """A toy of the same shape for the CPU tests: two KV heads of two query
    heads each, heads of 32 (whole tiles of the expansion and of its 128-lane
    chunks, so the kernel takes it), a chunk passed in a few tokens."""
    kw = dict(vocab_size=96, hidden_size=64, num_hidden_layers=2,
              intermediate_size=96, num_attention_heads=4,
              num_key_value_heads=2, head_dim=32, rms_norm_eps=1e-6,
              rope_theta=10000.0, retention_eps=1e-6, retention_chunk=8,
              max_length=128, dtype=dtype)
    kw.update(overrides)
    cfg = BrumbyConfig(**kw)
    return Brumby(cfg), cfg
