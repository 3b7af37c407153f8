"""The ``pangu_ultra_moe`` language model (FreedomIntelligence/openPangu-
Ultra-MoE-718B, ``config.json``): decoder blocks under a SANDWICH norm (an
RMSNorm before and one after each of the two sub-blocks, ``sandwich_norm``)
whose attention is LATENT over every earlier position (queries and
keys/values go through low-rank bottlenecks; the cache row is the normed
latent plus one shared rotary key) and whose feed-forward is ROUTED (256
sigmoid-scored experts, 8 a token, one shared expert) after
``first_k_dense_replace`` leading dense layers.

Layer ``i``, every RMSNorm at ``rms_norm_eps``, no biases:

- ``x' = x + N_attn_post(MLA(N_in(x)))``, ``x'' = x' + N_ffn_post(FFN(
  N_ffn_pre(x')))``;
- MLA: ``c_q = RMSNorm(x W_qa)``, per head ``[q_nope | q_rope] = c_q W_qb``;
  ``[c_kv | k_r] = x W_kva``, ``c_kv = RMSNorm(c_kv)``, ``k_rope =
  RoPE(k_r)`` one key shared by every head (consecutive pairs,
  ``rope_theta``); per head ``[k_nope | v] = c_kv W_kvb``; scores
  ``(q_nope . k_nope + RoPE(q_rope) . k_rope) / sqrt(nope + rope)`` with a
  softmax over ALL ``s <= t``; output ``concat_h(sum p v) W_o``.  No gate,
  no rescale of the latents;
- FFN: SwiGLU at ``intermediate_size`` for the first
  ``first_k_dense_replace`` layers; then sigmoid routing over all
  ``n_routed_experts`` with a selection bias used for the choice only, the
  ``num_experts_per_tok`` weights normalised (``norm_topk_prob``) and scaled
  by ``routed_scaling_factor``, plus one shared expert;
- head: ``N_f(x) W_head``, untied.

The MTP module (``num_nextn_predict_layers``) is not part of this file.

The block is built from the published keys (``PanguUltraMoEConfig.from_hf``).
``held_experts = (lo, n)`` and ``vocab_slice = (lo, n)`` say what of a layer
this chip holds when experts and vocabulary are divided over chips (as
``models.dots3`` has them): routing is over all ``n_routed_experts``, the
result is the held experts' part plus the shared expert, ids and logits are
over the slice.

``decode_description()`` is what the serving engine consumes
(``models.layered.LayeredEngine``): per layer the attention (kind ``latent``
with its sizes), the feed-forward, the cache kind ``latent`` (one latent row
under the main page table) and ``post_norms``.  ``forward`` is the full
causal pass through that engine's dense (fresh pools) form.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp

from ..gluon.block import HybridBlock

__all__ = ["PanguUltraMoEConfig", "PanguUltraMoE", "pangu_tiny",
           "parameter_shapes"]


@dataclass
class PanguUltraMoEConfig:
    vocab_size: int = 153600
    hidden_size: int = 7680
    num_hidden_layers: int = 61
    first_k_dense_replace: int = 3
    intermediate_size: int = 18432
    rms_norm_eps: float = 1e-5
    num_attention_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 25600000.0
    # routed feed-forward
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 2048
    routed_scaling_factor: float = 2.5
    # what this chip holds, and the cache horizon
    held_experts: tuple = (0, 256)
    vocab_slice: tuple = (0, 153600)
    max_length: int = 131072
    dtype: str = "float32"

    @classmethod
    def from_hf(cls, hf, num_hidden_layers=None, held_experts=None,
                vocab_slice=None, max_length=None, dtype="float32"):
        """From the published ``config.json`` keys; the four arguments are
        the cuts a deployment makes (depth, experts held, vocabulary held,
        cache horizon).  A value this file does not implement raises."""
        names = {f for f in cls.__dataclass_fields__}
        kw = {k: v for k, v in hf.items() if k in names}
        kw["num_hidden_layers"] = int(num_hidden_layers
                                      or hf["num_hidden_layers"])
        kw["held_experts"] = tuple(held_experts) if held_experts \
            else (0, int(hf["n_routed_experts"]))
        kw["vocab_slice"] = tuple(vocab_slice) if vocab_slice \
            else (0, int(hf["vocab_size"]))
        kw["max_length"] = int(max_length
                               or hf["max_position_embeddings"])
        kw["dtype"] = dtype
        for k, want in (("sandwich_norm", True), ("norm_topk_prob", True),
                        ("hidden_act", "silu"), ("attention_bias", False),
                        ("tie_word_embeddings", False),
                        ("rope_scaling", None), ("n_shared_experts", 1),
                        ("scoring_func", "sigmoid"),
                        ("topk_method", "noaux_tc"), ("n_group", 1),
                        ("topk_group", 1)):
            if hf.get(k, want) != want:
                raise ValueError(f"pangu_ultra_moe: {k}={hf[k]!r} is not "
                                 f"implemented (only {want!r})")
        heads = int(hf.get("num_key_value_heads", kw.get(
            "num_attention_heads", cls.num_attention_heads)))
        if heads != kw.get("num_attention_heads", cls.num_attention_heads):
            raise ValueError("pangu_ultra_moe: latent attention keeps one "
                             "latent row for every head; num_key_value_heads "
                             f"{heads} is not implemented")
        return cls(**kw)

    def attention(self, layer):
        """The sizes of every layer's attention: kind ``latent``."""
        return {"kind": "latent", "heads": self.num_attention_heads,
                "q_rank": self.q_lora_rank, "kv_rank": self.kv_lora_rank,
                "nope": self.qk_nope_head_dim, "rope": self.qk_rope_head_dim,
                "v": self.v_head_dim, "theta": float(self.rope_theta)}

    def ffn(self, layer):
        if layer < self.first_k_dense_replace:
            return {"kind": "swiglu", "width": self.intermediate_size}
        return {"kind": "routed", "experts": self.n_routed_experts,
                "held": tuple(self.held_experts),
                "top_k": self.num_experts_per_tok,
                "width": self.moe_intermediate_size,
                "shared": self.n_shared_experts,
                "scale": float(self.routed_scaling_factor)}

    def description(self):
        """Per layer: ``{"attn", "ffn", "cache", "post_norms"}``."""
        return [{"attn": self.attention(i), "ffn": self.ffn(i),
                 "cache": "latent", "post_norms": True}
                for i in range(self.num_hidden_layers)]


def _layer_shapes(cfg, i):
    """``{parameter suffix: shape}`` of layer ``i``; matrices are stored
    ``(in, out)``, so a product is ``x @ W``."""
    H = cfg.hidden_size
    a, f = cfg.attention(i), cfg.ffn(i)
    hh, rq, r = a["heads"], a["q_rank"], a["kv_rank"]
    out = {
        "norm1_gamma": (H,), "post1_gamma": (H,),
        "norm2_gamma": (H,), "post2_gamma": (H,),
        "qa_weight": (H, rq), "qnorm_gamma": (rq,),
        "qb_weight": (rq, hh * (a["nope"] + a["rope"])),
        "kva_weight": (H, r + a["rope"]), "kvnorm_gamma": (r,),
        "kvb_weight": (r, hh * (a["nope"] + a["v"])),
        "o_weight": (hh * a["v"], H),
    }
    if f["kind"] == "swiglu":
        out.update({"gu_weight": (H, 2 * f["width"]),
                    "down_weight": (f["width"], H)})
    else:
        n, w = f["held"][1], f["width"]
        out.update({"router_weight": (H, f["experts"]),
                    "router_bias": (f["experts"],),
                    "egu_weight": (n, H, 2 * w),
                    "edown_weight": (n, w, H),
                    "sgu_weight": (H, 2 * w * f["shared"]),
                    "sdown_weight": (w * f["shared"], H)})
    return out


# rows kept in float32 whatever the model's dtype: norm gains and the router
# (its scores decide a discrete choice)
_F32 = ("_gamma", "router_weight", "router_bias")


def parameter_shapes(cfg):
    """``{parameter name (no prefix): (shape, dtype)}`` of the whole
    model, in declaration order."""
    H, V = cfg.hidden_size, cfg.vocab_slice[1]
    out = {"wte_weight": (V, H), "normf_gamma": (H,),
           "head_weight": (H, V)}
    for i in range(cfg.num_hidden_layers):
        for k, s in _layer_shapes(cfg, i).items():
            out[f"h{i}_{k}"] = s
    return {k: (s, "float32" if k.endswith(_F32) else cfg.dtype)
            for k, s in out.items()}


class PanguUltraMoE(HybridBlock):
    """tokens ``(B, L)`` (ids of the held vocabulary slice) -> logits
    ``(B, L, held vocabulary)``."""

    def __init__(self, config: PanguUltraMoEConfig, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        self._cfg = config
        self._names = []
        with self.name_scope():
            for name, (shape, dtype) in parameter_shapes(config).items():
                init = "ones" if name.endswith("_gamma") else \
                    "zeros" if name.endswith("_bias") else None
                setattr(self, "p_" + name, self.params.get(
                    name, shape=shape, dtype=dtype, init=init))
                self._names.append(name)

    def weights(self):
        """``{"wte", "normf", "head", "layers": [{suffix: array}]}`` of
        the parameters' current (possibly traced) values."""
        val = {n: getattr(self, "p_" + n).data()._data
               for n in self._names}
        layers = []
        for i in range(self._cfg.num_hidden_layers):
            pre = f"h{i}_"
            layers.append({n[len(pre):]: v for n, v in val.items()
                           if n.startswith(pre)})
        return {"wte": val["wte_weight"], "normf": val["normf_gamma"],
                "head": val["head_weight"], "layers": layers}

    def decode_description(self):
        """What ``serve`` builds its pools and its executables from."""
        return self._cfg.description()

    def forward(self, tokens, *args, **kwargs):
        from ..ndarray.ndarray import NDArray
        from .layered import LayeredEngine

        toks = tokens._data if isinstance(tokens, NDArray) else tokens
        eng = LayeredEngine(self, toks.shape[0], toks.shape[1],
                            toks.shape[1])
        out = eng.forward_dense(self.weights(), jnp.asarray(toks))
        return NDArray(out) if isinstance(tokens, NDArray) else out


def pangu_tiny(dtype="float32", **overrides):
    """A toy of the same shape for the CPU tests: one dense layer, then
    routed layers, every attention over every position."""
    kw = dict(
        vocab_size=96, hidden_size=32, num_hidden_layers=4,
        first_k_dense_replace=1, intermediate_size=64,
        num_attention_heads=4, q_lora_rank=16, kv_lora_rank=16,
        qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
        rope_theta=10000.0, n_routed_experts=16, num_experts_per_tok=4,
        moe_intermediate_size=16, held_experts=(0, 16), vocab_slice=(0, 96),
        max_length=128, dtype=dtype)
    kw.update(overrides)
    cfg = PanguUltraMoEConfig(**kw)
    return PanguUltraMoE(cfg), cfg
