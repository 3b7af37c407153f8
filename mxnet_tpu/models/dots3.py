"""The ``dots3_note`` language model (dots-studio/dots3-note-prev,
``config.json``): pre-norm decoder blocks whose attention is LATENT (queries
and keys/values go through low-rank bottlenecks; the cache row is the
normed latent plus one shared rotary key) and whose feed-forward is ROUTED
(256 sigmoid-scored experts, 8 a token, one shared expert) after a leading
dense layer.

Two attention kinds alternate by ``layer_types``:

- ``full_attention``: a learned INDEXER scores every earlier position
  (``I[t, s] = sum_j w[t, j] relu(q_j[t] . k[s])`` over 64 small heads) and
  attention runs over the ``index_topk`` positions of largest score only;
- ``sliding_attention``: the same latent attention at the ``swa_*`` sizes
  over the last ``sliding_window_size`` positions, the query's own
  included, with no indexer.

Both end in a head-wise sigmoid gate computed from the normed layer input.
The vision and audio towers and the MTP module are not part of this file.

The block is built from the published keys (``Dots3Config.from_hf``).
``held_experts = (lo, n)`` and ``vocab_slice = (lo, n)`` say what of a
layer this chip holds when experts and vocabulary are divided over chips:
routing is over all ``n_routed_experts``, the result is the held experts'
part plus the shared expert, ids and logits are over the slice.

``decode_description()`` is what the serving engine consumes
(``models.layered.LayeredEngine``): per layer the attention kind, the
feed-forward kind, the cache kind, and their sizes.  ``forward`` is the
full causal pass through that engine's dense (no cache) form.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp

from ..gluon.block import HybridBlock

__all__ = ["Dots3Config", "Dots3", "dots3_tiny"]


@dataclass
class Dots3Config:
    vocab_size: int = 152064
    hidden_size: int = 5120
    num_hidden_layers: int = 46
    layer_types: tuple = ()
    first_k_dense_replace: int = 1
    intermediate_size: int = 13824
    rms_norm_eps: float = 1e-5
    # full-attention layers
    num_attention_heads: int = 128
    q_lora_rank: int = 1024
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 8e7
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    # sliding layers
    swa_num_attention_heads: int = 64
    swa_q_lora_rank: int = 1024
    swa_kv_lora_rank: int = 1024
    swa_qk_nope_head_dim: int = 192
    swa_qk_rope_head_dim: int = 64
    swa_v_head_dim: int = 128
    swa_rope_theta: float = 5e4
    sliding_window_size: int = 513
    apply_mla_qkv_lora_rescale: bool = True
    # routed feed-forward
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 1536
    routed_scaling_factor: float = 1.0
    # what this chip holds, and the cache horizon
    held_experts: tuple = (0, 256)
    vocab_slice: tuple = (0, 152064)
    max_length: int = 524288
    index_norm_eps: float = 1e-6
    dtype: str = "float32"

    @classmethod
    def from_hf(cls, hf, num_hidden_layers=None, held_experts=None,
                vocab_slice=None, max_length=None, dtype="float32"):
        """From the published ``config.json`` keys; the four arguments are
        the cuts a deployment makes (depth, experts held, vocabulary held,
        cache horizon)."""
        names = {f for f in cls.__dataclass_fields__}
        kw = {k: v for k, v in hf.items() if k in names}
        nl = int(num_hidden_layers or hf["num_hidden_layers"])
        kw["num_hidden_layers"] = nl
        kw["layer_types"] = tuple(hf["layer_types"][:nl])
        kw["held_experts"] = tuple(held_experts) if held_experts \
            else (0, int(hf["n_routed_experts"]))
        kw["vocab_slice"] = tuple(vocab_slice) if vocab_slice \
            else (0, int(hf["vocab_size"]))
        kw["max_length"] = int(max_length
                               or hf["max_position_embeddings"])
        kw["dtype"] = dtype
        for k, want in (("scoring_func", "sigmoid"),
                        ("topk_method", "noaux_tc"),
                        ("norm_topk_prob", True),
                        ("attention_gate_type", "headwise"),
                        ("hidden_act", "silu")):
            if hf.get(k, want) != want:
                raise ValueError(f"dots3: {k}={hf[k]!r} is not "
                                 f"implemented (only {want!r})")
        return cls(**kw)

    def attention(self, layer):
        """The sizes of layer ``layer``'s attention, by kind."""
        if self.layer_types[layer] == "full_attention":
            return {"kind": "latent_sparse",
                    "heads": self.num_attention_heads,
                    "q_rank": self.q_lora_rank,
                    "kv_rank": self.kv_lora_rank,
                    "nope": self.qk_nope_head_dim,
                    "rope": self.qk_rope_head_dim,
                    "v": self.v_head_dim, "theta": float(self.rope_theta),
                    "index_heads": self.index_n_heads,
                    "index_dim": self.index_head_dim,
                    "topk": self.index_topk, "gate": True}
        return {"kind": "latent_window",
                "heads": self.swa_num_attention_heads,
                "q_rank": self.swa_q_lora_rank,
                "kv_rank": self.swa_kv_lora_rank,
                "nope": self.swa_qk_nope_head_dim,
                "rope": self.swa_qk_rope_head_dim,
                "v": self.swa_v_head_dim,
                "theta": float(self.swa_rope_theta),
                "window": self.sliding_window_size, "gate": True}

    def ffn(self, layer):
        if layer < self.first_k_dense_replace:
            return {"kind": "swiglu", "width": self.intermediate_size}
        return {"kind": "routed", "experts": self.n_routed_experts,
                "held": tuple(self.held_experts),
                "top_k": self.num_experts_per_tok,
                "width": self.moe_intermediate_size,
                "shared": self.n_shared_experts,
                "scale": float(self.routed_scaling_factor)}


def _layer_shapes(cfg, i):
    """``{parameter suffix: shape}`` of layer ``i``; matrices are stored
    ``(in, out)``, so a product is ``x @ W``."""
    H = cfg.hidden_size
    a, f = cfg.attention(i), cfg.ffn(i)
    hh, rq, r = a["heads"], a["q_rank"], a["kv_rank"]
    out = {
        "norm1_gamma": (H,), "norm2_gamma": (H,),
        "qa_weight": (H, rq), "qnorm_gamma": (rq,),
        "qb_weight": (rq, hh * (a["nope"] + a["rope"])),
        "kva_weight": (H, r + a["rope"]), "kvnorm_gamma": (r,),
        "kvb_weight": (r, hh * (a["nope"] + a["v"])),
        "o_weight": (hh * a["v"], H), "gate_weight": (H, hh),
    }
    if a["kind"] == "latent_sparse":
        out.update({
            "iq_weight": (rq, a["index_heads"] * a["index_dim"]),
            "ik_weight": (H, a["index_dim"]),
            "iknorm_gamma": (a["index_dim"],),
            "iknorm_beta": (a["index_dim"],),
            "iw_weight": (H, a["index_heads"])})
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


# rows kept in float32 whatever the model's dtype: norm gains, the
# LayerNorm of the index key, and the router (its scores decide a
# discrete choice)
_F32 = ("_gamma", "_beta", "router_weight", "router_bias")


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


class Dots3(HybridBlock):
    """tokens ``(B, L)`` (ids of the held vocabulary slice) -> logits
    ``(B, L, held vocabulary)``."""

    def __init__(self, config: Dots3Config, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._cfg = config
        self._names = []
        with self.name_scope():
            for name, (shape, dtype) in parameter_shapes(config).items():
                init = "ones" if name.endswith("_gamma") else \
                    "zeros" if name.endswith(("_beta", "_bias")) else None
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
        """Per layer: ``{"attn": {...kind and sizes}, "ffn": {...},
        "cache": kind}`` — what ``serve`` builds its pools and its
        executables from."""
        c = self._cfg
        cache = {"latent_sparse": "latent_index",
                 "latent_window": "latent_window"}
        return [{"attn": c.attention(i), "ffn": c.ffn(i),
                 "cache": cache[c.attention(i)["kind"]]}
                for i in range(c.num_hidden_layers)]

    def forward(self, tokens, *args, **kwargs):
        from ..ndarray.ndarray import NDArray
        from .layered import LayeredEngine

        toks = tokens._data if isinstance(tokens, NDArray) else tokens
        eng = LayeredEngine(self, toks.shape[0], toks.shape[1],
                            toks.shape[1])
        out = eng.forward_dense(self.weights(), jnp.asarray(toks))
        return NDArray(out) if isinstance(tokens, NDArray) else out


def dots3_tiny(dtype="float32", **overrides):
    """A toy of the same shape for the CPU tests: every kind of layer,
    a window and an ``index_topk`` short enough to be passed in a few
    dozen tokens."""
    kw = dict(
        vocab_size=96, hidden_size=32, num_hidden_layers=5,
        layer_types=("full_attention", "full_attention",
                     "sliding_attention", "sliding_attention",
                     "sliding_attention"),
        intermediate_size=64, num_attention_heads=4, q_lora_rank=16,
        kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
        v_head_dim=8, index_n_heads=4, index_head_dim=8, index_topk=8,
        swa_num_attention_heads=2, swa_q_lora_rank=16,
        swa_kv_lora_rank=24, swa_qk_nope_head_dim=12,
        swa_qk_rope_head_dim=4, swa_v_head_dim=8, sliding_window_size=9,
        n_routed_experts=16, num_experts_per_tok=4,
        moe_intermediate_size=16, held_experts=(0, 16),
        vocab_slice=(0, 96), max_length=128, dtype=dtype)
    kw.update(overrides)
    cfg = Dots3Config(**kw)
    return Dots3(cfg), cfg
