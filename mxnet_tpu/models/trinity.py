"""The ``afmoe`` language model (arcee-ai/Trinity-Large-Preview,
``config.json``): decoder blocks under a SANDWICH norm (an RMSNorm before
and one after each of the two sub-blocks) whose attention is grouped-query
with a sigmoid OUTPUT GATE and whose feed-forward is ROUTED (256
sigmoid-scored experts, 4 a token, one shared expert) after
``num_dense_layers`` leading dense layers.

Two attention kinds alternate by ``layer_types``:

- ``sliding_attention``: RoPE on q and k (the halves ``(j, j + head_dim /
  2)`` of a head rotated together, ``rope_theta``, no scaling) and keys ``t
  - sliding_window < s <= t``, the query's own position counted;
- ``full_attention``: NO positional rotation at all and every key ``s <=
  t``.

Both: ``q``, ``k``, ``v`` and the gate ``g`` are projections of the normed
layer input without biases; ``q`` and ``k`` go through an RMSNorm over each
head's ``head_dim`` (one learned gain each, shared by the heads) before the
rotation; scores ``q . k / sqrt(head_dim)``; the output is ``(o *
sigmoid(g)) W_o``, the gate elementwise over all ``heads x head_dim``.  The
embedding is multiplied by ``sqrt(hidden_size)`` (``mup_enabled``), the head
is untied.

The block is built from the published keys (``TrinityConfig.from_hf``).
``held_experts = (lo, n)`` and ``vocab_slice = (lo, n)`` say what of a layer
this chip holds when experts and vocabulary are divided over chips (as
``models.dots3`` has them): routing is over all ``num_experts``, the result
is the held experts' part plus the shared expert, ids and logits are over
the slice.

The parameters of a RUN of like layers (consecutive equal entries of the
description) are ONE stacked array a kind of matrix, ``(layers of the run,
...)``: the serving engine scans a run (``models.layered.LayeredEngine``).
``decode_description()`` is what that engine consumes: per layer the
attention (kind ``gqa`` with its sizes and the optional keys ``window``,
``theta``, ``rope``, ``qk_norm``, ``gate``), the feed-forward, the cache kind
(``kv`` under the main page table | ``kv_window`` under the window table)
and ``post_norms``.  ``forward`` is the full causal pass through that
engine's dense (fresh pools) form.
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass

import jax.numpy as jnp

from ..gluon.block import HybridBlock

__all__ = ["TrinityConfig", "Trinity", "trinity_tiny", "parameter_shapes",
           "layer_runs"]


@dataclass
class TrinityConfig:
    vocab_size: int = 200192
    hidden_size: int = 3072
    num_hidden_layers: int = 60
    layer_types: tuple = ()
    num_dense_layers: int = 6
    intermediate_size: int = 12288
    rms_norm_eps: float = 1e-5
    num_attention_heads: int = 48
    num_key_value_heads: int = 8
    head_dim: int = 128
    rope_theta: float = 10000.0
    sliding_window: int = 4096
    mup_enabled: bool = True
    # routed feed-forward
    num_experts: int = 256
    num_shared_experts: int = 1
    num_experts_per_tok: int = 4
    moe_intermediate_size: int = 3072
    route_scale: float = 2.448
    # what this chip holds, and the cache horizon
    held_experts: tuple = (0, 256)
    vocab_slice: tuple = (0, 200192)
    max_length: int = 262144
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
            else (0, int(hf["num_experts"]))
        kw["vocab_slice"] = tuple(vocab_slice) if vocab_slice \
            else (0, int(hf["vocab_size"]))
        kw["max_length"] = int(max_length
                               or hf["max_position_embeddings"])
        kw["dtype"] = dtype
        for k, want in (("score_func", "sigmoid"), ("route_norm", True),
                        ("n_group", 1), ("topk_group", 1),
                        ("num_expert_groups", 1), ("num_limited_groups", 1),
                        ("rope_scaling", None), ("hidden_act", "silu"),
                        ("tie_word_embeddings", False),
                        ("attention_bias", False)):
            if hf.get(k, want) != want:
                raise ValueError(f"trinity: {k}={hf[k]!r} is not "
                                 f"implemented (only {want!r})")
        cfg = cls(**kw)
        if len(cfg.layer_types) != nl:
            raise ValueError(f"trinity: layer_types names "
                             f"{len(cfg.layer_types)} layers, "
                             f"num_hidden_layers {nl}")
        unknown = set(cfg.layer_types) - {"sliding_attention",
                                          "full_attention"}
        if unknown:
            raise ValueError(f"trinity: layer types {sorted(unknown)} are "
                             "not implemented")
        return cfg

    @property
    def embedding_multiplier(self):
        return float(self.hidden_size) ** 0.5 if self.mup_enabled else 1.0

    def attention(self, layer):
        """The sizes of layer ``layer``'s attention: kind ``gqa`` with q/k
        norms and an output gate; a sliding layer adds its window and its
        rotation."""
        a = {"kind": "gqa", "heads": self.num_attention_heads,
             "kv_heads": self.num_key_value_heads,
             "head_dim": self.head_dim,
             "scale": float(self.head_dim) ** -0.5,
             "qk_norm": True, "gate": True}
        if self.layer_types[layer] == "sliding_attention":
            a.update(window=int(self.sliding_window),
                     theta=float(self.rope_theta), rope="halves")
        return a

    def ffn(self, layer):
        if layer < self.num_dense_layers:
            return {"kind": "swiglu", "width": self.intermediate_size}
        return {"kind": "routed", "experts": self.num_experts,
                "held": tuple(self.held_experts),
                "top_k": self.num_experts_per_tok,
                "width": self.moe_intermediate_size,
                "shared": self.num_shared_experts,
                "scale": float(self.route_scale)}

    def description(self):
        """Per layer: ``{"attn", "ffn", "cache", "post_norms"}``."""
        out = []
        for i in range(self.num_hidden_layers):
            a = self.attention(i)
            out.append({"attn": a, "ffn": self.ffn(i),
                        "cache": "kv_window" if "window" in a else "kv",
                        "post_norms": True})
        return out


def layer_runs(cfg):
    """``[(first layer, layers)]``: the maximal runs of like layers (equal
    entries of the description)."""
    out, i = [], 0
    for _, grp in itertools.groupby(cfg.description()):
        n = len(list(grp))
        out.append((i, n))
        i += n
    return out


def _layer_shapes(cfg, i):
    """``{parameter suffix: shape}`` of layer ``i``; matrices are stored
    ``(in, out)``, so a product is ``x @ W``."""
    H, D = cfg.hidden_size, cfg.head_dim
    qw, kvw = cfg.num_attention_heads * D, cfg.num_key_value_heads * D
    f = cfg.ffn(i)
    out = {"norm1_gamma": (H,), "post1_gamma": (H,), "norm2_gamma": (H,),
           "post2_gamma": (H,), "q_weight": (H, qw),
           "kv_weight": (H, 2 * kvw), "gate_weight": (H, qw),
           "qnorm_gamma": (D,), "knorm_gamma": (D,), "o_weight": (qw, H)}
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


# rows kept in float32 whatever the model's dtype: norm gains and the
# router (its scores decide a discrete choice)
_F32 = ("_gamma", "router_weight", "router_bias")


def parameter_shapes(cfg):
    """``{parameter name (no prefix): (shape, dtype)}`` of the whole model,
    in declaration order: run ``r``'s layers stacked along a leading axis."""
    H, V = cfg.hidden_size, cfg.vocab_slice[1]
    out = {"wte_weight": (V, H), "normf_gamma": (H,),
           "head_weight": (H, V)}
    for r, (first, n) in enumerate(layer_runs(cfg)):
        for k, s in _layer_shapes(cfg, first).items():
            out[f"r{r}_{k}"] = (n,) + s
    return {k: (s, "float32" if k.endswith(_F32) else cfg.dtype)
            for k, s in out.items()}


class Trinity(HybridBlock):
    """tokens ``(B, L)`` (ids of the held vocabulary slice) -> logits ``(B,
    L, held vocabulary)``."""

    def __init__(self, config: TrinityConfig, prefix=None, params=None):
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
        """``{"wte", "normf", "head", "runs": [{suffix: stacked array}]}``
        of the parameters' current (possibly traced) values."""
        val = {n: getattr(self, "p_" + n).data()._data
               for n in self._names}
        runs = []
        for r in range(len(layer_runs(self._cfg))):
            pre = f"r{r}_"
            runs.append({n[len(pre):]: v for n, v in val.items()
                         if n.startswith(pre)})
        return {"wte": val["wte_weight"], "normf": val["normf_gamma"],
                "head": val["head_weight"], "runs": runs}

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


def trinity_tiny(dtype="float32", **overrides):
    """A toy of the same shape for the CPU tests: one dense layer, two whole
    periods of routed layers (three sliding, one full), a window passed in a
    few tokens."""
    kw = dict(
        vocab_size=96, hidden_size=32, num_hidden_layers=9,
        layer_types=("sliding_attention",)
        + ("sliding_attention",) * 3 + ("full_attention",)
        + ("sliding_attention",) * 3 + ("full_attention",),
        num_dense_layers=1, intermediate_size=64, num_attention_heads=4,
        num_key_value_heads=2, head_dim=8, sliding_window=9,
        num_experts=16, num_experts_per_tok=4, moe_intermediate_size=16,
        route_scale=2.448, held_experts=(0, 16), vocab_slice=(0, 96),
        max_length=128, dtype=dtype)
    kw.update(overrides)
    cfg = TrinityConfig(**kw)
    return Trinity(cfg), cfg
