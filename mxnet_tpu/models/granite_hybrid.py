"""The ``granitemoehybrid`` language model (ibm-granite/granite-4.0-h-micro,
``config.json``): pre-norm decoder blocks of two kinds by ``layer_types`` —

- ``mamba``: a Mamba-2 state-space mixer (``ops.ssd``): one projection to
  ``[z | u | dt]``, a causal depthwise convolution of width ``mamba_d_conv``
  with bias and ``silu`` over ``u``, ``[x | B | C] = u'``, ``dt =
  softplus(dt + dt_bias)``, ``A = -exp(A_log)``, the recurrence over a state
  ``(heads, head width, state width)`` a stream, ``+ D x``, a gate ``y *
  silu(z)`` BEFORE an RMSNorm over the whole inner width, and the output
  projection;
- ``attention``: grouped-query attention WITHOUT positions
  (``position_embedding_type`` ``nope``), scores scaled by
  ``attention_multiplier`` (not ``1 / sqrt(head width)``);

each followed by a SwiGLU feed-forward of ``shared_intermediate_size`` (the
dense models of the family route nothing: ``num_local_experts`` 0).  The
embedding is multiplied by ``embedding_multiplier``, every sub-block's output
by ``residual_multiplier`` before it joins the stream, the tied head's logits
are divided by ``logits_scaling``.  RMSNorm eps ``rms_norm_eps``, no biases
but the convolution's.

The parameters of a RUN of like layers (the description's consecutive equal
entries: 5, 1, 9, 1, 9, 1, 9, 1, 4 for the published pattern) are ONE
stacked array a kind of matrix, ``(layers of the run, ...)``: the serving
engine scans a run (``models.layered.LayeredEngine``), so forty layers trace
and compile as nine bodies, and the weights are resident once, as declared.

``decode_description()`` is what the serving engine consumes: per layer the
attention kind (``ssm`` | ``gqa``) with its sizes and scale, the feed-forward
kind, the cache kind (``ssm_state`` under the SLOT table | ``kv`` under the
main page table) and the residual multiplier.  ``forward`` is the full
causal pass through that engine's dense (fresh pools) form.
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass

import jax.numpy as jnp

from ..gluon.block import HybridBlock

__all__ = ["GraniteHybridConfig", "GraniteHybrid", "granite_hybrid_tiny",
           "parameter_shapes", "layer_runs"]


@dataclass
class GraniteHybridConfig:
    vocab_size: int = 100352
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    layer_types: tuple = ()
    shared_intermediate_size: int = 8192
    rms_norm_eps: float = 1e-5
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    attention_multiplier: float = 0.015625
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    logits_scaling: float = 8.0
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_chunk_size: int = 256
    max_length: int = 131072
    dtype: str = "float32"

    @classmethod
    def from_hf(cls, hf, max_length=None, dtype="float32"):
        """From the published ``config.json`` keys."""
        names = {f for f in cls.__dataclass_fields__}
        kw = {k: v for k, v in hf.items() if k in names}
        kw["layer_types"] = tuple(hf["layer_types"])
        kw["max_length"] = int(max_length or hf["max_position_embeddings"])
        kw["dtype"] = dtype
        for k, want in (("position_embedding_type", "nope"),
                        ("hidden_act", "silu"), ("mamba_n_groups", 1),
                        ("num_local_experts", 0), ("attention_bias", False),
                        ("mamba_proj_bias", False),
                        ("mamba_conv_bias", True),
                        ("tie_word_embeddings", True),
                        ("normalization_function", "rmsnorm")):
            if hf.get(k, want) != want:
                raise ValueError(f"granite_hybrid: {k}={hf[k]!r} is not "
                                 f"implemented (only {want!r})")
        cfg = cls(**kw)
        if len(cfg.layer_types) != cfg.num_hidden_layers:
            raise ValueError("granite_hybrid: layer_types names "
                             f"{len(cfg.layer_types)} layers, "
                             f"num_hidden_layers {cfg.num_hidden_layers}")
        if cfg.mamba_expand * cfg.hidden_size \
                != cfg.mamba_n_heads * cfg.mamba_d_head:
            raise ValueError("granite_hybrid: mamba_expand x hidden_size "
                             "is not mamba_n_heads x mamba_d_head")
        return cfg

    @property
    def inner(self):
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_width(self):
        return self.inner + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    def attention(self, layer):
        """The sizes of layer ``layer``'s mixer, by kind."""
        if self.layer_types[layer] == "mamba":
            return {"kind": "ssm", "heads": self.mamba_n_heads,
                    "head_dim": self.mamba_d_head,
                    "state": self.mamba_d_state, "conv": self.mamba_d_conv,
                    "chunk": self.mamba_chunk_size}
        return {"kind": "gqa", "heads": self.num_attention_heads,
                "kv_heads": self.num_key_value_heads,
                "head_dim": self.head_dim,
                "scale": float(self.attention_multiplier)}


def layer_runs(cfg):
    """``[(kind, first layer, layers)]``: the maximal runs of like layers."""
    out, i = [], 0
    for kind, grp in itertools.groupby(cfg.layer_types):
        n = len(list(grp))
        out.append(("ssm" if kind == "mamba" else "gqa", i, n))
        i += n
    return out


def _run_shapes(cfg, kind):
    """``{parameter suffix: shape of ONE layer}`` of a run of ``kind``;
    matrices are stored ``(in, out)``, so a product is ``x @ W``."""
    H, F = cfg.hidden_size, cfg.shared_intermediate_size
    out = {"norm1_gamma": (H,), "norm2_gamma": (H,),
           "gu_weight": (H, 2 * F), "down_weight": (F, H)}
    if kind == "ssm":
        inner, W, nh = cfg.inner, cfg.conv_width, cfg.mamba_n_heads
        out.update({"in_weight": (H, inner + W + nh),
                    "conv_weight": (cfg.mamba_d_conv, W),
                    "conv_bias": (W,), "dt_bias": (nh,), "a_log": (nh,),
                    "d_skip": (nh,), "gnorm_gamma": (inner,),
                    "out_weight": (inner, H)})
    else:
        kvw = cfg.num_key_value_heads * cfg.head_dim
        out.update({"q_weight": (H, H), "kv_weight": (H, 2 * kvw),
                    "o_weight": (H, H)})
    return out


# rows kept in float32 whatever the model's dtype: norm gains and the
# three per-head rows of the recurrence (a step, a decay rate, a skip)
_F32 = ("_gamma", "dt_bias", "a_log", "d_skip")


def parameter_shapes(cfg):
    """``{parameter name (no prefix): (shape, dtype)}`` of the whole model,
    in declaration order: run ``r``'s layers stacked along a leading axis."""
    out = {"wte_weight": (cfg.vocab_size, cfg.hidden_size),
           "normf_gamma": (cfg.hidden_size,)}
    for r, (kind, _, n) in enumerate(layer_runs(cfg)):
        for k, s in _run_shapes(cfg, kind).items():
            out[f"r{r}_{k}"] = (n,) + s
    return {k: (s, "float32" if k.endswith(_F32) else cfg.dtype)
            for k, s in out.items()}


class GraniteHybrid(HybridBlock):
    """tokens ``(B, L)`` -> logits ``(B, L, vocabulary)``."""

    def __init__(self, config: GraniteHybridConfig, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        self._cfg = config
        self._names = []
        with self.name_scope():
            for name, (shape, dtype) in parameter_shapes(config).items():
                init = "ones" if name.endswith(("_gamma", "d_skip")) else \
                    "zeros" if name.endswith(("_bias", "a_log")) else None
                setattr(self, "p_" + name, self.params.get(
                    name, shape=shape, dtype=dtype, init=init))
                self._names.append(name)

    def weights(self):
        """``{"wte", "normf", "runs": [{suffix: stacked array}]}`` of the
        parameters' current (possibly traced) values; the head is ``wte``
        (tied)."""
        val = {n: getattr(self, "p_" + n).data()._data
               for n in self._names}
        runs = []
        for r in range(len(layer_runs(self._cfg))):
            pre = f"r{r}_"
            runs.append({n[len(pre):]: v for n, v in val.items()
                         if n.startswith(pre)})
        return {"wte": val["wte_weight"], "normf": val["normf_gamma"],
                "runs": runs}

    def decode_description(self):
        """Per layer: ``{"attn": {...kind and sizes}, "ffn": {...},
        "cache": kind, "residual": multiplier}``."""
        c = self._cfg
        cache = {"ssm": "ssm_state", "gqa": "kv"}
        return [{"attn": c.attention(i),
                 "ffn": {"kind": "swiglu",
                         "width": c.shared_intermediate_size},
                 "cache": cache[c.attention(i)["kind"]],
                 "residual": float(c.residual_multiplier)}
                for i in range(c.num_hidden_layers)]

    def forward(self, tokens, *args, **kwargs):
        from ..ndarray.ndarray import NDArray
        from .layered import LayeredEngine

        toks = tokens._data if isinstance(tokens, NDArray) else tokens
        eng = LayeredEngine(self, toks.shape[0], toks.shape[1],
                            toks.shape[1])
        out = eng.forward_dense(self.weights(), jnp.asarray(toks))
        return NDArray(out) if isinstance(tokens, NDArray) else out


def granite_hybrid_tiny(dtype="float32", **overrides):
    """A toy of the same shape for the CPU tests: both kinds of layer in
    runs of unequal length, a chunk short enough to be passed in a few
    dozen tokens."""
    kw = dict(
        vocab_size=96, hidden_size=32, num_hidden_layers=6,
        layer_types=("mamba", "mamba", "attention", "mamba", "mamba",
                     "mamba"),
        shared_intermediate_size=48, num_attention_heads=4,
        num_key_value_heads=2, attention_multiplier=0.2,
        embedding_multiplier=3.0, residual_multiplier=0.5,
        logits_scaling=2.0, mamba_n_heads=4, mamba_d_head=16,
        mamba_d_state=8, mamba_d_conv=4, mamba_expand=2,
        mamba_chunk_size=8, max_length=128, dtype=dtype)
    kw.update(overrides)
    cfg = GraniteHybridConfig(**kw)
    return GraniteHybrid(cfg), cfg
