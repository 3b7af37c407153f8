"""What both entries need of a GPT-2 configuration file: its geometry in
the names ``shapes.py`` and ``weights.py`` use, and the seeded weights put
into the program's parameters."""
import jax.numpy as jnp

from chipbench import weights

# the program's parameter-name suffix of each per-layer kind
_LAYER_SUFFIX = {
    "ln1_g": "ln1_gamma", "ln1_b": "ln1_beta",
    "qkv_w": "attn_qkv_weight", "qkv_b": "attn_qkv_bias",
    "proj_w": "attn_out_weight", "proj_b": "attn_out_bias",
    "ln2_g": "ln2_gamma", "ln2_b": "ln2_beta",
    "fc1_w": "ffn_fc1_weight", "fc1_b": "ffn_fc1_bias",
    "fc2_w": "ffn_fc2_weight", "fc2_b": "ffn_fc2_bias",
}
_TOP_SUFFIX = {"wte": "wte_weight", "wpe": "wpe_weight",
               "lnf_g": "lnf_gamma", "lnf_b": "lnf_beta"}


def geometry(config):
    """The published keys of a GPT-2 ``config.json`` under the names the
    benchmark's shape functions use."""
    return {"num_layers": int(config["n_layer"]),
            "units": int(config["n_embd"]),
            "num_heads": int(config["n_head"]),
            "hidden_size": int(config["n_inner"]),
            "vocab_size": int(config["vocab_size"]),
            "max_length": int(config["n_positions"])}


def seeded_weights(config, seed):
    """The configuration's weights from ``seed`` (``weights.make`` with the
    file's geometry, dtype and ``init`` group)."""
    return weights.make(geometry(config), seed, config["dtype"],
                        config.get("init"))


def leaf_names(net, geom):
    """``[(parameter name, kind, layer or None)]`` for every parameter of
    ``net``, in the order ``collect_params`` lists them."""
    by_suffix = {v: (k, None) for k, v in _TOP_SUFFIX.items()}
    for i in range(geom["num_layers"]):
        for k, v in _LAYER_SUFFIX.items():
            by_suffix[f"h{i}_{v}"] = (k, i)
    out = []
    for name in net.collect_params().keys():
        suffix = name[len(net.prefix):] if name.startswith(net.prefix) \
            else name
        if suffix not in by_suffix:
            raise KeyError(f"parameter {name} has no seeded weight")
        out.append((name,) + by_suffix[suffix])
    if len(out) != len(by_suffix):
        raise KeyError(f"{len(by_suffix)} seeded leaves, the model has "
                       f"{len(out)} parameters")
    return out


def load_into(net, geom, w):
    """Set every parameter of ``net`` from the stacked weights ``w``.
    Every parameter gets a buffer of its own (a trainer donates them), so
    ``w`` stays whole."""
    params = net.collect_params()
    for name, kind, layer in leaf_names(net, geom):
        value = w[kind] if layer is None else w[kind][layer]
        p = params[name]
        p.set_data(jnp.array(value, jnp.dtype(p.dtype), copy=True))
