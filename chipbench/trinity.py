"""What the trinity entry, its reference and its shape functions need of the
configuration file: the model as the program builds it, the same sizes as
the plain reference reads them, and the seeded weights put into the
program's parameters."""
from chipbench import weights_trinity

# the published keys the reference reads, as the file states them
_KEYS = ("hidden_size", "intermediate_size", "rms_norm_eps",
         "num_dense_layers", "layer_types", "num_attention_heads",
         "num_key_value_heads", "head_dim", "rope_theta", "sliding_window",
         "mup_enabled", "num_experts_per_tok", "moe_intermediate_size",
         "route_scale", "route_norm", "num_shared_experts",
         "num_hidden_layers")


def reference_config(config):
    """The sizes as ``reference_trinity`` reads them: the published keys, the
    router's full width, and what this chip holds."""
    cfg = {k: config[k] for k in _KEYS}
    held = config["held"]
    cfg["num_experts"] = int(held["router_experts"])
    cfg["held_experts"] = (int(held["first_expert"]),
                           int(config["num_experts"]))
    cfg["vocab_size"] = int(config["vocab_size"])
    return cfg


def build(config):
    """``(net, TrinityConfig)``: the program's model of the file."""
    from mxnet_tpu.models import trinity

    ref = reference_config(config)
    hf = dict(config, num_experts=ref["num_experts"])
    cfg = trinity.TrinityConfig.from_hf(
        hf, num_hidden_layers=ref["num_hidden_layers"],
        held_experts=ref["held_experts"],
        vocab_slice=(int(config["held"]["first_vocab_id"]),
                     ref["vocab_size"]),
        max_length=int(config["server"]["max_total_len"]),
        dtype=config["dtype"])
    return trinity.Trinity(cfg), cfg


def shapes(model_cfg):
    from mxnet_tpu.models import trinity
    return trinity.parameter_shapes(model_cfg)


def seeded_weights(config, model_shapes, seed):
    return weights_trinity.make(model_shapes, seed, config.get("init"))


def load_seeded(net, config, model_shapes, seed):
    """Set every parameter of ``net`` from the seeded weights, leaf by leaf:
    each leaf is handed over as it is made, so the model is never on the
    device twice."""
    params = net.collect_params()
    by_suffix = {(n[len(net.prefix):] if n.startswith(net.prefix) else n): p
                 for n, p in params.items()}
    done = 0
    for name, leaf in weights_trinity.leaves(model_shapes, seed,
                                             config.get("init")):
        by_suffix[name].set_data(leaf)
        done += 1
    if done != len(by_suffix):
        raise KeyError(f"{done} seeded leaves, the model has "
                       f"{len(by_suffix)} parameters")
