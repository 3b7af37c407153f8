"""What the granite entry, its reference and its shape functions need of the
configuration file: the model as the program builds it, the published keys
as the plain reference reads them, and the seeded weights put into the
program's parameters."""
from chipbench import weights_granite

# the published keys the reference reads, as the file states them
_KEYS = ("hidden_size", "num_hidden_layers", "layer_types", "vocab_size",
         "shared_intermediate_size", "rms_norm_eps", "num_attention_heads",
         "num_key_value_heads", "attention_multiplier",
         "embedding_multiplier", "residual_multiplier", "logits_scaling",
         "mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_n_groups",
         "mamba_d_conv", "mamba_expand", "mamba_chunk_size")


def reference_config(config):
    return {k: config[k] for k in _KEYS}


def build(config):
    """``(net, GraniteHybridConfig)``: the program's model of the file."""
    from mxnet_tpu.models import granite_hybrid as gh

    cfg = gh.GraniteHybridConfig.from_hf(
        config, max_length=int(config["server"]["max_total_len"]),
        dtype=config["dtype"])
    return gh.GraniteHybrid(cfg), cfg


def shapes(model_cfg):
    from mxnet_tpu.models import granite_hybrid as gh
    return gh.parameter_shapes(model_cfg)


def seeded_weights(config, model_shapes, seed):
    return weights_granite.make(model_shapes, seed, config.get("init"))


def load_seeded(net, config, model_shapes, seed):
    """Set every parameter of ``net`` from the seeded weights, leaf by leaf:
    each leaf is handed over as it is made, so the model is never on the
    device twice."""
    params = net.collect_params()
    by_suffix = {(n[len(net.prefix):] if n.startswith(net.prefix) else n): p
                 for n, p in params.items()}
    done = 0
    for name, leaf in weights_granite.leaves(model_shapes, seed,
                                             config.get("init")):
        by_suffix[name].set_data(leaf)
        done += 1
    if done != len(by_suffix):
        raise KeyError(f"{done} seeded leaves, the model has "
                       f"{len(by_suffix)} parameters")
