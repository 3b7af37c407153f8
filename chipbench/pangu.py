"""What the pangu entry, its reference and its shape functions need of the
configuration file: the model as the program builds it, the same sizes as
the plain reference reads them, and the seeded weights put into the
program's parameters."""
from chipbench import weights_pangu

# the published keys the reference reads, as the file states them
_KEYS = ("hidden_size", "intermediate_size", "rms_norm_eps",
         "first_k_dense_replace", "num_attention_heads", "q_lora_rank",
         "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
         "v_head_dim", "rope_theta", "num_experts_per_tok",
         "moe_intermediate_size", "routed_scaling_factor",
         "n_shared_experts", "norm_topk_prob", "num_hidden_layers")


def reference_config(config):
    """The sizes as ``reference_pangu`` reads them: the published keys, the
    router's full width, and what this chip holds."""
    cfg = {k: config[k] for k in _KEYS}
    held = config["held"]
    cfg["n_routed_experts"] = int(held["router_experts"])
    cfg["held_experts"] = (int(held["first_expert"]),
                           int(config["n_routed_experts"]))
    cfg["vocab_size"] = int(config["vocab_size"])
    return cfg


def build(config):
    """``(net, PanguUltraMoEConfig)``: the program's model of the file."""
    from mxnet_tpu.models import pangu_moe

    ref = reference_config(config)
    hf = dict(config, n_routed_experts=ref["n_routed_experts"])
    cfg = pangu_moe.PanguUltraMoEConfig.from_hf(
        hf, num_hidden_layers=ref["num_hidden_layers"],
        held_experts=ref["held_experts"],
        vocab_slice=(int(config["held"]["first_vocab_id"]),
                     ref["vocab_size"]),
        max_length=int(config["server"]["max_total_len"]),
        dtype=config["dtype"])
    return pangu_moe.PanguUltraMoE(cfg), cfg


def shapes(model_cfg):
    from mxnet_tpu.models import pangu_moe
    return pangu_moe.parameter_shapes(model_cfg)


def seeded_weights(config, model_shapes, seed):
    return weights_pangu.make(model_shapes, seed, config.get("init"))


def load_seeded(net, config, model_shapes, seed):
    """Set every parameter of ``net`` from the seeded weights, leaf by leaf:
    each leaf is handed over as it is made, so the model is never on the
    device twice."""
    params = net.collect_params()
    by_suffix = {(n[len(net.prefix):] if n.startswith(net.prefix) else n): p
                 for n, p in params.items()}
    done = 0
    for name, leaf in weights_pangu.leaves(model_shapes, seed,
                                           config.get("init")):
        by_suffix[name].set_data(leaf)
        done += 1
    if done != len(by_suffix):
        raise KeyError(f"{done} seeded leaves, the model has "
                       f"{len(by_suffix)} parameters")
