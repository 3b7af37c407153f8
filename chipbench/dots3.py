"""What the dots3 entry, its reference and its shape functions need of the
configuration file: the model as the program builds it, the same sizes as
the plain reference reads them, and the seeded weights put into the
program's parameters."""
from chipbench import weights_dots3

# the published keys the reference reads, as the file states them
_KEYS = ("hidden_size", "intermediate_size", "rms_norm_eps",
         "first_k_dense_replace", "layer_types", "num_attention_heads",
         "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
         "qk_rope_head_dim", "v_head_dim", "rope_theta", "index_n_heads",
         "index_head_dim", "index_topk", "swa_num_attention_heads",
         "swa_q_lora_rank", "swa_kv_lora_rank", "swa_qk_nope_head_dim",
         "swa_qk_rope_head_dim", "swa_v_head_dim", "swa_rope_theta",
         "sliding_window_size", "apply_mla_qkv_lora_rescale",
         "num_experts_per_tok", "moe_intermediate_size",
         "routed_scaling_factor", "n_shared_experts", "num_hidden_layers")


def reference_config(config):
    """The sizes as ``reference_dots3`` reads them: the published keys, the
    router's full width, and what this chip holds."""
    cfg = {k: config[k] for k in _KEYS}
    held = config["held"]
    cfg["n_routed_experts"] = int(held["router_experts"])
    cfg["held_experts"] = (int(held["first_expert"]),
                           int(config["n_routed_experts"]))
    cfg["vocab_size"] = int(config["vocab_size"])
    cfg["index_norm_eps"] = float(config["assumed_values"]["index_norm_eps"])
    return cfg


def build(config):
    """``(net, Dots3Config)``: the program's model of the file."""
    from mxnet_tpu.models import dots3

    ref = reference_config(config)
    hf = dict(ref, max_position_embeddings=config["max_position_embeddings"])
    cfg = dots3.Dots3Config.from_hf(
        hf, num_hidden_layers=ref["num_hidden_layers"],
        held_experts=ref["held_experts"],
        vocab_slice=(int(config["held"]["first_vocab_id"]),
                     ref["vocab_size"]),
        max_length=int(config["server"]["max_total_len"]),
        dtype=config["dtype"])
    cfg.index_norm_eps = ref["index_norm_eps"]
    return dots3.Dots3(cfg), cfg


def shapes(model_cfg):
    from mxnet_tpu.models import dots3
    return dots3.parameter_shapes(model_cfg)


def seeded_weights(config, model_shapes, seed):
    return weights_dots3.make(model_shapes, seed, config.get("init"))


def load_seeded(net, config, model_shapes, seed):
    """Set every parameter of ``net`` from the seeded weights, leaf by leaf:
    each leaf is handed over as it is made, so the model is never on the
    device twice."""
    params = net.collect_params()
    by_suffix = {(n[len(net.prefix):] if n.startswith(net.prefix) else n): p
                 for n, p in params.items()}
    done = 0
    for name, leaf in weights_dots3.leaves(model_shapes, seed,
                                           config.get("init")):
        by_suffix[name].set_data(leaf)
        done += 1
    if done != len(by_suffix):
        raise KeyError(f"{done} seeded leaves, the model has "
                       f"{len(by_suffix)} parameters")
