"""What the brumby entry, its reference and its shape functions need of the
configuration file: the model as the program builds it, the published keys
(and the ``assumed`` ones the equations need) as the plain reference reads
them, and the seeded weights put into the program's parameters."""
from chipbench import weights_brumby

# the published keys the reference reads, as the file states them
_KEYS = ("hidden_size", "num_hidden_layers", "vocab_size",
         "intermediate_size", "rms_norm_eps", "num_attention_heads",
         "num_key_value_heads", "head_dim", "rope_theta")


def reference_config(config):
    """The published keys, with ``retention_eps`` from the file's
    ``retention`` group (the value ``assumed`` states)."""
    out = {k: config[k] for k in _KEYS}
    out["retention_eps"] = float(config["retention"]["eps"])
    return out


def build(config):
    """``(net, BrumbyConfig)``: the program's model of the file."""
    from mxnet_tpu.models import brumby

    cfg = brumby.BrumbyConfig.from_hf(
        dict(config, retention_eps=config["retention"]["eps"]),
        max_length=int(config["server"]["max_total_len"]),
        dtype=config["dtype"])
    return brumby.Brumby(cfg), cfg


def shapes(model_cfg):
    from mxnet_tpu.models import brumby
    return brumby.parameter_shapes(model_cfg)


def seeded_weights(config, model_shapes, seed):
    return weights_brumby.make(model_shapes, seed, config.get("init"))


def load_seeded(net, config, model_shapes, seed):
    """Set every parameter of ``net`` from the seeded weights, leaf by leaf:
    each leaf is handed over as it is made, so the model is never on the
    device twice."""
    params = net.collect_params()
    by_suffix = {(n[len(net.prefix):] if n.startswith(net.prefix) else n): p
                 for n, p in params.items()}
    done = 0
    for name, leaf in weights_brumby.leaves(model_shapes, seed,
                                            config.get("init")):
        by_suffix[name].set_data(leaf)
        done += 1
    if done != len(by_suffix):
        raise KeyError(f"{done} seeded leaves, the model has "
                       f"{len(by_suffix)} parameters")
