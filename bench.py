"""Headline benchmark: BERT-base pretrain-style train step, tokens/sec/chip.

One process.  Prints ONE JSON line: {"metric", "value", "unit",
"vs_baseline", "platform", "device_kind", "devices"}.  A missing chip or any
exception is a non-zero exit with no JSON line; a CPU run happens only when
``JAX_PLATFORMS=cpu`` is set explicitly, at a cut-down geometry, and says
``"platform": "cpu"``.

Baseline: upstream-MXNet-era BERT-base pretrain throughput on V100 fp16 was
~10-20k tokens/sec/GPU; vs_baseline is measured against the 15k midpoint.
The model is BERT-base geometry (12 layers, 768 units, 12 heads, seq 128)
in bfloat16 with a full-vocab tied MLM head, trained by the fused SPMD step
(forward+backward+AdamW in one donated jit) on a dp mesh over every local
device.
"""
import json
import os
import sys
import time

BASELINE_TOKENS_PER_SEC = 15000.0
METRIC = "bert_base_tokens_per_sec_per_chip"
UNIT = "tokens/sec/chip"

BERT_BASE = dict(num_layers=12, units=768, num_heads=12, hidden_size=3072,
                 vocab_size=30528, seq=128, dtype="bfloat16")


def build_bert_trainer(devices=None, **geom):
    """BERT with a tied MLM head under ``SPMDTrainer`` (AdamW) on a dp mesh
    over ``devices`` (default: every local device).  ``geom`` overrides
    ``BERT_BASE``; ``chip_smoke.py`` trains the same program.  Returns
    ``(net, trainer, mesh)``."""
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel
    from mxnet_tpu.models import BERTModel, BERTConfig

    geom = {**BERT_BASE, **geom}
    cfg = BERTConfig(max_length=geom.pop("seq"), **geom)
    bert = BERTModel(cfg, use_pooler=False, use_mlm=True)

    class _MLMHeadOnly(gluon.Block):
        """Select the MLM logits as the training output."""

        def __init__(self):
            super().__init__()
            self.bert = bert

        def forward(self, tokens):
            return self.bert(tokens)[-1]

    net = _MLMHeadOnly()
    net.initialize(mx.init.Normal(0.02))
    devices = list(devices if devices is not None else jax.devices())
    mesh = parallel.make_mesh({"dp": len(devices)}, devices)
    trainer = parallel.SPMDTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "adamw",
        {"learning_rate": 1e-4}, mesh=mesh)
    return net, trainer, mesh


def repeat_batch(x, n):
    """``x`` stacked ``n`` times along a new leading steps axis for
    ``run_steps``: one host->device transfer, broadcast on the device."""
    import jax.numpy as jnp

    import mxnet_tpu as mx
    return mx.nd.from_jax(jnp.broadcast_to(jnp.asarray(x), (n,) + x.shape))


def main():
    import numpy as onp
    import jax

    import mxnet_tpu as mx

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if not on_tpu and os.environ.get("JAX_PLATFORMS") != "cpu":
        print(f"bench.py: no TPU (jax found platform {dev.platform!r}); "
              "set JAX_PLATFORMS=cpu for an explicit CPU run",
              file=sys.stderr)
        return 1
    mx.random.seed(0)

    seq, vocab = BERT_BASE["seq"], BERT_BASE["vocab_size"]
    if on_tpu:
        batch, n_steps, repeats = 64, 60, 3
        _, trainer, _ = build_bert_trainer()
    else:  # explicit CPU run: cut to a size the host finishes
        batch, n_steps, repeats = 8, 4, 1
        _, trainer, _ = build_bert_trainer(num_layers=2, dtype="float32")

    rng = onp.random.RandomState(0)
    toks = rng.randint(0, vocab, (batch, seq))
    labels = rng.randint(0, vocab, (batch, seq))
    data = mx.nd.array(toks)
    label = mx.nd.array(labels)

    # warmup (compile) + steady-state timing; every timed region ends in a
    # device->host readback of the loss.  The timed region runs N steps in
    # ONE dispatch (lax.scan inside the jit) so host dispatch stays out of
    # the device-throughput measurement.
    float(trainer.step(data, label).asnumpy().reshape(()))
    steps_data = repeat_batch(toks, n_steps)
    steps_label = repeat_batch(labels, n_steps)
    # compile the multi-step program outside the timed region
    float(trainer.run_steps(steps_data, steps_label).asnumpy().reshape(-1)[0])
    best_dt = None
    for _ in range(repeats):
        t0 = time.perf_counter()
        losses = trainer.run_steps(steps_data, steps_label)
        float(losses.asnumpy().reshape(-1)[-1])
        dt = time.perf_counter() - t0
        best_dt = dt if best_dt is None else min(best_dt, dt)

    n_dev = len(jax.devices())
    value = batch * seq * n_steps / best_dt / n_dev
    print(json.dumps({
        "metric": METRIC,
        "value": round(value, 1),
        "unit": UNIT,
        "vs_baseline": round(value / BASELINE_TOKENS_PER_SEC, 3),
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "devices": n_dev,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
