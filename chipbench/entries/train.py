"""The training entry: a GPT-2 under ``parallel.SPMDTrainer`` on a dp = 1
mesh, one ``trainer.step(data, label)`` call a step, each with a fresh
seeded batch.

Set-up builds ONE trainer with the benchmark's seeded weights and drives it
through its first three steps with the window's own call and feed — that is
also the warm-up — then hands the same object to the window.  After step 1
the norm of every leaf's first gradient is worked out from the optimizer's
state (Adam's first moment is (1 - beta1) x gradient), after step 3 the norm
of every leaf's change since the seeded weights (the float32 master copy
where the optimizer keeps one); both are a few hundred scalars, taken
before the next step donates the state.

The window keeps two steps in flight: it reads the loss of step i - 2
before it dispatches step i, as a loop that logs its loss does, and ends in
``block_until_ready`` of the last step's loss.

``correct``: once the window has closed, the peak has been read and the
trainer is gone, the plain reference follows the same three steps from the
same weights and batches.  Compared: each step's loss, the first gradient's
norm and the three steps' change by the worst leaf (the gap between the two
norms over the reference's norm of that leaf or of the median leaf,
whichever is larger).
"""
import gc
import time

import numpy as np

from chipbench import gpt, harness, reference

FOLLOWED = 3          # steps the reference follows
IN_FLIGHT = 2


def worst_leaf_gap(prog, ref, floor_of=None, skip_below=None):
    """max over leaves of |prog - ref| / max(ref, median(ref)); leaves whose
    ``floor_of`` entry is under ``skip_below`` x its median are left out."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    keep = np.ones(ref.shape, bool)
    if skip_below is not None:
        keep = floor_of >= skip_below * np.median(floor_of)
    gap = np.abs(prog - ref) / np.maximum(ref, np.median(ref))
    return float(np.max(gap[keep]))


def compare(prog, ref):
    """The cell's numbers from the program's and the reference's readings
    (each ``{"loss": (3,), "grad": (leaves,), "probe": (leaves, PROBES),
    "delta": (leaves,)}`` with leaves in one order)."""
    loss_p, loss_r = (np.asarray(x["loss"], np.float64) for x in (prog, ref))
    grad_r = np.asarray(ref["grad"], np.float64)
    # root mean square over the probes of the gap between the program's and
    # the reference's: an estimate of the norm of the gradient's ERROR
    err = np.sqrt(np.mean(np.square(
        np.asarray(prog["probe"], np.float64)
        - np.asarray(ref["probe"], np.float64)), axis=1))
    return {
        "grad_probe_gap": float(np.max(
            err / np.maximum(grad_r, np.median(grad_r)))),
        "loss_gap": float(np.max(np.abs(loss_p - loss_r) / np.abs(loss_r))),
        "grad_norm_gap": worst_leaf_gap(prog["grad"], ref["grad"]),
        # a leaf whose gradient is nought to rounding in the reference
        # moves under Adam by round-off alone: left out by the rule on the
        # reference's gradient, not by name
        "update_norm_gap": worst_leaf_gap(prog["delta"], ref["delta"],
                                          floor_of=grad_r, skip_below=1e-3),
    }


def flatten(norms, leaves):
    """The reference's ``{kind: (NL,) or ()}`` norms in the program's leaf
    order (``leaves`` is ``gpt.leaf_names``)."""
    norms = {k: np.asarray(v) for k, v in norms.items()}
    return np.asarray([norms[kind] if layer is None else norms[kind][layer]
                       for _, kind, layer in leaves], np.float64)


def first_moment_of(state):
    """Adam's first moment in a trainer's per-leaf state: (master, (m, v))
    where the optimizer keeps a float32 master copy, else (m, v)."""
    return state[1][0] if isinstance(state[1], tuple) else state[0]


def reference_readings(ctx, geom, batches, leaves, **variant):
    """The reference (or, with ``control`` / ``half_batch``, the control or
    a planted fault) over the first ``FOLLOWED`` steps."""
    import jax.numpy as jnp
    cfg = ctx.config
    hp = tuple(sorted((k, float(cfg["optimizer"][k])) for k in
                      ("learning_rate", "beta1", "beta2", "epsilon", "wd")))
    feeds = [batches.batch(i) for i in range(FOLLOWED)]
    tokens = jnp.asarray(np.stack([d for d, _ in feeds]))
    labels = jnp.asarray(np.stack([l for _, l in feeds]))
    w0 = gpt.seeded_weights(cfg, ctx.seed)
    losses, g1, p1, delta = reference.train_steps(
        w0, tokens, labels, geom["num_heads"], hp, **variant)
    return {"loss": np.asarray(losses), "grad": flatten(g1, leaves),
            "probe": flatten(p1, leaves), "delta": flatten(delta, leaves)}


def build(ctx, geom):
    """The net with the seeded weights and its trainer, as the window
    uses them."""
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, models, parallel

    cfg = ctx.config
    net, _ = getattr(models, cfg["preset"])(dtype=cfg["dtype"], **geom)
    net.initialize(mx.init.Zero())
    gpt.load_into(net, geom, gpt.seeded_weights(cfg, ctx.seed))
    mesh = parallel.make_mesh({"dp": 1}, jax.devices()[:1])
    opt = dict(cfg["optimizer"])
    trainer = parallel.SPMDTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), opt.pop("name"), opt,
        mesh=mesh)
    return net, trainer


def program_readings(trainer, seeded, leaves, feed, beta1):
    """Drive ``trainer`` through its first ``FOLLOWED`` steps and read what
    the comparison needs.  ``seeded()`` makes the weights it started from
    (again: they are not kept over the steps, whose memory is the cell's)."""
    import jax
    import jax.numpy as jnp

    def norms(arrays):
        return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(
            a.astype(jnp.float32)))) for a in arrays])

    first_moment = jax.jit(lambda states: (
        norms([first_moment_of(s) for s in states]),
        jnp.stack([reference.leaf_probes(first_moment_of(s), kind,
                                         layer or 0)
                   for s, (_, kind, layer) in zip(states, leaves)])))

    def current(vals, states):
        return [s[0] if isinstance(s[1], tuple) else v
                for v, s in zip(vals, states)]

    change = jax.jit(lambda cur, w: norms(
        [c.astype(jnp.float32) - (w[kind] if layer is None
                                  else w[kind][layer]).astype(jnp.float32)
         for c, (_, kind, layer) in zip(cur, leaves)]))

    losses, grad, probe = [], None, None
    for i in range(FOLLOWED):
        losses.append(trainer.step(*feed(i))._data)
        if i == 0:
            by_name = {p.name: s for p, s in zip(trainer._train_params,
                                                 trainer._opt_states)}
            grad, probe = (x / (1.0 - beta1) for x in first_moment(
                [by_name[n] for n, _, _ in leaves]))
    by_name = {p.name: (v, s) for p, v, s in zip(
        trainer._train_params, trainer._train_vals, trainer._opt_states)}
    pairs = [by_name[n] for n, _, _ in leaves]
    delta = change(current([v for v, _ in pairs], [s for _, s in pairs]),
                   seeded())
    return {"loss": losses, "grad": grad, "probe": probe, "delta": delta}


def run(ctx):
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import telemetry

    cfg, geom = ctx.config, gpt.geometry(ctx.config)
    batches = ctx.generator().make(ctx.traffic, ctx.seed, geom["vocab_size"])

    def feed(i):
        d, l = batches.batch(i)
        return mx.nd.array(d, dtype="int32"), mx.nd.array(l, dtype="int32")

    net, trainer = build(ctx, geom)
    leaves = gpt.leaf_names(net, geom)
    prog = program_readings(
        trainer, lambda: gpt.seeded_weights(cfg, ctx.seed), leaves, feed,
        float(cfg["optimizer"]["beta1"]))
    prog = {k: np.asarray(jax.device_get(v), np.float64)
            for k, v in prog.items()}
    compiles_warm = len(telemetry.events("compile"))

    tracer = None
    if ctx.trace:
        tracer = harness.Tracer(float(ctx.traffic["trace_delay_s"]),
                                min(float(ctx.traffic["trace_seconds"]),
                                    ctx.seconds))
        tracer.start()
    pending, losses, stamps = [], [], []
    step = FOLLOWED
    t_open = time.perf_counter()
    setup_s = time.time() - ctx.t_start
    t_close = t_open + ctx.seconds
    while time.perf_counter() < t_close:
        if len(pending) >= IN_FLIGHT:
            losses.append(float(np.asarray(pending.pop(0))))
        pending.append(trainer.step(*feed(step))._data)
        stamps.append(time.perf_counter())
        step += 1
    jax.block_until_ready(pending[-1])
    t_end = time.perf_counter()
    losses.extend(float(np.asarray(p)) for p in pending)
    steps = step - FOLLOWED
    compiles_window = len(telemetry.events("compile")) - compiles_warm
    trace = tracer.finish() if tracer is not None else None
    memory_peak = harness.memory_peak_bytes()

    rows, seq = batches.rows, batches.seq
    bad = sum(1 for x in losses if not np.isfinite(x))
    del trainer, net, pending
    gc.collect()
    ref = reference_readings(ctx, geom, batches, leaves)
    numbers = compare(prog, ref)
    control = {}
    if ctx.control:
        control = {k: compare(reference_readings(
            ctx, geom, batches, leaves, **{k: True}), ref)
            for k in ("control", "half_batch")}
    limits = cfg["limits"]
    compared = {k: {"value": v, "limit": limits[k]}
                for k, v in numbers.items() if k in limits}
    compared["compiles_in_window"] = {"value": compiles_window, "limit": 0}
    return {
        "end_to_end": {
            "train_tok_s": steps * rows * seq / (t_end - t_open),
            "setup_s": setup_s,
        },
        "attempted": steps, "failed": bad,
        "compared": compared, "memory_peak_bytes": memory_peak,
        "trace": trace, "geometry": geom, "control": control,
        "numbers": numbers,
        "records": [{"step": FOLLOWED + i, "dispatched": t, "loss": l}
                    for i, (t, l) in enumerate(zip(stamps, losses))],
        "window": {"t_open": t_open, "t_close": t_close, "t_end": t_end},
        "counters": {"steps": steps, "rows": rows, "seq": seq,
                     # a host stall shows here before it shows in the rate
                     "longest_dispatch_gap_s": float(np.max(np.diff(
                         [t_open] + stamps))),
                     "first_losses": prog["loss"].tolist(),
                     "reference_losses": ref["loss"].tolist(),
                     "uncompared": {k: v for k, v in numbers.items()
                                    if k not in limits}},
    }
