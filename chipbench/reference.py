"""GPT-2 in plain ``jax.numpy``: forward, loss, gradients and AdamW.

The plain reference of both configurations (Radford et al. 2019, "Language
Models are Unsupervised Multitask Learners"; the layer equations of
``openai-community/gpt2-*``): pre-norm blocks, fused qkv, causal softmax
attention scaled by 1/sqrt(head size), tanh-approximated GELU, learned
positions, LayerNorm eps 1e-5, the head tied to the token embedding.
float32 throughout, every matrix product at ``highest`` precision.  It
imports nothing of ``mxnet_tpu``; its weights are ``weights.make``'s.

Departures, each noted where it is made: the AdamW step adds epsilon to
sqrt(v) BEFORE the bias correction (Kingma & Ba 2015, section 2's
"efficient" form), as the configuration states; weight decay goes to every
leaf, biases and LayerNorm rows included, as the configuration states.

``mm`` is the one matrix product every layer goes through, so that the
control (``mm_int8``: the same mathematics with weights and activations
rounded to int8, the precision below bfloat16) is the reference with one
argument changed.
"""
import functools

import jax
import jax.numpy as jnp

from . import weights as _weights

HIGHEST = jax.lax.Precision.HIGHEST


def mm_f32(x, w):
    """x (.., K) @ w (N, K)^T in float32 at ``highest``."""
    return jnp.einsum("...k,nk->...n", x, w, precision=HIGHEST)


def _int8(a, axis):
    """Round to 255 levels by the absmax along ``axis``."""
    scale = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.clip(jnp.round(a / scale), -127, 127) * scale


@jax.custom_vjp
def mm_int8(x, w):
    """The control's product: every operand of every matrix product rounded
    to int8 (per-row activations, per-output-channel weights), forward and
    backward, accumulated exactly — what a step "computed in int8" does."""
    return mm_f32(_int8(x, -1), _int8(w, -1))


def _mm_int8_fwd(x, w):
    return mm_int8(x, w), (x, w)


def _mm_int8_bwd(res, g):
    x, w = res
    gq = _int8(g, -1)
    dx = jnp.einsum("...n,nk->...k", gq, _int8(w, 0), precision=HIGHEST)
    g2, x2 = gq.reshape(-1, gq.shape[-1]), x.reshape(-1, x.shape[-1])
    dw = jnp.einsum("mn,mk->nk", _int8(g2, 0), _int8(x2, 0),
                    precision=HIGHEST)
    return dx, dw


mm_int8.defvjp(_mm_int8_fwd, _mm_int8_bwd)


def _layer_norm(x, g, b):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + 1e-5) * g + b


def _block(x, lw, heads, mm, attn_int8=False):
    B, L, U = x.shape
    D = U // heads
    h = _layer_norm(x, lw["ln1_g"], lw["ln1_b"])
    qkv = mm(h, lw["qkv_w"]) + lw["qkv_b"]
    q, k, v = (qkv[..., i * U:(i + 1) * U].reshape(B, L, heads, D)
               for i in range(3))
    if attn_int8:       # q and k by the row, as an int8 K/V cache holds them
        q, k = _int8(q, -1), _int8(k, -1)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) / D ** 0.5
    causal = jnp.tril(jnp.ones((L, L), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    if attn_int8:       # probabilities by the row, v by the row as cached
        p, v = _int8(p, -1), _int8(v, -1)
    a = jnp.einsum("bhqk,bkhd->bqhd", p, v,
                   precision=HIGHEST).reshape(B, L, U)
    x = x + mm(a, lw["proj_w"]) + lw["proj_b"]
    h = _layer_norm(x, lw["ln2_g"], lw["ln2_b"])
    h = jax.nn.gelu(mm(h, lw["fc1_w"]) + lw["fc1_b"], approximate=True)
    return x + mm(h, lw["fc2_w"]) + lw["fc2_b"]


def hidden(w, tokens, heads, mm=mm_f32, remat=False, attn_int8=False):
    """tokens (B, L) int32 -> final-LayerNorm states (B, L, U) float32.
    ``attn_int8`` (forward only: rounding has no gradient) also rounds the
    operands of the two attention products to int8."""
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    L = tokens.shape[1]
    x = w["wte"][tokens] + w["wpe"][:L]
    layers = {k: w[k] for k in _weights.LAYER_KINDS}

    def body(x, lw):
        return _block(x, lw, heads, mm, attn_int8), None

    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, layers)
    return _layer_norm(x, w["lnf_g"], w["lnf_b"])


def logits(w, tokens, heads, mm=mm_f32, remat=False, attn_int8=False):
    return mm(hidden(w, tokens, heads, mm, remat, attn_int8),
              w["wte"].astype(jnp.float32))


# --------------------------------------------------------------------------- #
# serving: how far below the reference's best a token's logit lies
# --------------------------------------------------------------------------- #

@functools.partial(jax.jit, static_argnames=("heads", "control"))
def served_gaps(w, context, nxt, heads, control=False):
    """For one request: ``context`` (T,) is prompt + served tokens (padded),
    ``nxt[t]`` the token that followed position ``t``.  Returns, per
    position, the reference's best logit minus its logit of ``nxt[t]``;
    with ``control`` also the same gap for the token the int8 control puts
    first there (it need not decode: same prompt, same context).  The
    serving control rounds the attention operands too (int8 weights and an
    int8 K/V cache, the program's own lossy modes); the training control
    leaves them, since a rounding passes no gradient."""
    z = logits(w, context[None], heads)[0]                    # (T, V)
    best = jnp.max(z, axis=-1)
    gap = best - jnp.take_along_axis(z, nxt[:, None], axis=-1)[:, 0]
    if not control:
        return gap, gap
    zq = logits(w, context[None], heads, mm_int8, attn_int8=True)[0]
    tq = jnp.argmax(zq, axis=-1)
    return gap, best - jnp.take_along_axis(z, tq[:, None], axis=-1)[:, 0]


# --------------------------------------------------------------------------- #
# training: loss, gradients, AdamW
# --------------------------------------------------------------------------- #

def loss(w, tokens, labels, heads, mm=mm_f32):
    """Mean next-token cross entropy over every position of every row."""
    z = logits(w, tokens, heads, mm, remat=True)
    lse = jax.nn.logsumexp(z, axis=-1)
    picked = jnp.take_along_axis(z, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - picked)


def adamw(w, g, m, v, t, hp):
    """One AdamW step (Loshchilov & Hutter 2019) in the "efficient" form:
    lr_t = lr sqrt(1 - b2^t) / (1 - b1^t), epsilon beside sqrt(v)."""
    b1, b2 = hp["beta1"], hp["beta2"]
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * jnp.square(g), v, g)
    lr_t = hp["learning_rate"] * jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)
    w = jax.tree.map(
        lambda w, m, v: w - lr_t * (m / (jnp.sqrt(v) + hp["epsilon"])
                                    + hp["wd"] * w), w, m, v)
    return w, m, v


def leaf_norms(tree):
    """L2 norm of every leaf as the PROGRAM cuts them: one per layer for a
    stacked kind, one for a top-level kind.  ``{kind: (NL,) or ()}``."""
    def norm(name, a):
        a = a.astype(jnp.float32)
        if name in _weights.LAYER_KINDS:
            return jnp.sqrt(jnp.sum(jnp.square(a).reshape(a.shape[0], -1),
                                    axis=1))
        return jnp.sqrt(jnp.sum(jnp.square(a)))
    return {k: norm(k, a) for k, a in tree.items()}


PROBES = 8
_KINDS = _weights.TOP_KINDS + _weights.LAYER_KINDS


def leaf_probes(a, kind, layer):
    """``PROBES`` inner products of one leaf with fixed +-1 vectors drawn
    from (kind, layer) alone.  Where the gap between two NORMS is of second
    order in a random error, the gap between two PROBES is of first order:
    for an error e it is N(0, |e|^2)."""
    key = jax.random.fold_in(jax.random.fold_in(
        jax.random.PRNGKey(0xC0FFEE), _KINDS.index(kind)), layer)
    a = a.astype(jnp.float32)

    def one(k):
        r = jax.random.rademacher(jax.random.fold_in(key, k), a.shape,
                                  jnp.float32)
        return jnp.sum(a * r)

    return jax.lax.map(one, jnp.arange(PROBES))


def tree_probes(tree):
    """``leaf_probes`` of every leaf as the program cuts them: ``{kind:
    (NL, PROBES) or (PROBES,)}``."""
    out = {}
    for kind, a in tree.items():
        if kind in _weights.LAYER_KINDS:
            out[kind] = jax.vmap(lambda x, i, kind=kind: leaf_probes(
                x, kind, i))(a, jnp.arange(a.shape[0]))
        else:
            out[kind] = leaf_probes(a, kind, 0)
    return out


@functools.partial(jax.jit, static_argnames=("heads", "hp", "control",
                                             "half_batch"))
def train_steps(w0, tokens, labels, heads, hp, control=False,
                half_batch=False):
    """Follow ``tokens.shape[0]`` steps from ``w0`` (tokens and labels are
    (steps, B, L)).  Returns each step's loss, the leaf norms and the
    probes of the first gradient, and the leaf norms of the parameters'
    change after the last step.

    ``control`` computes the forward products in int8; ``half_batch`` is
    the planted fault "half of the batch left out, the mean taken over the
    rest"."""
    hp = dict(hp)
    mm = mm_int8 if control else mm_f32
    w0 = jax.tree.map(lambda a: a.astype(jnp.float32), w0)
    zeros = jax.tree.map(jnp.zeros_like, w0)
    w, m, v = w0, zeros, zeros
    losses, g1, p1 = [], None, None
    for i in range(tokens.shape[0]):
        tk, lb = tokens[i], labels[i]
        if half_batch:
            tk, lb = tk[:tk.shape[0] // 2], lb[:lb.shape[0] // 2]
        l, g = jax.value_and_grad(loss)(w, tk, lb, heads, mm)
        if g1 is None:
            g1, p1 = leaf_norms(g), tree_probes(g)
        w, m, v = adamw(w, g, m, v, i + 1, hp)
        losses.append(l)
    delta = leaf_norms(jax.tree.map(lambda a, b: a - b, w, w0))
    return jnp.stack(losses), g1, p1, delta
