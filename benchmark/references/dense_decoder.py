"""Plain reference of a dense decoder-only transformer and of AdamW.

Written from the published description (Mistral-7B-v0.1's modeling code:
pre-norm blocks, RMSNorm with a float32 variance, rotary embeddings on
the two halves of a head, grouped-query causal attention scaled by
head_dim**-0.5, SwiGLU, an untied output head, mean next-token cross
entropy) and from Loshchilov & Hutter's AdamW behind a clip of the global
gradient norm. float32 throughout, every product at
``lax.Precision.HIGHEST`` (a TPU's default float32 product is bfloat16
passes). No kernel, no scan over layers, no rematerialisation policy, no
optimizer library; it imports nothing of ray_tpu and is given nothing
ray_tpu made: weights and batches come from the benchmark's own seeded
makers.

It is laid out for memory, not speed, so that it fits one 16 GB chip
beside float32 weights and gradients of a 1.1 G-parameter model: one
batch row and one layer at a time (each layer's input kept for its
backward, 1 GB for 16384 tokens through 4 layers), attention one
key/value group at a time, the output head 512 rows at a time, each
recomputed in its backward; the first step's gradient waits on the host
while the second is computed. Every piece runs where its weights are: a
caller with several chips puts blocks of layers on each, and the rows then
follow one another through them. Departures from the description: none in the mathematics;
``sliding_window`` is not applied, which is exact while the sequence is
no longer than the window (the caller checks).

An operand rule is applied to both operands of a matrix product. The
identity gives the reference; ``int8_operands`` on the projections gives
the control, the precision below bfloat16 that a v5e's int8 peak would
tempt a later PR to take (``OPERANDS``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST
LAYER_LEAVES = ("attn_norm", "wq", "wk", "wv", "wo", "mlp_norm", "w_gate",
                "w_up", "w_down")
TOP_LEAVES = ("embed", "final_norm", "unembed")
HEAD_ROWS = 512


def identity(x):
    return x


def int8_operands(x):
    """Symmetric int8 with one scale for the tensor, as a quantised
    product sees its operand; the gradient passes straight through."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127) * scale
    return x + lax.stop_gradient(q - x)


# ``int8_linear`` rounds the projections' operands alone, as a quantised
# matmul library would: the mildest step down. Rounding the products
# inside attention too wipes out most probabilities at 4096 keys (one
# under 1/254 becomes 0) and reads far above any limit.
OPERANDS = {"float32": identity, "int8_linear": int8_operands}


class Dims:
    def __init__(self, cfg: dict):
        self.hidden = cfg["hidden_size"]
        self.heads = cfg["num_attention_heads"]
        self.kv_heads = cfg["num_key_value_heads"]
        self.head_dim = cfg.get("head_dim") or self.hidden // self.heads
        self.group = self.heads // self.kv_heads
        self.layers = cfg["num_hidden_layers"]
        self.vocab = cfg["vocab_size"]
        self.eps = cfg["rms_norm_eps"]
        self.theta = cfg["rope_theta"]


def rms_norm(x, weight, eps):
    variance = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * lax.rsqrt(variance + eps) * weight


def rotary(x, theta):
    """x [seq, heads, head_dim]: rotate the pair (i, i + head_dim/2) by
    position * theta**(-2i/head_dim)."""
    seq, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def layer_row(x, w, dims: Dims, operand):
    """One block on one sequence: x [seq, hidden] -> [seq, hidden].
    ``operand`` rounds the operands of the projections; the two products
    inside attention keep theirs."""
    seq = x.shape[0]
    d, kv, g = dims.head_dim, dims.kv_heads, dims.group

    def mm(a, b):
        return jnp.matmul(operand(a), operand(b), precision=HIGHEST)

    xn = rms_norm(x, w["attn_norm"], dims.eps)
    q = rotary(mm(xn, w["wq"]).reshape(seq, kv * g, d), dims.theta)
    k = rotary(mm(xn, w["wk"]).reshape(seq, kv, d), dims.theta)
    v = mm(xn, w["wv"]).reshape(seq, kv, d)
    causal = jnp.tril(jnp.ones((seq, seq), bool))

    @jax.checkpoint
    def one_group(qkv):
        qg, kg, vg = qkv  # [seq, g, d], [seq, d], [seq, d]
        scores = jnp.einsum("sgd,td->gst", qg, kg,
                            precision=HIGHEST) * d ** -0.5
        p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return jnp.einsum("gst,td->sgd", p, vg, precision=HIGHEST)

    # query head h reads key/value head h // group
    out = lax.map(one_group, (
        q.reshape(seq, kv, g, d).transpose(1, 0, 2, 3),
        k.transpose(1, 0, 2), v.transpose(1, 0, 2)))     # [kv, seq, g, d]
    x = x + mm(out.transpose(1, 0, 2, 3).reshape(seq, kv * g * d), w["wo"])
    xn = rms_norm(x, w["mlp_norm"], dims.eps)
    hidden = jax.nn.silu(mm(xn, w["w_gate"])) * mm(xn, w["w_up"])
    return x + mm(hidden, w["w_down"])


def head_row(x, final_norm, unembed, targets, dims: Dims, operand):
    """Summed next-token negative log likelihood of one sequence."""
    seq = x.shape[0]
    rows = HEAD_ROWS if seq % HEAD_ROWS == 0 else seq

    @jax.checkpoint
    def block(xt):
        xb, tb = xt
        logits = jnp.matmul(operand(rms_norm(xb, final_norm, dims.eps)),
                            operand(unembed), precision=HIGHEST)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, tb[:, None], axis=-1).sum()

    return lax.map(block, (x.reshape(seq // rows, rows, -1),
                           targets.reshape(seq // rows, rows))).sum()


class Model:
    """The jitted pieces for one configuration and one operand rule."""

    def __init__(self, cfg: dict, operand=identity):
        dims = self.dims = Dims(cfg)
        layer = functools.partial(layer_row, dims=dims, operand=operand)
        head = functools.partial(head_row, dims=dims, operand=operand)
        self.layer_fwd = jax.jit(layer)

        def layer_bwd(x, w, dy, acc):
            _, vjp = jax.vjp(layer, x, w)
            dx, dw = vjp(dy)
            return dx, jax.tree.map(jnp.add, acc, dw)

        self.layer_bwd = jax.jit(layer_bwd, donate_argnums=(3,))

        def head_bwd(x, final_norm, unembed, targets, scale, acc):
            nll, vjp = jax.vjp(
                lambda x, n, u: head(x, n, u, targets), x, final_norm,
                unembed)
            dx, dn, du = vjp(scale)
            return nll, dx, (acc[0] + dn, acc[1] + du)

        self.head_bwd = jax.jit(head_bwd, donate_argnums=(5,))
        self.embed_bwd = jax.jit(
            lambda acc, ids, dx: acc.at[ids].add(dx), donate_argnums=(0,))

    def loss_and_grads(self, weights, tokens):
        """Mean cross entropy of ``tokens`` [rows, seq + 1] and its
        gradient, in the layout of ``weights`` (layers as a list). One
        row at a time through all the layers, then one row at a time back;
        each piece runs on the device that holds its layer, so rows follow
        one another through the devices like micro-batches through
        pipeline stages."""
        tokens = np.asarray(tokens)
        n_rows, seq = tokens.shape[0], tokens.shape[1] - 1
        scale = jnp.float32(1.0 / (n_rows * seq))
        layers = weights["layers"]
        grads = {"embed": jnp.zeros_like(weights["embed"]),
                 "layers": [jax.tree.map(jnp.zeros_like, w) for w in layers]}
        head_acc = (jnp.zeros_like(weights["final_norm"]),
                    jnp.zeros_like(weights["unembed"]))
        # every row's forward first, then every row's backward: a device
        # runs what it is sent in order, so with the layers on several
        # devices the rows follow one another through them both ways
        forward = []
        for r in range(n_rows):
            ids = _put_like(jnp.asarray(tokens[r, :-1]), weights["embed"])
            x, inputs = jnp.take(weights["embed"], ids, axis=0), []
            for w in layers:
                x = _put_like(x, w["wq"])
                inputs.append(x)
                x = self.layer_fwd(x, w)
            forward.append((ids, inputs, x))
        nlls = []
        for r in range(n_rows):
            ids, inputs, x = forward[r]
            forward[r] = None
            targets = _put_like(jnp.asarray(tokens[r, 1:]), weights["unembed"])
            row_nll, dx, head_acc = self.head_bwd(
                _put_like(x, weights["unembed"]), weights["final_norm"],
                weights["unembed"], targets, scale, head_acc)
            nlls.append(row_nll)
            for l in reversed(range(len(layers))):
                dx, grads["layers"][l] = self.layer_bwd(
                    inputs[l], layers[l], _put_like(dx, layers[l]["wq"]),
                    grads["layers"][l])
            grads["embed"] = self.embed_bwd(
                grads["embed"], ids, _put_like(dx, weights["embed"]))
        grads["final_norm"], grads["unembed"] = head_acc
        return float(sum(float(n) for n in nlls) * float(scale)), grads


def _put_like(x, like):
    """``x`` on the device that holds ``like`` (no copy if it is there)."""
    device, = like.devices()
    return x if x.devices() == {device} else jax.device_put(x, device)


def leaves(tree):
    """(name, layer or None, array) of every leaf, in a fixed order."""
    for name in TOP_LEAVES:
        yield name, None, tree[name]
    for l, layer in enumerate(tree["layers"]):
        for name in LAYER_LEAVES:
            yield "layers/" + name, l, layer[name]


def set_leaf(tree, name, layer, value):
    if layer is None:
        tree[name] = value
    else:
        tree["layers"][layer][name.split("/")[1]] = value


_sumsq = jax.jit(lambda x: jnp.sum(jnp.square(x.astype(jnp.float32))))


@functools.partial(jax.jit, static_argnames=("step", "b1", "b2", "eps",
                                             "lr", "wd"),
                   donate_argnums=(0,))
def adamw_leaf(p, g, m, v, clip_scale, *, step, b1, b2, eps, lr, wd):
    g = g * clip_scale
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    update = (m / (1 - b1 ** step)) / (jnp.sqrt(v / (1 - b2 ** step)) + eps)
    return p - lr * (update + wd * p), m, v


def norms_by_leaf(named_sumsq):
    """{leaf name: norms, one per layer} from (name, layer, sum of squares)."""
    out = {}
    for name, _layer, s in named_sumsq:
        out.setdefault(name, []).append(float(np.sqrt(float(s))))
    return {k: np.array(v) for k, v in out.items()}


def follow_two_steps(cfg: dict, hp: dict, initial_leaf, batches,
                     operand=identity):
    """Two AdamW steps on ``batches[0]`` and ``batches[1]``. Returns each
    step's loss and raw global gradient norm, the norm of the first raw
    gradient by leaf, and the norm of the parameters' change over the two
    steps by leaf.

    ``initial_leaf(name, layer)`` makes one float32 leaf of the starting
    point (``layer`` is None for the embedding, the final norm and the
    output head). Each is made twice, so that the starting point need not
    be kept while the steps run."""
    model = Model(cfg, operand)
    kw = dict(b1=hp["b1"], b2=hp["b2"], eps=hp["eps"],
              lr=hp["learning_rate"], wd=hp["weight_decay"])

    def clip_scale(gnorm):
        return jnp.float32(min(1.0, hp["grad_clip"] / max(gnorm, 1e-30)))

    weights = {name: initial_leaf(name, None) for name in TOP_LEAVES}
    weights["layers"] = [{name: initial_leaf(name, l) for name in LAYER_LEAVES}
                         for l in range(cfg["num_hidden_layers"])]
    loss1, g1 = model.loss_and_grads(weights, batches[0])
    sq1 = [(n, l, _sumsq(g)) for n, l, g in leaves(g1)]
    gnorm1 = float(np.sqrt(sum(float(s) for _, _, s in sq1)))
    # step 1: moments start at nought, so they follow from g1 alone; the
    # (clipped) gradient goes to the host until step 2 needs it
    host_g1 = {}
    for name, layer, g in list(leaves(g1)):
        key = name.split("/")[-1]
        p = (weights if layer is None else weights["layers"][layer])[key]
        zero = jnp.zeros_like(g)
        p, _, _ = adamw_leaf(p, g, zero, zero, clip_scale(gnorm1), step=1,
                             **kw)
        set_leaf(weights, name, layer, p)
        host_g1[name, layer] = np.asarray(g)
        set_leaf(g1, name, layer, None)
        del g, zero
    loss2, g2 = model.loss_and_grads(weights, batches[1])
    gnorm2 = float(np.sqrt(sum(float(_sumsq(g)) for _, _, g in leaves(g2))))
    delta = []
    for name, layer, g in list(leaves(g2)):
        key = name.split("/")[-1]
        p = (weights if layer is None else weights["layers"][layer])[key]
        g_first = jnp.asarray(host_g1.pop((name, layer))) * clip_scale(gnorm1)
        m1, v1 = (1 - kw["b1"]) * g_first, (1 - kw["b2"]) * g_first * g_first
        p, _, _ = adamw_leaf(p, g, m1, v1, clip_scale(gnorm2), step=2, **kw)
        delta.append((name, layer, _sumsq(p - initial_leaf(key, layer))))
        set_leaf(weights, name, layer, None)
        set_leaf(g2, name, layer, None)
        del p, g, g_first, m1, v1
    return {"loss": [loss1, loss2], "grad_norm": [gnorm1, gnorm2],
            "first_grad": norms_by_leaf(sq1), "change": norms_by_leaf(delta)}
