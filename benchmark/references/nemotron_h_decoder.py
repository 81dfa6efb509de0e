"""Plain reference of the Nemotron-H hybrid decoder (model_type
``nemotron_h``) and of AdamW: what ``config.json`` of
Nemotron-Labs-TwoTower-30B-A3B-Base-BF16 defines, and no further.

A stack of layers ``x + f(RMSNorm(x))`` with one ``f`` each, by the
characters of ``hybrid_override_pattern``:

- ``M`` Mamba-2 mixer (Dao & Gu 2024; the family's modeling file):
  ``zxBCdt = u W_in``; ``xBC = silu(causal depthwise conv(xBC))``;
  ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; per head h of
  group g, ``S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t (outer) B_t``,
  ``y_t = S_t C_t + D_h x_t`` from ``S_0 = 0``; then the grouped RMSNorm
  of ``y silu(z)`` (the norm after the gate) and ``W_out``. The
  recurrence is a SEQUENTIAL ``lax.scan`` over the positions, one state
  [heads, head_dim, state] at a time: no chunks, no duality.
- ``E`` mixture of experts: ``s = sigmoid(u W_r)``, the
  ``num_experts_per_tok`` largest of ``s + b`` chosen, their ``s`` over
  their own sum times ``routed_scaling_factor`` as weights, each expert
  ``W_down relu(W_up u)^2``, one shared expert of the same form added
  unweighted. A loop over the experts with dense masks: every token goes
  through every expert and is weighed by nought where it was not chosen.
- ``*`` grouped-query causal attention scaled by head_dim**-0.5, plain
  softmax, a block of query rows at a time so that 8192 keys fit.

Embedding, final RMSNorm, untied head, mean next-token cross entropy.
float32 throughout, every product at ``lax.Precision.HIGHEST``. It
imports nothing of ray_tpu; weights and batches come from the
benchmark's own seeded makers. One batch row and one layer at a time,
each layer recomputed in its backward, as ``dense_decoder.py`` (whose
head, AdamW and operand rules are used as they are).

Departures from the published description, each marked DEPARTURE at its
line: the chip's share of an 8-way expert-parallel deployment (the
experts held and the vocabulary slice are the configuration's, the same
as the program's); no rotary embedding; the correction bias follows the
update rule the configuration assumes (``run.router_bias_rate``). Not
built, because ``config.json`` does not define them: the
second tower, adaLN, cross-tower conditioning, the diffusion objective.

``FAULTS`` are this model's planted faults, for the limits of the
comparison: the carried state zeroed at every ``chunk_size`` boundary,
and the routed experts left out.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.references.dense_decoder import (
    HIGHEST,
    _sumsq,
    adamw_leaf,
    head_row,
    identity,
    int8_operands,
    norms_by_leaf,
    rms_norm,
)

KINDS = {"M": "mamba", "E": "moe", "*": "attention"}
LEAVES = {
    "mamba": ("norm", "w_in", "conv_w", "conv_b", "dt_bias", "a_log", "d",
              "gate_norm", "w_out"),
    "moe": ("norm", "router", "router_bias", "w_up", "w_down", "shared_up",
            "shared_down"),
    "attention": ("attn_norm", "wq", "wk", "wv", "wo"),
}
TOP_LEAVES = ("embed", "final_norm", "unembed")
FAULTS = ("state_reset", "no_routed")
QUERY_ROWS = 1024
def float8_operands(x):
    """float8 (e4m3: 3 bits of mantissa against bfloat16's 7) with one
    scale for the tensor, as an 8-bit floating product sees its operand;
    the gradient passes straight through."""
    top = float(jnp.finfo(jnp.float8_e4m3fn).max)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + lax.stop_gradient(q - x)


# An operand rule is a pair: what rounds both operands of a projection
# (every weight matrix, the experts, the head), and what rounds the
# operands that the products without a weight read (x, B, C and the
# carried state of the scan; q, k, v of attention). ``int8_linear`` is
# dense_decoder's control; ``int8_products`` rounds every operand the
# program hands a product in bfloat16; ``float8_products`` does so in the
# nearest floating type under bfloat16. Symmetric int8 with the tensor's
# own scale keeps a tensor's largest values as well as bfloat16 does
# (1/254 against 2**-8), and at this cell's size its gaps lie inside the
# bfloat16 program's own (PERF.md section 6, PR 26): the cell's control
# is ``float8_products``.
OPERANDS = {"float32": (identity, identity),
            "int8_linear": (int8_operands, identity),
            "int8_products": (int8_operands, int8_operands),
            "float8_products": (float8_operands, float8_operands)}


class Dims:
    def __init__(self, cfg: dict):
        self.pattern = cfg["hybrid_override_pattern"]
        self.hidden = cfg["hidden_size"]
        self.eps = cfg["layer_norm_epsilon"]
        self.vocab = cfg["vocab_size"]
        # M
        self.ssm_heads = cfg["mamba_num_heads"]
        self.ssm_head_dim = cfg["mamba_head_dim"]
        # the inner width is heads x head_dim (the family's modeling
        # file), not ``expand`` x hidden
        self.inner = self.ssm_heads * self.ssm_head_dim
        self.groups = cfg["n_groups"]
        self.state = cfg["ssm_state_size"]
        self.conv = cfg["conv_kernel"]
        self.chunk = cfg["chunk_size"]
        # E; DEPARTURE: ``n_routed_experts`` counts the experts held here
        # (``experts_held_first`` onwards), the router keeps its published
        # width ``router_width``
        self.router_width = cfg["router_width"]
        self.held_first = cfg["experts_held_first"]
        self.held = cfg["n_routed_experts"]
        self.top_k = cfg["num_experts_per_tok"]
        self.scale = cfg["routed_scaling_factor"]
        # *
        self.heads = cfg["num_attention_heads"]
        self.kv_heads = cfg["num_key_value_heads"]
        self.head_dim = cfg["head_dim"]


def mamba_row(x, w, dims: Dims, operands, fault=None):
    """One ``M`` layer on one sequence: x [seq, hidden]."""
    seq = x.shape[0]
    h, p, g, n = dims.ssm_heads, dims.ssm_head_dim, dims.groups, dims.state
    operand, inner = operands

    def mm(a, b):
        return jnp.matmul(operand(a), operand(b), precision=HIGHEST)

    z, xbc, dt = jnp.split(
        mm(rms_norm(x, w["norm"], dims.eps), w["w_in"]),
        [dims.inner, 2 * dims.inner + 2 * g * n], axis=-1)
    # causal depthwise convolution: y_t = b + sum_j w_j x_{t-(K-1)+j}
    padded = jnp.pad(xbc, ((dims.conv - 1, 0), (0, 0)))
    xbc = jax.nn.silu(w["conv_b"] + sum(
        w["conv_w"][j] * padded[j:j + seq] for j in range(dims.conv)))
    xs, bm, cm = (inner(t) for t in jnp.split(
        xbc, [dims.inner, dims.inner + g * n], axis=-1))
    xs = xs.reshape(seq, h, p)
    # head h reads the B and C of group h // (heads / groups)
    bm = jnp.repeat(bm.reshape(seq, g, n), h // g, axis=1)
    cm = jnp.repeat(cm.reshape(seq, g, n), h // g, axis=1)
    dt = jax.nn.softplus(dt + w["dt_bias"])     # time_step_limit (0, inf)
    a = -jnp.exp(w["a_log"])

    def position(state, inputs):
        x_t, dt_t, b_t, c_t, t = inputs
        if fault == "state_reset":
            state = jnp.where(t % dims.chunk == 0, 0.0, state)
        state = jnp.exp(dt_t * a)[:, None, None] * state \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        # the carried state is an operand of its product with C
        return state, jnp.sum(inner(state) * c_t[:, None, :], axis=-1) \
            + w["d"][:, None] * x_t

    # blocks of positions, each rebuilt in its backward, only so that
    # the backward need not hold a state for every position
    rows = dims.chunk if seq % dims.chunk == 0 else seq

    @jax.checkpoint
    def block(state, inputs):
        return lax.scan(position, state, inputs)

    _, y = lax.scan(block, jnp.zeros((h, p, n), jnp.float32), jax.tree.map(
        lambda t: t.reshape(seq // rows, rows, *t.shape[1:]),
        (xs, dt, bm, cm, jnp.arange(seq))))
    gated = (y.reshape(seq, dims.inner) * jax.nn.silu(z)).reshape(seq, g, -1)
    gated = gated * lax.rsqrt(
        jnp.mean(gated * gated, axis=-1, keepdims=True) + dims.eps)
    return x + mm(gated.reshape(seq, dims.inner) * w["gate_norm"], w["w_out"])


def route(u, w, dims: Dims):
    """(every expert's score, the experts chosen) for normed rows u.
    n_group 1, topk_group 1: no group limit."""
    scores = jax.nn.sigmoid(jnp.matmul(u, w["router"], precision=HIGHEST))
    _, chosen = lax.top_k(scores + w["router_bias"], dims.top_k)
    return scores, chosen


def drawn_row(x, w, dims: Dims):
    """How many of one sequence's tokens chose each expert of the
    router's width, in one ``E`` layer whose input is x."""
    _, chosen = route(rms_norm(x, w["norm"], dims.eps), w, dims)
    return (chosen[..., None] == jnp.arange(dims.router_width)).sum((0, 1))


def moe_row(x, w, dims: Dims, operands, fault=None):
    """One ``E`` layer on one sequence."""
    operand, _ = operands

    def mm(a, b):
        return jnp.matmul(operand(a), operand(b), precision=HIGHEST)

    def relu2(u, up, down):
        return mm(jnp.square(jax.nn.relu(mm(u, up))), down)

    u = rms_norm(x, w["norm"], dims.eps)
    scores, chosen = route(u, w, dims)
    gates = jnp.take_along_axis(scores, chosen, axis=-1)
    gates = gates / gates.sum(-1, keepdims=True) * dims.scale
    out = relu2(u, w["shared_up"], w["shared_down"])
    if fault == "no_routed":
        return x + out

    # DEPARTURE: the loop is over the experts held here alone; what the
    # absent experts would add is left out
    @jax.checkpoint
    def expert(acc, e_up_down):
        e, up, down = e_up_down
        weight = jnp.where(chosen == dims.held_first + e, gates, 0.0).sum(-1)
        return acc + weight[:, None] * relu2(u, up, down), None

    out, _ = lax.scan(expert, out,
                      (jnp.arange(dims.held), w["w_up"], w["w_down"]))
    return x + out


def attention_row(x, w, dims: Dims, operands, fault=None):
    """One ``*`` layer on one sequence. DEPARTURE: no rotary embedding
    (the family's attention layers have none; ``rope_theta`` is unused)."""
    seq = x.shape[0]
    operand, inner = operands
    d, kv, g = dims.head_dim, dims.kv_heads, dims.heads // dims.kv_heads
    rows = QUERY_ROWS if seq % QUERY_ROWS == 0 else seq

    def mm(a, b):
        return jnp.matmul(operand(a), operand(b), precision=HIGHEST)

    xn = rms_norm(x, w["attn_norm"], dims.eps)
    q = inner(mm(xn, w["wq"])).reshape(seq // rows, rows, kv, g, d)
    k = inner(mm(xn, w["wk"])).reshape(seq, kv, d)
    v = inner(mm(xn, w["wv"])).reshape(seq, kv, d)

    def one_group(qkv):
        qg, kg, vg = qkv  # [blocks, rows, g, d], [seq, d], [seq, d]

        @jax.checkpoint
        def one_block(block):
            qb, first = block
            scores = jnp.einsum("rgd,td->grt", qb, kg,
                                precision=HIGHEST) * d ** -0.5
            causal = (first + jnp.arange(rows))[:, None] \
                >= jnp.arange(seq)[None, :]
            p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
            return jnp.einsum("grt,td->rgd", p, vg, precision=HIGHEST)

        return lax.map(one_block, (qg, jnp.arange(0, seq, rows)))

    # query head h reads key/value head h // group
    out = lax.map(one_group, (q.transpose(2, 0, 1, 3, 4),
                              k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    out = out.transpose(1, 2, 0, 3, 4).reshape(seq, kv * g * d)
    return x + mm(out, w["wo"])


LAYER_ROW = {"mamba": mamba_row, "moe": moe_row, "attention": attention_row}


class Model:
    """The jitted pieces for one configuration, operand rule and fault."""

    def __init__(self, cfg: dict, operands=OPERANDS["float32"], fault=None):
        dims = self.dims = Dims(cfg)
        self.kinds = [KINDS[c] for c in dims.pattern]
        self.layer_fwd, self.layer_bwd = {}, {}
        for kind in set(self.kinds):
            layer = functools.partial(LAYER_ROW[kind], dims=dims,
                                      operands=operands, fault=fault)
            self.layer_fwd[kind] = jax.jit(layer)
            self.layer_bwd[kind] = jax.jit(
                functools.partial(_layer_bwd, layer), donate_argnums=(3,))
        head = functools.partial(head_row, dims=dims, operand=operands[0])

        def head_bwd(x, final_norm, unembed, targets, scale, acc):
            nll, vjp = jax.vjp(
                lambda x, n, u: head(x, n, u, targets), x, final_norm,
                unembed)
            dx, dn, du = vjp(scale)
            return nll, dx, (acc[0] + dn, acc[1] + du)

        self.head_bwd = jax.jit(head_bwd, donate_argnums=(5,))
        self.embed_bwd = jax.jit(
            lambda acc, ids, dx: acc.at[ids].add(dx), donate_argnums=(0,))
        self.drawn = jax.jit(functools.partial(drawn_row, dims=dims))

    def loss_and_grads(self, weights, tokens):
        """Mean cross entropy of ``tokens`` [rows, seq + 1], its gradient
        in the layout of ``weights`` (layers as a list), and for each
        ``E`` layer the tokens that chose each expert {layer: [width]}."""
        tokens = np.asarray(tokens)
        n_rows, seq = tokens.shape[0], tokens.shape[1] - 1
        scale = jnp.float32(1.0 / (n_rows * seq))
        layers = weights["layers"]
        grads = {"embed": jnp.zeros_like(weights["embed"]),
                 "layers": [jax.tree.map(jnp.zeros_like, w) for w in layers]}
        head_acc = (jnp.zeros_like(weights["final_norm"]),
                    jnp.zeros_like(weights["unembed"]))
        nlls, drawn = [], {}
        for r in range(n_rows):
            ids = jnp.asarray(tokens[r, :-1])
            x, inputs = jnp.take(weights["embed"], ids, axis=0), []
            for l, (kind, w) in enumerate(zip(self.kinds, layers)):
                inputs.append(x)
                if kind == "moe":
                    drawn[l] = drawn.get(l, 0) + self.drawn(x, w)
                x = self.layer_fwd[kind](x, w)
            row_nll, dx, head_acc = self.head_bwd(
                x, weights["final_norm"], weights["unembed"],
                jnp.asarray(tokens[r, 1:]), scale, head_acc)
            nlls.append(row_nll)
            for l in reversed(range(len(layers))):
                dx, grads["layers"][l] = self.layer_bwd[self.kinds[l]](
                    inputs.pop(), layers[l], dx, grads["layers"][l])
            grads["embed"] = self.embed_bwd(grads["embed"], ids, dx)
        grads["final_norm"], grads["unembed"] = head_acc
        return float(sum(float(n) for n in nlls) * float(scale)), grads, drawn


def _layer_bwd(layer, x, w, dy, acc):
    _, vjp = jax.vjp(layer, x, w)
    dx, dw = vjp(dy)
    return dx, jax.tree.map(jnp.add, acc, dw)


def leaves(tree, kinds):
    """(name, layer or None, array) of every leaf, in a fixed order;
    a layer's leaf is named ``layers/<kind>/<leaf>``."""
    for name in TOP_LEAVES:
        yield name, None, tree[name]
    for l, (kind, layer) in enumerate(zip(kinds, tree["layers"])):
        for name in LEAVES[kind]:
            yield f"layers/{kind}/{name}", l, layer[name]


def set_leaf(tree, name, layer, value):
    if layer is None:
        tree[name] = value
    else:
        tree["layers"][layer][name.split("/")[-1]] = value


def follow_two_steps(cfg: dict, hp: dict, initial_leaf, batches,
                     operands=OPERANDS["float32"], fault=None):
    """Two AdamW steps on ``batches[0]`` and ``batches[1]``, as
    ``dense_decoder.follow_two_steps`` returns them: each step's loss and
    raw global gradient norm, the norm of the first raw gradient by leaf,
    the norm of the parameters' change over the two steps by leaf (the
    leaves of one kind in the order of their layers).

    ``initial_leaf(name, layer)`` makes one float32 leaf of the starting
    point (``layer`` counts all the layers; None for the embedding, the
    final norm and the head). Each is made twice."""
    model = Model(cfg, operands, fault)
    kinds = model.kinds
    kw = dict(b1=hp["b1"], b2=hp["b2"], eps=hp["eps"],
              wd=hp["weight_decay"])

    def lr(step):
        """The learning rate of step ``step`` (from 1): the configuration
        assumes a linear warm-up over ``warmup_steps`` steps."""
        return hp["learning_rate"] * min(
            1.0, step / max(1, hp.get("warmup_steps", 0)))

    def clip_scale(gnorm):
        return jnp.float32(min(1.0, hp["grad_clip"] / max(gnorm, 1e-30)))

    # DEPARTURE: the config gives the correction bias no update rule. As
    # the configuration assumes (Wang et al. 2024, arXiv 2408.15664, by
    # the size of the error): after a step's AdamW, every expert's bias
    # gains the rate times the share by which the tokens it drew in that
    # step fell short of an even draw. The bias has no gradient.
    rows, width = np.asarray(batches[0]).shape
    even = rows * (width - 1) * cfg["num_experts_per_tok"] \
        / cfg["router_width"]

    def balanced(name, layer, p, drawn):
        if not name.endswith("/router_bias"):
            return p
        return p + cfg["run"]["router_bias_rate"] * (1.0 - drawn[layer] / even)

    weights = {name: initial_leaf(name, None) for name in TOP_LEAVES}
    weights["layers"] = [{name: initial_leaf(name, l)
                          for name in LEAVES[kind]}
                         for l, kind in enumerate(kinds)]
    loss1, g1, drawn1 = model.loss_and_grads(weights, batches[0])
    sq1 = [(n, l, _sumsq(g)) for n, l, g in leaves(g1, kinds)]
    gnorm1 = float(np.sqrt(sum(float(s) for _, _, s in sq1)))
    # step 1: moments start at nought, so they follow from g1 alone; the
    # gradient goes to the host until step 2 needs it
    host_g1 = {}
    for name, layer, g in list(leaves(g1, kinds)):
        key = name.split("/")[-1]
        p = (weights if layer is None else weights["layers"][layer])[key]
        zero = jnp.zeros_like(g)
        p, _, _ = adamw_leaf(p, g, zero, zero, clip_scale(gnorm1), step=1,
                             lr=lr(1), **kw)
        set_leaf(weights, name, layer, balanced(name, layer, p, drawn1))
        host_g1[name, layer] = np.asarray(g)
        set_leaf(g1, name, layer, None)
        del g, zero
    loss2, g2, drawn2 = model.loss_and_grads(weights, batches[1])
    gnorm2 = float(np.sqrt(sum(float(_sumsq(g))
                               for _, _, g in leaves(g2, kinds))))
    delta = []
    for name, layer, g in list(leaves(g2, kinds)):
        key = name.split("/")[-1]
        p = (weights if layer is None else weights["layers"][layer])[key]
        g_first = jnp.asarray(host_g1.pop((name, layer))) * clip_scale(gnorm1)
        m1, v1 = (1 - kw["b1"]) * g_first, (1 - kw["b2"]) * g_first * g_first
        p, _, _ = adamw_leaf(p, g, m1, v1, clip_scale(gnorm2), step=2,
                             lr=lr(2), **kw)
        p = balanced(name, layer, p, drawn2)
        delta.append((name, layer, _sumsq(p - initial_leaf(key, layer))))
        set_leaf(weights, name, layer, None)
        set_leaf(g2, name, layer, None)
        del p, g, g_first, m1, v1
    return {"loss": [loss1, loss2], "grad_norm": [gnorm1, gnorm2],
            "first_grad": norms_by_leaf(sq1), "change": norms_by_leaf(delta)}
