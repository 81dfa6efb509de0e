"""Plain reference of the LFM2 decoder with sparse experts (model_type
``lfm2_moe``) and of AdamW: what ``config.json`` of LFM2-24B-A2B defines,
the gated short convolution and the attention as the LFM2 family's public
modeling file computes them, and no further; what the config leaves open
is the configuration's ``assumed``.

Every layer is ``x + Mixer(RMSNorm(x))`` and then ``x + FFN(RMSNorm(x))``,
listed as two entries a layer, by ``layer_types``:

- ``C`` (``conv``) the gated short convolution: ``[B | C | x~] = u
  W_in``, ``v = B * x~``, ``w_t = sum_j K[j] v_{t-(L-1)+j}`` over
  ``conv_L_cache`` = L taps a channel, WRITTEN AS AN EXPLICIT SUM OF L
  SHIFTED SLICES of v padded with L - 1 noughts before the row (causal,
  depthwise, no bias: ``conv_bias`` false), ``y = C * w``, then
  ``W_out``; no activation.
- ``*`` (``full_attention``) grouped-query causal softmax attention, a
  head of hidden / heads: q and k each RMSNormed over a head with a
  weight of their own (``q_layernorm``, ``k_layernorm``) BEFORE the
  rotary embedding (the plain table at ``rope_theta`` over the whole
  head, pairs (i, i + d/2)), scaled by ``d^-1/2``, a block of query rows
  at a time.
- ``D`` (the first ``num_dense_layers`` layers) the SwiGLU MLP of
  ``intermediate_size``.
- ``E`` mixture of experts: ``s = sigmoid(u W_r)``, the
  ``num_experts_per_tok`` largest of ``s + b`` chosen (``use_expert_bias``),
  their ``s`` over their own sum plus 1e-6 (``norm_topk_prob``, the
  family's form) times ``routed_scaling_factor`` as weights, each expert
  ``W_down (silu(W_gate u) * W_up u)``, no shared expert. A loop over the
  experts with dense masks.

Embedding, final RMSNorm (the family's ``embedding_norm``), the head tied
to the embedding (``tie_word_embeddings``), mean next-token cross entropy.
float32 throughout, every product at ``lax.Precision.HIGHEST``. It
imports nothing of ray_tpu; weights and batches come from the benchmark's
own seeded makers. One batch row and one layer at a time, each layer
recomputed in its backward (``nemotron_h_decoder.Model``'s walk, whose
AdamW and operand rules are used as they are).

Departures from the published description, each marked DEPARTURE at its
line: the chip's share of an 8-way expert-parallel deployment (the
experts held and the vocabulary slice are the configuration's, the same
as the program's); the correction bias follows the update rule the
configuration assumes (``run.router_bias_rate``). The program leaves the
1e-6 out of the weights' denominator (``transformer.route``): a relative
difference of under 1e-6 in a weight, far under bfloat16's spacing.

``FAULTS`` are this model's planted faults, for the limits of the
comparison, each breaking one mechanism silently: the convolution's
output gate left out (``ungated_conv``: ``y = w``), its input gate left
out (``ungated_input``: ``v = x~``), the taps in reverse order
(``taps_reversed``), the norms of q and k left out (``no_qk_norm``), the
experts weighed by ``s + b`` where the bias should only choose
(``bias_weighs``), the routed experts left out (``no_routed``).
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
    norms_by_leaf,
    rms_norm,
    rotary,
)
from benchmark.references.nemotron_h_decoder import OPERANDS, _layer_bwd

KINDS = {"C": "short_conv", "*": "attention", "D": "dense", "E": "moe"}
LEAVES = {
    "short_conv": ("norm", "w_in", "conv_w", "w_out"),
    "attention": ("attn_norm", "wq", "wk", "wv", "q_norm", "k_norm", "wo"),
    "dense": ("mlp_norm", "w_gate", "w_up", "w_down"),
    "moe": ("norm", "router", "router_bias", "w_gate", "w_up", "w_down"),
}
TOP_LEAVES = ("embed", "final_norm")
FAULTS = ("ungated_conv", "ungated_input", "taps_reversed", "no_qk_norm",
          "bias_weighs", "no_routed")
QUERY_ROWS = 1024


class Dims:
    def __init__(self, cfg: dict):
        assert (cfg["conv_bias"], cfg["use_expert_bias"],
                cfg["norm_topk_prob"], cfg["tie_word_embeddings"],
                cfg["rope_parameters"]["rope_type"]) == (
                    False, True, True, True, "default")
        # (tree of the program's parameters, kind) of every entry, two a
        # layer: the mixer, then the MLP; the first ``num_dense_layers``
        # published layers are dense and run before the periods
        self.entries = []
        for i, kind in enumerate(cfg["layer_types"]):
            dense = cfg["first_layer"] + i < cfg["num_dense_layers"]
            where = "lead" if dense else "layers"
            self.entries += [
                (where, "attention" if kind == "full_attention"
                 else "short_conv"), (where, "dense" if dense else "moe")]
        self.kinds = [kind for _, kind in self.entries]
        self.hidden = cfg["hidden_size"]
        self.eps = cfg["norm_eps"]
        self.vocab = cfg["vocab_size"]
        self.taps = cfg["conv_L_cache"]
        # *
        self.heads = cfg["num_attention_heads"]
        self.kv_heads = cfg["num_key_value_heads"]
        self.head_dim = self.hidden // self.heads
        self.theta = cfg["rope_parameters"]["rope_theta"]
        # E; DEPARTURE: ``num_experts`` counts the experts held here
        # (``experts_held_first`` onwards), the router keeps its published
        # width ``router_width``
        self.router_width = cfg["router_width"]
        self.held_first = cfg["experts_held_first"]
        self.held = cfg["num_experts"]
        self.top_k = cfg["num_experts_per_tok"]
        self.scale = cfg["routed_scaling_factor"]


def conv_row(x, w, dims: Dims, operands, fault=None):
    """One ``C`` layer on one sequence: x [seq, hidden]."""
    seq, h = x.shape[0], dims.hidden
    operand, inner = operands

    def mm(a, b):
        return jnp.matmul(operand(a), operand(b), precision=HIGHEST)

    proj = mm(rms_norm(x, w["norm"], dims.eps), w["w_in"])
    b, c, xs = (inner(proj[:, i * h:(i + 1) * h]) for i in range(3))
    v = xs if fault == "ungated_input" else b * xs
    taps = w["conv_w"][::-1] if fault == "taps_reversed" else w["conv_w"]
    # w_t = sum_j K[j] v_{t-(L-1)+j}, v nought before the row
    padded = jnp.pad(v, ((dims.taps - 1, 0), (0, 0)))
    conv = sum(taps[j] * padded[j:j + seq] for j in range(dims.taps))
    y = conv if fault == "ungated_conv" else c * conv
    return x + mm(y, w["w_out"])


def attention_row(x, w, dims: Dims, operands, fault=None):
    """One ``*`` layer on one sequence."""
    seq = x.shape[0]
    operand, inner = operands
    d, kv, g = dims.head_dim, dims.kv_heads, dims.heads // dims.kv_heads
    rows = QUERY_ROWS if seq % QUERY_ROWS == 0 else seq

    def mm(a, b):
        return jnp.matmul(operand(a), operand(b), precision=HIGHEST)

    def head_norm(t, weight):
        return t if fault == "no_qk_norm" else rms_norm(t, weight, dims.eps)

    xn = rms_norm(x, w["attn_norm"], dims.eps)
    q = rotary(head_norm(mm(xn, w["wq"]).reshape(seq, dims.heads, d),
                         w["q_norm"]), dims.theta)
    k = rotary(head_norm(mm(xn, w["wk"]).reshape(seq, kv, d), w["k_norm"]),
               dims.theta)
    v = mm(xn, w["wv"]).reshape(seq, kv, d)
    q = inner(q).reshape(seq // rows, rows, kv, g, d)
    k, v = inner(k), inner(v)

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


def dense_row(x, w, dims: Dims, operands, fault=None):
    """One leading layer's SwiGLU MLP on one sequence."""
    operand, _ = operands

    def mm(a, b):
        return jnp.matmul(operand(a), operand(b), precision=HIGHEST)

    u = rms_norm(x, w["mlp_norm"], dims.eps)
    return x + mm(jax.nn.silu(mm(u, w["w_gate"])) * mm(u, w["w_up"]),
                  w["w_down"])


def route(u, w, dims: Dims):
    """(every expert's score, the experts chosen) for normed rows u."""
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

    def glu(u, gate, up, down):
        return mm(jax.nn.silu(mm(u, gate)) * mm(u, up), down)

    if fault == "no_routed":
        return x
    u = rms_norm(x, w["norm"], dims.eps)
    scores, chosen = route(u, w, dims)
    if fault == "bias_weighs":
        scores = scores + w["router_bias"]
    gates = jnp.take_along_axis(scores, chosen, axis=-1)
    gates = gates / (gates.sum(-1, keepdims=True) + 1e-6) * dims.scale

    # DEPARTURE: the loop is over the experts held here alone; what the
    # absent experts would add is left out
    @jax.checkpoint
    def expert(acc, held):
        e, gate, up, down = held
        weight = jnp.where(chosen == dims.held_first + e, gates, 0.0).sum(-1)
        return acc + weight[:, None] * glu(u, gate, up, down), None

    out, _ = lax.scan(expert, jnp.zeros_like(x), (
        jnp.arange(dims.held), w["w_gate"], w["w_up"], w["w_down"]))
    return x + out


LAYER_ROW = {"short_conv": conv_row, "attention": attention_row,
             "dense": dense_row, "moe": moe_row}


class Model:
    """The jitted pieces for one configuration, operand rule and fault."""

    def __init__(self, cfg: dict, operands=OPERANDS["float32"], fault=None):
        dims = self.dims = Dims(cfg)
        self.kinds = dims.kinds
        self.layer_fwd, self.layer_bwd = {}, {}
        for kind in set(self.kinds):
            layer = functools.partial(LAYER_ROW[kind], dims=dims,
                                      operands=operands, fault=fault)
            self.layer_fwd[kind] = jax.jit(layer)
            self.layer_bwd[kind] = jax.jit(
                functools.partial(_layer_bwd, layer), donate_argnums=(3,))
        head = functools.partial(head_row, dims=dims, operand=operands[0])

        def head_bwd(x, final_norm, embed, targets, scale, acc):
            # the head is the embedding turned: its cotangent is the
            # embedding's too
            nll, vjp = jax.vjp(
                lambda x, n, e: head(x, n, e.T, targets), x, final_norm,
                embed)
            dx, dn, de = vjp(scale)
            return nll, dx, (acc[0] + dn, acc[1] + de)

        self.head_bwd = jax.jit(head_bwd, donate_argnums=(5,))
        self.embed_bwd = jax.jit(
            lambda acc, ids, dx: acc.at[ids].add(dx), donate_argnums=(0,))
        self.drawn = jax.jit(functools.partial(drawn_row, dims=dims))

    def loss_and_grads(self, weights, tokens):
        """Mean cross entropy of ``tokens`` [rows, seq + 1], its gradient
        in the layout of ``weights`` (the entries as a list), and for each
        ``E`` entry the tokens that chose each expert {entry: [width]}."""
        tokens = np.asarray(tokens)
        n_rows, seq = tokens.shape[0], tokens.shape[1] - 1
        scale = jnp.float32(1.0 / (n_rows * seq))
        layers = weights["layers"]
        grads = {"embed": jnp.zeros_like(weights["embed"]),
                 "layers": [jax.tree.map(jnp.zeros_like, w) for w in layers]}
        head_acc = (jnp.zeros_like(weights["final_norm"]),
                    jnp.zeros_like(weights["embed"]))
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
                x, weights["final_norm"], weights["embed"],
                jnp.asarray(tokens[r, 1:]), scale, head_acc)
            nlls.append(row_nll)
            for l in reversed(range(len(layers))):
                dx, grads["layers"][l] = self.layer_bwd[self.kinds[l]](
                    inputs.pop(), layers[l], dx, grads["layers"][l])
            grads["embed"] = self.embed_bwd(grads["embed"], ids, dx)
        grads["final_norm"] = head_acc[0]
        grads["embed"] = grads["embed"] + head_acc[1]
        return float(sum(float(n) for n in nlls) * float(scale)), grads, drawn


def leaves(tree, dims: Dims):
    """(name as the comparison knows it, entry or None, key, array) of
    every leaf, in a fixed order: the name says where the program keeps
    the leaf (``lead/<kind>/<leaf>``, ``layers/<kind>/<leaf>``)."""
    for name in TOP_LEAVES:
        yield name, None, name, tree[name]
    for l, ((where, kind), layer) in enumerate(zip(dims.entries,
                                                   tree["layers"])):
        for name in LEAVES[kind]:
            yield f"{where}/{kind}/{name}", l, name, layer[name]


def follow_two_steps(cfg: dict, hp: dict, initial_leaf, batches,
                     operands=OPERANDS["float32"], fault=None, against=None,
                     keep=False):
    """Two AdamW steps on ``batches[0]`` and ``batches[1]``, as
    ``solar_open2_decoder.follow_two_steps`` returns them: each step's loss
    and raw global gradient norm, the norm of the first raw gradient by
    leaf, the norm of the parameters' change over the two steps by leaf
    (the leaves of one kind and place in the order of their layers), and
    ``loss_parts`` (this model's loss has one part: empty).

    ``against(name, entry, key)``, where given, is somebody else's first
    raw gradient of that leaf in float32: the norm of the DIFFERENCE of
    the two by leaf comes back as ``first_grad_diff``. ``keep`` hands the
    first gradient's leaves back on the host, ``first_grad_leaves``
    {(name, entry): array}, for a later call's ``against``.

    ``initial_leaf(name, entry)`` makes one float32 leaf of the starting
    point (``entry`` counts the entries, two a layer; None for the
    embedding and the final norm). Each is made twice."""
    model = Model(cfg, operands, fault)
    dims = model.dims
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
    even = rows * (width - 1) * dims.top_k / dims.router_width

    def balanced(key, layer, p, drawn):
        if key != "router_bias":
            return p
        return p + cfg["run"]["router_bias_rate"] * (1.0 - drawn[layer] / even)

    def put(tree, key, layer, value):
        (tree if layer is None else tree["layers"][layer])[key] = value

    def parameter(tree, key, layer):
        return (tree if layer is None else tree["layers"][layer])[key]

    weights = {name: initial_leaf(name, None) for name in TOP_LEAVES}
    weights["layers"] = [{name: initial_leaf(name, l)
                          for name in LEAVES[kind]}
                         for l, kind in enumerate(dims.kinds)]
    loss1, g1, drawn1 = model.loss_and_grads(weights, batches[0])
    sq1 = [(n, l, _sumsq(g)) for n, l, _, g in leaves(g1, dims)]
    gnorm1 = float(np.sqrt(sum(float(s) for _, _, s in sq1)))
    out = {}
    if against is not None:
        out["first_grad_diff"] = norms_by_leaf(
            [(n, l, _sumsq(g - against(n, l, k)))
             for n, l, k, g in leaves(g1, dims)])
    # step 1: moments start at nought, so they follow from g1 alone; the
    # gradient goes to the host until step 2 needs it
    host_g1 = {}
    for name, layer, key, g in list(leaves(g1, dims)):
        p = parameter(weights, key, layer)
        zero = jnp.zeros_like(g)
        p, _, _ = adamw_leaf(p, g, zero, zero, clip_scale(gnorm1), step=1,
                             lr=lr(1), **kw)
        put(weights, key, layer, balanced(key, layer, p, drawn1))
        host_g1[name, layer] = np.asarray(g)
        put(g1, key, layer, None)
        del g, zero
    if keep:
        out["first_grad_leaves"] = dict(host_g1)
    loss2, g2, drawn2 = model.loss_and_grads(weights, batches[1])
    gnorm2 = float(np.sqrt(sum(float(_sumsq(g))
                               for _, _, _, g in leaves(g2, dims))))
    delta = []
    for name, layer, key, g in list(leaves(g2, dims)):
        p = parameter(weights, key, layer)
        g_first = jnp.asarray(host_g1.pop((name, layer))) * clip_scale(gnorm1)
        m1, v1 = (1 - kw["b1"]) * g_first, (1 - kw["b2"]) * g_first * g_first
        p, _, _ = adamw_leaf(p, g, m1, v1, clip_scale(gnorm2), step=2,
                             lr=lr(2), **kw)
        p = balanced(key, layer, p, drawn2)
        delta.append((name, layer, _sumsq(p - initial_leaf(key, layer))))
        put(weights, key, layer, None)
        put(g2, key, layer, None)
        del p, g, g_first, m1, v1
    return dict(out, loss=[loss1, loss2], grad_norm=[gnorm1, gnorm2],
                loss_parts=[{}, {}], first_grad=norms_by_leaf(sq1),
                change=norms_by_leaf(delta))
