"""Plain reference of the Solar-Open2 decoder (model_type ``solar_open2``)
and of AdamW: what ``config.json`` of Solar-Open2-250B defines, Kimi Delta
Attention as the Kimi Linear paper (arXiv 2510.26692) publishes it where
the config's ``kda_*`` and ``linear_attn_config`` keys name it, and no
further; what the config leaves open is the configuration's ``assumed``.

Every layer is ``x + Mixer(RMSNorm(x))`` and then ``x + MoE(RMSNorm(x))``
(``first_k_dense_replace`` 0), listed as two entries a layer:

- ``K`` Kimi Delta Attention, H heads of d: ``q, k, v = silu(conv4(u W))``
  (causal, depthwise, no bias), q and k normalised a head (``x / sqrt(sum
  x^2 + 1e-6)``), q scaled by ``d^-1/2``; the decay a head and channel
  ``g = -exp(A_log_h) softplus(u W_down W_up + dt_bias)``, ``alpha =
  exp(g)``; ``beta = 2 sigmoid(u W_beta)`` a head; then THE RECURRENCE
  STEP BY STEP, a ``lax.scan`` over the positions with the state updated
  exactly as the equation reads, from ``S_0 = 0``:

      S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
      o_t = S_t^T q_t

  no chunks, no triangular inverse; ``y = (RMSNorm_head(o; w [d]) *
  sigmoid(u W_gdown W_gup)) W_o``. A group of heads at a time (each
  group's columns of the weights; ``W_o`` sums the groups), the
  positions in blocks that are rebuilt in the backward, only so that the
  backward need not hold a state of [H, d, d] for every position (kept
  whole that is 34 GB at 8192 positions and 64 heads of 128) nor every
  float32 array of [8192, 8192] at once beside 10.4 GB of weights and
  gradients.
- ``*`` grouped-query causal softmax attention scaled by ``d^-1/2``, no
  position (``use_rope`` false), a block of query rows at a time, its
  output weighed element for element by ``sigmoid(u W_g)`` before ``W_o``
  (``use_gqa_gate``).
- ``E`` mixture of experts: ``s = sigmoid(u W_r)``, the
  ``num_experts_per_tok`` largest of ``s + b`` chosen, their ``s`` over
  their own sum times ``routed_scaling_factor`` as weights, each expert
  ``W_down (silu(W_gate u) * W_up u)``, one shared expert of the same
  form added unweighted. A loop over the experts with dense masks.

Embedding, final RMSNorm, untied head, mean next-token cross entropy.
float32 throughout, every product at ``lax.Precision.HIGHEST``. It
imports nothing of ray_tpu; weights and batches come from the benchmark's
own seeded makers. One batch row and one layer at a time, each layer
recomputed in its backward (``nemotron_h_decoder.Model``'s walk, whose
head, AdamW and operand rules are used as they are).

Departures from the published description, each marked DEPARTURE at its
line: the chip's share of a 40-way expert-parallel deployment (the
experts held and the vocabulary slice are the configuration's, the same
as the program's); the correction bias follows the update rule the
configuration assumes (``run.router_bias_rate``).

``FAULTS`` are this model's planted faults, for the limits of the
comparison, each breaking one thing silently: the routed experts left out
(``no_routed``), the decay averaged over a head's channels
(``scalar_decay``: the gated delta rule without KDA's channels), beta in
(0, 1) (``beta_undoubled``), no decay at all (``no_decay``), attention's
gate left out (``ungated_attention``), the state zeroed every ``chunk``
positions (``state_reset``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.references import nemotron_h_decoder as nh
from benchmark.references.dense_decoder import (
    HIGHEST,
    _sumsq,
    adamw_leaf,
    head_row,
    norms_by_leaf,
    rms_norm,
)
from benchmark.references.nemotron_h_decoder import OPERANDS, _layer_bwd

KINDS = {"*": "attention", "K": "kda", "E": "moe"}
LEAVES = {
    "attention": ("attn_norm", "wq", "wk", "wv", "w_gate", "wo"),
    "kda": ("norm", "w_qkv", "conv_w", "w_decay_down", "w_decay_up",
            "dt_bias", "a_log", "w_beta", "w_gate_down", "w_gate_up",
            "head_norm", "w_out"),
    "moe": ("norm", "router", "router_bias", "w_gate", "w_up", "w_down",
            "shared_gate", "shared_up", "shared_down"),
}
TOP_LEAVES = ("embed", "final_norm", "unembed")
FAULTS = ("no_routed", "scalar_decay", "beta_undoubled", "no_decay",
          "ungated_attention", "state_reset")
QUERY_ROWS = 1024
# heads of the delta rule worked at once, and positions a block that the
# backward rebuilds
HEADS_AT_ONCE = 8
BLOCK = 64


class Dims:
    def __init__(self, cfg: dict):
        assert (cfg["first_k_dense_replace"], cfg["norm_topk_prob"],
                cfg["use_rope"], cfg["use_gqa_gate"],
                cfg["kda_use_full_proj"]) == (0, True, False, True, False)
        self.pattern = "".join(
            ("*" if i in cfg["gqa_layers"] else "K") + "E"
            for i in range(cfg["num_hidden_layers"]))
        self.kinds = [KINDS[c] for c in self.pattern]
        self.hidden = cfg["hidden_size"]
        self.eps = cfg["rms_norm_eps"]
        self.vocab = cfg["vocab_size"]
        # K
        lin = cfg["linear_attn_config"]
        assert lin["num_kv_heads"] is None
        self.kda_heads, self.kda_head_dim = lin["num_heads"], lin["head_dim"]
        self.inner = self.kda_heads * self.kda_head_dim
        self.conv = lin["short_conv_kernel_size"]
        self.beta_max = 2.0 if cfg["kda_allow_neg_eigval"] else 1.0
        # what ``state_reset`` zeroes the state at
        self.chunk = cfg["run"]["kda_chunk"]
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


def delta_rule(q, k, v, g, beta, inner=lambda t: t, reset_every: int = 0):
    """The recurrence step by step for one sequence: q, k, v, g [seq, H,
    d], beta [seq, H] -> o [seq, H, d]. ``inner`` rounds the operands of
    the products (the state among them); ``reset_every`` above nought
    zeroes the state at every such position (the ``state_reset`` fault).
    The positions in blocks of ``BLOCK`` rebuilt in the backward."""
    seq, heads, d = q.shape
    rows = BLOCK if seq % BLOCK == 0 else seq

    def position(state, inputs):
        q_t, k_t, v_t, g_t, beta_t, t = inputs          # [H, d], [H]
        if reset_every:
            state = jnp.where(t % reset_every == 0, 0.0, state)
        state = jnp.exp(g_t)[:, :, None] * state        # Diag(alpha) S
        # what the state answers to k_t, and the correction it takes
        answered = jnp.sum(inner(state) * k_t[:, :, None], axis=1)
        state = state + (beta_t[:, None] * k_t)[:, :, None] * (
            v_t - answered)[:, None, :]
        return state, jnp.sum(inner(state) * q_t[:, :, None], axis=1)

    @jax.checkpoint
    def block(state, inputs):
        return lax.scan(position, state, inputs)

    _, o = lax.scan(
        block, jnp.zeros((heads, d, d), jnp.float32), jax.tree.map(
            lambda t: t.reshape(seq // rows, rows, *t.shape[1:]),
            (inner(q), inner(k), inner(v), g, beta, jnp.arange(seq))))
    return o.reshape(seq, heads, d)


def kda_row(x, w, dims: Dims, operands, fault=None):
    """One ``K`` layer on one sequence: x [seq, hidden]. Between the
    normed input and the sum that ``W_o`` makes the heads share nothing:
    a group of ``HEADS_AT_ONCE`` heads at a time, each group's columns of
    the weights, each group rebuilt in its backward, only so that the
    layer fits the chip beside this model's float32 weights and gradients
    (10.4 GB). Under an operand rule other than float32 a rounding's
    scale is a group's tensor's, not the layer's."""
    seq = x.shape[0]
    heads, d = dims.kda_heads, dims.kda_head_dim
    at_once = max(n for n in range(1, min(heads, HEADS_AT_ONCE) + 1)
                  if heads % n == 0)
    groups, width = heads // at_once, at_once * d
    operand, inner = operands

    def mm(a, b):
        return jnp.matmul(operand(a), operand(b), precision=HIGHEST)

    def unit(t):
        return t * lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6)

    def columns(t, parts: int = 1):
        """[rows, parts * H * d] -> [groups, rows, parts, width]."""
        return t.reshape(t.shape[0], parts, groups, width).transpose(
            2, 0, 1, 3)

    u = rms_norm(x, w["norm"], dims.eps)
    decay_low, gate_low = mm(u, w["w_decay_down"]), mm(u, w["w_gate_down"])
    beta = (1.0 if fault == "beta_undoubled" else dims.beta_max) \
        * jax.nn.sigmoid(mm(u, w["w_beta"]))

    @jax.checkpoint
    def group(mine):
        (w_qkv, taps, w_decay_up, dt_bias, a_log, w_gate_up, w_out,
         beta_g) = mine

        def conv(t, taps):
            # causal depthwise, no bias: y_t = sum_j w_j x_{t-(K-1)+j}
            padded = jnp.pad(t, ((dims.conv - 1, 0), (0, 0)))
            return jax.nn.silu(sum(taps[j] * padded[j:j + seq]
                                   for j in range(dims.conv)))

        q, k, v = (conv(mm(u, w_qkv[:, i]), taps[:, i]).reshape(
            seq, at_once, d) for i in range(3))
        q, k = unit(q) * d ** -0.5, unit(k)
        g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
            (mm(decay_low, w_decay_up[:, 0]) + dt_bias).reshape(
                seq, at_once, d))
        if fault == "scalar_decay":
            g = jnp.broadcast_to(jnp.log(jnp.mean(
                jnp.exp(g), axis=-1, keepdims=True)), g.shape)
        if fault == "no_decay":
            g = jnp.zeros_like(g)
        o = delta_rule(q, k, v, g, beta_g, inner,
                       dims.chunk if fault == "state_reset" else 0)
        o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                          + dims.eps) * w["head_norm"]
        gate = jax.nn.sigmoid(mm(gate_low, w_gate_up[:, 0]))
        return mm(o.reshape(seq, width) * gate, w_out)

    # the residual and the groups' sum in one carry
    out, _ = lax.scan(lambda total, mine: (total + group(mine), None), x, (
        columns(w["w_qkv"], 3), columns(w["conv_w"], 3),
        columns(w["w_decay_up"]), w["dt_bias"].reshape(groups, width),
        w["a_log"].reshape(groups, at_once), columns(w["w_gate_up"]),
        w["w_out"].reshape(groups, width, -1),
        jnp.moveaxis(beta.reshape(seq, groups, at_once), 1, 0)))
    return out


def attention_row(x, w, dims: Dims, operands, fault=None):
    """One ``*`` layer on one sequence: ``nemotron_h_decoder``'s row (no
    rotary embedding) with the gate on its output."""
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
    if fault != "ungated_attention":
        out = out * jax.nn.sigmoid(mm(xn, w["w_gate"]))
    return x + mm(out, w["wo"])


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

    u = rms_norm(x, w["norm"], dims.eps)
    scores, chosen = route(u, w, dims)
    gates = jnp.take_along_axis(scores, chosen, axis=-1)
    gates = gates / gates.sum(-1, keepdims=True) * dims.scale
    out = glu(u, w["shared_gate"], w["shared_up"], w["shared_down"])
    if fault == "no_routed":
        return x + out

    # DEPARTURE: the loop is over the experts held here alone; what the
    # absent experts would add is left out
    @jax.checkpoint
    def expert(acc, held):
        e, gate, up, down = held
        weight = jnp.where(chosen == dims.held_first + e, gates, 0.0).sum(-1)
        return acc + weight[:, None] * glu(u, gate, up, down), None

    out, _ = lax.scan(expert, out, (
        jnp.arange(dims.held), w["w_gate"], w["w_up"], w["w_down"]))
    return x + out


LAYER_ROW = {"kda": kda_row, "attention": attention_row, "moe": moe_row}


class Model(nh.Model):
    """The jitted pieces for one configuration, operand rule and fault;
    ``loss_and_grads`` is ``nemotron_h_decoder.Model``'s."""

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


def leaves(tree, dims: Dims):
    """(name as the comparison knows it, entry or None, key, array) of
    every leaf, in a fixed order: ``layers/<kind>/<leaf>`` says where the
    program keeps a layer's leaf."""
    for name in TOP_LEAVES:
        yield name, None, name, tree[name]
    for l, (kind, layer) in enumerate(zip(dims.kinds, tree["layers"])):
        for name in LEAVES[kind]:
            yield f"layers/{kind}/{name}", l, name, layer[name]


def follow_two_steps(cfg: dict, hp: dict, initial_leaf, batches,
                     operands=OPERANDS["float32"], fault=None, against=None,
                     keep=False):
    """Two AdamW steps on ``batches[0]`` and ``batches[1]``, as
    ``nemotron3_decoder.follow_two_steps`` returns them: each step's loss
    and raw global gradient norm, the norm of the first raw gradient by
    leaf, the norm of the parameters' change over the two steps by leaf
    (the leaves of one kind in the order of their layers), and
    ``loss_parts`` (this model's loss has one part: empty).

    ``against(name, entry, key)``, where given, is somebody else's first
    raw gradient of that leaf in float32 (the program's, or for a control
    or a fault the float32 reference's): the norm of the DIFFERENCE of
    the two by leaf comes back as ``first_grad_diff``. ``keep`` hands the
    first gradient's leaves back on the host, ``first_grad_leaves``
    {(name, entry): array}, for a later call's ``against``.

    ``initial_leaf(name, entry)`` makes one float32 leaf of the starting
    point (``entry`` counts the entries, two a layer; None for the
    embedding, the final norm and the head). Each is made twice."""
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
