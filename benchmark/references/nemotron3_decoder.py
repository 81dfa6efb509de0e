"""Plain reference of the Nemotron-3 decoder (model_type ``nemotron_h``
with ``moe_latent_size`` and ``mtp_hybrid_override_pattern``) with its
multi-token-prediction module, and of AdamW: what ``config.json`` of
NVIDIA-Nemotron-3-Super-120B-A12B-BF16 defines, the expert layer's
latent as the family's public modeling file fixes it, the module as the
DeepSeek-V3 report (arXiv 2412.19437, section 2.2) defines one of depth
1, and no further.

A stack of layers ``x + f(RMSNorm(x))`` with one ``f`` each, by the
characters of ``hybrid_override_pattern``:

- ``*`` attention without rotary embedding: ``nemotron_h_decoder``'s
  row as it is (a dense causal softmax a block of query rows at a time).
- ``M`` Mamba-2: ``nemotron_h_decoder.mamba_row``'s mathematics, line for
  line (the convolution with its SiLU, ``softplus`` of dt, a SEQUENTIAL
  ``lax.scan`` over the positions, the norm after the gate), computed a
  group of heads at a time (``mamba_row`` below): a group's heads read
  only the group's B and C and the gated norm is a group's own, so the
  groups share nothing between the two projections, and at 128 heads of
  64 with a state of 128 the accepted row's backward, which holds B and
  C copied to every head for the whole sequence, asks 6.2 GB of a chip
  that holds this model's float32 weights and gradients (9.4 GB) already.
- ``E`` the latent expert layer, ``n`` the normed input: ``s =
  sigmoid(n W_r)`` over the router's whole width at the full hidden
  size, the ``num_experts_per_tok`` largest of ``s + b`` chosen
  (``n_group`` 1, ``topk_group`` 1: no group step), their ``s`` over
  their own sum times ``routed_scaling_factor`` as weights; ``l = n
  W_fc1`` (hidden -> ``moe_latent_size``, a bare linear map); each
  expert ``W_down relu(W_up l)^2`` in the latent; ``y = x + (sum_e g_e
  expert_e(l)) W_fc2 + W_sdown relu(W_sup n)^2``: one map back, the
  shared expert on the hidden state. A loop over the held experts with
  dense masks: every token goes through every held expert and is weighed
  by nought where it was not chosen.
- The module: ``z_i = [RMSNorm(Emb(t_{i+1})); RMSNorm(x_i)] W_eh`` with
  ``x_i`` the stack's output after its final norm, a block of the layers
  ``mtp_hybrid_override_pattern`` names (``*E``: attention, then latent
  experts, each with its own weights, norm and residual) over all the
  positions, ``RMSNorm`` of its own, the model's head asked for
  ``t_{i+2}``; the last position has no such token and weighs nought
  (its routing is counted). ``L = L_main + mtp_weight L_mtp``, each a
  mean over its own positions: ``glm_decoder``'s merge, head and
  bookkeeping of the two losses as they are.

float32 throughout, every product at ``lax.Precision.HIGHEST``. It
imports nothing of ray_tpu; weights and batches come from the
benchmark's own seeded makers. One batch row and one layer at a time,
each layer recomputed in its backward.

Departures from the published description, each marked DEPARTURE at its
line: the chip's share of a 64-way expert-parallel deployment (the
experts held and the vocabulary slice are the configuration's, the same
as the program's; the held experts' part goes through the whole
``W_fc2``, which is linear, so the shares add up); the correction bias
follows the update rule the configuration assumes
(``run.router_bias_rate``); the loss's weight is the configuration's
``run.mtp_weight``.

``FAULTS`` are this model's planted faults, for the limits of the
comparison, each breaking one thing silently: the routed experts left
out (``no_routed``), the module's loss left out (``mtp_ignored``), the
Mamba layers' carried state zeroed at every ``chunk_size`` boundary
(``state_reset``), the routed part weighed by 1 for
``routed_scaling_factor`` (``unscaled_routed``), the router reading the
latent (through its first ``moe_latent_size`` rows) for the hidden state
(``router_on_latent``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.references import glm_decoder, nemotron_h_decoder as nh
from benchmark.references.dense_decoder import (
    HIGHEST,
    _sumsq,
    adamw_leaf,
    norms_by_leaf,
    rms_norm,
)
from benchmark.references.glm_decoder import (
    MTP_LEAVES,
    TOP_LEAVES,
    head_row,
    merge_row,
)
from benchmark.references.nemotron_h_decoder import OPERANDS, _layer_bwd

KINDS = nh.KINDS
LEAVES = dict(nh.LEAVES, moe=(
    "norm", "router", "router_bias", "latent_in", "latent_out", "w_up",
    "w_down", "shared_up", "shared_down"))
FAULTS = ("no_routed", "mtp_ignored", "state_reset", "unscaled_routed",
          "router_on_latent")


class Dims(nh.Dims):
    def __init__(self, cfg: dict):
        super().__init__(cfg)
        # n_group 1, topk_group 1: the group-limited choice is the identity
        assert (cfg["n_group"], cfg["topk_group"],
                cfg["norm_topk_prob"]) == (1, 1, True)
        self.latent = cfg["moe_latent_size"]
        # DEPARTURE: the weight of the module's loss is the configuration's
        self.mtp_weight = cfg["run"]["mtp_weight"]
        # (where its leaves are named, kind) of every layer; the module's
        # block last
        self.entries = [("layers", KINDS[c]) for c in self.pattern]
        self.main = len(self.entries)
        assert cfg["num_nextn_predict_layers"] == 1
        self.entries += [("mtp/block", KINDS[c])
                         for c in cfg["mtp_hybrid_override_pattern"]]
        self.kinds = [kind for _, kind in self.entries]


def route(u, latent, w, dims: Dims, fault=None):
    """(every expert's score, the experts chosen) for normed rows u."""
    logits = jnp.matmul(u, w["router"], precision=HIGHEST)
    if fault == "router_on_latent":
        logits = jnp.matmul(latent, w["router"][:dims.latent],
                            precision=HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, chosen = lax.top_k(scores + w["router_bias"], dims.top_k)
    return scores, chosen


def drawn_row(x, w, dims: Dims, fault=None):
    """How many of one sequence's tokens chose each expert of the
    router's width, in one ``E`` layer whose input is x."""
    u = rms_norm(x, w["norm"], dims.eps)
    latent = jnp.matmul(u, w["latent_in"], precision=HIGHEST)
    _, chosen = route(u, latent, w, dims, fault)
    return (chosen[..., None] == jnp.arange(dims.router_width)).sum((0, 1))


def moe_row(x, w, dims: Dims, operands, fault=None):
    """One ``E`` layer on one sequence: x [seq, hidden]."""
    operand, _ = operands

    def mm(a, b):
        return jnp.matmul(operand(a), operand(b), precision=HIGHEST)

    def relu2(u, up, down):
        return mm(jnp.square(jax.nn.relu(mm(u, up))), down)

    u = rms_norm(x, w["norm"], dims.eps)
    latent = mm(u, w["latent_in"])
    scores, chosen = route(u, latent, w, dims, fault)
    gates = jnp.take_along_axis(scores, chosen, axis=-1)
    gates = gates / gates.sum(-1, keepdims=True) * (
        1.0 if fault == "unscaled_routed" else dims.scale)
    out = x + relu2(u, w["shared_up"], w["shared_down"])
    if fault == "no_routed":
        return out

    # DEPARTURE: the loop is over the experts held here alone; what the
    # absent experts would add is left out, and what the held ones give
    # goes through the whole map back
    @jax.checkpoint
    def expert(acc, e_up_down):
        e, up, down = e_up_down
        weight = jnp.where(chosen == dims.held_first + e, gates, 0.0).sum(-1)
        return acc + weight[:, None] * relu2(latent, up, down), None

    routed, _ = lax.scan(expert, jnp.zeros_like(latent),
                         (jnp.arange(dims.held), w["w_up"], w["w_down"]))
    return out + mm(routed, w["latent_out"])


def mamba_row(x, w, dims: Dims, operands, fault=None):
    """One ``M`` layer on one sequence, x [seq, hidden]:
    ``nemotron_h_decoder.mamba_row`` a group of heads at a time, each
    group rebuilt in its backward. Under an operand rule other than
    float32 a rounding's scale is a group's tensor's, not the layer's."""
    seq = x.shape[0]
    h, p, g, n = dims.ssm_heads, dims.ssm_head_dim, dims.groups, dims.state
    ratio = h // g
    width = ratio * p                       # a group's lanes of x, z and y
    operand, inner = operands

    def mm(a, b):
        return jnp.matmul(operand(a), operand(b), precision=HIGHEST)

    def by_group(t, per):
        """[..., g * per] -> [g, ..., per]."""
        return jnp.moveaxis(t.reshape(*t.shape[:-1], g, per), -2, 0)

    def cut(t):
        """The convolution's channels -> x's, B's and C's, by group."""
        xs, bm, cm = jnp.split(t, [dims.inner, dims.inner + g * n], axis=-1)
        return by_group(xs, width), by_group(bm, n), by_group(cm, n)

    z, xbc, dt = jnp.split(
        mm(rms_norm(x, w["norm"], dims.eps), w["w_in"]),
        [dims.inner, 2 * dims.inner + 2 * g * n], axis=-1)
    a = -jnp.exp(w["a_log"])

    def conv(t, taps, bias):
        # causal depthwise convolution: y_t = b + sum_j w_j x_{t-(K-1)+j}
        padded = jnp.pad(t, ((dims.conv - 1, 0), (0, 0)))
        return jax.nn.silu(bias + sum(
            taps[j] * padded[j:j + seq] for j in range(dims.conv)))

    @jax.checkpoint
    def group(mine):
        z_g, pre, taps, bias, dt_g, dt_bias, a_g, d_g, gate_norm = mine
        xs, bm, cm = (inner(conv(t, k, b))
                      for t, k, b in zip(pre, taps, bias))
        xs = xs.reshape(seq, ratio, p)
        dt_g = jax.nn.softplus(dt_g + dt_bias)  # time_step_limit (0, inf)

        def position(state, inputs):
            x_t, dt_t, b_t, c_t, t = inputs
            if fault == "state_reset":
                state = jnp.where(t % dims.chunk == 0, 0.0, state)
            # every head of the group reads the group's B and C
            state = jnp.exp(dt_t * a_g)[:, None, None] * state \
                + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
            # the carried state is an operand of its product with C
            return state, jnp.sum(inner(state) * c_t[None, None, :],
                                  axis=-1) + d_g[:, None] * x_t

        # blocks of positions, each rebuilt in its backward, only so that
        # the backward need not hold a state for every position
        rows = dims.chunk if seq % dims.chunk == 0 else seq

        @jax.checkpoint
        def block(state, inputs):
            return lax.scan(position, state, inputs)

        _, y = lax.scan(
            block, jnp.zeros((ratio, p, n), jnp.float32), jax.tree.map(
                lambda t: t.reshape(seq // rows, rows, *t.shape[1:]),
                (xs, dt_g, bm, cm, jnp.arange(seq))))
        gated = y.reshape(seq, width) * jax.nn.silu(z_g)
        gated = gated * lax.rsqrt(
            jnp.mean(gated * gated, axis=-1, keepdims=True) + dims.eps)
        return gated * gate_norm

    gated = lax.map(group, (
        by_group(z, width), cut(xbc), cut(w["conv_w"]), cut(w["conv_b"]),
        by_group(dt, ratio), by_group(w["dt_bias"], ratio),
        by_group(a, ratio), by_group(w["d"], ratio),
        by_group(w["gate_norm"], width)))
    return x + mm(jnp.moveaxis(gated, 0, 1).reshape(seq, dims.inner),
                  w["w_out"])


LAYER_ROW = {"mamba": mamba_row, "moe": moe_row,
             "attention": nh.attention_row}


class Model(glm_decoder.Model):
    """The jitted pieces for one configuration, operand rule and fault;
    ``loss_and_grads`` is ``glm_decoder.Model``'s (the stack, the head,
    the module's merge, block and head, both losses)."""

    def __init__(self, cfg: dict, operands=OPERANDS["float32"], fault=None):
        dims = self.dims = Dims(cfg)
        self.kinds = dims.kinds
        self.mtp_weight = 0.0 if fault == "mtp_ignored" else dims.mtp_weight
        self.layer_fwd, self.layer_bwd = {}, {}
        for kind in set(self.kinds):
            layer = functools.partial(LAYER_ROW[kind], dims=dims,
                                      operands=operands, fault=fault)
            self.layer_fwd[kind] = jax.jit(layer)
            self.layer_bwd[kind] = jax.jit(
                functools.partial(_layer_bwd, layer), donate_argnums=(3,))
        head = functools.partial(head_row, dims=dims, operand=operands[0])

        def head_bwd(x, norm, unembed, targets, weights, scale, acc):
            nll, vjp = jax.vjp(
                lambda x, n, u: head(x, n, u, targets, weights), x, norm,
                unembed)
            dx, dn, du = vjp(scale)
            return nll, dx, (acc[0] + dn, acc[1] + du)

        self.head_bwd = jax.jit(head_bwd, donate_argnums=(6,))
        merge = functools.partial(merge_row, dims=dims, operand=operands[0])
        self.merge = jax.jit(merge)

        def merge_bwd(x, e, w, dz, acc):
            _, vjp = jax.vjp(merge, x, e, w)
            dx, de, dw = vjp(dz)
            return dx, de, jax.tree.map(jnp.add, acc, dw)

        self.merge_bwd = jax.jit(merge_bwd, donate_argnums=(4,))
        self.embed_bwd = jax.jit(
            lambda acc, ids, dx: acc.at[ids].add(dx), donate_argnums=(0,))
        self.drawn = jax.jit(functools.partial(drawn_row, dims=dims,
                                               fault=fault))


def leaves(tree, dims: Dims):
    """(name as the comparison knows it, entry or None, key, array) of
    every leaf, in a fixed order: the name says where the program keeps
    the leaf (``layers/<kind>/<leaf>``, ``mtp/block/...``, ``mtp/<leaf>``
    for the module's own)."""
    for name in TOP_LEAVES:
        yield name, None, name, tree[name]
    for name in MTP_LEAVES:
        yield "mtp/" + name, None, name, tree[name]
    for l, ((where, kind), layer) in enumerate(zip(dims.entries,
                                                   tree["layers"])):
        for name in LEAVES[kind]:
            yield f"{where}/{kind}/{name}", l, name, layer[name]


def follow_two_steps(cfg: dict, hp: dict, initial_leaf, batches,
                     operands=OPERANDS["float32"], fault=None, against=None,
                     keep=False):
    """Two AdamW steps on ``batches[0]`` and ``batches[1]``, as
    ``glm_decoder.follow_two_steps`` returns them: each step's loss and
    raw global gradient norm, the norm of the first raw gradient by leaf,
    the norm of the parameters' change over the two steps by leaf (the
    leaves of one kind and place in the order of their layers), and
    ``loss_parts``, each step's ``loss_main`` and ``loss_mtp``.

    ``against(name, entry, key)``, where given, is somebody else's first
    raw gradient of that leaf in float32 (the program's, or for a control
    or a fault the float32 reference's): the norm of the DIFFERENCE of
    the two by leaf comes back as ``first_grad_diff``. ``keep`` hands the
    first gradient's leaves back on the host, ``first_grad_leaves``
    {(name, entry): array}, for a later call's ``against``.

    ``initial_leaf(name, entry)`` makes one float32 leaf of the starting
    point (``entry`` counts the layers, the module's block last; None
    for the embedding, the final norm, the head and the module's own
    four). Each is made twice."""
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
    # step fell short of an even draw; the module's router like the
    # layers' (it sees every position of a row). The bias has no gradient.
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

    weights = {name: initial_leaf(name, None)
               for name in TOP_LEAVES + MTP_LEAVES}
    weights["layers"] = [{name: initial_leaf(name, l)
                          for name in LEAVES[kind]}
                         for l, kind in enumerate(dims.kinds)]
    loss1, g1, parts1, drawn1 = model.loss_and_grads(weights, batches[0])
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
    loss2, g2, parts2, drawn2 = model.loss_and_grads(weights, batches[1])
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
                loss_parts=[parts1, parts2], first_grad=norms_by_leaf(sq1),
                change=norms_by_leaf(delta))
