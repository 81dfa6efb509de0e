"""Plain reference of the GLM-4.7-Flash decoder (model_type
``glm4_moe_lite``) with its multi-token-prediction module, and of AdamW:
what ``config.json`` of GLM-4.7-Flash defines, the module as the
DeepSeek-V3 report (arXiv 2412.19437, section 2.2) defines the one the
family's checkpoints carry, and no further.

Every layer is ``h = x + Attn(RMSNorm(x))``, ``y = h + MLP(RMSNorm(h))``,
no biases, an untied head, mean next-token cross entropy.

- Latent attention (every layer), u the normed input: ``c_q =
  RMSNorm(u W_dq)``, ``q = c_q W_uq``, a head of ``qk_nope_head_dim``
  dimensions without position and then ``qk_rope_head_dim`` rotated ones;
  ``[c_kv; k_r] = u W_dkv``, ``c_kv`` normed, ``[k_n; v] = c_kv W_ukv``
  head by head; the head's key is ``[k_n; rot(k_r)]``, the same ``k_r``
  in every head; logits scaled by (nope + rope)**-0.5, a DENSE causal
  softmax over an explicitly built mask, a block of query rows at a time
  so that 8192 keys fit. The plain rotary table ``theta**(-2i/d)`` over
  the rotated dimensions, pairs (i, i + d/2).
- Layer 0 (``first_k_dense_replace``): SwiGLU of ``intermediate_size``.
- The other layers: ``s = sigmoid(u W_r)``, the ``num_experts_per_tok``
  largest of ``s + b`` chosen (``n_group`` 1, ``topk_group`` 1: no group
  step), their ``s`` over their own sum times ``routed_scaling_factor``
  as weights, each expert ``W_down (silu(W_gate u) * W_up u)``, one shared
  expert of the same form added unweighed. A loop over the experts with
  dense masks: every token goes through every held expert and is weighed
  by nought where it was not chosen.
- The module: ``z_i = [RMSNorm(Emb(t_{i+1})); RMSNorm(x_i)] W_eh`` with
  ``x_i`` the stack's output after its final norm, one block of the
  module's own weights (latent attention, experts) over all the
  positions, ``RMSNorm`` of its own, the model's head asked for
  ``t_{i+2}``; the last position has no such token and weighs nought
  (its routing is counted). ``L = L_main + mtp_weight L_mtp``, each a
  mean over its own positions.

float32 throughout, every product at ``lax.Precision.HIGHEST``. It
imports nothing of ray_tpu; weights and batches come from the
benchmark's own seeded makers. One batch row and one layer at a time,
each layer recomputed in its backward; AdamW and the operand rules are
``dense_decoder.py``'s and ``nemotron_h_decoder.py``'s as they are (an
operand rule is a pair: what rounds both operands of a projection or an
expert's product, and what rounds q, k, v of attention's products).

Departures from the published description, each marked DEPARTURE at its
line: the chip's share of an 8-way expert-parallel deployment (the experts
held and the vocabulary slice are the configuration's, the same as the
program's); the correction bias follows the update rule the configuration
assumes (``run.router_bias_rate``); the loss's weight is the
configuration's ``run.mtp_weight``.

``FAULTS`` are this model's planted faults, for the limits of the
comparison: the module's loss left out (``mtp_ignored``), all of a head's
dimensions rotated (``rope_over_whole_head``), the two latents not normed
(``latent_norms_ignored``), the routed experts left out (``no_routed``).
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
    norms_by_leaf,
    rms_norm,
)
from benchmark.references.nemotron_h_decoder import OPERANDS, _layer_bwd

LEAVES = {
    "latent": ("attn_norm", "w_dq", "q_norm", "w_uq", "w_dkv", "kv_norm",
               "w_ukv", "wo"),
    "dense": ("mlp_norm", "w_gate", "w_up", "w_down"),
    "moe": ("norm", "router", "router_bias", "w_gate", "w_up", "w_down",
            "shared_gate", "shared_up", "shared_down"),
}
TOP_LEAVES = ("embed", "final_norm", "unembed")
MTP_LEAVES = ("enorm", "hnorm", "eh_proj", "head_norm")
FAULTS = ("mtp_ignored", "rope_over_whole_head", "latent_norms_ignored",
          "no_routed")
QUERY_ROWS = 1024
HEADS_AT_ONCE = 4
HEAD_ROWS = 512


class Dims:
    def __init__(self, cfg: dict):
        self.hidden = cfg["hidden_size"]
        self.eps = cfg["rms_norm_eps"]
        self.vocab = cfg["vocab_size"]
        self.heads = cfg["num_attention_heads"]
        self.q_rank = cfg["q_lora_rank"]
        self.kv_rank = cfg["kv_lora_rank"]
        self.nope = cfg["qk_nope_head_dim"]
        self.rope = cfg["qk_rope_head_dim"]
        self.v_dim = cfg["v_head_dim"]
        self.theta = float(cfg["rope_theta"])
        assert (cfg["rope_scaling"], cfg["partial_rotary_factor"]) == (None, 1)
        # n_group 1, topk_group 1: the group-limited choice is the identity
        assert (cfg["n_group"], cfg["topk_group"]) == (1, 1)
        # DEPARTURE: ``n_routed_experts`` counts the experts held here
        # (``experts_held_first`` onwards), the router keeps its published
        # width ``router_width``
        self.router_width = cfg["router_width"]
        self.held_first = cfg["experts_held_first"]
        self.held = cfg["n_routed_experts"]
        self.top_k = cfg["num_experts_per_tok"]
        self.scale = cfg["routed_scaling_factor"]
        # DEPARTURE: the weight of the module's loss is the configuration's
        self.mtp_weight = cfg["run"]["mtp_weight"]
        # (where its leaves are named, kind) of every entry: a layer's
        # attention, then its MLP; the module's block last
        dense, layers = cfg["first_k_dense_replace"], cfg["num_hidden_layers"]
        self.entries = [("lead", kind) for _ in range(dense)
                        for kind in ("latent", "dense")]
        self.entries += [("layers", kind) for _ in range(layers - dense)
                         for kind in ("latent", "moe")]
        self.main = len(self.entries)
        assert cfg["num_nextn_predict_layers"] == 1
        self.entries += [("mtp/block", "latent"), ("mtp/block", "moe")]
        self.kinds = [kind for _, kind in self.entries]


def rotary(x, theta: float):
    """x [seq, heads, d]: rotate the pair (i, i + d/2) by position *
    theta**(-2i/d)."""
    seq, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def latent_row(x, w, dims: Dims, operands, fault=None):
    """One latent attention block on one sequence: x [seq, hidden]."""
    seq = x.shape[0]
    operand, inner = operands
    heads, nope, d = dims.heads, dims.nope, dims.nope + dims.rope
    rows = QUERY_ROWS if seq % QUERY_ROWS == 0 else seq
    at_once = HEADS_AT_ONCE if heads % HEADS_AT_ONCE == 0 else 1

    def mm(a, b):
        return jnp.matmul(operand(a), operand(b), precision=HIGHEST)

    def latent_norm(c, weight):
        if fault == "latent_norms_ignored":
            return c
        return rms_norm(c, weight, dims.eps)

    u = rms_norm(x, w["attn_norm"], dims.eps)
    q = mm(latent_norm(mm(u, w["w_dq"]), w["q_norm"]),
           w["w_uq"]).reshape(seq, heads, d)
    c_kv, k_r = jnp.split(mm(u, w["w_dkv"]), [dims.kv_rank], axis=-1)
    k_n, v = jnp.split(
        mm(latent_norm(c_kv, w["kv_norm"]), w["w_ukv"]).reshape(
            seq, heads, nope + dims.v_dim), [nope], axis=-1)
    # the one key that carries the position, the same in every head
    k_r = jnp.broadcast_to(k_r[:, None, :], (seq, heads, dims.rope))
    if fault == "rope_over_whole_head":
        q = rotary(q, dims.theta)
        k = rotary(jnp.concatenate([k_n, k_r], -1), dims.theta)
    else:
        q = jnp.concatenate(
            [q[..., :nope], rotary(q[..., nope:], dims.theta)], -1)
        k = jnp.concatenate([k_n, rotary(k_r, dims.theta)], -1)
    q, k, v = inner(q), inner(k), inner(v)

    def some_heads(qkv):
        qg, kg, vg = qkv  # [blocks, rows, g, d], [seq, g, d], [seq, g, d]

        @jax.checkpoint
        def one_block(block):
            qb, first = block
            scores = jnp.einsum("rgd,tgd->grt", qb, kg,
                                precision=HIGHEST) * d ** -0.5
            causal = (first + jnp.arange(rows))[:, None] \
                >= jnp.arange(seq)[None, :]
            p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
            return jnp.einsum("grt,tgd->rgd", p, vg, precision=HIGHEST)

        return lax.map(one_block, (qg, jnp.arange(0, seq, rows)))

    def grouped(t):
        """[seq, heads, d] -> [groups, seq, heads at once, d]."""
        return t.reshape(seq, heads // at_once, at_once, -1).transpose(
            1, 0, 2, 3)

    out = lax.map(some_heads, (
        grouped(q).reshape(heads // at_once, seq // rows, rows, at_once, d),
        grouped(k), grouped(v)))    # [groups, blocks, rows, g, v_dim]
    out = out.reshape(heads // at_once, seq, at_once, dims.v_dim).transpose(
        1, 0, 2, 3).reshape(seq, heads * dims.v_dim)
    return x + mm(out, w["wo"])


def dense_row(x, w, dims: Dims, operands, fault=None):
    """The leading layers' MLP: SwiGLU of ``intermediate_size``."""
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
    router's width, in one expert layer whose input is x."""
    _, chosen = route(rms_norm(x, w["norm"], dims.eps), w, dims)
    return (chosen[..., None] == jnp.arange(dims.router_width)).sum((0, 1))


def moe_row(x, w, dims: Dims, operands, fault=None):
    """One layer's mixture of experts on one sequence."""
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


LAYER_ROW = {"latent": latent_row, "dense": dense_row, "moe": moe_row}


def head_row(x, norm, unembed, targets, weights, dims: Dims, operand):
    """Summed negative log likelihood of ``targets`` over one sequence's
    positions, each weighed by ``weights`` (1, or 0 for a position that is
    not asked), from hidden states that ``norm`` norms first."""
    seq = x.shape[0]
    rows = HEAD_ROWS if seq % HEAD_ROWS == 0 else seq

    @jax.checkpoint
    def block(xtw):
        xb, tb, wb = xtw
        logits = jnp.matmul(operand(rms_norm(xb, norm, dims.eps)),
                            operand(unembed), precision=HIGHEST)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -(jnp.take_along_axis(logp, tb[:, None], axis=-1)[:, 0]
                 * wb).sum()

    return lax.map(block, (x.reshape(seq // rows, rows, -1),
                           targets.reshape(seq // rows, rows),
                           weights.reshape(seq // rows, rows))).sum()


def merge_row(x, e, w, dims: Dims, operand):
    """What the module's block reads: position i's hidden state after the
    model's final norm and the embedding of token i + 1, each normed,
    joined by one projection (the embedding's half first)."""
    h = rms_norm(x, w["final_norm"], dims.eps)
    joined = jnp.concatenate([rms_norm(e, w["enorm"], dims.eps),
                              rms_norm(h, w["hnorm"], dims.eps)], axis=-1)
    return jnp.matmul(operand(joined), operand(w["eh_proj"]),
                      precision=HIGHEST)


MERGE_LEAVES = ("final_norm", "enorm", "hnorm", "eh_proj")


class Model:
    """The jitted pieces for one configuration, operand rule and fault."""

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
        self.drawn = jax.jit(functools.partial(drawn_row, dims=dims))

    def loss_and_grads(self, weights, tokens):
        """``L_main + mtp_weight L_mtp`` of ``tokens`` [rows, seq + 1], its
        gradient in the layout of ``weights`` (the entries as a list), the
        two parts of the loss, and for each expert layer the tokens that
        chose each expert {entry: [width]}."""
        tokens = np.asarray(tokens)
        n_rows, seq = tokens.shape[0], tokens.shape[1] - 1
        dims, lam = self.dims, self.mtp_weight
        layers = weights["layers"]
        main_scale = jnp.float32(1.0 / (n_rows * seq))
        # the module's loss is a mean over the positions that have a
        # token after the next: all but a row's last
        asked = jnp.arange(seq) < seq - 1
        ahead_scale = jnp.float32(lam / (n_rows * (seq - 1)))
        grads = {name: jnp.zeros_like(weights[name])
                 for name in TOP_LEAVES + MTP_LEAVES}
        grads["layers"] = [jax.tree.map(jnp.zeros_like, w) for w in layers]
        main_acc = (grads.pop("final_norm"), grads.pop("unembed"))
        ahead_acc = (grads.pop("head_norm"), jnp.zeros_like(main_acc[1]))
        merge_acc = {name: (jnp.zeros_like(weights[name])
                            if name == "final_norm" else grads.pop(name))
                     for name in MERGE_LEAVES}
        merge_w = {name: weights[name] for name in MERGE_LEAVES}
        main_nll, ahead_nll, drawn = [], [], {}

        def through(x, entries, inputs):
            for l in entries:
                kind, w = self.kinds[l], layers[l]
                inputs[l] = x
                if kind == "moe":
                    drawn[l] = drawn.get(l, 0) + self.drawn(x, w)
                x = self.layer_fwd[kind](x, w)
            return x

        def back(dx, entries, inputs):
            for l in reversed(entries):
                dx, grads["layers"][l] = self.layer_bwd[self.kinds[l]](
                    inputs.pop(l), layers[l], dx, grads["layers"][l])
            return dx

        stack = range(dims.main)
        module = range(dims.main, len(self.kinds))
        for r in range(n_rows):
            ids = jnp.asarray(tokens[r, :-1])
            nexts = jnp.asarray(tokens[r, 1:])
            inputs = {}
            x = through(jnp.take(weights["embed"], ids, axis=0), stack,
                        inputs)
            nll, dx, main_acc = self.head_bwd(
                x, weights["final_norm"], weights["unembed"], nexts,
                jnp.ones((seq,), jnp.float32), main_scale, main_acc)
            main_nll.append(nll)
            # the module runs (and its routers count) whatever its loss
            # weighs: mtp_ignored takes the loss away, not the layers
            e = jnp.take(weights["embed"], nexts, axis=0)
            z = through(self.merge(x, e, merge_w), module, inputs)
            after = jnp.asarray(np.append(tokens[r, 2:], 0))
            nll, dz, ahead_acc = self.head_bwd(
                z, weights["head_norm"], weights["unembed"], after,
                asked.astype(jnp.float32), ahead_scale, ahead_acc)
            ahead_nll.append(nll)
            dz = back(dz, module, inputs)
            dx_ahead, de, merge_acc = self.merge_bwd(x, e, merge_w, dz,
                                                     merge_acc)
            grads["embed"] = self.embed_bwd(grads["embed"], nexts, de)
            dx = back(dx + dx_ahead, stack, inputs)
            grads["embed"] = self.embed_bwd(grads["embed"], ids, dx)
        grads["final_norm"] = main_acc[0] + merge_acc.pop("final_norm")
        grads["unembed"] = main_acc[1] + ahead_acc[1]
        grads["head_norm"] = ahead_acc[0]
        grads.update(merge_acc)
        loss_main = float(sum(float(n) for n in main_nll)) / (n_rows * seq)
        loss_mtp = float(sum(float(n) for n in ahead_nll)) / (
            n_rows * (seq - 1))
        return (loss_main + lam * loss_mtp, grads,
                {"loss_main": loss_main, "loss_mtp": loss_mtp}, drawn)


def leaves(tree, dims: Dims):
    """(name as the comparison knows it, entry or None, key, array) of
    every leaf, in a fixed order: the name says where the program keeps
    the leaf (``lead/<kind>/<leaf>``, ``layers/...``, ``mtp/block/...``,
    ``mtp/<leaf>`` for the module's own)."""
    for name in TOP_LEAVES:
        yield name, None, name, tree[name]
    for name in MTP_LEAVES:
        yield "mtp/" + name, None, name, tree[name]
    for l, ((where, kind), layer) in enumerate(zip(dims.entries,
                                                   tree["layers"])):
        for name in LEAVES[kind]:
            yield f"{where}/{kind}/{name}", l, name, layer[name]


def follow_two_steps(cfg: dict, hp: dict, initial_leaf, batches,
                     operands=OPERANDS["float32"], fault=None):
    """Two AdamW steps on ``batches[0]`` and ``batches[1]``, as
    ``nemotron_h_decoder.follow_two_steps`` returns them: each step's
    loss and raw global gradient norm, the norm of the first raw gradient
    by leaf, the norm of the parameters' change over the two steps by
    leaf (the leaves of one kind and place in the order of their layers);
    and ``loss_parts``, each step's ``loss_main`` and ``loss_mtp``.

    ``initial_leaf(name, entry)`` makes one float32 leaf of the starting
    point (``entry`` counts the entries, two a decoder layer, the
    module's block last; None for the embedding, the final norm, the head
    and the module's own four). Each is made twice."""
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
    return {"loss": [loss1, loss2], "grad_norm": [gnorm1, gnorm2],
            "loss_parts": [parts1, parts2],
            "first_grad": norms_by_leaf(sq1), "change": norms_by_leaf(delta)}
