"""Plain reference of the Mellum decoder (model_type ``mellum``) and of
AdamW: what ``config.json`` of Mellum2-12B-A2.5B-Instruct defines, and
no further.

Every layer is ``h = x + Attn(RMSNorm(x))``, ``y = h + MoE(RMSNorm(h))``,
no biases, an untied head, mean next-token cross entropy.

- Attention: grouped-query (query head h reads key/value head h //
  group), logits scaled by head_dim**-0.5, a DENSE softmax over an
  explicitly built mask, a block of query rows at a time so that 8192
  keys fit. ``layer_types`` says which mask and which rotary table a
  layer takes. ``sliding_attention``: query i sees the keys j with
  i - sliding_window < j <= i; the plain table ``theta**(-2i/d)``.
  ``full_attention``: causal over the whole sequence; YaRN's table
  (``yarn_inverse_frequencies``) with cos and sin both multiplied by
  ``attention_factor``. Rotation is of the pairs (i, i + d/2).
- Mixture of experts (every layer): ``p = softmax(u W_r)`` over the
  router's width, the ``num_experts_per_tok`` largest chosen, weights
  ``p_e`` over the chosen ones' sum (``norm_topk_prob``), each expert
  ``W_down (silu(W_gate u) * W_up u)``; no shared expert, no bias, no
  scale. A loop over the experts with dense masks: every token goes
  through every held expert and is weighed by nought where it was not
  chosen.

float32 throughout, every product at ``lax.Precision.HIGHEST``. It
imports nothing of ray_tpu; weights and batches come from the
benchmark's own seeded makers. One batch row and one layer at a time,
each layer recomputed in its backward; head, AdamW and the operand rules
are ``dense_decoder.py``'s and ``nemotron_h_decoder.py``'s as they are
(an operand rule is a pair: what rounds both operands of a projection or
an expert's product, and what rounds q, k, v of attention's products).

Departures from the published description, each marked DEPARTURE at its
line: the chip's share of a 4-way expert-parallel deployment (the experts
held and the vocabulary slice are the configuration's, the same as the
program's). Not built, because ``config.json`` does not define it: the
MTP head.

``FAULTS`` are this model's planted faults, for the limits of the
comparison: every layer causal over the whole sequence
(``window_ignored``), the full layers with the plain rotary table and no
factor (``plain_rope``), the routed experts left out (``no_routed``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.references import nemotron_h_decoder
from benchmark.references.dense_decoder import (
    HIGHEST,
    _sumsq,
    adamw_leaf,
    head_row,
    norms_by_leaf,
    rms_norm,
)
from benchmark.references.nemotron_h_decoder import OPERANDS  # noqa: F401

KIND_OF = {"sliding_attention": "window", "full_attention": "attention"}
ATTENTION_LEAVES = ("attn_norm", "wq", "wk", "wv", "wo")
LEAVES = {"window": ATTENTION_LEAVES, "attention": ATTENTION_LEAVES,
          "moe": ("norm", "router", "w_gate", "w_up", "w_down")}
TOP_LEAVES = ("embed", "final_norm", "unembed")
FAULTS = ("window_ignored", "plain_rope", "no_routed")
QUERY_ROWS = 1024


class Dims:
    def __init__(self, cfg: dict):
        self.hidden = cfg["hidden_size"]
        self.eps = cfg["rms_norm_eps"]
        self.vocab = cfg["vocab_size"]
        self.heads = cfg["num_attention_heads"]
        self.kv_heads = cfg["num_key_value_heads"]
        self.head_dim = cfg["head_dim"]
        self.window = cfg["sliding_window"]
        self.rope = cfg["rope_parameters"]
        # one entry for a layer's attention, one for its experts
        self.kinds = []
        for attention, mlp in zip(cfg["layer_types"],
                                  cfg["mlp_layer_types"]):
            if mlp != "sparse":
                raise ValueError(f"no layer of kind {mlp!r} in this model")
            self.kinds += [KIND_OF[attention], "moe"]
        # DEPARTURE: ``num_experts`` counts the experts held here
        # (``experts_held_first`` onwards), the router keeps its published
        # width ``router_width``
        self.router_width = cfg["router_width"]
        self.held_first = cfg["experts_held_first"]
        self.held = cfg["num_experts"]
        self.top_k = cfg["num_experts_per_tok"]


def yarn_inverse_frequencies(d: int, theta: float, factor: float,
                             original: int, beta_fast: float,
                             beta_slow: float):
    """YaRN (Peng et al. 2023): ``inv_i = theta**(-2i/d)``; with
    ``low = floor(d ln(original / (beta_fast 2 pi)) / (2 ln theta))`` and
    ``high = ceil(d ln(original / (beta_slow 2 pi)) / (2 ln theta))``,
    both clamped to [0, d/2 - 1], ``ramp_i = clip((i - low) / (high -
    low), 0, 1)`` and ``inv'_i = inv_i (1 - ramp_i) + inv_i / factor
    ramp_i``."""
    i = np.arange(d // 2, dtype=np.float64)
    inv = theta ** (-2.0 * i / d)

    def pair(turns):
        return d * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = min(max(math.floor(pair(beta_fast)), 0), d // 2 - 1)
    high = min(max(math.ceil(pair(beta_slow)), 0), d // 2 - 1)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return inv * (1.0 - ramp) + inv / factor * ramp


def rotary(x, inv, factor: float = 1.0):
    """x [seq, heads, head_dim]: rotate the pair (i, i + head_dim/2) by
    position * inv_i; cos and sin both times ``factor``."""
    seq, _, d = x.shape
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv, jnp.float32)[None, :]
    cos = factor * jnp.cos(angle)[:, None, :]
    sin = factor * jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def rope_table(dims: Dims, kind: str, fault=None):
    """(inverse frequencies, factor on cos and sin) of a layer of
    ``kind``, from the configuration's ``rope_parameters``."""
    d = dims.head_dim
    section = dims.rope[
        "sliding_attention" if kind == "window" else "full_attention"]
    theta = float(section["rope_theta"])
    if section["rope_type"] == "default" or fault == "plain_rope":
        return theta ** (-2.0 * np.arange(d // 2, dtype=np.float64) / d), 1.0
    assert section["rope_type"] == "yarn", section
    return yarn_inverse_frequencies(
        d, theta, section["factor"],
        section["original_max_position_embeddings"], section["beta_fast"],
        section["beta_slow"]), section["attention_factor"]


def attention_row(x, w, dims: Dims, operands, kind: str, fault=None):
    """One attention layer on one sequence: x [seq, hidden]."""
    seq = x.shape[0]
    operand, inner = operands
    d, kv, g = dims.head_dim, dims.kv_heads, dims.heads // dims.kv_heads
    rows = QUERY_ROWS if seq % QUERY_ROWS == 0 else seq
    windowed = kind == "window" and fault != "window_ignored"
    inv, factor = rope_table(dims, kind, fault)

    def mm(a, b):
        return jnp.matmul(operand(a), operand(b), precision=HIGHEST)

    xn = rms_norm(x, w["attn_norm"], dims.eps)
    q = inner(rotary(mm(xn, w["wq"]).reshape(seq, kv * g, d), inv, factor)
              ).reshape(seq // rows, rows, kv, g, d)
    k = inner(rotary(mm(xn, w["wk"]).reshape(seq, kv, d), inv, factor))
    v = inner(mm(xn, w["wv"])).reshape(seq, kv, d)

    def one_group(qkv):
        qg, kg, vg = qkv  # [blocks, rows, g, d], [seq, d], [seq, d]

        @jax.checkpoint
        def one_block(block):
            qb, first = block
            scores = jnp.einsum("rgd,td->grt", qb, kg,
                                precision=HIGHEST) * d ** -0.5
            i = (first + jnp.arange(rows))[:, None]
            j = jnp.arange(seq)[None, :]
            seen = j <= i
            if windowed:
                seen = seen & (j > i - dims.window)
            p = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
            return jnp.einsum("grt,td->rgd", p, vg, precision=HIGHEST)

        return lax.map(one_block, (qg, jnp.arange(0, seq, rows)))

    out = lax.map(one_group, (q.transpose(2, 0, 1, 3, 4),
                              k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    out = out.transpose(1, 2, 0, 3, 4).reshape(seq, kv * g * d)
    return x + mm(out, w["wo"])


def route(u, w, dims: Dims):
    """(every expert's probability, the experts chosen) for normed rows."""
    p = jax.nn.softmax(jnp.matmul(u, w["router"], precision=HIGHEST), -1)
    _, chosen = lax.top_k(p, dims.top_k)
    return p, chosen


def drawn_row(x, w, dims: Dims):
    """How many of one sequence's tokens chose each expert of the
    router's width, in one expert layer whose input is x."""
    _, chosen = route(rms_norm(x, w["norm"], dims.eps), w, dims)
    return (chosen[..., None] == jnp.arange(dims.router_width)).sum((0, 1))


def moe_row(x, w, dims: Dims, operands, kind: str = "moe", fault=None):
    """One layer's mixture of experts on one sequence."""
    operand, _ = operands
    if fault == "no_routed":
        return x

    def mm(a, b):
        return jnp.matmul(operand(a), operand(b), precision=HIGHEST)

    u = rms_norm(x, w["norm"], dims.eps)
    p, chosen = route(u, w, dims)
    gates = jnp.take_along_axis(p, chosen, axis=-1)
    gates = gates / gates.sum(-1, keepdims=True)    # norm_topk_prob

    # DEPARTURE: the loop is over the experts held here alone; what the
    # absent experts would add is left out
    @jax.checkpoint
    def expert(acc, held):
        e, gate, up, down = held
        weight = jnp.where(chosen == dims.held_first + e, gates, 0.0).sum(-1)
        out = mm(jax.nn.silu(mm(u, gate)) * mm(u, up), down)
        return acc + weight[:, None] * out, None

    out, _ = lax.scan(expert, jnp.zeros_like(x), (
        jnp.arange(dims.held), w["w_gate"], w["w_up"], w["w_down"]))
    return x + out


LAYER_ROW = {"window": attention_row, "attention": attention_row,
             "moe": moe_row}


class Model(nemotron_h_decoder.Model):
    """The jitted pieces for one configuration, operand rule and fault;
    ``loss_and_grads`` is the hybrid reference's (one row and one layer
    at a time, by ``self.kinds``)."""

    def __init__(self, cfg: dict, operands=OPERANDS["float32"], fault=None):
        dims = self.dims = Dims(cfg)
        self.kinds = dims.kinds
        self.layer_fwd, self.layer_bwd = {}, {}
        for kind in set(self.kinds):
            layer = functools.partial(LAYER_ROW[kind], dims=dims,
                                      operands=operands, kind=kind,
                                      fault=fault)
            self.layer_fwd[kind] = jax.jit(layer)
            self.layer_bwd[kind] = jax.jit(
                functools.partial(nemotron_h_decoder._layer_bwd, layer),
                donate_argnums=(3,))
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


def leaves(tree, kinds):
    """(name, layer or None, array) of every leaf, in a fixed order;
    a layer's leaf is named ``layers/<kind>/<leaf>``."""
    for name in TOP_LEAVES:
        yield name, None, tree[name]
    for l, (kind, layer) in enumerate(zip(kinds, tree["layers"])):
        for name in LEAVES[kind]:
            yield f"layers/{kind}/{name}", l, layer[name]


def follow_two_steps(cfg: dict, hp: dict, initial_leaf, batches,
                     operands=OPERANDS["float32"], fault=None):
    """Two AdamW steps on ``batches[0]`` and ``batches[1]``, as
    ``nemotron_h_decoder.follow_two_steps`` returns them: each step's
    loss and raw global gradient norm, the norm of the first raw gradient
    by leaf, the norm of the parameters' change over the two steps by
    leaf (the leaves of one kind in the order of their layers).

    ``initial_leaf(name, layer)`` makes one float32 leaf of the starting
    point (``layer`` counts the entries of the pattern, two a decoder
    layer; None for the embedding, the final norm and the head). Each is
    made twice. No balancing term: nothing but AdamW moves a leaf."""
    model = Model(cfg, operands, fault)
    kinds = model.kinds
    set_leaf = nemotron_h_decoder.set_leaf
    kw = dict(b1=hp["b1"], b2=hp["b2"], eps=hp["eps"],
              wd=hp["weight_decay"])

    def lr(step):
        """The learning rate of step ``step`` (from 1): the configuration
        assumes a linear warm-up over ``warmup_steps`` steps."""
        return hp["learning_rate"] * min(
            1.0, step / max(1, hp.get("warmup_steps", 0)))

    def clip_scale(gnorm):
        return jnp.float32(min(1.0, hp["grad_clip"] / max(gnorm, 1e-30)))

    def parameter(weights, name, layer):
        key = name.split("/")[-1]
        return key, (weights if layer is None
                     else weights["layers"][layer])[key]

    weights = {name: initial_leaf(name, None) for name in TOP_LEAVES}
    weights["layers"] = [{name: initial_leaf(name, l)
                          for name in LEAVES[kind]}
                         for l, kind in enumerate(kinds)]
    loss1, g1, _ = model.loss_and_grads(weights, batches[0])
    sq1 = [(n, l, _sumsq(g)) for n, l, g in leaves(g1, kinds)]
    gnorm1 = float(np.sqrt(sum(float(s) for _, _, s in sq1)))
    # step 1: moments start at nought, so they follow from g1 alone; the
    # gradient goes to the host until step 2 needs it
    host_g1 = {}
    for name, layer, g in list(leaves(g1, kinds)):
        _, p = parameter(weights, name, layer)
        zero = jnp.zeros_like(g)
        p, _, _ = adamw_leaf(p, g, zero, zero, clip_scale(gnorm1), step=1,
                             lr=lr(1), **kw)
        set_leaf(weights, name, layer, p)
        host_g1[name, layer] = np.asarray(g)
        set_leaf(g1, name, layer, None)
        del g, zero
    loss2, g2, _ = model.loss_and_grads(weights, batches[1])
    gnorm2 = float(np.sqrt(sum(float(_sumsq(g))
                               for _, _, g in leaves(g2, kinds))))
    delta = []
    for name, layer, g in list(leaves(g2, kinds)):
        key, p = parameter(weights, name, layer)
        g_first = jnp.asarray(host_g1.pop((name, layer))) * clip_scale(gnorm1)
        m1, v1 = (1 - kw["b1"]) * g_first, (1 - kw["b2"]) * g_first * g_first
        p, _, _ = adamw_leaf(p, g, m1, v1, clip_scale(gnorm2), step=2,
                             lr=lr(2), **kw)
        delta.append((name, layer, _sumsq(p - initial_leaf(key, layer))))
        set_leaf(weights, name, layer, None)
        set_leaf(g2, name, layer, None)
        del p, g, g_first, m1, v1
    return {"loss": [loss1, loss2], "grad_norm": [gnorm1, gnorm2],
            "first_grad": norms_by_leaf(sq1), "change": norms_by_leaf(delta)}
