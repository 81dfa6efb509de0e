"""Seeded weights of an LFM2 decoder's leaves with sparse experts
(model_type ``lfm2_moe``: gated short-convolution layers and QK-normed
GQA layers, ``num_dense_layers`` leading layers with a dense SwiGLU MLP,
sigmoid-routed SwiGLU experts in the others, the embedding tied to the
head), by the rule of ``benchmark/weights.py`` and the other families'
makers: every leaf of every layer has a key of its own (the seed, the
leaf's position in ``ALL_LEAVES``, the layer counted over the entries),
is drawn in float32 and rounded to the type it is trained in; the
reference gets the same values widened to float32.

The program lays a decoder layer out as two entries: ``C`` (a ``conv``
layer) or ``*`` (a ``full_attention`` one), then ``D`` (dense) or ``E``
(experts). ``entries`` lists them all with the tree of the program's
parameters each lies in: the leading layers' (``lead``), the periods'
(``layers``). Distributions: normal with standard deviation 0.02 for the
embedding and fan_in**-0.5 for every projection, the router, the experts
and the convolutions' taps (fan_in 3); norms at 1 (``q_norm`` and
``k_norm`` too), the correction bias at 0. float32
stay the norms, the taps, the router and its bias; the rest is rounded
to bfloat16 and kept in the configuration's ``torch_dtype``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark import flops_lfm2
from benchmark.weights import seed_key, token_batch  # noqa: F401

KINDS = {"C": "short_conv", "*": "attention", "D": "dense", "E": "moe"}
LEAVES = {
    "short_conv": ("norm", "w_in", "conv_w", "w_out"),
    "attention": ("attn_norm", "wq", "wk", "wv", "q_norm", "k_norm", "wo"),
    "dense": ("mlp_norm", "w_gate", "w_up", "w_down"),
    "moe": ("norm", "router", "router_bias", "w_gate", "w_up", "w_down"),
}
# the head is the embedding's (tie_word_embeddings)
TOP_LEAVES = ("embed", "final_norm")
MTP_LEAVES = ()  # the family has no prediction module
ALL_LEAVES = [(None, name) for name in TOP_LEAVES] + [
    (kind, name) for kind, names in LEAVES.items() for name in names]
FLOAT32 = {"router", "router_bias", "conv_w"}


def patterns_of(cfg: dict) -> dict:
    """{tree of the program's parameters: its layers' kinds}: the leading
    dense layers, the layers that repeat."""
    assert cfg["tie_word_embeddings"]
    kinds = flops_lfm2.pattern(cfg)
    lead = 2 * max(0, cfg["num_dense_layers"] - cfg["first_layer"])
    return {"lead": kinds[:lead], "layers": kinds[lead:]}


def entries(cfg: dict):
    """[(tree, kind)] of every entry, in the order the model runs them."""
    return [(where, KINDS[c]) for where, kinds in patterns_of(cfg).items()
            for c in kinds]


def leaf_shapes(cfg: dict) -> dict:
    """{kind (None for the top): {leaf: shape}}."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    d = h // cfg["num_attention_heads"]
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    held, f, i = (cfg["num_experts"], cfg["moe_intermediate_size"],
                  cfg["intermediate_size"])
    return {
        None: {"embed": (v, h), "final_norm": (h,)},
        "short_conv": {"norm": (h,), "w_in": (h, 3 * h),
                       "conv_w": (cfg["conv_L_cache"], h), "w_out": (h, h)},
        "attention": {"attn_norm": (h,), "wq": (h, q), "wk": (h, kv),
                      "wv": (h, kv), "q_norm": (d,), "k_norm": (d,),
                      "wo": (q, h)},
        "dense": {"mlp_norm": (h,), "w_gate": (h, i), "w_up": (h, i),
                  "w_down": (i, h)},
        "moe": {"norm": (h,), "router": (h, cfg["router_width"]),
                "router_bias": (cfg["router_width"],),
                "w_gate": (held, h, f), "w_up": (held, h, f),
                "w_down": (held, f, h)},
    }


def make_leaf(cfg: dict, key, kind, name: str, layer=None):
    """One leaf in the type it is trained in: of the top (``kind`` None)
    or of entry ``layer``, which is of ``kind``."""
    shape = leaf_shapes(cfg)[kind][name]
    if name.endswith("norm"):
        return jnp.ones(shape, jnp.float32)
    if name == "router_bias":
        return jnp.zeros(shape, jnp.float32)
    key = jax.random.fold_in(key, ALL_LEAVES.index((kind, name)))
    if layer is not None:
        key = jax.random.fold_in(key, layer)
    std = 0.02 if name == "embed" else shape[-2] ** -0.5
    value = jax.random.normal(key, shape, jnp.float32) * std
    return value if name in FLOAT32 else value.astype(jnp.bfloat16).astype(
        jnp.dtype(cfg.get("torch_dtype", "bfloat16")))


def make_stacked(cfg: dict, key) -> dict:
    """Every leaf as the program lays them out: in each tree each kind's
    leaves stacked over that kind's layers there, in their order."""
    out = {name: make_leaf(cfg, key, None, name) for name in TOP_LEAVES}
    listed = list(enumerate(entries(cfg)))
    for where in patterns_of(cfg):
        mine = [(l, kind) for l, (tree, kind) in listed if tree == where]
        if mine:
            out[where] = {
                kind: {name: jnp.stack([make_leaf(cfg, key, kind, name, l)
                                        for l, k in mine if k == kind])
                       for name in LEAVES[kind]}
                for kind in dict.fromkeys(k for _, k in mine)}
    return out
