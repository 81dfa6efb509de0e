"""Seeded weights of a Solar-Open2 decoder's leaves (model_type
``solar_open2``: Kimi Delta Attention in three layers of four, gated
softmax attention without position in the fourth, sigmoid-routed SwiGLU
experts with a shared expert in every layer), by the rule of
``benchmark/weights.py`` and the other families' makers: every leaf of
every layer has a key of its own (the seed, the leaf's position in
``ALL_LEAVES``, the layer counted over the entries), is drawn in float32
and rounded to the type it is trained in; the reference gets the same
values widened to float32.

The program lays a decoder layer out as two entries: ``*`` (a layer of
``gqa_layers``) or ``K``, then ``E``. Distributions: normal with standard
deviation 0.02 for the embedding and fan_in**-0.5 for every projection,
the router, the experts and the convolutions' taps (fan_in 4); norms at
1, the correction bias at 0; the decay's own as the Kimi Linear family
starts them (which is Mamba-2's way): ``dt`` log-uniform in
[``DT_MIN``, ``DT_MAX``] floored at ``DT_FLOOR`` with ``dt_bias`` its
inverse softplus, a channel each; ``A`` uniform in [1, 16], a head each.
float32 stay the norms, ``A_log``, ``dt_bias``, the taps, the router and
its bias; the rest is rounded to bfloat16 and kept in the configuration's
``torch_dtype``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark import flops_solar
from benchmark.weights import seed_key, token_batch  # noqa: F401

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
MTP_LEAVES = ()  # the family has no prediction module
ALL_LEAVES = [(None, name) for name in TOP_LEAVES] + [
    (kind, name) for kind, names in LEAVES.items() for name in names]
FLOAT32 = {"router", "router_bias", "conv_w", "dt_bias", "a_log"}
DT_MIN, DT_MAX, DT_FLOOR = 1e-3, 1e-1, 1e-4


def patterns_of(cfg: dict) -> dict:
    """{tree of the program's parameters: its layers' kinds}."""
    assert cfg["first_k_dense_replace"] == 0
    return {"layers": flops_solar.pattern(cfg)}


def entries(cfg: dict):
    """[(tree, kind)] of every entry, in the order the model runs them."""
    return [(where, KINDS[c]) for where, kinds in patterns_of(cfg).items()
            for c in kinds]


def leaf_shapes(cfg: dict) -> dict:
    """{kind (None for the top): {leaf: shape}}."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    lin = cfg["linear_attn_config"]
    heads, hd = lin["num_heads"], lin["head_dim"]
    inner, rank = heads * hd, hd   # the gates' rank is a head's width
    held, f = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    shared = cfg["n_shared_experts"] * f
    return {
        None: {"embed": (v, h), "final_norm": (h,), "unembed": (h, v)},
        "attention": {"attn_norm": (h,), "wq": (h, q), "wk": (h, kv),
                      "wv": (h, kv), "w_gate": (h, q), "wo": (q, h)},
        "kda": {"norm": (h,), "w_qkv": (h, 3 * inner),
                "conv_w": (lin["short_conv_kernel_size"], 3 * inner),
                "w_decay_down": (h, rank), "w_decay_up": (rank, inner),
                "dt_bias": (inner,), "a_log": (heads,),
                "w_beta": (h, heads), "w_gate_down": (h, rank),
                "w_gate_up": (rank, inner), "head_norm": (hd,),
                "w_out": (inner, h)},
        "moe": {"norm": (h,), "router": (h, cfg["router_width"]),
                "router_bias": (cfg["router_width"],),
                "w_gate": (held, h, f), "w_up": (held, h, f),
                "w_down": (held, f, h), "shared_gate": (h, shared),
                "shared_up": (h, shared), "shared_down": (shared, h)},
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
    if name == "dt_bias":
        dt = jnp.exp(jax.random.uniform(
            key, shape, minval=jnp.log(DT_MIN), maxval=jnp.log(DT_MAX)))
        dt = jnp.maximum(dt, DT_FLOOR)
        return dt + jnp.log(-jnp.expm1(-dt))    # softplus(dt_bias) = dt
    if name == "a_log":
        return jnp.log(jax.random.uniform(key, shape, minval=1.0,
                                          maxval=16.0))
    std = 0.02 if name == "embed" else shape[-2] ** -0.5
    value = jax.random.normal(key, shape, jnp.float32) * std
    return value if name in FLOAT32 else value.astype(jnp.bfloat16).astype(
        jnp.dtype(cfg.get("torch_dtype", "bfloat16")))


def make_stacked(cfg: dict, key) -> dict:
    """Every leaf as the program lays them out: each kind's leaves stacked
    over that kind's layers, in their order."""
    out = {name: make_leaf(cfg, key, None, name) for name in TOP_LEAVES}
    listed = list(enumerate(entries(cfg)))
    out["layers"] = {
        kind: {name: jnp.stack([make_leaf(cfg, key, kind, name, l)
                                for l, (_, k) in listed if k == kind])
               for name in LEAVES[kind]}
        for kind in dict.fromkeys(k for _, (_, k) in listed)}
    return out
