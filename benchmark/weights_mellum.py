"""Seeded weights of a Mellum decoder's leaves (model_type ``mellum``:
sliding-window and full attention by ``layer_types``, a mixture of
SwiGLU experts in every layer), by the rule of ``benchmark/weights.py``
and ``weights_hybrid.py``: every leaf of every layer has a key of its own
(the seed, the leaf's position in ``ALL_LEAVES``, the layer counted over
the entries of the pattern), is drawn in float32 and rounded to the type
it is trained in; the reference gets the same values widened to float32.

The program lays a decoder layer out as two entries of its pattern: ``W``
(sliding) or ``*`` (full) attention, then ``E`` (``pattern_of``).
Distributions: normal with standard deviation 0.02 for the embedding and
fan_in**-0.5 for every projection, the router and the experts; norms at
1. float32 stay the norms and the router; the rest is rounded to bfloat16
and kept in the configuration's ``torch_dtype``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.weights import seed_key, token_batch  # noqa: F401

KINDS = {"W": "window", "*": "attention", "E": "moe"}
ATTENTION_OF = {"sliding_attention": "W", "full_attention": "*"}
ATTENTION_LEAVES = ("attn_norm", "wq", "wk", "wv", "wo")
LEAVES = {"window": ATTENTION_LEAVES, "attention": ATTENTION_LEAVES,
          "moe": ("norm", "router", "w_gate", "w_up", "w_down")}
TOP_LEAVES = ("embed", "final_norm", "unembed")
ALL_LEAVES = [(None, name) for name in TOP_LEAVES] + [
    (kind, name) for kind, names in LEAVES.items() for name in names]
FLOAT32 = {"norm", "attn_norm", "final_norm", "router"}


def pattern_of(cfg: dict) -> str:
    """The program's pattern: a layer's attention by ``layer_types``,
    then its experts."""
    if set(cfg["mlp_layer_types"]) != {"sparse"}:
        raise ValueError("a dense MLP layer is not this configuration's")
    return "".join(ATTENTION_OF[kind] + "E" for kind in cfg["layer_types"])


def kinds_of(cfg: dict):
    return [KINDS[c] for c in pattern_of(cfg)]


def leaf_shapes(cfg: dict) -> dict:
    """{kind (None for the top): {leaf: shape}}."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    held, f = cfg["num_experts"], cfg["moe_intermediate_size"]
    attention = {"attn_norm": (h,), "wq": (h, q), "wk": (h, kv),
                 "wv": (h, kv), "wo": (q, h)}
    return {
        None: {"embed": (v, h), "final_norm": (h,), "unembed": (h, v)},
        "window": attention, "attention": attention,
        "moe": {"norm": (h,), "router": (h, cfg["router_width"]),
                "w_gate": (held, h, f), "w_up": (held, h, f),
                "w_down": (held, f, h)},
    }


def make_leaf(cfg: dict, key, kind, name: str, layer=None):
    """One leaf in the type it is trained in: of the top (``kind`` None)
    or of entry ``layer`` of the pattern, which is of ``kind``."""
    shape = leaf_shapes(cfg)[kind][name]
    dtype = jnp.float32 if name in FLOAT32 else jnp.dtype(
        cfg.get("torch_dtype", "bfloat16"))
    if name.endswith("norm"):
        return jnp.ones(shape, dtype)
    key = jax.random.fold_in(key, ALL_LEAVES.index((kind, name)))
    if layer is not None:
        key = jax.random.fold_in(key, layer)
    std = 0.02 if name == "embed" else shape[-2] ** -0.5
    value = jax.random.normal(key, shape, jnp.float32) * std
    return value if name in FLOAT32 else value.astype(jnp.bfloat16).astype(
        dtype)


def make_stacked(cfg: dict, key) -> dict:
    """Every leaf as the program lays them out: each kind's leaves
    stacked over that kind's layers, in the order of the pattern."""
    kinds = kinds_of(cfg)
    out = {name: make_leaf(cfg, key, None, name) for name in TOP_LEAVES}
    out["layers"] = {
        kind: {name: jnp.stack([make_leaf(cfg, key, kind, name, l)
                                for l, k in enumerate(kinds) if k == kind])
               for name in LEAVES[kind]}
        for kind in dict.fromkeys(kinds)}
    return out
