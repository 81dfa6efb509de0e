"""Seeded weights of a hybrid (pattern) decoder's leaves, by the rule of
``benchmark/weights.py``: every leaf of every layer has a key of its own
(the seed, the leaf's position in ``ALL_LEAVES``, the layer counted over
all the layers), is drawn in float32 and rounded to the type it is
trained in; the reference gets the same values widened to float32.

Distributions: normal with standard deviation 0.02 for the embedding and
fan_in**-0.5 for every projection, the experts and the convolution (fan_in
``conv_kernel``); norms and ``D`` at 1, the convolution's and the router's
bias at 0; ``dt`` log-uniform in [``time_step_min``, ``time_step_max``]
floored at ``time_step_floor`` with ``dt_bias`` its inverse softplus; ``A``
uniform in [1, 16]. float32 stay the norms, ``A_log``, ``D``, ``dt_bias``,
the convolution (its values lie near 1, where bfloat16's spacing of 4e-3
would lose every update of 3e-4), the router and its bias; the rest is rounded to bfloat16 and kept in the
configuration's ``torch_dtype`` (a float32 model trains the same values).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.weights import seed_key, token_batch  # noqa: F401

KINDS = {"M": "mamba", "E": "moe", "*": "attention"}
LEAVES = {
    "mamba": ("norm", "w_in", "conv_w", "conv_b", "dt_bias", "a_log", "d",
              "gate_norm", "w_out"),
    "moe": ("norm", "router", "router_bias", "w_up", "w_down", "shared_up",
            "shared_down"),
    "attention": ("attn_norm", "wq", "wk", "wv", "wo"),
}
TOP_LEAVES = ("embed", "final_norm", "unembed")
ALL_LEAVES = [(None, name) for name in TOP_LEAVES] + [
    (kind, name) for kind, names in LEAVES.items() for name in names]
FLOAT32 = {"norm", "attn_norm", "gate_norm", "final_norm", "dt_bias",
           "a_log", "d", "router", "router_bias", "conv_w", "conv_b"}


def kinds_of(cfg: dict):
    return [KINDS[c] for c in cfg["hybrid_override_pattern"]]


def leaf_shapes(cfg: dict) -> dict:
    """{kind (None for the top): {leaf: shape}}."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    inner = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    conv = inner + 2 * cfg["n_groups"] * cfg["ssm_state_size"]
    heads = cfg["mamba_num_heads"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    held, f = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    fs = cfg["moe_shared_expert_intermediate_size"]
    return {
        None: {"embed": (v, h), "final_norm": (h,), "unembed": (h, v)},
        "mamba": {"norm": (h,), "w_in": (h, inner + conv + heads),
                  "conv_w": (cfg["conv_kernel"], conv), "conv_b": (conv,),
                  "dt_bias": (heads,), "a_log": (heads,), "d": (heads,),
                  "gate_norm": (inner,), "w_out": (inner, h)},
        "moe": {"norm": (h,), "router": (h, cfg["router_width"]),
                "router_bias": (cfg["router_width"],),
                "w_up": (held, h, f), "w_down": (held, f, h),
                "shared_up": (h, fs), "shared_down": (fs, h)},
        "attention": {"attn_norm": (h,), "wq": (h, q), "wk": (h, kv),
                      "wv": (h, kv), "wo": (q, h)},
    }


def make_leaf(cfg: dict, key, kind, name: str, layer=None):
    """One leaf in the type it is trained in: of the top (``kind`` None)
    or of layer ``layer``, counted over all the layers, which is of
    ``kind``."""
    shape = leaf_shapes(cfg)[kind][name]
    dtype = jnp.float32 if name in FLOAT32 else jnp.dtype(
        cfg.get("torch_dtype", "bfloat16"))
    if name.endswith("norm") or name == "d":
        return jnp.ones(shape, dtype)
    if name in ("conv_b", "router_bias"):
        return jnp.zeros(shape, dtype)
    key = jax.random.fold_in(key, ALL_LEAVES.index((kind, name)))
    if layer is not None:
        key = jax.random.fold_in(key, layer)
    if name == "dt_bias":
        dt = jnp.exp(jax.random.uniform(
            key, shape, minval=jnp.log(cfg["time_step_min"]),
            maxval=jnp.log(cfg["time_step_max"])))
        dt = jnp.maximum(dt, cfg["time_step_floor"])
        return dt + jnp.log(-jnp.expm1(-dt))    # softplus(dt_bias) = dt
    if name == "a_log":
        return jnp.log(jax.random.uniform(key, shape, minval=1.0,
                                          maxval=16.0))
    std = 0.02 if name == "embed" else (
        shape[0] if name == "conv_w" else shape[-2]) ** -0.5
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
