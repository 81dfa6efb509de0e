"""Seeded weights of a GLM-4.7-Flash decoder's leaves (model_type
``glm4_moe_lite``: latent attention in every layer, ``first_k_dense_replace``
leading layers with a dense SwiGLU MLP, sigmoid-routed SwiGLU experts with
a shared expert in the others, ``num_nextn_predict_layers`` multi-token-
prediction modules), by the rule of ``benchmark/weights.py``,
``weights_hybrid.py`` and ``weights_mellum.py``: every leaf of every layer
has a key of its own (the seed, the leaf's position in ``ALL_LEAVES``, the
layer counted over the entries), is drawn in float32 and rounded to the
type it is trained in; the reference gets the same values widened to
float32.

The program lays a decoder layer out as two entries: ``L`` latent
attention, then ``D`` (dense) or ``E`` (experts). ``entries`` lists them
all with the tree of the program's parameters each lies in: the leading
layers' (``lead``), the periods' (``layers``), the module's block
(``mtp``). Distributions: normal with standard deviation 0.02 for the
embedding and fan_in**-0.5 for every projection, the router and the
experts; norms at 1, the correction bias at 0. float32 stay the norms, the
router and its bias; the rest is rounded to bfloat16 and kept in the
configuration's ``torch_dtype``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.weights import seed_key, token_batch  # noqa: F401

KINDS = {"L": "latent", "D": "dense", "E": "moe"}
LEAVES = {
    "latent": ("attn_norm", "w_dq", "q_norm", "w_uq", "w_dkv", "kv_norm",
               "w_ukv", "wo"),
    "dense": ("mlp_norm", "w_gate", "w_up", "w_down"),
    "moe": ("norm", "router", "router_bias", "w_gate", "w_up", "w_down",
            "shared_gate", "shared_up", "shared_down"),
}
TOP_LEAVES = ("embed", "final_norm", "unembed")
# the module's own leaves beside its block's; kind "mtp", no layer
MTP_LEAVES = ("enorm", "hnorm", "eh_proj", "head_norm")
ALL_LEAVES = [(None, name) for name in TOP_LEAVES] + [
    ("mtp", name) for name in MTP_LEAVES] + [
    (kind, name) for kind, names in LEAVES.items() for name in names]
FLOAT32 = {"router", "router_bias"}


def patterns_of(cfg: dict) -> dict:
    """{tree of the program's parameters: its layers' kinds}: the leading
    dense layers, the sparse layers that repeat, the module's block."""
    dense = cfg["first_k_dense_replace"]
    return {"lead": "LD" * dense,
            "layers": "LE" * (cfg["num_hidden_layers"] - dense),
            "mtp": "LE" * cfg["num_nextn_predict_layers"]}


def entries(cfg: dict):
    """[(tree, kind)] of every entry, in the order the model runs them."""
    return [(where, KINDS[c]) for where, kinds in patterns_of(cfg).items()
            for c in kinds]


def leaf_shapes(cfg: dict) -> dict:
    """{kind (None for the top, "mtp" for the module's own): {leaf: shape}}."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    heads = cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    qr, kvr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    held, f = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    shared, i = cfg["n_shared_experts"] * f, cfg["intermediate_size"]
    return {
        None: {"embed": (v, h), "final_norm": (h,), "unembed": (h, v)},
        "mtp": {"enorm": (h,), "hnorm": (h,), "eh_proj": (2 * h, h),
                "head_norm": (h,)},
        "latent": {"attn_norm": (h,), "w_dq": (h, qr), "q_norm": (qr,),
                   "w_uq": (qr, heads * (nope + rope)),
                   "w_dkv": (h, kvr + rope), "kv_norm": (kvr,),
                   "w_ukv": (kvr, heads * (nope + cfg["v_head_dim"])),
                   "wo": (heads * cfg["v_head_dim"], h)},
        "dense": {"mlp_norm": (h,), "w_gate": (h, i), "w_up": (h, i),
                  "w_down": (i, h)},
        "moe": {"norm": (h,), "router": (h, cfg["router_width"]),
                "router_bias": (cfg["router_width"],),
                "w_gate": (held, h, f), "w_up": (held, h, f),
                "w_down": (held, f, h), "shared_gate": (h, shared),
                "shared_up": (h, shared), "shared_down": (shared, h)},
    }


def make_leaf(cfg: dict, key, kind, name: str, layer=None):
    """One leaf in the type it is trained in: of the top (``kind`` None),
    of the module's own (``"mtp"``), or of entry ``layer``, which is of
    ``kind``."""
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
        if not mine:
            continue
        stacked = {
            kind: {name: jnp.stack([make_leaf(cfg, key, kind, name, l)
                                    for l, k in mine if k == kind])
                   for name in LEAVES[kind]}
            for kind in dict.fromkeys(k for _, k in mine)}
        if where == "mtp":
            stacked = dict({name: make_leaf(cfg, key, "mtp", name)
                            for name in MTP_LEAVES}, block=stacked)
        out[where] = stacked
    return out
