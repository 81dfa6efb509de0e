"""Seeded weights of a Nemotron-3 decoder's leaves (model_type
``nemotron_h`` with ``moe_latent_size`` and a prediction module:
Mamba-2, attention, experts that read and write a latent between two
linear maps, one multi-token-prediction module whose block is a string
of the model's own kinds), by the rule of ``benchmark/weights.py``: every
leaf of every layer has a key of its own, is drawn in float32 and rounded
to the type it is trained in; the reference gets the same values widened
to float32.

What the Nemotron-H stack already has is ``weights_hybrid``'s, leaf for
leaf, key and distribution (the top, the Mamba-2 and attention kinds, the
expert layer's norm, router, bias and shared expert): ``make_leaf`` hands
those on. New here, with keys of their own behind ``weights_hybrid``'s:
the two latent maps, the experts' banks at the latent's width, and the
module's own four leaves; all normal at fan_in**-0.5, rounded to
bfloat16 and kept in the configuration's ``torch_dtype``, the norms 1.

``entries`` lists every layer with the tree of the program's parameters
it lies in: the periods' (``layers``) and the module's block (``mtp``);
a layer is counted over the entries, the module's block last.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark import weights_hybrid as hybrid
from benchmark.weights import seed_key, token_batch  # noqa: F401

KINDS = hybrid.KINDS
LEAVES = dict(hybrid.LEAVES, moe=(
    "norm", "router", "router_bias", "latent_in", "latent_out", "w_up",
    "w_down", "shared_up", "shared_down"))
TOP_LEAVES = hybrid.TOP_LEAVES
# the module's own leaves beside its block's; kind "mtp", no layer
MTP_LEAVES = ("enorm", "hnorm", "eh_proj", "head_norm")
# the leaves drawn here, in the order that gives each its key
OWN_LEAVES = [("moe", name) for name in ("latent_in", "latent_out", "w_up",
                                         "w_down")] + [
    ("mtp", name) for name in MTP_LEAVES]


def patterns_of(cfg: dict) -> dict:
    """{tree of the program's parameters: its layers' kinds}."""
    assert cfg["num_nextn_predict_layers"] == 1
    return {"layers": cfg["hybrid_override_pattern"],
            "mtp": cfg["mtp_hybrid_override_pattern"]}


def entries(cfg: dict):
    """[(tree, kind)] of every layer, in the order the model runs them."""
    return [(where, KINDS[c]) for where, kinds in patterns_of(cfg).items()
            for c in kinds]


def own_shapes(cfg: dict) -> dict:
    h, latent = cfg["hidden_size"], cfg["moe_latent_size"]
    held, f = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    return {
        "moe": {"latent_in": (h, latent), "latent_out": (latent, h),
                "w_up": (held, latent, f), "w_down": (held, f, latent)},
        "mtp": {"enorm": (h,), "hnorm": (h,), "eh_proj": (2 * h, h),
                "head_norm": (h,)},
    }


def make_leaf(cfg: dict, key, kind, name: str, layer=None):
    """One leaf in the type it is trained in: of the top (``kind`` None),
    of the module's own (``"mtp"``), or of entry ``layer``, which is of
    ``kind``."""
    if (kind, name) not in OWN_LEAVES:
        return hybrid.make_leaf(cfg, key, kind, name, layer)
    shape = own_shapes(cfg)[kind][name]
    if name.endswith("norm"):
        return jnp.ones(shape, jnp.float32)
    key = jax.random.fold_in(
        key, len(hybrid.ALL_LEAVES) + OWN_LEAVES.index((kind, name)))
    if layer is not None:
        key = jax.random.fold_in(key, layer)
    value = jax.random.normal(key, shape, jnp.float32) * shape[-2] ** -0.5
    return value.astype(jnp.bfloat16).astype(
        jnp.dtype(cfg.get("torch_dtype", "bfloat16")))


def make_stacked(cfg: dict, key) -> dict:
    """Every leaf as the program lays them out: in each tree each kind's
    leaves stacked over that kind's layers there, in their order."""
    out = {name: make_leaf(cfg, key, None, name) for name in TOP_LEAVES}
    listed = list(enumerate(entries(cfg)))
    for where in patterns_of(cfg):
        mine = [(l, kind) for l, (tree, kind) in listed if tree == where]
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
