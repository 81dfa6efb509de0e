"""Seeded weights and token batches, made by the benchmark itself.

The program under test and the plain reference are both handed what is
made here, so neither takes anything the other made. Every leaf has a key
of its own (the seed, the leaf's position, the layer), so one leaf can be
made again alone. Matrices are drawn in float32 and rounded to bfloat16,
the type they are trained in; the reference gets the same bfloat16 values
widened to float32. Distributions: normal with standard deviation 0.02
for the embedding and fan_in**-0.5 for every projection, norms at 1.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

LAYER_LEAVES = ("attn_norm", "wq", "wk", "wv", "wo", "mlp_norm", "w_gate",
                "w_up", "w_down")
TOP_LEAVES = ("embed", "final_norm", "unembed")


def seed_key(seed: int):
    """A key from any whole number; seeds above 2**31 keep their high bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % (1 << 31)),
                              seed >> 31)


def leaf_shapes(cfg: dict) -> dict:
    h, i, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    d = cfg.get("head_dim") or h // cfg["num_attention_heads"]
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    return {"embed": (v, h), "final_norm": (h,), "unembed": (h, v),
            "attn_norm": (h,), "mlp_norm": (h,), "wq": (h, q), "wk": (h, kv),
            "wv": (h, kv), "wo": (q, h), "w_gate": (h, i), "w_up": (h, i),
            "w_down": (i, h)}


def make_leaf(cfg: dict, key, name: str, layer=None):
    """One leaf (of one layer) in the type it is trained in."""
    shape = leaf_shapes(cfg)[name]
    if name.endswith("norm"):
        return jnp.ones(shape, jnp.float32)
    index = (TOP_LEAVES + LAYER_LEAVES).index(name)
    key = jax.random.fold_in(key, index)
    if layer is not None:
        key = jax.random.fold_in(key, layer)
    std = 0.02 if name == "embed" else shape[0] ** -0.5
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(
        jnp.bfloat16)


def make_stacked(cfg: dict, key) -> dict:
    """Every leaf, the layers' stacked on a leading axis."""
    out = {name: make_leaf(cfg, key, name) for name in TOP_LEAVES}
    if cfg.get("tie_word_embeddings"):
        del out["unembed"]
    out["layers"] = {
        name: jnp.stack([make_leaf(cfg, key, name, l)
                         for l in range(cfg["num_hidden_layers"])])
        for name in LAYER_LEAVES}
    return out


def token_batch(seed: int, index: int, rows: int, seq: int, vocab: int):
    """Batch ``index`` of the run: [rows, seq + 1] token ids, every row
    different, the same for the same seed."""
    rng = np.random.default_rng([int(seed), int(index)])
    return rng.integers(0, vocab, size=(rows, seq + 1), dtype=np.int32)
