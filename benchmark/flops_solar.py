"""Operations a Solar-Open2 decoder needs (Kimi Delta Attention, gated
softmax attention without position, sigmoid-routed SwiGLU experts with a
shared expert), from shapes alone, by the rule of ``benchmark/flops.py``:
what the mathematics asks for, whatever computes it; recomputation is
not counted.

The configuration is given with its published keys as
``benchmark/configs/solar_open2_l4_ep40.json`` holds them:
``n_routed_experts`` counts the experts held here, ``router_width`` the
router's outputs.
"""

from __future__ import annotations

from benchmark.flops import causal_attention_matmuls


def _widths(cfg: dict) -> dict:
    lin = cfg["linear_attn_config"]
    return {"h": cfg["hidden_size"],
            "q": cfg["num_attention_heads"] * cfg["head_dim"],
            "kv": cfg["num_key_value_heads"] * cfg["head_dim"],
            "heads": lin["num_heads"], "hd": lin["head_dim"],
            "inner": lin["num_heads"] * lin["head_dim"],
            "rank": lin["head_dim"], "taps": lin["short_conv_kernel_size"],
            "f": cfg["moe_intermediate_size"],
            "shared": cfg["n_shared_experts"] * cfg["moe_intermediate_size"]}


def params_by_kind(cfg: dict) -> dict:
    """{kind: every parameter of one layer of it held here}."""
    w = _widths(cfg)
    h, inner, rank = w["h"], w["inner"], w["rank"]
    return {
        "K": h + 3 * h * inner + 3 * w["taps"] * inner
        + h * rank + rank * inner + inner + w["heads"]      # the decay
        + h * w["heads"]                                    # beta
        + h * rank + rank * inner                           # the gate
        + w["hd"] + inner * h,
        "*": h + 2 * h * w["q"] + 2 * h * w["kv"] + h * w["q"],
        "E": h + h * cfg["router_width"] + cfg["router_width"]
        + 3 * h * w["shared"] + cfg["n_routed_experts"] * 3 * h * w["f"],
    }


def pattern(cfg: dict) -> str:
    """The program's kinds, two entries a layer: ``*`` (a layer of
    ``gqa_layers``) or ``K``, then ``E``."""
    return "".join(("*" if i in cfg["gqa_layers"] else "K") + "E"
                   for i in range(cfg["num_hidden_layers"]))


def solar_params(cfg: dict) -> int:
    """Every parameter held here (the set-up line prints the same)."""
    per_kind = params_by_kind(cfg)
    h = cfg["hidden_size"]
    return sum(per_kind[c] for c in pattern(cfg)) \
        + 2 * h * cfg["vocab_size"] + h


def layer_matmul_params(cfg: dict) -> dict:
    """{kind: parameters a token is multiplied by in one layer of it}: the
    routed experts by what a token is EXPECTED to meet here under even
    routing, ``num_experts_per_tok`` x held / router_width experts (8 x 8
    / 320 = 0.2 of an expert in the cell; the other choices go to experts
    on other chips)."""
    w = _widths(cfg)
    h, inner, rank = w["h"], w["inner"], w["rank"]
    met = (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
           / cfg["router_width"])
    return {
        "K": 3 * h * inner + 2 * (h * rank + rank * inner) + h * w["heads"]
        + inner * h,
        "*": 2 * h * w["q"] + 2 * h * w["kv"] + h * w["q"],
        "E": h * cfg["router_width"] + 3 * h * w["shared"]
        + met * 3 * h * w["f"],
    }


def kda_forward_ops_per_token(heads: int, head_dim: int, chunk: int) -> float:
    """Operations a token of one head-set needs in the forward of the
    chunked gated delta rule at ``chunk`` positions a chunk, 2 a multiply
    and add: a chunk's two decayed score matrices (k k^T and q k^T, each
    C x C x d), the unit lower-triangular inverse as forward substitution
    (C^3 / 3 multiply-adds), T's products with the decayed keys and with
    v, and the five products with the state or what the positions write
    (W S, q S, B U, k^T U: C x d x d each, B U C x C x d)."""
    c, d = chunk, head_dim
    a_chunk = (2.0 * 2 * c * c * d            # A and B
               + 2.0 * c * c * c / 3          # the inverse
               + 2.0 * 2 * c * c * d          # W and U~
               + 2.0 * 3 * c * d * d          # W S, q S, k^T U
               + 2.0 * c * c * d)             # B U
    return heads * a_chunk / c


def kda_cost(which: str, batch: int, seq: int, heads: int, head_dim: int,
             chunk: int, itemsize: int = 2):
    """(operations, bytes) of one call of the delta rule over [batch,
    seq]: ``which`` "fwd" or "bwd" (each forward product has two backward
    ones). Bytes, what any implementation must move: q, k, v read and o
    written in ``itemsize``, g (a channel) and beta (a head) in float32;
    the backward reads those and do and writes dq, dk, dv, dg, dbeta."""
    tokens = batch * seq
    ops = tokens * kda_forward_ops_per_token(heads, head_dim, chunk)
    wide = tokens * heads * head_dim
    g_like, beta_like = wide * 4, tokens * heads * 4
    if which == "fwd":
        return float(ops), float(4 * wide * itemsize + g_like + beta_like)
    if which == "bwd":
        return float(2 * ops), float(8 * wide * itemsize
                                     + 2 * (g_like + beta_like))
    raise ValueError(f"kda_cost: which is 'fwd' or 'bwd', not {which!r}")


def solar_train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward and backward of the decoder, per token: 6 per multiplied
    parameter that a token actually meets here (``layer_matmul_params``),
    the head, per ``*`` layer the attention's six products counted
    causally, per ``K`` layer three times the delta rule's forward
    products in its chunked form at the run's ``kda_chunk``, the chunk
    the program runs at and ``kda_*_roofline`` count at (the published
    config states none: ``assumed.chunk``). Recomputation is not
    counted."""
    w = _widths(cfg)
    per_kind = layer_matmul_params(cfg)
    kinds = pattern(cfg)
    attention = 6 * causal_attention_matmuls(seq, w["q"]) / seq
    delta = 3 * kda_forward_ops_per_token(w["heads"], w["hd"],
                                          min(cfg["run"]["kda_chunk"], seq))
    return (6.0 * (sum(per_kind[c] for c in kinds)
                   + w["h"] * cfg["vocab_size"])
            + kinds.count("*") * attention + kinds.count("K") * delta)
