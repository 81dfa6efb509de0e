"""Operations an LFM2 decoder with sparse experts needs (model_type
``lfm2_moe``: gated short-convolution layers and QK-normed GQA layers,
two leading dense SwiGLU layers, sigmoid-routed SwiGLU experts without a
shared expert), from shapes alone, by the rule of ``benchmark/flops.py``:
what the mathematics asks for, whatever computes it; recomputation is
not counted.

The configuration is given with its published keys as
``benchmark/configs/lfm2_24b_a2b_l9_ep8.json`` holds them: ``layer_types``
and ``num_hidden_layers`` the layers kept, from published layer
``first_layer``; ``num_experts`` the experts held here, ``router_width``
the router's outputs.
"""

from __future__ import annotations

from benchmark.flops import causal_attention_matmuls


def _widths(cfg: dict) -> dict:
    h = cfg["hidden_size"]
    d = h // cfg["num_attention_heads"]
    return {"h": h, "d": d, "q": cfg["num_attention_heads"] * d,
            "kv": cfg["num_key_value_heads"] * d,
            "taps": cfg["conv_L_cache"], "dense": cfg["intermediate_size"],
            "f": cfg["moe_intermediate_size"]}


def pattern(cfg: dict) -> str:
    """The program's kinds, two entries a layer: ``*`` (a
    ``full_attention`` layer) or ``C`` (a ``conv`` one), then ``D`` (a
    leading dense layer) or ``E``."""
    first = cfg["first_layer"]
    return "".join(
        ("*" if kind == "full_attention" else "C")
        + ("D" if first + i < cfg["num_dense_layers"] else "E")
        for i, kind in enumerate(cfg["layer_types"]))


def params_by_kind(cfg: dict) -> dict:
    """{kind: every parameter of one layer of it held here}."""
    w = _widths(cfg)
    h = w["h"]
    return {
        "C": h + 3 * h * h + w["taps"] * h + h * h,
        "*": h + 2 * h * w["q"] + 2 * h * w["kv"] + 2 * w["d"],
        "D": h + 3 * h * w["dense"],
        "E": h + h * cfg["router_width"] + cfg["router_width"]
        + cfg["num_experts"] * 3 * h * w["f"],
    }


def lfm2_params(cfg: dict) -> int:
    """Every parameter held here (the set-up line prints the same): the
    tied embedding once, the final norm."""
    per_kind = params_by_kind(cfg)
    h = cfg["hidden_size"]
    head = 1 if cfg["tie_word_embeddings"] else 2
    return sum(per_kind[c] for c in pattern(cfg)) \
        + head * h * cfg["vocab_size"] + h


def layer_matmul_params(cfg: dict) -> dict:
    """{kind: parameters a token is multiplied by in one layer of it}: the
    convolution's taps among them; the routed experts by what a token is
    EXPECTED to meet here under even routing, ``num_experts_per_tok`` x
    held / router_width experts (4 x 8 / 64 = half an expert in the cell;
    the other choices go to experts on other chips)."""
    w = _widths(cfg)
    h = w["h"]
    met = cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / cfg["router_width"]
    return {
        "C": 3 * h * h + w["taps"] * h + h * h,
        "*": 2 * h * w["q"] + 2 * h * w["kv"],
        "D": 3 * h * w["dense"],
        "E": h * cfg["router_width"] + met * 3 * h * w["f"],
    }


def short_conv_cost(which: str, batch: int, seq: int, hidden: int,
                    taps: int, itemsize: int = 2):
    """(operations, bytes) of one call of the gated short convolution over
    [batch, seq]: ``which`` "fwd" or "bwd". Operations a channel and
    position forwards: B * x, the taps' K products and K - 1 sums, C *
    (2K + 1); backwards twice that. Bytes, what any implementation must
    move: forwards ``proj`` [B, S, 3h] read and ``y`` [B, S, h] written;
    backwards ``proj`` and ``dy`` read, ``d proj`` and the taps' partial
    sums (eight float32 rows a tap and batch row) written."""
    elements = batch * seq * hidden
    ops = elements * (2 * taps + 1)
    if which == "fwd":
        return float(ops), float(4 * elements * itemsize)
    if which == "bwd":
        return float(2 * ops), float(7 * elements * itemsize
                                     + batch * 8 * taps * hidden * 4)
    raise ValueError(f"short_conv_cost: which is 'fwd' or 'bwd', not "
                     f"{which!r}")


def lfm2_train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward and backward of the decoder, per token: 6 per multiplied
    parameter that a token actually meets here (``layer_matmul_params``,
    the taps among them), the head (the tied embedding turned), and per
    ``*`` layer the attention's six products counted causally at
    ``num_attention_heads`` heads of hidden / heads. Recomputation is not
    counted."""
    w = _widths(cfg)
    per_kind = layer_matmul_params(cfg)
    kinds = pattern(cfg)
    attention = 6 * causal_attention_matmuls(seq, w["q"]) / seq
    return (6.0 * (sum(per_kind[c] for c in kinds)
                   + w["h"] * cfg["vocab_size"])
            + kinds.count("*") * attention)
