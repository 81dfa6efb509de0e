"""Operations and bytes a Mellum decoder needs (sliding-window and full
attention by ``layer_types``, softmax-routed SwiGLU experts in every
layer), from shapes alone, by the rule of ``benchmark/flops.py``: what
the mathematics asks for, whatever computes it; recomputation is not
counted.

The configuration is given with its published keys as
``benchmark/configs/mellum2_12b_l8_ep4.json`` holds them: ``num_experts``
counts the experts held here, ``router_width`` the router's outputs.
"""

from __future__ import annotations

from benchmark.flops import (  # noqa: F401
    FLASH_ARRAYS,
    FLASH_MATMULS,
    causal_attention_matmuls,
    least_seconds,
)

# a windowed kernel's name -> the causal kernel whose products and arrays
# it has
SWA_KERNELS = {"swa_fwd": "flash_fwd", "swa_bwd_dq": "flash_bwd_dq",
               "swa_bwd_dkdv": "flash_bwd_dkdv"}


def band_pairs(seq: int, window: int) -> int:
    """(query, key) pairs of one head that a sliding window keeps: query
    i sees the keys i - window < j <= i, so the first ``window`` queries
    see the causal triangle and each later one ``window`` keys."""
    w = min(window, seq)
    return w * (w + 1) // 2 + (seq - w) * w


def layer_matmul_params(cfg: dict) -> dict:
    """{what: parameters a token is multiplied by in one layer}: the four
    attention projections (windowed or full alike), the router, and the
    routed experts by what a token is EXPECTED to meet here under even
    routing: ``num_experts_per_tok`` x held / router_width experts of
    three matrices (8 x 16 / 64 = 2 in the cell; the other choices go to
    experts on other chips, whose work is not done here)."""
    h = cfg["hidden_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    met = cfg["num_experts_per_tok"] * cfg["num_experts"] / cfg["router_width"]
    return {"attention": 2 * h * q + 2 * h * kv,
            "router": h * cfg["router_width"],
            "experts": met * 3 * h * cfg["moe_intermediate_size"]}


def mellum_matmul_params(cfg: dict) -> float:
    return cfg["num_hidden_layers"] * sum(layer_matmul_params(cfg).values()) \
        + cfg["hidden_size"] * cfg["vocab_size"]


def mellum_params(cfg: dict) -> int:
    """Every parameter held here (the set-up line prints the same)."""
    h = cfg["hidden_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    layer = (2 * h * q + 2 * h * kv + h * cfg["router_width"]
             + cfg["num_experts"] * 3 * h * cfg["moe_intermediate_size"]
             + 2 * h)
    return cfg["num_hidden_layers"] * layer + 2 * h * cfg["vocab_size"] + h


def swa_call_cost(kernel: str, batch: int, seq: int, heads: int,
                  kv_heads: int, head_dim: int, window: int,
                  itemsize: int = 2):
    """(operations, bytes) of one windowed call of ``kernel``
    (``SWA_KERNELS``): the products of the causal kernel it mirrors over
    the pairs inside the band alone, and the same arrays read and
    written as ``flops.flash_call_cost`` counts."""
    causal = SWA_KERNELS[kernel]
    ops = FLASH_MATMULS[causal] * batch * 2.0 * heads * head_dim \
        * band_pairs(seq, window)
    q_like, kv_like, rows = FLASH_ARRAYS[causal]
    one = batch * seq * head_dim * itemsize
    nbytes = (q_like * heads + kv_like * kv_heads) * one \
        + rows * batch * seq * heads * 4
    return float(ops), float(nbytes)


def glu_grouped_mlp_cost(rows: float, hidden: int, width: int, experts: int,
                         itemsize: int = 2):
    """(operations, bytes) of the routed SwiGLU experts of one layer for
    one step's forward and backward over ``rows`` rows (token, choice)
    that experts held here really have: three grouped products forward
    (gate, up, down) and six backward, 2 x hidden x width operations a
    row and product. Bytes: the three weight banks read forward and for
    the rows' gradient and their gradients written; the rows read at the
    hidden width, written at the experts' width twice, read there and
    written at the hidden width, forward, and as much twice backward.
    Rows of nought that pad a buffer ask for nothing."""
    ops = 9 * 2.0 * rows * hidden * width
    weights = 3 * experts * hidden * width * itemsize
    nbytes = 3 * weights + 3 * rows * (2 * hidden + 3 * width) * itemsize
    return float(ops), float(nbytes)


def attention_flops_per_token(cfg: dict, seq: int) -> dict:
    """{"full" | "sliding": operations a token of one such layer needs in
    attention's six products (two forward, four backward)}: the causal
    pairs of a full layer, the band's of a sliding one."""
    q_width = cfg["num_attention_heads"] * cfg["head_dim"]
    return {"full": 6 * causal_attention_matmuls(seq, q_width) / seq,
            "sliding": 6 * 2.0 * q_width
            * band_pairs(seq, cfg["sliding_window"]) / seq}


def mellum_train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward and backward of the decoder, per token: 6 per multiplied
    parameter that a token actually meets here (``layer_matmul_params``),
    per full layer the attention's six products counted causally, per
    sliding layer over the band's pairs. Recomputation is not counted."""
    attention = attention_flops_per_token(cfg, seq)
    return 6.0 * mellum_matmul_params(cfg) + sum(
        attention["sliding" if kind == "sliding_attention" else "full"]
        for kind in cfg["layer_types"])
