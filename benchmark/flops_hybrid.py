"""Operations and bytes a hybrid (pattern) decoder needs, from shapes
alone, by the rule of ``benchmark/flops.py``: what the mathematics asks
for, whatever computes it; recomputation is not counted.

The configuration is given with its published keys (``hidden_size``,
``mamba_num_heads``, ``hybrid_override_pattern``, ...) as
``benchmark/configs/nemotron_twotower_30b_l9_ep8.json`` holds them:
``n_routed_experts`` counts the experts held here, ``router_width`` the
router's outputs.
"""

from __future__ import annotations

from benchmark.flops import causal_attention_matmuls, least_seconds  # noqa: F401


def layer_matmul_params(cfg: dict) -> dict:
    """{kind: parameters a token is multiplied by in one layer of it}.
    ``M``: both projections and the four taps of the convolution. ``*``:
    the four projections. ``E``: the router, the shared expert, and the
    routed experts by what a token is EXPECTED to meet here under even
    routing: ``num_experts_per_tok`` x held / router_width of an expert
    (6 x 16 / 128 = 0.75 in the cell; the other choices go to experts on
    other chips, whose work is not done here)."""
    h = cfg["hidden_size"]
    inner = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    conv = inner + 2 * cfg["n_groups"] * cfg["ssm_state_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    met = (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
           / cfg["router_width"])
    return {
        "M": h * (inner + conv + cfg["mamba_num_heads"]) + inner * h
        + cfg["conv_kernel"] * conv,
        "*": 2 * h * q + 2 * h * kv,
        "E": h * cfg["router_width"]
        + 2 * h * cfg["moe_shared_expert_intermediate_size"]
        + met * 2 * h * cfg["moe_intermediate_size"],
    }


def hybrid_matmul_params(cfg: dict) -> float:
    per_kind = layer_matmul_params(cfg)
    return sum(per_kind[c] for c in cfg["hybrid_override_pattern"]) \
        + cfg["hidden_size"] * cfg["vocab_size"]


def hybrid_params(cfg: dict) -> int:
    """Every parameter held here (the set-up line prints the same)."""
    h = cfg["hidden_size"]
    inner = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    conv = inner + 2 * cfg["n_groups"] * cfg["ssm_state_size"]
    heads = cfg["mamba_num_heads"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    per_kind = {
        "M": h * (inner + conv + heads) + inner * h
        + (cfg["conv_kernel"] + 1) * conv + 3 * heads + h + inner,
        "*": 2 * h * q + 2 * h * kv + h,
        "E": h * cfg["router_width"] + cfg["router_width"] + h
        + 2 * h * cfg["moe_shared_expert_intermediate_size"]
        + cfg["n_routed_experts"] * 2 * h * cfg["moe_intermediate_size"],
    }
    return sum(per_kind[c] for c in cfg["hybrid_override_pattern"]) \
        + 2 * h * cfg["vocab_size"] + h


def ssd_forward_ops_per_token(heads: int, head_dim: int, groups: int,
                              state: int, chunk: int) -> float:
    """Operations of the state-space scan's products for one token of
    one layer, forward, in the chunked form at ``chunk``: per chunk of L
    positions the scores C.B^T of each group and their product with x of
    each head, both causal (L (L + 1) / 2 of L^2), the chunk's state
    (x (outer) B summed over L), and the carried state read through C.
    At the cell's sizes (64 x 64, 8 groups, state 128, L 128):
    132 096 + 528 384 + 2 x 1 048 576 = 2 757 632. The sequential
    recurrence needs 5 per element of the state and token (decay,
    outer product, sum; the product with C and its sum):
    5 x 64 x 64 x 128 = 2 621 440."""
    causal = (chunk + 1) / 2
    return (2.0 * groups * state * causal + 2.0 * heads * head_dim * causal
            + 4.0 * heads * head_dim * state)


def ssd_cost(which: str, batch: int, seq: int, heads: int, head_dim: int,
             groups: int, state: int, chunk: int, itemsize: int = 2):
    """(operations, bytes) of one call of the scan over [batch, seq]:
    ``which`` "fwd" or "bwd" (each forward product has two backward
    ones). Bytes: x, B, C read and y written in ``itemsize``, dt in
    float32; the backward reads those and dy and writes dx, dB, dC, ddt."""
    tokens = batch * seq
    ops = tokens * ssd_forward_ops_per_token(heads, head_dim, groups, state,
                                             chunk)
    x_like = tokens * heads * head_dim * itemsize
    bc_like = 2 * tokens * groups * state * itemsize
    dt_like = tokens * heads * 4
    if which == "fwd":
        return float(ops), float(2 * x_like + bc_like + dt_like)
    if which == "bwd":
        return float(2 * ops), float(4 * x_like + 2 * bc_like + 2 * dt_like)
    raise ValueError(f"ssd_cost: which is 'fwd' or 'bwd', not {which!r}")


def grouped_mlp_cost(rows: float, hidden: int, width: int, experts: int,
                     itemsize: int = 2):
    """(operations, bytes) of the routed experts of one layer for one
    step's forward and backward over ``rows`` rows (token, choice) that
    experts held here really have: two grouped products forward and two
    backward ones each, 2 x hidden x width operations a row and product.
    Bytes: both weight banks read forward and for the rows' gradient and
    their gradients written, the rows read and written at both widths.
    Rows of nought that pad a buffer ask for nothing."""
    ops = 3 * 2 * 2.0 * rows * hidden * width
    weights = 2 * experts * hidden * width * itemsize
    nbytes = 3 * weights + 3 * 2 * rows * (hidden + width) * itemsize
    return float(ops), float(nbytes)


def hybrid_train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward and backward of the hybrid decoder, per token: 6 per
    multiplied parameter that a token actually meets here
    (``layer_matmul_params``), per ``*`` layer the attention's six
    products counted causally, per ``M`` layer three times the scan's
    forward products in its chunked form. Recomputation is not counted."""
    pattern = cfg["hybrid_override_pattern"]
    q_width = cfg["num_attention_heads"] * cfg["head_dim"]
    attention = 6 * causal_attention_matmuls(seq, q_width) / seq
    scan = 3 * ssd_forward_ops_per_token(
        cfg["mamba_num_heads"], cfg["mamba_head_dim"], cfg["n_groups"],
        cfg["ssm_state_size"], min(cfg["chunk_size"], seq))
    return (6.0 * hybrid_matmul_params(cfg) + pattern.count("*") * attention
            + pattern.count("M") * scan)
