"""Operations a Nemotron-3 decoder needs (Mamba-2, attention, experts
that work in a latent between two linear maps, one multi-token-prediction
module whose block is a string of the model's kinds), from shapes alone,
by the rule of ``benchmark/flops.py``: what the mathematics asks for,
whatever computes it; recomputation is not counted.

The configuration is given with its published keys as
``benchmark/configs/nemotron3_super_l9_ep64.json`` holds them:
``n_routed_experts`` counts the experts held here, ``router_width`` the
router's outputs. The Mamba-2 and attention kinds and the scan are
``flops_hybrid``'s; the experts' grouped products are
``flops_hybrid.grouped_mlp_cost``'s, asked at the latent's width.
"""

from __future__ import annotations

from benchmark import flops_hybrid
from benchmark.flops import causal_attention_matmuls


def expert_layer_matmul_params(cfg: dict) -> float:
    """Parameters a token is multiplied by in one ``E`` layer: the router
    and the shared expert on the hidden state, both latent maps, and the
    routed experts by what a token is EXPECTED to meet here under even
    routing: ``num_experts_per_tok`` x held / router_width experts of two
    latent x width matrices (22 x 8 / 512 = 0.34375 of an expert in the
    cell; the other choices go to experts on other chips)."""
    h, latent = cfg["hidden_size"], cfg["moe_latent_size"]
    met = (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
           / cfg["router_width"])
    return (h * cfg["router_width"] + 2 * h * latent
            + 2 * h * cfg["moe_shared_expert_intermediate_size"]
            + met * 2 * latent * cfg["moe_intermediate_size"])


def layer_matmul_params(cfg: dict) -> dict:
    """{kind: parameters a token is multiplied by in one layer of it}."""
    return dict(flops_hybrid.layer_matmul_params(cfg),
                E=expert_layer_matmul_params(cfg))


def params_by_part(cfg: dict) -> dict:
    """Every parameter held here, by part (the configuration's
    ``deployment`` gives the same)."""
    h, latent = cfg["hidden_size"], cfg["moe_latent_size"]
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
        + 2 * h * latent
        + 2 * h * cfg["moe_shared_expert_intermediate_size"]
        + cfg["n_routed_experts"] * 2 * latent * cfg["moe_intermediate_size"],
    }
    block = sum(per_kind[c] for c in cfg["mtp_hybrid_override_pattern"])
    return {
        "stack": sum(per_kind[c] for c in cfg["hybrid_override_pattern"]),
        "vocabulary": 2 * h * cfg["vocab_size"] + h,
        # a module: the norms of its two halves, the projection that joins
        # them, its block, its head's norm; embedding and head the model's
        "module": cfg["num_nextn_predict_layers"] * (
            2 * h + 2 * h * h + block + h),
        "by_kind": per_kind,
    }


def nemotron3_params(cfg: dict) -> int:
    """Every parameter held here (the set-up line prints the same)."""
    parts = params_by_part(cfg)
    return parts["stack"] + parts["vocabulary"] + parts["module"]


def nemotron3_train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward and backward of the decoder and its module, per token of
    the batch: 6 per multiplied parameter that a token actually meets
    here (``layer_matmul_params``), the head once for the main loss and
    once for the module's, per ``*`` layer the attention's six products
    counted causally, per ``M`` layer three times the scan's forward
    products in its chunked form. The module's parts are asked for the
    ``seq - 1`` positions that have a token after the next.
    Recomputation is not counted."""
    h = cfg["hidden_size"]
    per_kind = layer_matmul_params(cfg)
    q_width = cfg["num_attention_heads"] * cfg["head_dim"]
    scan = 3 * flops_hybrid.ssd_forward_ops_per_token(
        cfg["mamba_num_heads"], cfg["mamba_head_dim"], cfg["n_groups"],
        cfg["ssm_state_size"], min(cfg["chunk_size"], seq))

    def kinds(pattern: str, positions: int) -> float:
        """Per token of the batch, the layers ``pattern`` names over
        ``positions`` positions of a row."""
        pairs = 6 * causal_attention_matmuls(positions, q_width) / seq
        return (6.0 * sum(per_kind[c] for c in pattern) * positions / seq
                + pattern.count("*") * pairs
                + pattern.count("M") * scan * positions / seq)

    head = h * cfg["vocab_size"]
    module = cfg["num_nextn_predict_layers"] * (
        6.0 * (2 * h * h + head) * (seq - 1) / seq
        + kinds(cfg["mtp_hybrid_override_pattern"], seq - 1))
    return kinds(cfg["hybrid_override_pattern"], seq) + 6.0 * head + module
