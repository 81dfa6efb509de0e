"""Operations a GLM-4.7-Flash decoder needs (latent attention in every
layer, a leading dense layer, sigmoid-routed SwiGLU experts with a shared
expert, one multi-token-prediction module), from shapes alone, by the rule
of ``benchmark/flops.py``: what the mathematics asks for, whatever
computes it; recomputation is not counted.

The configuration is given with its published keys as
``benchmark/configs/glm47_flash_l7_ep8.json`` holds them:
``n_routed_experts`` counts the experts held here, ``router_width`` the
router's outputs. The experts' grouped products are
``flops_mellum.glu_grouped_mlp_cost``'s: three matrices an expert.
"""

from __future__ import annotations

from benchmark.flops import causal_attention_matmuls


def attention_matmul_params(cfg: dict) -> int:
    """Parameters a token is multiplied by in one latent attention block:
    the two down projections, the two up projections, the output."""
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    return (h * cfg["q_lora_rank"]
            + cfg["q_lora_rank"] * heads * (nope + rope)
            + h * (cfg["kv_lora_rank"] + rope)
            + cfg["kv_lora_rank"] * heads * (nope + v)
            + heads * v * h)


def expert_layer_matmul_params(cfg: dict) -> float:
    """... in one expert layer's MLP: the router, the shared expert, and
    the routed experts by what a token is EXPECTED to meet here under
    even routing: ``num_experts_per_tok`` x held / router_width experts
    of three matrices (4 x 8 / 64 = half an expert in the cell; the other
    choices go to experts on other chips, whose work is not done here)."""
    h, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    met = (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
           / cfg["router_width"])
    return h * cfg["router_width"] \
        + (cfg["n_shared_experts"] + met) * 3 * h * f


def blocks(cfg: dict) -> dict:
    """How many blocks of each make the model: attention blocks and
    expert layers of the stack and of the modules, leading dense MLPs."""
    dense, modules = (cfg["first_k_dense_replace"],
                      cfg["num_nextn_predict_layers"])
    return {"stack": cfg["num_hidden_layers"], "dense": dense,
            "sparse": cfg["num_hidden_layers"] - dense, "modules": modules}


def glm_params(cfg: dict) -> int:
    """Every parameter held here (the set-up line prints the same)."""
    h, f, n = cfg["hidden_size"], cfg["moe_intermediate_size"], blocks(cfg)
    attention = attention_matmul_params(cfg) + h + cfg["q_lora_rank"] \
        + cfg["kv_lora_rank"]
    dense = 3 * h * cfg["intermediate_size"] + h
    sparse = (h * cfg["router_width"] + cfg["router_width"] + h
              + (cfg["n_shared_experts"] + cfg["n_routed_experts"])
              * 3 * h * f)
    # a module: the norms of its two halves, the projection that joins
    # them, one block, its head's norm; embedding and head are the model's
    module = 2 * h + 2 * h * h + attention + sparse + h
    return (n["stack"] * attention + n["dense"] * dense
            + n["sparse"] * sparse + n["modules"] * module
            + 2 * h * cfg["vocab_size"] + h)


def glm_train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward and backward of the decoder and its module, per token of
    the batch: 6 per multiplied parameter that a token actually meets
    here (the routed experts by ``expert_layer_matmul_params``' expected
    share), the head once for the main loss and once for each module's,
    per attention block the six products over the causal pairs at heads
    x (qk_nope_head_dim + qk_rope_head_dim). A module's parts are asked
    for the ``seq - 1`` positions that have a token after the next.
    Recomputation is not counted."""
    h, n = cfg["hidden_size"], blocks(cfg)
    width = cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"])
    head = h * cfg["vocab_size"]
    attention = attention_matmul_params(cfg)
    sparse = expert_layer_matmul_params(cfg)
    stack = (n["stack"] * attention
             + n["dense"] * 3 * h * cfg["intermediate_size"]
             + n["sparse"] * sparse + head)
    module = 2 * h * h + attention + sparse + head
    pairs = 6 * causal_attention_matmuls(seq, width) / seq
    ahead = 6 * causal_attention_matmuls(seq - 1, width) / seq
    return (6.0 * stack + n["stack"] * pairs
            + n["modules"] * (6.0 * module * (seq - 1) / seq + ahead))
