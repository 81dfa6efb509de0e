"""Operations and bytes the algorithms need, from shapes alone.

The yardstick's side of every utilisation: nothing here looks at how the
program computes a thing, only at what the mathematics asks for.
Recomputation (remat, a backward kernel that rebuilds the scores) is the
program's choice and is not counted, so a share of the peak read against
these counts cannot pass 100 %.

A dense decoder's configuration is given with its published keys
(``hidden_size``, ``num_attention_heads``, ...), as the files under
``benchmark/configs`` hold them.
"""

from __future__ import annotations

import math


def head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def dense_matmul_params(cfg: dict) -> int:
    """Parameters that a token is multiplied by: every projection of every
    layer and the output head. The embedding is a look-up."""
    h, i = cfg["hidden_size"], cfg["intermediate_size"]
    q = cfg["num_attention_heads"] * head_dim(cfg)
    kv = cfg["num_key_value_heads"] * head_dim(cfg)
    layer = h * q + 2 * h * kv + q * h + 3 * h * i
    return cfg["num_hidden_layers"] * layer + h * cfg["vocab_size"]


def dense_params(cfg: dict) -> int:
    h = cfg["hidden_size"]
    embeds = cfg["vocab_size"] * h * (1 if cfg.get("tie_word_embeddings") else 2)
    norms = (2 * cfg["num_hidden_layers"] + 1) * h
    return dense_matmul_params(cfg) - h * cfg["vocab_size"] + embeds + norms


def causal_attention_matmuls(seq: int, q_width: int) -> float:
    """Operations of ONE [seq, seq, head_dim] product summed over the
    heads, per sequence, counted causally: the lower triangle with its
    diagonal, seq*(seq+1)/2 of the seq*seq scores."""
    return 2.0 * q_width * seq * (seq + 1) / 2


def dense_train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward and backward of a dense decoder, per token: 6 per
    multiplied parameter, and per layer the attention's two products
    forward (scores, values) and four backward (dV, dP, dQ, dK), each
    counted causally. Recomputation is not counted."""
    q_width = cfg["num_attention_heads"] * head_dim(cfg)
    attention = 6 * causal_attention_matmuls(seq, q_width) / seq
    return 6.0 * dense_matmul_params(cfg) + cfg["num_hidden_layers"] * attention


# The flash kernels, per call. ``matmuls`` is how many [seq, seq, head_dim]
# products the call's own outputs need: forward the scores and the values;
# dQ needs scores, dP and dQ; dK/dV needs scores, dV, dP and dK.
FLASH_MATMULS = {"flash_fwd": 2, "flash_bwd_dq": 3, "flash_bwd_dkdv": 4}
# [batch, seq, heads, head_dim] arrays a call reads and writes, in
# units of the query array (K and V at their own width), plus the
# float32 rows of logsumexp / delta: forward reads q k v, writes o and lse;
# dQ reads q k v do lse delta, writes dq; dK/dV reads the same, writes dk dv.
FLASH_ARRAYS = {"flash_fwd": (2, 2, 1), "flash_bwd_dq": (3, 2, 2),
                "flash_bwd_dkdv": (2, 4, 2)}


def flash_call_cost(kernel: str, batch: int, seq: int, heads: int,
                    kv_heads: int, head_dim: int, itemsize: int = 2):
    """(operations, bytes) of one causal call of ``kernel``."""
    ops = FLASH_MATMULS[kernel] * batch * causal_attention_matmuls(
        seq, heads * head_dim)
    q_like, kv_like, rows = FLASH_ARRAYS[kernel]
    one = batch * seq * head_dim * itemsize
    nbytes = (q_like * heads + kv_like * kv_heads) * one \
        + rows * batch * seq * heads * 4
    return float(ops), float(nbytes)


def sched_solve_cost(nodes: int, resources: int, classes: int):
    """(operations, bytes) of one batched placement solve, whatever
    implements it: for each class in turn, a quotient and a minimum over
    the resources of every node (how many tasks fit), a utilisation and a
    comparison per node, a sort of the nodes (n log2 n comparisons), a
    running sum, and the subtraction of what was placed. Bytes: total,
    available and alive read once, the demands, the counts written."""
    per_class = nodes * (2 * resources       # quotient, minimum
                         + 2 * resources     # utilisation over resources
                         + math.ceil(math.log2(max(nodes, 2)))  # sort
                         + 2                 # running sum, clip
                         + 2 * resources)    # subtract what was placed
    ops = classes * per_class
    nbytes = 4 * (2 * nodes * resources + nodes + classes * resources
                  + classes + classes * nodes)
    return float(ops), float(nbytes)


def least_seconds(ops: float, nbytes: float, peak: dict,
                  ops_key: str = "bf16_flops_per_s"):
    """(least time the chip could take, which bound sets it)."""
    t_ops = ops / peak[ops_key]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "memory")
