"""Traffic ``solar_train_steps``: ``nemotron3_train_steps`` for a
Solar-Open2 decoder (model_type ``solar_open2``: Kimi Delta Attention, the
gated delta rule with a decay a channel, in three layers of four; softmax
attention without position, gated element for element, in the fourth;
sigmoid-routed SwiGLU experts, a share of which is held here, with a
shared expert, in every layer; no prediction module).

The run is ``benchmark/drivers/_expert_train_steps.py``'s, the body the
expert cells' drivers share: ``train.Trainer`` builds
``build_train_step`` for the configuration's widths, the weights come
from the seed (``benchmark/weights_solar.py``), the compiled step is
driven through its first two steps for the comparison and handed to the
window. Here is what this model differs by: how its ``Stack`` is built,
the delta rule's calls a step that ``kda_*_roofline`` ask, the counts the
set-up line prints beside the parameters held
(``flops_solar.solar_params``).

The ``Stack`` is built before anything touches a device: a program whose
``Stack`` cannot describe the delta-rule kind or the gate exits 1 with a
sentence.

Parameters of the mix: as ``train_steps``; ``check.faults`` names the
reference's planted faults that ``benchmark.tools.readings_expert``
reads.
"""

from __future__ import annotations

import dataclasses
import functools

from benchmark import flops_solar, weights_solar as weights
from benchmark.drivers import _expert_train_steps as body

# what the program's ``Stack`` has to describe for this model
STACK_FIELDS = {"kda_heads", "kda_head_dim", "kda_gate_rank", "kda_beta_max",
                "kda_chunk", "attention_gate"}


def model_config(config: dict, seq: int):
    import jax.numpy as jnp

    from ray_tpu.models import transformer as tfm

    stack_type = getattr(tfm, "Stack", None)
    described = ({f.name for f in dataclasses.fields(stack_type)}
                 if stack_type else set())
    if not STACK_FIELDS <= described:
        raise SystemExit(
            "benchmark: this program's Stack describes no "
            f"{sorted(STACK_FIELDS - described)} (a delta-rule "
            "linear-attention kind with a decay a channel, and a gate on "
            "softmax attention's output): it cannot run this configuration")
    run, lin = config["run"], config["linear_attn_config"]
    assert (config["norm_topk_prob"], config["n_shared_experts"],
            config["use_rope"], config["use_gqa_gate"],
            config["kda_use_full_proj"], lin["num_kv_heads"]) == (
                True, 1, False, True, False, None)
    stack = tfm.Stack(
        pattern=weights.patterns_of(config)["layers"],
        head_dim=config["head_dim"], attention_gate=config["use_gqa_gate"],
        kda_heads=lin["num_heads"], kda_head_dim=lin["head_dim"],
        kda_gate_rank=lin["head_dim"],
        kda_beta_max=2.0 if config["kda_allow_neg_eigval"] else 1.0,
        kda_chunk=run["kda_chunk"],
        conv_kernel=lin["short_conv_kernel_size"],
        routed_experts=config["router_width"],
        experts_per_token=config["num_experts_per_tok"],
        expert_width=config["moe_intermediate_size"],
        shared_width=config["n_shared_experts"]
        * config["moe_intermediate_size"],
        routed_scale=config["routed_scaling_factor"],
        experts_held=(config["experts_held_first"],
                      config["n_routed_experts"]),
        rows_over_expected=run["row_buffer_over_expected"],
        router_score="sigmoid", expert_act="swiglu",
        bias_rate=run["router_bias_rate"])
    return tfm.ModelConfig(
        vocab_size=config["vocab_size"], hidden=config["hidden_size"],
        layers=len(stack.pattern), heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], intermediate=0, max_seq=seq,
        norm_eps=config["rms_norm_eps"],
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
            config["torch_dtype"]],
        remat=run["remat"], remat_policy=run["remat_policy"],
        tie_embeddings=config["tie_word_embeddings"],
        logits_chunk=run["logits_chunk"], stack=stack)


def facts(config: dict, mcfg, mesh) -> dict:
    """What ``kda_fwd_roofline`` and ``kda_bwd_roofline`` ask: a ``K``
    layer's delta rule runs forward once, once more where the layer is
    rematerialised, and backward once."""
    st = mcfg.stack
    n_kda = st.every_kind.count("K")
    return {"kda_fwd_calls_per_step": n_kda * (
                2 if config["run"]["remat"] else 1),
            "kda_bwd_calls_per_step": n_kda,
            "kda_heads": st.kda_heads, "kda_head_dim": st.kda_head_dim,
            "kda_chunk": st.kda_chunk}


FAMILY = body.Family(
    model_config=model_config, weights=weights,
    params=flops_solar.solar_params, counts="flops_solar", facts=facts)

# what the harness (``run``) and ``benchmark.tools.readings_expert``
# (all three) call
run = functools.partial(body.run, family=FAMILY)
follow = functools.partial(body.follow, family=FAMILY)
leaf_of = functools.partial(body.leaf_of, family=FAMILY)
