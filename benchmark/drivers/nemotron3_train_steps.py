"""Traffic ``nemotron3_train_steps``: ``glm_train_steps`` for a Nemotron-3
decoder (model_type ``nemotron_h`` with ``moe_latent_size`` and a
prediction module: Mamba-2 at 16 heads a group, attention without rotary
embedding, sigmoid-routed relu2 experts that read and write a latent
between two linear maps, a share of which is held here, a shared expert,
one multi-token-prediction module whose block is a string of the model's
own kinds and whose loss is weighed and added).

The run is ``benchmark/drivers/_expert_train_steps.py``'s, the body the
expert cells' drivers share: ``train.Trainer`` builds
``build_train_step`` for the configuration's widths, the weights come
from the seed (``benchmark/weights_nemotron3.py``), the compiled step is
driven through its first two steps for the comparison and handed to the
window. Here is what this model differs by: how its ``Stack`` is built,
the scans a step that ``ssd_*_roofline`` ask (``hybrid_train_steps``'s
facts), the counts the set-up line prints beside the parameters held
(``flops_nemotron3.nemotron3_params``), and the comparison, which has
the norm of the gradients' difference beside the accepted numbers
(``benchmark/compare_difference.py``: the program's first gradient is
kept on the host leaf by leaf and set against the reference's inside
``nemotron3_decoder.follow_two_steps``).

The ``Stack`` is built before anything touches a device: a program
whose ``Stack`` has no ``expert_latent`` exits 1 with a sentence.

Parameters of the mix: as ``train_steps``; ``check.faults`` names the
reference's planted faults that ``benchmark.tools.readings_expert``
reads.
"""

from __future__ import annotations

import dataclasses
import functools

from benchmark import flops_nemotron3, weights_nemotron3 as weights
from benchmark.drivers import _expert_train_steps as body

# what the program's ``Stack`` has to describe for this model
STACK_FIELDS = {"expert_latent", "mtp", "mtp_weight", "rows_over_expected"}


def model_config(config: dict, seq: int):
    import jax.numpy as jnp

    from ray_tpu.models import transformer as tfm

    stack_type = getattr(tfm, "Stack", None)
    described = ({f.name for f in dataclasses.fields(stack_type)}
                 if stack_type else set())
    if not STACK_FIELDS <= described:
        raise SystemExit(
            "benchmark: this program's Stack describes no "
            f"{sorted(STACK_FIELDS - described)} (experts that read and "
            "write a latent between two linear maps, under a router on the "
            "hidden state): it cannot run this configuration")
    run, patterns = config["run"], weights.patterns_of(config)
    assert (config["n_group"], config["topk_group"], config["norm_topk_prob"],
            config["n_shared_experts"]) == (1, 1, True, 1)
    stack = tfm.Stack(
        pattern=patterns["layers"], mtp=patterns["mtp"],
        mtp_weight=run["mtp_weight"], head_dim=config["head_dim"],
        ssm_heads=config["mamba_num_heads"],
        ssm_head_dim=config["mamba_head_dim"],
        ssm_groups=config["n_groups"], ssm_state=config["ssm_state_size"],
        conv_kernel=config["conv_kernel"], chunk=config["chunk_size"],
        routed_experts=config["router_width"],
        experts_per_token=config["num_experts_per_tok"],
        expert_width=config["moe_intermediate_size"],
        expert_latent=config["moe_latent_size"],
        shared_width=config["moe_shared_expert_intermediate_size"],
        routed_scale=config["routed_scaling_factor"],
        experts_held=(config["experts_held_first"],
                      config["n_routed_experts"]),
        rows_over_expected=run["row_buffer_over_expected"],
        bias_rate=run["router_bias_rate"])
    return tfm.ModelConfig(
        vocab_size=config["vocab_size"], hidden=config["hidden_size"],
        layers=len(stack.pattern), heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], intermediate=0, max_seq=seq,
        norm_eps=config["layer_norm_epsilon"],
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
            config["torch_dtype"]],
        remat=run["remat"], remat_policy=run["remat_policy"],
        tie_embeddings=config["tie_word_embeddings"],
        logits_chunk=run["logits_chunk"], stack=stack)


def facts(config: dict, mcfg, mesh) -> dict:
    """What ``ssd_fwd_roofline`` and ``ssd_bwd_roofline`` ask: a Mamba
    layer's scan runs forward once, once more where the layer is
    rematerialised, and backward once."""
    n_mamba = mcfg.stack.every_kind.count("M")
    return {"ssd_fwd_calls_per_step": n_mamba * (
                2 if config["run"]["remat"] else 1),
            "ssd_bwd_calls_per_step": n_mamba}


FAMILY = body.Family(
    model_config=model_config, weights=weights,
    params=flops_nemotron3.nemotron3_params, counts="flops_nemotron3",
    facts=facts)

# what the harness (``run``) and ``benchmark.tools.readings_expert``
# (all three) call
run = functools.partial(body.run, family=FAMILY)
follow = functools.partial(body.follow, family=FAMILY)
leaf_of = functools.partial(body.leaf_of, family=FAMILY)
