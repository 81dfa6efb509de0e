"""Traffic ``lfm2_train_steps``: ``nemotron3_train_steps`` for an LFM2
decoder with sparse experts (model_type ``lfm2_moe``: gated
short-convolution layers, ``C * conv(B * x)`` of one projection's three
parts, three to one with GQA layers whose q and k are normed a head;
leading dense SwiGLU layers, then sigmoid-routed SwiGLU experts without a
shared expert, a share of which is held here; the embedding tied to the
head; no prediction module).

The run is ``benchmark/drivers/_expert_train_steps.py``'s, the body the
expert cells' drivers share: ``train.Trainer`` builds
``build_train_step`` for the configuration's widths, the weights come
from the seed (``benchmark/weights_lfm2.py``), the compiled step is
driven through its first two steps for the comparison and handed to the
window. Here is what this model differs by: how its ``Stack`` is built,
the gated short convolution's calls a step that
``short_conv_*_roofline`` ask, the counts the set-up line prints beside
the parameters held (``flops_lfm2.lfm2_params``).

The ``Stack`` is built before anything touches a device: a program whose
``Stack`` cannot describe the short-convolution kind or the norm of q and
k exits 1 with a sentence.

Parameters of the mix: as ``train_steps``; ``check.faults`` names the
reference's planted faults that ``benchmark.tools.readings_expert``
reads.
"""

from __future__ import annotations

import dataclasses
import functools

from benchmark import flops_lfm2, weights_lfm2 as weights
from benchmark.drivers import _expert_train_steps as body

# what the program's ``Stack`` has to describe for this model
STACK_FIELDS = {"short_conv_taps", "qk_norm"}


def model_config(config: dict, seq: int):
    import jax.numpy as jnp

    from ray_tpu.models import transformer as tfm

    stack_type = getattr(tfm, "Stack", None)
    described = ({f.name for f in dataclasses.fields(stack_type)}
                 if stack_type else set())
    if not STACK_FIELDS <= described:
        raise SystemExit(
            "benchmark: this program's Stack describes no "
            f"{sorted(STACK_FIELDS - described)} (a gated short-convolution "
            "kind, and a norm of each head of q and k): it cannot run this "
            "configuration")
    run = config["run"]
    assert (config["conv_bias"], config["use_expert_bias"],
            config["norm_topk_prob"],
            config["rope_parameters"]["rope_type"]) == (
                False, True, True, "default")
    patterns = weights.patterns_of(config)
    stack = tfm.Stack(
        lead=patterns["lead"], pattern=patterns["layers"],
        head_dim=config["hidden_size"] // config["num_attention_heads"],
        rope=tfm.Rope(config["rope_parameters"]["rope_theta"]),
        qk_norm=True, short_conv_taps=config["conv_L_cache"],
        routed_experts=config["router_width"],
        experts_per_token=config["num_experts_per_tok"],
        expert_width=config["moe_intermediate_size"],
        routed_scale=config["routed_scaling_factor"],
        experts_held=(config["experts_held_first"], config["num_experts"]),
        rows_over_expected=run["row_buffer_over_expected"],
        router_score="sigmoid", expert_act="swiglu",
        bias_rate=run["router_bias_rate"])
    return tfm.ModelConfig(
        vocab_size=config["vocab_size"], hidden=config["hidden_size"],
        layers=len(stack.lead + stack.pattern),
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"],
        intermediate=config["intermediate_size"], max_seq=seq,
        norm_eps=config["norm_eps"],
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
            config["torch_dtype"]],
        remat=run["remat"], remat_policy=run["remat_policy"],
        tie_embeddings=config["tie_word_embeddings"],
        logits_chunk=run["logits_chunk"], stack=stack)


def facts(config: dict, mcfg, mesh) -> dict:
    """What ``short_conv_fwd_roofline`` and ``short_conv_bwd_roofline``
    ask: a ``C`` layer's gated convolution runs forward once, once more
    where the layer is rematerialised, and backward once."""
    n_conv = mcfg.stack.every_kind.count("C")
    return {"short_conv_fwd_calls_per_step": n_conv * (
                2 if config["run"]["remat"] else 1),
            "short_conv_bwd_calls_per_step": n_conv,
            "short_conv_taps": mcfg.stack.short_conv_taps}


FAMILY = body.Family(
    model_config=model_config, weights=weights,
    params=flops_lfm2.lfm2_params, counts="flops_lfm2", facts=facts)


# what the harness (``run``) and ``benchmark.tools.readings_expert``
# (all three) call
def run(ctx):
    # the body counts the experts held by ``n_routed_experts``, the other
    # expert configurations' key; this one keeps its source's ``num_experts``
    ctx.cell.config.setdefault("n_routed_experts",
                               ctx.cell.config["num_experts"])
    return body.run(ctx, family=FAMILY)


follow = functools.partial(body.follow, family=FAMILY)
leaf_of = functools.partial(body.leaf_of, family=FAMILY)
