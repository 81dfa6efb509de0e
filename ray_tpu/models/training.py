"""Sharded training steps for the model family.

Two builders over one model:

  build_train_step   GSPMD path (pp == 1): jit with NamedSharding
                     annotations; dp shards batch (fsdp optionally shards
                     params over dp), tp shards heads/mlp/vocab, sp runs
                     ring attention inside a partial shard_map over the
                     ``sp`` axis. XLA inserts all collectives
                     (scaling-book recipe). A stack by pattern with
                     expert layers runs with dp = sp = 1: they compute
                     the experts one chip holds; windowed and latent
                     attention and the delta-rule mixer (``K``) have no
                     sp path (``_refuse_unbuilt``).

  build_pipeline_train_step
                     pp > 1: the uniform dense stack (transformer.
                     dense_layers, the block build_train_step scans)
                     shards over ``pp`` and runs the GPipe schedule
                     (parallel/pipeline.py) inside a shard_map manual
                     over pp (dp/tp stay automatic).

Both return (step_fn, init_fn) where step_fn(params, opt_state, tokens)
-> (params, opt_state, metrics) is donate-safe and jit-compiled over the
given mesh.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.models import transformer as tfm
from ray_tpu.observability.device_programs import Noted, named_jit
from ray_tpu.observability.metrics import moe_rows, train_loss_parts
from ray_tpu.ops.attention import flash_attention, flash_attention_on_mesh
from ray_tpu.parallel.mesh import DEFAULT_RULES, fsdp_rules, spec_for
from ray_tpu.parallel.ring_attention import ring_attention


def make_optimizer(learning_rate: float = 3e-4, weight_decay: float = 0.1,
                   b1: float = 0.9, b2: float = 0.95,
                   grad_clip: float = 1.0, warmup_steps: int = 0,
                   carry: bool = False) -> optax.GradientTransformation:
    """Clip by the global norm, then AdamW. ``warmup_steps``: step t of
    the first that many takes t / warmup_steps of the learning rate (a
    fresh Adam moves every weight by the whole rate whatever its
    gradient's size, which a run survives only at a small rate).
    ``carry``: ``carry_rounding`` behind it, so that such steps, far
    under a bfloat16 parameter's spacing, add up instead of vanishing."""
    if warmup_steps:
        peak = learning_rate

        def learning_rate(count):
            return peak * jnp.minimum(1.0, (count + 1) / warmup_steps)

    chain = [optax.clip_by_global_norm(grad_clip),
             optax.adamw(learning_rate, b1=b1, b2=b2,
                         weight_decay=weight_decay)]
    if carry:
        chain.append(carry_rounding())
    return optax.chain(*chain)


class CarriedRounding(NamedTuple):
    """What each parameter's last rounding lost, in the parameter's type."""
    lost: Any


def carry_rounding() -> optax.GradientTransformation:
    """Compensated (Kahan) summation of the updates into parameters
    narrower than float32: the update that reaches the parameter is the
    one wanted plus what earlier roundings lost, as far as the
    parameter's type can take it, and the rest is carried on. A
    parameter and its carry together follow the float32 sum of the
    updates to about 16 bits. Float32 parameters pass through."""

    def narrow(p):
        return p.dtype != jnp.float32

    def wanted(u, lost):
        return u.astype(jnp.float32) + lost.astype(jnp.float32)

    def taken(u, lost, p):
        if not narrow(p):
            return u
        wide = p.astype(jnp.float32)
        # reduce_precision and not a conversion there and back, which
        # XLA on a TPU may fuse away (excess precision), carry and all
        bits = jnp.finfo(p.dtype)
        return jax.lax.reduce_precision(
            wide + wanted(u, lost), bits.nexp, bits.nmant) - wide

    def init(params):
        return CarriedRounding(jax.tree.map(jnp.zeros_like, params))

    def update(updates, state, params):
        moved = jax.tree.map(taken, updates, state.lost, params)
        lost = jax.tree.map(
            lambda u, lost, p, m: (wanted(u, lost) - m).astype(p.dtype)
            if narrow(p) else lost, updates, state.lost, params, moved)
        return moved, CarriedRounding(lost)

    return optax.GradientTransformation(init, update)


def carried_params(params, opt_state):
    """``params`` in float32 with what ``carry_rounding`` carries for
    them added: what the run's parameters stand at. Without a carry in
    ``opt_state``, the parameters widened."""
    wide = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    for state in jax.tree.leaves(
            opt_state, is_leaf=lambda s: isinstance(s, CarriedRounding)):
        if isinstance(state, CarriedRounding):
            wide = jax.tree.map(lambda w, lost: w + lost.astype(jnp.float32),
                                wide, state.lost)
    return wide


def param_shardings(cfg: tfm.ModelConfig, mesh: Mesh,
                    fsdp: bool = False) -> Dict[str, Any]:
    rules = fsdp_rules() if fsdp else DEFAULT_RULES
    axes = tfm.logical_axes(cfg)
    return jax.tree.map(
        lambda ax: NamedSharding(mesh, spec_for(ax, rules)), axes,
        is_leaf=lambda x: isinstance(x, tuple))


def _build_init(cfg: tfm.ModelConfig, mesh: Mesh, p_shard,
                optimizer: optax.GradientTransformation) -> Callable:
    """init_fn(key) -> (params, opt_state), one jitted program that makes
    every array where it lives: parameters as ``p_shard`` says, each
    optimizer moment like its parameter, the rest replicated."""

    def init(key):
        params = tfm.init_params(cfg, key)
        return params, optimizer.init(params)

    opt_shard = optax.tree_map_params(
        optimizer, lambda _, s: s,
        jax.eval_shape(init, jax.random.PRNGKey(0))[1], p_shard,
        transform_non_params=lambda _: NamedSharding(mesh, P()))
    return named_jit(init, "train_init",
                     out_shardings=(p_shard, opt_shard))


def _flash_attention(mesh: Mesh, nested: bool = False):
    """``flash_attention`` for a step over ``mesh``, called under jit or
    ``nested`` in a shard_map that is manual over pp alone. One device
    under jit has nothing to partition and gets the bare op; a mesh gets
    ops.attention.flash_attention_on_mesh, which runs the Pallas tier
    per (dp, tp) shard and says why. Either takes a ``W`` layer's
    ``window`` by keyword."""
    if mesh.size == 1 and not nested:
        return lambda q, k, v, window=None: flash_attention(
            q, k, v, True, None, None, None, window)
    qkv = P("dp", None, "tp", None)       # [batch, seq, heads, head_dim]
    if nested:
        return flash_attention_on_mesh(
            qkv, axis_names=set(mesh.axis_names) - {"pp"})
    return flash_attention_on_mesh(qkv, mesh)


def _make_attention_fn(mesh: Mesh, cfg: tfm.ModelConfig,
                       sp_strategy: str = "ring"):
    """Attention for a GSPMD step over ``mesh``: the flash op, or — when
    the mesh has an sp axis > 1 — sequence-parallel attention inside a
    shard_map over every axis. Two sp strategies: "ring" (K/V rotation,
    O(1) memory, parallel/ring_attention.py) and "ulysses" (all-to-all
    head/seq swap, parallel/ulysses.py) — pick ulysses when heads >> sp
    and all-to-all bandwidth is plentiful."""
    if mesh.shape.get("sp", 1) == 1:
        return _flash_attention(mesh)
    if sp_strategy == "ulysses":
        from ray_tpu.parallel.ulysses import ulysses_attention

        body = functools.partial(ulysses_attention, axis_name="sp",
                                 causal=True)
    elif sp_strategy == "ring":
        body = functools.partial(ring_attention, axis_name="sp",
                                 causal=True)
    else:
        raise ValueError(f"unknown sp_strategy {sp_strategy!r}")
    spec = P("dp", "sp", "tp", None)
    return shard_map(body, mesh=mesh, in_specs=(spec,) * 3, out_specs=spec)


def build_train_step(cfg: tfm.ModelConfig, mesh: Mesh, *,
                     fsdp: bool = False,
                     optimizer: Optional[optax.GradientTransformation] = None,
                     sp_strategy: str = "ring",
                     ) -> Tuple[Callable, Callable]:
    """GSPMD data/tensor/sequence-parallel train step (pp=1)."""
    _refuse_unbuilt(cfg, mesh, fsdp)
    optimizer = optimizer or make_optimizer()
    p_shard = param_shardings(cfg, mesh, fsdp=fsdp)
    tok_shard = NamedSharding(mesh, P("dp", None))
    attention_fn = _make_attention_fn(mesh, cfg, sp_strategy=sp_strategy)
    init_fn = _build_init(cfg, mesh, p_shard, optimizer)

    unembed_at = _unembed_sharding(cfg, p_shard)

    def loss(params, tokens):
        return tfm.loss_and_rows(params, tokens, cfg, attention_fn,
                                 sharded=mesh.size > 1,
                                 unembed_sharding=unembed_at)

    return _jit_step(loss, optimizer, "train_step", p_shard,
                     tok_shard), init_fn


def _unembed_sharding(cfg: tfm.ModelConfig, p_shard) -> NamedSharding:
    """Where the step holds the unembedding ``[hidden, vocab]``: its own
    leaf's sharding, or the tied embedding's turned."""
    if not cfg.tie_embeddings:
        return p_shard["unembed"]
    vocab, hidden = p_shard["embed"].spec
    return NamedSharding(p_shard["embed"].mesh, P(hidden, vocab))


def _refuse_unbuilt(cfg: tfm.ModelConfig, mesh: Mesh, fsdp: bool,
                    pipeline: bool = False) -> None:
    """A pattern stack's ``E`` layers compute the experts they are told
    they hold, for the tokens of their own chip. Over a ``dp`` axis the
    experts' leaves shard over the chips (``experts`` -> ``dp``) and each
    chip's rows would have to reach the chip that holds their expert: that
    exchange is not built (of latent rows, where the experts work in a
    latent: ``Stack.expert_latent``). Mamba heads, the shared expert, the
    latent maps and attention shard over ``tp`` as named. Windowed attention (``W``) runs where
    the sequence is whole on a chip: the ring and the all-to-all over
    ``sp`` know no window. Latent attention (``L``) is built for
    training with value heads as wide as query heads, the sequence whole
    on a chip; an MTP module for the step that holds the whole stack. The
    delta-rule mixer (``K``) carries a state from position to position:
    it runs where the sequence is whole on a chip, its heads over ``tp``
    as named. The gated short convolution (``C``) looks back over the
    rows before, and so runs where the sequence is whole on a chip."""
    st = cfg.stack
    # kinds whose leaves no pipeline stage knows
    own = [c for c in "KC" if c in st.every_kind]
    if st.pattern and (pipeline or mesh.shape.get("pp", 1) > 1):
        raise NotImplementedError(
            f"the pipeline path runs the uniform dense stack alone. A "
            f"stack by pattern ({st.lead + st.pattern!r}) stacks its "
            "parameters by kind, not by layer, so a pp axis has no whole "
            "layers to hand a stage, and its expert layers' row counts "
            "would have to leave the stages"
            + (". Its MTP module reads the last stage's output and the "
               "first stage's embedding, which no schedule of "
               "parallel/pipeline.py passes on" if st.mtp else "")
            + (f". Its {' and '.join(own)} layers' leaves are a kind's of "
               "their own, which no stage of parallel/pipeline.py knows how "
               "to run" if own else "")
            + ". Build it with build_train_step on a mesh with pp=1.")
    if "W" in st.every_kind and mesh.shape.get("sp", 1) > 1:
        raise NotImplementedError(
            "windowed attention over an sp axis is not built: "
            "parallel/ring_attention.py and parallel/ulysses.py are causal "
            "over the whole sequence. Run the pattern's W layers with sp=1.")
    if "K" in st.every_kind and mesh.shape.get("sp", 1) > 1:
        raise NotImplementedError(
            "the delta-rule mixer (K) over an sp axis is not built: "
            "ops/kda.py walks a sequence's chunks one after the other with "
            "a state of [heads, head_dim, head_dim] and the three "
            "convolutions look back over the rows before, and nothing "
            "hands either from one chip's share of the sequence to the "
            "next. Run the pattern's K layers with sp=1.")
    if "C" in st.every_kind and mesh.shape.get("sp", 1) > 1:
        raise NotImplementedError(
            "the gated short convolution (C) over an sp axis is not built: "
            "its taps look back over the rows before, and nothing hands a "
            "chip's last rows to the chip that holds the next share of the "
            "sequence. Run the pattern's C layers with sp=1.")
    if "L" in st.every_kind:
        if st.v_head_dim not in (0, cfg.head_dim):
            raise NotImplementedError(
                f"latent attention with value heads of {st.v_head_dim} "
                f"beside query and key heads of {cfg.head_dim} is not "
                "built: ops/attention.py's kernels take q, k and v of one "
                "width. Run it with v_head_dim equal to head_dim.")
        if mesh.shape.get("sp", 1) > 1:
            raise NotImplementedError(
                "latent attention over an sp axis is not built: the ring "
                "and the all-to-all of parallel/ would pass the heads' "
                "keys and values where the latent would do. Run the "
                "pattern's L layers with sp=1.")
    if "E" not in st.every_kind:
        return
    if mesh.shape.get("dp", 1) > 1 or fsdp:
        # what the chips would exchange: the rows the experts read
        rows = (f"rows of the experts' latent ({st.expert_latent} wide, "
                "after the map down and before the map back up)"
                if st.expert_latent else "tokens")
        raise NotImplementedError(
            f"the pattern stack's expert layers hold experts "
            f"{cfg.stack.held} whole on one chip; a mesh with dp="
            f"{mesh.shape.get('dp', 1)} (fsdp={fsdp}) would spread them "
            f"over chips, which needs the all-to-all of {rows} between "
            "the chips that ray_tpu.parallel does not have yet. Run it "
            "with dp=1 (tp may be more), one share of the experts a "
            "program.")
    if mesh.shape.get("sp", 1) > 1:
        raise NotImplementedError(
            "a pattern stack over an sp axis is not built: the Mamba "
            "layers' state would have to pass between the sequence's "
            "shards")


def publish_moe_rows(metrics: Dict[str, Any]) -> Dict[str, int]:
    """The ``E`` layers' row counts of one step's metrics
    (``tfm.MOE_ROWS``) as whole numbers, added to the counter
    ``moe_rows{where}`` with the rows the movement touched
    (``where="moved"``); {} for a model without such layers. Reads the
    device: call it where the loss is read."""
    counted = {name: int(metrics[name]) for name in tfm.MOE_ROWS
               if name in metrics}
    for name, value in counted.items():
        moe_rows.inc(value, {"where": name[len("moe_rows_"):]})
    # published, not returned: the callers add up what they are handed
    # under the names of ``MOE_ROWS``
    if "moe_rows_moved" in metrics:
        moe_rows.inc(int(metrics["moe_rows_moved"]), {"where": "moved"})
    return counted


def publish_loss_parts(metrics: Dict[str, Any]) -> Dict[str, float]:
    """The parts of one step's loss (``loss_main``, ``loss_mtp`` of a
    stack with an MTP module) set on the gauge ``train_loss_parts{part}``;
    {} for a model whose loss has one part. Reads the device."""
    parts = {name[len("loss_"):]: float(metrics[name])
             for name in ("loss_main", "loss_mtp") if name in metrics}
    for part, value in parts.items():
        train_loss_parts.set(value, {"part": part})
    return parts


def _jit_step(loss: Callable, optimizer: optax.GradientTransformation,
              name: str, p_shard, tok_shard) -> Noted:
    """The jitted step of both builders: ``loss(params, tokens)`` (with
    what it counts beside the loss, which joins the step's metrics)
    differentiated, the optimizer applied, the state donated. Returned
    in the wrapper that notes an explicitly compiled executable."""

    def step(params, opt_state, tokens):
        (l, counted), grads = jax.value_and_grad(loss, has_aux=True)(
            params, tokens)
        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            if "router_bias_step" in counted:
                params = tfm.add_router_bias(
                    params, counted.pop("router_bias_step"))
        with jax.named_scope("grad_norm"):
            gnorm = optax.global_norm(grads)
        return params, opt_state, {"loss": l, "grad_norm": gnorm, **counted}

    return Noted(named_jit(
        step, name,
        in_shardings=(p_shard, None, tok_shard),
        out_shardings=(p_shard, None, None),
        donate_argnums=(0, 1),
    ))


def build_forward(cfg: tfm.ModelConfig, mesh: Optional[Mesh] = None):
    """Jitted inference forward (the graft entry's single-chip fn)."""
    attention_fn = None
    if mesh is not None:
        attention_fn = _make_attention_fn(mesh, cfg)

    def fwd(params, tokens):
        logits, _ = tfm.forward(
            params, tokens, cfg, attention_fn,
            sharded=mesh is not None and mesh.size > 1)
        return logits

    return named_jit(fwd, "forward")


# -- pipeline path -----------------------------------------------------------


def build_pipeline_train_step(cfg: tfm.ModelConfig, mesh: Mesh, *,
                              num_microbatches: Optional[int] = None,
                              optimizer: Optional[
                                  optax.GradientTransformation] = None,
                              ) -> Tuple[Callable, Callable]:
    """pp > 1: layer stack sharded over ``pp``, GPipe schedule inside a
    shard_map; embed/unembed replicated across stages."""
    from ray_tpu.parallel.pipeline import pipeline_spmd

    _refuse_unbuilt(cfg, mesh, False, pipeline=True)
    pp = mesh.shape["pp"]
    assert cfg.layers % pp == 0, "pp must divide layers"
    optimizer = optimizer or make_optimizer()
    num_microbatches = num_microbatches or pp

    p_shard = param_shardings(cfg, mesh)  # layers axis -> pp
    tok_shard = NamedSharding(mesh, P("dp", None))
    init_fn = _build_init(cfg, mesh, p_shard, optimizer)

    cos, sin = tfm.rope_frequencies(cfg.head_dim, cfg.max_seq,
                                    cfg.rope_theta)
    attention_fn = _flash_attention(mesh, nested=True)

    def stage_fn(stage_layers, x):
        # x: [mb, S, H]; stage_layers: layer stack slice of size L/pp
        return tfm.dense_layers(x, stage_layers, cfg, cos, sin,
                                attention_fn, sharded=True)

    def pipe_apply(layer_params, hidden):
        body = functools.partial(pipeline_spmd, stage_fn, axis_name="pp",
                                 num_microbatches=num_microbatches)
        # manual only over pp: specs may mention pp alone; dp/tp sharding
        # of the same arrays stays automatic inside the region
        layer_specs = jax.tree.map(
            lambda s: P(*[a if a == "pp" else None for a in
                          (s.spec + (None,) * 8)[:8]][: len(s.spec)]),
            p_shard["layers"],
            is_leaf=lambda x: isinstance(x, NamedSharding))
        f = shard_map(
            body, mesh=mesh,
            in_specs=(layer_specs, P()),
            out_specs=P(),
            axis_names={"pp"},
        )
        return f(layer_params, hidden)

    def loss(params, tokens):
        with jax.named_scope("embed"):
            x = jnp.take(params["embed"], tokens[:, :-1], axis=0)
        with jax.named_scope("layers"):
            x = pipe_apply(params["layers"], x)
        with jax.named_scope("final_norm"):
            x = tfm.rms_norm(x, params["final_norm"], cfg.norm_eps)
        with jax.named_scope("loss"):
            unembed = (params["embed"].T if cfg.tie_embeddings
                       else params["unembed"])
            return tfm.token_nll(x, tokens[:, 1:], unembed).mean(), {}

    return _jit_step(loss, optimizer, "pipeline_train_step", p_shard,
                     tok_shard), init_fn
