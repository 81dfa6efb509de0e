"""The body the expert cells' train-steps drivers share, written once:
``train.Trainer(backend="jax", num_workers=1).run(fn)`` builds
``models.training.build_train_step`` for the configuration's widths on
the configuration's mesh, the weights come from the seed, the compiled
step is driven through its first two steps for the comparison and handed
to the window, which feeds it a fresh batch of seeded ids each step.
Spans (``feed``, ``step``, ``wait``), the expert rows (those of the
stack's layers and the module's together) and the common facts are the
accepted drivers'; a step that returns ``moe_rows_over`` above nought
left rows uncomputed and counts as failed.

The comparison is ``benchmark/compare_difference.py``'s: the accepted
numbers and the norm of the gradients' difference, for which the
program's first gradient is kept on the host leaf by leaf and handed to
the reference (``follow_two_steps(..., against=)``).

A driver is a ``Family`` (what its model differs by: how the ``Stack`` is
built from the configuration, the seeded weights, the counts, the facts
its readers ask beyond the common ones) and the functions the harness
and the tools call, each one line on this module's.
``nemotron3_train_steps`` is the first; the accepted drivers (``hybrid``,
``mellum``, ``glm``) are three earlier copies of this body and a
``benchmark`` PR's to move onto it, with ``against`` in their references.

No driver: the name starts with ``_`` and no cell names it.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable

import numpy as np

from benchmark import compare, compare_difference, loader
from benchmark.drivers.hybrid_train_steps import MOE_ROWS
from benchmark.drivers.train_steps import adam_state, mesh_of


@dataclasses.dataclass(frozen=True)
class Family:
    # (config, seq) -> the program's ModelConfig, built before anything
    # touches a device; SystemExit with a sentence where the program's
    # Stack cannot describe the model
    model_config: Callable
    # the seeded weights' module: seed_key, token_batch, make_stacked,
    # make_leaf, entries, MTP_LEAVES
    weights: Any
    # (config) -> the counts' parameter total, and the counts' name
    params: Callable
    counts: str
    # (config, mcfg, mesh) -> the facts this model's readers ask beyond
    # the common ones
    facts: Callable


def tree_norms(tree):
    """{leaf: float32 norm of each layer of its kind and place} of a tree
    laid out as the program's parameters: the model's own leaves and the
    module's (``mtp/<leaf>``) one norm each, ``lead/<kind>/<leaf>``,
    ``layers/<kind>/<leaf>`` and ``mtp/block/<kind>/<leaf>`` stacked over
    that kind's layers there."""
    import jax.numpy as jnp

    def norm(x, keep_first):
        x = jnp.square(x.astype(jnp.float32))
        return jnp.sqrt(x.sum(axis=tuple(range(1, x.ndim))) if keep_first
                        else x.sum())

    def stacked(where, kinds):
        return {f"{where}/{kind}/{k}": norm(v, True)
                for kind, leaves in kinds.items() for k, v in leaves.items()}

    out = {k: norm(v, False)[None] for k, v in tree.items()
           if not isinstance(v, dict)}
    for where in ("lead", "layers"):
        out.update(stacked(where, tree.get(where, {})))
    if "mtp" in tree:
        out.update({f"mtp/{k}": norm(v, False)[None]
                    for k, v in tree["mtp"].items() if k != "block"})
        out.update(stacked("mtp/block", tree["mtp"]["block"]))
    return out


def leaf_of(tree, family: Family, config: dict, scale: float):
    """(name, entry, key) -> that leaf of ``tree``, which is laid out as
    the program's parameters, in float32 times ``scale``: the reference's
    way of asking (``follow_two_steps(..., against=)``)."""
    import jax.numpy as jnp

    listed = family.weights.entries(config)

    def leaf(name, entry, key):
        if entry is None:
            value = (tree["mtp"] if name.startswith("mtp/") else tree)[key]
        else:
            where, kind = listed[entry]
            kinds = tree[where]["block"] if where == "mtp" else tree[where]
            value = kinds[kind][key][listed[:entry].count((where, kind))]
        # widened on the device: the host sends what the optimizer holds
        return jnp.asarray(value).astype(jnp.float32) * scale

    return leaf


def run(ctx, family: Family):
    import ray_tpu
    from ray_tpu.train.trainer import Trainer

    # a program that cannot describe the stack says so before anything
    # is started
    family.model_config(ctx.cell.config, ctx.cell.workload["seq"])
    t0 = time.perf_counter()
    # the worker is a thread of this process: what is too large to hand
    # back through the Trainer (the first gradient's leaves) is left here
    kept = {}
    ray_tpu.init(num_cpus=2)
    try:
        trainer = Trainer(backend="jax", num_workers=1, max_retries=0)
        try:
            trainer.start()
            ctx.say(f"[train] ray_tpu.init and the Trainer's worker in "
                    f"{time.perf_counter() - t0:.1f} s")
            outcome, = trainer.run(lambda: train_func(ctx, family, kept))
        finally:
            trainer.shutdown()
    finally:
        ray_tpu.shutdown()
    outcome["program_numbers"].update(kept)
    outcome["check"] = lambda: check(ctx, family, outcome["program_numbers"])
    return outcome


def train_func(ctx, family: Family, kept: dict):
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.models.training import (
        build_train_step,
        carried_params,
        make_optimizer,
        param_shardings,
        publish_loss_parts,
        publish_moe_rows,
    )
    from ray_tpu.parallel.mesh import MeshSpec, build_mesh

    config, mix, weights = ctx.cell.config, ctx.cell.workload, family.weights
    rows, seq = mix["batch"], mix["seq"]
    hp = config["run"]["optimizer"]
    say, spans = ctx.say, ctx.spans

    t0 = time.perf_counter()
    axes, fsdp = mesh_of(config)
    mesh = build_mesh(MeshSpec(**axes))
    if mesh.size != ctx.cell.chips:
        raise SystemExit(f"benchmark: the configuration's mesh "
                         f"{dict(mesh.shape)} is not the cell's "
                         f"{ctx.cell.chips} chip(s)")
    mcfg = family.model_config(config, seq)
    optimizer = make_optimizer(
        learning_rate=hp["learning_rate"], weight_decay=hp["weight_decay"],
        b1=hp["b1"], b2=hp["b2"], grad_clip=hp["grad_clip"],
        warmup_steps=hp["warmup_steps"], carry=hp["carry_rounding"])
    step, _ = build_train_step(mcfg, mesh, fsdp=fsdp, optimizer=optimizer)
    p_shard = param_shardings(mcfg, mesh, fsdp=fsdp)
    tok_shard = NamedSharding(mesh, P("dp", None))
    key = weights.seed_key(ctx.seed)

    make = jax.jit(lambda k: weights.make_stacked(config, k),
                   out_shardings=p_shard)
    opt_shard = optax.tree_map_params(
        optimizer, lambda _, s: s,
        jax.eval_shape(lambda k: optimizer.init(make(k)), key), p_shard,
        transform_non_params=lambda _: NamedSharding(mesh, P()))
    params = make(key)
    opt_state = jax.jit(optimizer.init, out_shardings=opt_shard)(params)
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    jax.block_until_ready((params, opt_state))
    say(f"[train] {n_params / 1e6:.1f} M parameters ({n_params}; "
        f"{family.counts} counts {family.params(config)}) on mesh "
        f"{dict(mesh.shape)} fsdp={fsdp}: weights and optimizer state in "
        f"{time.perf_counter() - t0:.1f} s")

    def feed(index: int):
        with spans.span("feed"):
            return jax.device_put(weights.token_batch(
                ctx.seed, index, rows, seq, config["vocab_size"]), tok_shard)

    t0 = time.perf_counter()
    tokens = feed(0)
    compiled = step.lower(params, opt_state, tokens).compile()
    say(f"[train] step compiled in {time.perf_counter() - t0:.1f} s; "
        f"memory_analysis: {compiled.memory_analysis()}")

    norms = jax.jit(tree_norms)

    def as_stored(leaf):
        """A leaf of the starting point in float32, as the parameter
        stored it (``hybrid_train_steps``: XLA on a TPU may keep the
        float32 it was drawn in through a fused conversion)."""
        if leaf.dtype == jnp.float32:
            return leaf
        bits = jnp.finfo(leaf.dtype)
        return jax.lax.reduce_precision(
            leaf.astype(jnp.float32), bits.nexp, bits.nmant)

    # what the parameters stand at: with what their rounding carries
    change = jax.jit(lambda p, s, k: tree_norms(jax.tree.map(
        lambda a, b: a - as_stored(b),
        carried_params(p, s), jax.lax.with_sharding_constraint(
            weights.make_stacked(config, k), p_shard))))

    def one_step(params, opt_state, tokens):
        with spans.span("step"):
            return compiled(params, opt_state, tokens)

    # the first two steps, through the window's own call and feed
    t0 = time.perf_counter()
    params, opt_state, m1 = one_step(params, opt_state, tokens)
    mu = adam_state(opt_state).mu
    first = jax.device_get(norms(mu))
    # leaf by leaf on the host, for the norm of the difference from the
    # reference's (``check``)
    kept["first_grad_leaves"] = jax.device_get(mu)
    del mu
    params, opt_state, m2 = one_step(params, opt_state, feed(1))
    moved = jax.device_get(change(params, opt_state, key))
    loss = [float(m1["loss"]), float(m2["loss"])]
    grad_norm = [float(m1["grad_norm"]), float(m2["grad_norm"])]
    first_rows = [publish_moe_rows(m) for m in (m1, m2)]
    parts = [publish_loss_parts(m) for m in (m1, m2)]
    # mu after one step is (1 - b1) times the clipped gradient
    unclip = max(1.0, grad_norm[0] / hp["grad_clip"]) / (1 - hp["b1"])
    program_numbers = {
        "loss": loss, "grad_norm": grad_norm,
        "first_grad": {k: np.asarray(v) * unclip for k, v in first.items()},
        "first_grad_scale": unclip,
        "change": {k: np.asarray(v) for k, v in moved.items()},
        "loss_parts": parts}
    say(f"[train] first two steps in {time.perf_counter() - t0:.1f} s: loss "
        f"{loss} in parts {parts}, grad_norm {grad_norm}, expert rows "
        f"{first_rows}")

    # the window: the same object goes on from step 3
    seconds = ctx.window_seconds
    counted = dict.fromkeys(MOE_ROWS, 0)
    held_by_step, fullest_by_step = [], []

    def read(metrics) -> int:
        """1 where the step failed: a loss that is no number, or rows
        that no expert computed."""
        step_rows = publish_moe_rows(metrics)
        held_by_step.append(step_rows.get("moe_rows_held", 0))
        fullest_by_step.append(step_rows.get("moe_rows_max_expert", 0))
        for name, value in step_rows.items():
            counted[name] += value
        return int(not np.isfinite(float(metrics["loss"]))
                   or step_rows.get("moe_rows_over", 0) > 0)

    done, failed, pending = 0, 0, None
    with ctx.window():
        start = time.perf_counter()
        while True:
            params, opt_state, metrics = one_step(
                params, opt_state, feed(2 + done))
            done += 1
            if pending is not None:
                with spans.span("wait"):  # one step behind the device
                    failed += read(pending)
            pending = metrics
            if time.perf_counter() - start >= seconds:
                break
        with spans.span("wait"):
            failed += read(pending)
            last = float(pending["loss"])
            last_parts = publish_loss_parts(pending)
            jax.block_until_ready(params)
        end = time.perf_counter()
    # the stack's expert layers and the module's
    n_moe = mcfg.stack.every_kind.count("E")
    held = config["n_routed_experts"]
    # rows a layer's held experts draw a step under even routing
    expected = (rows * seq * config["num_experts_per_tok"] * held
                / config["router_width"])
    say(f"[train] window: {done} steps of {rows * seq} tokens in "
        f"{end - start:.3f} s, last loss {last:.4f} in parts {last_parts}; "
        f"expert rows {counted} "
        f"against a buffer of {mcfg.stack.row_buffer(rows * seq)} a layer; "
        f"held a step {min(held_by_step)} to {max(held_by_step)}, expected "
        f"{n_moe * expected:.0f}; the fullest held expert of a step "
        f"{min(fullest_by_step)} to {max(fullest_by_step)}, expected "
        f"{expected / held:.0f}; by step, held "
        f"{held_by_step} and fullest {fullest_by_step}")
    return {
        "end_to_end": {"tokens_per_s": done * rows * seq / (end - start)},
        "attempted": done, "failed": int(failed),
        "window": (start, end),
        "facts": {"steps": done, "batch": rows, "seq": seq,
                  "layers": config["num_hidden_layers"],
                  "batch_per_device": rows // mesh.shape["dp"],
                  "heads_per_device": mcfg.heads // mesh.shape["tp"],
                  "kv_heads_per_device": max(
                      1, mcfg.kv_heads // mesh.shape["tp"]),
                  "head_dim": mcfg.head_dim,
                  **counted,
                  "moe_row_buffer": mcfg.stack.row_buffer(rows * seq),
                  "moe_rows_mean_expert": counted["moe_rows_held"] / max(
                      1, n_moe * held),
                  "moe_layers": n_moe,
                  "moe_rows_expected_a_layer": expected,
                  "moe_rows_held_a_layer": counted["moe_rows_held"] / max(
                      1, n_moe * done),
                  **family.facts(config, mcfg, mesh)},
        "program_numbers": program_numbers,
    }


def follow(ctx, family: Family, operand: str = "float32", fault=None,
           rows=None, **more):
    """The plain reference's two steps over the run's first two batches
    from the run's seed. ``operand`` other than float32 gives the
    control; ``fault`` one of the reference's planted faults; ``rows``
    the first rows of each batch alone (the half-batch fault); ``more``
    goes to the reference's ``follow_two_steps`` as it is."""
    import jax
    import jax.numpy as jnp

    config, mix, weights = ctx.cell.config, ctx.cell.workload, family.weights
    reference = loader.plugin("references", mix["check"]["reference"])
    key = weights.seed_key(ctx.seed)
    kinds = [kind for _, kind in weights.entries(config)]
    leaf = jax.jit(lambda k, layer, kind, name: weights.make_leaf(
        config, k, kind, name, layer).astype(jnp.float32),
        static_argnames=("kind", "name"))

    def initial_leaf(name, layer):
        if layer is None:
            return leaf(key, None, kind="mtp" if name in weights.MTP_LEAVES
                        else None, name=name)
        return leaf(key, layer, kind=kinds[layer], name=name)

    batches = [weights.token_batch(ctx.seed, i, mix["batch"], mix["seq"],
                                   config["vocab_size"])[:rows]
               for i in range(2)]
    return reference.follow_two_steps(
        config, config["run"]["optimizer"], initial_leaf, batches,
        reference.OPERANDS[operand], fault, **more)


def check(ctx, family: Family, program_numbers):
    """(correct, {number: {value, limit}}): the program's first two steps
    against the plain reference's."""
    t0 = time.perf_counter()
    ref = follow(ctx, family, against=leaf_of(
        program_numbers["first_grad_leaves"], family, ctx.cell.config,
        program_numbers["first_grad_scale"]))
    numbers = compare_difference.training_numbers(program_numbers, ref)
    ctx.say(f"[train] reference followed two steps in "
            f"{time.perf_counter() - t0:.1f} s: loss {ref['loss']} in parts "
            f"{ref['loss_parts']}, grad_norm {ref['grad_norm']}; {numbers}")
    return compare.judge(numbers, ctx.cell.workload["check"]["limits"])
