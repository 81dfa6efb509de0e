"""Traffic ``train_steps``: a training job's steps, one after another.

Through the entry a user calls: ``train.Trainer(backend="jax",
num_workers=1).run(fn)``, whose function builds
``models.training.build_train_step`` for the configuration's widths on
the configuration's mesh and feeds it a fresh batch of seeded token ids
each step, put on the device inside the window. One object, the compiled
step with its state, is built in set-up, driven from the seed through its
first two steps (their losses, the first gradient as the optimizer's
state holds it, and the parameters' change are kept for the comparison),
and handed to the window.

Parameters of the mix (``benchmark/workloads/<cell>.json``): ``batch``
rows of ``seq`` tokens a step, ``trace_seconds`` for the window of a
traced run, ``check``: which reference, which operand rule, the limits.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import compare, loader, weights


def mesh_of(config: dict):
    """(MeshSpec arguments, fsdp) from the configuration's ``mesh``:
    ``fsdp`` is data parallelism with the parameters sharded over it."""
    axes = dict(config.get("mesh") or {})
    fsdp = axes.pop("fsdp", 0)
    if fsdp:
        axes["dp"] = axes.get("dp", 1) * fsdp
    return axes, bool(fsdp)


def model_config(config: dict, seq: int):
    import jax.numpy as jnp

    from ray_tpu.models import transformer as tfm

    run = config["run"]
    if config["sliding_window"] and seq > config["sliding_window"]:
        raise SystemExit("benchmark: the program has no sliding window, so "
                         "a sequence longer than the window is not this model")
    return tfm.ModelConfig(
        vocab_size=config["vocab_size"], hidden=config["hidden_size"],
        layers=config["num_hidden_layers"],
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"],
        intermediate=config["intermediate_size"], max_seq=seq,
        rope_theta=config["rope_theta"], norm_eps=config["rms_norm_eps"],
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
            config["torch_dtype"]],
        remat=run["remat"], remat_policy=run["remat_policy"],
        tie_embeddings=config["tie_word_embeddings"],
        logits_chunk=run["logits_chunk"])


def adam_state(opt_state):
    """The optimizer's Adam moments, wherever the chain keeps them."""
    import jax

    found = [s for s in jax.tree.leaves(
        opt_state, is_leaf=lambda x: hasattr(x, "mu")) if hasattr(s, "mu")]
    if len(found) != 1:
        raise RuntimeError(f"expected one Adam state in the optimizer's "
                           f"state, found {len(found)}")
    return found[0]


def tree_norms(tree):
    """{leaf: float32 norm of each layer} of a tree laid out as the
    program's parameters (layers stacked on the leading axis)."""
    import jax.numpy as jnp

    def norm(x, keep_first):
        x = jnp.square(x.astype(jnp.float32))
        return jnp.sqrt(x.sum(axis=tuple(range(1, x.ndim))) if keep_first
                        else x.sum())

    out = {k: norm(v, False)[None] for k, v in tree.items() if k != "layers"}
    out.update({"layers/" + k: norm(v, True)
                for k, v in tree["layers"].items()})
    return out


def run(ctx):
    import ray_tpu
    from ray_tpu.train.trainer import Trainer

    t0 = time.perf_counter()
    ray_tpu.init(num_cpus=2)
    try:
        trainer = Trainer(backend="jax", num_workers=1, max_retries=0)
        try:
            trainer.start()
            ctx.say(f"[train] ray_tpu.init and the Trainer's worker in "
                    f"{time.perf_counter() - t0:.1f} s")
            outcome, = trainer.run(lambda: train_func(ctx))
        finally:
            trainer.shutdown()
    finally:
        ray_tpu.shutdown()
    # the program's state went with the worker's function; the reference
    # runs only when the harness asks, after it has read the memory
    outcome["check"] = lambda: check(ctx, outcome["program_numbers"])
    return outcome


def train_func(ctx):
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.models.training import (
        build_train_step,
        make_optimizer,
        param_shardings,
    )
    from ray_tpu.parallel.mesh import MeshSpec, build_mesh

    config, mix = ctx.cell.config, ctx.cell.workload
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
    mcfg = model_config(config, seq)
    optimizer = make_optimizer(
        learning_rate=hp["learning_rate"], weight_decay=hp["weight_decay"],
        b1=hp["b1"], b2=hp["b2"], grad_clip=hp["grad_clip"])
    step, _ = build_train_step(mcfg, mesh, fsdp=fsdp, optimizer=optimizer)
    p_shard = param_shardings(mcfg, mesh, fsdp=fsdp)
    tok_shard = NamedSharding(mesh, P("dp", None))
    key = weights.seed_key(ctx.seed)

    # weights from the seed in one jitted call, in the type they are
    # trained in, each where it lives; moments like their parameter
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
    say(f"[train] {n_params / 1e6:.1f} M parameters on mesh "
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
    change = jax.jit(lambda p, k: tree_norms(jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
        p, jax.lax.with_sharding_constraint(
            weights.make_stacked(config, k), p_shard))))

    def one_step(params, opt_state, tokens):
        with spans.span("step"):
            return compiled(params, opt_state, tokens)

    # the first two steps, through the window's own call and feed
    t0 = time.perf_counter()
    params, opt_state, m1 = one_step(params, opt_state, tokens)
    first = jax.device_get(norms(adam_state(opt_state).mu))
    params, opt_state, m2 = one_step(params, opt_state, feed(1))
    moved = jax.device_get(change(params, key))
    loss = [float(m1["loss"]), float(m2["loss"])]
    grad_norm = [float(m1["grad_norm"]), float(m2["grad_norm"])]
    # mu after one step is (1 - b1) times the clipped gradient
    unclip = max(1.0, grad_norm[0] / hp["grad_clip"]) / (1 - hp["b1"])
    program_numbers = {
        "loss": loss, "grad_norm": grad_norm,
        "first_grad": {k: np.asarray(v) * unclip for k, v in first.items()},
        "change": {k: np.asarray(v) for k, v in moved.items()}}
    say(f"[train] first two steps in {time.perf_counter() - t0:.1f} s: loss "
        f"{loss}, grad_norm {grad_norm}")

    # the window: the same object goes on from step 3
    seconds = ctx.window_seconds
    done, failed, pending = 0, 0, None
    with ctx.window():
        start = time.perf_counter()
        while True:
            params, opt_state, metrics = one_step(
                params, opt_state, feed(2 + done))
            done += 1
            if pending is not None:
                with spans.span("wait"):  # one step behind the device
                    failed += not np.isfinite(float(pending["loss"]))
            pending = metrics
            if time.perf_counter() - start >= seconds:
                break
        with spans.span("wait"):
            last = float(pending["loss"])
            jax.block_until_ready(params)
        end = time.perf_counter()
    failed += not np.isfinite(last)
    say(f"[train] window: {done} steps of {rows * seq} tokens in "
        f"{end - start:.3f} s, last loss {last:.4f}")
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
                  "head_dim": mcfg.head_dim},
        "program_numbers": program_numbers,
    }


def follow(ctx, operand: str = "float32", rows=None):
    """The plain reference's two steps over the run's first two batches
    from the run's seed. ``operand`` other than float32 gives the control;
    ``rows`` keeps only the first rows of each batch (a planted fault)."""
    import jax
    import jax.numpy as jnp

    config, mix = ctx.cell.config, ctx.cell.workload
    reference = loader.plugin("references", mix["check"]["reference"])
    key = weights.seed_key(ctx.seed)
    leaf = jax.jit(lambda k, layer, name: weights.make_leaf(
        config, k, name, layer).astype(jnp.float32), static_argnames="name")
    batches = [weights.token_batch(ctx.seed, i, mix["batch"], mix["seq"],
                                   config["vocab_size"])[:rows]
               for i in range(2)]
    # blocks of layers on each of the cell's chips, the embedding with the
    # first block, the final norm and the output head with the last
    devices = jax.devices()[: ctx.cell.chips]
    n_layers = config["num_hidden_layers"]

    def initial_leaf(name, layer):
        device = (devices[layer * len(devices) // n_layers]
                  if layer is not None
                  else devices[0 if name == "embed" else -1])
        return jax.device_put(leaf(key, layer, name=name), device)

    return reference.follow_two_steps(
        config, config["run"]["optimizer"], initial_leaf, batches,
        reference.OPERANDS[operand])


def check(ctx, program_numbers):
    """(correct, {number: {value, limit}}): the program's first two steps
    against the plain reference's."""
    t0 = time.perf_counter()
    ref = follow(ctx)
    numbers = compare.training_numbers(program_numbers, ref)
    ctx.say(f"[train] reference followed two steps in "
            f"{time.perf_counter() - t0:.1f} s: loss {ref['loss']}, "
            f"grad_norm {ref['grad_norm']}; {numbers}")
    return compare.judge(numbers, ctx.cell.workload["check"]["limits"])
