"""What a whole-model test of a stack by pattern asks, written once: the
program's parameters and the plain reference's gradients by one set of
names, and the program's first two steps through ``build_train_step``
beside the reference's. A family's test hands it the family's tiny
configuration, seeded weights' module, driver and reference; the five
earlier model tests keep copies of their own (ROADMAP C11a)."""

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.drivers import _expert_train_steps as body
from ray_tpu.models.training import (
    build_train_step,
    carried_params,
    make_optimizer,
)
from ray_tpu.observability import device_programs as dp
from ray_tpu.parallel.mesh import MeshSpec, build_mesh


def seeded(weights, cfg: dict, seed: int = 1):
    """The program's parameters from the seed, widened to float32."""
    return jax.tree.map(lambda a: a.astype(jnp.float32),
                        weights.make_stacked(cfg, weights.seed_key(seed)))


def by_name(tree):
    """The program's tree, leaf by leaf, by the reference's names:
    ``<where>/<kind>/<leaf>[i]`` for the i-th layer of a kind there."""
    out = {k: v for k, v in tree.items() if not isinstance(v, dict)}
    for where in ("lead", "layers"):
        for kind, leaves in tree.get(where, {}).items():
            for k, v in leaves.items():
                for i in range(v.shape[0]):
                    out[f"{where}/{kind}/{k}[{i}]"] = v[i]
    return out


def reference_by_name(named_leaves):
    """``by_name``'s names for the reference's (name, entry, key, array)
    of every leaf, the entries of one name counted in their order."""
    out, seen = {}, {}
    for name, layer, _, g in named_leaves:
        if layer is None:
            out[name] = g
        else:
            i = seen[name] = seen.get(name, -1) + 1
            out[f"{name}[{i}]"] = g
    return out


def program_numbers(mcfg, hp: dict, params, batches):
    """The program's first two steps through ``build_train_step`` on one
    device from ``params``, as the expert cells' driver reads them: the
    losses, the first gradient's norms (unclipped) and leaves, the
    parameters' change. -> (numbers, (metrics 1, metrics 2))."""
    mesh = build_mesh(MeshSpec(), jax.devices()[:1])
    optimizer = make_optimizer(carry=True, **{
        k: hp[k] for k in ("learning_rate", "weight_decay", "b1", "b2",
                           "grad_clip", "warmup_steps")})
    step, _ = build_train_step(mcfg, mesh, optimizer=optimizer)
    # the step donates its arguments
    start = jax.tree.map(jnp.copy, params)
    opt_state = optimizer.init(params)
    dp.clear()
    compiled = step.lower(params, opt_state, batches[0]).compile()
    params, opt_state, m1 = compiled(params, opt_state, batches[0])
    # on the host before the next step takes the state's buffers
    mu = jax.device_get(body.adam_state(opt_state).mu)
    first = body.tree_norms(mu)
    params, opt_state, m2 = compiled(params, opt_state, batches[1])
    unclip = max(1.0, float(m1["grad_norm"])) / (1 - hp["b1"])
    return {
        "loss": [float(m1["loss"]), float(m2["loss"])],
        "first_grad": {k: np.asarray(v) * unclip for k, v in first.items()},
        "first_grad_leaves": mu, "first_grad_scale": unclip,
        "change": {k: np.asarray(v) for k, v in body.tree_norms(
            jax.tree.map(jnp.subtract, carried_params(params, opt_state),
                         start)).items()}}, (m1, m2)


def reference_numbers(reference, weights, cfg: dict, hp: dict, seed: int,
                      batches, **more):
    """The reference's two steps from the same seeded leaves."""
    kinds = [kind for _, kind in weights.entries(cfg)]
    key = weights.seed_key(seed)

    def initial_leaf(name, layer):
        kind = kinds[layer] if layer is not None else None
        return weights.make_leaf(cfg, key, kind, name, layer).astype(
            jnp.float32)

    return reference.follow_two_steps(cfg, hp, initial_leaf, batches,
                                      **more)
