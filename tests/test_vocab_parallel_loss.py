"""The vocabulary-parallel loss (``transformer._mean_nll_on_mesh``): on a
mesh, each chip's rows meet its share of the vocabulary and only the
softmax's ``[rows, chunk]`` sums cross the chips. It must give what
``_mean_nll`` gives on one device, value and gradients, and say which
form a traced loss took (``loss_unembed_calls{layout}``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu.models import transformer as tfm
from ray_tpu.models.training import build_train_step
from ray_tpu.observability.metrics import loss_unembed_calls
from ray_tpu.parallel.mesh import MeshSpec, build_mesh

B, S, H, V = 4, 32, 64, 256

MESHES = {
    "dp2xtp2 fsdp": (MeshSpec(dp=2, tp=2), True),
    "dp2xtp2": (MeshSpec(dp=2, tp=2), False),
    "dp2xsp2xtp2 fsdp": (MeshSpec(dp=2, sp=2, tp=2), True),
}


def _inputs(seed=0):
    kx, kw, kt, km = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(kx, (B, S, H), jnp.float32)
    w = 0.3 * jax.random.normal(kw, (H, V), jnp.float32)
    targets = jax.random.randint(kt, (B, S), 0, V)
    weights = jax.random.bernoulli(km, 0.7, (B, S))
    return x, w, targets, weights


def _close(got, want, rtol=1e-5):
    got, want = np.asarray(got), np.asarray(want)
    np.testing.assert_allclose(got, want, rtol=rtol,
                               atol=rtol * np.abs(want).max())


@pytest.mark.parametrize("chunk", [8, 0], ids=["chunked", "unchunked"])
@pytest.mark.parametrize("weighted", [False, True],
                         ids=["all", "weighted"])
@pytest.mark.parametrize("mesh_name", list(MESHES))
def test_mesh_loss_equals_one_device(mesh_name, weighted, chunk):
    spec, fsdp = MESHES[mesh_name]
    mesh = build_mesh(spec)
    x, w, targets, weights = _inputs()
    weights = weights if weighted else None
    at = NamedSharding(mesh, P("dp" if fsdp else None, "tp"))

    def loss(x, w, sharding=None):
        return tfm._mean_nll(x, targets, w, chunk, weights, sharding)

    want, (want_dx, want_dw) = jax.value_and_grad(loss, (0, 1))(x, w)
    # x laid out otherwise than the loss takes it: hidden over dp
    x_on = jax.device_put(x, NamedSharding(mesh, P(None, None, "dp")))
    got, (dx, dw) = jax.jit(jax.value_and_grad(
        lambda x, w: loss(x, w, at), (0, 1)))(x_on, jax.device_put(w, at))
    _close(got, want)
    _close(dx, want_dx)
    _close(dw, want_dw)


def _step_counts(mesh, fsdp):
    cfg = tfm.ModelConfig.debug(logits_chunk=16)
    step, init_fn = build_train_step(cfg, mesh, fsdp=fsdp)
    params, opt_state = init_fn(jax.random.PRNGKey(0))
    tokens = jax.device_put(
        jnp.zeros((4, 33), jnp.int32), NamedSharding(mesh, P("dp", None)))
    before = dict(loss_unembed_calls.series())
    step.lower(params, opt_state, tokens)
    after = dict(loss_unembed_calls.series())
    return {k[0]: after[k] - before.get(k, 0) for k in after
            if after[k] != before.get(k, 0)}


@pytest.mark.parametrize("spec,fsdp,layout", [
    (MeshSpec(dp=2, tp=2), True, "vocab_parallel"),
    (MeshSpec(), False, "plain"),
])
def test_counter_says_which_loss_a_step_traced(spec, fsdp, layout):
    assert _step_counts(build_mesh(spec), fsdp) == {layout: 1}
