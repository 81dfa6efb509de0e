"""The router's choice (``ray_tpu/ops/router.py``): the kernel
``router_topk_fwd`` under Pallas's interpreter and both tiers' shared
backward against plain ``jnp`` differentiated by JAX (``lax.top_k``,
``take_along_axis``, the one-hot count), to the bit, at the expert cells'
widths and choices, at widths whose pieces of a block are not the cells',
and where the scores tie; ``route``'s weights and their gradient; the rule
between the tiers; the counter."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import transformer as tfm
from ray_tpu.observability.metrics import moe_router_calls
from ray_tpu.ops import attention, router

# (router width, choices a token, score) of the expert cells: Nemotron-3
# Super, Solar-Open2, Nemotron-H, Mellum 2, and LFM2 and GLM-4.7-Flash
# (one shape)
CELLS = [(512, 22, "sigmoid"), (320, 8, "sigmoid"), (128, 6, "sigmoid"),
         (64, 8, "softmax"), (64, 4, "sigmoid")]


@pytest.fixture(autouse=True)
def interpreted(monkeypatch):
    monkeypatch.setattr(attention, "_FORCE_INTERPRET", True)


def _counted(run):
    """(run's result, what ``moe_router_calls`` counted meanwhile)."""
    before = dict(moe_router_calls.series())
    out = run()
    return out, {k: v - before.get(k, 0)
                 for k, v in moe_router_calls.series().items()
                 if v != before.get(k, 0)}


def _scores(width, score, tokens, seed):
    """(scores [tokens, width] float32, the correction bias or None)."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 2)
    logits = jax.random.normal(keys[0], (tokens, width))
    if score == "softmax":
        return jax.nn.softmax(logits, axis=-1), None
    return (jax.nn.sigmoid(logits),
            0.05 * jax.random.normal(keys[1], (width,)))


def _pulled(scores, bias, k, kernel, d_gates):
    """(chosen, gates, drawn, d scores) of one tier's custom VJP (``kernel``
    True or False), or of plain ``jnp`` differentiated by JAX, the
    oracle (``kernel`` None)."""
    def gates(s):
        chosen, gates, drawn = (
            router._jnp_top_k(s, bias, k) if kernel is None
            else router._top_k(s, bias, k, kernel))
        return gates, (chosen, drawn)

    gates, vjp, (chosen, drawn) = jax.vjp(gates, scores, has_aux=True)
    return (chosen, gates, drawn, *vjp(d_gates))


def _same(got, want):
    for name, a, b in zip(("chosen", "gates", "drawn", "d scores"),
                          got, want):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype and a.shape == b.shape, name
        assert np.array_equal(a.view(np.int32), b.view(np.int32)), name


@pytest.mark.parametrize("width,k,score,blocks", [
    cell + (blocks,) for cell, blocks in zip(CELLS, (2, 3, 2, 2, 3))] + [
    # widths whose block is worked in pieces of 256 tokens, not the cells'
    (72, 6, "sigmoid", 2), (80, 4, "softmax", 2)])
def test_the_kernel_is_the_jnp_tier_to_the_bit(width, k, score, blocks):
    """Chosen experts, their scores, the counts and the scores' gradient
    equal plain ``jnp``'s bit for bit, over several blocks of tokens, in
    both tiers."""
    tokens = blocks * router.BLOCK_TOKENS
    scores, bias = _scores(width, score, tokens, width + k)
    d_gates = jax.random.normal(jax.random.PRNGKey(k), (tokens, k))
    assert router.router_tier(tokens, width, k)
    got, counted = _counted(
        lambda: _pulled(scores, bias, k, True, d_gates))
    assert counted == {("kernel", "fwd"): 1, ("kernel", "bwd"): 1}
    want = _pulled(scores, bias, k, None, d_gates)
    _same(got, want)
    _same(_pulled(scores, bias, k, False, d_gates), want)
    assert int(got[2].sum()) == tokens * k
    # every entry of d scores is one of d gates or nought
    assert int((np.asarray(got[3]) != 0).sum()) == tokens * k


def _tied(tie, width, tokens):
    """(scores, bias) whose order ties: ``experts``, four values shared
    by the experts of a row; ``rows``, every score of a row the same;
    ``bias``, every score the same and the bias, a permutation of the
    experts, the one thing that orders them."""
    keys = jax.random.split(jax.random.PRNGKey(width), 2)
    if tie == "experts":
        levels = jax.random.randint(keys[0], (tokens, width), 0, 4)
        return 0.25 + 0.125 * levels.astype(jnp.float32), jnp.zeros(width)
    scores = jnp.full((tokens, width), 0.5, jnp.float32)
    if tie == "rows":
        return scores, None
    return scores, jax.random.permutation(keys[1], width).astype(
        jnp.float32) / width


@pytest.mark.parametrize("width,k", [(512, 22), (64, 8)])
@pytest.mark.parametrize("tie", ["experts", "rows", "bias"])
def test_ties_go_to_the_lower_expert_as_top_k_has_them(tie, width, k):
    """Planted ties: the kernel chooses what ``lax.top_k`` chooses, in its
    order (descending, the lower expert first among equals)."""
    tokens = 2 * router.BLOCK_TOKENS
    scores, bias = _tied(tie, width, tokens)
    d_gates = jax.random.normal(jax.random.PRNGKey(3), (tokens, k))
    got = _pulled(scores, bias, k, True, d_gates)
    _same(got, _pulled(scores, bias, k, None, d_gates))
    chosen = np.asarray(got[0])
    if tie == "rows":
        assert (chosen == np.arange(k)).all()
    elif tie == "bias":
        order = np.argsort(-np.asarray(bias), kind="stable")[:k]
        assert (chosen == order).all()
        assert (np.asarray(got[1]) == 0.5).all()
    else:
        picked = np.take_along_axis(np.asarray(scores), chosen, axis=1)
        assert (np.diff(picked, axis=1) <= 0).all()
        # equal scores side by side: the lower expert first
        same = np.diff(picked, axis=1) == 0
        assert same.any() and (np.diff(chosen, axis=1)[same] > 0).all()


@pytest.mark.parametrize("width,k,score", [CELLS[0], CELLS[3]])
def test_the_routers_weights_and_their_gradient_are_the_jnp_tiers(
        width, k, score, monkeypatch):
    """``route``: the normed weights times ``routed_scale`` and the
    gradient of a sum of them with respect to the tokens and the router's
    weights (through its float32 logits) are, in both tiers, those of
    plain ``jnp`` differentiated by JAX, to the bit."""
    st = tfm.Stack(routed_experts=width, experts_per_token=k,
                   router_score=score, routed_scale=2.5)
    tokens, hidden = 2 * router.BLOCK_TOKENS, 32
    keys = jax.random.split(jax.random.PRNGKey(width), 4)
    xt = jax.random.normal(keys[0], (tokens, hidden))
    layer = {"router": 0.3 * jax.random.normal(keys[1], (hidden, width))}
    if st.router_bias:
        layer["router_bias"] = 0.05 * jax.random.normal(keys[2], (width,))
    weigh = jax.random.normal(keys[3], (tokens, k))

    def tier(kernel):
        monkeypatch.setattr(attention, "_FORCE_INTERPRET", kernel)

        def loss(xt, layer):
            chosen, gates, drawn = tfm.route(xt, layer, st)
            return jnp.sum(gates * weigh), (chosen, gates, drawn)

        (_, out), grads = jax.value_and_grad(loss, (0, 1), has_aux=True)(
            xt, layer)
        return out, grads

    got, counted = _counted(lambda: tier(True))
    assert counted == {("kernel", "fwd"): 1, ("kernel", "bwd"): 1}
    jnp_tier, counted = _counted(lambda: tier(False))
    assert counted == {("jnp", "fwd"): 1, ("jnp", "bwd"): 1}
    monkeypatch.setattr(tfm, "top_k_route", lambda s, b, k, _: (
        router._jnp_top_k(s, b, k)))
    want = tier(False)
    for a, b, c in zip(jax.tree.leaves(got), jax.tree.leaves(jnp_tier),
                       jax.tree.leaves(want)):
        a, b, c = np.asarray(a), np.asarray(b), np.asarray(c)
        assert np.array_equal(a.view(np.int32), c.view(np.int32))
        assert np.array_equal(b.view(np.int32), c.view(np.int32))
    (_, gates, _), (d_xt, d_layer) = got
    assert float(jnp.abs(d_xt).max()) > 0
    assert float(jnp.abs(d_layer["router"]).max()) > 0
    # the bias chooses and never weighs: its gradient is nought
    assert not float(jnp.abs(d_layer.get("router_bias", 0.0)).max())
    np.testing.assert_allclose(np.asarray(gates.sum(-1)), 2.5, rtol=1e-6)


@pytest.mark.parametrize("tokens,width,k,sharded,on", [
    (16384, 512, 22, False, True),     # the Super cell's router
    (16384, 80, 6, False, True),       # a block worked 256 tokens at once
    (16384, 512, 64, False, True),     # the most choices the kernel takes
    (16384, 512, 65, False, False),    # more choices than it unrolls
    (16384, 512, 22, True, False),     # a step partitioned over a mesh
    (16384, 500, 22, False, False),    # a width off the 8 sublanes
    (16000, 512, 22, False, False),    # tokens off the blocks
    (16384, 4096, 22, False, False),   # wider than a block may be
    (16384, 8, 9, False, False),       # more choices than experts
])
def test_the_rule_between_the_tiers(tokens, width, k, sharded, on):
    assert router.router_tier(tokens, width, k, sharded) == on


def test_a_block_is_worked_in_whole_pieces():
    """Every width the rule admits works a block in pieces of whole
    registers of lanes that tile it."""
    for width in range(8, router.MAX_WIDTH + 1, 8):
        wide = router._lanes_at_once(width)
        assert wide % 128 == 0 and router.BLOCK_TOKENS % wide == 0, width


def test_off_the_rule_the_jnp_tier_runs(monkeypatch):
    """A shape the rule refuses, and any shape where kernels are off, is
    the ``jnp`` tier, counted so."""
    scores, bias = _scores(64, "sigmoid", 640, 1)
    _, counted = _counted(lambda: router.top_k_route(scores, bias, 4))
    assert counted == {("jnp", "fwd"): 1}
    monkeypatch.setattr(attention, "_FORCE_INTERPRET", False)
    scores, bias = _scores(64, "sigmoid", 1024, 1)
    assert not router.router_tier(1024, 64, 4)
    _, counted = _counted(lambda: router.top_k_route(scores, bias, 4))
    assert counted == {("jnp", "fwd"): 1}
