"""The Mellum stack (sliding-window and full attention with a rotary
table each, softmax-routed SwiGLU experts in every layer, a share of them
held) at tiny widths on the CPU, each piece against the plain reference
``benchmark/references/mellum_decoder.py`` or a stated identity."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import compare, flops_mellum, weights_mellum
from benchmark.drivers import mellum_train_steps as driver
from benchmark.references import mellum_decoder as reference
from ray_tpu.models import transformer as tfm
from ray_tpu.models.training import (
    build_train_step,
    carried_params,
    make_optimizer,
    publish_moe_rows,
)
from ray_tpu.parallel.mesh import MeshSpec, build_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(
        ROOT, "benchmark/configs/mellum2_12b_l8_ep4.json")) as f:
    MELLUM = json.load(f)
# hidden 64, 4 / 2 heads x 16, a window of 12 in 32 positions, 8 experts
# top-2 of width 24, experts 2-4 held; two periods sliding, sliding, full
TINY = dict(
    MELLUM, hidden_size=64, head_dim=16, num_attention_heads=4,
    num_key_value_heads=2, sliding_window=12, moe_intermediate_size=24,
    router_width=8, num_experts=3, experts_held_first=2,
    num_experts_per_tok=2, vocab_size=256, torch_dtype="float32",
    layer_types=["sliding_attention", "sliding_attention",
                 "full_attention"] * 2,
    mlp_layer_types=["sparse"] * 6, num_hidden_layers=6,
    rope_parameters={
        "full_attention": dict(MELLUM["rope_parameters"]["full_attention"],
                               original_max_position_embeddings=16,
                               factor=4, beta_fast=4),
        "sliding_attention": MELLUM["rope_parameters"]["sliding_attention"]},
    run=dict(MELLUM["run"], logits_chunk=16))
HP = dict(MELLUM["run"]["optimizer"], warmup_steps=8)
SEQ = 32


@pytest.fixture(autouse=True)
def exact_products():
    with jax.default_matmul_precision("highest"):
        yield


def model_config(cfg=TINY, seq=SEQ, **stack):
    built = driver.model_config(cfg, seq)
    return dataclasses.replace(
        built, stack=dataclasses.replace(built.stack, **stack))


def seeded(cfg=TINY, seed=1):
    return jax.tree.map(
        lambda a: a.astype(jnp.float32),
        weights_mellum.make_stacked(cfg, weights_mellum.seed_key(seed)))


def one_layer(params, kind, index=0):
    return jax.tree.map(lambda a: a[index], params["layers"][kind])


# ------------------------------------------------------------ the stack
def test_the_configuration_describes_the_stack():
    """What the driver hands ``Stack`` from the published keys: two
    entries a layer, every width as published, the cut as the file says."""
    cfg = driver.model_config(MELLUM, 8192)
    st = cfg.stack
    assert st.pattern == "WEWEWE*E" * 2 and st.period == "WEWEWE*E"
    assert cfg.layers == 16 and [st.count(c) for c in "WE*"] == [6, 8, 2]
    assert (cfg.hidden, cfg.heads, cfg.kv_heads, cfg.head_dim) == (
        2304, 32, 4, 128)
    assert (st.window, st.routed_experts, st.experts_per_token,
            st.expert_width, st.shared_width, st.held) == (
        1024, 64, 8, 896, 0, (0, 16))
    assert (st.router_score, st.expert_act, st.router_bias) == (
        "softmax", "swiglu", False)
    assert st.bias_rate == 0.0 and st.routed_scale == 1.0
    assert st.window_rope == tfm.Rope(theta=500000)
    assert st.rope.factor == 16 and st.rope.original_max_seq == 8192
    assert cfg.rope_of("W") is st.window_rope and cfg.rope_of("*") is st.rope
    assert cfg.rotary and st.row_buffer(2 * 8192) == 65536
    shapes = jax.eval_shape(lambda k: tfm.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    assert set(shapes["layers"]) == {"window", "attention", "moe"}
    assert set(shapes["layers"]["moe"]) == {"norm", "router", "w_gate",
                                            "w_up", "w_down"}
    count = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert count == flops_mellum.mellum_params(MELLUM) == 1_077_057_792
    # the published stack, whole: 28 layers, seven periods
    whole = tfm.Stack(pattern="WEWEWE*E" * 7, window=1024)
    assert whole.period == "WEWEWE*E" and whole.count("W") == 21
    assert MELLUM["published"]["num_hidden_layers"] == 28
    assert set(MELLUM) >= {"published", "reduced", "deployment", "assumed",
                           "departures"}
    assert "four chips share each layer" in MELLUM["deployment"]


def test_what_a_stack_cannot_describe_is_refused():
    with pytest.raises(ValueError, match="window"):
        tfm.Stack(pattern="WE")
    with pytest.raises(ValueError, match="router_score"):
        tfm.Stack(pattern="E", routed_experts=4, router_score="top")
    with pytest.raises(ValueError, match="expert_act"):
        tfm.Stack(pattern="E", routed_experts=4, expert_act="gelu")
    # the Nemotron-H stack's kinds take no rotary table, the uniform
    # stack the plain one
    assert tfm.ModelConfig().rope_of() == tfm.Rope(10000.0)
    assert tfm.ModelConfig(layers=2, stack=tfm.Stack(pattern="M*")
                           ).rope_of("*") is None
    assert not tfm.ModelConfig(layers=2, stack=tfm.Stack(pattern="M*")
                               ).rotary


def test_windowed_attention_over_an_sp_axis_is_refused():
    cfg = model_config()
    mesh = build_mesh(MeshSpec(sp=2), jax.devices()[:2])
    with pytest.raises(NotImplementedError, match="windowed attention"):
        build_train_step(cfg, mesh)
    with pytest.raises(NotImplementedError, match="expert layers hold"):
        build_train_step(cfg, build_mesh(MeshSpec(dp=2), jax.devices()[:2]))


# ------------------------------------------------------------ the layers
@pytest.mark.parametrize("kind", ["window", "attention", "moe"])
def test_a_layer_is_the_reference_layer(kind):
    """One layer of each kind on the seeded weights against the
    reference's row function: the band and the plain table, the causal
    mask and YaRN's table, the softmax router and the SwiGLU experts."""
    cfg = model_config()
    params = seeded()
    w = one_layer(params, kind, 1)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, SEQ, 64))
    dims = reference.Dims(TINY)
    if kind == "moe":
        got, drawn = tfm.moe_block(x, w, cfg)
        want = jnp.stack([reference.moe_row(
            row, w, dims, reference.OPERANDS["float32"]) for row in x])
        counted = sum(reference.drawn_row(row, w, dims) for row in x)
        assert np.array_equal(np.asarray(drawn), np.asarray(counted))
    else:
        char = "W" if kind == "window" else "*"
        cos, sin = cfg.rope_of(char).table(cfg.head_dim, SEQ)
        attend = lambda q, k, v, window=None: tfm.flash_attention(  # noqa: E731
            q, k, v, True, None, None, None, window)
        got = tfm.attention_block(
            x, w, cfg, cos, sin, attend,
            cfg.stack.window if kind == "window" else 0)
        want = jnp.stack([reference.attention_row(
            row, w, dims, reference.OPERANDS["float32"], kind) for row in x])
    assert float(jnp.abs(got - want).max()) < 2e-4
    assert float(jnp.abs(got - x).max()) > 1e-2


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_a_planted_fault_changes_its_layer(fault):
    """Each fault moves the layer it is planted in, and no other."""
    dims, params = reference.Dims(TINY), seeded()
    x = jax.random.normal(jax.random.PRNGKey(4), (SEQ, 64))
    rule = reference.OPERANDS["float32"]
    hit = {"window_ignored": "window", "plain_rope": "attention",
           "no_routed": "moe"}[fault]
    for kind in ("window", "attention", "moe"):
        w = one_layer(params, kind, 0)
        row = reference.LAYER_ROW[kind]
        moved = float(jnp.abs(row(x, w, dims, rule, kind, fault)
                              - row(x, w, dims, rule, kind)).max())
        assert (moved > 1e-3) == (kind == hit), (kind, moved)


@pytest.mark.parametrize("score,act,shared", [
    ("softmax", "swiglu", 0),       # Mellum's
    ("softmax", "swiglu", 40),
    ("sigmoid", "swiglu", 0),
    ("softmax", "relu2", 40),
    ("sigmoid", "relu2", 40),       # Nemotron-H's
])
def test_the_expert_layer_is_a_dense_loop(score, act, shared):
    """Whatever the stack describes of the ``E`` kind, the layer is the
    loop over the held experts with dense masks: every token through
    every held expert, weighed by nought where it was not chosen."""
    st = dataclasses.replace(
        model_config().stack, router_score=score, expert_act=act,
        shared_width=shared, routed_scale=1.5, experts_held=(1, 5))
    cfg = tfm.ModelConfig(vocab_size=64, hidden=64, layers=2, heads=4,
                          kv_heads=2, max_seq=SEQ, dtype=jnp.float32,
                          stack=dataclasses.replace(st, pattern="*E"))
    leaves = set(jax.eval_shape(lambda k: tfm.init_params(cfg, k),
                                jax.random.PRNGKey(0))["layers"]["moe"])
    assert ("router_bias" in leaves) == (score == "sigmoid")
    assert ("w_gate" in leaves) == (act == "swiglu")
    assert ("shared_up" in leaves) == bool(shared)
    assert ("shared_gate" in leaves) == (bool(shared) and act == "swiglu")
    w = one_layer(tfm.init_params(cfg, jax.random.PRNGKey(2)), "moe")
    if score == "sigmoid":
        w = dict(w, router_bias=jax.random.normal(
            jax.random.PRNGKey(3), (8,)) * 0.3)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, SEQ, 64))
    got, drawn = tfm.moe_block(x, w, cfg)

    u = tfm.rms_norm(x, w["norm"], cfg.norm_eps)
    logits = u @ w["router"]
    scores = (jax.nn.softmax(logits, -1) if score == "softmax"
              else jax.nn.sigmoid(logits))
    _, chosen = jax.lax.top_k(scores + w.get("router_bias", 0.0), 2)
    gates = jnp.take_along_axis(scores, chosen, -1)
    gates = gates / gates.sum(-1, keepdims=True) * 1.5

    def mlp(u, gate, up, down):
        inner = (jax.nn.silu(u @ gate) * (u @ up) if act == "swiglu"
                 else jnp.square(jax.nn.relu(u @ up)))
        return inner @ down

    want = x
    for e in range(5):
        weight = jnp.where(chosen == 1 + e, gates, 0.0).sum(-1)
        want = want + weight[..., None] * mlp(
            u, w["w_gate"][e] if act == "swiglu" else None, w["w_up"][e],
            w["w_down"][e])
    if shared:
        want = want + mlp(u, w.get("shared_gate"), w["shared_up"],
                          w["shared_down"])
    assert float(jnp.abs(got - want).max()) < 2e-4
    assert int(drawn.sum()) == 2 * SEQ * 2
    report = tfm.routing_report(drawn[None], cfg.stack, 2 * SEQ)
    assert ("router_bias_step" in report) == (score == "sigmoid")


def test_the_four_shares_add_up_to_the_uncut_layer():
    """The tie of the share to the model (guide section 4): the parts of
    the result that the four shares of 16 experts give add up to what
    the uncut layer of 64 gives; nothing is computed by every chip alike
    but the residual, counted once."""
    cfg = dict(TINY, router_width=64, num_experts=64, experts_held_first=0,
               num_experts_per_tok=8)
    uncut = model_config(cfg)
    w = one_layer(seeded(cfg, seed=2), "moe")
    x = jax.random.normal(jax.random.PRNGKey(7), (2, SEQ, 64))
    whole, drawn = tfm.moe_block(x, w, uncut)
    parts = 0
    for first in (0, 16, 32, 48):
        share = model_config(dict(cfg, num_experts=16,
                                  experts_held_first=first))
        held = {k: w[k][first:first + 16]
                for k in ("w_gate", "w_up", "w_down")}
        out, drawn_here = tfm.moe_block(x, dict(w, **held), share)
        parts = parts + (out - x)
        assert np.array_equal(np.asarray(drawn_here), np.asarray(drawn))
        # and the reference's share is the program's
        dims = reference.Dims(dict(cfg, num_experts=16,
                                   experts_held_first=first))
        want = reference.moe_row(x[0], dict(w, **held), dims,
                                 reference.OPERANDS["float32"])
        assert float(jnp.abs(out[0] - want).max()) < 2e-4
    assert float(jnp.abs(parts + x - whole).max()) < 2e-4
    assert float(jnp.abs(whole - x).max()) > 1e-2


def test_rows_beyond_the_buffer_are_counted(monkeypatch):
    """A router that sends every token to the held experts: the buffer,
    twice the even draw, holds half of them, and the step says so."""
    cfg = model_config(experts_held=(2, 2))
    w = one_layer(seeded(), "moe")
    w = dict(w, router=jnp.zeros((64, 8)).at[:, 2:4].set(1.0),
             **{k: w[k][:2] for k in ("w_gate", "w_up", "w_down")})
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(8), (2 * SEQ, 64))) + 1
    out, drawn = tfm.routed_experts(x, w, cfg.stack)
    report = tfm.routing_report(drawn[None], cfg.stack, 2 * SEQ)
    assert cfg.stack.row_buffer(2 * SEQ) == 2 * SEQ
    assert int(report["moe_rows_held"]) == 2 * SEQ * 2
    assert int(report["moe_rows_over"]) == 2 * SEQ
    assert bool(jnp.isfinite(out).all())


def poisoned_tail(lhs, rhs, sizes, out_dtype=None):
    """A grouped product that does with the buffer's tail what the
    chip's kernels do: selects it away on the way in (the rows and their
    cotangents alike) and leaves whatever the memory held on the way
    out, here NaN, in the product and in the rows' gradient."""
    tail = jnp.arange(lhs.shape[0])[:, None] >= sizes.sum()

    def product(lhs, rhs):
        return jax.lax.ragged_dot(jnp.where(tail, 0, lhs), rhs, sizes)

    @jax.custom_vjp
    def planted(lhs, rhs):
        return jnp.where(tail, jnp.nan, product(lhs, rhs))

    def backward(kept, g):
        d_lhs, d_rhs = jax.vjp(product, *kept)[1](jnp.where(tail, 0, g))
        return jnp.where(tail, jnp.nan, d_lhs), d_rhs

    planted.defvjp(lambda lhs, rhs: (planted(lhs, rhs), (lhs, rhs)),
                   backward)
    return planted(lhs, rhs)


@pytest.mark.parametrize("act", ["swiglu", "relu2"])
def test_what_the_buffers_tail_holds_reaches_no_gradient(monkeypatch, act):
    """Rows of the buffer beyond the last group belong to nobody and a
    chip's grouped product leaves them as the memory was (NaN, on the
    chip, in this model's first step): neither the layer's value nor any
    gradient may see them, the router's through the weights least of all."""
    monkeypatch.setattr(tfm, "grouped_matmul", poisoned_tail)
    st = dataclasses.replace(model_config().stack, expert_act=act,
                             experts_held=(2, 2))
    cfg = tfm.ModelConfig(vocab_size=64, hidden=64, layers=2, heads=4,
                          kv_heads=2, max_seq=SEQ, dtype=jnp.float32,
                          stack=dataclasses.replace(st, pattern="*E"))
    w = one_layer(tfm.init_params(cfg, jax.random.PRNGKey(2)), "moe")
    x = jax.random.normal(jax.random.PRNGKey(5), (2, SEQ, 64))
    dout = jax.random.normal(jax.random.PRNGKey(6), (2, SEQ, 64))

    def loss(x, w):
        return jnp.sum(tfm.moe_block(x, w, cfg)[0] * dout)

    _, drawn = tfm.moe_block(x, w, cfg)
    assert int(drawn[2:4].sum()) < cfg.stack.row_buffer(2 * SEQ)  # a tail
    value, grads = jax.value_and_grad(loss, (0, 1))(x, w)
    assert bool(jnp.isfinite(value))
    for leaf in jax.tree.leaves(grads):
        assert bool(jnp.isfinite(leaf).all())
    assert float(jnp.abs(grads[1]["router"]).max()) > 0


# tokens of 1024 that choose both held experts: twice as many rows drawn,
# of a buffer of 1024 (two tiles, and two passes of one tile here)
DRAWS = {"none": 0, "under_a_tile": 50, "half": 250, "all": 512, "more": 700}


@pytest.mark.parametrize("draw", list(DRAWS))
@pytest.mark.parametrize("score,act", [
    ("softmax", "swiglu"),      # Mellum's
    ("sigmoid", "relu2"),       # Nemotron-H's
])
def test_the_trimmed_movement_is_the_plain_one(monkeypatch, score, act,
                                               draw):
    """Dispatch and combine that stop where the rows stop (the tier a
    TPU takes for a buffer of whole tiles) give the plain tier's layer:
    the value and the gradients of the tokens, the router and every
    expert bank, at draws from none of the buffer to more than it holds,
    with NaN wherever a product leaves the buffer's tail."""
    from ray_tpu.ops import attention, grouped

    tokens, chosen = 1024, DRAWS[draw]
    st = dataclasses.replace(
        model_config().stack, router_score=score, expert_act=act,
        experts_held=(2, 2))
    assert st.row_buffer(tokens) == 1024 == 2 * grouped.TILE_M
    cfg = tfm.ModelConfig(vocab_size=64, hidden=64, layers=2, heads=4,
                          kv_heads=2, max_seq=SEQ, dtype=jnp.float32,
                          stack=dataclasses.replace(st, pattern="*E"))
    w = one_layer(tfm.init_params(cfg, jax.random.PRNGKey(2)), "moe")
    # feature 0 marks a token that chooses the two held experts: their
    # logit is 1.6 with it and -3.4 without, the others' about nought
    marked = (jnp.arange(tokens) * 7919 % tokens) < chosen
    x = 0.1 + 0.1 * jnp.abs(jax.random.normal(jax.random.PRNGKey(5),
                                              (tokens, 64)))
    x = x.at[:, 0].set(marked.astype(jnp.float32))
    router = 0.01 * jax.random.normal(jax.random.PRNGKey(6), (64, 8))
    router = router.at[:, 2:4].set(-0.3).at[0, 2:4].set(
        jnp.array([5.0, 5.2]))
    w = dict(w, router=router)
    dout = jax.random.normal(jax.random.PRNGKey(7), (tokens, 64))
    monkeypatch.setattr(tfm, "grouped_matmul", poisoned_tail)
    monkeypatch.setattr(grouped, "MOVE_ROWS", grouped.TILE_M)

    def layer(on):
        monkeypatch.setattr(attention, "_FORCE_INTERPRET", on)
        assert bool(grouped.movement_block(st.row_buffer(tokens),
                                           tokens)) == on

        def loss(x, w):
            out, drawn = tfm.routed_experts(x, w, st)
            return jnp.sum(out * dout), (out, drawn)

        text = str(jax.make_jaxpr(jax.grad(lambda x, w: loss(x, w)[0]))(
            x, w))
        assert ("while" in text) == ("rows_added" in text) == on
        (_, (out, drawn)), grads = jax.value_and_grad(
            loss, (0, 1), has_aux=True)(x, w)
        return out, grads, tfm.routing_report(drawn[None], st, tokens)

    want, want_grads, plain = layer(False)
    got, got_grads, trimmed = layer(True)
    assert int(plain["moe_rows_held"]) == 2 * chosen
    assert int(plain["moe_rows_over"]) == int(
        trimmed["moe_rows_over"]) == max(2 * chosen - 1024, 0)
    assert int(plain["moe_rows_moved"]) == 1024
    assert int(trimmed["moe_rows_moved"]) == -(-min(
        2 * chosen, 1024) // 512) * 512
    assert (float(jnp.abs(want).max()) > 1e-3) == bool(chosen)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-6, rtol=1e-5)
    banks = [k for k in ("w_gate", "w_up", "w_down") if k in w]
    assert len(banks) == (3 if act == "swiglu" else 2)
    for name, a, b in [("x", got_grads[0], want_grads[0])] + [
            (k, got_grads[1][k], want_grads[1][k])
            for k in ["router"] + banks]:
        assert bool(jnp.isfinite(a).all()), name
        assert (float(jnp.abs(b).max()) > 0) == bool(chosen), name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6,
                                   rtol=1e-4, err_msg=name)


# ------------------------------------------------------------ the model
def _program_numbers(cfg, hp, seed, batches):
    mesh = build_mesh(MeshSpec(), jax.devices()[:1])
    optimizer = make_optimizer(carry=True, **{
        k: hp[k] for k in ("learning_rate", "weight_decay", "b1", "b2",
                           "grad_clip", "warmup_steps")})
    step, _ = build_train_step(cfg, mesh, optimizer=optimizer)
    params = seeded(seed=seed)
    opt_state = optimizer.init(params)
    params, opt_state, m1 = step(params, opt_state, batches[0])
    first = driver.tree_norms(driver.adam_state(opt_state).mu)
    params, opt_state, m2 = step(params, opt_state, batches[1])
    unclip = max(1.0, float(m1["grad_norm"])) / (1 - hp["b1"])
    return {
        "loss": [float(m1["loss"]), float(m2["loss"])],
        "first_grad": {k: np.asarray(v) * unclip for k, v in first.items()},
        "change": {k: np.asarray(v) for k, v in driver.tree_norms(
            jax.tree.map(jnp.subtract, carried_params(params, opt_state),
                         seeded(seed=seed))).items()}}, m2


def _reference_numbers(seed, batches, operand="float32", fault=None):
    kinds = weights_mellum.kinds_of(TINY)
    key = weights_mellum.seed_key(seed)
    return reference.follow_two_steps(
        TINY, HP, lambda name, layer: weights_mellum.make_leaf(
            TINY, key, None if layer is None else kinds[layer], name,
            layer).astype(jnp.float32), batches,
        reference.OPERANDS[operand], fault)


def test_the_step_follows_the_reference_for_two_steps():
    """Loss, every leaf's first gradient and the two-step change of the
    whole model, through ``build_train_step``, against the plain
    reference; a planted fault in the reference's place does not pass."""
    batches = [weights_mellum.token_batch(3, i, 2, SEQ, 256) for i in (0, 1)]
    program, m2 = _program_numbers(model_config(), HP, 3, batches)
    ref = _reference_numbers(3, batches)
    numbers = compare.training_numbers(program, ref)
    assert numbers["loss_gap"] < 1e-5, numbers
    assert numbers["first_grad_gap"] < 1e-3, numbers
    assert numbers["grad_share_gap"] < 1e-3, numbers
    assert numbers["change_gap"] < 1e-2, numbers
    assert set(compare.flat(program["first_grad"])) == set(
        compare.flat(ref["first_grad"]))
    assert len(compare.flat(ref["first_grad"])) == 3 + 6 * (5 + 5)
    assert publish_moe_rows(m2)["moe_rows_over"] == 0
    assert "router_bias_step" not in m2
    for fault in reference.FAULTS:
        broken = compare.training_numbers(
            _reference_numbers(3, batches, fault=fault), ref)
        assert max(broken["first_grad_gap"], broken["change_gap"]) > 5e-2, (
            fault, broken)


def test_the_kernels_tier_runs_the_same_model(monkeypatch):
    """The windowed layers through the interpreted Pallas kernels (a
    sequence of 256 at d 128 tiles): the same loss as the blockwise tier,
    with the band's kernels in the program and the causal ones beside
    them."""
    from ray_tpu.ops import attention as A

    cfg = dict(TINY, head_dim=128, num_attention_heads=2,
               num_key_value_heads=1, sliding_window=100,
               layer_types=["sliding_attention", "full_attention"],
               mlp_layer_types=["sparse"] * 2, num_hidden_layers=2)
    mcfg = driver.model_config(cfg, 256)
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          weights_mellum.make_stacked(
                              cfg, weights_mellum.seed_key(5)))
    tokens = weights_mellum.token_batch(5, 0, 1, 256, 256)
    loss = lambda p: tfm.loss_fn(p, tokens, mcfg)  # noqa: E731
    want, want_grads = jax.value_and_grad(loss)(params)
    monkeypatch.setattr(A, "_FORCE_INTERPRET", True)
    text = str(jax.make_jaxpr(jax.grad(loss))(params))
    for name in ("swa_fwd", "swa_bwd_dq", "swa_bwd_dkdv", "flash_fwd",
                 "flash_bwd_dq", "flash_bwd_dkdv"):
        assert name in text
    got, got_grads = jax.value_and_grad(loss)(params)
    assert float(abs(got - want)) < 1e-5
    for a, b in zip(jax.tree.leaves(got_grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5,
                                   rtol=2e-3)


# ------------------------------------------------------------ the counts
def test_mellum_counts_by_hand():
    attention = 2 * 2304 * 4096 + 2 * 2304 * 512
    assert attention == 21_233_664
    assert flops_mellum.layer_matmul_params(MELLUM) == {
        "attention": attention, "router": 147_456,
        "experts": 2 * 6_193_152}
    multiplied = 8 * (attention + 147_456 + 2 * 6_193_152) + 2304 * 24576
    assert flops_mellum.mellum_matmul_params(MELLUM) == multiplied
    assert round(multiplied / 1e6, 1) == 326.8
    assert flops_mellum.mellum_params(MELLUM) == 8 * (
        attention + 147_456 + 16 * 6_193_152 + 4_608) \
        + 2 * 56_623_104 + 2_304
    # the band: 1024 queries see the triangle, 7168 see 1024 keys each
    pairs = 1024 * 1025 // 2 + 7168 * 1024
    assert flops_mellum.band_pairs(8192, 1024) == pairs == 7_864_832
    assert flops_mellum.band_pairs(512, 1024) == 512 * 513 // 2
    per_token = flops_mellum.attention_flops_per_token(MELLUM, 8192)
    assert per_token["full"] == 6 * 2 * 4096 * 8193 / 2
    assert per_token["sliding"] == 6 * 2 * 4096 * pairs / 8192
    assert round(pairs / 8192, 1) == 960.1
    assert flops_mellum.mellum_train_flops_per_token(MELLUM, 8192) == \
        6 * multiplied + 2 * per_token["full"] + 6 * per_token["sliding"]
    # a windowed call against the causal one: the same arrays, the
    # band's share of the pairs
    from benchmark import flops

    for swa, causal in flops_mellum.SWA_KERNELS.items():
        ops, nbytes = flops_mellum.swa_call_cost(swa, 4, 8192, 32, 4, 128,
                                                 1024)
        full_ops, full_bytes = flops.flash_call_cost(causal, 4, 8192, 32, 4,
                                                     128)
        assert nbytes == full_bytes
        assert ops / full_ops == pytest.approx(pairs / (8192 * 8193 / 2))
        wide = flops_mellum.swa_call_cost(swa, 4, 8192, 32, 4, 128, 8192)
        assert wide == (full_ops, full_bytes)
    # three grouped products forward and six backward over 65 536 rows
    ops, nbytes = flops_mellum.glu_grouped_mlp_cost(65536, 2304, 896, 16)
    assert ops == 18 * 65536 * 2304 * 896
    assert nbytes == 3 * 3 * 16 * 2304 * 896 * 2 \
        + 3 * 65536 * (2 * 2304 + 3 * 896) * 2
