"""The GLM-4.7-Flash stack (latent attention, a leading dense layer,
sigmoid-routed SwiGLU experts with a shared expert, a share of them held,
one multi-token-prediction module) at tiny widths on the CPU, each piece
against the plain reference ``benchmark/references/glm_decoder.py`` or a
stated identity."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import compare, flops_glm, weights_glm
from benchmark.drivers import glm_train_steps as driver
from benchmark.references import glm_decoder as reference
from ray_tpu.models import transformer as tfm
from ray_tpu.models.training import (
    build_pipeline_train_step,
    build_train_step,
    carried_params,
    make_optimizer,
    publish_loss_parts,
    publish_moe_rows,
)
from ray_tpu.parallel.mesh import MeshSpec, build_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(
        ROOT, "benchmark/configs/glm47_flash_l7_ep8.json")) as f:
    GLM = json.load(f)
# hidden 64, 4 heads of 24 + 8 with values of 32, latents of 24 and 16, a
# dense width of 96, 8 experts top-2 of width 24 with experts 2-4 held;
# layer 0 dense, two expert layers, the module
TINY = dict(
    GLM, hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
    q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=24, qk_rope_head_dim=8,
    v_head_dim=32, intermediate_size=96, moe_intermediate_size=24,
    router_width=8, n_routed_experts=3, experts_held_first=2,
    num_experts_per_tok=2, vocab_size=256, num_hidden_layers=3,
    torch_dtype="float32", run=dict(GLM["run"], logits_chunk=16))
HP = dict(GLM["run"]["optimizer"], warmup_steps=8)
SEQ = 32
RULE = reference.OPERANDS["float32"]


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
        weights_glm.make_stacked(cfg, weights_glm.seed_key(seed)))


def one_layer(kinds, kind, index=0):
    return jax.tree.map(lambda a: a[index], kinds[kind])


def reference_weights(cfg=TINY, seed=1):
    """The same seeded leaves in the reference's layout: the entries as a
    list, the model's and the module's own leaves beside them."""
    key = weights_glm.seed_key(seed)
    out = {name: weights_glm.make_leaf(cfg, key, None, name)
           for name in weights_glm.TOP_LEAVES}
    out.update({name: weights_glm.make_leaf(cfg, key, "mtp", name)
                for name in weights_glm.MTP_LEAVES})
    out["layers"] = [
        {name: weights_glm.make_leaf(cfg, key, kind, name, l)
         for name in weights_glm.LEAVES[kind]}
        for l, (_, kind) in enumerate(weights_glm.entries(cfg))]
    return jax.tree.map(lambda a: a.astype(jnp.float32), out)


def attend(q, k, v, window=None):
    return tfm.flash_attention(q, k, v, True, None, None, None, window)


# ------------------------------------------------------------ the stack
def test_the_configuration_describes_the_stack():
    """What the driver hands ``Stack`` from the published keys: a leading
    dense block, six periods of latent attention and experts, the
    module's block; every width as published, the cut as the file says."""
    cfg = driver.model_config(GLM, 8192)
    st = cfg.stack
    assert (st.lead, st.pattern, st.mtp) == ("LD", "LE" * 6, "LE")
    assert st.period == "LE" and cfg.layers == 14
    assert (cfg.hidden, cfg.heads, cfg.head_dim, cfg.intermediate) == (
        2048, 20, 256, 10240)
    assert (st.q_rank, st.kv_rank, st.rope_dim, st.v_head_dim) == (
        768, 512, 64, 256)
    assert (st.routed_experts, st.experts_per_token, st.expert_width,
            st.shared_width, st.routed_scale, st.held) == (
        64, 4, 1536, 1536, 1.8, (0, 8))
    assert (st.router_score, st.expert_act, st.router_bias) == (
        "sigmoid", "swiglu", True)
    assert (st.bias_rate, st.mtp_weight, st.rows_over_expected) == (
        0.02, 0.3, 3)
    assert st.rope == tfm.Rope(theta=1000000) and cfg.rope_of("L") is st.rope
    assert cfg.rotary and st.row_buffer(2 * 8192) == 3 * 8192
    assert cfg.norm_eps == 1e-5 and not cfg.tie_embeddings
    shapes = jax.eval_shape(lambda k: tfm.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    assert set(shapes) == {"embed", "final_norm", "unembed", "lead",
                           "layers", "mtp"}
    assert set(shapes["lead"]) == {"latent", "dense"}
    assert set(shapes["layers"]) == set(shapes["mtp"]["block"]) == {
        "latent", "moe"}
    assert shapes["layers"]["latent"]["w_ukv"].shape == (6, 512, 20 * 448)
    assert shapes["mtp"]["eh_proj"].shape == (4096, 2048)
    count = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert count == flops_glm.glm_params(GLM) == 920_177_088
    # every leaf has its logical axes, and the seeded weights its shape
    axes = tfm.logical_axes(cfg)
    flat = jax.tree.leaves_with_path(shapes)
    named = dict(jax.tree.leaves_with_path(
        axes, is_leaf=lambda a: isinstance(a, tuple)))
    assert {p: len(s.shape) for p, s in flat} == {
        p: len(a) for p, a in named.items()}
    made = jax.eval_shape(lambda k: weights_glm.make_stacked(GLM, k),
                          jax.random.PRNGKey(0))
    assert jax.tree.map(lambda s: (s.shape, s.dtype), made) == jax.tree.map(
        lambda s: (s.shape, s.dtype), shapes)
    # the published stack, whole: layer 0 and 46 periods
    whole = tfm.Stack(pattern="LE" * 46, lead="LD", head_dim=256,
                      q_rank=768, kv_rank=512, rope_dim=64, rope=st.rope)
    assert whole.period == "LE" and GLM["published"] == {
        "num_hidden_layers": 47, "n_routed_experts": 64,
        "vocab_size": 154880}
    assert "eight chips share each layer" in GLM["deployment"]


REFUSED = {
    "latent width": (dict(v_head_dim=16), MeshSpec(), "value heads of 16"),
    "latent over sp": ({}, MeshSpec(sp=2), "latent attention over an sp"),
    "experts over dp": ({}, MeshSpec(dp=2), "expert layers hold"),
    "the module on the pipeline path": (
        {}, MeshSpec(pp=2), "Its MTP module reads the last stage's output"),
}


@pytest.mark.parametrize("what", list(REFUSED))
def test_what_is_not_built_is_refused_with_a_sentence(what):
    stack, spec, sentence = REFUSED[what]
    cfg = model_config(**stack)
    mesh = build_mesh(spec, jax.devices()[:2 if spec != MeshSpec() else 1])
    build = (build_pipeline_train_step if spec.pp > 1 else build_train_step)
    with pytest.raises(NotImplementedError, match=sentence):
        build(cfg, mesh)
    if spec.pp > 1:
        with pytest.raises(NotImplementedError, match="uniform dense stack"):
            build_train_step(cfg, mesh)


def test_what_a_stack_cannot_describe_is_refused():
    with pytest.raises(ValueError, match="q_rank"):
        tfm.Stack(pattern="LE", head_dim=32, routed_experts=4)
    with pytest.raises(ValueError, match="rope_dim"):
        tfm.Stack(pattern="L", head_dim=32, q_rank=8, kv_rank=8, rope_dim=48,
                  rope=tfm.Rope())
    with pytest.raises(ValueError, match="a rope"):
        tfm.Stack(pattern="L", head_dim=32, q_rank=8, kv_rank=8, rope_dim=8)
    with pytest.raises(ValueError, match="belong to a stack by pattern"):
        tfm.Stack(lead="LD", head_dim=32, q_rank=8, kv_rank=8, rope_dim=8,
                  rope=tfm.Rope())
    # the module's block is no layer of the stack: lead and pattern are
    with pytest.raises(ValueError, match="layers=6"):
        tfm.ModelConfig(layers=6, stack=tfm.Stack(
            pattern="LE", lead="LD", mtp="LE", head_dim=32, q_rank=8,
            kv_rank=8, rope_dim=8, rope=tfm.Rope(), routed_experts=4))


# ------------------------------------------------------------ the layers
def _program_layer(kind, cfg):
    if kind == "latent":
        cos, sin = cfg.rope_of("L").table(cfg.stack.rope_dim, SEQ)
        return lambda x, w: tfm.latent_attention_block(
            x, w, cfg, cos, sin, attend)
    if kind == "dense":
        return lambda x, w: tfm.mlp_block(x, w, cfg)
    return lambda x, w: tfm.moe_block(x, w, cfg)[0]


@pytest.mark.parametrize("kind", ["latent", "dense", "moe"])
def test_a_layer_and_its_gradients_are_the_reference_layers(kind):
    """One layer of each kind on the seeded weights against the
    reference's row function, the output and the gradient of every leaf
    and of the input: the low-rank paths with their norms and the head
    that is part rotary, the dense SwiGLU, the sigmoid router with its
    bias, SwiGLU experts and the SwiGLU shared expert together."""
    cfg, dims = model_config(), reference.Dims(TINY)
    tree = seeded()["layers" if kind != "dense" else "lead"]
    w = one_layer(tree, kind, 1 if kind != "dense" else 0)
    if kind == "moe":
        w = dict(w, router_bias=jax.random.normal(
            jax.random.PRNGKey(3), (8,)) * 0.3)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, SEQ, 64))
    cot = jax.random.normal(jax.random.PRNGKey(5), (2, SEQ, 64))
    layer = _program_layer(kind, cfg)
    row = reference.LAYER_ROW[kind]

    def want_fn(x, w):
        return jnp.stack([row(r, w, dims, RULE) for r in x])

    got, want = layer(x, w), want_fn(x, w)
    assert float(jnp.abs(got - want).max()) < 2e-4
    assert float(jnp.abs(got - x).max()) > 1e-2
    got_g = jax.grad(lambda x, w: (layer(x, w) * cot).sum(), (0, 1))(x, w)
    want_g = jax.grad(lambda x, w: (want_fn(x, w) * cot).sum(), (0, 1))(x, w)
    names = ["x"] + sorted(w)
    for name, a, b in zip(names, jax.tree.leaves(got_g),
                          jax.tree.leaves(want_g)):
        scale = float(jnp.abs(b).max())
        assert float(jnp.abs(a - b).max()) <= 1e-4 + 2e-4 * scale, name
        if name != "router_bias":
            assert scale > 1e-4, name
    if kind == "moe":
        counted = sum(reference.drawn_row(r, w, dims) for r in x)
        assert np.array_equal(np.asarray(tfm.moe_block(x, w, cfg)[1]),
                              np.asarray(counted))
        return
    if kind != "latent":
        return
    # the shared rotated key's path by name: the last rope_dim columns of
    # W_dkv make the one key that every head reads, so their gradient is
    # a sum over the heads; with the other heads' cotangent taken away it
    # is one head's alone, and the four add up
    rank = cfg.stack.kv_rank
    shared = got_g[1]["w_dkv"][:, rank:]
    assert float(jnp.abs(shared).max()) > 1e-4
    hd = cfg.head_dim

    def one_heads(head):
        def loss(w):
            wo = jnp.zeros_like(w["wo"]).at[head * hd:(head + 1) * hd].set(
                w["wo"][head * hd:(head + 1) * hd])
            return ((layer(x, dict(w, wo=wo)) - x) * cot).sum()
        return jax.grad(loss)(w)["w_dkv"][:, rank:]

    by_head = sum(one_heads(head) for head in range(cfg.heads))
    assert float(jnp.abs(by_head - shared).max()) < 1e-4
    assert float(jnp.abs(one_heads(0) - shared).max()) > 1e-3


@pytest.mark.parametrize("fault", reference.FAULTS[1:])
def test_a_planted_fault_changes_its_layer(fault):
    """Each fault of a layer moves the layer it is planted in, and no
    other (``mtp_ignored`` is the loss's: see the model's tests)."""
    dims, params = reference.Dims(TINY), seeded()
    x = jax.random.normal(jax.random.PRNGKey(4), (SEQ, 64))
    hit = {"rope_over_whole_head": "latent", "latent_norms_ignored": "latent",
           "no_routed": "moe"}[fault]
    for kind in ("latent", "dense", "moe"):
        w = one_layer(params["lead" if kind == "dense" else "layers"], kind)
        row = reference.LAYER_ROW[kind]
        moved = float(jnp.abs(row(x, w, dims, RULE, fault)
                              - row(x, w, dims, RULE)).max())
        assert (moved > 1e-3) == (kind == hit), (kind, moved)


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The tie of the share to the model (guide section 4): the routed
    parts that the eight shares of 8 experts give, with the shared expert
    and the residual (which every chip computes alike) counted once, add
    up to what the uncut reference gives for the whole layer of 64."""
    cfg = dict(TINY, router_width=64, n_routed_experts=64,
               experts_held_first=0, num_experts_per_tok=4)
    w = one_layer(seeded(cfg, seed=2)["layers"], "moe")
    w = dict(w, router_bias=jax.random.normal(
        jax.random.PRNGKey(3), (64,)) * 0.05)
    x = jax.random.normal(jax.random.PRNGKey(7), (2, SEQ, 64))
    whole = jnp.stack([reference.moe_row(r, w, reference.Dims(cfg), RULE)
                       for r in x])
    alike = jnp.stack([reference.moe_row(r, w, reference.Dims(cfg), RULE,
                                         "no_routed") for r in x])
    routed = 0
    for first in range(0, 64, 8):
        share = dict(cfg, n_routed_experts=8, experts_held_first=first)
        held = {k: w[k][first:first + 8]
                for k in ("w_gate", "w_up", "w_down")}
        out, drawn = tfm.moe_block(x, dict(w, **held), model_config(share))
        routed = routed + (out - alike)
        assert int(drawn.sum()) == 2 * SEQ * 4
        report = tfm.routing_report(drawn[None], model_config(share).stack,
                                    2 * SEQ)
        assert int(report["moe_rows_over"]) == 0
        # and the reference's share is the program's
        want = reference.moe_row(x[0], dict(w, **held),
                                 reference.Dims(share), RULE)
        assert float(jnp.abs(out[0] - want).max()) < 2e-4
    assert float(jnp.abs(routed + alike - whole).max()) < 2e-4
    assert float(jnp.abs(whole - alike).max()) > 1e-2
    assert float(jnp.abs(alike - x).max()) > 1e-2


# ------------------------------------------------------------ the model
def _by_name(tree):
    """The program's tree, leaf by leaf, by the reference's names."""
    out = {k: v for k, v in tree.items()
           if k not in ("lead", "layers", "mtp")}
    out.update({f"mtp/{k}": v for k, v in tree["mtp"].items()
                if k != "block"})
    for where, kinds in (("lead", tree["lead"]), ("layers", tree["layers"]),
                         ("mtp/block", tree["mtp"]["block"])):
        for kind, leaves in kinds.items():
            for k, v in leaves.items():
                for i in range(v.shape[0]):
                    out[f"{where}/{kind}/{k}[{i}]"] = v[i]
    return out


def _reference_by_name(grads, dims):
    out, seen = {}, {}
    for name, layer, _, g in reference.leaves(grads, dims):
        if layer is None:
            out[name] = g
        else:
            i = seen[name] = seen.get(name, -1) + 1
            out[f"{name}[{i}]"] = g
    return out


@pytest.mark.parametrize("weight", [0.3, 0.0])
def test_the_loss_and_every_gradient_are_the_references(weight):
    """Layer 0, the periods, the final norm and the module in one stack:
    ``L_main + weight L_mtp`` and the gradient of every leaf against the
    reference's, the embedding and the head (one leaf each, read by both
    losses) among them. With the weight at nought the loss is the main
    one alone and the module's own leaves get no gradient at all."""
    cfg = dict(TINY, run=dict(TINY["run"], mtp_weight=weight))
    mcfg = model_config(cfg)
    params = seeded()
    tokens = weights_glm.token_batch(3, 0, 2, SEQ, 256)
    (loss, counted), grads = jax.value_and_grad(
        lambda p: tfm.loss_and_rows(p, tokens, mcfg), has_aux=True)(params)
    model = reference.Model(cfg)
    want, want_grads, parts, drawn = model.loss_and_grads(
        reference_weights(), tokens)
    assert float(loss) == pytest.approx(want, rel=2e-6)
    assert float(counted["loss_main"]) == pytest.approx(parts["loss_main"],
                                                        rel=2e-6)
    assert float(counted["loss_mtp"]) == pytest.approx(parts["loss_mtp"],
                                                       rel=2e-6)
    assert float(loss) == pytest.approx(
        float(counted["loss_main"]) + weight * float(counted["loss_mtp"]),
        rel=1e-6)
    # the routing of the stack's two expert layers, then the module's
    assert sorted(drawn) == [3, 5, 7]
    bias_step = np.asarray(counted["router_bias_step"])
    even = 2 * SEQ * 2 / 8
    for row, entry in zip(bias_step, (3, 5, 7)):
        np.testing.assert_allclose(
            row, 0.02 * (1.0 - np.asarray(drawn[entry]) / even), rtol=1e-6)
    got = _by_name(grads)
    ref = _reference_by_name(want_grads, model.dims)
    assert set(got) == set(ref)
    for name in sorted(ref):
        a, b = np.asarray(got[name]), np.asarray(ref[name])
        scale = float(np.abs(b).max())
        assert float(np.abs(a - b).max()) <= 1e-6 + 3e-4 * scale, name
        own = name.startswith("mtp/")
        if name.endswith("router_bias]") or "router_bias[" in name:
            assert scale == 0.0, name
        elif own and weight == 0.0:
            assert scale == 0.0 and float(np.abs(a).max()) == 0.0, name
        else:
            assert scale > 0.0, name
    # both losses reach the shared arrays: the main loss alone gives the
    # head and the embedding another gradient
    if weight:
        alone = jax.grad(lambda p: tfm.loss_and_rows(
            p, tokens, model_config(dict(TINY, run=dict(
                TINY["run"], mtp_weight=0.0))))[0])(params)
        for name in ("embed", "unembed", "final_norm"):
            assert float(jnp.abs(grads[name] - alone[name]).max()) > 1e-6


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
                         seeded(seed=seed))).items()}}, (m1, m2), params


def _reference_numbers(seed, batches, operand="float32", fault=None):
    kinds = [kind for _, kind in weights_glm.entries(TINY)]
    key = weights_glm.seed_key(seed)

    def initial_leaf(name, layer):
        kind = (kinds[layer] if layer is not None else
                "mtp" if name in weights_glm.MTP_LEAVES else None)
        return weights_glm.make_leaf(TINY, key, kind, name, layer).astype(
            jnp.float32)

    return reference.follow_two_steps(
        TINY, HP, initial_leaf, batches, reference.OPERANDS[operand], fault)


def test_the_step_follows_the_reference_for_two_steps():
    """Loss and its two parts, every leaf's first gradient and the
    two-step change of the whole model, through ``build_train_step``,
    against the plain reference, the correction bias of the stack's
    routers and of the module's moved by the same rule; a planted fault
    in the reference's place does not pass."""
    batches = [weights_glm.token_batch(3, i, 2, SEQ, 256) for i in (0, 1)]
    program, (m1, m2), params = _program_numbers(model_config(), HP, 3,
                                                 batches)
    ref = _reference_numbers(3, batches)
    numbers = compare.training_numbers(program, ref)
    assert numbers["loss_gap"] < 1e-5, numbers
    assert numbers["first_grad_gap"] < 1e-3, numbers
    assert numbers["grad_share_gap"] < 1e-3, numbers
    assert numbers["change_gap"] < 1e-2, numbers
    assert set(compare.flat(program["first_grad"])) == set(
        compare.flat(ref["first_grad"]))
    # 3 + 4 of the top, layer 0 (8 + 4), two expert layers and the
    # module's block (8 + 9 each)
    assert len(compare.flat(ref["first_grad"])) == 7 + 12 + 3 * 17
    for m, parts in zip((m1, m2), ref["loss_parts"]):
        got = publish_loss_parts(m)
        assert got["main"] == pytest.approx(parts["loss_main"], rel=1e-5)
        assert got["mtp"] == pytest.approx(parts["loss_mtp"], rel=1e-5)
    assert publish_moe_rows(m2)["moe_rows_over"] == 0
    assert "router_bias_step" not in m2
    # the bias moved, in the stack and in the module, and by the rule
    # alone: its change is what the reference's rule gives
    for name in ("layers/moe/router_bias", "mtp/block/moe/router_bias"):
        assert np.all(ref["change"][name] > 0)
        np.testing.assert_allclose(program["change"][name],
                                   ref["change"][name], rtol=1e-4)
    assert float(jnp.abs(
        params["mtp"]["block"]["moe"]["router_bias"]).max()) > 0
    for fault in reference.FAULTS:
        broken = compare.training_numbers(
            _reference_numbers(3, batches, fault=fault), ref)
        assert max(broken["first_grad_gap"], broken["change_gap"]) > 5e-2, (
            fault, broken)


def test_the_kernels_tier_runs_the_same_model(monkeypatch):
    """The latent blocks through the interpreted Pallas kernels at heads
    of 256 (a sequence of 256 tiles): the same loss and gradients as the
    blockwise tier, and the module's block calls them too."""
    from ray_tpu.observability.metrics import flash_fwd_subblocks
    from ray_tpu.ops import attention as A

    # two experts held: a row buffer under one tile of the grouped product,
    # which then stays with XLA (its kernels have no interpreter here)
    cfg = dict(TINY, qk_nope_head_dim=192, qk_rope_head_dim=64,
               v_head_dim=256, num_attention_heads=2, num_key_value_heads=2,
               num_hidden_layers=2, n_routed_experts=2,
               run=dict(TINY["run"], logits_chunk=64))
    mcfg = driver.model_config(cfg, 256)
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          weights_glm.make_stacked(
                              cfg, weights_glm.seed_key(5)))
    tokens = weights_glm.token_batch(5, 0, 1, 256, 256)
    loss = lambda p: tfm.loss_and_rows(p, tokens, mcfg)[0]  # noqa: E731
    want, want_grads = jax.value_and_grad(loss)(params)
    monkeypatch.setattr(A, "_FORCE_INTERPRET", True)
    assert A.kernel_tiers(256, 256, 256) == (True, True)

    def traced(cfg):
        """(the program, the forward sub-blocks its trace counted)."""
        before = sum(flash_fwd_subblocks.series().values())
        text = str(jax.make_jaxpr(jax.grad(
            lambda p: tfm.loss_and_rows(p, tokens, cfg)[0]))(params))
        return text, sum(flash_fwd_subblocks.series().values()) - before

    text, with_module = traced(mcfg)
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkdv"):
        assert name in text
    # the module's block is counted with the stack's two
    _, alone = traced(dataclasses.replace(
        mcfg, stack=dataclasses.replace(mcfg.stack, mtp="")))
    assert with_module > alone > 0
    got, got_grads = jax.value_and_grad(loss)(params)
    assert float(abs(got - want)) < 1e-5
    for a, b in zip(jax.tree.leaves(got_grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5,
                                   rtol=2e-3)


# ------------------------------------------------------------ the counts
def test_glm_counts_by_hand():
    attention = (2048 * 768 + 768 * 5120 + 2048 * 576 + 512 * 8960
                 + 5120 * 2048)
    assert flops_glm.attention_matmul_params(GLM) == attention == 21_757_952
    assert attention + 2048 + 768 + 512 == 21_761_280
    # the router, the shared expert, half a routed expert a token
    sparse = 2048 * 64 + 1.5 * 3 * 2048 * 1536
    assert flops_glm.expert_layer_matmul_params(GLM) == sparse
    assert flops_glm.glm_params(GLM) == (
        84_677_888 + 6 * 106_829_120 + 79_300_608 + 115_223_872)
    head = 2048 * 19360
    stack = 7 * attention + 3 * 2048 * 10240 + 6 * sparse + head
    module = 4096 * 2048 + attention + sparse + head
    pairs = 6 * 2 * 5120 * 8193 / 2
    ahead = 6 * 2 * 5120 * (8191 * 8192 / 2) / 8192
    per_token = flops_glm.glm_train_flops_per_token(GLM, 8192)
    assert per_token == pytest.approx(
        6 * stack + 7 * pairs + 6 * module * 8191 / 8192 + ahead, rel=1e-12)
    assert round(per_token / 1e9, 2) == 4.56
    # the reduced keys are exactly those that differ from the published
    assert set(GLM["reduced"]) == set(GLM["published"]) == {
        k for k, v in GLM["published"].items() if GLM[k] != v}
