"""The Nemotron-3 stack (Mamba-2 at 16 heads a group, attention without
rotary embedding, sigmoid-routed relu2 experts that read and write a
latent between two linear maps under a router on the hidden state, a
share of them held, a prediction module whose block is two layers of the
model's own kinds) at tiny widths on the CPU, each piece against the
plain reference ``benchmark/references/nemotron3_decoder.py``, a stated
identity or what the parent commit computed."""

import dataclasses
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import compare, flops_nemotron3, weights_nemotron3 as weights
from benchmark.drivers import (
    _expert_train_steps as body,
    nemotron3_train_steps as driver,
)
from benchmark.references import nemotron3_decoder as reference
from ray_tpu.models import transformer as tfm
from ray_tpu.models.training import (
    build_pipeline_train_step,
    build_train_step,
    carried_params,
    make_optimizer,
    publish_loss_parts,
    publish_moe_rows,
)
from ray_tpu.observability import device_programs as dp
from ray_tpu.observability.metrics import moe_latent_proj_calls
from ray_tpu.ops import attention, ssd
from ray_tpu.parallel.mesh import MeshSpec, build_mesh
from tests import test_hybrid_model as hybrid_tests

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(
        ROOT, "benchmark/configs/nemotron3_super_l9_ep64.json")) as f:
    SUPER = json.load(f)
# the cell's pattern; hidden 64; 16 Mamba heads of 4 in one group (16 a
# group, as the cell), state 16, chunk 8; 4 / 2 attention heads of 16; 16
# experts top-4 of width 24 in a latent of 16 with experts 2-4 held, a
# shared expert of 96; the module *E
TINY = dict(
    SUPER, hidden_size=64, mamba_num_heads=16, mamba_head_dim=4, n_groups=1,
    ssm_state_size=16, chunk_size=8, router_width=16, n_routed_experts=3,
    experts_held_first=2, num_experts_per_tok=4, moe_intermediate_size=24,
    moe_latent_size=16, moe_shared_expert_intermediate_size=96,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16, vocab_size=256,
    torch_dtype="float32", run=dict(SUPER["run"], logits_chunk=16))
HP = dict(SUPER["run"]["optimizer"], warmup_steps=8)
SEQ = 32
RULE = reference.OPERANDS["float32"]


@pytest.fixture(autouse=True)
def exact_products():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setattr(attention, "_FORCE_INTERPRET", True)


def model_config(cfg=TINY, seq=SEQ, **stack):
    built = driver.model_config(cfg, seq)
    return dataclasses.replace(
        built, stack=dataclasses.replace(built.stack, **stack))


def seeded(cfg=TINY, seed=1):
    return jax.tree.map(
        lambda a: a.astype(jnp.float32),
        weights.make_stacked(cfg, weights.seed_key(seed)))


def one_layer(kinds, kind, index=0):
    return jax.tree.map(lambda a: a[index], kinds[kind])


def reference_weights(cfg=TINY, seed=1):
    """The same seeded leaves in the reference's layout: the layers as a
    list, the model's and the module's own leaves beside them."""
    key = weights.seed_key(seed)
    out = {name: weights.make_leaf(cfg, key, None, name)
           for name in weights.TOP_LEAVES}
    out.update({name: weights.make_leaf(cfg, key, "mtp", name)
                for name in weights.MTP_LEAVES})
    out["layers"] = [
        {name: weights.make_leaf(cfg, key, kind, name, l)
         for name in weights.LEAVES[kind]}
        for l, (_, kind) in enumerate(weights.entries(cfg))]
    return jax.tree.map(lambda a: a.astype(jnp.float32), out)


def attend(q, k, v, window=None):
    return tfm.flash_attention(q, k, v, True, None, None, None, window)


# ------------------------------------------------------------ the stack
def test_the_configuration_describes_the_stack():
    """What the driver hands ``Stack`` from the published keys: the
    period, the module's block of the model's own kinds, every width as
    published, the latent, the cut as the file says."""
    cfg = driver.model_config(SUPER, 8192)
    st = cfg.stack
    assert (st.lead, st.pattern, st.mtp) == ("", "MEMEMEM*E", "*E")
    assert st.period == "MEMEMEM*E" and cfg.layers == 9
    assert (cfg.hidden, cfg.heads, cfg.kv_heads, cfg.head_dim) == (
        4096, 32, 2, 128)
    assert (st.ssm_heads, st.ssm_head_dim, st.ssm_groups, st.ssm_state,
            st.conv_kernel, st.chunk, st.ssm_inner, st.conv_width) == (
        128, 64, 8, 128, 4, 128, 8192, 10240)
    assert (st.routed_experts, st.experts_per_token, st.expert_width,
            st.expert_latent, st.shared_width, st.routed_scale, st.held) == (
        512, 22, 2688, 1024, 5376, 5, (0, 8))
    assert (st.router_score, st.expert_act, st.router_bias) == (
        "sigmoid", "relu2", True)
    assert (st.bias_rate, st.mtp_weight, st.rows_over_expected) == (
        0.02, 0.1, 3)
    assert not cfg.rotary and cfg.rope_of("*") is None
    # three times the even draw of 16384 x 22 x 8 / 512, whole tiles
    assert st.row_buffer(2 * 8192) == 3 * 5632 == 33 * 512
    assert cfg.norm_eps == 1e-5 and not cfg.tie_embeddings
    shapes = jax.eval_shape(lambda k: tfm.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    assert set(shapes) == {"embed", "final_norm", "unembed", "layers", "mtp"}
    assert set(shapes["layers"]) == {"mamba", "moe", "attention"}
    assert set(shapes["mtp"]["block"]) == {"moe", "attention"}
    moe = shapes["layers"]["moe"]
    assert (moe["latent_in"].shape, moe["latent_out"].shape,
            moe["w_up"].shape, moe["w_down"].shape, moe["router"].shape,
            moe["shared_up"].shape) == (
        (4, 4096, 1024), (4, 1024, 4096), (4, 8, 1024, 2688),
        (4, 8, 2688, 1024), (4, 4096, 512), (4, 4096, 5376))
    assert moe["latent_in"].dtype == jnp.bfloat16
    assert shapes["layers"]["mamba"]["w_in"].shape == (4, 4096, 18560)
    assert shapes["mtp"]["block"]["moe"]["w_up"].shape == (1, 8, 1024, 2688)
    count = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert count == flops_nemotron3.nemotron3_params(SUPER) == 1_170_513_920
    # every leaf has its logical axes, the latent maps the shared
    # expert's; and the seeded weights the program's shapes and types
    axes = tfm.logical_axes(cfg)
    flat = jax.tree.leaves_with_path(shapes)
    named = dict(jax.tree.leaves_with_path(
        axes, is_leaf=lambda a: isinstance(a, tuple)))
    assert {p: len(s.shape) for p, s in flat} == {
        p: len(a) for p, a in named.items()}
    assert axes["layers"]["moe"]["latent_in"] == axes["layers"]["moe"][
        "shared_up"] == ("layers", "hidden", "mlp")
    assert axes["layers"]["moe"]["latent_out"] == axes["layers"]["moe"][
        "shared_down"] == ("layers", "mlp", "hidden")
    made = jax.eval_shape(lambda k: weights.make_stacked(SUPER, k),
                          jax.random.PRNGKey(0))
    assert jax.tree.map(lambda s: (s.shape, s.dtype), made) == jax.tree.map(
        lambda s: (s.shape, s.dtype), shapes)
    # the published stack, whole: 88 layers whose pattern opens with the
    # cell's period
    whole = SUPER["published"]["hybrid_override_pattern"]
    assert (len(whole), whole.count("M"), whole.count("E"),
            whole.count("*")) == (88, 40, 40, 8)
    assert whole.startswith("MEMEMEM*E" * 3)
    assert "64 chips share each layer" in SUPER["deployment"]


REFUSED = {
    "latent experts over dp": (
        MeshSpec(dp=2), False,
        r"all-to-all of rows of the experts' latent \(16 wide"),
    "latent experts over fsdp": (
        MeshSpec(dp=2), True, "rows of the experts' latent"),
    "the stack over sp": (MeshSpec(sp=2), False, "over an sp axis"),
    "the module on the pipeline path": (
        MeshSpec(pp=2), False,
        "Its MTP module reads the last stage's output"),
}


@pytest.mark.parametrize("what", list(REFUSED))
def test_what_is_not_built_is_refused_with_a_sentence(what):
    spec, fsdp, sentence = REFUSED[what]
    cfg = model_config()
    mesh = build_mesh(spec, jax.devices()[:2])
    if spec.pp > 1:
        with pytest.raises(NotImplementedError, match=sentence):
            build_pipeline_train_step(cfg, mesh)
        return
    with pytest.raises(NotImplementedError, match=sentence):
        build_train_step(cfg, mesh, fsdp=fsdp)


def test_experts_on_the_hidden_state_are_refused_in_their_own_words():
    """A stack without a latent keeps the sentence it had."""
    cfg = model_config(expert_latent=0)
    with pytest.raises(NotImplementedError,
                       match="the all-to-all of tokens between"):
        build_train_step(cfg, build_mesh(MeshSpec(dp=2), jax.devices()[:2]))


@pytest.mark.parametrize("which, params_sha, loss_bits, held", [
    # read on the parent commit (PR 35) with ``_params_and_loss_bits``
    ("hybrid", "84859d8c51973feb", "b6cfc440", 178),
    ("glm", "32e5713080d555a8", "c539fe40", 144),
])
def test_an_accepted_stack_is_unchanged_to_the_bit(which, params_sha,
                                                   loss_bits, held):
    """A latent width of 0, every accepted configuration's case: the two
    accepted stacks that have expert layers draw the parameters they
    drew and read the loss they read on the parent commit, bit for bit,
    and have no latent leaf."""
    from tests import test_glm_model as glm_tests

    cfg = {"hybrid": hybrid_tests, "glm": glm_tests}[which].model_config()
    assert cfg.stack.expert_latent == 0
    with jax.default_matmul_precision("default"):
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        assert "latent_in" not in params["layers"]["moe"]
        digest = hashlib.sha256()
        for leaf in jax.tree.leaves(params):
            digest.update(np.asarray(leaf).tobytes())
        assert digest.hexdigest()[:16] == params_sha
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0,
                                    cfg.vocab_size)
        before = dict(moe_latent_proj_calls.series())
        loss, counted = jax.jit(
            lambda p, t: tfm.loss_and_rows(p, t, cfg))(params, tokens)
        assert np.asarray(loss).tobytes().hex() == loss_bits
        assert int(counted["moe_rows_held"]) == held
        assert moe_latent_proj_calls.series() == before


# ------------------------------------------------------------ the layers
def _program_layer(kind, cfg):
    if kind == "mamba":
        return lambda x, w: tfm.mamba_block(x, w, cfg)
    if kind == "attention":
        return lambda x, w: tfm.attention_block(x, w, cfg, None, None, attend)
    return lambda x, w: tfm.moe_block(x, w, cfg)[0]


@pytest.mark.parametrize("kind", ["mamba", "attention", "moe"])
def test_a_layer_and_its_gradients_are_the_reference_layers(kind):
    """One layer of each kind on the seeded weights against the
    reference's row function, the output and the gradient of every leaf
    and of the input: Mamba-2 at 16 heads a group, attention without
    rotary embedding, and the latent expert layer: the router on the
    hidden state with its bias, the map down, relu2 experts in the
    latent, the map back, the shared expert on the hidden state."""
    cfg, dims = model_config(), reference.Dims(TINY)
    assert dims.ssm_heads // dims.groups == 16
    w = one_layer(seeded()["layers"], kind, 1 if kind != "attention" else 0)
    if kind == "moe":
        w = dict(w, router_bias=jax.random.normal(
            jax.random.PRNGKey(3), (16,)) * 0.3)
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


@pytest.mark.parametrize("fault", [None, "state_reset"])
def test_the_mixer_a_group_at_a_time_is_the_accepted_mixer(fault):
    """The reference's ``mamba_row`` computes the accepted reference's
    mixer a group of heads at a time so that it fits the chip beside this
    model's weights: the same output and gradients, with and without the
    planted fault, at 4 heads in each of 2 groups."""
    from benchmark.references import nemotron_h_decoder as accepted

    cfg = dict(TINY, mamba_num_heads=8, mamba_head_dim=8, n_groups=2)
    dims = reference.Dims(cfg)
    w = one_layer(seeded(cfg, seed=6)["layers"], "mamba", 2)
    x = jax.random.normal(jax.random.PRNGKey(4), (SEQ, 64))
    cot = jax.random.normal(jax.random.PRNGKey(5), (SEQ, 64))

    def both(row):
        return jax.value_and_grad(lambda x, w: (
            row(x, w, dims, RULE, fault) * cot).sum(), (0, 1))(x, w)

    (got, got_g), (want, want_g) = both(reference.mamba_row), both(
        accepted.mamba_row)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for a, b in zip(jax.tree.leaves(got_g), jax.tree.leaves(want_g)):
        assert float(jnp.abs(a - b).max()) <= 1e-5 + 1e-4 * float(
            jnp.abs(b).max())
    moved = reference.mamba_row(x, w, dims, RULE, "state_reset") \
        - reference.mamba_row(x, w, dims, RULE)
    assert float(jnp.abs(moved).max()) > 1e-3


@pytest.mark.parametrize("fault", [f for f in reference.FAULTS
                                   if f != "mtp_ignored"])
def test_a_planted_fault_changes_its_layer(fault):
    """Each fault of a layer moves the layer it is planted in, and no
    other (``mtp_ignored`` is the loss's: see the model's tests)."""
    dims, params = reference.Dims(TINY), seeded()
    x = jax.random.normal(jax.random.PRNGKey(4), (SEQ, 64))
    hit = "mamba" if fault == "state_reset" else "moe"
    for kind in ("mamba", "attention", "moe"):
        w = one_layer(params["layers"], kind)
        row = reference.LAYER_ROW[kind]
        moved = float(jnp.abs(row(x, w, dims, RULE, fault)
                              - row(x, w, dims, RULE)).max())
        assert (moved > 1e-3) == (kind == hit), (kind, moved)


def test_the_four_shares_add_up_to_the_uncut_layer():
    """The tie of the share to the model (guide section 4): with 16
    experts in 4 shares of 4, the routed parts the four chips give, each
    taken back up through the whole ``W_fc2``, with the shared expert
    and the residual (which every chip computes alike) counted once, add
    up to what the uncut reference gives for the whole layer."""
    cfg = dict(TINY, router_width=16, n_routed_experts=16,
               experts_held_first=0, num_experts_per_tok=4)
    w = one_layer(seeded(cfg, seed=2)["layers"], "moe")
    w = dict(w, router_bias=jax.random.normal(
        jax.random.PRNGKey(3), (16,)) * 0.05)
    x = jax.random.normal(jax.random.PRNGKey(7), (2, SEQ, 64))
    whole = jnp.stack([reference.moe_row(r, w, reference.Dims(cfg), RULE)
                       for r in x])
    alike = jnp.stack([reference.moe_row(r, w, reference.Dims(cfg), RULE,
                                         "no_routed") for r in x])
    routed = 0
    for first in range(0, 16, 4):
        share = dict(cfg, n_routed_experts=4, experts_held_first=first)
        held = {k: w[k][first:first + 4] for k in ("w_up", "w_down")}
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


def test_identity_maps_give_the_experts_of_the_hidden_state():
    """A latent as wide as the hidden state between two identity maps is
    the expert layer the accepted stacks have: the same output, and two
    products counted where that one counts none."""
    wide = dict(TINY, moe_latent_size=64)
    w = one_layer(seeded(wide, seed=4)["layers"], "moe")
    eye = jnp.eye(64)
    x = jax.random.normal(jax.random.PRNGKey(8), (2, SEQ, 64))
    before = dict(moe_latent_proj_calls.series())
    got, drawn = tfm.moe_block(
        x, dict(w, latent_in=eye, latent_out=eye), model_config(wide))
    counted = {k: v - before.get(k, 0)
               for k, v in moe_latent_proj_calls.series().items()}
    assert counted == {("in",): 1, ("out",): 1}
    plain = {k: v for k, v in w.items() if not k.startswith("latent_")}
    want, same = tfm.moe_block(x, plain, model_config(wide, expert_latent=0))
    assert np.array_equal(np.asarray(drawn), np.asarray(same))
    assert float(jnp.abs(got - want).max()) < 1e-5
    assert float(jnp.abs(want - x).max()) > 1e-2


# ------------------------------------------------------------ the kernels
SIXTEEN = {
    # 32 heads of 4 in 2 groups, off the tiles (one piece of 64 lanes)
    "small": (dict(batch=1, seq=128, heads=32, p=4, groups=2, n=8), 64),
    # the cell's group: 16 heads of 64 in eight pieces of 128 lanes, state
    # and chunk 128, two chunks a grid step (at 512 positions ``a``'s
    # gradient, a sum over them all, reads 1.2e-4 of its largest, as it
    # does at 8 heads a group)
    "on_the_tiles": (dict(batch=1, seq=256, heads=16, p=64, groups=1, n=128),
                     128),
}


@pytest.mark.parametrize("shape", list(SIXTEEN))
def test_the_scan_kernels_at_sixteen_heads_a_group_are_the_jnp_scan(
        shape, interpreted):
    """Forward and every gradient of the two scan kernels under Pallas's
    interpreter at 16 heads a group, inside the tolerance the tests have
    for 8 a group; the small shape also against the sequential
    recurrence."""
    sizes, chunk = SIXTEEN[shape]
    assert sizes["heads"] // sizes["groups"] == 16
    args = hybrid_tests.ssd_inputs(**sizes)
    want, wanted = hybrid_tests.scan_and_grads(
        hybrid_tests.scan_of("jnp", chunk), args)
    got, grads = hybrid_tests.scan_and_grads(
        hybrid_tests.scan_of("kernel", chunk), args)
    for g, w in zip((got,) + grads, (want,) + wanted):
        assert g.shape == w.shape and g.dtype == w.dtype
        assert float(jnp.abs(g - w).max()) < 1e-4 * float(jnp.abs(w).max())
    if shape == "small":
        exact, exacts = hybrid_tests.scan_and_grads(
            hybrid_tests.sequential_ssd, args)
        for g, w in zip((want,) + wanted, (exact,) + exacts):
            assert float(jnp.abs(g - w).max()) < 1e-4 * float(
                jnp.abs(w).max())


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_conv_kernels_at_the_cells_proportions(dtype, interpreted):
    """The convolution pair where 16 heads a group put the cuts: x of 16
    heads of 64, then B and C of one group of 128, inside a projection
    with z before and dt behind (the cell's 8192 / 1024 / 1024 lanes from
    lane 8192, an eighth of it)."""
    channels, cuts, first, after = 1280, (1024, 1152), 1024, 16
    assert ssd._conv_blocks(192, channels, cuts, first) == (64, 128)
    args = hybrid_tests.conv_inputs(2, 192, channels, 4, dtype, first, after)
    want, wanted = hybrid_tests.conv_and_grads(False, cuts, first, *args)
    got, grads = hybrid_tests.conv_and_grads(True, cuts, first, *args)
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    for g, w in zip((got,) + grads, (want,) + wanted):
        assert g.shape == w.shape and g.dtype == w.dtype
        g, w = g.astype(jnp.float32), w.astype(jnp.float32)
        assert float(jnp.abs(g - w).max()) < tol * float(jnp.abs(w).max())


def test_the_rules_say_yes_to_the_cells_shapes(monkeypatch):
    """``scan_tier`` and ``conv_tier`` at 16 heads a group, by their own
    rules: pieces of two heads, two chunks a grid step, blocks of 1024
    lanes over the cuts at 8192 and 9216 from lane 8192."""
    monkeypatch.setattr(attention, "kernels_on", lambda: True)
    assert ssd._pieces(16, 64) == (128, 2)
    assert ssd._steps(64, 128, 16) == 2
    assert ssd.scan_tier(128, 128, 64, 8, 128)
    assert not ssd.scan_tier(128, 128, 64, 8, 128, sharded=True)
    assert ssd._conv_blocks(8192, 10240, (8192, 9216), 8192) == (1024, 1024)
    assert ssd.conv_tier(8192, 10240, 4, cuts=(8192, 9216), first=8192)
    # a group's three numbers a head and position within a chunk's square
    assert not ssd.scan_tier(128, 48 * 8, 64, 8, 128)


# ------------------------------------------------------------ the model
def _by_name(tree):
    """The program's tree, leaf by leaf, by the reference's names."""
    out = {k: v for k, v in tree.items() if k not in ("layers", "mtp")}
    out.update({f"mtp/{k}": v for k, v in tree["mtp"].items()
                if k != "block"})
    for where, kinds in (("layers", tree["layers"]),
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


@pytest.mark.parametrize("weight", [0.1, 0.0])
def test_the_loss_and_every_gradient_are_the_references(weight):
    """The period MEMEMEM*E, the final norm and the module *E in one
    stack: ``L_main + weight L_mtp``, both parts, and the gradient of
    every leaf against the reference's, the embedding and the head (one
    leaf each, read by both losses) and both latent maps of all five
    expert layers among them. With the weight at nought the loss is the
    main one alone and the module's own leaves get no gradient at all."""
    cfg = dict(TINY, run=dict(TINY["run"], mtp_weight=weight))
    mcfg = model_config(cfg)
    params = seeded()
    tokens = weights.token_batch(3, 0, 2, SEQ, 256)
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
    # the routing of the stack's four expert layers, then the module's
    assert sorted(drawn) == [1, 3, 5, 8, 10]
    bias_step = np.asarray(counted["router_bias_step"])
    even = 2 * SEQ * 4 / 16
    for row, entry in zip(bias_step, sorted(drawn)):
        np.testing.assert_allclose(
            row, 0.02 * (1.0 - np.asarray(drawn[entry]) / even), rtol=1e-6)
    got = _by_name(grads)
    ref = _reference_by_name(want_grads, model.dims)
    assert set(got) == set(ref)
    assert {"layers/moe/latent_in[3]", "mtp/block/moe/latent_out[0]",
            "mtp/block/attention/wq[0]", "mtp/eh_proj"} <= set(ref)
    for name in sorted(ref):
        a, b = np.asarray(got[name]), np.asarray(ref[name])
        scale = float(np.abs(b).max())
        assert float(np.abs(a - b).max()) <= 1e-6 + 3e-4 * scale, name
        own = name.startswith("mtp/")
        if "router_bias[" in name:
            assert scale == 0.0, name
        elif own and weight == 0.0:
            assert scale == 0.0 and float(np.abs(a).max()) == 0.0, name
        else:
            assert scale > 0.0, name


def _program_numbers(cfg, hp, seed, batches):
    mesh = build_mesh(MeshSpec(), jax.devices()[:1])
    optimizer = make_optimizer(carry=True, **{
        k: hp[k] for k in ("learning_rate", "weight_decay", "b1", "b2",
                           "grad_clip", "warmup_steps")})
    step, _ = build_train_step(cfg, mesh, optimizer=optimizer)
    params = seeded(seed=seed)
    opt_state = optimizer.init(params)
    dp.clear()
    compiled = step.lower(params, opt_state, batches[0]).compile()
    params, opt_state, m1 = compiled(params, opt_state, batches[0])
    first = body.tree_norms(body.adam_state(opt_state).mu)
    params, opt_state, m2 = compiled(params, opt_state, batches[1])
    unclip = max(1.0, float(m1["grad_norm"])) / (1 - hp["b1"])
    return {
        "loss": [float(m1["loss"]), float(m2["loss"])],
        "first_grad": {k: np.asarray(v) * unclip for k, v in first.items()},
        "change": {k: np.asarray(v) for k, v in body.tree_norms(
            jax.tree.map(jnp.subtract, carried_params(params, opt_state),
                         seeded(seed=seed))).items()}}, (m1, m2), params


def _reference_numbers(seed, batches, operand="float32", fault=None):
    kinds = [kind for _, kind in weights.entries(TINY)]
    key = weights.seed_key(seed)

    def initial_leaf(name, layer):
        kind = (kinds[layer] if layer is not None else
                "mtp" if name in weights.MTP_LEAVES else None)
        return weights.make_leaf(TINY, key, kind, name, layer).astype(
            jnp.float32)

    return reference.follow_two_steps(
        TINY, HP, initial_leaf, batches, reference.OPERANDS[operand], fault)


def test_the_step_follows_the_reference_for_two_steps():
    """Loss and its two parts, every leaf's first gradient and the
    two-step change of the whole model, through ``build_train_step``,
    against the plain reference, the correction bias of the stack's
    routers and of the module's moved by the same rule; a planted fault
    in the reference's place does not pass; and the compiled step's scope
    table has the latent maps beside the expert layer's other scopes, in
    the stack and in the module."""
    batches = [weights.token_batch(3, i, 2, SEQ, 256) for i in (0, 1)]
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
    # 3 + 4 of the top; four Mamba layers (9), one attention layer (5),
    # four expert layers (9); the module's block (5 + 9)
    assert len(compare.flat(ref["first_grad"])) == 7 + 36 + 5 + 36 + 14
    for m, parts in zip((m1, m2), ref["loss_parts"]):
        got = publish_loss_parts(m)
        assert got["main"] == pytest.approx(parts["loss_main"], rel=1e-5)
        assert got["mtp"] == pytest.approx(parts["loss_mtp"], rel=1e-5)
    assert publish_moe_rows(m2)["moe_rows_over"] == 0
    assert "router_bias_step" not in m2
    for name in ("layers/moe/router_bias", "mtp/block/moe/router_bias"):
        assert np.all(ref["change"][name] > 0)
        np.testing.assert_allclose(program["change"][name],
                                   ref["change"][name], rtol=1e-4)
    assert float(jnp.abs(
        params["mtp"]["block"]["moe"]["router_bias"]).max()) > 0
    for fault in reference.FAULTS:
        broken = compare.training_numbers(
            _reference_numbers(3, batches, fault=fault), ref)
        assert max(broken["first_grad_gap"], broken["change_gap"],
                   10 * broken["loss_gap"]) > 5e-2, (fault, broken)
    # the step's scopes, as the benchmark's readers will look for them
    paths = set(dp.scope_table_of("train_step").values())
    for scope in ("router", "latent_in", "dispatch", "experts", "combine",
                  "latent_out", "shared_expert"):
        assert any(f"/mlp/moe/{scope}/" in p and "layers" in p
                   for p in paths), scope
        assert any(f"/mlp/moe/{scope}/" in p and "mtp" in p
                   for p in paths), scope
    assert any("/mamba/ssd/" in p for p in paths)
    assert any("mtp" in p and "/attention/flash/" in p for p in paths)


def test_the_latent_maps_are_counted_when_traced():
    """``moe_latent_proj_calls{side}``: one a map each time Python traces
    the expert layer, as ``mamba_conv_calls`` counts the convolution: the
    stack's four expert layers share one trace of their kind's function
    under ``jax.checkpoint``, the module's block has its own; the
    gradient's trace counts its forward and recompute, and the two
    transposes' products are autodiff's, not calls."""
    mcfg = model_config()
    params = seeded()
    tokens = weights.token_batch(3, 0, 2, SEQ, 256)
    before = dict(moe_latent_proj_calls.series())
    jax.make_jaxpr(lambda p: tfm.loss_and_rows(p, tokens, mcfg)[0])(params)
    forward = {k: v - before.get(k, 0)
               for k, v in moe_latent_proj_calls.series().items()}
    assert forward == {("in",): 2, ("out",): 2}
    jax.make_jaxpr(jax.grad(
        lambda p: tfm.loss_and_rows(p, tokens, mcfg)[0]))(params)
    after = moe_latent_proj_calls.series()
    assert after[("in",)] == after[("out",)] >= before.get(("in",), 0) + 4
    # a stack whose experts read the hidden state counts none
    plain = model_config(expert_latent=0)
    jax.eval_shape(lambda k: tfm.loss_and_rows(
        tfm.init_params(plain, k), tokens, plain)[0], jax.random.PRNGKey(0))
    assert moe_latent_proj_calls.series() == after


def test_a_tensor_parallel_mesh_runs_the_latent_layer():
    """``tp`` 2: the latent maps shard like the shared expert (the latent
    over ``tp``), the banks stay whole, and the step's loss is the one
    chip's."""
    mcfg = model_config()
    tokens = weights.token_batch(3, 0, 2, SEQ, 256)
    losses = []
    for spec, n in ((MeshSpec(), 1), (MeshSpec(tp=2), 2)):
        mesh = build_mesh(spec, jax.devices()[:n])
        step, _ = build_train_step(mcfg, mesh, optimizer=make_optimizer())
        params = seeded()
        _, _, metrics = step(params, make_optimizer().init(params), tokens)
        losses.append(float(metrics["loss"]))
    assert losses[1] == pytest.approx(losses[0], rel=1e-5)


def test_the_program_s_leaves_are_found_as_the_reference_asks_for_them():
    """``_expert_train_steps.leaf_of``: the reference asks for a leaf by
    (name, entry, key), the program keeps each kind's leaves stacked over
    that kind's layers in each tree; every leaf of the seeded weights
    comes back as ``make_leaf`` draws it, times the scale."""
    key = weights.seed_key(5)
    leaf = driver.leaf_of(weights.make_stacked(TINY, key), config=TINY,
                          scale=2.0)
    dims, seen = reference.Dims(TINY), 0
    tree = {name: None for name in reference.TOP_LEAVES
            + reference.MTP_LEAVES}
    tree["layers"] = [dict.fromkeys(reference.LEAVES[kind])
                      for kind in dims.kinds]
    for name, entry, k, _ in reference.leaves(tree, dims):
        kind = (dims.kinds[entry] if entry is not None else
                "mtp" if k in weights.MTP_LEAVES else None)
        drawn = weights.make_leaf(TINY, key, kind, k, entry)
        np.testing.assert_array_equal(
            np.asarray(leaf(name, entry, k)),
            2.0 * np.asarray(drawn, np.float32), err_msg=name)
        seen += 1
    assert seen == 7 + 36 + 5 + 36 + 14


def test_the_difference_s_number_is_the_median_leaf_s():
    """``compare_difference``: a leaf's difference over the reference's
    norm of that leaf or of the median leaf; the number compared is the
    median over the leaves, so two leaves that a tie in the routing moved
    do not carry it and a rounding that moves every leaf does; it comes
    from whichever side was followed second."""
    from benchmark import compare_difference

    norms = {"a": np.array([4.0, 2.0]), "b": np.array([1.0]),
             "c": np.array([0.5, 0.1])}
    diff = {"a": np.array([0.4, 0.02]), "b": np.array([0.01]),
            "c": np.array([0.01, 0.5])}
    gaps = compare_difference.leaf_differences(diff, norms)
    assert gaps == pytest.approx({"a[0]": 0.1, "a[1]": 0.01, "b[0]": 0.01,
                                  "c[0]": 0.01, "c[1]": 0.5})
    side = {"loss": [1.0, 1.0], "first_grad": norms, "change": norms}
    for program, ref in ((dict(side, first_grad_diff=diff), side),
                         (side, dict(side, first_grad_diff=diff))):
        numbers = compare_difference.training_numbers(program, ref)
        assert numbers["first_grad_diff"] == pytest.approx(0.01)
        assert numbers["first_grad_diff_worst"] == pytest.approx(0.5)
        assert numbers["first_grad_diff_leaf"] == "c[1]"
        assert numbers["first_grad_gap"] == 0.0
    everywhere = {k: 0.05 * v for k, v in norms.items()}
    assert compare_difference.training_numbers(
        dict(side, first_grad_diff=everywhere), side)[
        "first_grad_diff"] == pytest.approx(0.05)
