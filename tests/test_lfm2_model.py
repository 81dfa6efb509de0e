"""The LFM2 stack with sparse experts (gated short-convolution layers
three to one with QK-normed GQA layers, a leading dense SwiGLU layer,
sigmoid-routed SwiGLU experts without a shared expert, a share of them
held, the head tied to the embedding) at tiny widths on the CPU: the
gated short convolution's kernel pair against its ``jnp`` tier, each
layer against the plain reference
``benchmark/references/lfm2_decoder.py``, and the whole step, its every
gradient and its first two steps (``tests/whole_model.py``)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import whole_model

from benchmark import compare, compare_difference, weights_lfm2 as weights
from benchmark.drivers import lfm2_train_steps as driver
from benchmark.references import lfm2_decoder as reference
from ray_tpu.models import transformer as tfm
from ray_tpu.models.training import (
    build_pipeline_train_step,
    build_train_step,
    publish_moe_rows,
)
from ray_tpu.observability import device_programs as dp
from ray_tpu.observability.metrics import short_conv_calls
from ray_tpu.ops import attention, short_conv
from ray_tpu.parallel.mesh import MeshSpec, build_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(
        ROOT, "benchmark/configs/lfm2_24b_a2b_l9_ep8.json")) as f:
    LFM2 = json.load(f)
# the cell's layers 1-9 (the lead C D, two periods * E C E C E C E);
# hidden 64: 4 / 2 attention heads of 16, three taps; a dense layer of
# 128; 16 experts top-4 of width 24 with experts 2-4 held
TINY = dict(
    LFM2, hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
    intermediate_size=128, router_width=16, num_experts=3,
    experts_held_first=2, moe_intermediate_size=24,
    vocab_size=256, torch_dtype="float32",
    run=dict(LFM2["run"], logits_chunk=16))
SEQ = 32
HP = dict(LFM2["run"]["optimizer"], warmup_steps=8)
RULE = reference.OPERANDS["float32"]


@pytest.fixture(autouse=True)
def exact_products():
    with jax.default_matmul_precision("highest"):
        yield


def model_config(cfg=TINY, seq=SEQ):
    return driver.model_config(cfg, seq)


def seeded(cfg=TINY, seed=1):
    return whole_model.seeded(weights, cfg, seed)


def one_layer(kinds, kind, index=0):
    return jax.tree.map(lambda a: a[index], kinds[kind])


def _counted(run):
    """(run's result, what ``short_conv_calls`` counted meanwhile)."""
    before = dict(short_conv_calls.series())
    out = run()
    return out, {k: v - before.get(k, 0)
                 for k, v in short_conv_calls.series().items()
                 if v != before.get(k, 0)}


# ------------------------------------------------- the gated convolution
@pytest.mark.parametrize("b, s, h", [
    (1, 1024, 128),     # two blocks of rows: the halo and the carried g
    (2, 128, 1024),     # two blocks of lanes a part, two batch rows
])
def test_the_kernel_pair_is_the_jnp_tier(b, s, h, monkeypatch):
    """``short_conv_fwd`` / ``short_conv_bwd`` under the interpreter
    against the ``jnp`` tier, both rounded once to bfloat16: y, d proj
    and the taps' gradient, each from the one custom VJP."""
    monkeypatch.setattr(attention, "_FORCE_INTERPRET", True)
    keys = jax.random.split(jax.random.PRNGKey(s + h), 3)
    proj = jax.random.normal(keys[0], (b, s, 3 * h)).astype(jnp.bfloat16)
    taps = jax.random.normal(keys[1], (3, h))
    dy = jax.random.normal(keys[2], (b, s, h)).astype(jnp.bfloat16)
    assert short_conv.short_conv_tier(s, h, 3)

    def pulled(kernel):
        y, vjp = jax.vjp(lambda p, w: short_conv._short_conv(p, w, kernel),
                         proj, taps)
        return (y, *vjp(dy))

    (got, counted) = _counted(lambda: pulled(True))
    assert counted == {("kernel", "fwd"): 1, ("kernel", "bwd"): 1}
    want = pulled(False)
    for name, a, w in zip(("y", "d proj", "d taps"), got, want):
        a, w = np.asarray(a, np.float32), np.asarray(w, np.float32)
        scale = float(np.abs(w).max())
        # y and d proj: one rounding to bfloat16 on each side, a unit of
        # the last place; the taps' float32 sums in another order
        bound = 1e-5 * scale if name == "d taps" else 2 ** -7 * scale
        assert float(np.abs(a - w).max()) <= bound, name
        assert scale > 1.0, name


def test_the_rule_says_yes_to_the_cells_shapes(monkeypatch):
    """``short_conv_tier``: the cell's 8192 rows of three parts of 2048
    lanes take the kernels where kernels run; a partitioned step, parts
    off the 128 lanes, rows off whole blocks and too many taps take the
    ``jnp`` tier."""
    assert not short_conv.short_conv_tier(8192, 2048, 3)      # the CPU
    monkeypatch.setattr(attention, "kernels_on", lambda: True)
    assert short_conv.short_conv_tier(8192, 2048, 3)
    assert not short_conv.short_conv_tier(8192, 2048, 3, sharded=True)
    assert not short_conv.short_conv_tier(8192, 2000, 3)
    assert not short_conv.short_conv_tier(8200, 2048, 3)
    assert not short_conv.short_conv_tier(8192, 2048, 12)


@pytest.mark.parametrize("kernel", [False, True])
def test_the_convolution_is_causal(kernel, monkeypatch):
    """Changing position t of B, C or x moves nothing before t, and moves
    positions t to t + 2 (three taps), in both tiers."""
    monkeypatch.setattr(attention, "_FORCE_INTERPRET", True)
    keys = jax.random.split(jax.random.PRNGKey(11), 2)
    proj = jax.random.normal(keys[0], (1, 1024, 384))
    taps = jax.random.normal(keys[1], (3, 128)) + 2.0
    t = 512         # the first row of the second block of rows

    def out(p):
        return np.asarray(short_conv._short_conv(p, taps, kernel))

    clean = out(proj)
    for part in range(3):
        moved = out(proj.at[0, t, part * 128:(part + 1) * 128].add(1.0))
        changed = np.abs(moved - clean).max(-1)[0]
        assert changed[:t].max() == 0.0, part
        assert changed[t] > 0.0, part
        assert (changed[t:t + 3] > 0.0).all() == (part != 1), part
        assert changed[t + 3:].max() == 0.0, part


# ------------------------------------------------------------ the stack
def test_the_configuration_describes_the_stack():
    """What the driver hands ``Stack`` from the published keys: the lead
    C D, two periods * E C E C E C E, every width as published, the cut
    as the file says, 832.65 M parameters."""
    from benchmark import flops_lfm2

    cfg = driver.model_config(LFM2, 8192)
    st = cfg.stack
    assert (st.lead, st.pattern, st.mtp, st.period) == (
        "CD", "*ECECECE" * 2, "", "*ECECECE")
    assert cfg.layers == 18
    assert (cfg.hidden, cfg.heads, cfg.kv_heads, cfg.head_dim,
            cfg.intermediate) == (2048, 32, 8, 64, 11776)
    assert st.qk_norm and st.short_conv_taps == 3 and cfg.rotary
    assert st.rope == tfm.Rope(1000000.0)
    assert (st.routed_experts, st.experts_per_token, st.expert_width,
            st.shared_width, st.routed_scale, st.held) == (
        64, 4, 1536, 0, 1, (0, 8))
    assert (st.router_score, st.expert_act, st.bias_rate,
            st.rows_over_expected) == ("sigmoid", "swiglu", 0.02, 3)
    # three times the even draw of 32768 x 4 x 8 / 64, whole tiles
    assert st.row_buffer(4 * 8192) == 49152
    assert cfg.norm_eps == 1e-5 and cfg.tie_embeddings
    shapes = jax.eval_shape(lambda k: tfm.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    assert set(shapes) == {"embed", "final_norm", "lead", "layers"}
    assert set(shapes["lead"]) == {"short_conv", "dense"}
    assert set(shapes["layers"]) == {"short_conv", "attention", "moe"}
    mixer = shapes["layers"]["short_conv"]
    assert {k: (v.shape, v.dtype) for k, v in mixer.items()} == {
        "norm": ((6, 2048), jnp.float32),
        "w_in": ((6, 2048, 6144), jnp.bfloat16),
        "conv_w": ((6, 3, 2048), jnp.float32),
        "w_out": ((6, 2048, 2048), jnp.bfloat16)}
    attn = shapes["layers"]["attention"]
    assert {k: v.shape for k, v in attn.items()} == {
        "attn_norm": (2, 2048), "wq": (2, 2048, 2048), "wk": (2, 2048, 512),
        "wv": (2, 2048, 512), "wo": (2, 2048, 2048), "q_norm": (2, 64),
        "k_norm": (2, 64)}
    assert attn["q_norm"].dtype == attn["k_norm"].dtype == jnp.float32
    held = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert held == flops_lfm2.lfm2_params(LFM2) == 832_652_032
    # the seeded weights are laid out as the program's parameters
    seeded_shapes = jax.eval_shape(
        lambda k: weights.make_stacked(LFM2, k), jax.random.PRNGKey(0))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), seeded_shapes) == \
        jax.tree.map(lambda a: (a.shape, a.dtype), shapes)


REFUSED = {
    "the convolution over sp": (
        MeshSpec(sp=2), r"gated short convolution \(C\) over an sp axis"),
    "the stack on the pipeline path": (
        MeshSpec(pp=2), "Its C layers' leaves are a kind's of their own"),
}


@pytest.mark.parametrize("what", list(REFUSED))
def test_what_is_not_built_is_refused_with_a_sentence(what):
    spec, sentence = REFUSED[what]
    mesh = build_mesh(spec, jax.devices()[:2])
    build = build_pipeline_train_step if spec.pp > 1 else build_train_step
    with pytest.raises(NotImplementedError, match=sentence):
        build(model_config(), mesh)
    with pytest.raises(ValueError, match="short_conv_taps"):
        tfm.Stack(pattern="CD")


# ------------------------------------------------------------ the layers
def attend(q, k, v, window=None):
    return tfm.flash_attention(q, k, v, True, None, None, None, window)


def _program_layer(kind, cfg):
    if kind == "short_conv":
        return lambda x, w: tfm.short_conv_block(x, w, cfg)
    if kind == "attention":
        cos, sin = cfg.stack.rope.table(cfg.head_dim, cfg.max_seq)
        return lambda x, w: tfm.attention_block(
            x, w, cfg, cos, sin, attend, qk_norm=True)
    if kind == "dense":
        return lambda x, w: tfm.mlp_block(x, w, cfg)
    return lambda x, w: tfm.moe_block(x, w, cfg)[0]


def _layers(cfg, seed=1):
    """One layer of each kind from the seeded weights, the expert layer's
    correction bias moved off nought as a trained router's stands (the
    seeded one is nought), so that it chooses."""
    params = seeded(cfg, seed)
    out = {kind: one_layer({**params["lead"], **params["layers"]}, kind)
           for kind in reference.LAYER_ROW}
    bias = out["moe"]["router_bias"]
    out["moe"]["router_bias"] = jax.random.normal(
        jax.random.PRNGKey(3), bias.shape) * 0.3
    return out


@pytest.mark.parametrize("kind, kernel", [
    ("short_conv", False), ("short_conv", True), ("attention", False),
    ("dense", False), ("moe", False)])
def test_a_layer_and_its_gradients_are_the_reference_layers(
        kind, kernel, monkeypatch):
    """One layer of each kind on the seeded weights against the
    reference's row function, the output and the gradient of every leaf
    and of the input: the gated short convolution (the kernel pair under
    the interpreter at a hidden width of 128, whose parts lie on the
    lanes), attention with q and k normed a head before the rotary
    embedding, the dense SwiGLU layer, the sigmoid-routed expert layer
    whose correction bias chooses and never weighs."""
    cfg_dict, seq = (dict(TINY, hidden_size=128), 64) if kernel else (
        TINY, SEQ)
    if kernel:
        monkeypatch.setattr(attention, "_FORCE_INTERPRET", True)
    cfg, dims = model_config(cfg_dict, seq), reference.Dims(cfg_dict)
    h = cfg.hidden
    w = _layers(cfg_dict)[kind]
    if kind == "attention":
        # norms away from 1, so that their weights are read
        w = dict(w, q_norm=w["q_norm"] * 1.5, k_norm=w["k_norm"] * 0.7)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, seq, h))
    cot = jax.random.normal(jax.random.PRNGKey(5), (2, seq, h))
    layer = _program_layer(kind, cfg)
    row = reference.LAYER_ROW[kind]

    def want_fn(x, w):
        return jnp.stack([row(r, w, dims, RULE) for r in x])

    got, counted = _counted(lambda: layer(x, w))
    if kind == "short_conv":
        assert counted == {("kernel" if kernel else "jnp", "fwd"): 1}
    want = want_fn(x, w)
    assert float(jnp.abs(got - want).max()) < 2e-4
    assert float(jnp.abs(got - x).max()) > 1e-2
    got_g = jax.jit(jax.grad(lambda x, w: (layer(x, w) * cot).sum(),
                             (0, 1)))(x, w)
    want_g = jax.jit(jax.grad(lambda x, w: (want_fn(x, w) * cot).sum(),
                              (0, 1)))(x, w)
    names = ["x"] + sorted(w)
    for name, a, b in zip(names, jax.tree.leaves(got_g),
                          jax.tree.leaves(want_g)):
        scale = float(jnp.abs(b).max())
        assert float(jnp.abs(a - b).max()) <= 1e-4 + 2e-4 * scale, name
        if name != "router_bias":
            assert scale > 1e-4, name


@pytest.mark.parametrize("fault", list(reference.FAULTS))
def test_a_planted_fault_changes_its_layer(fault):
    kind = {"no_qk_norm": "attention", "bias_weighs": "moe",
            "no_routed": "moe"}.get(fault, "short_conv")
    dims = reference.Dims(TINY)
    layers = _layers(TINY)
    x = jax.random.normal(jax.random.PRNGKey(4), (SEQ, 64))
    for other, w in layers.items():
        row = reference.LAYER_ROW[other]
        clean, broken = row(x, w, dims, RULE), row(x, w, dims, RULE, fault)
        if other == kind:
            assert float(jnp.abs(clean - broken).max()) > 1e-3, fault
        else:
            np.testing.assert_array_equal(clean, broken)


def test_the_shares_add_up():
    """The tie of the share to the model (guide section 4): with 40
    experts in 5 shares of 8, the routed parts the five chips give, with
    the residual (which every chip computes alike) counted once, add up
    to what the uncut reference gives for the whole layer."""
    cfg = dict(TINY, router_width=40, num_experts=40,
               experts_held_first=0, num_experts_per_tok=8)
    w = _layers(cfg, seed=2)["moe"]
    x = jax.random.normal(jax.random.PRNGKey(7), (2, SEQ, 64))
    whole = jnp.stack([reference.moe_row(r, w, reference.Dims(cfg), RULE)
                       for r in x])
    routed = 0
    for first in range(0, 40, 8):
        share = dict(cfg, num_experts=8,
                     experts_held_first=first)
        held = {k: w[k][first:first + 8]
                for k in ("w_gate", "w_up", "w_down")}
        out, drawn = tfm.moe_block(x, dict(w, **held), model_config(share))
        routed = routed + (out - x)
        assert int(drawn.sum()) == 2 * SEQ * 8
        report = tfm.routing_report(drawn[None], model_config(share).stack,
                                    2 * SEQ)
        assert int(report["moe_rows_over"]) == 0
    assert float(jnp.abs(routed + x - whole).max()) < 2e-4
    assert float(jnp.abs(whole - x).max()) > 1e-2


# ------------------------------------------------------- the whole model
def test_the_loss_and_every_gradient_are_the_references():
    """The lead C D and two periods, the final norm and the head tied to
    the embedding in one stack: the loss and the gradient of every leaf
    (the embedding's two parts summed) against the reference's."""
    mcfg = model_config()
    tokens = weights.token_batch(3, 0, 2, SEQ, 256)
    (loss, counted), grads = jax.jit(jax.value_and_grad(
        lambda p: tfm.loss_and_rows(p, tokens, mcfg), has_aux=True))(
        seeded())
    model = reference.Model(TINY)
    key = weights.seed_key(1)
    start = {name: weights.make_leaf(TINY, key, None, name)
             for name in weights.TOP_LEAVES}
    start["layers"] = [{name: weights.make_leaf(TINY, key, kind, name, l)
                        for name in weights.LEAVES[kind]}
                       for l, (_, kind) in enumerate(weights.entries(TINY))]
    want, want_grads, drawn = model.loss_and_grads(
        jax.tree.map(lambda a: a.astype(jnp.float32), start), tokens)
    assert float(loss) == pytest.approx(want, rel=2e-6)
    assert sorted(drawn) == [3, 5, 7, 9, 11, 13, 15, 17]
    even = 2 * SEQ * 4 / 16
    for row, entry in zip(np.asarray(counted["router_bias_step"]),
                          sorted(drawn)):
        np.testing.assert_allclose(
            row, 0.02 * (1.0 - np.asarray(drawn[entry]) / even), rtol=1e-6)
    got = whole_model.by_name(grads)
    ref = whole_model.reference_by_name(
        reference.leaves(want_grads, model.dims))
    assert set(got) == set(ref)
    for name in sorted(ref):
        a, b = np.asarray(got[name]), np.asarray(ref[name])
        scale = float(np.abs(b).max())
        assert float(np.abs(a - b).max()) <= 1e-6 + 3e-4 * scale, name
        assert (scale == 0.0) == ("router_bias[" in name), name


def test_the_step_follows_the_reference_for_two_steps():
    """Loss, every leaf's first gradient and the two-step change of the
    whole model (the lead C D, two periods, the tied head), through
    ``build_train_step``, against the plain reference, the correction
    bias moved by the same rule, the program's first gradient found leaf
    by leaf as the reference asks for it; and the compiled step's scope
    table has the mixers' scopes."""
    batches = [weights.token_batch(3, i, 2, SEQ, 256) for i in (0, 1)]
    (program, (m1, m2)), counted = _counted(
        lambda: whole_model.program_numbers(model_config(), HP,
                                            seeded(seed=3), batches))
    # the seven C layers share their kind's function: traced once for the
    # lead and once inside the loop over the periods, a pass each way
    assert counted == {("jnp", "fwd"): 2, ("jnp", "bwd"): 2}
    ref = whole_model.reference_numbers(
        reference, weights, TINY, HP, 3, batches,
        against=driver.leaf_of(program["first_grad_leaves"], config=TINY,
                               scale=program["first_grad_scale"]))
    numbers = compare_difference.training_numbers(program, ref)
    assert numbers["loss_gap"] < 1e-5, numbers
    assert numbers["first_grad_gap"] < 1e-3, numbers
    assert numbers["grad_share_gap"] < 1e-3, numbers
    assert numbers["change_gap"] < 1e-2, numbers
    assert numbers["first_grad_diff"] < 1e-3, numbers
    names = set(compare.flat(ref["first_grad"]))
    assert set(compare.flat(program["first_grad"])) == names
    # 2 of the top; the lead's mixer (4) and dense layer (4); six mixers
    # (24), two attention layers (14), eight expert layers (48)
    assert len(names) == 2 + 8 + 24 + 14 + 48
    assert {"lead/short_conv/conv_w[0]", "layers/short_conv/conv_w[5]",
            "layers/attention/q_norm[1]", "layers/attention/k_norm[0]",
            "layers/moe/router[7]", "lead/dense/w_up[0]"} <= names
    assert publish_moe_rows(m2)["moe_rows_over"] == 0
    np.testing.assert_allclose(program["change"]["layers/moe/router_bias"],
                               ref["change"]["layers/moe/router_bias"],
                               rtol=1e-4)
    # the planted faults: test_a_planted_fault_changes_its_layer[*] (this
    # file) holds each to a change in its layer, and
    # benchmark/tests/test_lfm2_train_steps.py::
    # test_the_control_and_the_planted_faults_are_not_correct[*] puts each
    # in the program's place against the cell's limits
    from benchmark.readers.trace_scope_share import scopes_of

    found = {tuple(scopes_of(p))
             for p in dp.scope_table_of("train_step").values()}

    def has(*scopes):
        return any(all(s in path for s in scopes) for path in found)

    for scope in ("in_proj", "gate_conv", "out_proj"):
        assert has("layers", "short_conv", scope), scope
    for scope in ("qkv_proj", "qk_norm", "rope", "flash", "out_proj"):
        assert has("layers", "attention", scope), scope
    for scope in ("router", "dispatch", "experts", "combine"):
        assert has("mlp", "moe", scope), scope
    assert not has("shared_expert")
