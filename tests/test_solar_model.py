"""The Solar-Open2 stack (Kimi Delta Attention, the gated delta rule with
a decay a channel, in three layers of four; softmax attention without
position, gated element for element; sigmoid-routed SwiGLU experts with a
shared expert, a share of them held) at tiny widths on the CPU, each
piece against the plain reference
``benchmark/references/solar_open2_decoder.py``, a stated identity or
what the parent commit computed."""

import dataclasses
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import compare, flops_solar, weights_solar as weights
from benchmark.drivers import (
    _expert_train_steps as body,
    solar_train_steps as driver,
)
from benchmark.references import solar_open2_decoder as reference
from ray_tpu.models import transformer as tfm
from ray_tpu.models.training import (
    build_pipeline_train_step,
    build_train_step,
    carried_params,
    make_optimizer,
    publish_moe_rows,
)
from ray_tpu.observability import device_programs as dp
from ray_tpu.observability.metrics import kda_chunks, mamba_conv_calls
from ray_tpu.ops import attention, kda
from ray_tpu.parallel.mesh import MeshSpec, build_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(
        ROOT, "benchmark/configs/solar_open2_l4_ep40.json")) as f:
    SOLAR = json.load(f)
# the cell's period G K K K; hidden 64; 4 delta-rule heads of 16, chunk 8;
# 4 / 2 attention heads of 16; 16 experts top-4 of width 24 with experts
# 2-4 held, a shared expert of 24
TINY = dict(
    SOLAR, hidden_size=64,
    linear_attn_config=dict(SOLAR["linear_attn_config"], num_heads=4,
                            head_dim=16),
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    router_width=16, n_routed_experts=3, experts_held_first=2,
    num_experts_per_tok=4, moe_intermediate_size=24, vocab_size=256,
    torch_dtype="float32",
    run=dict(SOLAR["run"], logits_chunk=16, kda_chunk=8))
HP = dict(SOLAR["run"]["optimizer"], warmup_steps=8)
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
        weights.make_stacked(cfg, weights.seed_key(seed)))


def one_layer(kinds, kind, index=0):
    return jax.tree.map(lambda a: a[index], kinds[kind])


def reference_weights(cfg=TINY, seed=1):
    """The same seeded leaves in the reference's layout: the layers as a
    list, the model's own leaves beside them."""
    key = weights.seed_key(seed)
    out = {name: weights.make_leaf(cfg, key, None, name)
           for name in weights.TOP_LEAVES}
    out["layers"] = [
        {name: weights.make_leaf(cfg, key, kind, name, l)
         for name in weights.LEAVES[kind]}
        for l, (_, kind) in enumerate(weights.entries(cfg))]
    return jax.tree.map(lambda a: a.astype(jnp.float32), out)


def attend(q, k, v, window=None):
    return tfm.flash_attention(q, k, v, True, None, None, None, window)


# ------------------------------------------------------------- the op
def delta_inputs(seed, decay, beta_near_two, b=2, s=64, h=3, d=16):
    """q and k normalised a head as the model hands them, g a log decay
    of about ``-decay`` a position and channel, beta in (0, 2)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q, k, v, cot = (jax.random.normal(key, (b, s, h, d)) for key in ks[:4])
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * d ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    g = -decay * jax.random.uniform(ks[4], (b, s, h, d), minval=0.5,
                                    maxval=1.5)
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[5], (b, s, h))
                              + (4.0 if beta_near_two else 0.0))
    return (q, k, v, g, beta), cot


def step_by_step(q, k, v, g, beta):
    """The reference's recurrence, a row at a time."""
    return jnp.stack([reference.delta_rule(*row)
                      for row in zip(q, k, v, g, beta)])


@pytest.mark.parametrize("decay, beta_near_two", [
    (0.05, False),   # the seed's mild decay
    (5.0, False),    # e^-5 a position: a single reference a chunk overflows
    (5.0, True),     # and the reflection's eigenvalue near -1
    (0.05, True),
])
def test_the_chunked_rule_is_the_recurrence(decay, beta_near_two):
    """``gated_delta_rule`` against the recurrence step by step: the
    output and the gradient of q, k, v, g and beta, at chunks of 16 in a
    sequence of 64 and 3 heads (one group of heads) and at chunks of 8."""
    args, cot = delta_inputs(0, decay, beta_near_two)
    want = step_by_step(*args)
    want_g = jax.grad(lambda *a: (step_by_step(*a) * cot).sum(),
                      argnums=(0, 1, 2, 3, 4))(*args)
    if beta_near_two:
        assert float(args[4].max()) > 1.99
    for chunk in (16, 8):
        rule = lambda *a: kda.gated_delta_rule(*a, chunk=chunk)  # noqa: E731
        got = rule(*args)
        assert float(jnp.abs(got - want).max()) <= 2e-5 * float(
            jnp.abs(want).max())
        got_g = jax.grad(lambda *a: (rule(*a) * cot).sum(),
                         argnums=(0, 1, 2, 3, 4))(*args)
        for name, a, b in zip("qkvgb", got_g, want_g):
            scale = float(jnp.abs(b).max())
            assert scale > 1e-3, name
            assert float(jnp.abs(a - b).max()) <= 1e-4 * scale, (name, chunk)


def test_the_rule_counts_its_chunks_and_refuses_a_ragged_chunk():
    args, cot = delta_inputs(1, 0.5, False, h=2, s=32)
    before = dict(kda_chunks.series())
    _, pull = jax.vjp(lambda *a: kda.gated_delta_rule(*a, chunk=8), *args)
    pull(cot)
    counted = {k: v - before.get(k, 0) for k, v in kda_chunks.series().items()
               if v != before.get(k, 0)}
    assert counted == {("jnp", "fwd"): 4, ("jnp", "bwd"): 4}
    with pytest.raises(ValueError, match="power of two that divides"):
        kda.gated_delta_rule(*args, chunk=12)


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setattr(attention, "_FORCE_INTERPRET", True)


FUSED = {
    # name: (type of q, k and v, decay, beta near two, keys' shared part)
    "f32_mild": (jnp.float32, 0.05, False, 0.0),
    # e^-5 a position: a single reference a chunk overflows
    "f32_strong": (jnp.float32, 5.0, False, 0.0),
    "bf16": (jnp.bfloat16, 0.5, False, 0.0),
    # the reflection's eigenvalue near -1
    "beta_near_two": (jnp.float32, 0.05, True, 0.0),
    # every two keys of a chunk at a cosine of about 0.5, where the six
    # products of the inverse's other form return noise
    "shared_keys": (jnp.float32, 0.05, True, 0.7),
}


@pytest.mark.parametrize("chunk", [16, 64])
@pytest.mark.parametrize("case", list(FUSED))
def test_the_fused_kernels_are_the_jnp_tier_and_the_recurrence(
        case, chunk, interpreted):
    """``kda_chunk_fwd`` / ``kda_chunk_bwd`` under the interpreter at a
    head of 128 lanes and chunks of 16 (two grid steps of 8, a group of 8
    chunks, three heads a step) and of 64 (one grid step, four groups of
    two, two heads a step): the output and
    all five gradients against the ``jnp`` tier's, with the state each
    chunk entered with kept (the gradient's forward) and without; the
    float32 cases against the recurrence step by step too."""
    dtype, decay, beta_near_two, shared = FUSED[case]
    args, cot = delta_inputs(2, decay, beta_near_two, b=1,
                             s=(16 if chunk == 16 else 8) * chunk,
                             h=3 if chunk == 16 else 2, d=128)
    if shared:
        k = args[1] * (1 - shared) + shared * jnp.ones((128,)) / 128 ** 0.5
        args = (args[0], k / jnp.linalg.norm(k, axis=-1, keepdims=True)
                ) + args[2:]
        assert float(jnp.einsum("bshd,bthd->bhst", k, k).mean()) > 0.4
    if beta_near_two:
        assert float(args[4].max()) > 1.99
    args = tuple(a.astype(dtype) for a in args[:3]) + args[3:]
    chunks = args[0].shape[1] // chunk
    before = dict(kda_chunks.series())

    def through(rule):
        return rule(*args), jax.grad(
            lambda *a: (rule(*a).astype(jnp.float32) * cot).sum(),
            argnums=(0, 1, 2, 3, 4))(*args)

    got, got_g = through(lambda *a: kda._rule(*a, chunk, True))
    want, want_g = through(lambda *a: kda._rule(*a, chunk, False))
    counted = {k: v - before.get(k, 0) for k, v in kda_chunks.series().items()
               if v != before.get(k, 0)}
    assert counted == {(tier, which): chunks * n for tier in ("kernel", "jnp")
                       for which, n in (("fwd", 2), ("bwd", 1))}
    assert kda._step_heads(args[0].shape[2]) == args[0].shape[2]

    def close(got, want, loose):
        for name, a, b in zip("oqkvgb", got, want):
            a, b = a.astype(jnp.float32), b.astype(jnp.float32)
            assert float(jnp.abs(a - b).max()) <= loose * float(
                jnp.abs(b).max()), name

    close((got,) + got_g, (want,) + want_g,
          5e-5 if dtype == jnp.float32 else 2e-2)
    if dtype == jnp.float32:
        exact = through(step_by_step)
        close((got,) + got_g, (exact[0],) + exact[1],
              1e-3 if shared else 1e-4)


def _tiny_mistral(file):
    from benchmark.drivers.train_steps import model_config as build

    with open(os.path.join(ROOT, "benchmark/configs", file)) as f:
        published = json.load(f)
    return build(dict(
        published, hidden_size=64, intermediate_size=96, vocab_size=256,
        num_attention_heads=4, num_key_value_heads=2, num_hidden_layers=2,
        torch_dtype="float32",
        run=dict(published["run"], logits_chunk=16)), SEQ)


def _tiny_of(module):
    import importlib

    return importlib.import_module(f"tests.{module}").model_config()


# every other train cell of BENCHMARK.json -> its stack at tiny widths
OTHER_CELLS = {
    "mistral7b_l4_train_s4096": lambda: _tiny_mistral("mistral_7b_l4.json"),
    "mistral7b_l4_train_s512": lambda: _tiny_mistral("mistral_7b_l4.json"),
    "mistral7b_l12_train_s4096_4chip": lambda: _tiny_mistral(
        "mistral_7b_l12_fsdp2tp2.json"),
    "nemotron_twotower_l9_train_s8192": lambda: _tiny_of("test_hybrid_model"),
    "mellum2_l8_train_s8192": lambda: _tiny_of("test_mellum_model"),
    "glm47flash_l7_train_s8192": lambda: _tiny_of("test_glm_model"),
    "nemotron3super_l9_train_s8192": lambda: _tiny_of(
        "test_latent_moe_model"),
}


def _delta_rule_traced(cfg, monkeypatch):
    """(whether the jaxpr of ``cfg``'s train step names a ``kda_*``
    kernel or the scope ``delta``, what ``kda_chunks`` counted while it
    was traced), with kernels on."""
    monkeypatch.setattr(attention, "kernels_on", lambda: True)
    step, init_fn = build_train_step(
        cfg, build_mesh(MeshSpec(), jax.devices()[:1]))
    state = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    before = dict(kda_chunks.series())
    text = str(jax.make_jaxpr(step)(
        *state, jax.ShapeDtypeStruct((2, cfg.max_seq + 1), jnp.int32)))
    return ("kda_" in text or "delta" in text), {
        k: v - before.get(k, 0) for k, v in kda_chunks.series().items()
        if v != before.get(k, 0)}


@pytest.mark.parametrize("cell", list(OTHER_CELLS))
def test_no_other_cell_runs_the_delta_rule(cell, monkeypatch):
    """``gated_delta_rule`` has one caller, ``kda_block``, which only the
    ``K`` kind calls: the seven other cells' stacks have no such layer,
    trace no ``kda_*`` kernel and count nothing in ``kda_chunks``; this
    cell's stack at the cell's head and chunk does both (the check's own
    control)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cells = {w["name"]: w["config"] for w in json.load(f)["workloads"]}
    assert cells[cell] != cells["solaropen2_l4_train_1row"]
    cfg = OTHER_CELLS[cell]()
    assert "K" not in cfg.stack.lead + cfg.stack.pattern + cfg.stack.mtp
    assert _delta_rule_traced(cfg, monkeypatch) == (False, {})


def test_this_cell_s_stack_runs_the_delta_rule_s_kernels(monkeypatch):
    cfg = model_config(dict(
        TINY, linear_attn_config=dict(TINY["linear_attn_config"],
                                      num_heads=1, head_dim=128),
        run=dict(TINY["run"], kda_chunk=16)), seq=128)
    named, counted = _delta_rule_traced(cfg, monkeypatch)
    assert named and set(counted) == {("kernel", "fwd"), ("kernel", "bwd")}


def test_the_rule_says_yes_to_the_cells_shapes(monkeypatch):
    """``walk_tier``: the cell's 128 chunks of 64 at heads of 128 take the
    kernels where kernels run; a partitioned step, a narrow head, a chunk
    off a 16-bit tile and chunks short of a grid step take the scan."""
    assert not kda.walk_tier(64, 128, 128)          # the CPU
    monkeypatch.setattr(attention, "kernels_on", lambda: True)
    assert kda.walk_tier(64, 128, 128)
    assert not kda.walk_tier(64, 128, 128, sharded=True)
    assert not kda.walk_tier(64, 64, 128)
    assert not kda.walk_tier(8, 128, 128)
    assert not kda.walk_tier(64, 128, 4)


def test_the_inverse_and_its_rule():
    """(I + A)^-1 of a strictly lower-triangular A from blocks of one
    position up, and its backward by two products, where the keys of a
    chunk share a direction (a cosine of 0.6 between any two, beta up to
    1.9): the powers of A reach 1e11 there, and the six products
    ``prod (I + (-A)^(2^i))`` return noise."""
    rng = np.random.default_rng(0)
    shared = rng.normal(size=(128,))
    k = rng.normal(size=(3, 64, 128)) / np.sqrt(128) * np.sqrt(0.4) \
        + shared / np.linalg.norm(shared) * np.sqrt(0.6)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    a = np.tril(k @ k.transpose(0, 2, 1), -1) * rng.uniform(
        0.5, 1.9, size=(3, 64, 1))
    want = np.linalg.inv(np.eye(64) + a)
    assert float(np.abs(np.linalg.matrix_power(a[0], 16)).max()) > 1e6
    inv = kda.unit_lower_inverse(jnp.asarray(a, jnp.float32))
    assert float(np.abs(np.asarray(inv) - want).max()) <= 2e-6 * float(
        np.abs(want).max())
    cot = jax.random.normal(jax.random.PRNGKey(3), a.shape)
    got = jax.grad(lambda a: (kda.unit_lower_inverse(a) * cot).sum())(
        jnp.asarray(a, jnp.float32))
    turned = want.transpose(0, 2, 1)
    want_g = -turned @ np.asarray(cot, np.float64) @ turned
    assert float(np.abs(np.asarray(got) - want_g).max()) <= 1e-5 * float(
        np.abs(want_g).max())


def test_keys_that_share_a_direction():
    """The chunked rule at chunks of 64 against the recurrence where every
    two keys of a chunk have a cosine of about 0.5 (what SiLU's positive
    mean gives a seeded model's keys, and more): output and gradients."""
    args, cot = delta_inputs(4, 0.05, True, b=1, s=128, h=2, d=16)
    q, k = args[:2]
    shared = jnp.ones((16,)) / 4.0
    k = k * 0.7 + shared * 0.7
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    args = (q, k) + args[2:]
    assert float(jnp.einsum("bshd,bthd->bhst", k, k).mean()) > 0.4
    want = step_by_step(*args)
    got = kda.gated_delta_rule(*args, chunk=64)
    assert float(jnp.abs(got - want).max()) <= 1e-4 * float(
        jnp.abs(want).max())
    want_g = jax.grad(lambda *a: (step_by_step(*a) * cot).sum(),
                      argnums=(0, 1, 2, 3, 4))(*args)
    got_g = jax.grad(lambda *a: (kda.gated_delta_rule(*a, chunk=64)
                                 * cot).sum(), argnums=(0, 1, 2, 3, 4))(*args)
    for name, a, b in zip("qkvgb", got_g, want_g):
        assert float(jnp.abs(a - b).max()) <= 1e-3 * float(
            jnp.abs(b).max()), name


# ------------------------------------------------------------ the stack
def test_the_configuration_describes_the_stack():
    """What the driver hands ``Stack`` from the published keys: the period
    G K K K with an expert layer behind each mixer, every width as
    published, the cut as the file says, 1295 M parameters."""
    cfg = driver.model_config(SOLAR, 8192)
    st = cfg.stack
    assert (st.lead, st.pattern, st.mtp) == ("", "*EKEKEKE", "")
    assert st.period == "*EKEKEKE" and cfg.layers == 8
    assert (cfg.hidden, cfg.heads, cfg.kv_heads, cfg.head_dim) == (
        4096, 64, 8, 128)
    assert (st.kda_heads, st.kda_head_dim, st.kda_gate_rank, st.kda_beta_max,
            st.kda_chunk, st.conv_kernel, st.kda_inner) == (
        64, 128, 128, 2.0, 64, 4, 8192)
    assert st.attention_gate and not cfg.rotary
    assert (st.routed_experts, st.experts_per_token, st.expert_width,
            st.shared_width, st.routed_scale, st.held, st.expert_latent) == (
        320, 8, 1280, 1280, 1, (0, 8), 0)
    assert (st.router_score, st.expert_act, st.bias_rate,
            st.rows_over_expected) == ("sigmoid", "swiglu", 0.02, 3)
    # three times the even draw of 8192 x 8 x 8 / 320, whole tiles
    assert st.row_buffer(8192) == 5120 == 10 * 512
    assert cfg.norm_eps == 1e-5 and not cfg.tie_embeddings
    shapes = jax.eval_shape(lambda k: tfm.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    assert set(shapes["layers"]) == {"kda", "moe", "attention"}
    mixer = shapes["layers"]["kda"]
    assert {k: v.shape for k, v in mixer.items()} == {
        "norm": (3, 4096), "w_qkv": (3, 4096, 24576),
        "conv_w": (3, 4, 24576), "w_decay_down": (3, 4096, 128),
        "w_decay_up": (3, 128, 8192), "dt_bias": (3, 8192), "a_log": (3, 64),
        "w_beta": (3, 4096, 64), "w_gate_down": (3, 4096, 128),
        "w_gate_up": (3, 128, 8192), "head_norm": (3, 128),
        "w_out": (3, 8192, 4096)}
    assert shapes["layers"]["attention"]["w_gate"].shape == (1, 4096, 8192)
    for name in ("conv_w", "dt_bias", "a_log", "head_norm", "norm"):
        assert mixer[name].dtype == jnp.float32, name
    held = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert held == flops_solar.solar_params(SOLAR) == 1_295_087_424
    by_kind = flops_solar.params_by_kind(SOLAR)
    assert (by_kind["K"], by_kind["*"], by_kind["E"]) == (
        137_736_384, 109_056_000, 142_872_896)
    # the seeded weights are laid out as the program's parameters
    seeded_shapes = jax.eval_shape(
        lambda k: weights.make_stacked(SOLAR, k), jax.random.PRNGKey(0))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), seeded_shapes) == \
        jax.tree.map(lambda a: (a.shape, a.dtype), shapes)
    # every published key is in the file as published, but the four cut
    published = dict(SOLAR["published"])
    assert sorted(published) == sorted(SOLAR["reduced"]) == [
        "gqa_layers", "n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert published["gqa_layers"] == list(range(0, 48, 4))


REFUSED = {
    "the delta rule over sp": (
        MeshSpec(sp=2), r"delta-rule mixer \(K\) over an sp axis"),
    "the stack on the pipeline path": (
        MeshSpec(pp=2), "Its K layers' leaves are a kind's of their own"),
    "the experts over dp": (MeshSpec(dp=2), "all-to-all of tokens between"),
}


@pytest.mark.parametrize("what", list(REFUSED))
def test_what_is_not_built_is_refused_with_a_sentence(what):
    spec, sentence = REFUSED[what]
    cfg = model_config()
    mesh = build_mesh(spec, jax.devices()[:2])
    build = build_pipeline_train_step if spec.pp > 1 else build_train_step
    with pytest.raises(NotImplementedError, match=sentence):
        build(cfg, mesh)


def test_a_stack_needs_its_delta_rule_described():
    with pytest.raises(ValueError, match="kda_heads, kda_head_dim"):
        tfm.Stack(pattern="KD")


@pytest.mark.parametrize("which, params_sha, loss_bits", [
    # read on the parent commit (PR 38) with this test's own body
    ("nemotron3", "9cfb4234a0be1af6", "3b29ce40"),
    ("hybrid", "84859d8c51973feb", "b6cfc440"),
])
def test_attention_without_the_gate_is_unchanged_to_the_bit(
        which, params_sha, loss_bits):
    """``attention_gate`` off, every accepted configuration's case: two
    accepted stacks with ``*`` layers draw the parameters they drew and
    read the loss they read on the parent commit, bit for bit, and have
    no gate's leaf."""
    from tests import test_hybrid_model, test_latent_moe_model

    cfg = {"nemotron3": test_latent_moe_model,
           "hybrid": test_hybrid_model}[which].model_config()
    assert not cfg.stack.attention_gate
    with jax.default_matmul_precision("default"):
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        assert "w_gate" not in params["layers"]["attention"]
        digest = hashlib.sha256()
        for leaf in jax.tree.leaves(params):
            digest.update(np.asarray(leaf).tobytes())
        assert digest.hexdigest()[:16] == params_sha
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0,
                                    cfg.vocab_size)
        loss, _ = jax.jit(
            lambda p, t: tfm.loss_and_rows(p, t, cfg))(params, tokens)
        assert np.asarray(loss).tobytes().hex() == loss_bits


# ------------------------------------------------------------ the layers
def _program_layer(kind, cfg):
    if kind == "kda":
        return lambda x, w: tfm.kda_block(x, w, cfg)
    if kind == "attention":
        return lambda x, w: tfm.attention_block(
            x, w, cfg, None, None, attend, gated=True)
    return lambda x, w: tfm.moe_block(x, w, cfg)[0]


@pytest.mark.parametrize("kind", ["kda", "attention", "moe"])
def test_a_layer_and_its_gradients_are_the_reference_layers(kind):
    """One layer of each kind on the seeded weights against the
    reference's row function, the output and the gradient of every leaf
    and of the input: the delta-rule mixer (three convolutions without
    bias, the normalised q and k, the decay a channel through its rank,
    beta in (0, 2), the head norm and the gate), gated attention without
    position, the sigmoid-routed SwiGLU expert layer."""
    cfg, dims = model_config(), reference.Dims(TINY)
    w = one_layer(seeded()["layers"], kind)
    if kind == "moe":
        w = dict(w, router_bias=jax.random.normal(
            jax.random.PRNGKey(3), (16,)) * 0.3)
    if kind == "kda":
        # a decay strong enough that a chunk's single reference would not do
        w = dict(w, dt_bias=w["dt_bias"] + 3.0)
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


def test_the_mixer_a_group_of_heads_at_a_time_is_the_mixer_whole(monkeypatch):
    """``kda_block`` works ``HEADS_AT_ONCE`` heads at a time, each group's
    columns of the weights in turn: 4 heads in groups of 1 and of 2 give
    what all 4 at once give, values and every gradient."""
    cfg = model_config()
    w = one_layer(seeded()["layers"], "kda", 1)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, SEQ, 64))

    def run(at_once):
        monkeypatch.setattr(tfm, "HEADS_AT_ONCE", at_once)
        return jax.value_and_grad(
            lambda x, w: jnp.sum(jnp.square(tfm.kda_block(x, w, cfg))),
            (0, 1))(x, w)

    whole, whole_g = run(4)
    for at_once in (1, 2):
        got, got_g = run(at_once)
        assert float(got) == pytest.approx(float(whole), rel=1e-5)
        for a, b in zip(jax.tree.leaves(got_g), jax.tree.leaves(whole_g)):
            assert float(jnp.abs(a - b).max()) <= 1e-5 + 1e-4 * float(
                jnp.abs(b).max())


@pytest.mark.parametrize("fault", list(reference.FAULTS))
def test_a_planted_fault_changes_its_layer(fault):
    kind = {"no_routed": "moe", "ungated_attention": "attention"}.get(
        fault, "kda")
    dims = reference.Dims(TINY)
    w = one_layer(reference_weights_by_kind(), kind)
    x = jax.random.normal(jax.random.PRNGKey(4), (SEQ, 64))
    row = reference.LAYER_ROW[kind]
    clean, broken = row(x, w, dims, RULE), row(x, w, dims, RULE, fault)
    assert float(jnp.abs(clean - broken).max()) > 1e-3, fault
    for other in set(reference.LAYER_ROW) - {kind}:
        w2 = one_layer(reference_weights_by_kind(), other)
        np.testing.assert_array_equal(
            reference.LAYER_ROW[other](x, w2, dims, RULE),
            reference.LAYER_ROW[other](x, w2, dims, RULE, fault))


def reference_weights_by_kind():
    return {kind: jax.tree.map(lambda a: a[None], next(
        layer for layer, k in zip(reference_weights()["layers"],
                                  reference.Dims(TINY).kinds) if k == kind))
            for kind in reference.LAYER_ROW}


def test_the_shares_add_up():
    """The tie of the share to the model (guide section 4): with 40
    experts in 5 shares of 8, the routed parts the five chips give, with
    the shared expert and the residual (which every chip computes alike)
    counted once, add up to what the uncut reference gives for the whole
    layer."""
    cfg = dict(TINY, router_width=40, n_routed_experts=40,
               experts_held_first=0, num_experts_per_tok=8)
    w = one_layer(seeded(cfg, seed=2)["layers"], "moe")
    w = dict(w, router_bias=jax.random.normal(
        jax.random.PRNGKey(3), (40,)) * 0.05)
    x = jax.random.normal(jax.random.PRNGKey(7), (2, SEQ, 64))
    whole = jnp.stack([reference.moe_row(r, w, reference.Dims(cfg), RULE)
                       for r in x])
    alike = jnp.stack([reference.moe_row(r, w, reference.Dims(cfg), RULE,
                                         "no_routed") for r in x])
    routed = 0
    for first in range(0, 40, 8):
        share = dict(cfg, n_routed_experts=8, experts_held_first=first)
        held = {k: w[k][first:first + 8]
                for k in ("w_gate", "w_up", "w_down")}
        out, drawn = tfm.moe_block(x, dict(w, **held), model_config(share))
        routed = routed + (out - alike)
        assert int(drawn.sum()) == 2 * SEQ * 8
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


# ------------------------------------------------------- the whole model
def _by_name(tree):
    """The program's tree, leaf by leaf, by the reference's names."""
    out = {k: v for k, v in tree.items() if k != "layers"}
    for kind, leaves in tree["layers"].items():
        for k, v in leaves.items():
            for i in range(v.shape[0]):
                out[f"layers/{kind}/{k}[{i}]"] = v[i]
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


def test_the_loss_and_every_gradient_are_the_references():
    """The period G K K K with its four expert layers, the final norm and
    the head in one stack: the loss and the gradient of every leaf
    against the reference's."""
    mcfg = model_config()
    params = seeded()
    tokens = weights.token_batch(3, 0, 2, SEQ, 256)
    (loss, counted), grads = jax.value_and_grad(
        lambda p: tfm.loss_and_rows(p, tokens, mcfg), has_aux=True)(params)
    model = reference.Model(TINY)
    want, want_grads, drawn = model.loss_and_grads(reference_weights(),
                                                   tokens)
    assert float(loss) == pytest.approx(want, rel=2e-6)
    assert sorted(drawn) == [1, 3, 5, 7]
    bias_step = np.asarray(counted["router_bias_step"])
    even = 2 * SEQ * 4 / 16
    for row, entry in zip(bias_step, sorted(drawn)):
        np.testing.assert_allclose(
            row, 0.02 * (1.0 - np.asarray(drawn[entry]) / even), rtol=1e-6)
    got = _by_name(grads)
    ref = _reference_by_name(want_grads, model.dims)
    assert set(got) == set(ref)
    assert {"layers/kda/a_log[2]", "layers/kda/w_decay_up[0]",
            "layers/attention/w_gate[0]", "layers/moe/w_gate[3]"} <= set(ref)
    for name in sorted(ref):
        a, b = np.asarray(got[name]), np.asarray(ref[name])
        scale = float(np.abs(b).max())
        assert float(np.abs(a - b).max()) <= 1e-6 + 3e-4 * scale, name
        if "router_bias[" in name:
            assert scale == 0.0, name
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
                         seeded(seed=seed))).items()}}, (m1, m2), params


def _reference_numbers(seed, batches, operand="float32", fault=None, **more):
    kinds = [kind for _, kind in weights.entries(TINY)]
    key = weights.seed_key(seed)

    def initial_leaf(name, layer):
        kind = kinds[layer] if layer is not None else None
        return weights.make_leaf(TINY, key, kind, name, layer).astype(
            jnp.float32)

    return reference.follow_two_steps(
        TINY, HP, initial_leaf, batches, reference.OPERANDS[operand], fault,
        **more)


def test_the_step_follows_the_reference_for_two_steps():
    """Loss, every leaf's first gradient and the two-step change of the
    whole model, through ``build_train_step``, against the plain
    reference, the correction bias moved by the same rule, the program's
    first gradient found leaf by leaf as the reference asks for it; every
    planted fault in the reference's place does not pass; and the
    compiled step's scope table has the mixer's scopes and the gate's."""
    batches = [weights.token_batch(3, i, 2, SEQ, 256) for i in (0, 1)]
    convs = dict(mamba_conv_calls.series())
    program, (m1, m2), params = _program_numbers(model_config(), HP, 3,
                                                 batches)
    # the three convolutions are one call of ``conv_silu`` a pass
    assert {k: v - convs.get(k, 0)
            for k, v in mamba_conv_calls.series().items()
            if v != convs.get(k, 0)} == {("jnp", "fwd"): 2, ("jnp", "bwd"): 1}
    ref = _reference_numbers(3, batches, against=driver.leaf_of(
        program["first_grad_leaves"], config=TINY,
        scale=program["first_grad_scale"]))
    numbers = compare.training_numbers(program, ref)
    assert numbers["loss_gap"] < 1e-5, numbers
    assert numbers["first_grad_gap"] < 1e-3, numbers
    assert numbers["grad_share_gap"] < 1e-3, numbers
    assert numbers["change_gap"] < 1e-2, numbers
    assert set(compare.flat(program["first_grad"])) == set(
        compare.flat(ref["first_grad"]))
    # 3 of the top; three mixers (12), one attention layer (6), four
    # expert layers (9)
    assert len(compare.flat(ref["first_grad"])) == 3 + 36 + 6 + 36
    from benchmark import compare_difference

    diff = compare_difference.training_numbers(program, ref)
    assert diff["first_grad_diff"] < 1e-3, diff
    assert publish_moe_rows(m2)["moe_rows_over"] == 0
    assert np.all(ref["change"]["layers/moe/router_bias"] > 0)
    np.testing.assert_allclose(program["change"]["layers/moe/router_bias"],
                               ref["change"]["layers/moe/router_bias"],
                               rtol=1e-4)
    for fault in reference.FAULTS:
        broken = compare.training_numbers(
            _reference_numbers(3, batches, fault=fault), ref)
        assert max(broken["first_grad_gap"], broken["change_gap"],
                   10 * broken["loss_gap"]) > 2e-2, (fault, broken)
    # the step's scopes, as the benchmark's readers find them (the
    # mixer's lie inside its loop over the groups of heads)
    from benchmark.readers.trace_scope_share import scopes_of

    found = {tuple(scopes_of(p))
             for p in dp.scope_table_of("train_step").values()}

    def has(*scopes):
        return any(all(s in path for s in scopes) for path in found)

    for scope in ("qkv_proj", "conv", "gates", "delta", "gate_norm",
                  "out_proj"):
        assert has("layers", "kda", scope), scope
    for scope in ("qkv_proj", "flash", "gate", "out_proj"):
        assert has("layers", "attention", scope), scope
    for scope in ("router", "dispatch", "experts", "combine",
                  "shared_expert"):
        assert has("mlp", "moe", scope), scope
