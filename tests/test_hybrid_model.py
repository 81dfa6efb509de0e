"""The pattern stack (Mamba-2, held-expert MoE, attention without rotary)
at tiny widths on the CPU, each piece against the plain reference
``benchmark/references/nemotron_h_decoder.py`` or a stated identity."""

import dataclasses
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from benchmark import compare, weights_hybrid
from benchmark.references import nemotron_h_decoder as reference
from ray_tpu.models import transformer as tfm
from ray_tpu.models.training import (
    build_pipeline_train_step,
    build_train_step,
    carried_params,
    carry_rounding,
    make_optimizer,
    param_shardings,
    publish_moe_rows,
)
from ray_tpu.ops import attention, ssd
from ray_tpu.ops.ssd import ssd_scan
from ray_tpu.parallel.mesh import MeshSpec, build_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PUBLISHED = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
# hidden 64, H 4 x P 8, N 16, chunk 8, 8 experts top-2, two periods
TINY = dict(
    hidden_size=64, hybrid_override_pattern="ME*E" * 2, num_hidden_layers=8,
    mamba_num_heads=4, mamba_head_dim=8, n_groups=2, ssm_state_size=16,
    conv_kernel=4, chunk_size=8, router_width=8, n_routed_experts=3,
    experts_held_first=2, num_experts_per_tok=2, moe_intermediate_size=48,
    moe_shared_expert_intermediate_size=96, routed_scaling_factor=2.5,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    vocab_size=256, layer_norm_epsilon=1e-5, time_step_min=0.001,
    time_step_max=0.1, time_step_floor=1e-4, torch_dtype="float32",
    tie_word_embeddings=False,
    run={"remat": True, "remat_policy": "full", "logits_chunk": 16,
         "router_bias_rate": 0.02})
HP = {"learning_rate": 3e-4, "weight_decay": 0.1, "b1": 0.9, "b2": 0.95,
      "eps": 1e-8, "grad_clip": 1.0, "warmup_steps": 8}
SEQ = 32


@pytest.fixture(autouse=True)
def exact_products():
    with jax.default_matmul_precision("highest"):
        yield


def model_config(cfg=TINY, seq=SEQ, **stack):
    """The driver's own mapping from the published keys, in float32, with
    ``stack`` fields replaced."""
    from benchmark.drivers.hybrid_train_steps import model_config as build

    built = build(dict(cfg, num_hidden_layers=len(
        cfg["hybrid_override_pattern"])), seq)
    return dataclasses.replace(
        built, stack=dataclasses.replace(built.stack, **stack))


def seeded(cfg, seed=1):
    """The benchmark's seeded weights widened to float32, stacked as the
    program lays them out."""
    return jax.tree.map(
        lambda a: a.astype(jnp.float32),
        weights_hybrid.make_stacked(cfg, weights_hybrid.seed_key(seed)))


def one_layer(params, kind, index=0):
    return jax.tree.map(lambda a: a[index], params["layers"][kind])


# ------------------------------------------------------------ the pattern
def test_the_published_pattern_parses():
    stack = tfm.Stack(pattern=PUBLISHED)
    assert len(PUBLISHED) == 52
    assert [stack.count(c) for c in "ME*"] == [23, 23, 6]
    assert stack.period == PUBLISHED          # it repeats nothing whole
    cut = tfm.Stack(pattern=PUBLISHED[35:44])
    assert cut.pattern == cut.period == "MEMEMEM*E"
    assert [cut.count(c) for c in "ME*"] == [4, 4, 1]
    assert tfm.Stack(pattern="ME*E" * 3).period == "ME*E"
    assert tfm.Stack().period == "" and tfm.ModelConfig().rotary
    with open(os.path.join(
            ROOT, "benchmark/configs/nemotron_twotower_30b_l9_ep8.json")) as f:
        config = json.load(f)
    assert config["hybrid_override_pattern"] == cut.pattern
    assert config["published"]["hybrid_override_pattern"] == PUBLISHED
    with pytest.raises(ValueError, match="kinds other than"):
        tfm.Stack(pattern="MXE")
    with pytest.raises(ValueError, match="the pattern"):
        tfm.ModelConfig(layers=3, stack=tfm.Stack(pattern="ME"))
    with pytest.raises(ValueError, match="not a range"):
        tfm.Stack(pattern="E", routed_experts=8, experts_held=(6, 4))


def test_two_periods_scan_and_one_period_does_too():
    cfg = model_config()
    tokens = jnp.zeros((2, SEQ), jnp.int32)
    params = jax.eval_shape(lambda k: tfm.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    jaxpr = str(jax.make_jaxpr(
        lambda p, t: tfm.hidden_states(p, t, cfg)[0])(params, tokens))
    # one scan over the two periods, not eight unrolled layers: each
    # kind's block is traced once (M and * once, E twice a period)
    assert jaxpr.count("length=2") >= 1
    assert params["layers"]["moe"]["w_up"].shape == (4, 3, 64, 48)
    assert params["layers"]["mamba"]["w_in"].shape == (2, 64, 32 + 96 + 4)


# ------------------------------------------------------------ the scan
def sequential_ssd(x, dt, a, bm, cm, d):
    ratio = x.shape[2] // bm.shape[2]

    def row(x, dt, bm, cm):
        def step(state, inputs):
            x_t, dt_t, b_t, c_t = inputs
            b_t, c_t = (jnp.repeat(v, ratio, axis=0) for v in (b_t, c_t))
            state = jnp.exp(dt_t * a)[:, None, None] * state \
                + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
            return state, jnp.einsum("hpn,hn->hp", state, c_t) \
                + d[:, None] * x_t
        zero = jnp.zeros(x.shape[1:] + (bm.shape[-1],))
        return lax.scan(step, zero, (x, dt, bm, cm))[1]

    return jax.vmap(row)(x, dt, bm, cm)


def ssd_inputs(batch=2, seq=SEQ, heads=4, p=8, groups=2, n=16):
    k = jax.random.split(jax.random.PRNGKey(0), 7)
    return (jax.random.normal(k[0], (batch, seq, heads, p)),
            jax.nn.softplus(jax.random.normal(k[1], (batch, seq, heads)) - 2),
            -jnp.exp(jax.random.uniform(k[2], (heads,)) * 2),
            jax.random.normal(k[3], (batch, seq, groups, n)),
            jax.random.normal(k[4], (batch, seq, groups, n)),
            jax.random.normal(k[5], (heads,)))


# the shape the jnp tier has always been tested at; the cell's 8 heads a
# group and 8 groups; a shape on the kernels' tiles (two heads of 64 a
# piece of 128 lanes, chunk and state 128)
SSD_SHAPES = {
    "tiny": {},
    "eight_by_eight": dict(batch=1, seq=64, heads=64, p=4, groups=8, n=8),
    "on_the_tiles": dict(batch=1, seq=256, heads=4, p=64, groups=2, n=128),
}


def scan_of(tier, chunk):
    """The scan of one tier whatever the rule would say of the shape: a
    test reaches the kernels as the flash tests reach theirs, by calling
    what ``ssd_scan`` dispatches to."""
    return lambda *z: ssd._scan(*z, chunk, tier == "kernel")


def scan_and_grads(scan, args):
    weight = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)
    return scan(*args), jax.grad(lambda *z: (scan(*z) * weight).sum(),
                                 argnums=range(6))(*args)


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setattr(attention, "_FORCE_INTERPRET", True)


@pytest.mark.parametrize("tier,shape,chunk", [
    ("jnp", "tiny", 8), ("jnp", "tiny", 16), ("jnp", "tiny", SEQ),
    ("kernel", "tiny", 8), ("kernel", "tiny", 16), ("kernel", "tiny", SEQ),
    ("jnp", "eight_by_eight", 32), ("kernel", "eight_by_eight", 32),
    ("kernel", "on_the_tiles", 128),
])
def test_ssd_scan_is_the_sequential_recurrence(tier, shape, chunk,
                                               interpreted):
    args = ssd_inputs(**SSD_SHAPES[shape])
    want, wanted = scan_and_grads(sequential_ssd, args)
    got, grads = scan_and_grads(scan_of(tier, chunk), args)
    assert float(jnp.abs(got - want).max()) < 1e-4 * float(
        jnp.abs(want).max())
    for g, w in zip(grads, wanted):
        assert float(jnp.abs(g - w).max()) < 1e-4 * float(jnp.abs(w).max())


@pytest.mark.parametrize("shape,chunk", [
    ("tiny", 8), ("tiny", 16), ("tiny", SEQ), ("eight_by_eight", 32),
    ("on_the_tiles", 128)])
def test_the_kernel_tier_is_the_jnp_tier(shape, chunk, interpreted):
    """Forward and every gradient (x, dt, a, B, C, d), the kernels under
    Pallas's interpreter: the same products in another order (the
    kernels add a head's lanes up in two bfloat16 pieces: 2 ** -17)."""
    args = ssd_inputs(**SSD_SHAPES[shape])
    want, wanted = scan_and_grads(scan_of("jnp", chunk), args)
    got, grads = scan_and_grads(scan_of("kernel", chunk), args)
    for g, w in zip((got,) + grads, (want,) + wanted):
        assert g.shape == w.shape and g.dtype == w.dtype
        assert float(jnp.abs(g - w).max()) < 1e-4 * float(jnp.abs(w).max())


def test_ssd_scan_takes_the_kernels_where_the_rule_says(interpreted):
    """Through the op itself, at a shape on the tiles: the rule sends it
    to the kernels (the counter says which tier was traced) and the
    answer is the jnp tier's, in bfloat16 as the cell runs it."""
    from ray_tpu.observability.metrics import ssd_scan_chunks

    x, dt, a, bm, cm, d = ssd_inputs(**SSD_SHAPES["on_the_tiles"])
    args = (x.astype(jnp.bfloat16), dt, a, bm.astype(jnp.bfloat16),
            cm.astype(jnp.bfloat16), d)
    before = ssd_scan_chunks.series()
    got, grads = scan_and_grads(lambda *z: ssd_scan(*z, 128), args)
    after = ssd_scan_chunks.series()
    assert {k: after[k] - before.get(k, 0) for k in after
            if after[k] != before.get(k, 0)} == {
        ("kernel", "fwd"): 2 * 2, ("kernel", "bwd"): 2}
    want, wanted = scan_and_grads(scan_of("jnp", 128), args)
    for g, w in zip((got,) + grads, (want,) + wanted):
        g, w = g.astype(jnp.float32), w.astype(jnp.float32)
        assert g.shape == w.shape
        # bfloat16 operands in another order: a few units of their last
        # place on the largest element
        assert float(jnp.abs(g - w).max()) < 2e-2 * float(jnp.abs(w).max())


# heads, head_dim, groups, state, chunk of the cell
CELL = dict(chunk=128, heads=64, head_dim=64, groups=8, state=128)


@pytest.mark.parametrize("on,change,sharded,kernel", [
    (True, {}, False, True),                   # the cell, on a TPU
    (False, {}, False, False),                 # the platform off
    (True, {}, True, False),                   # ssm_heads sharded over a mesh
    (True, {"chunk": 64}, False, False),       # a chunk off the lanes
    (True, {"state": 64}, False, False),       # a state off the lanes
    (True, {"head_dim": 48}, False, False),    # heads that straddle pieces
    (True, {"heads": 8, "head_dim": 8}, False, False),   # 8 lanes a group
    (True, {"heads": 512}, False, False),      # 3 x 64 heads over a chunk
    (True, {"head_dim": 128}, False, True),    # a head a piece
    (True, {"heads": 32, "head_dim": 32}, False, True),  # four heads a piece
])
def test_the_rule_that_picks_the_scan_tier(monkeypatch, on, change, sharded,
                                           kernel):
    """``scan_tier`` is all that decides, from the platform, the shapes
    and whether the step is partitioned; ``ssd_scan`` takes its word."""
    from ray_tpu.observability.metrics import ssd_scan_chunks

    monkeypatch.setattr(attention, "kernels_on", lambda: on)
    shape = dict(CELL, **change)
    assert ssd.scan_tier(sharded=sharded, **shape) is kernel
    heads, p, groups, n = (shape[k] for k in (
        "heads", "head_dim", "groups", "state"))
    seq = 2 * shape["chunk"]
    struct = jax.ShapeDtypeStruct
    before = ssd_scan_chunks.series()
    out = jax.eval_shape(
        lambda *z: ssd_scan(*z, shape["chunk"], sharded),
        struct((1, seq, heads, p), jnp.bfloat16),
        struct((1, seq, heads), jnp.float32), struct((heads,), jnp.float32),
        struct((1, seq, groups, n), jnp.bfloat16),
        struct((1, seq, groups, n), jnp.bfloat16),
        struct((heads,), jnp.float32))
    assert out.shape == (1, seq, heads, p) and out.dtype == jnp.bfloat16
    after = ssd_scan_chunks.series()
    tier = "kernel" if kernel else "jnp"
    assert after[(tier, "fwd")] - before.get((tier, "fwd"), 0) == 2


def test_a_step_over_a_mesh_takes_the_jnp_scan(monkeypatch):
    """``build_train_step`` tells the Mamba layers that the step is
    partitioned: on one device the scan of a shape on the tiles takes the
    kernels, over tp 2 (``ssm_heads`` sharded) the jnp tier, which the
    partitioner splits itself."""
    from ray_tpu.observability.metrics import (
        mamba_conv_calls,
        mamba_gate_norm_calls,
        ssd_scan_chunks,
    )

    monkeypatch.setattr(attention, "kernels_on", lambda: True)
    cfg = model_config(dict(
        TINY, hybrid_override_pattern="M*", mamba_head_dim=64,
        ssm_state_size=128, chunk_size=128), seq=128)
    tokens = jax.ShapeDtypeStruct((2, 129), jnp.int32)
    counters = (ssd_scan_chunks, mamba_conv_calls, mamba_gate_norm_calls)

    def traced(mesh):
        step, init_fn = build_train_step(cfg, mesh)
        state = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
        before = [c.series() for c in counters]
        jax.eval_shape(step, *state, tokens)
        return [{k for k in c.series() if c.series()[k] != was.get(k, 0)}
                for c, was in zip(counters, before)]

    # the convolution in front of the scan goes the scan's way (768
    # channels cut at 256 and 512: whole blocks of 256 lanes), and so do
    # the gate and the norm behind it (two groups of 128 lanes)
    one = traced(build_mesh(MeshSpec(), jax.devices()[:1]))
    assert one == [{("kernel", "fwd"), ("kernel", "bwd")}] * 3
    two = traced(build_mesh(MeshSpec(tp=2), jax.devices()[:2]))
    assert two == [{("jnp", "fwd"), ("jnp", "bwd")}] * 3


def test_ssd_scan_refuses_a_chunk_that_does_not_divide():
    with pytest.raises(ValueError, match="has to divide"):
        ssd_scan(*ssd_inputs(), 5)


@pytest.mark.parametrize("tier", ["jnp", "kernel"])
def test_ssd_backward_keeps_no_state_per_position(tier, interpreted):
    """What differentiating the scan keeps: the carried state at each
    chunk boundary and the inputs, nothing of [B, S, H, P, N]. The kernel
    tier keeps exactly that: its six inputs and the states."""
    from jax._src.ad_checkpoint import saved_residuals

    args = ssd_inputs()
    batch, seq, heads, p = args[0].shape
    n, chunk = args[3].shape[-1], 8
    kept = [aval for aval, _ in saved_residuals(
        scan_of(tier, chunk), *args)]
    largest = max(int(np.prod(aval.shape)) for aval in kept)
    assert largest == (seq // chunk) * batch * heads * p * n
    assert largest * chunk == batch * seq * heads * p * n
    if tier == "kernel":
        assert sorted(aval.shape for aval in kept) == sorted(
            [a.shape for a in args]
            + [(batch, args[3].shape[2], seq // chunk, n,
                heads // args[3].shape[2] * p)])


# ------------------------------------------------------- the convolution
def conv_inputs(batch, seq, channels, width, dtype, first=0, after=0):
    """x with ``first`` lanes before the convolution's channels and
    ``after`` behind them, weight, bias, and a weight for the sum."""
    k = jax.random.split(jax.random.PRNGKey(3), 4)
    return ((jax.random.normal(
        k[0], (batch, seq, first + channels + after))).astype(dtype),
            (jax.random.normal(k[1], (width, channels)) / 2).astype(dtype),
            (jax.random.normal(k[2], (channels,)) / 8).astype(dtype),
            jax.random.normal(k[3], (batch, seq, channels)))


def conv_and_grads(kernel, cuts, first, x, weight, bias, weigh):
    def whole(*z):
        return jnp.concatenate(ssd._conv(*z, cuts, first, kernel), axis=-1)

    return whole(x, weight, bias), jax.grad(
        lambda *z: (whole(*z) * weigh).sum(), argnums=(0, 1, 2))(
            x, weight, bias)


# three blocks of 64 rows; three of 128, each worked 64 rows at a time;
# K = 4 as the cell and two other widths (the widest looks back a whole
# 8 rows); whole and cut where the cell cuts, at its proportions; the
# channels alone and, as in the cell, inside the projection's output,
# lanes of z before them and of dt behind
@pytest.mark.parametrize("seq,channels,width,cuts,first,after,dtype", [
    (192, 256, 4, (), 0, 0, jnp.float32),
    (384, 384, 4, (128, 256), 128, 64, jnp.float32),
    (192, 128, 2, (), 0, 0, jnp.float32),
    (192, 128, 9, (), 256, 0, jnp.float32),
    (192, 384, 4, (128, 256), 256, 64, jnp.bfloat16),
    (384, 128, 3, (), 0, 0, jnp.bfloat16),
])
def test_the_conv_kernels_are_the_jnp_convolution(
        seq, channels, width, cuts, first, after, dtype, interpreted):
    """``conv_silu``'s kernel tier under Pallas's interpreter against
    silu(causal_conv1d) and autodiff's transpose of it: the forward and
    d_x, d_weight, d_bias, over a sequence of several blocks, so that the
    rows a block reads of the block before (forwards, x) and of the block
    after (backwards, g) are both exercised."""
    assert ssd._conv_blocks(seq, channels, cuts, first)[0] * 3 == seq
    args = conv_inputs(2, seq, channels, width, dtype, first, after)
    want, wanted = conv_and_grads(False, cuts, first, *args)
    got, grads = conv_and_grads(True, cuts, first, *args)
    assert got.shape == want.shape and got.dtype == want.dtype == dtype
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    ulp = 2.0 ** (jnp.floor(jnp.log2(jnp.maximum(
        jnp.abs(want), 2.0 ** -100))) - 7)
    if dtype == jnp.float32:
        assert float(jnp.abs(got - want).max()) < 1e-4 * float(
            jnp.abs(want).max())
    else:
        # the kernel rounds once, as XLA's fusion does on a TPU (PR 35:
        # it keeps the float32 sum through the SiLU; 0.1 % of the
        # elements differ on the chip), the CPU's jnp tier three times
        # (the sum, the sigmoid, the product): a unit of bfloat16's last
        # place from the first (the interpreter's approximate reciprocal
        # is a bfloat16 one, 1.5e-5 after Newton's step); from the second
        # as far as its rounded sum carries into a SiLU that falls off
        # exponentially, within the scan's tier tests' tolerance
        pre = ssd.causal_conv1d(*(a.astype(jnp.float32) for a in (
            args[0][..., first:first + channels], *args[1:3])))
        once = (pre * jax.nn.sigmoid(pre)).astype(dtype).astype(jnp.float32)
        assert bool((jnp.abs(got - once) <= ulp).all())
        assert float((got != once).mean()) < 0.01
        assert float(jnp.abs(got - want).max()) < 2e-2 * float(
            jnp.abs(want).max())
    # the tolerances of the scan's tier tests
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    for g, w in zip(grads, wanted):
        assert g.shape == w.shape and g.dtype == w.dtype
        g, w = g.astype(jnp.float32), w.astype(jnp.float32)
        assert float(jnp.abs(g - w).max()) < tol * float(jnp.abs(w).max())
    # x's lanes outside the convolution's get nought
    d_x = grads[0]
    assert not d_x[..., :first].any() and not d_x[..., first + channels:].any()


def test_the_convolution_keeps_its_input_alone(interpreted):
    """What differentiating the kernel tier keeps: x, the weights and
    the bias; no pre-activation, nothing padded, nothing in float32."""
    from jax._src.ad_checkpoint import saved_residuals

    x, weight, bias, _ = conv_inputs(1, 128, 256, 4, jnp.bfloat16, 128, 64)
    kept = [aval for aval, _ in saved_residuals(
        lambda *z: ssd._conv(*z, (128,), 128, True), x, weight, bias)]
    assert sorted((a.shape, a.dtype) for a in kept) == sorted(
        (a.shape, a.dtype) for a in (x, weight, bias))


# sequence, channels, taps, cuts of the cell, and the lane of the
# projection's output [4, 8192, 10304] the channels start at
CONV_CELL = dict(seq=8192, channels=6144, width=4, cuts=(4096, 5120),
                 first=4096)


@pytest.mark.parametrize("on,change,sharded,kernel", [
    (True, {}, False, True),                    # the cell, on a TPU
    (True, {"cuts": (), "first": 0}, False, True),  # uncut, alone
    (True, {"first": 4096 + 64}, False, False),  # a start off the lanes
    (False, {}, False, False),                  # off a TPU
    (True, {}, True, False),                    # a step partitioned over a mesh
    (True, {"channels": 6144 + 64}, False, False),  # channels off the lanes
    (True, {"cuts": (4096, 5120 + 64)}, False, False),  # a cut off the lanes
    (True, {"seq": 8192 + 32}, False, False),   # no whole blocks of rows
    (True, {"width": 10}, False, False),        # more than 8 rows back
    (True, {"seq": 192, "channels": 128, "cuts": (), "first": 128}, False,
     True),
])
def test_the_rule_that_picks_the_conv_tier(monkeypatch, on, change, sharded,
                                           kernel):
    """``conv_tier`` is all that decides, from the platform, the shapes
    and whether the step is partitioned; ``conv_silu`` takes its word and
    ``mamba_conv_calls`` says which tier was traced, by pass."""
    from ray_tpu.observability.metrics import mamba_conv_calls

    monkeypatch.setattr(attention, "kernels_on", lambda: on)
    shape = dict(CONV_CELL, **change)
    assert ssd.conv_tier(sharded=sharded, **shape) is kernel
    struct = jax.ShapeDtypeStruct
    seq, channels, cuts, first = (shape[k] for k in (
        "seq", "channels", "cuts", "first"))
    args = (struct((1, seq, first + channels + 64), jnp.bfloat16),
            struct((shape["width"], channels), jnp.bfloat16),
            struct((channels,), jnp.bfloat16))

    def conv(*z):
        return ssd.conv_silu(*z, cuts, first, sharded)

    def counted(traced):
        before = mamba_conv_calls.series()
        out = traced()
        after = mamba_conv_calls.series()
        return out, {k: after[k] - before.get(k, 0) for k in after
                     if after[k] != before.get(k, 0)}

    tier = "kernel" if kernel else "jnp"
    out, calls = counted(lambda: jax.eval_shape(conv, *args))
    assert calls == {(tier, "fwd"): 1}
    edges = (0, *cuts, channels)
    if cuts:
        assert [piece.shape for piece in out] == [
            (1, seq, hi - lo) for lo, hi in zip(edges, edges[1:])]
    else:
        assert out.shape == (1, seq, channels)
    grads, calls = counted(lambda: jax.eval_shape(jax.grad(lambda *z: sum(
        piece.astype(jnp.float32).sum()
        for piece in jax.tree.leaves(conv(*z))), argnums=(0, 1, 2)), *args))
    assert calls == {(tier, "fwd"): 1, (tier, "bwd"): 1}
    assert [g.shape for g in grads] == [a.shape for a in args]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_mamba_block_on_the_cpu_is_unchanged_to_the_bit(dtype):
    """Off a TPU ``conv_silu`` is silu(causal_conv1d) and ``jnp.split``
    as ``mamba_block`` wrote them before the kernels: the block and its
    gradients are the same numbers."""
    cfg = model_config()
    layer = jax.tree.map(lambda a: a.astype(dtype),
                         one_layer(seeded(TINY), "mamba"))
    x = jax.random.normal(jax.random.PRNGKey(5), (2, SEQ, 64)).astype(dtype)

    def as_it_was(proj, weight, bias, cuts, first, sharded):
        xbc = jnp.split(proj, [first, first + weight.shape[1]], axis=-1)[1]
        return jnp.split(jax.nn.silu(ssd.causal_conv1d(xbc, weight, bias)),
                         list(cuts), axis=-1)

    def block_and_grads():
        return jax.value_and_grad(
            lambda x, layer: tfm.mamba_block(x, layer, cfg).astype(
                jnp.float32).sum(), argnums=(0, 1))(x, layer)

    got = block_and_grads()
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(tfm, "conv_silu", as_it_was)
        want = block_and_grads()
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.dtype == w.dtype and bool((g == w).all())


# ---------------------------------------------------- the gate and the norm
def gate_norm_inputs(batch, seq, groups, width, dtype, after=0):
    """y, the gate as the first lanes of an array with ``after`` lanes of
    something else behind them, the norm's weight, a weight for the sum."""
    inner = groups * width
    k = jax.random.split(jax.random.PRNGKey(4), 4)
    return (jax.random.normal(k[0], (batch, seq, inner)).astype(dtype),
            jax.random.normal(k[1], (batch, seq, inner + after)).astype(dtype),
            1 + jax.random.normal(k[2], (inner,)) / 4,
            jax.random.normal(k[3], (batch, seq, inner)))


def gate_norm_and_grads(kernel, groups, y, z, weight, weigh):
    def normed(*a):
        return ssd._gate_norm(*a, groups, 1e-5, kernel)

    return normed(y, z, weight), jax.grad(
        lambda *a: (normed(*a) * weigh).sum(), argnums=(0, 1, 2))(
            y, z, weight)


# both cells' groups (8 of 512 lanes, two a block; 8 of 1024, one a block)
# over two blocks of rows, so that the weight's sums run over the blocks;
# the gate alone and, as in the cells, in front of the projection's other
# lanes; a group of three tiles, which no power of two divides
@pytest.mark.parametrize("seq,groups,width,after,dtype", [
    (256, 8, 512, 64, jnp.float32),
    (256, 8, 512, 64, jnp.bfloat16),
    (256, 8, 1024, 128, jnp.float32),
    (256, 8, 1024, 128, jnp.bfloat16),
    (128, 3, 384, 0, jnp.float32),
])
def test_the_gate_norm_kernels_are_the_jnp_form(seq, groups, width, after,
                                                dtype, interpreted):
    """``gated_group_norm``'s kernel tier under Pallas's interpreter
    against the jnp form and autodiff's transpose of it: the value and
    d_y, d_z, d_weight."""
    rows, lanes, _ = ssd._gate_norm_blocks(seq, groups * width, groups)
    assert lanes % width == 0 and seq % rows == 0
    args = gate_norm_inputs(2, seq, groups, width, dtype, after)
    want, wanted = gate_norm_and_grads(False, groups, *args)
    got, grads = gate_norm_and_grads(True, groups, *args)
    assert got.shape == want.shape and got.dtype == want.dtype == dtype
    # the tolerances of the scan's and the convolution's tier tests: in
    # bfloat16 the CPU's jnp form rounds the sigmoid's result where the
    # kernel (and XLA's fusion on a TPU) keeps float32 to the end
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    for g, w in zip((got, *grads), (want, *wanted)):
        assert g.shape == w.shape and g.dtype == w.dtype
        g, w = g.astype(jnp.float32), w.astype(jnp.float32)
        assert float(jnp.abs(g - w).max()) < tol * float(jnp.abs(w).max())
    if dtype == jnp.bfloat16:
        # one rounding: a unit of the last place from the float32 result
        y, z, weight = (a.astype(jnp.float32) for a in args[:3])
        once = ssd._jnp_gate_norm(y, z, weight, groups, 1e-5).astype(
            dtype).astype(jnp.float32)
        got = got.astype(jnp.float32)
        ulp = 2.0 ** (jnp.floor(jnp.log2(jnp.maximum(
            jnp.abs(once), 2.0 ** -100))) - 7)
        assert bool((jnp.abs(got - once) <= ulp).all())
        assert float((got != once).mean()) < 0.01
    # the lanes behind the gate get nought
    assert not grads[1][..., groups * width:].any()


def test_the_gate_norm_keeps_its_inputs_alone(interpreted):
    """What differentiating the kernel tier keeps: y, the array the gate
    lies in, the weight; nothing in float32 of [B, S, inner]."""
    from jax._src.ad_checkpoint import saved_residuals

    y, z, weight, _ = gate_norm_inputs(1, 128, 2, 128, jnp.bfloat16, 64)
    kept = [aval for aval, _ in saved_residuals(
        lambda *a: ssd._gate_norm(*a, 2, 1e-5, True), y, z, weight)]
    assert sorted((a.shape, a.dtype) for a in kept) == sorted(
        (a.shape, a.dtype) for a in (y, z, weight))


# sequence, lanes and groups of the cell nemotron_twotower_l9_train_s8192
GATE_NORM_CELL = dict(seq=8192, inner=4096, groups=8)


@pytest.mark.parametrize("on,change,sharded,kernel", [
    (True, {}, False, True),                    # the cell, on a TPU
    (True, {"inner": 8192}, False, True),       # the Super cell's groups
    (False, {}, False, False),                  # kernels off
    (True, {}, True, False),                    # a step partitioned over a mesh
    (True, {"inner": 4096 + 512}, False, False),  # a group off the tiles
    (True, {"groups": 3}, False, False),        # groups that do not divide
    (True, {"seq": 8192 + 64}, False, False),   # a sequence off the blocks
    (True, {"seq": 128, "inner": 256, "groups": 2}, False, True),
])
def test_the_rule_that_picks_the_gate_norm_tier(monkeypatch, on, change,
                                                sharded, kernel):
    """``gate_norm_tier`` is all that decides, from the platform, the
    shapes and whether the step is partitioned; ``gated_group_norm``
    takes its word and ``mamba_gate_norm_calls`` says which tier was
    traced, by pass."""
    from ray_tpu.observability.metrics import mamba_gate_norm_calls

    monkeypatch.setattr(attention, "kernels_on", lambda: on)
    shape = dict(GATE_NORM_CELL, **change)
    assert ssd.gate_norm_tier(sharded=sharded, **shape) is kernel
    seq, inner, groups = (shape[k] for k in ("seq", "inner", "groups"))
    if inner % groups:
        return
    struct = jax.ShapeDtypeStruct
    args = (struct((1, seq, inner), jnp.bfloat16),
            struct((1, seq, inner + 192), jnp.bfloat16),
            struct((inner,), jnp.float32))

    def normed(*a):
        return ssd.gated_group_norm(*a, groups, 1e-5, sharded)

    def counted(traced):
        before = mamba_gate_norm_calls.series()
        out = traced()
        after = mamba_gate_norm_calls.series()
        return out, {k: after[k] - before.get(k, 0) for k in after
                     if after[k] != before.get(k, 0)}

    tier = "kernel" if kernel else "jnp"
    out, calls = counted(lambda: jax.eval_shape(normed, *args))
    assert calls == {(tier, "fwd"): 1}
    assert out.shape == (1, seq, inner) and out.dtype == jnp.bfloat16
    grads, calls = counted(lambda: jax.eval_shape(jax.grad(
        lambda *a: normed(*a).astype(jnp.float32).sum(),
        argnums=(0, 1, 2)), *args))
    assert calls == {(tier, "fwd"): 1, (tier, "bwd"): 1}
    assert [(g.shape, g.dtype) for g in grads] == [
        (a.shape, a.dtype) for a in args]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_jnp_gate_norm_is_the_form_it_was_to_the_bit(dtype):
    """Off a TPU ``gated_group_norm`` is the function it was before the
    kernels, applied to the gate cut off the projection's output as
    ``mamba_block`` cut it: the block and its gradients are the same
    numbers."""
    cfg = model_config()
    layer = jax.tree.map(lambda a: a.astype(dtype),
                         one_layer(seeded(TINY), "mamba"))
    x = jax.random.normal(jax.random.PRNGKey(5), (2, SEQ, 64)).astype(dtype)

    def as_it_was(y, proj, weight, groups, eps, sharded):
        z = jnp.split(proj, [y.shape[-1]], axis=-1)[0]
        gated = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
        width = gated.shape[-1] // groups
        mean_squares = jnp.stack(
            [jnp.mean(jnp.square(gated[..., g * width:(g + 1) * width]),
                      axis=-1) for g in range(groups)], axis=-1)
        scale = jnp.repeat(lax.rsqrt(mean_squares + eps), width, axis=-1)
        return (gated * scale * weight.astype(jnp.float32)).astype(y.dtype)

    def block_and_grads():
        return jax.value_and_grad(
            lambda x, layer: tfm.mamba_block(x, layer, cfg).astype(
                jnp.float32).sum(), argnums=(0, 1))(x, layer)

    got = block_and_grads()
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(tfm, "gated_group_norm", as_it_was)
        want = block_and_grads()
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.dtype == w.dtype and bool((g == w).all())


# ------------------------------------------------------------ the layers
@pytest.mark.parametrize("kind", ["mamba", "moe", "attention"])
def test_a_layer_is_the_reference_layer(kind):
    cfg = model_config()
    params = seeded(TINY)
    dims = reference.Dims(TINY)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, SEQ, 64))
    w = one_layer(params, kind)
    got = {
        "mamba": lambda: tfm.mamba_block(x, w, cfg),
        "moe": lambda: tfm.moe_block(x, w, cfg)[0],
        "attention": lambda: tfm.attention_block(
            x, w, cfg, None, None,
            lambda q, k, v: tfm.flash_attention(q, k, v, True)),
    }[kind]()
    want = jnp.stack([reference.LAYER_ROW[kind](
        row, w, dims, reference.OPERANDS["float32"]) for row in x])
    assert float(jnp.abs(got - want).max()) < 2e-5 * float(
        jnp.abs(want).max())


def whole_moe(cfg_dict):
    """(model config, reference dims, weights) of one ``E`` layer that
    holds all 8 experts."""
    whole = dict(cfg_dict, n_routed_experts=8, experts_held_first=0)
    return model_config(whole), reference.Dims(whole), one_layer(
        seeded(whole), "moe")


def test_moe_with_every_expert_held_is_the_loop():
    cfg, dims, w = whole_moe(TINY)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, SEQ, 64))
    got, drawn = tfm.moe_block(x, w, cfg)
    want = jnp.stack([reference.moe_row(row, w, dims, reference.OPERANDS["float32"])
                      for row in x])
    assert float(jnp.abs(got - want).max()) < 2e-5 * float(
        jnp.abs(want).max())
    # every (token, choice) pair is held, none is over; the reference
    # counts the same draws
    report = tfm.routing_report(drawn[None], cfg.stack, 2 * SEQ)
    assert int(report["moe_rows_held"]) == 2 * SEQ * 2
    assert int(report["moe_rows_over"]) == 0
    assert [int(d) for d in drawn] == [int(d) for d in sum(
        reference.drawn_row(row, w, dims) for row in x)]


def test_the_shares_add_up():
    """The parts that every share of the experts gives, with the shared
    expert (which every chip computes alike) counted once, sum to the
    uncut layer's output."""
    cfg, _, w = whole_moe(TINY)
    x = jax.random.normal(jax.random.PRNGKey(7), (2 * SEQ, 64))
    whole, drawn = tfm.routed_experts(x, w, cfg.stack)
    parts, held = [], 0
    for first in (0, 2, 4, 6):
        share = dataclasses.replace(cfg.stack, experts_held=(first, 2))
        w_share = dict(w, w_up=w["w_up"][first:first + 2],
                       w_down=w["w_down"][first:first + 2])
        part, part_drawn = tfm.routed_experts(x, w_share, share)
        parts.append(part)
        report = tfm.routing_report(part_drawn[None], share, 2 * SEQ)
        held += int(report["moe_rows_held"])
        assert int(report["moe_rows_over"]) == 0
        assert (part_drawn == drawn).all()
    assert held == int(drawn.sum()) == 2 * SEQ * 2
    assert float(jnp.abs(sum(parts) - whole).max()) < 1e-5 * float(
        jnp.abs(whole).max())
    # and through the layer: shares of the layer, less the three extra
    # copies of what is not routed, is the uncut layer
    xb = x.reshape(2, SEQ, 64)
    shared = tfm.relu2_mlp(tfm.rms_norm(xb, w["norm"], 1e-5),
                           w["shared_up"], w["shared_down"])
    layers = sum(
        tfm.moe_block(xb, dict(w, w_up=w["w_up"][f:f + 2],
                               w_down=w["w_down"][f:f + 2]),
                      model_config(dict(TINY, n_routed_experts=2,
                                        experts_held_first=f)))[0]
        for f in (0, 2, 4, 6))
    uncut = tfm.moe_block(xb, w, cfg)[0]
    assert float(jnp.abs(layers - 3 * (xb + shared) - uncut).max()) < 1e-4


def test_rows_beyond_the_buffer_are_counted_not_dropped_unseen():
    """A correction bias that sends every token to the two experts of one
    share: the share's buffer, twice its even draw, holds half of them."""
    cfg, _, w = whole_moe(TINY)
    x = jax.random.normal(jax.random.PRNGKey(8), (2 * SEQ, 64))
    share = dataclasses.replace(cfg.stack, experts_held=(2, 2))
    assert share.row_buffer(2 * SEQ) == 2 * SEQ < cfg.stack.row_buffer(
        2 * SEQ) == 2 * SEQ * 2
    w = dict(w, w_up=w["w_up"][2:4], w_down=w["w_down"][2:4],
             router_bias=jnp.zeros(8).at[2:4].set(10.0))
    out, drawn = tfm.routed_experts(x, w, share)
    report = tfm.routing_report(drawn[None], share, 2 * SEQ)
    assert int(report["moe_rows_held"]) == 2 * SEQ * 2
    assert int(report["moe_rows_over"]) == 2 * SEQ
    assert int(report["moe_rows_max_expert"]) == 2 * SEQ
    assert bool(jnp.isfinite(out).all())


def test_the_correction_bias_moves_towards_an_even_draw():
    """``router_bias_step``: nought for an expert that drew its even
    share, the rate for one that drew nothing, and negative by the
    excess for one that drew too much; the step adds it to the bias and
    the draws that follow are more even."""
    st = dataclasses.replace(model_config().stack, bias_rate=0.1)
    even = 2 * SEQ * 2 / 8
    drawn = jnp.array([[even, 0, 3 * even, even, even, even, even, 0]],
                      jnp.int32)
    step = tfm.routing_report(drawn, st, 2 * SEQ)["router_bias_step"]
    assert np.allclose(step[0], [0, 0.1, -0.2, 0, 0, 0, 0, 0.1])
    cfg, _, w = whole_moe(TINY)
    x = jax.random.normal(jax.random.PRNGKey(8), (16 * SEQ, 64))
    spread = []
    for _ in range(8):
        _, drawn = tfm.routed_experts(x, w, cfg.stack)
        spread.append(int(drawn.max() - drawn.min()))
        w = dict(w, router_bias=w["router_bias"] + tfm.routing_report(
            drawn[None], cfg.stack, 16 * SEQ)["router_bias_step"][0])
    assert max(spread[-3:]) < spread[0] / 2, spread
    params = {"layers": {"moe": {"router_bias": jnp.zeros((1, 8))}}}
    moved = tfm.add_router_bias(params, step)
    assert np.allclose(moved["layers"]["moe"]["router_bias"], step)
    assert float(params["layers"]["moe"]["router_bias"].sum()) == 0


# ------------------------------------------------------------ the model
def test_the_step_follows_the_reference_for_two_steps():
    """Loss, first gradient and the two-step change of the whole model,
    through ``build_train_step``, against the plain reference."""
    from benchmark.drivers import hybrid_train_steps as driver

    cfg = model_config()
    mesh = build_mesh(MeshSpec(), jax.devices()[:1])
    optimizer = make_optimizer(carry=True, **{
        k: HP[k] for k in ("learning_rate", "weight_decay", "b1", "b2",
                           "grad_clip", "warmup_steps")})
    step, _ = build_train_step(cfg, mesh, optimizer=optimizer)
    start = seeded(TINY, seed=3)
    batches = [weights_hybrid.token_batch(3, i, 2, SEQ, 256) for i in (0, 1)]
    params, opt_state = start, optimizer.init(start)
    params, opt_state, m1 = step(params, opt_state, batches[0])
    first = driver.tree_norms(driver.adam_state(opt_state).mu)
    params, opt_state, m2 = step(params, opt_state, batches[1])
    unclip = max(1.0, float(m1["grad_norm"])) / (1 - HP["b1"])
    program = {
        "loss": [float(m1["loss"]), float(m2["loss"])],
        "first_grad": {k: np.asarray(v) * unclip for k, v in first.items()},
        "change": {k: np.asarray(v) for k, v in driver.tree_norms(
            jax.tree.map(jnp.subtract, carried_params(params, opt_state),
                         seeded(TINY, seed=3))).items()}}
    kinds = weights_hybrid.kinds_of(TINY)
    key = weights_hybrid.seed_key(3)
    ref = reference.follow_two_steps(
        TINY, HP, lambda name, layer: weights_hybrid.make_leaf(
            TINY, key, None if layer is None else kinds[layer], name,
            layer).astype(jnp.float32), batches)
    numbers = compare.training_numbers(program, ref)
    assert numbers["loss_gap"] < 1e-5, numbers
    assert numbers["first_grad_gap"] < 1e-3, numbers
    assert numbers["grad_share_gap"] < 1e-3, numbers
    assert numbers["change_gap"] < 1e-2, numbers
    assert set(compare.flat(program["first_grad"])) == set(
        compare.flat(ref["first_grad"]))
    assert publish_moe_rows(m2)["moe_rows_over"] == 0


def test_the_warm_up_takes_its_share_of_the_rate():
    """A fresh AdamW moves a weight by the rate whatever its gradient:
    step t of a warm-up of 4 by t / 4 of it."""
    import optax

    optimizer = make_optimizer(learning_rate=1e-2, weight_decay=0.0,
                               warmup_steps=4, carry=True)
    params = {"w": jnp.ones((3,), jnp.float32)}
    state = optimizer.init(params)
    steps = []
    for _ in range(6):
        updates, state = optimizer.update({"w": jnp.ones((3,))}, state,
                                          params)
        moved = optax.apply_updates(params, updates)
        steps.append(float(params["w"][0] - moved["w"][0]))
        params = moved
    assert np.allclose(steps, [0.0025, 0.005, 0.0075, 0.01, 0.01, 0.01],
                       rtol=1e-3)


def test_steps_under_a_parameters_spacing_add_up_in_its_carry():
    """300 steps of 1e-6 on a bfloat16 weight near 0.02, whose spacing
    is 1.2e-4: alone it never moves; with its carry it follows the
    float32 sum to a few parts in a hundred of the way gone (the carry
    is bfloat16 too), and the weight itself is that sum rounded."""
    import optax

    start = jnp.full((4,), 0.02, jnp.bfloat16)
    step = {"w": jnp.full((4,), -1e-6, jnp.float32)}
    bare = optax.apply_updates({"w": start}, step)["w"]
    assert (bare == start).all()
    carry = carry_rounding()
    params, state = {"w": start}, carry.init({"w": start})
    for _ in range(300):
        moved, state = carry.update(step, state, params)
        params = optax.apply_updates(params, moved)
    stands = carried_params(params, state)["w"]
    want = start.astype(jnp.float32) - 3e-4
    assert float(jnp.abs(stands - want).max()) < 0.05 * 3e-4
    assert (params["w"] == stands.astype(jnp.bfloat16)).all()
    assert (params["w"] != start).all()
    # a float32 parameter passes through, and carries nothing
    wide = {"w": jnp.ones((2,), jnp.float32)}
    moved, state = carry.update({"w": jnp.full((2,), 1e-3)}, carry.init(wide),
                                wide)
    assert (moved["w"] == 1e-3).all() and not state.lost["w"].any()
    assert (carried_params(wide, ())["w"] == 1).all()


def test_the_step_counts_its_rows_and_publishes_them():
    from ray_tpu.observability.metrics import moe_rows

    cfg = model_config()
    mesh = build_mesh(MeshSpec(), jax.devices()[:1])
    step, init_fn = build_train_step(cfg, mesh)
    params, opt_state = init_fn(jax.random.PRNGKey(0))
    tokens = weights_hybrid.token_batch(1, 0, 2, SEQ, 256)
    _, _, metrics = step(params, opt_state, tokens)
    assert set(tfm.MOE_ROWS) <= set(metrics)
    assert "router_bias_step" not in metrics
    before = dict(moe_rows.series())
    counted = publish_moe_rows(metrics)
    # 4 E layers, 64 tokens, top-2 of 8 with 3 held: 192 expected
    assert 100 < counted["moe_rows_held"] < 300
    assert counted["moe_rows_max_expert"] <= counted["moe_rows_held"]
    after = moe_rows.series()
    assert after[("held",)] - before.get(("held",), 0) == \
        counted["moe_rows_held"]
    # a buffer under a tile: the plain tier, 4 E layers' buffers whole
    assert int(metrics["moe_rows_moved"]) == 4 * cfg.stack.row_buffer(
        2 * SEQ) == after[("moved",)] - before.get(("moved",), 0)
    assert publish_moe_rows({"loss": 1.0}) == {}


def test_the_step_says_what_rows_its_layers_moved(monkeypatch):
    """``moe_rows_moved``: the buffers' rows that dispatch and combine
    touched, every buffer whole on the plain tier, whole passes up to the
    rows held where the movement is trimmed; ``publish_moe_rows`` adds
    it to ``moe_rows{where="moved"}`` and still hands the drivers the
    names of ``MOE_ROWS`` and no other (they add up what they are handed
    under those three)."""
    from ray_tpu.observability.metrics import moe_rows
    from ray_tpu.ops import grouped

    cfg = model_config()
    # 4096 tokens, top-2 of 8 with 4 held: 4096 rows expected, a buffer
    # of 8192; three layers draw about half, nothing, and more than it
    st = dataclasses.replace(cfg.stack, experts_held=(2, 4))
    rows = st.row_buffer(4096)
    assert rows == 8192 == 16 * grouped.TILE_M
    drawn = jnp.zeros((3, 8), jnp.int32).at[0, 2:6].set(
        jnp.array([1000, 1100, 900, 1001])).at[2, 2:6].set(2500)
    monkeypatch.setattr(attention, "kernels_on", lambda: True)
    block = grouped.movement_block(rows, 4096)
    assert block == grouped.MOVE_ROWS == 8 * grouped.TILE_M
    report = tfm.routing_report(drawn, st, 4096)
    moved = int(report["moe_rows_moved"])
    assert moved == 4096 + 0 + 8192 and moved % grouped.TILE_M == 0
    held_within = int(report["moe_rows_held"] - report["moe_rows_over"])
    assert held_within == 4001 + 8192 <= moved < 3 * rows
    before = moe_rows.series().get(("moved",), 0)
    counted = publish_moe_rows(report)
    assert tuple(counted) == tfm.MOE_ROWS
    assert moe_rows.series()[("moved",)] - before == moved
    monkeypatch.setattr(attention, "kernels_on", lambda: False)
    assert int(tfm.routing_report(drawn, st, 4096)["moe_rows_moved"]
               ) == 3 * rows


@pytest.mark.parametrize("on,rows,tokens,block", [
    (True, 49152, 32768, 4096), (True, 65536, 16384, 4096),  # the cells'
    (True, 1536, 1024, 1536), (True, 5120, 512, 2560),  # tiles that divide
    (True, 520, 1024, 0),       # a row buffer that is not whole tiles
    (True, 1024, 520, 0),       # tokens that are not whole blocks
    (False, 65536, 16384, 0),   # off a TPU
])
def test_the_rule_that_trims_the_movement(monkeypatch, on, rows, tokens,
                                          block):
    """``movement_block`` asks ops.attention's public predicate and the
    two lengths, and nothing else: a TPU with a buffer of whole tiles
    and tokens of whole blocks moves the rows that hold a pair, anything
    else moves the whole buffer."""
    from ray_tpu.ops import grouped

    assert not [name for name in grouped.movement_block.__code__.co_names
                if name.startswith("_")]
    monkeypatch.setattr(attention, "kernels_on", lambda: on)
    assert grouped.movement_block(rows, tokens) == block
    count = jnp.array([0, 1, rows // 2, rows], jnp.int32)
    passes = -(-count // block) * block if block else jnp.full(4, rows)
    assert np.array_equal(
        np.asarray(grouped.rows_moved(count, rows, tokens)),
        np.asarray(passes))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("share", [0.0, 0.02, 0.45, 0.8, 1.0])
def test_rows_move_as_far_as_they_were_drawn(monkeypatch, share, dtype):
    """The two movements and their transposes, each the other one: the
    trimmed tier's (a loop of gathers towards the buffer, the kernel
    ``rows_added`` towards the tokens, under the interpreter here) are
    the plain tier's over the rows that hold a pair, and what lies
    beyond them (NaN here) reaches nothing. ``share`` of the pairs
    choose one of 3 held experts, unevenly; the buffer holds 2048 of the
    3072 there are."""
    from ray_tpu.ops import grouped

    tokens, k, held, rows, h = 1024, 3, 3, 2048, 128
    keys = jax.random.split(jax.random.PRNGKey(int(share * 100)), 6)
    u = jax.random.uniform(keys[0], (tokens, k))
    # a token chooses an expert once: choice j takes expert j or none
    local = jnp.where(u < share * jnp.array([1.0, 0.6, 0.9]),
                      jnp.arange(k), held).reshape(-1)
    order = jnp.argsort(local, stable=True)[:rows]
    count = jnp.minimum((local < held).sum(), rows)
    xt = jax.random.normal(keys[1], (tokens, h), dtype)
    weights = jax.random.normal(keys[2], (rows,))
    through = jax.random.normal(keys[3], (rows, h))
    dout = jax.random.normal(keys[4], (tokens, h))
    live = (jnp.arange(rows) < count)[:, None]
    monkeypatch.setattr(grouped, "MOVE_ROWS", grouped.TILE_M)

    def both(on):
        monkeypatch.setattr(attention, "_FORCE_INTERPRET", on)
        where = grouped.places(local, order, count, held, k)
        assert (where.spans is not None) == on

        def there_and_back(xt, weights):
            rows_in, again = grouped.rows_from_tokens(xt, where, readers=2)
            # products that leave NaN beyond the rows they were given
            rows_out = jnp.where(
                live, (0.25 * rows_in + 0.75 * again).astype(jnp.float32)
                * through, jnp.nan)
            out = grouped.tokens_from_rows(rows_out, weights, where,
                                           tokens, dtype)
            return jnp.sum(out.astype(jnp.float32) * dout), (rows_in, out)

        return jax.value_and_grad(there_and_back, (0, 1), has_aux=True)(
            xt, weights)

    (_, (rows_in, out)), (d_xt, d_weights) = both(True)
    (_, (want_in, want_out)), (want_xt, want_weights) = both(False)
    assert np.array_equal(np.asarray(rows_in, np.float32),
                          np.asarray(want_in, np.float32))
    assert not np.asarray(rows_in[int(count):], np.float32).any()
    assert out.dtype == d_xt.dtype == dtype
    # float32 sums rounded once: the plain tier's bfloat16 transpose of
    # the dispatch rounds after every addition
    close = dict(atol=1e-5, rtol=1e-5) if dtype == jnp.float32 else dict(
        atol=0.05, rtol=0.02)
    for got, want in ((out, want_out), (d_xt, want_xt),
                      (d_weights, want_weights)):
        assert bool(jnp.isfinite(got).all())
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), **close)
    assert not np.asarray(d_weights[int(count):]).any()
    assert (float(jnp.abs(d_xt.astype(jnp.float32)).max()) > 0) == bool(
        int(count))


def test_a_mesh_that_would_spread_the_experts_is_refused():
    cfg = model_config()
    assert len(jax.devices()) >= 4
    mesh = build_mesh(MeshSpec(dp=2, tp=2), jax.devices()[:4])
    shardings = param_shardings(cfg, mesh)
    spec = lambda kind, leaf: shardings["layers"][kind][leaf].spec  # noqa: E731
    assert spec("moe", "w_up") == jax.sharding.PartitionSpec(
        "pp", "dp", None, None)
    assert spec("mamba", "w_in")[-1] == "tp" == spec("moe", "shared_up")[-1]
    assert spec("attention", "wq")[-1] == "tp"
    with pytest.raises(NotImplementedError, match="all-to-all"):
        build_train_step(cfg, mesh)
    with pytest.raises(NotImplementedError, match="all-to-all"):
        build_train_step(cfg, build_mesh(MeshSpec(), jax.devices()[:1]),
                         fsdp=True)
    # a stack without expert layers has nothing to exchange
    plain = model_config(dict(TINY, hybrid_override_pattern="M*"))
    build_train_step(plain, mesh)
    # the pipeline path hands a stage whole dense layers: no pattern, with
    # experts or without
    pipe = build_mesh(MeshSpec(pp=2), jax.devices()[:2])
    for cfg in (model_config(), plain):
        with pytest.raises(NotImplementedError, match="uniform dense stack"):
            build_pipeline_train_step(cfg, pipe)


@pytest.mark.parametrize("on,rows,takes_gmm", [
    (True, 512, True), (True, 1024, True),
    (True, 520, False),     # a row buffer that is not whole tiles
    (False, 512, False),    # off a TPU
])
def test_the_grouped_product_takes_the_kernel_where_kernels_run(
        monkeypatch, on, rows, takes_gmm):
    """``grouped_matmul`` asks ops.attention's public predicate, and
    nothing else of it, whether the megablox kernels run."""
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

    from ray_tpu.ops import attention, grouped

    assert not [name for name in grouped.grouped_matmul.__code__.co_names
                if name.startswith("_")]
    took = []

    def gmm(lhs, rhs, sizes, **kw):
        took.append(kw["tiling"])
        return lax.ragged_dot(lhs, rhs, sizes)

    monkeypatch.setattr(megablox, "gmm", gmm)
    monkeypatch.setattr(attention, "kernels_on", lambda: on)
    lhs = jax.random.normal(jax.random.PRNGKey(0), (rows, 16))
    rhs = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 8))
    sizes = jnp.array([rows // 4, rows // 2], jnp.int32)
    got = grouped.grouped_matmul(lhs, rhs, sizes)
    assert took == ([grouped.TILING] if takes_gmm else [])
    held = rows // 4 + rows // 2
    want = jnp.concatenate([lhs[:rows // 4] @ rhs[0],
                            lhs[rows // 4:held] @ rhs[1]])
    np.testing.assert_allclose(np.asarray(got[:held]), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def remat_policies(jaxpr, found=None):
    """The ``policy`` of every ``jax.checkpoint`` in a jaxpr, nested ones
    included."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("checkpoint", "remat2"):
            found.append(eqn.params["policy"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            remat_policies(sub, found)
    return found


@pytest.mark.parametrize("policy", ["full", "dots"])
@pytest.mark.parametrize("stack", ["uniform", "pattern", "pipeline"])
def test_every_stack_applies_the_remat_policy(stack, policy):
    """One wrapper (``tfm.remat``) reads ``remat_policy`` for the uniform
    stack, each kind of a pattern and the pipeline's stages: the layers'
    checkpoints of the traced step carry the policy, or none."""
    if stack == "pattern":
        cfg = dataclasses.replace(model_config(), remat_policy=policy)
        layers = len(cfg.stack.period)
    else:
        cfg = tfm.ModelConfig.debug(remat_policy=policy)
        layers = 1      # one scanned block
    if stack == "pipeline":
        step, init_fn = build_pipeline_train_step(
            cfg, build_mesh(MeshSpec(pp=2), jax.devices()[:2]))
    else:
        step, init_fn = build_train_step(
            cfg, build_mesh(MeshSpec(), jax.devices()[:1]))
    state = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((2, SEQ + 1), jnp.int32)
    found = remat_policies(jax.make_jaxpr(step)(*state, tokens).jaxpr)
    dots = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    assert found.count(dots) == (layers if policy == "dots" else 0)
    # whatever else is checkpointed (the ssd's chunks, the loss's) keeps
    # everything it recomputes from, under either policy
    assert set(found) <= {None, dots} and len(found) >= layers


# ------------------------------------------------------------ the dense stack
@pytest.mark.parametrize("preset, params_sha, loss_bits", [
    # read on the parent commit (PR 25) with the same two lines
    ("debug", "06878f3d95580f42", "a719b240"),
])
def test_the_dense_stack_is_unchanged_to_the_bit(preset, params_sha,
                                                 loss_bits):
    with jax.default_matmul_precision("default"):
        cfg = getattr(tfm.ModelConfig, preset)()
        assert cfg.stack == tfm.Stack() and cfg.rotary
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        digest = hashlib.sha256()
        for leaf in jax.tree.leaves(params):
            digest.update(np.asarray(leaf).tobytes())
        assert digest.hexdigest()[:16] == params_sha
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0,
                                    cfg.vocab_size)
        loss = jax.jit(lambda p, t: tfm.loss_fn(p, t, cfg))(params, tokens)
        assert np.asarray(loss).tobytes().hex() == loss_bits
        both = jax.jit(lambda p, t: tfm.loss_and_rows(p, t, cfg))(
            params, tokens)
        assert both[1] == {} and float(both[0]) == float(loss)
