"""Sliding-window attention (ops/attention.py, ``window``): the three
Pallas kernels under the interpreter and the blockwise tier against the
O(S^2) reference with the band mask, the block plans' counts, the
counters, the kernels' names, YaRN's rotary table."""

import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import attention as A
from ray_tpu.ops.attention import attention_reference, flash_attention
from ray_tpu.ops.layers import rope_frequencies, yarn_frequencies

SEQ, BLOCK, D = 512, 128, 128
# smaller than a block, a whole number of blocks, not a multiple of one,
# one key (the query's own), one short of the sequence
WINDOWS = (50, 256, 200, 1, SEQ - 1)
ACCEPTED = re.compile("flash_(fwd|bwd_dq|bwd_dkdv)")


def _qkv(seq=SEQ, d=D, heads=2, seed=5):
    keys = jax.random.split(jax.random.PRNGKey(seed + seq), 4)
    shape = (1, seq, heads, d)
    return tuple(jax.random.normal(k, shape) for k in keys)


@pytest.fixture
def tier(request, monkeypatch):
    """``kernels``: the Pallas kernels under the interpreter, 128-wide
    blocks so that 512 positions make a band of several; ``blockwise``:
    the jnp tier the CPU takes by itself."""
    if request.param == "kernels":
        monkeypatch.setattr(A, "_FORCE_INTERPRET", True)
        return BLOCK, BLOCK
    return None, None


def _loss(attn, dout):
    return lambda q, k, v: jnp.sum(attn(q, k, v) * dout)


@pytest.mark.parametrize("tier", ["kernels", "blockwise"], indirect=True)
@pytest.mark.parametrize("window", WINDOWS)
def test_windowed_attention_matches_the_band_reference(tier, window):
    """Forward and all three gradients through ``flash_attention``."""
    q, k, v, dout = _qkv()
    out = flash_attention(q, k, v, True, None, *tier, window)
    want = attention_reference(q, k, v, True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    got = jax.grad(_loss(lambda q, k, v: flash_attention(
        q, k, v, True, None, *tier, window), dout), (0, 1, 2))(q, k, v)
    ref = jax.grad(_loss(lambda q, k, v: attention_reference(
        q, k, v, True, window=window), dout), (0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", got, ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4,
                                   rtol=2e-4, err_msg=f"d{name}")
    # the band really is narrower than the causal call
    assert not np.allclose(np.asarray(out), np.asarray(
        attention_reference(q, k, v, True)), atol=1e-3) or window >= SEQ - 1


@pytest.mark.parametrize("tier", ["kernels", "blockwise"], indirect=True)
@pytest.mark.parametrize("window", [SEQ, SEQ + 1, 10 * SEQ])
def test_a_window_of_the_whole_sequence_is_the_causal_call(tier, window):
    """Bit for bit, in value and gradients, and the same program: the
    kernels and names the causal call has."""
    q, k, v, dout = _qkv()

    def both(window):
        attn = lambda q, k, v: flash_attention(  # noqa: E731
            q, k, v, True, None, *tier, window)
        return attn(q, k, v), jax.grad(_loss(attn, dout), (0, 1, 2))(q, k, v)

    for a, b in zip(jax.tree.leaves(both(window)),
                    jax.tree.leaves(both(None))):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    text = [str(jax.make_jaxpr(jax.grad(_loss(
        lambda q, k, v: flash_attention(q, k, v, True, None, *tier, w),
        dout), (0, 1, 2)))(q, k, v)) for w in (window, None)]
    assert text[0] == text[1] and "swa_" not in text[0]


def test_windowed_kernels_carry_names_of_their_own(monkeypatch):
    """``benchmark/trace.py::matching`` searches names with re.search and
    the accepted flash rooflines reckon every match as causal over the
    whole sequence: no windowed kernel's name may match."""
    monkeypatch.setattr(A, "_FORCE_INTERPRET", True)
    q, k, v, dout = _qkv()

    def names(window):
        text = str(jax.make_jaxpr(jax.grad(_loss(
            lambda q, k, v: flash_attention(q, k, v, True, None, BLOCK,
                                            BLOCK, window), dout),
            (0, 1, 2)))(q, k, v))
        return set(re.findall(r"\b((?:swa|flash)_\w+)", text))

    assert names(200) == {"swa_fwd", "swa_bwd_dq", "swa_bwd_dkdv"}
    assert not any(ACCEPTED.search(n) for n in names(200))
    assert names(None) == {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkdv"}


def test_a_window_needs_a_causal_call():
    q, k, v, _ = _qkv(128)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, False, None, None, None, 16)
    with pytest.raises(ValueError, match="the key itself"):
        flash_attention(q, k, v, True, None, None, None, 0)


# ------------------------------------------------------------------ the plans
def test_the_band_at_8192_visits_45_of_136_sub_blocks():
    """ISSUE 30's count: S 8192, W 1024, 512-wide sub-blocks."""
    causal = A.fwd_block_plan(8192, 8192, 128, True)
    assert (causal.unmasked, causal.masked, causal.edge) == (120, 16, 0)
    for plan in (A.fwd_block_plan(8192, 8192, 128, True, window=1024),
                 A.bwd_block_plan(8192, 8192, 128, True, window=1024)):
        assert (plan.unmasked, plan.masked, plan.edge) == (15, 16, 14)
        assert plan.window == 1024 and plan.block_q == plan.block_k == 512
    # K/V of a head are fetched four major blocks' worth, not sixteen
    windowed = A.fwd_block_plan(8192, 8192, 128, True, window=1024)
    assert causal.kv_bytes == 16 * 2 * 4096 * 128 * 2
    assert windowed.kv_bytes == 4 * 2 * 4096 * 128 * 2


def _classes(sq, sk, bq, bk, window):
    """Every sub-block of the [sq, sk] logits by looking at each of its
    pairs: never run, unmasked, on the diagonal, on the lower edge alone."""
    rows, cols = np.arange(sq)[:, None], np.arange(sk)[None, :]
    causal = rows >= cols
    seen = causal & (rows - cols < window)
    counts = {"none": 0, "diagonal": 0, "band_edge": 0}
    for r in range(0, sq, bq):
        for c in range(0, sk, bk):
            block = np.s_[r:r + bq, c:c + bk]
            if not seen[block].any():
                continue
            if not causal[block].all():
                counts["diagonal"] += 1
            elif not seen[block].all():
                counts["band_edge"] += 1
            else:
                counts["none"] += 1
    return counts["none"], counts["diagonal"], counts["band_edge"]


@pytest.mark.parametrize("sq,sk,bq,bk,window", [
    (1024, 1024, 128, 128, 256), (1024, 1024, 128, 128, 200),
    (1024, 1024, 128, 128, 50), (1024, 1024, 256, 128, 300),
    (1024, 1024, 128, 256, 129), (512, 512, 128, 128, 511),
    (1024, 1024, 128, 128, 1), (2048, 2048, 512, 512, 1024),
])
def test_the_plans_count_what_the_mask_says(sq, sk, bq, bk, window):
    assert A._mask_counts(sq, sk, bq, bk, True, window) == _classes(
        sq, sk, bq, bk, window)
    # the k-block walk of dk/dv visits the same sub-blocks
    visited = 0
    for ki in range(sk // bk):
        first, _, _, upto = A._band_of_k_block(ki, bq, bk, window)
        visited += max(0, min(upto, sq // bq) - first)
    assert visited == sum(_classes(sq, sk, bq, bk, window))


def test_the_counters_gain_the_bands_edge(monkeypatch):
    """A head's sub-blocks at 8192 / 1024 / 512 read 15 / 16 / 14 for
    none / diagonal / band_edge, forward and both backward kernels: only
    visited blocks are counted."""
    from ray_tpu.observability.metrics import (
        flash_bwd_subblocks,
        flash_fwd_subblocks,
    )

    monkeypatch.setattr(A, "_FORCE_INTERPRET", True)
    shape = jax.ShapeDtypeStruct((1, 8192, 1, 128), jnp.bfloat16)
    masks = ("none", "diagonal", "band_edge")

    def counted(window):
        before = flash_fwd_subblocks.series(), flash_bwd_subblocks.series()
        jax.eval_shape(jax.grad(lambda q, k, v: flash_attention(
            q, k, v, True, None, None, None, window).sum().astype(
                jnp.float32), (0, 1, 2)), shape, shape, shape)
        after = flash_fwd_subblocks.series(), flash_bwd_subblocks.series()
        fwd = tuple(after[0].get((m,), 0) - before[0].get((m,), 0)
                    for m in masks)
        bwd = {kernel: tuple(
            after[1].get((kernel, m), 0) - before[1].get((kernel, m), 0)
            for m in masks) for kernel in ("dq", "dkdv")}
        return fwd, bwd

    fwd, bwd = counted(1024)
    assert fwd == (15, 16, 14) and bwd == {"dq": (15, 16, 14),
                                           "dkdv": (15, 16, 14)}
    fwd, bwd = counted(None)
    assert fwd == (120, 16, 0) and bwd["dq"] == bwd["dkdv"] == (120, 16, 0)


@pytest.mark.parametrize("window", [300, 100, 640])
def test_several_major_blocks_under_a_window(monkeypatch, window):
    """A resident side of two sub-blocks (four major blocks a head): the
    index maps clamp the blocks outside the band from both sides, and the
    loops' bounds are counted from each major block's start."""
    monkeypatch.setattr(A, "_FORCE_INTERPRET", True)
    seq, budget = 1024, 2 * (2 * 2 * BLOCK * D * 4)
    q, k, v, dout = _qkv(seq)
    scale = D ** -0.5
    plan = A.fwd_block_plan(seq, seq, D, True, 4, BLOCK, BLOCK, budget,
                            window=window)
    assert plan.block_k_major == 2 * BLOCK and plan.window == window
    out, lse = A._pallas_fwd(q, k, v, True, scale, plan)
    want_out, want_lse = A._blockwise_fwd(q, k, v, True, scale, BLOCK, window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want_out),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want_lse),
                               atol=2e-5, rtol=2e-5)
    back = A.bwd_block_plan(seq, seq, D, True, 4, BLOCK, BLOCK, budget,
                            window=window)
    assert back.block_k_major == back.block_q_major == 2 * BLOCK
    got = A._pallas_bwd(q, k, v, out, lse, dout, True, scale, back)
    want = A._blockwise_bwd(q, k, v, want_out, want_lse, dout, True, scale,
                            BLOCK, window)
    for name, a, b in zip("qkv", got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4,
                                   rtol=2e-4, err_msg=f"d{name}")


def test_the_mesh_wrapper_passes_the_window_through(monkeypatch):
    """``flash_attention_on_mesh``: the kernels per shard in shard_maps,
    a ``W`` layer's window by keyword."""
    from jax.sharding import PartitionSpec as P

    from ray_tpu.parallel.mesh import MeshSpec, build_mesh

    monkeypatch.setattr(A, "_FORCE_INTERPRET", True)
    mesh = build_mesh(MeshSpec(dp=2, tp=2))
    attn = A.flash_attention_on_mesh(P("dp", None, "tp", None), mesh)
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    q, k, v, dout = (jax.random.normal(key, (2, 256, 2, D)) for key in keys)
    with mesh:
        got = jax.jit(jax.value_and_grad(_loss(
            lambda q, k, v: attn(q, k, v, window=100), dout),
            (0, 1, 2)))(q, k, v)
    want = jax.value_and_grad(_loss(lambda q, k, v: attention_reference(
        q, k, v, True, window=100), dout), (0, 1, 2))(q, k, v)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4,
                                   rtol=2e-4)


# ----------------------------------------------------------------- YaRN
MELLUM_FULL = dict(theta=500000.0, factor=16.0, original_max_seq=8192,
                   beta_fast=32.0, beta_slow=1.0,
                   attention_factor=1.2772588722239782)


def test_yarn_table_against_the_closed_form():
    """d 128, Mellum 2's full-attention section, pair by pair."""
    d, seq = 128, 64
    cos, sin = yarn_frequencies(d, seq, **MELLUM_FULL)
    theta, factor, length = 500000.0, 16.0, 8192
    low = math.floor(d * math.log(length / (32 * 2 * math.pi))
                     / (2 * math.log(theta)))
    high = math.ceil(d * math.log(length / (1 * 2 * math.pi))
                     / (2 * math.log(theta)))
    assert (low, high) == (18, 35)
    inv = np.array([theta ** (-2 * i / d) for i in range(d // 2)])
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0, 1)
    stretched = inv * (1 - ramp) + inv / factor * ramp
    # the fastest pairs as they were, the slowest 16 times slower
    assert np.all(stretched[:19] == inv[:19])
    np.testing.assert_allclose(stretched[35:], inv[35:] / 16, rtol=1e-12)
    angle = np.arange(seq)[:, None] * stretched[None, :]
    factor_ = MELLUM_FULL["attention_factor"]
    np.testing.assert_allclose(np.asarray(cos), factor_ * np.cos(angle),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(sin), factor_ * np.sin(angle),
                               atol=2e-5)
    # the published factor is 0.1 ln(16) + 1
    assert factor_ == pytest.approx(0.1 * math.log(16) + 1, abs=1e-12)


def test_yarn_at_factor_one_is_the_plain_table():
    plain = rope_frequencies(128, 32, 500000.0)
    same = yarn_frequencies(128, 32, 500000.0, 1.0, 8192)
    for a, b in zip(plain, same):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
