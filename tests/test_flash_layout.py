"""The layout the flash kernels index (``ops/attention.py::lane_layout``):
a head of whole 128-lane tiles is the block of ``D`` lanes at lane offset
``h * D`` of the [B, S, H*D] array the projections write, and no array is
copied on the way in or out; a narrower head is copied heads-major.

On the CPU the kernels run under Pallas's interpreter
(``_FORCE_INTERPRET``) against the blockwise tier, which is their
specification.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.observability.metrics import flash_calls
from ray_tpu.ops import attention as A
from ray_tpu.ops.attention import attention_reference, flash_attention

BLOCK = 128


def _qkv(batch, seq, heads, d, kv_heads=None, seed=0):
    """q, k, v, dout [B, S, H, D]; K and V repeated from ``kv_heads``
    heads where given, as the attention kinds hand them over."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, dout = (jax.random.normal(key, (batch, seq, heads, d))
               for key in keys[:2])
    k, v = (jax.random.normal(key, (batch, seq, kv_heads or heads, d))
            for key in keys[2:])
    if kv_heads:
        k, v = (jnp.repeat(x, heads // kv_heads, axis=2) for x in (k, v))
    return q, k, v, dout


def _calls():
    return dict(flash_calls.series())


def _counted(before):
    return {key: n - before.get(key, 0) for key, n in _calls().items()
            if n != before.get(key, 0)}


SHAPES = [
    # heads, head_dim, K/V heads
    pytest.param(20, 256, None, id="h20-d256"),       # the GLM cell's heads
    pytest.param(4, 128, 2, id="h4-d128-kv2"),        # K/V repeated from 2
]


@pytest.mark.parametrize("window", [None, 200], ids=["causal", "window"])
@pytest.mark.parametrize("heads,d,kv_heads", SHAPES)
def test_forward_in_the_lane_layout(monkeypatch, heads, d, kv_heads, window):
    """Two K/V major blocks a head: the row-block index is clamped as
    before, the head picks the block of lanes."""
    monkeypatch.setattr(A, "_FORCE_INTERPRET", True)
    seq, budget = 512, 2 * (2 * 2 * BLOCK * d * 4)
    q, k, v, _ = _qkv(2, seq, heads, d, kv_heads)
    plan = A.fwd_block_plan(seq, seq, d, True, 4, BLOCK, BLOCK, budget,
                            window=window)
    assert plan.block_k_major == 2 * BLOCK
    before = _calls()
    out, lse = A._pallas_fwd(q, k, v, True, d ** -0.5, plan)
    assert _counted(before) == {("fwd", "lanes"): 1}
    want, want_lse = A._blockwise_fwd(q, k, v, True, d ** -0.5, BLOCK,
                                      window)
    assert out.shape == q.shape and lse.shape == (2, heads, seq)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want_lse),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("window", [None, 200], ids=["causal", "window"])
@pytest.mark.parametrize("heads,d,kv_heads", SHAPES)
def test_backward_in_the_lane_layout(monkeypatch, heads, d, kv_heads,
                                     window):
    """dq and dk/dv, two major blocks of the resident side each."""
    monkeypatch.setattr(A, "_FORCE_INTERPRET", True)
    seq, budget = 512, 2 * (2 * 2 * BLOCK * d * 4)
    q, k, v, dout = _qkv(2, seq, heads, d, kv_heads, seed=1)
    scale = d ** -0.5
    out, lse = A._blockwise_fwd(q, k, v, True, scale, BLOCK, window)
    plan = A.bwd_block_plan(seq, seq, d, True, 4, BLOCK, BLOCK, budget,
                            window=window)
    assert plan.block_k_major == plan.block_q_major == 2 * BLOCK
    before = _calls()
    got = A._pallas_bwd(q, k, v, out, lse, dout, True, scale, plan)
    assert _counted(before) == {("dq", "lanes"): 1, ("dkdv", "lanes"): 1}
    want = A._blockwise_bwd(q, k, v, out, lse, dout, True, scale, BLOCK,
                            window)
    for name, a, b in zip("qkv", got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4,
                                   rtol=2e-4, err_msg=f"d{name}")


def test_half_a_tile_of_lanes_is_copied_heads_major(monkeypatch):
    """d 64: a head is half a tile, which no block may be; the forward
    and the backward kernels keep the heads-major copy and agree
    (``kernel_tiers``)."""
    monkeypatch.setattr(A, "_FORCE_INTERPRET", True)
    q, k, v, dout = _qkv(2, 256, 4, 64, seed=2)
    assert A.kernel_tiers(256, 256, 64) == (True, True)
    before = _calls()
    got, grads = jax.value_and_grad(
        lambda q, k, v: (flash_attention(q, k, v, True) * dout).sum(),
        (0, 1, 2))(q, k, v)
    assert _counted(before) == {("fwd", "heads_major"): 1,
                                ("dq", "heads_major"): 1,
                                ("dkdv", "heads_major"): 1}
    want, want_grads = jax.value_and_grad(
        lambda q, k, v: (attention_reference(q, k, v, True) * dout).sum(),
        (0, 1, 2))(q, k, v)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-4)
    for a, b in zip(grads, want_grads):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4,
                                   rtol=2e-4)
    # called directly, the backward kernels take the same copy
    out, lse = A._blockwise_fwd(q, k, v, True, 0.125, BLOCK)
    before = _calls()
    direct = A._pallas_bwd(q, k, v, out, lse, dout, True, 0.125)
    assert _counted(before) == {("dq", "heads_major"): 1,
                                ("dkdv", "heads_major"): 1}
    for a, b in zip(direct, A._blockwise_bwd(q, k, v, out, lse, dout, True,
                                             0.125, BLOCK)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4,
                                   rtol=2e-4)


def _transposes(jaxpr, least: int):
    """The ``transpose`` equations of a jaxpr, its sub-jaxprs (custom VJP
    rules, pjit, the kernels' own bodies) included, whose operand holds
    at least ``least`` elements."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if (eqn.primitive.name == "transpose"
                    and eqn.invars[0].aval.size >= least):
                found.append(eqn)
            for value in eqn.params.values():
                for sub in (value if isinstance(value, (list, tuple))
                            else [value]):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        walk(inner)

    walk(jaxpr.jaxpr)
    return found


@pytest.mark.parametrize("heads,d,window,moved", [
    (20, 256, None, 0), (4, 128, None, 0), (4, 128, 100, 0),
    (4, 64, None, 2),  # the forward's q, k, v in and out (its backward
                       # is blockwise): what the other cases are without
], ids=["h20-d256", "h4-d128", "h4-d128-window", "h4-d64"])
def test_no_array_is_turned_on_the_way_to_the_kernels(monkeypatch, heads, d,
                                                      window, moved):
    """The jaxpr of the op and of its VJP at ``head_dim % 128 == 0``
    holds no ``transpose`` of a q-sized array: what pins the mechanism
    where no chip is."""
    monkeypatch.setattr(A, "_FORCE_INTERPRET", True)
    shape = jax.ShapeDtypeStruct((2, 256, heads, d), jnp.float32)

    def loss(q, k, v):
        return flash_attention(q, k, v, True, None, None, None,
                               window).sum()

    size = 2 * 256 * heads * d
    fwd = jax.make_jaxpr(loss)(shape, shape, shape)
    both = jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(shape, shape, shape)
    assert (len(_transposes(fwd, size)) > 0) == (moved > 0)
    assert (len(_transposes(both, size)) > 0) == (moved > 0)
    if not moved:
        # nor of anything else: delta is summed by a kernel of its own
        assert _transposes(both, 1) == []


@pytest.mark.parametrize("d,layout", [(128, "lanes"), (256, "lanes"),
                                      (64, "heads_major")])
def test_flash_calls_counts_the_layout(monkeypatch, d, layout):
    """``flash_calls{kernel, layout}``: one a kernel traced, counted
    where ``flash_fwd_subblocks`` is."""
    monkeypatch.setattr(A, "_FORCE_INTERPRET", True)
    shape = jax.ShapeDtypeStruct((1, 256, 2, d), jnp.bfloat16)
    before = _calls()
    jax.eval_shape(jax.grad(lambda q, k, v: flash_attention(
        q, k, v, True).sum().astype(jnp.float32), (0, 1, 2)),
        shape, shape, shape)
    # every d here takes the backward kernels too (``kernel_tiers``)
    want = {("fwd", layout): 1, ("dq", layout): 1, ("dkdv", layout): 1}
    assert _counted(before) == want


def test_on_a_mesh_a_shard_keeps_the_heads_major_copy(monkeypatch):
    """``flash_attention_on_mesh`` on a 2 x 2 CPU mesh (float32
    rehearsal): each shard's kernels take its [B/2 * H/2, S, D] copies
    (``lane_layout``: the lanes lost the four-chip cell 1.1 %; a shard is
    known by its arrays varying over the mesh's axes) and agree with the
    reference, as before."""
    from jax.sharding import PartitionSpec as P

    from ray_tpu.parallel.mesh import MeshSpec, build_mesh

    monkeypatch.setattr(A, "_FORCE_INTERPRET", True)
    mesh = build_mesh(MeshSpec(dp=2, tp=2))
    attn = A.flash_attention_on_mesh(P("dp", None, "tp", None), mesh)
    q, k, v, dout = _qkv(2, 256, 4, 128, kv_heads=2, seed=3)

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v) * dout).sum()

    before = _calls()
    with mesh:
        got = jax.jit(jax.value_and_grad(loss(attn), (0, 1, 2)))(q, k, v)
    assert set(_counted(before)) == {
        ("fwd", "heads_major"), ("dq", "heads_major"),
        ("dkdv", "heads_major")}
    want = jax.value_and_grad(loss(lambda q, k, v: attention_reference(
        q, k, v, True)), (0, 1, 2))(q, k, v)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4,
                                   rtol=2e-4)


# ------------------------------------------- the attention kinds' side of it
def _rope_reference(x, cos, sin, heads, rope_dim):
    """The rotation as it reads on the [B, S, H, D] view: a head's last
    ``rope_dim`` in halves, (x1 c - x2 s, x1 s + x2 c)."""
    b, s, width = x.shape
    x = x.reshape(b, s, heads, width // heads)
    plain, (x1, x2) = x[..., :-rope_dim], jnp.split(x[..., -rope_dim:], 2, -1)
    c, sn = cos[None, :s, None], sin[None, :s, None]
    return jnp.concatenate([plain, x1 * c - x2 * sn, x1 * sn + x2 * c],
                           -1).reshape(b, s, width)


@pytest.mark.parametrize("interpret", [False, True],
                         ids=["pieces", "kernel"])
@pytest.mark.parametrize("heads,head,rope_dim", [
    (3, 256, 64), (4, 128, 128), (1, 128, 32)])
def test_rope_on_the_lanes(monkeypatch, heads, head, rope_dim, interpret):
    """Both tiers of ``rope_lanes`` against the rotation on the heads'
    view, values and gradient (the kernel's backward is the kernel with
    the sines negated)."""
    from ray_tpu.ops import layers as L

    monkeypatch.setattr(A, "_FORCE_INTERPRET", interpret)
    assert L.rope_tier(256, head, rope_dim) == interpret
    cos, sin = L.rope_frequencies(rope_dim, 512, 10000.0)
    x, weigh = (jax.random.normal(key, (2, 256, heads * head))
                for key in jax.random.split(jax.random.PRNGKey(5)))
    got, grad = jax.value_and_grad(lambda x: (L.rope_lanes(
        x, cos, sin, heads, rope_dim) * weigh).sum())(x)
    want, want_grad = jax.value_and_grad(lambda x: (_rope_reference(
        x, cos, sin, heads, rope_dim) * weigh).sum())(x)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(grad), np.asarray(want_grad),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(L.rope_lanes(x, cos, sin, heads, rope_dim)),
        np.asarray(_rope_reference(x, cos, sin, heads, rope_dim)),
        atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("seq,head,rope_dim,sharded,want", [
    (8192, 256, 64, False, True), (4096, 128, 128, False, True),
    (4096, 128, 128, True, False),   # a partitioned step: plain jnp
    (4096, 64, 64, False, False),    # half a tile of lanes a head
    (4096, 256, 256, False, False),  # rotated lanes in two tiles
    (1001, 128, 128, False, False),  # no block of rows divides it
])
def test_the_rope_kernels_tier(monkeypatch, seq, head, rope_dim, sharded,
                               want):
    from ray_tpu.ops import layers as L

    monkeypatch.setattr(A, "kernels_on", lambda: True)
    assert L.rope_tier(seq, head, rope_dim, sharded) == want
    monkeypatch.setattr(A, "kernels_on", lambda: False)
    assert not L.rope_tier(seq, head, rope_dim, sharded)


def _handed_over(block, *args):
    """What an attention kind hands its ``attention_fn``."""
    seen = {}

    def attention_fn(q, k, v, **kw):
        seen.update(q=q, k=k, v=v, **kw)
        return q

    block(*args, attention_fn)
    return seen


@pytest.mark.parametrize("sharded", [False, True], ids=["chip", "mesh"])
@pytest.mark.parametrize("rotary", [True, False], ids=["rotary", "plain"])
def test_the_dense_kind_hands_over_whole_heads(rotary, sharded):
    """q rotated, K rotated and V as projected, each K/V head copied to
    the query heads that share it: what the [B, S, H, D] forms gave, by
    either way of copying (``repeat_kv``)."""
    from ray_tpu.models import transformer as tfm
    from ray_tpu.ops import layers as L

    cfg = tfm.ModelConfig(hidden=256, heads=4, kv_heads=2, layers=1,
                          dtype=jnp.float32)
    hd, keys = cfg.head_dim, jax.random.split(jax.random.PRNGKey(6), 5)
    layer = {"attn_norm": jnp.ones((256,)),
             "wq": jax.random.normal(keys[0], (256, 256)) / 16,
             "wk": jax.random.normal(keys[1], (256, 128)) / 16,
             "wv": jax.random.normal(keys[2], (256, 128)) / 16,
             "wo": jax.random.normal(keys[3], (256, 256)) / 16}
    x = jax.random.normal(keys[4], (2, 32, 256))
    cos, sin = L.rope_frequencies(hd, 64, 10000.0) if rotary else (None,
                                                                   None)
    seen = _handed_over(
        lambda *args: tfm.attention_block(*args, sharded=sharded),
        x, layer, cfg, cos, sin)
    xn = L.rms_norm(x, layer["attn_norm"], cfg.norm_eps)
    q, k, v = (xn @ layer[w] for w in ("wq", "wk", "wv"))
    if rotary:
        q = _rope_reference(q, cos, sin, 4, hd)
        k = _rope_reference(k, cos, sin, 2, hd)
    want = {"q": q.reshape(2, 32, 4, hd),
            "k": jnp.repeat(k.reshape(2, 32, 2, hd), 2, axis=2),
            "v": jnp.repeat(v.reshape(2, 32, 2, hd), 2, axis=2)}
    for name in "qkv":
        np.testing.assert_allclose(np.asarray(seen[name]),
                                   np.asarray(want[name]), atol=1e-5,
                                   rtol=1e-5, err_msg=name)


def test_the_latent_kind_hands_over_whole_heads():
    """Every head's key is its own k_n beside the one rotated k_r, its
    value the other columns of W_ukv, the query's last lanes rotated:
    what the split and the concatenations on [B, S, H, D] gave."""
    from ray_tpu.models import transformer as tfm
    from ray_tpu.ops import layers as L

    heads, hd, rope, q_rank, kv_rank, hidden = 3, 128, 32, 48, 40, 64
    nope = hd - rope
    cfg = tfm.ModelConfig(
        hidden=hidden, heads=heads, kv_heads=heads, layers=1,
        dtype=jnp.float32, stack=tfm.Stack(
            pattern="L", head_dim=hd, q_rank=q_rank, kv_rank=kv_rank,
            rope_dim=rope, rope=tfm.Rope(10000.0)))
    shapes = {name: spec[0]
              for name, spec in tfm._kind_leaves(cfg)["latent"].items()}
    keys = jax.random.split(jax.random.PRNGKey(7), len(shapes) + 1)
    layer = {name: (jnp.ones(shape) if len(shape) == 1 else
                    jax.random.normal(key, shape) * shape[0] ** -0.5)
             for key, (name, shape) in zip(keys, shapes.items())}
    x = jax.random.normal(keys[-1], (2, 32, hidden))
    cos, sin = L.rope_frequencies(rope, 64, 10000.0)
    seen = _handed_over(tfm.latent_attention_block, x, layer, cfg, cos, sin)
    xn = L.rms_norm(x, layer["attn_norm"], cfg.norm_eps)
    q = L.rms_norm(xn @ layer["w_dq"], layer["q_norm"],
                   cfg.norm_eps) @ layer["w_uq"]
    ckv, k_rope = jnp.split(xn @ layer["w_dkv"], [kv_rank], -1)
    k_nope, v = jnp.split(
        (L.rms_norm(ckv, layer["kv_norm"], cfg.norm_eps)
         @ layer["w_ukv"]).reshape(2, 32, heads, nope + hd), [nope], -1)
    k_rope = _rope_reference(k_rope, cos, sin, 1, rope)[:, :, None, :]
    want = {"q": _rope_reference(q, cos, sin, heads, rope).reshape(
                2, 32, heads, hd),
            "k": jnp.concatenate([k_nope, jnp.broadcast_to(
                k_rope, (2, 32, heads, rope))], -1),
            "v": v}
    for name in "qkv":
        np.testing.assert_allclose(np.asarray(seen[name]),
                                   np.asarray(want[name]), atol=1e-5,
                                   rtol=1e-5, err_msg=name)


@pytest.mark.parametrize("sharded", [True, False], ids=["repeat", "lanes"])
@pytest.mark.parametrize("kv_heads,d", [(2, 128), (1, 256), (4, 128)])
def test_shared_heads_are_copied_for_their_query_heads(sharded, kv_heads, d):
    """``repeat_kv``: K/V head g serves query heads g * rep .. g * rep +
    rep - 1, as whole tiles along the lanes where the kernels index them
    and on the heads' axis on a mesh, and the cotangent is summed over
    them."""
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 16, kv_heads, d))
    weigh = jax.random.normal(jax.random.PRNGKey(9), (2, 16, 4, d))
    got, grad = jax.value_and_grad(
        lambda x: (A.repeat_kv(x, 4, sharded) * weigh).sum())(x)
    want, want_grad = jax.value_and_grad(
        lambda x: (jnp.repeat(x, 4 // kv_heads, axis=2) * weigh).sum())(x)
    np.testing.assert_array_equal(
        np.asarray(A.repeat_kv(x, 4, sharded)),
        np.asarray(jnp.repeat(x, 4 // kv_heads, axis=2)))
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(grad), np.asarray(want_grad),
                               atol=1e-5, rtol=1e-5)
    # which way: a concatenation of lane tiles, or a repeat of heads
    jaxpr = str(jax.make_jaxpr(lambda x: A.repeat_kv(x, 4, sharded))(x))
    assert ("concatenate" in jaxpr) == (kv_heads < 4 and not sharded)


@pytest.mark.parametrize("head_dim,sharded,lanes", [
    (128, False, True), (256, False, True),
    (64, False, False),    # half a tile of lanes
    (192, False, False),   # a tile and a half
    (128, True, False),    # a mesh keeps the heads-major copy
])
def test_the_one_rule_of_the_layout(head_dim, sharded, lanes):
    """``lane_layout``, which the kernels' layout, ``repeat_kv`` and
    ``rope_tier`` all ask."""
    assert A.lane_layout(head_dim, sharded) == lanes
    q = jnp.zeros((1, 8, 2, head_dim))
    if not sharded:
        assert A._layout_of(q).name == ("lanes" if lanes else "heads_major")
