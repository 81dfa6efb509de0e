"""Elementwise / normalization building blocks.

Pure jnp: XLA fuses these into surrounding matmuls on TPU (HBM-bandwidth
friendly), so no hand kernel is needed; the hot op with real tiling needs
is attention (ops/attention.py). The one exception is the rotation of
the arrays on their way to its kernels (``rope_lanes``), which XLA can
only slice inside a tile.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ray_tpu.observability.device_programs import kernel_trace
from ray_tpu.ops import attention


def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * scale * weight.astype(jnp.float32)).astype(dtype)


def rope_frequencies(head_dim: int, max_seq: int, theta: float = 10000.0):
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                           / head_dim))
    t = jnp.arange(max_seq, dtype=jnp.float32)
    freqs = jnp.outer(t, inv)  # [S, D/2]
    return jnp.cos(freqs), jnp.sin(freqs)


def yarn_frequencies(head_dim: int, max_seq: int, theta: float,
                     factor: float, original_max_seq: int,
                     beta_fast: float = 32.0, beta_slow: float = 1.0,
                     attention_factor: float = 1.0):
    """``rope_frequencies`` stretched by YaRN (Peng et al. 2023, arXiv
    2309.00071) for a model trained to ``original_max_seq`` positions and
    read to ``factor`` times that: pair i turns ``theta**(-2i/d)`` a
    position; the pairs that make under ``beta_slow`` turns over the
    original length turn ``factor`` times slower (interpolated), those
    over ``beta_fast`` turns are left as they are, a linear ramp over the
    pair's index between the two (its ends rounded down and up, the
    transformers default); cos and sin both carry ``attention_factor``,
    so the logits do squared."""
    half = head_dim // 2
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                           / head_dim))

    def pair_of(turns: float) -> float:
        """The pair that makes ``turns`` turns over the original length."""
        return (head_dim * math.log(original_max_seq / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = min(max(math.floor(pair_of(beta_fast)), 0), half - 1)
    high = min(max(math.ceil(pair_of(beta_slow)), 0), half - 1)
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    inv = inv * (1.0 - ramp) + inv / factor * ramp
    freqs = jnp.outer(jnp.arange(max_seq, dtype=jnp.float32), inv)
    return (jnp.cos(freqs) * attention_factor,
            jnp.sin(freqs) * attention_factor)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array,
               position_offset: int | jax.Array = 0) -> jax.Array:
    """x: [B, S, H, D]; cos/sin: [max_seq, D/2] (sliced by position)."""
    s = x.shape[1]
    if isinstance(position_offset, int) and position_offset == 0:
        c = cos[:s]
        sn = sin[:s]
    else:
        c = jax.lax.dynamic_slice_in_dim(cos, position_offset, s, axis=0)
        sn = jax.lax.dynamic_slice_in_dim(sin, position_offset, s, axis=0)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    c = c[None, :, None, :]
    sn = sn[None, :, None, :]
    out = jnp.concatenate([x1 * c - x2 * sn, x1 * sn + x2 * c], axis=-1)
    return out.astype(x.dtype)


_LANES = 128
_ROPE_ROWS = 512


def _rope_on_heads(x, cos, sin, heads: int, rope_dim: int):
    """``rope_lanes`` as ``apply_rope`` on the [B, S, heads, D] view: the
    tier of a CPU, of a partitioned step (the heads' axis is what a mesh
    splits) and of shapes off the kernel's tiles."""
    b, s, width = x.shape
    x = x.reshape(b, s, heads, width // heads)
    plain = x.shape[-1] - rope_dim
    turned = apply_rope(x[..., plain:], cos, sin)
    if plain:
        turned = jnp.concatenate([x[..., :plain], turned], axis=-1)
    return turned.reshape(b, s, width)


def _rope_kernel(x_ref, c_ref, up_ref, down_ref, o_ref, *, half: int):
    """One head's last 128 lanes of a block of rows: every lane times its
    cosine (1 where the head is not rotated) plus its partner, ``half``
    lanes up or down, times its sine (0 likewise): two lane rotations, no
    slice narrower than a tile."""
    from jax.experimental.pallas import tpu as pltpu

    r = x_ref[0].astype(jnp.float32)
    out = (r * c_ref[...] + pltpu.roll(r, _LANES - half, 1) * up_ref[...]
           + pltpu.roll(r, half, 1) * down_ref[...])
    o_ref[0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("heads", "half"))
def _rope_call(x, c, up, down, heads: int, half: int):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, width = x.shape
    tiles = width // heads // _LANES  # of a head; the rotated one is the last
    rows = _rope_rows(s)
    lanes = pl.BlockSpec((1, rows, _LANES),
                         lambda bi, si, hi: (bi, si, hi * tiles + tiles - 1))
    table = pl.BlockSpec((rows, _LANES), lambda bi, si, hi: (si, 0))
    with kernel_trace("rope_lanes"):
        return pl.pallas_call(
            functools.partial(_rope_kernel, half=half),
            # the heads innermost: a block of the tables serves every head
            grid=(b, s // rows, heads),
            in_specs=[lanes, table, table, table], out_specs=lanes,
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           vma=jax.typeof(x).vma),
            # in place: the lanes no block visits are the input's
            input_output_aliases={0: 0},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=attention.kernels_interpreted(),
            name="rope_lanes",
        )(x, c, up, down)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _rope_op(x, c, up, down, heads: int, half: int):
    return _rope_call(x, c, up, down, heads, half)


def _rope_op_fwd(x, c, up, down, heads, half):
    return _rope_call(x, c, up, down, heads, half), (c, up, down)


def _rope_op_bwd(heads, half, tables, g):
    # a rotation's transpose is the rotation back: the sines negated
    c, up, down = tables
    return _rope_call(g, c, -up, -down, heads, half), None, None, None


_rope_op.defvjp(_rope_op_fwd, _rope_op_bwd)


def _rope_rows(seq: int):
    """Rows a grid step of the kernel: the largest of 512 halved down to
    8 that divides the sequence, or None."""
    rows = _ROPE_ROWS
    while rows >= 8 and seq % rows:
        rows //= 2
    return rows if rows >= 8 else None


def rope_tier(seq: int, head: int, rope_dim: int,
              sharded: bool = False) -> bool:
    """Whether ``rope_lanes`` of these shapes takes the kernel: where
    kernels run at all (``attention.kernels_on``) and the attention
    kernels will index the lanes it leaves (``attention.lane_layout``: a
    head whole 128-lane tiles, the step not ``sharded`` over a mesh,
    whose partitioner refuses a Mosaic kernel), the rotated lanes lie
    inside a head's last tile, and the sequence is whole blocks of
    rows."""
    return (attention.kernels_on() and attention.lane_layout(head, sharded)
            and rope_dim <= _LANES and _rope_rows(seq) is not None)


def rope_lanes(x: jax.Array, cos: jax.Array, sin: jax.Array, heads: int,
               rope_dim: int | None = None,
               sharded: bool = False) -> jax.Array:
    """``apply_rope`` on x: [B, S, heads * D] as a projection writes it,
    a head a block of D lanes, from position 0: the last ``rope_dim``
    lanes of each head rotated (all D where None), the same products on
    the same pairs.

    Why a kernel where one runs (``rope_tier``): the halves that
    ``apply_rope`` slices on the [B, S, H, D] view are parts of a
    128-lane tile, and XLA's TPU backend then lays the whole array
    S-minor (the slices become whole sublanes), has the projection write
    that layout, and turns the result back with a copy of its own before
    an attention kernel that wants the lanes minor: one pass over HBM
    more an operand, forward, recompute and backward (PERF.md section 6,
    PR 33). The kernel rotates lanes in place in VMEM and slices nothing
    narrower than a tile, so the array stays as the projection wrote
    it."""
    head = x.shape[-1] // heads
    rope_dim = rope_dim or head
    s = x.shape[1]
    if not rope_tier(s, head, rope_dim, sharded):
        return _rope_on_heads(x, cos, sin, heads, rope_dim)
    half = rope_dim // 2
    c, sn = cos[:s].astype(jnp.float32), sin[:s].astype(jnp.float32)
    one = jnp.ones((s, _LANES - rope_dim), jnp.float32)
    zero, none = jnp.zeros_like(one), jnp.zeros_like(sn)
    return _rope_op(
        x, jnp.concatenate([one, c, c], axis=-1),
        jnp.concatenate([zero, -sn, none], axis=-1),  # x1 c - x2 s
        jnp.concatenate([zero, none, sn], axis=-1),   # x1 s + x2 c
        heads, half)


def swiglu(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
           w_down: jax.Array) -> jax.Array:
    """SwiGLU MLP: silu(x@w_gate) * (x@w_up) @ w_down."""
    gate = jax.nn.silu(jnp.einsum("...h,hm->...m", x, w_gate))
    up = jnp.einsum("...h,hm->...m", x, w_up)
    return jnp.einsum("...m,mh->...h", gate * up, w_down)
