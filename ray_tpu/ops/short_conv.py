"""The gated short convolution: the token mixer of LFM2's convolution
layers (Liquid AI's LFM2 family, ``model_type`` ``lfm2`` / ``lfm2_moe``).

For the in-projection's output ``proj = [B | C | x]`` ([batch, seq, 3h],
the three parts side by side along the lanes) and taps ``weight`` [K, h]:

    v_t = B_t * x_t
    w_t = sum_j weight[j] * v_{t - (K-1) + j}      (v = 0 before the row)
    y_t = C_t * w_t

causal and depthwise, one filter of K taps a channel, no bias and no
activation. ``v`` is a product of two values of the model's type and is
exact in float32; the taps' sum and the gate are float32 and ``y`` is
rounded once to ``proj``'s type.

Two tiers behind ``gated_short_conv``, as ``ops/ssd.py``'s convolution
has them, and ``short_conv_tier`` the one rule between them (its form is
``ssd.conv_tier``'s, which it asks: kernels on, the step not partitioned
over a mesh, each part on whole blocks of 128 lanes, the sequence whole
blocks of rows):

  -> the Pallas kernel pair ``short_conv_fwd`` / ``short_conv_bwd``,
     joined by a custom VJP. They read ``proj`` where the projection
     wrote it, three BlockSpecs of the one array (B, C and x at lane
     blocks 0, h and 2h: nothing is sliced or copied on the way), take
     the K - 1 rows before a block from ``ssd``'s halo BlockSpecs and
     form the taps by rotating sublanes in registers (``ssd._taps``);
     the forward writes ``y`` [B, S, h]. The backward walks a row's
     blocks from the last to the first with the first rows of ``g = dy *
     C`` of the block after carried in VMEM (``ssd._ahead``), rebuilds
     ``v`` and ``w`` from ``proj`` (the one residual) and writes ``d
     proj`` [B, S, 3h] as the projection's transpose takes it: a fourth
     grid axis of three steps hands the three parts, made once in VMEM,
     each to its block of lanes. The taps' gradient is eight partial sums
     a batch row (a sublane each) in a resident float32 block, summed
     outside.
  -> plain ``jnp``: the path off the TPU, of a step partitioned over a
     mesh (whose partitioner cannot split a Mosaic kernel), of shapes off
     the tiles, and the kernels' oracle.

Tracing a call counts it in ``short_conv_calls{tier, pass}``; each
``pallas_call`` is traced under ``kernel_trace``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.observability.device_programs import kernel_trace
from ray_tpu.observability.metrics import short_conv_calls
from ray_tpu.ops import attention
from ray_tpu.ops.ssd import (
    _AT_ONCE,
    _HALO,
    _ahead,
    _block_and_halo,
    _conv_blocks,
    _halo_rows,
    _lanes_at_once,
    _taps,
    conv_tier,
)

# a grid step's block, rows x lanes of one part: the kernels read three
# parts of a block (and dy backwards) and the backward keeps the three
# parts of its cotangent in VMEM, so a block is a quarter of the
# convolution's and the double-buffered whole stays under the 16 MiB of
# VMEM a v5e kernel may use by default
BLOCK_ROWS = 512
BLOCK_LANES = 512


def short_conv_tier(seq: int, channels: int, taps: int,
                    sharded: bool = False) -> bool:
    """Whether a gated short convolution of these shapes takes the
    kernels: ``ssd.conv_tier`` asked of the three parts side by side, cut
    where each starts."""
    return conv_tier(seq, 3 * channels, taps, sharded,
                     (channels, 2 * channels))


def _blocks(seq: int, channels: int):
    rows, lanes = _conv_blocks(seq, 3 * channels, (channels, 2 * channels))
    return min(rows, BLOCK_ROWS), min(lanes, BLOCK_LANES)


def _taps_sum(taps, w_ref, lanes):
    """sum_j w[j] * tap j in float32, tap 0 first: the jnp form's order."""
    out = w_ref[0:1, lanes] * taps[0]
    for j in range(1, len(taps)):
        out = out + w_ref[j:j + 1, lanes] * taps[j]
    return out


def _fwd_kernel(b_ref, bh_ref, c_ref, x_ref, xh_ref, w_ref, y_ref):
    """One block [rows, lanes] of one batch row: ``_AT_ONCE`` rows x the
    ``ssd`` convolution's lanes at a time, the rows from the first to the
    last; ``v``'s last rows carried to the next rows' taps."""
    from jax.experimental import pallas as pl

    rows, width = b_ref.shape[1:]
    k = w_ref.shape[0]
    first_block = pl.program_id(2) == 0
    wide = _lanes_at_once(width)

    def some_lanes(piece, _):
        lanes = pl.ds(pl.multiple_of(piece * wide, wide), wide)

        def some_rows(i, before):
            at = pl.ds(pl.multiple_of(i * _AT_ONCE, _AT_ONCE), _AT_ONCE)
            v = (b_ref[0, at, lanes].astype(jnp.float32)
                 * x_ref[0, at, lanes].astype(jnp.float32))
            w = _taps_sum(_taps(v, before, k), w_ref, lanes)
            y_ref[0, at, lanes] = (c_ref[0, at, lanes].astype(jnp.float32)
                                   * w).astype(y_ref.dtype)
            return v[_AT_ONCE - 8:]

        lax.fori_loop(0, rows // _AT_ONCE, some_rows,
                      _halo_rows(bh_ref, lanes, first_block)
                      * _halo_rows(xh_ref, lanes, first_block))

    lax.fori_loop(0, width // wide, some_lanes, None)


def _bwd_kernel(b_ref, bh_ref, c_ref, x_ref, xh_ref, w_ref, dy_ref,
                dproj_ref, dw_ref, g_scr, parts):
    """The same block with the sequence's blocks and a block's rows from
    the last to the first, at the first of the part axis's three steps:
    ``v`` and ``w`` rebuilt, ``g = dy * C``, ``dv_t = sum_j w[j] g[t +
    K-1-j]`` with the K - 1 rows of g after the block carried in
    ``g_scr``; dB = dv x, dC = dy w, dx = dv B into ``parts``, and the
    taps' sums (``sum_t v_t g[t + K-1-j]``) into ``dw_ref`` [8 K, lanes],
    eight partial sums a tap over all the blocks of a batch row. Each
    step of the part axis then writes its part's block."""
    from jax.experimental import pallas as pl

    rows, width = b_ref.shape[1:]
    k = w_ref.shape[0]
    steps = rows // _AT_ONCE
    part = pl.program_id(3)
    first_block = pl.program_id(2) == pl.num_programs(2) - 1

    @pl.when((pl.program_id(2) == 0) & (part == 0))
    def _start():
        g_scr[...] = jnp.zeros_like(g_scr)
        dw_ref[...] = jnp.zeros_like(dw_ref)

    def by_sublane(values):
        return sum(values[r:r + 8] for r in range(0, _AT_ONCE, 8))

    def f32(ref, at, lanes):
        return ref[0, at, lanes].astype(jnp.float32)

    wide = _lanes_at_once(width)

    def some_lanes(piece, _):
        lanes = pl.ds(pl.multiple_of(piece * wide, wide), wide)
        halo = (_halo_rows(bh_ref, lanes, first_block)
                * _halo_rows(xh_ref, lanes, first_block))

        def some_rows(n, carried):
            after, dw = carried
            start = pl.multiple_of((steps - 1 - n) * _AT_ONCE, _AT_ONCE)
            at = pl.ds(start, _AT_ONCE)
            bb, xx = f32(b_ref, at, lanes), f32(x_ref, at, lanes)
            v = bb * xx
            # the 8 rows of v before these: the block's own, the halo's
            # where these start the block
            prev = pl.ds(pl.multiple_of(jnp.maximum(start - _HALO, 0),
                                        _HALO), _HALO)
            own = f32(b_ref, prev, lanes) * f32(x_ref, prev, lanes)
            before = jnp.where(start == 0, halo, own[_HALO - 8:])
            w = _taps_sum(_taps(v, before, k), w_ref, lanes)
            dy = f32(dy_ref, at, lanes)
            g = dy * f32(c_ref, at, lanes)
            # tap j's weight meets g[t + K-1-j] in dv[t], and so does v[t]
            # in the tap's own sum
            ahead = [_ahead(g, after, k - 1 - j) for j in range(k)]
            dv = w_ref[k - 1:k, lanes] * g
            for j in range(k - 1):
                dv = dv + w_ref[j:j + 1, lanes] * ahead[j]
            parts[0, at, lanes] = (dv * xx).astype(parts.dtype)
            parts[1, at, lanes] = (dy * w).astype(parts.dtype)
            parts[2, at, lanes] = (dv * bb).astype(parts.dtype)
            return (g[:8], [one + by_sublane(v * moved)
                            for one, moved in zip(dw, ahead)])

        nought = jnp.zeros((8, wide), jnp.float32)
        g_first, dw = lax.fori_loop(0, steps, some_rows,
                                    (g_scr[:, lanes], [nought] * k))
        g_scr[:, lanes] = g_first
        for j in range(k):
            dw_ref[0, 8 * j:8 * (j + 1), lanes] += dw[j]

    @pl.when(part == 0)
    def _compute():
        lax.fori_loop(0, width // wide, some_lanes, None)

    dproj_ref[0] = parts[part]


# jitted for the reason ``ssd._conv_call`` is: the layers of a step share
# one trace and one lowering of each kernel
@jax.jit
def _fwd_call(proj, weight):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, _ = proj.shape
    k, h = weight.shape
    rows, lanes = _blocks(s, h)
    part = h // lanes

    def row(j):
        return j

    with kernel_trace("short_conv_fwd"):
        return pl.pallas_call(
            _fwd_kernel,
            grid=(b, part, s // rows),
            in_specs=(_block_and_halo(rows, lanes, 0, row)
                      + _block_and_halo(rows, lanes, part, row)[:1]
                      + _block_and_halo(rows, lanes, 2 * part, row)
                      + [pl.BlockSpec((k, lanes), lambda b, c, j: (0, c))]),
            out_specs=pl.BlockSpec((1, rows, lanes),
                                   lambda b, c, j: (b, j, c)),
            out_shape=jax.ShapeDtypeStruct((b, s, h), proj.dtype,
                                           vma=jax.typeof(proj).vma),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel")),
            interpret=attention.kernels_interpreted(),
            name="short_conv_fwd",
        )(proj, proj, proj, proj, proj, weight.astype(jnp.float32))


@jax.jit
def _bwd_call(proj, weight, dy):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, _ = proj.shape
    k, h = weight.shape
    rows, lanes = _blocks(s, h)
    part = h // lanes
    last = s // rows - 1
    vma = jax.typeof(proj).vma

    def row(j):
        return last - j

    with kernel_trace("short_conv_bwd"):
        dproj, dw = pl.pallas_call(
            _bwd_kernel,
            grid=(b, part, s // rows, 3),
            in_specs=(_block_and_halo(rows, lanes, 0, row)
                      + _block_and_halo(rows, lanes, part, row)[:1]
                      + _block_and_halo(rows, lanes, 2 * part, row)
                      + [pl.BlockSpec((k, lanes),
                                      lambda b, c, j, p: (0, c)),
                         pl.BlockSpec((1, rows, lanes),
                                      lambda b, c, j, p: (b, last - j, c))]),
            out_specs=[
                pl.BlockSpec((1, rows, lanes),
                             lambda b, c, j, p: (b, last - j, p * part + c)),
                pl.BlockSpec((1, 8 * k, lanes),
                             lambda b, c, j, p: (b, 0, c)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct(proj.shape, proj.dtype, vma=vma),
                jax.ShapeDtypeStruct((b, 8 * k, h), jnp.float32, vma=vma),
            ],
            scratch_shapes=[pltpu.VMEM((8, lanes), jnp.float32),
                            pltpu.VMEM((3, rows, lanes), proj.dtype)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary",
                                     "arbitrary")),
            interpret=attention.kernels_interpreted(),
            name="short_conv_bwd",
        )(proj, proj, proj, proj, proj, weight.astype(jnp.float32), dy)
    return dproj, dw.reshape(b, k, 8, h).sum((0, 2)).astype(weight.dtype)


def _jnp_short_conv(proj, weight):
    k, h = weight.shape
    s = proj.shape[1]
    b, c, x = (lax.slice_in_dim(proj, i * h, (i + 1) * h, axis=-1).astype(
        jnp.float32) for i in range(3))
    padded = jnp.pad(b * x, ((0, 0), (k - 1, 0), (0, 0)))
    taps = weight.astype(jnp.float32)
    w = taps[0] * padded[:, :s]
    for j in range(1, k):
        w = w + taps[j] * padded[:, j:j + s]
    return (c * w).astype(proj.dtype)


def _count(kernel: bool, which: str) -> None:
    short_conv_calls.inc(1, {"tier": "kernel" if kernel else "jnp",
                             "pass": which})


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _short_conv(proj, weight, kernel: bool):
    _count(kernel, "fwd")
    return (_fwd_call if kernel else _jnp_short_conv)(proj, weight)


def _short_conv_fwd(proj, weight, kernel: bool):
    _count(kernel, "fwd")
    if kernel:
        return _fwd_call(proj, weight), (proj, weight)
    return jax.vjp(_jnp_short_conv, proj, weight)


def _short_conv_bwd(kernel: bool, kept, dy):
    _count(kernel, "bwd")
    if kernel:
        return _bwd_call(*kept, dy)
    return kept(dy)


_short_conv.defvjp(_short_conv_fwd, _short_conv_bwd)


def gated_short_conv(proj, weight, sharded: bool = False):
    """``C * conv(B * x)`` for ``proj`` [B, S, 3h] (``[B | C | x]`` side by
    side) and taps ``weight`` [K, h] -> [B, S, h] of ``proj``'s type.
    ``sharded``: the step is partitioned over a mesh
    (``short_conv_tier``)."""
    k, h = weight.shape
    if proj.shape[-1] != 3 * h:
        raise ValueError(f"gated_short_conv: proj has {proj.shape[-1]} "
                         f"lanes, not three parts of the taps' {h}")
    return _short_conv(proj, weight, short_conv_tier(
        proj.shape[1], h, k, sharded))
