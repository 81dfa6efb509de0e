"""Mamba-2's state-space layer: the chunked scan (SSD), the causal
depthwise convolution in front of it and the gated grouped norm behind.

The recurrence, per head h with its group g = h // (heads / groups):

    S_t = exp(dt_t * A_h) * S_{t-1} + dt_t * x_t (outer) B_t
    y_t = S_t . C_t + D_h * x_t,                      S_0 = 0

is computed a chunk at a time (state-space duality, Dao & Gu 2024):
inside a chunk the quadratic form (C.B^T masked by the decay, times x),
between chunks the carried state [batch, heads, head_dim, state]. The
cumulative log-decay, the decays and the carried state are float32; the
operands of the products take the type of ``x`` (bfloat16 in training)
and accumulate in float32.

One algorithm, two tiers behind ``ssd_scan``, as ops/attention.py has
them; ``scan_tier`` says which a call takes, from the platform
(``attention.kernels_on``), the shapes and whether the step is
partitioned over a mesh, and nothing a user sets moves it:

  -> two Pallas kernels joined by a custom VJP. The grid runs over
     (batch row, group of heads, block of chunks) with the chunks
     innermost and sequential; the carried state of a group (forward) or
     its cotangent (backward) lives in a VMEM scratch from a row's first
     chunk to its last and never passes through HBM between chunks. The
     kernels read x [B, S, H.P], B and C [B, S, G.N] and write y as
     ``mamba_block`` holds them, through their BlockSpecs (dt alone
     comes turned, a chunk's positions on the lanes); C.B^T is formed
     once a group. The forward rule keeps the state at each chunk's
     start ([B, G, S/chunk, N, R.P] float32) and the scan's inputs,
     nothing per position; the backward kernel walks the chunks from the
     last to the first, rebuilds a chunk's log-decays, decays and scores
     from its inputs and writes dx, dB, dC, ddt and the sums for ``a``
     and ``d``. The plain forward (the first pass of a rematerialised
     layer) writes no states.
  -> plain jnp: a ``lax.scan`` over the chunks whose body is
     checkpointed. The path off the TPU, of shapes off the kernels'
     tiles, of a step partitioned over a mesh (Mosaic kernels cannot be
     partitioned automatically and no per-shard shard_map is built for
     the scan: the partitioner splits the jnp form itself), and the
     kernels' oracle. The carried state goes through HBM once a chunk.

The convolution in front of the scan, with its SiLU, has the same two
tiers behind ``conv_silu`` and its own rule, ``conv_tier`` (below, above
the kernels ``conv_fwd`` and ``conv_bwd``); the gate and the grouped norm
behind the scan are the third pair, behind ``gated_group_norm`` with the
rule ``gate_norm_tier`` (at the end, above the kernels ``gate_norm_fwd``
and ``gate_norm_bwd``).

Tracing a scan counts its chunks in ``ssd_scan_chunks{tier, pass}``, a
convolution itself in ``mamba_conv_calls{tier, pass}``, a gate and norm in
``mamba_gate_norm_calls{tier, pass}``.
Device time a call at the cell nemotron_twotower_l9_train_s8192's shapes
(B4-S8192, 64 heads of 64 in 8 groups, state 128, chunk 128, bfloat16) on
TPU v5 lite, from profiler traces (PR 29): the jnp tier 6.44 ms forward
and 12.45 backward in the step; the kernels 2.18 and 4.76 in the step
(1.82 and 4.47 at four chunks a grid step, ``BLOCK_POSITIONS``). The least time the yardstick gives (x, B, C, dt in
and y out) is 0.83 and 1.66 ms; a kernel that only moves the forward's
blocks takes 1.10.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.observability.device_programs import kernel_trace
from ray_tpu.observability.metrics import (
    mamba_conv_calls,
    mamba_gate_norm_calls,
    ssd_scan_chunks,
)
from ray_tpu.ops import attention

_LANES = 128
# positions of a sequence a grid step of the kernels takes (whole chunks,
# one after the other in one block of code). 512 makes the forward kernel
# 0.26 ms a call faster at the cell's shapes and the backward no faster,
# and costs the step 2.6 s of set-up more: tracing and lowering the
# unrolled kernels is 5.3 of the step's 12.7 s at 512 (my chip run, PR 29)
BLOCK_POSITIONS = 256


def _chunk(state, inputs, a_log_decay, d_skip, ratio: int):
    """One chunk: state [B,H,P,N] float32, x [B,L,H,P], dt [B,L,H]
    float32, bm/cm [B,L,G,N] -> (state at the chunk's end, y [B,L,H,P])."""
    x, dt, bm, cm = inputs
    b, l, h, p = x.shape
    g, n = bm.shape[2], bm.shape[3]
    dtype = x.dtype
    # cumulative log-decay from the chunk's start, [B,G,R,L]
    cs = jnp.cumsum(dt * a_log_decay, axis=1).transpose(0, 2, 1).reshape(
        b, g, ratio, l)
    dt_h = dt.transpose(0, 2, 1).reshape(b, g, ratio, l)
    xg = x.reshape(b, l, g, ratio, p)
    # within the chunk: position t reads s <= t with decay exp(cs_t - cs_s)
    causal = jnp.tril(jnp.ones((l, l), bool))
    seg = cs[..., :, None] - cs[..., None, :]
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))         # [B,G,R,L,L]
    scores = jnp.einsum("blgn,bsgn->bgls", cm, bm,
                        preferred_element_type=jnp.float32)
    mixed = scores[:, :, None] * decay * dt_h[..., None, :]
    y = jnp.einsum("bgrls,bsgrp->blgrp", mixed.astype(dtype), xg,
                   preferred_element_type=jnp.float32)
    # what the carried state adds: exp(cs_t) * C_t . S
    sg = state.reshape(b, g, ratio, p, n)
    y = y + jnp.einsum("blgn,bgrpn->blgrp", cm, sg.astype(dtype),
                       preferred_element_type=jnp.float32) \
        * jnp.exp(cs).transpose(0, 3, 1, 2)[..., None]
    # the state at the chunk's end
    last = cs[..., -1:]
    weight = (jnp.exp(last - cs) * dt_h).transpose(0, 3, 1, 2)  # [B,L,G,R]
    local = jnp.einsum("blgrp,blgn->bgrpn",
                       (xg.astype(jnp.float32) * weight[..., None]
                        ).astype(dtype), bm,
                       preferred_element_type=jnp.float32)
    state = (sg * jnp.exp(last)[..., None] + local).reshape(b, h, p, n)
    y = y.reshape(b, l, h, p) + d_skip[:, None] * x.astype(jnp.float32)
    return state, y.astype(dtype)


def _jnp_scan(x, dt, a, bm, cm, d, chunk: int):
    """The jnp tier: differentiating it keeps the carried state at each
    chunk boundary and the scan's own inputs, and rebuilds a chunk's
    decays in its backward."""
    b, s, h, p = x.shape
    g, n = bm.shape[2], bm.shape[3]

    def chunks(t):  # [B,S,...] -> [S/chunk, B, chunk, ...]
        return jnp.moveaxis(
            t.reshape(b, s // chunk, chunk, *t.shape[2:]), 1, 0)

    body = jax.checkpoint(
        lambda state, inputs: _chunk(state, inputs, a, d, h // g))
    _, y = lax.scan(body, jnp.zeros((b, h, p, n), jnp.float32),
                    (chunks(x), chunks(dt), chunks(bm), chunks(cm)))
    return jnp.moveaxis(y, 0, 1).reshape(b, s, h, p)


# ===========================================================================
# The kernel tier. A group of R heads is R.P lanes of x beside the
# group's N lanes of B and C; its lanes are worked a piece at a time
# (``_pieces``: whole vregs of 128 lanes, two heads of 64 side by side),
# a head's [chunk, chunk] decays against the piece's lanes with the other
# heads' lanes chosen away. What is one number a position and head (dt,
# the cumulative log-decay and what follows from them) is worked with the
# positions on the lanes ([R, chunk]: one vreg for the cell's 8 heads a
# group), for all the chunks of a grid step at once, and turned once a
# grid step (``_turn``) for the few places that want the positions on the
# sublanes. What the kernels were measured to be bound by, in order
# (bundles of the compiled schedule and the chip agree, PR 29): the
# number of [128, 128] products (each streams 128 rows through one of the
# four MXUs), how often a column is laid over the lanes (16 permutes of
# the XLU a head and quantity), the spills of [chunk, chunk] float32
# values (the backward), and the length of one chunk's chain of dependent
# steps, which the scheduler does not overlap with the next chunk's: so
# the running sums and the turn are made once a grid step, the carried
# state is the only thing a chunk waits for, and nothing but a head's two
# products and the state's four go through the MXU a chunk.
# ===========================================================================


def _pieces(ratio: int, head_dim: int):
    """(lanes of a piece of a group, heads in it), or None where heads
    do not lie whole in pieces that lie whole in the group."""
    lanes = ratio * head_dim
    width = head_dim if head_dim % _LANES == 0 else min(_LANES, lanes)
    if width % head_dim or lanes % width:
        return None
    return width, width // head_dim


def scan_tier(chunk: int, heads: int, head_dim: int, groups: int, state: int,
              sharded: bool = False) -> bool:
    """Whether a scan of these shapes takes the kernels: the one rule
    behind ``ssd_scan``. Where kernels run at all
    (``attention.kernels_on``), the step is not partitioned over a mesh
    (``sharded``: ``ssm_heads`` over ``tp``, or the batch over ``dp``),
    and the shapes lie on the kernels' tiles: the chunk and the state on
    the 128 lanes (a chunk's positions are the lanes of its decays), a
    group's lanes of x in whole pieces, and a group's numbers a
    position (three a head: 3R rows) within a chunk's square."""
    ratio = heads // groups
    return (attention.kernels_on() and not sharded
            and chunk % _LANES == 0 and state % _LANES == 0
            and (ratio * head_dim) % _LANES == 0
            and _pieces(ratio, head_dim) is not None
            and 3 * ratio <= chunk)


def _turn(rows):
    """[m, L] float32 with the positions on the lanes -> [L, .] with
    them on the sublanes (column c is row c), through a square transpose,
    which is what Mosaic takes: the columns from m on are nought."""
    m, l = rows.shape
    if m < l:
        rows = jnp.concatenate(
            [rows, jnp.zeros((l - m, l), rows.dtype)], axis=0)
    return rows.T


def _dot(lhs, rhs, contract):
    return lax.dot_general(lhs, rhs, (contract, ((), ())),
                           preferred_element_type=jnp.float32)


_NN = ((1,), (0,))   # [m, k] . [k, n]
_NT = ((1,), (1,))   # [m, k] . [n, k]^T
_TN = ((0,), (0,))   # [k, m]^T . [k, n]


def _split3(rows):
    """float32 [m, L] -> [3m, L]: three pieces, each a bfloat16 number,
    that add up to ``rows`` exactly, so that a product with noughts and
    ones in bfloat16 adds float32 numbers up in float32."""
    high = rows.astype(jnp.bfloat16).astype(jnp.float32)
    rest = rows - high
    mid = rest.astype(jnp.bfloat16).astype(jnp.float32)
    return jnp.concatenate([high, mid, rest - mid], axis=0)


def _sums_along(rows, upto, contract):
    """Running sums of float32 ``rows`` [R, L] along the lanes, on the
    MXU: with ``upto`` [s, t] = (s <= t) and ``_NN`` the sums from the
    chunk's start up to each position, with ``_NT`` from each position to
    the chunk's end."""
    ratio = rows.shape[0]
    parts = _dot(_split3(rows).astype(jnp.bfloat16), upto, contract)
    return parts[:ratio] + parts[ratio:2 * ratio] + parts[2 * ratio:]


def _log_decays(dt_ref, a_ref, upto, steps: int):
    """For all the chunks of a grid step at once (row i.R + r: chunk i,
    head r, positions on the lanes): dt, ``a`` beside it, and the
    cumulative log-decay from each chunk's start."""
    dt_rows = dt_ref[0].reshape(-1, dt_ref.shape[-1])
    a_rows = jnp.concatenate([a_ref[...]] * steps, axis=0)
    return dt_rows, a_rows, _sums_along(dt_rows * a_rows, upto, _NN)


def _over_lanes(cols, column: int, lanes: int):
    """Column ``column`` of ``cols`` [L, .] over ``lanes`` lanes: what the
    kernels ask of the unit that permutes lanes."""
    return jnp.broadcast_to(cols[:, column:column + 1],
                            (cols.shape[0], lanes))


def _by_head(per_head, head_of_lane):
    """[L, W] arrays, one a head of a piece -> one [L, W] that holds
    each head's over the head's own lanes."""
    out = per_head[0]
    for q, one in enumerate(per_head[1:], 1):
        out = jnp.where(head_of_lane == q, one, out)
    return out


def _fwd_kernel(x_ref, b_ref, c_ref, dt_ref, a_ref, d_ref, y_ref, *rest,
                chunk: int, ratio: int, head_dim: int, steps: int,
                keep: bool):
    """``steps`` chunks of one (batch row, group): y, the state carried
    in ``state_scr`` [N, R.P] float32 (the state of head r, transposed,
    in its lanes), and with ``keep`` the state each chunk started from."""
    from jax.experimental import pallas as pl

    states_ref, state_scr = rest if keep else (None,) + rest
    dtype = x_ref.dtype
    width, per = _pieces(ratio, head_dim)

    @pl.when(pl.program_id(2) == 0)
    def _start():
        state_scr[...] = jnp.zeros_like(state_scr)

    sub = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    lane = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = sub >= lane                                    # [t, s]
    upto = (sub <= lane).astype(jnp.bfloat16)
    head_of_lane = lax.broadcasted_iota(
        jnp.int32, (chunk, width), 1) // head_dim

    # what position s leaves in the state at its chunk's end, and what
    # the state a chunk starts from keeps
    rows = steps * ratio
    dt_rows, _, cs_rows = _log_decays(dt_ref, a_ref, upto, steps)
    w_rows = jnp.exp(cs_rows[:, chunk - 1:] - cs_rows) * dt_rows
    kept = _over_lanes(jnp.exp(cs_rows), chunk - 1, width)
    cols = _turn(jnp.concatenate([cs_rows, w_rows], axis=0))

    # the chunks one after the other in one block of code
    for i in range(steps):
        at = pl.ds(i * chunk, chunk)
        bm, cm = b_ref[0, at, :], c_ref[0, at, :]
        scores = _dot(cm, bm, _NT)                          # [t, s]
        state = state_scr[...]
        if keep:
            states_ref[0, 0, i] = state
        for piece in range(ratio // per):
            lanes = slice(piece * width, (piece + 1) * width)
            heads = range(i * ratio + piece * per,
                          i * ratio + (piece + 1) * per)
            x = x_ref[0, at, lanes]
            within, grown = [], []
            for r in heads:
                cs_t = _over_lanes(cols, r, max(chunk, width))
                decay = jnp.exp(jnp.where(
                    causal, cs_t[:, :chunk] - cs_rows[r:r + 1, :],
                    -jnp.inf))
                mixed = scores * decay * dt_rows[r:r + 1, :]
                within.append(_dot(mixed.astype(dtype), x, _NN))
                grown.append(jnp.exp(cs_t[:, :width]))
            xf = x.astype(jnp.float32)
            y = _by_head(within, head_of_lane) + _dot(
                cm, state[:, lanes].astype(dtype), _NN) * _by_head(
                    grown, head_of_lane)
            y = y + d_ref[:, lanes] * xf
            y_ref[0, at, lanes] = y.astype(dtype)
            w_s = _by_head([_over_lanes(cols, rows + r, width)
                            for r in heads], head_of_lane)
            state_scr[:, lanes] = (
                state[:, lanes] * _by_head(
                    [kept[r:r + 1, :] for r in heads], head_of_lane[:1])
                + _dot(bm, (xf * w_s).astype(dtype), _TN))


def _bwd_kernel(x_ref, b_ref, c_ref, dt_ref, a_ref, d_ref, dy_ref,
                states_ref, dx_ref, db_ref, dc_ref, ddt_ref, da_ref, dd_ref,
                dstate_scr, dz_scr, xw_scr, *,
                chunk: int, ratio: int, head_dim: int, steps: int):
    """The same chunks from the last to the first: ``dstate_scr`` carries
    the cotangent of the state at a chunk's end. A head's decays are
    rebuilt with the key position on the sublanes ([s, t]: the products
    that give dx and dB then need no transpose); what is summed over a
    head's lanes comes out of a product with the positions on the lanes,
    as ``ddt_ref`` holds them. ``dz_scr`` and ``xw_scr`` hold a chunk's
    two left operands whole, for one product each over the group's lanes."""
    from jax.experimental import pallas as pl

    dtype = x_ref.dtype
    f32 = jnp.float32
    width, per = _pieces(ratio, head_dim)
    pieces = ratio // per

    @pl.when(pl.program_id(2) == 0)
    def _start():
        dstate_scr[...] = jnp.zeros_like(dstate_scr)
        dd_ref[...] = jnp.zeros_like(dd_ref)
        da_ref[...] = jnp.zeros_like(da_ref)

    square = (chunk, chunk)
    sub = lax.broadcasted_iota(jnp.int32, square, 0)
    lane = lax.broadcasted_iota(jnp.int32, square, 1)
    later = lane >= sub                                     # [s, t]
    upto = (sub <= lane).astype(jnp.bfloat16)
    head_of_lane = lax.broadcasted_iota(
        jnp.int32, (chunk, width), 1) // head_dim
    row_of = lax.broadcasted_iota(jnp.int32, (ratio, chunk), 0)
    at_end = lax.broadcasted_iota(jnp.int32, (ratio, chunk), 1) == chunk - 1
    # row h: ones over the lanes of head h where it lies in the piece
    heads_lanes = [
        (lax.broadcasted_iota(jnp.int32, (ratio, width), 0)
         == piece * per + lax.broadcasted_iota(
             jnp.int32, (ratio, width), 1) // head_dim
         ).astype(jnp.bfloat16) for piece in range(pieces)]

    def head_sums(values, piece):
        """[L, W] float32 -> [R, L]: row h the sum over head h's lanes
        (nought for a head of another piece), in two bfloat16 pieces."""
        high = values.astype(jnp.bfloat16)
        low = (values - high.astype(f32)).astype(jnp.bfloat16)
        return (_dot(heads_lanes[piece], high, _NT)
                + _dot(heads_lanes[piece], low, _NT))

    rows = steps * ratio
    dt_rows, a_rows, cs_rows = _log_decays(dt_ref, a_ref, upto, steps)
    tail_rows = jnp.exp(cs_rows[:, chunk - 1:] - cs_rows)
    kept = _over_lanes(jnp.exp(cs_rows), chunk - 1, width)
    cols = _turn(jnp.concatenate([cs_rows, dt_rows, tail_rows], axis=0))
    dcs, ddt = [], []

    for i in reversed(range(steps)):
        at = pl.ds(i * chunk, chunk)
        bm, cm = b_ref[0, at, :], c_ref[0, at, :]
        scores = _dot(bm, cm, _NT)                          # [s, t]
        state = states_ref[0, 0, i]
        dstate = dstate_scr[...]
        state_b, dstate_b = state.astype(dtype), dstate.astype(dtype)
        dscores = jnp.zeros(square, f32)
        ddt_rows = jnp.zeros((ratio, chunk), f32)
        dcs_rows = jnp.zeros((ratio, chunk), f32)
        for piece in range(pieces):
            lanes = slice(piece * width, (piece + 1) * width)
            heads = range(i * ratio + piece * per,
                          i * ratio + (piece + 1) * per)
            x, dy = x_ref[0, at, lanes], dy_ref[0, at, lanes]
            xf, dyf = x.astype(f32), dy.astype(f32)
            decays, grown = [], []
            for r in heads:
                cs_s = _over_lanes(cols, r, max(chunk, width))
                decays.append(jnp.exp(jnp.where(
                    later, cs_rows[r:r + 1, :] - cs_s[:, :chunk],
                    -jnp.inf)))
                grown.append(jnp.exp(cs_s[:, :width]))
            dts = [_over_lanes(cols, rows + r, max(chunk, width))
                   for r in heads]
            dt_s = _by_head([one[:, :width] for one in dts], head_of_lane)
            tail_s = _by_head([_over_lanes(cols, 2 * rows + r, width)
                               for r in heads], head_of_lane)
            e_end = _by_head([kept[r:r + 1, :] for r in heads],
                             head_of_lane[:1])
            dz = dyf * _by_head(grown, head_of_lane)
            dz_b = dz.astype(dtype)
            xw = xf * (tail_s * dt_s)
            dz_scr[:, lanes] = dz_b
            xw_scr[:, lanes] = xw.astype(dtype)
            dxw = _dot(bm, dstate_b[:, lanes], _NN)
            dstate_scr[:, lanes] = (dstate[:, lanes] * e_end
                                    + _dot(cm, dz_b, _TN))
            dd_ref[0, 0, :, lanes] += jnp.sum(dyf * xf, axis=0,
                                              keepdims=True)
            dcs_rows += head_sums(
                dz * _dot(cm, state_b[:, lanes], _NN), piece)
            # what the chunk's last log-decay gets: through the state it
            # multiplies and through every position's weight
            lastly = (jnp.sum(dstate[:, lanes] * state[:, lanes], axis=0,
                              keepdims=True) * e_end
                      + jnp.sum(xw * dxw, axis=0, keepdims=True))
            moved = dxw * tail_s
            skipped = d_ref[:, lanes] * dyf
            within = []
            for q, r in enumerate(heads):
                mine = head_of_lane == q
                unweighed = (scores * decays[q]).astype(dtype)
                within.append(_dot(unweighed, dy, _NN))
                # dt_s times the cotangent of the head's mixing matrix
                dmixed = _dot(jnp.where(mine, x, jnp.zeros_like(x)), dy,
                              _NT) * dts[q][:, :chunk]
                dscores += dmixed * decays[q]
                # the log-decay's part as position t: from the matrix the
                # product above used, rounded as it was, so that it and
                # position s's part (``head_sums`` of x times ``moved``)
                # are the sums of one matrix down and across
                dcs_rows += jnp.where(
                    row_of == r - i * ratio,
                    jnp.sum(dmixed * unweighed.astype(f32), axis=0,
                            keepdims=True)
                    + jnp.where(at_end[:1], jnp.sum(
                        jnp.where(mine[:1], lastly, 0.0), axis=1,
                        keepdims=True), 0.0),
                    0.0)
            moved = moved + _by_head(within, head_of_lane)
            dx_ref[0, at, lanes] = (moved * dt_s + skipped).astype(dtype)
            # x widened again: cheaper than keeping ``xf`` over the heads
            ddt_rows += head_sums(x.astype(f32) * moved, piece)
        dscores_b = dscores.astype(dtype)
        db_ref[0, at, :] = (
            _dot(xw_scr[...], dstate_b, _NT) + _dot(dscores_b, cm, _NN)
        ).astype(dtype)
        dc_ref[0, at, :] = (
            _dot(dz_scr[...], state_b, _NT) + _dot(dscores_b, bm, _TN)
        ).astype(dtype)
        dcs.append(dcs_rows)
        ddt.append(ddt_rows)

    # the log-decay's cotangent back through its running sum to dt * a
    ddt_rows = jnp.concatenate(ddt[::-1], axis=0)
    dlog = _sums_along(
        jnp.concatenate(dcs[::-1], axis=0) - dt_rows * ddt_rows, upto, _NT)
    ddt_ref[0] = (ddt_rows + dlog * a_rows).reshape(steps, ratio, chunk)
    da_steps = jnp.sum(dlog * dt_rows, axis=1, keepdims=True)
    da_ref[0] += sum(da_steps[i * ratio:(i + 1) * ratio]
                     for i in range(steps))


def _steps(chunks: int, chunk: int, ratio: int) -> int:
    """Chunks a grid step: the most that divide the sequence's, stay
    within ``BLOCK_POSITIONS`` and whose heads' numbers (three a head
    and chunk) lie within a chunk's square when turned."""
    most = max(1, min(BLOCK_POSITIONS // chunk, chunk // (3 * ratio)))
    return max(k for k in range(1, most + 1) if chunks % k == 0)


def _operands(x, dt, a, bm, cm, d, chunk: int):
    """What both kernels read: x, B and C with a position's heads and
    groups side by side on the lanes, as ``mamba_block`` holds them; dt
    with a chunk's positions on the lanes [B, S/chunk, H, chunk] (whole
    tiles of 8 heads: the one array the kernels want another way than it
    comes); ``a`` down the sublanes; D over its head's lanes."""
    b, s, h, p = x.shape
    return (x.reshape(b, s, h * p), bm.reshape(b, s, -1),
            cm.reshape(b, s, -1),
            dt.reshape(b, s // chunk, chunk, h).transpose(0, 1, 3, 2),
            a[:, None], jnp.repeat(d, p)[None, :])


# jitted so that a step's Mamba layers share one trace and one lowering of
# each kernel (they cost seconds of set-up a layer and pass otherwise)
@functools.partial(jax.jit, static_argnames=("chunk", "keep"))
def _scan_call(x, dt, a, bm, cm, d, chunk: int, keep: bool):
    """The forward kernel -> (y like x, with ``keep`` the state each
    chunk started from [B, G, S/chunk, N, R.P] float32, else None)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, h, p = x.shape
    g, n = bm.shape[2], bm.shape[3]
    ratio, chunks = h // g, s // chunk
    steps = _steps(chunks, chunk, ratio)
    span, lanes = steps * chunk, ratio * p
    operands = _operands(x, dt, a, bm, cm, d, chunk)
    vma = jax.typeof(operands[0]).vma
    out_shape = [jax.ShapeDtypeStruct((b, s, h * p), x.dtype, vma=vma)]
    out_specs = [pl.BlockSpec((1, span, lanes), lambda b, g, j: (b, j, g))]
    if keep:
        out_shape.append(jax.ShapeDtypeStruct(
            (b, g, chunks, n, lanes), jnp.float32, vma=vma))
        out_specs.append(pl.BlockSpec(
            (1, 1, steps, n, lanes), lambda b, g, j: (b, g, j, 0, 0)))
    with kernel_trace("ssd_fwd"):
        out = pl.pallas_call(
            functools.partial(_fwd_kernel, chunk=chunk, ratio=ratio,
                              head_dim=p, steps=steps, keep=keep),
            grid=(b, g, chunks // steps),
            in_specs=[
                pl.BlockSpec((1, span, lanes), lambda b, g, j: (b, j, g)),
                pl.BlockSpec((1, span, n), lambda b, g, j: (b, j, g)),
                pl.BlockSpec((1, span, n), lambda b, g, j: (b, j, g)),
                pl.BlockSpec((1, steps, ratio, chunk),
                             lambda b, g, j: (b, j, g, 0)),
                pl.BlockSpec((ratio, 1), lambda b, g, j: (g, 0)),
                pl.BlockSpec((1, lanes), lambda b, g, j: (0, g)),
            ],
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=[pltpu.VMEM((n, lanes), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=attention.kernels_interpreted(),
            name="ssd_fwd",
        )(*operands)
    return out[0].reshape(x.shape), (out[1] if keep else None)


@functools.partial(jax.jit, static_argnames=("chunk",))
def _scan_grad_call(x, dt, a, bm, cm, d, states, dy, chunk: int):
    """The backward kernel -> the cotangents of x, dt, a, bm, cm, d."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, h, p = x.shape
    g, n = bm.shape[2], bm.shape[3]
    ratio, chunks = h // g, s // chunk
    steps = _steps(chunks, chunk, ratio)
    span, lanes = steps * chunk, ratio * p
    last = chunks // steps - 1
    operands = _operands(x, dt, a, bm, cm, d, chunk)
    vma = jax.typeof(operands[0]).vma
    wide = pl.BlockSpec((1, span, lanes), lambda b, g, j: (b, last - j, g))
    narrow = pl.BlockSpec((1, span, n), lambda b, g, j: (b, last - j, g))
    per_chunk = pl.BlockSpec((1, steps, ratio, chunk),
                             lambda b, g, j: (b, last - j, g, 0))
    a_head = pl.BlockSpec((ratio, 1), lambda b, g, j: (g, 0))
    with kernel_trace("ssd_bwd"):
        dxs, dbs, dcs, ddts, das, dds = pl.pallas_call(
            functools.partial(_bwd_kernel, chunk=chunk, ratio=ratio,
                              head_dim=p, steps=steps),
            grid=(b, g, chunks // steps),
            in_specs=[
                wide, narrow, narrow, per_chunk, a_head,
                pl.BlockSpec((1, lanes), lambda b, g, j: (0, g)),
                wide,
                pl.BlockSpec((1, 1, steps, n, lanes),
                             lambda b, g, j: (b, g, last - j, 0, 0)),
            ],
            out_specs=[
                wide, narrow, narrow, per_chunk,
                pl.BlockSpec((1, ratio, 1), lambda b, g, j: (b, g, 0)),
                pl.BlockSpec((1, 1, 1, lanes), lambda b, g, j: (b, g, 0, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((b, s, h * p), x.dtype, vma=vma),
                jax.ShapeDtypeStruct((b, s, g * n), bm.dtype, vma=vma),
                jax.ShapeDtypeStruct((b, s, g * n), cm.dtype, vma=vma),
                jax.ShapeDtypeStruct((b, chunks, h, chunk), jnp.float32,
                                     vma=vma),
                jax.ShapeDtypeStruct((b, h, 1), jnp.float32, vma=vma),
                jax.ShapeDtypeStruct((b, g, 1, lanes), jnp.float32, vma=vma),
            ],
            scratch_shapes=[pltpu.VMEM((n, lanes), jnp.float32),
                            pltpu.VMEM((chunk, lanes), x.dtype),
                            pltpu.VMEM((chunk, lanes), x.dtype)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=attention.kernels_interpreted(),
            name="ssd_bwd",
        )(*operands, dy.reshape(b, s, h * p), states)
    return (dxs.reshape(x.shape),
            ddts.transpose(0, 1, 3, 2).reshape(b, s, h), das.sum((0, 2)),
            dbs.reshape(bm.shape), dcs.reshape(cm.shape),
            dds.reshape(b, g, ratio, p).sum((0, 3)).reshape(h))


# ===========================================================================
# One op, two tiers.
# ===========================================================================


def _count(like, chunk: int, kernel: bool, which: str) -> None:
    ssd_scan_chunks.inc(like.shape[1] // chunk,
                        {"tier": "kernel" if kernel else "jnp", "pass": which})


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _scan(x, dt, a, bm, cm, d, chunk: int, kernel: bool):
    _count(x, chunk, kernel, "fwd")
    if kernel:
        return _scan_call(x, dt, a, bm, cm, d, chunk, keep=False)[0]
    return _jnp_scan(x, dt, a, bm, cm, d, chunk)


def _scan_fwd(x, dt, a, bm, cm, d, chunk: int, kernel: bool):
    _count(x, chunk, kernel, "fwd")
    if kernel:
        y, states = _scan_call(x, dt, a, bm, cm, d, chunk, keep=True)
        return y, (x, dt, a, bm, cm, d, states)
    return jax.vjp(functools.partial(_jnp_scan, chunk=chunk),
                   x, dt, a, bm, cm, d)


def _scan_bwd(chunk: int, kernel: bool, kept, dy):
    _count(dy, chunk, kernel, "bwd")
    if kernel:
        return _scan_grad_call(*kept, dy, chunk)
    return kept(dy)


_scan.defvjp(_scan_fwd, _scan_bwd)


def ssd_scan(x, dt, a, bm, cm, d, chunk: int, sharded: bool = False):
    """x [B,S,H,P], dt [B,S,H] float32 (after its softplus), a [H]
    float32 (negative), bm and cm [B,S,G,N], d [H] float32 -> y like x.
    ``chunk`` has to divide S; the state starts at nought. ``sharded``:
    the step is partitioned over a mesh (``scan_tier``)."""
    b, s, h, p = x.shape
    g, n = bm.shape[2], bm.shape[3]
    if s % chunk or h % g:
        raise ValueError(f"ssd_scan: chunk {chunk} has to divide the "
                         f"sequence {s}, and groups {g} the heads {h}")
    return _scan(x, dt.astype(jnp.float32), a.astype(jnp.float32), bm, cm,
                 d.astype(jnp.float32), chunk,
                 scan_tier(chunk, h, p, g, n, sharded))


def causal_conv1d(x, weight, bias):
    """Depthwise causal convolution along the sequence: x [B,S,C],
    weight [K,C], bias [C]; y_t = bias + sum_j weight[j] * x[t-(K-1)+j]."""
    k, s = weight.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    out = bias.astype(jnp.float32)
    for j in range(k):
        out = out + weight[j].astype(jnp.float32) \
            * padded[:, j:j + s].astype(jnp.float32)
    return out.astype(x.dtype)


# ===========================================================================
# The convolution and its SiLU, the kernel tier. The jnp form above pads a
# copy of the whole array in HBM, slices it four times at sublane offsets
# 0..3 (each realigned against the (8, 128) tile), widens all of it to
# float32, and its transpose pads four float32 cotangents back and adds
# them: 19.6 ms a layer and step at the cell's [4, 8192, 6144] for a pass
# that has to move 0.8 GB forwards and 1.2 GB backwards (PR 35). The
# kernels read a block [rows, lanes] once, take the K - 1 rows before it
# from a second BlockSpec on the same array (the 16 rows that end where the
# block starts: one bfloat16 tile), form the taps by rotating sublanes in
# registers, and round where the jnp form does once XLA has compiled it for
# a TPU: float32 sums in its order (bias, tap 0 .. K-1), the SiLU on that
# float32 (XLA keeps it through its fusion: the rounding between the two
# that the jnp form spells is excess precision to it), one rounding to the
# input's type. On the chip the forward then differs from XLA's in 0.1 % of
# the elements by a unit of the last place, where a kernel that rounds the
# sum too differs in 24 % and lies 44 % further from the float32 result.
# Device time a call at [4, 8192, 6144] of a [4, 8192, 10304] bfloat16 array
# on TPU v5 lite (PR 35, the kernels alone): forward 1.3 ms, backward 2.2,
# against 4.1 and 13.0 (forward; forward + gradient) of the jnp form.
# ===========================================================================

# a grid step's block: rows of the sequence x lanes of the channels (2 KB
# of a row in one piece: at 512 lanes the forward is 8 % slower)
CONV_ROWS = 1024
CONV_LANES = 1024
# what is worked at once, rows x lanes: 16 float32 registers an array. The
# vector unit bounds both kernels (27 operations a register forwards, 54
# backwards, four a cycle, against the 7.4 and 11 cycles a register that
# HBM leaves them), so what counts is how full the scheduler packs a loop
# body: at 8 registers an iteration the forward's is 10.0 cycles a
# register, at 16 it is 8.2 (the compiled schedules for a v5e, PR 35)
_AT_ONCE = 64
_WIDE = 256
_HALO = 16  # rows of the block before: a whole tile of a 16-bit type


def _conv_blocks(seq: int, channels: int, cuts=(), first: int = 0):
    """(rows, lanes) of a grid step's block for a sequence and channels
    that start at lane ``first`` of their array and are cut at ``cuts``,
    or None where no whole blocks tile them."""
    rows = next((r for r in (CONV_ROWS, 512, 256, 128, _AT_ONCE)
                 if seq % r == 0), None)
    lanes = next((l for l in (CONV_LANES, 512, 256, _LANES)
                  if all(c % l == 0 for c in (channels, first, *cuts))), None)
    return (rows, lanes) if rows and lanes else None


def conv_tier(seq: int, channels: int, width: int, sharded: bool = False,
              cuts=(), first: int = 0) -> bool:
    """Whether a convolution of these shapes takes the kernels: the one
    rule behind ``conv_silu``, beside ``scan_tier`` and of its form.
    Where kernels run at all (``attention.kernels_on``), the step is not
    partitioned over a mesh (``sharded``), the channels and every cut lie
    on the 128 lanes, the sequence is whole blocks of rows, and the taps
    before a row lie within the 8 rows a kernel looks back."""
    return (attention.kernels_on() and not sharded and 1 <= width <= 9
            and _conv_blocks(seq, channels, cuts, first) is not None)


def _sigmoid(pre):
    """1 / (1 + exp(-pre)) to float32's last places: the unit that takes
    exp also takes an approximate reciprocal, and one step of Newton's on
    the vector unit squares its error, three operations where a division
    is a dozen with its special cases (which a denominator in [1, 2 ** 116]
    has none of: below -80 the result is sigmoid(-80), 2e-35)."""
    from jax.experimental import pallas as pl

    over = 1.0 + jnp.exp(-jnp.maximum(pre, -80.0))
    near = pl.reciprocal(over, approx=True)
    return near * (2.0 - over * near)


def _behind(rows, before, back: int):
    """``rows`` [R, W] float32 moved down by ``back`` sublanes: row t is
    ``rows[t - back]``, and the first ``back`` rows the last of
    ``before`` [8, W]."""
    from jax.experimental.pallas import tpu as pltpu

    if not back:
        return rows
    whole = jnp.concatenate([before, rows], axis=0)
    return pltpu.roll(whole, back, 0)[8:]


def _ahead(rows, after, ahead: int):
    """Row t is ``rows[t + ahead]``; the last rows the first of
    ``after`` [8, W]."""
    from jax.experimental.pallas import tpu as pltpu

    if not ahead:
        return rows
    whole = jnp.concatenate([rows, after], axis=0)
    return pltpu.roll(whole, whole.shape[0] - ahead, 0)[:rows.shape[0]]


def _taps(rows, before, k: int):
    """The K operands of a position's sum: tap j reads K - 1 - j back."""
    return [_behind(rows, before, k - 1 - j) for j in range(k)]


def _pre_activation(taps, w_ref, b_ref, lanes):
    """bias + sum_j w[j] * tap j in float32, in the jnp form's order."""
    out = b_ref[:, lanes]
    for j, tap in enumerate(taps):
        out = out + w_ref[j:j + 1, lanes] * tap
    return out


def _halo_rows(halo_ref, lanes, first_block):
    """The 8 rows of x before the block, float32: nought where the
    sequence starts with it."""
    halo = halo_ref[0, _HALO - 8:, lanes].astype(jnp.float32)
    return jnp.where(first_block, 0.0, halo)


def _lanes_at_once(width: int) -> int:
    return _WIDE if width % _WIDE == 0 else _LANES


def _block_and_halo(rows: int, lanes: int, at: int, row):
    """The BlockSpecs of a block of x and of the ``_HALO`` rows of x that
    end where it starts (the first rows again where it starts the
    sequence: the kernels put nought there), for a grid (batch row, block
    of lanes c, j, ...): ``at`` the first block of lanes in x, ``row`` the
    block of rows a grid step j takes."""
    from jax.experimental import pallas as pl

    return [
        pl.BlockSpec((1, rows, lanes),
                     lambda b, c, j, *_: (b, row(j), at + c)),
        pl.BlockSpec((1, _HALO, lanes), lambda b, c, j, *_: (
            b, jnp.maximum(row(j) * (rows // _HALO) - 1, 0), at + c)),
    ]


def _conv_in_specs(rows: int, lanes: int, k: int, at: int, off: int, row):
    """The BlockSpecs of what both kernels read: a block of x and its
    halo (``_block_and_halo``), the weights and the bias of its lanes.
    ``at``, ``off``: the piece's first block of lanes in x and in the
    weights; ``row``: the block of rows a grid step j takes."""
    from jax.experimental import pallas as pl

    return _block_and_halo(rows, lanes, at, row) + [
        pl.BlockSpec((k, lanes), lambda b, c, j: (0, off + c)),
        pl.BlockSpec((1, lanes), lambda b, c, j: (0, off + c)),
    ]


def _conv_fwd_kernel(x_ref, halo_ref, w_ref, b_ref, y_ref):
    """One block [rows, lanes] of one batch row: ``_AT_ONCE`` rows x
    ``_WIDE`` lanes at a time, the rows from the first to the last. The
    block's lanes are a loop too, so that its body is traced once: four
    pieces of 256 spelled out cost both kernels 1.4 s of every warm
    set-up for the same schedule (PR 35)."""
    from jax.experimental import pallas as pl

    rows, width = x_ref.shape[1:]
    k = w_ref.shape[0]
    first_block = pl.program_id(2) == 0
    wide = _lanes_at_once(width)

    def some_lanes(piece, _):
        lanes = pl.ds(pl.multiple_of(piece * wide, wide), wide)

        def some_rows(i, before):
            at = pl.ds(pl.multiple_of(i * _AT_ONCE, _AT_ONCE), _AT_ONCE)
            x = x_ref[0, at, lanes].astype(jnp.float32)
            pre = _pre_activation(_taps(x, before, k), w_ref, b_ref, lanes)
            y_ref[0, at, lanes] = (pre * _sigmoid(pre)).astype(y_ref.dtype)
            return x[_AT_ONCE - 8:]

        lax.fori_loop(0, rows // _AT_ONCE, some_rows,
                      _halo_rows(halo_ref, lanes, first_block))

    lax.fori_loop(0, width // wide, some_lanes, None)


def _conv_bwd_kernel(x_ref, halo_ref, w_ref, b_ref, dy_ref, dx_ref, dw_ref,
                     db_ref, g_scr):
    """The same block, the sequence's blocks and a block's rows from the
    last to the first: the pre-activation rebuilt from x, g = dy .
    silu'(pre), dx_t = sum_j w[j] . g[t + K-1-j] with the K - 1 rows of g
    after a block carried in ``g_scr`` from the block after, and the
    weights' and the bias's sums over the rows in ``dw_ref`` [8 K, lanes]
    and ``db_ref`` [8, lanes], eight partial sums each (a sublane each),
    over all the blocks of a batch row."""
    from jax.experimental import pallas as pl

    rows, width = x_ref.shape[1:]
    k = w_ref.shape[0]
    steps = rows // _AT_ONCE
    first_block = pl.program_id(2) == pl.num_programs(2) - 1

    @pl.when(pl.program_id(2) == 0)
    def _start():
        g_scr[...] = jnp.zeros_like(g_scr)
        dw_ref[...] = jnp.zeros_like(dw_ref)
        db_ref[...] = jnp.zeros_like(db_ref)

    def by_sublane(values):
        return sum(values[r:r + 8] for r in range(0, _AT_ONCE, 8))

    wide = _lanes_at_once(width)

    def some_lanes(piece, _):
        lanes = pl.ds(pl.multiple_of(piece * wide, wide), wide)
        halo = _halo_rows(halo_ref, lanes, first_block)

        def some_rows(n, carried):
            after, dw, db = carried
            start = pl.multiple_of((steps - 1 - n) * _AT_ONCE, _AT_ONCE)
            at = pl.ds(start, _AT_ONCE)
            x = x_ref[0, at, lanes].astype(jnp.float32)
            # the 8 rows before these: the block's own, the halo's where
            # these start the block (a whole tile of the input's type read)
            own = x_ref[0, pl.ds(pl.multiple_of(jnp.maximum(
                start - _HALO, 0), _HALO), _HALO), lanes].astype(jnp.float32)
            before = jnp.where(start == 0, halo, own[_HALO - 8:])
            pre = _pre_activation(_taps(x, before, k), w_ref, b_ref, lanes)
            gate = _sigmoid(pre)
            g = dy_ref[0, at, lanes].astype(jnp.float32) * (
                gate * (1.0 + pre * (1.0 - gate)))
            # tap j's weight meets g[t + K-1-j] in d_x[t], and so does
            # x[t] in the weight's own sum (sum_t g[t] . x[t - (K-1-j)],
            # counted from the other end): one moved g serves both, and
            # the K moved copies of x are dead before g is alive
            ahead = [_ahead(g, after, k - 1 - j) for j in range(k)]
            dx = w_ref[k - 1:k, lanes] * g
            for j in range(k - 1):
                dx = dx + w_ref[j:j + 1, lanes] * ahead[j]
            dx_ref[0, at, lanes] = dx.astype(dx_ref.dtype)
            return (g[:8], [one + by_sublane(x * moved)
                            for one, moved in zip(dw, ahead)],
                    db + by_sublane(g))

        nought = jnp.zeros((8, wide), jnp.float32)
        g_first, dw, db = lax.fori_loop(
            0, steps, some_rows, (g_scr[:, lanes], [nought] * k, nought))
        g_scr[:, lanes] = g_first
        for j in range(k):
            dw_ref[0, 8 * j:8 * (j + 1), lanes] += dw[j]
        db_ref[0, :, lanes] += db

    lax.fori_loop(0, width // wide, some_lanes, None)


def _pieces_of(channels: int, cuts):
    """(first channel, width) of each piece between the cuts."""
    edges = (0, *cuts, channels)
    return [(lo, hi - lo) for lo, hi in zip(edges, edges[1:])]


# jitted for the reason ``_scan_call`` is: the Mamba layers of a step share
# one trace and one lowering of each kernel
@functools.partial(jax.jit, static_argnames=("cuts", "first"))
def _conv_call(x, weight, bias, cuts=(), first: int = 0):
    """The forward kernel, once a piece between the cuts -> the pieces."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, _ = x.shape
    k, c = weight.shape
    rows, lanes = _conv_blocks(s, c, cuts, first)
    vma = jax.typeof(x).vma
    w32, b32 = weight.astype(jnp.float32), bias.astype(jnp.float32)[None]
    out = []
    for start, width in _pieces_of(c, cuts):
        off, at = start // lanes, (first + start) // lanes
        with kernel_trace("conv_fwd"):
            out.append(pl.pallas_call(
                _conv_fwd_kernel,
                grid=(b, width // lanes, s // rows),
                in_specs=_conv_in_specs(rows, lanes, k, at, off,
                                        lambda j: j),
                out_specs=pl.BlockSpec((1, rows, lanes),
                                       lambda b, c, j: (b, j, c)),
                out_shape=jax.ShapeDtypeStruct((b, s, width), x.dtype,
                                               vma=vma),
                compiler_params=pltpu.CompilerParams(
                    dimension_semantics=("parallel", "parallel",
                                         "parallel")),
                interpret=attention.kernels_interpreted(),
                name="conv_fwd",
            )(x, x, w32, b32))
    return tuple(out)


@functools.partial(jax.jit, static_argnames=("cuts", "first"))
def _conv_grad_call(x, weight, bias, dys, cuts=(), first: int = 0):
    """The backward kernel, once a piece -> the cotangents of x, weight
    and bias."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, wide = x.shape
    k, c = weight.shape
    rows, lanes = _conv_blocks(s, c, cuts, first)
    last = s // rows - 1
    vma = jax.typeof(x).vma
    w32, b32 = weight.astype(jnp.float32), bias.astype(jnp.float32)[None]
    dxs, dws, dbs = [], [], []
    for (start, width), dy in zip(_pieces_of(c, cuts), dys):
        off, at = start // lanes, (first + start) // lanes
        block = pl.BlockSpec((1, rows, lanes),
                             lambda b, c, j: (b, last - j, c))
        with kernel_trace("conv_bwd"):
            dx, dw, db = pl.pallas_call(
                _conv_bwd_kernel,
                grid=(b, width // lanes, s // rows),
                in_specs=_conv_in_specs(
                    rows, lanes, k, at, off, lambda j: last - j) + [block],
                out_specs=[
                    block,
                    pl.BlockSpec((1, 8 * k, lanes), lambda b, c, j: (b, 0, c)),
                    pl.BlockSpec((1, 8, lanes), lambda b, c, j: (b, 0, c)),
                ],
                out_shape=[
                    jax.ShapeDtypeStruct((b, s, width), x.dtype, vma=vma),
                    jax.ShapeDtypeStruct((b, 8 * k, width), jnp.float32,
                                         vma=vma),
                    jax.ShapeDtypeStruct((b, 8, width), jnp.float32,
                                         vma=vma),
                ],
                scratch_shapes=[pltpu.VMEM((8, lanes), jnp.float32)],
                compiler_params=pltpu.CompilerParams(
                    dimension_semantics=("parallel", "parallel",
                                         "arbitrary")),
                interpret=attention.kernels_interpreted(),
                name="conv_bwd",
            )(x, x, w32, b32, dy)
        dxs.append(dx)
        dws.append(dw.reshape(b, k, 8, width).sum((0, 2)))
        dbs.append(db.sum((0, 1)))
    # nought over the lanes of x that the convolution does not read: a
    # pad that XLA fuses with the sum of x's other cotangents
    return (jnp.pad(jnp.concatenate(dxs, axis=-1),
                    ((0, 0), (0, 0), (first, wide - first - c))),
            jnp.concatenate(dws, axis=-1).astype(weight.dtype),
            jnp.concatenate(dbs, axis=-1).astype(bias.dtype))


def _jnp_conv(x, weight, bias, cuts, first):
    x = lax.slice_in_dim(x, first, first + weight.shape[1], axis=-1)
    return tuple(jnp.split(jax.nn.silu(causal_conv1d(x, weight, bias)),
                           cuts, axis=-1))


def _count_call(counter, kernel: bool, which: str) -> None:
    counter.inc(1, {"tier": "kernel" if kernel else "jnp", "pass": which})


def _count_conv(kernel: bool, which: str) -> None:
    _count_call(mamba_conv_calls, kernel, which)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _conv(x, weight, bias, cuts, first: int, kernel: bool):
    _count_conv(kernel, "fwd")
    return (_conv_call if kernel else _jnp_conv)(x, weight, bias, cuts, first)


def _conv_fwd(x, weight, bias, cuts, first: int, kernel: bool):
    _count_conv(kernel, "fwd")
    if kernel:
        return _conv_call(x, weight, bias, cuts, first), (x, weight, bias)
    return jax.vjp(functools.partial(_jnp_conv, cuts=cuts, first=first),
                   x, weight, bias)


def _conv_bwd(cuts, first: int, kernel: bool, kept, dys):
    _count_conv(kernel, "bwd")
    if kernel:
        return _conv_grad_call(*kept, dys, cuts, first)
    return kept(dys)


_conv.defvjp(_conv_fwd, _conv_bwd)


def conv_silu(x, weight, bias, cuts=(), first: int = 0,
              sharded: bool = False):
    """silu(causal_conv1d(xc, weight, bias)) for weight [K,C], bias [C]
    and the C lanes xc of x [B,S,.] that start at lane ``first`` (all of
    x where it has no more) -> [B,S,C], or with ``cuts`` its pieces along
    the channels, what ``jnp.split`` gives. Both are there so that no
    copy of XLA's stands on either side of the kernels: they read their
    lanes of the array the projection wrote and write each piece as the
    scan takes it. ``sharded``: the step is partitioned over a mesh
    (``conv_tier``)."""
    cuts = tuple(cuts)
    pieces = _conv(x, weight, bias, cuts, first, conv_tier(
        x.shape[1], weight.shape[1], weight.shape[0], sharded, cuts, first))
    return pieces if cuts else pieces[0]


# ===========================================================================
# The gate and the grouped norm behind the scan, the kernel tier. The jnp
# form below writes y * silu(z) to HBM in float32 (537 MB at both cells'
# shapes), reads it again for the groups' means of squares and a third time
# for the product with rsqrt and weight, and its transpose does the same
# several times over: 17 ms a layer and step for a pass that has to move
# 0.8 GB forwards and 1.3 GB backwards (PR 38). The kernels take a block
# [rows, lanes] of whole groups and work a few rows of one group at once,
# in float32 registers: g = y * silu(z), the mean of g^2 over the group's
# lanes (a sum along the lanes of the block as it lies: no reshape), g *
# rsqrt(mean + eps) * weight, rounded once to the input's type, as the jnp
# form rounds once. ``z`` is the first lanes of the array the projection
# wrote, indexed where it lies.
# Device time a call on TPU v5 lite (PR 38, from profiler traces, the
# kernels alone; in the step they read the same to 0.03 ms), bfloat16, at
# [4, 8192, 4096] in 8 groups of 512 lanes of a [4, 8192, 10304] array and
# at [2, 8192, 8192] in 8 groups of 1024 of a [2, 8192, 18560] one: forward
# 1.29 and 1.29 ms, backward 2.01 and 1.97, against 8.35 and 6.65 (forward)
# and 16.5 and 16.9 (forward + gradient) of the jnp form. The bytes alone
# (y, z in and the result out; y, z, dout in and dy, dz out) take 0.98 and
# 1.64 ms at the 819 GB/s the yardstick reckons with, 1.30 and 2.17 at the
# 617 GB/s that a kernel which only moves its blocks reached (PR 29): HBM
# bounds both. Their compiled schedules say as much: 8.5 and 7.6 bundles a
# float32 register forwards, 11.3 and 11.0 backwards, against the 7.05 and
# 11.75 cycles that 819 GB/s leave a register. 8 registers at once (16 rows
# of a group of 512 lanes) take the forward 70 % longer, 16 to 64 read
# within 8 %; the lanes' sums as a product with ones on the MXU, which is
# idle here, read within 3 % of this for three times the code, and with
# them a block of half the elements 4 to 7 % slower.
# ===========================================================================

# a grid step's block: at most this many elements of whole groups, about
# GATE_NORM_LANES wide (2 KB of a bfloat16 row in one piece of a copy)
GATE_NORM_BLOCK = 512 * 1024
GATE_NORM_LANES = 1024
_NORM_AT_ONCE = 32 * 1024  # elements worked at once: 32 float32 registers


def _gate_norm_blocks(seq: int, inner: int, groups: int):
    """(rows, lanes, rows worked at once) of a grid step's block for a
    sequence and ``groups`` groups over ``inner`` lanes, or None where no
    whole blocks tile them: a group whole tiles of 128 lanes, a block
    whole groups, the sequence whole blocks of rows."""
    if groups < 1 or inner % groups or (inner // groups) % _LANES:
        return None
    width = inner // groups
    per = max(k for k in range(1, groups + 1)
              if groups % k == 0 and (k == 1 or k * width <= GATE_NORM_LANES))
    lanes = per * width
    rows = next((r for r in (1024, 512, 256, 128)
                 if seq % r == 0 and (r * lanes <= GATE_NORM_BLOCK
                                      or r == 128)), None)
    if rows is None:
        return None
    at_once = next((r for r in (128, 64, 32)
                    if r * width <= _NORM_AT_ONCE), 16)
    return rows, lanes, at_once


def gate_norm_tier(seq: int, inner: int, groups: int,
                   sharded: bool = False) -> bool:
    """Whether a gate and norm of these shapes take the kernels: the one
    rule behind ``gated_group_norm``, beside ``scan_tier`` and
    ``conv_tier`` and of their form. Where kernels run at all
    (``attention.kernels_on``), the step is not partitioned over a mesh
    (``sharded``), a group's width is whole tiles of 128 lanes and the
    sequence whole blocks of rows."""
    return (attention.kernels_on() and not sharded
            and _gate_norm_blocks(seq, inner, groups) is not None)


def _some_rows_of_a_group(n, rows: int, width: int, at_once: int):
    """Step n of a block's loop -> (its rows, its group's lanes): the
    groups of the block one after the other, a group's rows ``at_once``
    at a time."""
    from jax.experimental import pallas as pl

    steps = rows // at_once
    return (pl.ds(pl.multiple_of(n % steps * at_once, at_once), at_once),
            pl.ds(pl.multiple_of(n // steps * width, _LANES), width))


def _gate_norm_fwd_kernel(y_ref, z_ref, w_ref, out_ref, *,
                          width: int, at_once: int, eps: float):
    """One block [rows, lanes] of one batch row."""
    rows, lanes = y_ref.shape[1:]

    def some_rows(n, _):
        at, group = _some_rows_of_a_group(n, rows, width, at_once)
        z = z_ref[0, at, group].astype(jnp.float32)
        g = y_ref[0, at, group].astype(jnp.float32) * (z * _sigmoid(z))
        rstd = lax.rsqrt(jnp.sum(g * g, axis=-1, keepdims=True)
                         * (1.0 / width) + eps)
        out_ref[0, at, group] = (g * rstd * w_ref[:, group]).astype(
            out_ref.dtype)

    lax.fori_loop(0, lanes // width * (rows // at_once), some_rows, None)


def _gate_norm_bwd_kernel(y_ref, z_ref, w_ref, dout_ref, dy_ref, dz_ref,
                          dw_ref, *, width: int, at_once: int, eps: float):
    """The same block: g and a group's rstd rebuilt from y and z; with dn
    = dout . weight, the cotangent of the normed value, dg = rstd . dn -
    g . rstd^3 . mean(g . dn), dy = dg . silu(z), dz = dg . y . silu'(z),
    and the weight's sums over the rows in ``dw_ref`` [8, lanes], eight
    partial sums (a sublane each) over all the blocks of a batch row."""
    from jax.experimental import pallas as pl

    rows, lanes = y_ref.shape[1:]
    f32 = jnp.float32

    @pl.when(pl.program_id(2) == 0)
    def _start():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    def some_rows(n, _):
        at, group = _some_rows_of_a_group(n, rows, width, at_once)
        y, z = y_ref[0, at, group].astype(f32), z_ref[0, at, group].astype(f32)
        dout = dout_ref[0, at, group].astype(f32)
        gate = _sigmoid(z)
        silu = z * gate
        g = y * silu
        dn = dout * w_ref[:, group]
        rstd = lax.rsqrt(jnp.sum(g * g, axis=-1, keepdims=True)
                         * (1.0 / width) + eps)
        pull = rstd * rstd * rstd * (
            jnp.sum(g * dn, axis=-1, keepdims=True) * (1.0 / width))
        dg = rstd * dn - g * pull
        dy_ref[0, at, group] = (dg * silu).astype(dy_ref.dtype)
        dz_ref[0, at, group] = (
            dg * y * (gate + silu * (1.0 - gate))).astype(dz_ref.dtype)
        normed = dout * (g * rstd)
        dw_ref[0, :, group] += sum(
            normed[r:r + 8] for r in range(0, at_once, 8))

    lax.fori_loop(0, lanes // width * (rows // at_once), some_rows, None)


def _gate_norm_specs(rows: int, lanes: int):
    """The BlockSpecs of a block of y (and of z, the first lanes of its
    array: the same blocks) and of the weight's lanes."""
    from jax.experimental import pallas as pl

    return (pl.BlockSpec((1, rows, lanes), lambda b, c, j: (b, j, c)),
            pl.BlockSpec((1, lanes), lambda b, c, j: (0, c)))


# jitted for the reason ``_scan_call`` is: the Mamba layers of a step share
# one trace and one lowering of each kernel
@functools.partial(jax.jit, static_argnames=("groups", "eps"))
def _gate_norm_call(y, z, weight, groups: int, eps: float):
    """The forward kernel -> the normed value, like y."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, inner = y.shape
    width = inner // groups
    rows, lanes, at_once = _gate_norm_blocks(s, inner, groups)
    block, w_lanes = _gate_norm_specs(rows, lanes)
    with kernel_trace("gate_norm_fwd"):
        return pl.pallas_call(
            functools.partial(_gate_norm_fwd_kernel, width=width,
                              at_once=at_once, eps=eps),
            grid=(b, inner // lanes, s // rows),
            in_specs=[block, block, w_lanes],
            out_specs=block,
            out_shape=jax.ShapeDtypeStruct(y.shape, y.dtype,
                                           vma=jax.typeof(y).vma),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel")),
            interpret=attention.kernels_interpreted(),
            name="gate_norm_fwd",
        )(y, z, weight.astype(jnp.float32)[None])


@functools.partial(jax.jit, static_argnames=("groups", "eps"))
def _gate_norm_grad_call(y, z, weight, dout, groups: int, eps: float):
    """The backward kernel -> the cotangents of y, z and weight."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, inner = y.shape
    width = inner // groups
    rows, lanes, at_once = _gate_norm_blocks(s, inner, groups)
    block, w_lanes = _gate_norm_specs(rows, lanes)
    vma = jax.typeof(y).vma
    with kernel_trace("gate_norm_bwd"):
        dy, dz, dw = pl.pallas_call(
            functools.partial(_gate_norm_bwd_kernel, width=width,
                              at_once=at_once, eps=eps),
            grid=(b, inner // lanes, s // rows),
            in_specs=[block, block, w_lanes, block],
            out_specs=[block, block,
                       pl.BlockSpec((1, 8, lanes), lambda b, c, j: (b, 0, c))],
            out_shape=[
                jax.ShapeDtypeStruct(y.shape, y.dtype, vma=vma),
                jax.ShapeDtypeStruct(y.shape, z.dtype, vma=vma),
                jax.ShapeDtypeStruct((b, 8, inner), jnp.float32, vma=vma)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=attention.kernels_interpreted(),
            name="gate_norm_bwd",
        )(y, z, weight.astype(jnp.float32)[None], dout)
    # nought over the lanes of z's array behind the gate: a pad that XLA
    # fuses with the sum of the projection's other cotangents
    return (dy, jnp.pad(dz, ((0, 0), (0, 0), (0, z.shape[-1] - inner))),
            dw.sum((0, 1)).astype(weight.dtype))


def _jnp_gate_norm(y, z, weight, groups: int, eps: float):
    """The jnp tier. A group's mean is taken over its own lanes of the
    array as it lies: reshaped to [..., groups, width], the TPU compiler
    lays the float32 product out again for the mean (PR 29)."""
    dtype = y.dtype
    z = lax.slice_in_dim(z, 0, y.shape[-1], axis=-1)
    gated = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    width = gated.shape[-1] // groups
    mean_squares = jnp.stack(
        [jnp.mean(jnp.square(gated[..., g * width:(g + 1) * width]), axis=-1)
         for g in range(groups)], axis=-1)
    scale = jnp.repeat(lax.rsqrt(mean_squares + eps), width, axis=-1)
    return (gated * scale * weight.astype(jnp.float32)).astype(dtype)


def _count_gate_norm(kernel: bool, which: str) -> None:
    _count_call(mamba_gate_norm_calls, kernel, which)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _gate_norm(y, z, weight, groups: int, eps: float, kernel: bool):
    _count_gate_norm(kernel, "fwd")
    return (_gate_norm_call if kernel else _jnp_gate_norm)(
        y, z, weight, groups, eps)


def _gate_norm_fwd(y, z, weight, groups: int, eps: float, kernel: bool):
    _count_gate_norm(kernel, "fwd")
    if kernel:
        return _gate_norm_call(y, z, weight, groups, eps), (y, z, weight)
    return jax.vjp(functools.partial(_jnp_gate_norm, groups=groups, eps=eps),
                   y, z, weight)


def _gate_norm_bwd(groups: int, eps: float, kernel: bool, kept, dout):
    _count_gate_norm(kernel, "bwd")
    if kernel:
        return _gate_norm_grad_call(*kept, dout, groups, eps)
    return kept(dout)


_gate_norm.defvjp(_gate_norm_fwd, _gate_norm_bwd)


def gated_group_norm(y, z, weight, groups: int, eps: float,
                     sharded: bool = False):
    """RMSNorm over each of ``groups`` slices of the last axis of
    y * silu(z) (the norm comes after the gate), times ``weight``, for y
    [B,S,C] and the gate z in the first C lanes of an array [B,S,.] (all
    of it where it has no more): the kernels read the gate where the
    projection wrote it, so that no copy of XLA's stands in front of
    them. ``sharded``: the step is partitioned over a mesh
    (``gate_norm_tier``)."""
    return _gate_norm(y, z, weight, groups, float(eps), gate_norm_tier(
        y.shape[1], y.shape[-1], groups, sharded))
