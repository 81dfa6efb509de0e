"""Flash attention: three Pallas TPU kernels and a blockwise tier behind
one op with a custom VJP.

The hot op of the model family. Two tiers behind one call:

  flash_attention(q, k, v, causal=...)
    -> Pallas kernels: the forward (K/V of a head resident in VMEM, the
       loop over the keys inside the kernel, online softmax, O(S)
       memory) and the backward's dq and dk/dv (flash-attention-2 split,
       the other side of the product resident and the loop over it inside
       the kernel, as the forward; ``attn_delta`` sums out * dO for both);
    -> blockwise lax.scan implementation (same math, XLA-fused): the CPU
       path, the path of shapes that do not tile, and the kernels' oracle.

Which a shape takes is ``kernel_tiers``' to say, from the platform
(``kernels_on``) and the shape alone; nothing a user sets moves it. Both
tiers recompute p from the saved logsumexp, so training never
materializes the [S, S] attention matrix.

A causal call may give a ``window``: query i then sees the keys j with
i - window < j <= i (the key itself counted). The kernels visit only the
sub-blocks of that band: the two edges of it (the diagonal, and the
lower edge ``window`` keys under it) build the mask, what lies between
them does not, what lies outside is never run. A windowed call's kernels
are named ``swa_fwd``, ``swa_bwd_dq``, ``swa_bwd_dkdv``; a window that is
absent or at least the number of keys is the causal call, kernels and
names as they were.

Layouts: the op takes and returns [batch, seq, heads, head_dim] (matches
parallel/ring_attention.py, which wraps this per-shard); ``repeat_kv``
copies K and V to the query heads for it. What the kernels index is
``lane_layout``'s to say, from the shape and whether the call is a
mesh's. A head of whole 128-lane tiles (head_dim % 128 == 0) is read
where the projections wrote it: the arrays handed to ``pallas_call`` are
[batch, seq, heads * head_dim], a rename of the op's arguments, and head
h is the block of head_dim lanes at lane offset h * head_dim (a block's
rows lie heads * head_dim * 2 B apart, in whole 4 KB tiles of HBM);
nothing is copied on the way in or out, so a caller that keeps its
arrays [batch, seq, heads * head_dim] from its projections on
(models/transformer.py's attention kinds) has no pass over HBM between
them and the kernels. A narrower head (the forward at d 64) is part of a
tile, which no block may be: its arrays are copied heads-major to
[batch * heads, seq, head_dim], the layout of every call before PR 33,
and so are a shard's on a mesh (``lane_layout`` says why). lse and delta
are [batch * heads, 1, seq] for both; ``flash_calls{kernel, layout}``
counts which a traced call took. On a mesh with batch and heads sharded,
flash_attention_on_mesh gives each kernel the shard_map the TPU compiler
needs.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from ray_tpu.observability.device_programs import kernel_trace
from ray_tpu.observability.metrics import (
    flash_bwd_subblocks,
    flash_calls,
    flash_fwd_subblocks,
)

# Per-path block defaults, resolved in fwd_block_plan / bwd_block_plan
# when the caller passes None. The BLOCKWISE tier keeps 128: its fp32
# [B,H,Sq,block_k] logits temporary scales with block_k.
#
# The three KERNELS share one shape of loop: 512 rows of one side a grid
# step against the other side of a whole head resident in VMEM, and a
# loop over 512-wide sub-blocks inside the kernel (fwd_block_plan,
# bwd_block_plan). Device time a call from profiler traces on TPU v5
# lite, bf16, causal, and the share of the roofline benchmark/flops.py
# reckons; the parent of each is the same kernel with (256, 512) blocks
# and its loop on the grid.
#
# `flash_fwd`, 30 Sep 2026 (PR 25), parent -> this kernel:
#   B4-S4096-H32-D128 (cell s4096)   11.68 at 23.9 % -> 5.22 at 53.5 %
#   B32-S512-H32-D128 (cell s512)     3.07 at 13.4 % -> 2.05 at 20.1 %
#   B2-S4096-H16-D128 (a chip of 4)   2.70 at 25.9 % -> 1.28 at 54.4 %
#   B4-S2048-H16-D64  (the MoE's)     1.55 at 11.2 % -> 0.85 at 20.6 %
# Of (block_q, block_k) in {128..2048} x {128..1024} nothing beat
# (512, 512) at any of the four: at S4096-D128 (256, 512) 5.88 ms,
# (1024, 512) 5.64, (512, 1024) 5.95, (512, 256) 7.27, (256, 256) 9.75.
# A pass of the loop costs 1.01 us per 512 x 512 logits whatever the
# blocks from 512 up (narrower ones pay the loop's fixed cost more
# often, wider ones waste more above the diagonal), and taking the
# exponent, the row max, the row sum or the scale out of it moves that
# by under 3 %: the vector work hides behind the two products. The chip
# repeats these to under 0.1 %.
#
# `flash_bwd_dq`, `flash_bwd_dkdv`, 1 Oct 2026 (PR 27), the kernels
# alone, parent -> these, ms a call (K/V at the query's 32 heads):
#   B4-S4096-H32-D128   9.72, 13.04 -> 6.10 (68.6 %), 7.49 (74.5 %)
#   B32-S512-H32-D128   1.97,  2.72 -> 1.89, 1.74 (one masked sub-block
#                                      a head: nothing to skip)
#   B2-S4096-H16-D128   2.12,  3.18 -> 1.52 (68.7 %), 1.81 (77.0 %)
#   B4-S8192-H32-D128  34.63, 49.47 -> 23.39 (71.6 %), 27.81 (80.3 %)
#   B4-S2048-H16-D64    1.15,  1.76 -> 0.93, 1.02 (called directly:
#                                      kernel_tiers sends d 64 blockwise)
#   B4-S2048-H16-D128, not causal: 1.46, 2.57 -> 1.36 (77 %), 1.58 (88 %)
# A pass costs 1.32 us (dq, three products: 1.02 us of MXU time) and
# 1.63 us (dk/dv, four: 1.36 us). dk/dv on the transposed logits
# [keys, q] beats the same loop on [q, keys] (a relayout of lse and delta
# and two transposed left operands a pass) by 12 %: 7.49 against 8.54 ms.
# Of (block_q, block_k) in {256, 512, 1024}^2 at S4096, dq + dk/dv:
# (512, 512) 13.59 ms, (1024, 1024) 13.47 at 28 MiB of VMEM, (512, 1024)
# 13.88, (1024, 512) 14.04, (1024, 256) 15.36, (512, 256) 15.79,
# (256, 1024) 15.72, (256, 512) 16.71, (256, 256) 21.71; at S512 the
# whole (512, 512) 3.63 against 4.43-5.18 for the halves; at S8192
# (1024, 512) takes 0.96 ms off dq's 23.39 and (512, 1024) 0.60 off
# dk/dv's 27.81, under 2 % of the pair. The whole of S8192 resident
# (8 MiB, one major block) gives dq 21.70 and dk/dv 27.27.
#
# The two layouts, 3 Oct 2026 (PR 33), the kernels alone, ms a call,
# heads-major [B*H, S, D] (a head's rows contiguous; every call before
# PR 33, with a transposing copy an operand around it) -> lanes
# [B, S, H*D] (no copy): fwd; dq; dk/dv
#   B4-S4096-H32-D128        5.224 -> 5.259; 6.102 -> 6.141; 7.490 -> 7.578
#   B32-S512-H32-D128        2.053 -> 2.090; 1.893 -> 1.945; 1.735 -> 1.858
#   B2-S4096-H16-D128        1.255 -> 1.304; 1.524 -> 1.565; 1.813 -> 1.851
#   B4-S8192-H32-D128      17.939 -> 18.129; 23.397 -> 23.775; 27.813 -> 27.988
#   B2-S8192-H32-D128 W1024  4.090 -> 4.177; 4.920 -> 5.074; 5.623 -> 5.739
#   B2-S8192-H20-D256      10.666 -> 10.844; 14.270 -> 14.487; 16.384 -> 16.502
# The strided blocks cost the kernels 0.6-1.7 % at the one-chip cells'
# long shapes, 2-4 % at a chip of four's and under a window, 7 % for
# dk/dv at S 512 (one grid step a head: nothing hides its copies): 0.4 to
# 2.7 points of a roofline. What they save is outside the kernels: a
# transposing copy an operand and result, forward, recompute and
# backward (PERF.md section 6, PR 33, for the steps' readings); on a
# mesh, where XLA rotates q and k and turns them anyway, nothing is
# saved and a shard keeps the heads-major copy.
# The attention block, grad of a checkpointed block, ms a call, the same
# attention kinds over: these kernels / the heads-major indexing with
# its copies / projections that write heads-major themselves
#   the GLM cell's latent block B2-S8192-H20-D256   70.20 / 75.34 / 74.49
#   the Mistral cells' block B4-S4096-H32-D128      57.31 / 58.19 / 61.59
#   the same at B32-S512                            41.16 / 41.89 / 45.31
DEFAULT_BLOCK_Q = None
DEFAULT_BLOCK_K = None
BLOCKWISE_BLOCK_K = 128
# The kernels' own (the backward pair reads the forward's): rows of the
# grid's side a grid step, of the resident side a pass of the inner loop,
# the longest sequence taken as one block when no size divides it, and the
# VMEM that the two resident arrays of one head may hold (K and V; for
# dk/dv q and dO), both double-buffered.
FWD_BLOCK_Q = 512
FWD_BLOCK_K = 512
FWD_WHOLE_BLOCK = 256
FWD_KV_VMEM_BYTES = 4 * 1024 * 1024
_VMEM_DEFAULT_LIMIT = 16 * 1024 * 1024
_LANES = 128
_NEG_INF = -1e30


# Test hook: force the Pallas kernels through the interpreter so the
# CPU suite exercises kernel code paths (pl.pallas_call(interpret=True)).
_FORCE_INTERPRET = False


def kernels_on() -> bool:
    """Whether this process runs Pallas TPU kernels at all: its backend
    is a TPU (or a test has put them under the interpreter). What
    ops/grouped.py asks too."""
    return _FORCE_INTERPRET or jax.default_backend() == "tpu"


def kernels_interpreted() -> bool:
    """Whether a test has put the kernels under Pallas's interpreter:
    what a kernel outside this file passes its ``pallas_call``."""
    return _FORCE_INTERPRET


# ===========================================================================
# Blockwise pure-JAX implementation (oracle + CPU path). Returns (out, lse).
# ===========================================================================


def _pad_kv(k, v, block_k: int):
    """Zero-pad K/V so every block slice is in-bounds — a clamped
    dynamic_slice on a partial final block would attribute rows to wrong
    key positions (the `k_pos < sk` mask handles the padding)."""
    sk = k.shape[1]
    pad = (-sk) % block_k
    if pad:
        cfg = [(0, 0), (0, pad), (0, 0), (0, 0)]
        k = jnp.pad(k, cfg)
        v = jnp.pad(v, cfg)
    return k, v


def _seen(q_pos, k_pos, window: Optional[int]):
    """Whether a query position sees a key position (arrays that
    broadcast against each other): the causal pairs, inside the window
    where there is one. What a masked sub-block of the kernels keeps too,
    one body for both edges of the band: a window under a block's width
    puts both in one sub-block."""
    if window:
        return (q_pos >= k_pos) & (q_pos - k_pos < window)
    return q_pos >= k_pos


def _blockwise_fwd(q, k, v, causal: bool, sm_scale: float, block_k: int,
                   window: Optional[int] = None):
    b, sq, h, d = q.shape
    sk = k.shape[1]
    block_k = min(block_k, sk)
    num_kb = (sk + block_k - 1) // block_k
    k, v = _pad_kv(k, v, block_k)
    qf = q.astype(jnp.float32)
    q_pos = jnp.arange(sq)

    def kv_step(carry, kb):
        acc, m_run, l_run = carry
        start = kb * block_k
        k_blk = lax.dynamic_slice_in_dim(k, start, block_k, axis=1)
        v_blk = lax.dynamic_slice_in_dim(v, start, block_k, axis=1)
        logits = jnp.einsum("bqhd,bkhd->bhqk", qf,
                            k_blk.astype(jnp.float32)) * sm_scale
        k_pos = start + jnp.arange(block_k)
        valid = k_pos < sk
        if causal:
            # a row whose keys of this block all lie under its window
            # gathers nonsense here, which the first block that holds a
            # key it sees wipes (alpha = exp(-1e30 - m) = 0): its own
            # key's block comes last
            valid = valid[None, :] & _seen(q_pos[:, None], k_pos[None, :],
                                            window)
        else:
            valid = jnp.broadcast_to(valid[None, :], (sq, block_k))
        logits = jnp.where(valid[None, None], logits, _NEG_INF)
        m_new = jnp.maximum(m_run, jnp.max(logits, axis=-1))
        p = jnp.exp(logits - m_new[..., None])
        alpha = jnp.exp(m_run - m_new)
        l_new = alpha * l_run + jnp.sum(p, axis=-1)
        acc = (acc * jnp.transpose(alpha, (0, 2, 1))[..., None]
               + jnp.einsum("bhqk,bkhd->bqhd", p,
                            v_blk.astype(jnp.float32)))
        return (acc, m_new, l_new), None

    # derive the initial carries from the inputs so their device-varying
    # set matches the body under any enclosing shard_map (see
    # parallel/ring_attention.py for the same pattern)
    acc0 = jnp.zeros_like(qf)
    base = jnp.transpose(qf.sum(-1), (0, 2, 1)) * 0.0
    m0 = base + _NEG_INF
    l0 = base
    (acc, m_run, l_run), _ = lax.scan(
        kv_step, (acc0, m0, l0), jnp.arange(num_kb))
    l_safe = jnp.maximum(l_run, 1e-20)
    out = acc / jnp.transpose(l_safe, (0, 2, 1))[..., None]
    lse = m_run + jnp.log(l_safe)  # [B, H, Sq]
    return out.astype(q.dtype), lse


def _blockwise_bwd(q, k, v, out, lse, dout, causal: bool, sm_scale: float,
                   block_k: int, window: Optional[int] = None):
    """dq/dk/dv from saved lse, one KV block at a time."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    block_k = min(block_k, sk)
    num_kb = (sk + block_k - 1) // block_k
    k_pad, v_pad = _pad_kv(k, v, block_k)
    qf, of, dof = (x.astype(jnp.float32) for x in (q, out, dout))
    delta = jnp.einsum("bqhd,bqhd->bhq", of, dof)  # [B,H,Sq]
    q_pos = jnp.arange(sq)

    def kv_step(carry, kb):
        dq_acc, dk_acc, dv_acc = carry
        start = kb * block_k
        k_blk = lax.dynamic_slice_in_dim(k_pad, start, block_k, axis=1
                                         ).astype(jnp.float32)
        v_blk = lax.dynamic_slice_in_dim(v_pad, start, block_k, axis=1
                                         ).astype(jnp.float32)
        logits = jnp.einsum("bqhd,bkhd->bhqk", qf, k_blk) * sm_scale
        k_pos = start + jnp.arange(block_k)
        valid = k_pos < sk
        if causal:
            valid = valid[None, :] & _seen(q_pos[:, None], k_pos[None, :],
                                            window)
        else:
            valid = jnp.broadcast_to(valid[None, :], (sq, block_k))
        p = jnp.where(valid[None, None],
                      jnp.exp(logits - lse[..., None]), 0.0)  # [B,H,q,k]
        dv_blk = jnp.einsum("bhqk,bqhd->bkhd", p, dof)
        dp = jnp.einsum("bqhd,bkhd->bhqk", dof, v_blk)
        ds = p * (dp - delta[..., None]) * sm_scale
        dq_acc = dq_acc + jnp.einsum("bhqk,bkhd->bqhd", ds, k_blk)
        dk_blk = jnp.einsum("bhqk,bqhd->bkhd", ds, qf)
        dk_acc = lax.dynamic_update_slice_in_dim(dk_acc, dk_blk, start,
                                                 axis=1)
        dv_acc = lax.dynamic_update_slice_in_dim(dv_acc, dv_blk, start,
                                                 axis=1)
        return (dq_acc, dk_acc, dv_acc), None

    dq0 = jnp.zeros_like(qf)
    dk0 = jnp.zeros_like(k_pad, dtype=jnp.float32)
    dv0 = jnp.zeros_like(v_pad, dtype=jnp.float32)
    (dq, dk, dv), _ = lax.scan(kv_step, (dq0, dk0, dv0), jnp.arange(num_kb))
    dk = dk[:, :sk]
    dv = dv[:, :sk]
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


# ===========================================================================
# Pallas TPU forward kernel.
# ===========================================================================


def _lanes(x, n: int):
    """A lane-replicated [rows, 128] value laid over ``n`` columns: a
    prefix of its lanes, or whole vregs side by side where ``n`` is a
    multiple of the 128 (no vector work either way); one lane broadcast
    for any other width."""
    if n <= _LANES:
        return x[:, :n]
    if n % _LANES == 0:
        return jnp.tile(x, (1, n // _LANES))
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _band_of_q_block(qi, block_q: int, block_k: int, window: int,
                     maximum=max):
    """The key sub-blocks that q block ``qi`` visits under a window, as
    four bounds counted from the sequence's start: [lo, upto) hold a key
    some row of the block sees; of them those before ``edge`` hold a
    (row, key) pair the window leaves out, those from ``below`` on one
    the diagonal leaves out. ``maximum``: ``jnp.maximum`` where ``qi`` is
    traced (every dividend is kept at nought or above)."""
    r0 = qi * block_q
    lo = maximum(r0 - window + 1, 0) // block_k
    edge = maximum(r0 + block_q - 1 - window + block_k, 0) // block_k
    below = (r0 + 1) // block_k
    upto = (r0 + block_q - 1) // block_k + 1
    return lo, edge, below, upto


def _band_of_k_block(ki, block_q: int, block_k: int, window: int):
    """The mirror of ``_band_of_q_block`` for the q sub-blocks that k
    block ``ki`` visits: [first, upto) hold a row that sees some key of
    the block; those before ``below`` cross the diagonal, those from
    ``edge`` on the window's lower edge."""
    c0 = ki * block_k
    first = c0 // block_q
    below = (c0 + block_k + block_q - 2) // block_q
    edge = (c0 + window) // block_q
    upto = (c0 + block_k + window - 2) // block_q + 1
    return first, below, edge, upto


def _q_block_loops(qi, mi, sub_block, *, block_q: int, block_k: int,
                   num_sub: int, window: Optional[int]):
    """The forward's and dq's loops over the key sub-blocks of major
    block ``mi`` for q block ``qi`` of a causal call."""
    first = mi * num_sub
    if window is None:
        # of the sub-blocks before this major block's end, `below` lie
        # wholly at or under the diagonal of every row of the q block and
        # `upto` reach it: [below, upto) cross it, the rest are never run
        below = jnp.clip((qi * block_q + 1) // block_k - first, 0, num_sub)
        upto = jnp.clip((qi * block_q + block_q - 1) // block_k + 1 - first,
                        0, num_sub)
        lax.fori_loop(0, below, sub_block(False), None)
        lax.fori_loop(below, upto, sub_block(True), None)
        return
    # the band: [lo, edge) cross its lower edge, [below, upto) the
    # diagonal, what lies between builds no mask, the rest is never run
    lo, edge, below, upto = _band_of_q_block(qi, block_q, block_k, window,
                                             jnp.maximum)
    lo = jnp.clip(lo - first, 0, num_sub)
    upto = jnp.clip(upto - first, 0, num_sub)
    edge = jnp.clip(edge - first, lo, upto)
    below = jnp.clip(below - first, edge, upto)
    lax.fori_loop(lo, edge, sub_block(True), None)
    lax.fori_loop(edge, below, sub_block(False), None)
    lax.fori_loop(below, upto, sub_block(True), None)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
                  acc_scr, *, causal: bool, sm_scale: float, block_q: int,
                  block_k: int, num_sub: int, num_major: int,
                  window: Optional[int] = None):
    """One q block against one resident K/V major block of ``num_sub``
    compute sub-blocks of ``block_k`` keys. The loop over the keys is in
    here, not in the grid: its trip count ends at the diagonal (and starts
    at the window's lower edge), and only the sub-blocks an edge crosses
    build the mask. A row whose keys of a sub-block all lie under its
    window gathers nonsense there (max -1e30, p 1), which the next
    sub-block that holds a key it sees wipes (alpha 0); the row's own
    key's sub-block is the last."""
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    mi = pl.program_id(2)
    d = q_ref.shape[-1]

    @pl.when(mi == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def sub_block(masked: bool):
        def body(j, carry):
            # operands stay in their NATIVE dtype: the MXU multiplies
            # bf16 at 4x its fp32 rate and accumulates in fp32 via
            # preferred_element_type
            if num_sub == 1:
                k, v = k_ref[0], v_ref[0]
            else:
                keys = pl.ds(pl.multiple_of(j * block_k, block_k), block_k)
                k, v = k_ref[0, keys, :], v_ref[0, keys, :]
            logits = jax.lax.dot_general(
                q_ref[0], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            if masked:
                q_pos = qi * block_q + lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0)
                k_pos = (mi * num_sub + j) * block_k + lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 1)
                logits = jnp.where(_seen(q_pos, k_pos, window), logits,
                                   _NEG_INF)
            # max and sum stay [block_q, 128], every lane the row's
            # value: no relayout between a column and a row per step
            m_prev = m_scr[:]
            m_new = jnp.maximum(
                m_prev, jnp.max(logits, axis=-1, keepdims=True))
            p = jnp.exp(logits - _lanes(m_new, block_k))
            alpha = jnp.exp(m_prev - m_new)
            l_scr[:] = alpha * l_scr[:] + jnp.sum(p, axis=-1, keepdims=True)
            m_scr[:] = m_new
            acc_scr[:] = (acc_scr[:] * _lanes(alpha, d)
                          + jax.lax.dot_general(
                              # P in the value dtype for a full-rate MXU
                              # pass; the accumulator itself stays fp32
                              p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32))
            return carry
        return body

    if causal:
        _q_block_loops(qi, mi, sub_block, block_q=block_q, block_k=block_k,
                       num_sub=num_sub, window=window)
    else:
        lax.fori_loop(0, num_sub, sub_block(False), None)

    @pl.when(mi == num_major - 1)
    def _finalize():
        l_safe = jnp.maximum(l_scr[:], 1e-20)
        o_ref[0] = (acc_scr[:] / _lanes(l_safe, d)).astype(o_ref.dtype)
        lse = m_scr[:] + jnp.log(l_safe)
        lse_ref[0] = lse[:, 0][None, :]


def _causal_kv_index_map(block_q: int, block_k: int, num_kb: int,
                         window: Optional[int] = None):
    """The row-block index of K/V under a (bh, qi, ki) grid with the
    causal fetch-trim: K/V (major) blocks wholly above the diagonal of
    q block ``qi`` run no sub-block, so their index is clamped to the
    q block's last needed one — an unchanged index between grid steps
    makes the Pallas pipeline elide the copy. The outer min with
    num_kb-1 covers sq > sk, where trailing q rows' diagonal lies beyond
    the last K block. Under a window the blocks wholly under the band
    are clamped up to its first one likewise. Shared by the forward and
    dq kernels; ``_causal_q_index_map`` is its mirror for dk/dv. Where
    the block of that row index lies in the array is the layout's to say
    (``_layout_of``)."""

    def index(qi, ki):
        kmax = jnp.minimum((qi * block_q + block_q - 1) // block_k,
                           num_kb - 1)
        ki = jnp.minimum(ki, kmax)
        if window:
            ki = jnp.maximum(
                ki, jnp.maximum(qi * block_q - window + 1, 0) // block_k)
        return ki

    return index


class _Layout(NamedTuple):
    """How one call's [B, S, H, D] arrays reach the kernels
    (``_layout_of``)."""
    name: str        # ``flash_calls``' layout
    enter: Callable  # [B, S, H, D] -> the array a ``pallas_call`` takes
    leave: Callable  # and back
    shape: Callable  # rows -> that array's shape
    block: Callable  # (bh, row block) -> the index of a (1, rows, D) block


def lane_layout(head_dim: int, sharded: bool = False) -> bool:
    """The one rule of what lies between the projections and the kernels:
    whether a head is read as a block of lanes of the [B, S, H*D] array a
    projection writes (``_layout_of``), which is also whether K/V are
    copied to the query heads along the lanes (``repeat_kv``) and whether
    q and k are rotated there (``ops.layers.rope_tier``).

    A head has to be whole 128-lane tiles (a narrower one would be part
    of a tile, which no block may be), and the arrays a chip's own, not
    ``sharded`` over a mesh. On a mesh q and k reach the kernels from
    XLA's own rotation (the partitioner refuses ``rope_lanes``' kernel
    outside a shard_map), which lays them S-minor and turns them whatever
    the kernels index; turned heads-major the blocks are contiguous and
    the kernels 2-4 % faster at a shard's shape, and K/V are copied to
    the query heads by a broadcast that writes that layout itself. With
    lanes the four-chip cell read 17 819 tokens/s against 18 013, a cell
    whose runs spread by 0.003 % (PERF.md section 6, PR 33), so a mesh
    keeps the heads-major copy."""
    return head_dim % _LANES == 0 and not sharded


def _layout_of(q) -> _Layout:
    """The layout the kernels index for a call whose arrays are like
    ``q`` [B, S, H, D] (``lane_layout``). Lanes: the array is [B, S, H*D]
    (a rename, no copy) and head ``bh % h`` of batch row ``bh // h`` is
    the block of ``d`` lanes at lane offset ``(bh % h) * d``. Else the
    arrays are copied heads-major to [B*H, S, D]. The grid, the kernels'
    bodies and the [B*H, 1, S] rows of lse and delta are the same for
    both. A call is a mesh's where its arrays vary over a mesh axis: the
    body of a shard_map, ``flash_attention_on_mesh``'s or a sequence-
    parallel one's."""
    b, _, h, d = q.shape
    if lane_layout(d, sharded=bool(jax.typeof(q).vma)):
        return _Layout(
            "lanes",
            lambda x: x.reshape(b, x.shape[1], h * d),
            lambda x: x.reshape(b, x.shape[1], h, d),
            lambda rows: (b, rows, h * d),
            lambda bh, row: (bh // h, row, bh % h))
    return _Layout(
        "heads_major",
        lambda x: x.transpose(0, 2, 1, 3).reshape(b * h, x.shape[1], d),
        lambda x: x.reshape(b, h, x.shape[1], d).transpose(0, 2, 1, 3),
        lambda rows: (b * h, rows, d),
        lambda bh, row: (bh, row, 0))


class FwdPlan(NamedTuple):
    """What ``_pallas_fwd`` lowers for one shape (``fwd_block_plan``)."""
    block_q: int        # q rows a grid step
    block_k: int        # keys a compute sub-block (one pass of the loop)
    block_k_major: int  # keys resident in VMEM a grid step
    grid_steps: int     # a head: q blocks x K/V major blocks
    unmasked: int       # sub-blocks a head run without building a mask
    masked: int         # sub-blocks a head the diagonal crosses
    kv_bytes: int       # K and V bytes a head fetched from HBM
    vmem_bytes: int     # the kernel's VMEM buffers, what Mosaic may use
    edge: int = 0       # sub-blocks a head on a window's lower edge alone
    window: Optional[int] = None  # the keys a query sees; None: all causal


def _fwd_blocks(sq: int, sk: int):
    """The forward's (q block, compute sub-block) for a shape: the
    largest of the preferred sizes, halved down to the 128 lanes, that
    divides the sequence; a short sequence that none divides is one
    block; None where neither holds."""
    def fit(n, prefer):
        size = prefer
        while size >= _LANES:
            if n % size == 0:
                return size
            size //= 2
        return n if n <= FWD_WHOLE_BLOCK else None

    return fit(sq, FWD_BLOCK_Q), fit(sk, FWD_BLOCK_K)


def _plan_blocks(sq: int, sk: int, block_q: Optional[int],
                 block_k: Optional[int]):
    """(q block, k block) of a kernel's sub-blocks: the caller's where
    named, by the shape otherwise; None where they do not tile."""
    auto_q, auto_k = _fwd_blocks(sq, sk)
    bq = min(block_q, sq) if block_q else auto_q
    bk = min(block_k, sk) if block_k else auto_k
    if bq and bk and _pallas_tileable(sq, sk, bq, bk):
        return bq, bk
    return None


def _resident_blocks(num_blocks: int, block: int, head_dim: int,
                     itemsize: int, vmem_bytes: int) -> int:
    """How many of a side's ``num_blocks`` blocks make one major block:
    the largest divisor whose two arrays (K and V, or q and dO), each
    double-buffered by the pipeline, fit ``vmem_bytes``; at least one."""
    per_block = 2 * 2 * block * head_dim * itemsize
    return max(n for n in range(1, num_blocks + 1) if num_blocks % n == 0
               and (n == 1 or n * per_block <= vmem_bytes))


def _mask_counts(sq: int, sk: int, bq: int, bk: int, causal: bool,
                 window: Optional[int] = None):
    """(sub-blocks a head run without building a mask, sub-blocks the
    diagonal crosses, sub-blocks that only a window's lower edge
    crosses), as the kernels' loops count them (causal: q row i sees keys
    <= i, whatever sq and sk are; under a window only the band's
    sub-blocks are run or counted)."""
    num_qb, num_kb = sq // bq, sk // bk
    if not causal:
        return num_qb * num_kb, 0, 0
    unmasked = masked = on_edge = 0
    for qi in range(num_qb):
        lo, edge, below, upto = (
            _band_of_q_block(qi, bq, bk, window) if window
            else (0, 0, (qi * bq + 1) // bk, (qi * bq + bq - 1) // bk + 1))
        upto = min(upto, num_kb)
        lo = min(lo, upto)
        below = min(max(below, lo), upto)
        edge = min(max(edge, lo), below)
        on_edge += edge - lo
        unmasked += below - edge
        masked += upto - below
    return unmasked, masked, on_edge


def fwd_block_plan(sq: int, sk: int, head_dim: int, causal: bool,
                   itemsize: int = 2, block_q: Optional[int] = None,
                   block_k: Optional[int] = None,
                   kv_vmem_bytes: int = FWD_KV_VMEM_BYTES,
                   window: Optional[int] = None) -> Optional[FwdPlan]:
    """The forward kernel's tiling as a pure function of the shape, or
    None where the shape does not tile (the blockwise tier's).

    The K/V major block is as many sub-blocks as ``kv_vmem_bytes`` holds
    of K and V, each double-buffered by the pipeline: the whole sequence
    at S4096-D128 bf16, so K/V are read once a head."""
    blocks = _plan_blocks(sq, sk, block_q, block_k)
    if blocks is None:
        return None
    bq, bk = blocks
    num_sub = _resident_blocks(sk // bk, bk, head_dim, itemsize,
                               kv_vmem_bytes)
    major = num_sub * bk
    num_qb, num_major = sq // bq, sk // major
    unmasked, masked, on_edge = _mask_counts(sq, sk, bq, bk, causal, window)
    fetches = 0
    held = None  # the major block the pipeline last copied for this head
    for qi in range(num_qb):
        last = min((qi * bq + bq - 1) // major, num_major - 1)
        start = max(qi * bq - window + 1, 0) // major if window else 0
        for mi in range(num_major):
            want = max(min(mi, last), start) if causal else mi
            fetches += want != held
            held = want
    f32 = 4
    vmem = (2 * 2 * major * head_dim * itemsize     # K, V: two buffers each
            + 2 * 2 * bq * head_dim * itemsize      # q, out: the same
            + 2 * bq * f32                          # lse
            + (2 * _LANES + head_dim) * bq * f32    # max, sum, accumulator
            + 4 * bq * bk * f32)                    # logits, p and their kin
    return FwdPlan(bq, bk, major, num_qb * num_major, unmasked, masked,
                   fetches * 2 * major * head_dim * itemsize, vmem, on_edge,
                   window)


def _vmem_limit(needed: int) -> Optional[int]:
    """Mosaic's own limit (16 MiB) unless the blocks need more."""
    return 2 * needed if 2 * needed > _VMEM_DEFAULT_LIMIT else None


def _pallas_fwd(q, k, v, causal: bool, sm_scale: float,
                plan: Optional[FwdPlan] = None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, sq, h, d = q.shape
    sk = k.shape[1]
    if plan is None:
        plan = fwd_block_plan(sq, sk, d, causal, q.dtype.itemsize)
    if plan is None:
        raise ValueError(f"flash_attention: sq {sq} x sk {sk} does not tile")
    block_q, block_k, major = plan.block_q, plan.block_k, plan.block_k_major
    window = plan.window
    num_major = sk // major
    flash_fwd_subblocks.inc(plan.unmasked, {"mask": "none"})
    flash_fwd_subblocks.inc(plan.masked, {"mask": "diagonal"})
    if window:
        flash_fwd_subblocks.inc(plan.edge, {"mask": "band_edge"})
    # batch x heads is grid dim 0; where head bh's blocks lie in the
    # arrays the layout says
    lay = _layout_of(q)
    flash_calls.inc(1, {"kernel": "fwd", "layout": lay.name})
    qt, kt, vt = lay.enter(q), lay.enter(k), lay.enter(v)

    # inside a shard_map the outputs vary over the axes the inputs do
    vma = jax.typeof(qt).vma

    kernel = functools.partial(
        _flash_kernel, causal=causal, sm_scale=sm_scale, block_q=block_q,
        block_k=block_k, num_sub=major // block_k, num_major=num_major,
        window=window)

    if causal:
        # major blocks wholly above the diagonal (or under the band) run
        # no sub-block: the clamp keeps their index unchanged, so nothing
        # is copied either
        kv_row = _causal_kv_index_map(block_q, major, num_major, window)
    else:
        def kv_row(qi, mi):
            return mi

    q_spec = pl.BlockSpec((1, block_q, d),
                          lambda bh, qi, mi: lay.block(bh, qi))
    kv_spec = pl.BlockSpec((1, major, d),
                           lambda bh, qi, mi: lay.block(bh, kv_row(qi, mi)))
    with kernel_trace("swa_fwd" if window else "flash_fwd"):
        out, lse = pl.pallas_call(
            kernel,
            grid=(b * h, sq // block_q, num_major),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=[
                q_spec,
                pl.BlockSpec((1, 1, block_q), lambda bh, qi, mi: (bh, 0, qi)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct(lay.shape(sq), q.dtype, vma=vma),
                jax.ShapeDtypeStruct((b * h, 1, sq), jnp.float32, vma=vma),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, _LANES), jnp.float32),
                pltpu.VMEM((block_q, _LANES), jnp.float32),
                pltpu.VMEM((block_q, d), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
                vmem_limit_bytes=_vmem_limit(plan.vmem_bytes)),
            interpret=_FORCE_INTERPRET,
            name="swa_fwd" if window else "flash_fwd",
        )(qt, kt, vt)
    return lay.leave(out), lse.reshape(b, h, sq)


# ===========================================================================
# Pallas TPU backward kernels (flash-attention-2 split: one kernel
# accumulates dq over the keys, a second accumulates dk/dv over the
# queries; both recompute p from the saved logsumexp so the [S, S] matrix
# never materializes — the blockwise math at _blockwise_bwd is the spec).
# As in the forward, the loop is inside the kernel: the other side of the
# product stays resident in VMEM, the trip counts end (dq) or start
# (dk/dv) at the diagonal, and only the sub-blocks it crosses build the
# mask (bwd_block_plan).
# ===========================================================================


def _bwd_dq_kernel(q_ref, k_ref, v_ref, lse_ref, delta_ref, do_ref, dq_ref,
                   lse_scr, delta_scr, dq_scr, *, causal: bool,
                   sm_scale: float, block_q: int, block_k: int,
                   num_sub: int, num_major: int,
                   window: Optional[int] = None):
    """One q block against one resident K/V major block of ``num_sub``
    sub-blocks of ``block_k`` keys: logits [q, keys], as the forward."""
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    mi = pl.program_id(2)

    @pl.when(mi == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)
        # the rows of lse and delta as lane-replicated columns, once a q
        # block: the one relayout, kept out of the loop
        lse_scr[:] = jnp.broadcast_to(lse_ref[0, 0][:, None],
                                      lse_scr.shape)
        delta_scr[:] = jnp.broadcast_to(delta_ref[0, 0][:, None],
                                        delta_scr.shape)

    def sub_block(masked: bool):
        def body(j, carry):
            # native-dtype operands + fp32 accumulation (see _flash_kernel)
            if num_sub == 1:
                k, v = k_ref[0], v_ref[0]
            else:
                keys = pl.ds(pl.multiple_of(j * block_k, block_k), block_k)
                k, v = k_ref[0, keys, :], v_ref[0, keys, :]
            logits = jax.lax.dot_general(
                q_ref[0], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            if masked:
                q_pos = qi * block_q + lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0)
                k_pos = (mi * num_sub + j) * block_k + lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 1)
                logits = jnp.where(_seen(q_pos, k_pos, window), logits,
                                   _NEG_INF)
            p = jnp.exp(logits - _lanes(lse_scr[:], block_k))
            dp = jax.lax.dot_general(
                do_ref[0], v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            ds = p * (dp - _lanes(delta_scr[:], block_k)) * sm_scale
            dq_scr[:] = dq_scr[:] + jax.lax.dot_general(
                ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return carry
        return body

    if causal:
        # [0, below) lie under the diagonal of every row of the q block,
        # [below, upto) cross it, the rest are never run (_flash_kernel)
        _q_block_loops(qi, mi, sub_block, block_q=block_q, block_k=block_k,
                       num_sub=num_sub, window=window)
    else:
        lax.fori_loop(0, num_sub, sub_block(False), None)

    @pl.when(mi == num_major - 1)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _delta_kernel(o_ref, do_ref, delta_ref):
    """delta of one block of q rows of one head: the row sums of
    out * dO in float32, as a row [1, block_q] like the forward's lse."""
    p = o_ref[0].astype(jnp.float32) * do_ref[0].astype(jnp.float32)
    delta_ref[0] = jnp.sum(p, axis=-1)[None, :]


def _bwd_dkdv_kernel(q_ref, k_ref, v_ref, lse_ref, delta_ref, do_ref,
                     dk_ref, dv_ref, dk_scr, dv_scr, *, causal: bool,
                     sm_scale: float, block_q: int, block_k: int,
                     num_sub: int, num_major: int,
                     window: Optional[int] = None):
    """One k block against one resident major block of ``num_sub``
    sub-blocks of ``block_q`` queries (q, dO, lse, delta). It works on the
    TRANSPOSED logits [keys, q] = K Q^T: lse and delta broadcast along
    rows straight from their [1, q] layout, and dV += P^T dO, dK += dS^T Q
    contract over the last axis of their left operand."""
    from jax.experimental import pallas as pl

    ki = pl.program_id(1)
    mi = pl.program_id(2)

    @pl.when(mi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def sub_block(masked: bool):
        def body(j, carry):
            if num_sub == 1:
                q, do = q_ref[0], do_ref[0]
                lse, delta = lse_ref[0], delta_ref[0]     # [1, bq]
            else:
                rows = pl.ds(pl.multiple_of(j * block_q, block_q), block_q)
                q, do = q_ref[0, rows, :], do_ref[0, rows, :]
                lse, delta = lse_ref[0, :, rows], delta_ref[0, :, rows]
            k, v = k_ref[0], v_ref[0]
            logits = jax.lax.dot_general(
                k, q, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale  # [bk, bq]
            if masked:
                k_pos = ki * block_k + lax.broadcasted_iota(
                    jnp.int32, (block_k, block_q), 0)
                q_pos = (mi * num_sub + j) * block_q + lax.broadcasted_iota(
                    jnp.int32, (block_k, block_q), 1)
                logits = jnp.where(_seen(q_pos, k_pos, window), logits,
                                   _NEG_INF)
            p = jnp.exp(logits - lse)
            dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
                p.astype(do.dtype), do, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(
                v, do, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            ds = p * (dp - delta) * sm_scale
            dk_scr[:] = dk_scr[:] + jax.lax.dot_general(
                ds.astype(q.dtype), q, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return carry
        return body

    if causal and window is None:
        # of this major block's q sub-blocks, those before `first` lie
        # wholly above the diagonal and are never run, [first, below)
        # cross it, and from `below` on every row sees every key here
        base = mi * num_sub
        first = jnp.clip((ki * block_k) // block_q - base, 0, num_sub)
        below = jnp.clip(
            (ki * block_k + block_k + block_q - 2) // block_q - base,
            0, num_sub)
        lax.fori_loop(first, below, sub_block(True), None)
        lax.fori_loop(below, num_sub, sub_block(False), None)
    elif causal:
        # the band, from the diagonal down: [first, below) cross the
        # diagonal, [edge, upto) the window's lower edge, what lies
        # between builds no mask, rows further down see no key here
        base = mi * num_sub
        first, below, edge, upto = _band_of_k_block(ki, block_q, block_k,
                                                    window)
        first = jnp.clip(first - base, 0, num_sub)
        upto = jnp.clip(upto - base, 0, num_sub)
        below = jnp.clip(below - base, first, upto)
        edge = jnp.clip(edge - base, below, upto)
        lax.fori_loop(first, below, sub_block(True), None)
        lax.fori_loop(below, edge, sub_block(False), None)
        lax.fori_loop(edge, upto, sub_block(True), None)
    else:
        lax.fori_loop(0, num_sub, sub_block(False), None)

    @pl.when(mi == num_major - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _causal_q_index_map(block_q: int, block_k: int, num_qb: int,
                        window: Optional[int] = None):
    """The row-block index of the q side under a (bh, ki, qi) grid, the
    mirror of ``_causal_kv_index_map``: q blocks wholly above the diagonal
    of k block ``ki`` run nothing, so their index is clamped up to the
    first block that does (run <=> qi*bq + bq - 1 >= ki*bk) and nothing is
    copied for them. The min with num_qb-1 covers sk > sq, where trailing
    k blocks have no q block at all. Under a window the q blocks wholly
    under the band are clamped down to its last one."""

    def index(ki, qi):
        qmin = jnp.minimum((ki * block_k) // block_q, num_qb - 1)
        qi = jnp.maximum(qi, qmin)
        if window:
            qi = jnp.minimum(qi, jnp.minimum(
                (ki * block_k + block_k + window - 2) // block_q,
                num_qb - 1))
        return qi

    return index


class BwdPlan(NamedTuple):
    """What ``_pallas_bwd`` lowers for one shape (``bwd_block_plan``)."""
    block_q: int        # q rows: a grid step of dq, a pass of dk/dv's loop
    block_k: int        # keys: a pass of dq's loop, a grid step of dk/dv
    block_k_major: int  # keys resident in VMEM a grid step of dq
    block_q_major: int  # q rows resident in VMEM a grid step of dk/dv
    unmasked: int       # sub-blocks a head either kernel runs without a mask
    masked: int         # sub-blocks a head the diagonal crosses
    dq_vmem_bytes: int    # each kernel's VMEM buffers, what Mosaic may use
    dkdv_vmem_bytes: int
    edge: int = 0       # sub-blocks a head on a window's lower edge alone
    window: Optional[int] = None  # the keys a query sees; None: all causal


def bwd_block_plan(sq: int, sk: int, head_dim: int, causal: bool,
                   itemsize: int = 2, block_q: Optional[int] = None,
                   block_k: Optional[int] = None,
                   resident_vmem_bytes: int = FWD_KV_VMEM_BYTES,
                   window: Optional[int] = None) -> Optional[BwdPlan]:
    """The two backward kernels' tiling as a pure function of the shape,
    or None where the shape does not tile (the blockwise tier's).

    Both kernels cut the [sq, sk] logits into the same block_q x block_k
    sub-blocks, so a head's counts are the same for both: dq walks them
    by q block, dk/dv by k block. The resident side (K and V for dq; q
    and dO for dk/dv) is as many sub-blocks as ``resident_vmem_bytes``
    holds of the two arrays, each double-buffered, as the forward's."""
    blocks = _plan_blocks(sq, sk, block_q, block_k)
    if blocks is None:
        return None
    bq, bk = blocks
    k_major = bk * _resident_blocks(sk // bk, bk, head_dim, itemsize,
                                    resident_vmem_bytes)
    q_major = bq * _resident_blocks(sq // bq, bq, head_dim, itemsize,
                                    resident_vmem_bytes)
    unmasked, masked, on_edge = _mask_counts(sq, sk, bq, bk, causal, window)
    f32 = 4
    pair = 2 * 2 * head_dim * itemsize    # two arrays, two buffers each
    rows = 2 * 2 * 8 * f32                # lse, delta: a row pads to 8
    logits = 5 * bq * bk * f32            # logits, p, dp, ds, their casts
    dq_vmem = (pair * k_major                        # K, V
               + (pair + pair // 2 + rows) * bq      # q, dO; dq; lse, delta
               + (2 * _LANES + head_dim) * bq * f32  # columns, accumulator
               + logits)
    dkdv_vmem = ((pair + rows) * q_major             # q, dO, lse, delta
                 + 2 * pair * bk                     # K, V; dk, dv
                 + 2 * head_dim * bk * f32           # accumulators
                 + logits)
    return BwdPlan(bq, bk, k_major, q_major, unmasked, masked, dq_vmem,
                   dkdv_vmem, on_edge, window)


def _pallas_bwd(q, k, v, out, lse, dout, causal: bool, sm_scale: float,
                plan: Optional[BwdPlan] = None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, sq, h, d = q.shape
    sk = k.shape[1]
    if plan is None:
        plan = bwd_block_plan(sq, sk, d, causal, q.dtype.itemsize)
    if plan is None:
        raise ValueError(f"flash_attention: sq {sq} x sk {sk} does not tile")
    block_q, block_k, window = plan.block_q, plan.block_k, plan.window
    k_major, q_major = plan.block_k_major, plan.block_q_major
    num_qb, num_kb = sq // block_q, sk // block_k
    for kernel in ("dq", "dkdv"):
        flash_bwd_subblocks.inc(plan.unmasked,
                                {"kernel": kernel, "mask": "none"})
        flash_bwd_subblocks.inc(plan.masked,
                                {"kernel": kernel, "mask": "diagonal"})
        if window:
            flash_bwd_subblocks.inc(plan.edge,
                                    {"kernel": kernel, "mask": "band_edge"})
    lay = _layout_of(q)  # see _pallas_fwd
    for kernel in ("dq", "dkdv"):
        flash_calls.inc(1, {"kernel": kernel, "layout": lay.name})
    qt, kt, vt, dot = lay.enter(q), lay.enter(k), lay.enter(v), lay.enter(dout)
    lse_t = lse.reshape(b * h, 1, sq)

    vma = jax.typeof(qt).vma  # see _pallas_fwd
    params = functools.partial(
        pltpu.CompilerParams,
        dimension_semantics=("parallel", "parallel", "arbitrary"))

    if lay.name == "lanes":
        # delta = rowsum(out * dO), read where out and dO lie and written
        # as the rows both kernels take. As an einsum to [B, H, S] the
        # compiler writes the float32 product [B, S, H*D] out, turns it
        # S-minor with a copy and reduces that: three passes over an array
        # twice q's size where H is not whole sublanes (20 heads: 1.0 GB a
        # call at the GLM cell's shape)
        rows_spec = pl.BlockSpec((1, block_q, d),
                                 lambda bh, qi: lay.block(bh, qi))
        with kernel_trace("attn_delta"):
            delta = pl.pallas_call(
                _delta_kernel,
                grid=(b * h, num_qb),
                in_specs=[rows_spec, rows_spec],
                out_specs=pl.BlockSpec((1, 1, block_q),
                                       lambda bh, qi: (bh, 0, qi)),
                out_shape=jax.ShapeDtypeStruct((b * h, 1, sq), jnp.float32,
                                               vma=vma),
                compiler_params=pltpu.CompilerParams(
                    dimension_semantics=("parallel", "parallel")),
                interpret=_FORCE_INTERPRET,
                name="attn_delta",
            )(lay.enter(out), dot)
    else:
        # heads-major the reduction reads what the copies wrote
        delta = jnp.einsum("bqhd,bqhd->bhq", out.astype(jnp.float32),
                           dout.astype(jnp.float32)).reshape(b * h, 1, sq)

    # dq: K and V resident, major blocks above the diagonal neither
    # copied nor run (as the forward)
    if causal:
        kv_row = _causal_kv_index_map(block_q, k_major, sk // k_major,
                                      window)
    else:
        def kv_row(qi, mi):
            return mi
    q_spec = pl.BlockSpec((1, block_q, d),
                          lambda bh, qi, mi: lay.block(bh, qi))
    kv_spec = pl.BlockSpec((1, k_major, d),
                           lambda bh, qi, mi: lay.block(bh, kv_row(qi, mi)))
    row_spec = pl.BlockSpec((1, 1, block_q), lambda bh, qi, mi: (bh, 0, qi))
    with kernel_trace("swa_bwd_dq" if window else "flash_bwd_dq"):
        dq = pl.pallas_call(
            functools.partial(_bwd_dq_kernel, causal=causal, sm_scale=sm_scale,
                              block_q=block_q, block_k=block_k,
                              num_sub=k_major // block_k,
                              num_major=sk // k_major, window=window),
            grid=(b * h, num_qb, sk // k_major),
            in_specs=[q_spec, kv_spec, kv_spec, row_spec, row_spec, q_spec],
            out_specs=q_spec,
            out_shape=jax.ShapeDtypeStruct(lay.shape(sq), q.dtype, vma=vma),
            scratch_shapes=[pltpu.VMEM((block_q, _LANES), jnp.float32),
                            pltpu.VMEM((block_q, _LANES), jnp.float32),
                            pltpu.VMEM((block_q, d), jnp.float32)],
            compiler_params=params(
                vmem_limit_bytes=_vmem_limit(plan.dq_vmem_bytes)),
            interpret=_FORCE_INTERPRET,
            name="swa_bwd_dq" if window else "flash_bwd_dq",
        )(qt, kt, vt, lse_t, delta, dot)

    # dk/dv: q, dO, lse and delta resident, major blocks above the
    # diagonal neither copied nor run
    if causal:
        q_row = _causal_q_index_map(q_major, block_k, sq // q_major, window)
    else:
        def q_row(ki, mi):
            return mi
    qm_spec = pl.BlockSpec((1, q_major, d),
                           lambda bh, ki, mi: lay.block(bh, q_row(ki, mi)))
    rowm_spec = pl.BlockSpec((1, 1, q_major),
                             lambda bh, ki, mi: (bh, 0, q_row(ki, mi)))
    k_spec = pl.BlockSpec((1, block_k, d),
                          lambda bh, ki, mi: lay.block(bh, ki))
    with kernel_trace("swa_bwd_dkdv" if window else "flash_bwd_dkdv"):
        dk, dv = pl.pallas_call(
            functools.partial(_bwd_dkdv_kernel, causal=causal,
                              sm_scale=sm_scale, block_q=block_q,
                              block_k=block_k, num_sub=q_major // block_q,
                              num_major=sq // q_major, window=window),
            grid=(b * h, num_kb, sq // q_major),
            in_specs=[qm_spec, k_spec, k_spec, rowm_spec, rowm_spec, qm_spec],
            out_specs=[k_spec, k_spec],
            out_shape=[jax.ShapeDtypeStruct(lay.shape(sk), k.dtype, vma=vma),
                       jax.ShapeDtypeStruct(lay.shape(sk), v.dtype, vma=vma)],
            scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                            pltpu.VMEM((block_k, d), jnp.float32)],
            compiler_params=params(
                vmem_limit_bytes=_vmem_limit(plan.dkdv_vmem_bytes)),
            interpret=_FORCE_INTERPRET,
            name="swa_bwd_dkdv" if window else "flash_bwd_dkdv",
        )(qt, kt, vt, lse_t, delta, dot)
    return lay.leave(dq), lay.leave(dk), lay.leave(dv)


# ===========================================================================
# Public op with custom VJP.
# ===========================================================================


def repeat_kv(x, heads: int, sharded: bool = False):
    """K or V [B, S, kv_heads, D] as the [B, S, heads, D] the op takes:
    each head copied for the ``heads // kv_heads`` consecutive query
    heads that share it (GQA); autodiff sums the cotangent over them.
    Where the kernels will index the result's lanes (``lane_layout``) the
    copy is whole 128-lane tiles laid side by side along the lanes of
    [B, S, kv_heads * D], which the compiler writes in one pass as the
    array the kernels take; ``jnp.repeat`` on the heads' axis, which is
    what a mesh splits, leaves a [.., kv_heads, rep, D] array and a copy
    of XLA's to turn it into lanes."""
    b, s, kv_heads, d = x.shape
    if kv_heads == heads:
        return x
    rep = heads // kv_heads
    if not lane_layout(d, sharded):
        return jnp.repeat(x, rep, axis=2)
    flat = x.reshape(b, s, kv_heads * d)
    return jnp.concatenate(
        [flat[..., head * d:(head + 1) * d]
         for head in range(kv_heads) for _ in range(rep)],
        axis=-1).reshape(b, s, heads, d)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal: bool = True,
                    sm_scale: Optional[float] = None,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    window: Optional[int] = None):
    """``window``: query i sees keys i - window < j <= i (causal calls
    only); None, or at least the number of keys, is the causal call."""
    out, _ = _fwd_dispatch(q, k, v, causal, sm_scale, block_q, block_k,
                           window)
    return out


def _effective_window(window: Optional[int], causal: bool,
                      sk: int) -> Optional[int]:
    """The window a call really has: None where every causal key is
    inside it already."""
    if window is None:
        return None
    if not causal or window < 1:
        raise ValueError(f"flash_attention: window {window} needs a causal "
                         "call and at least the key itself")
    return window if window < sk else None


def _pallas_tileable(sq: int, sk: int, block_q: int, block_k: int) -> bool:
    """Mosaic requires each block's trailing dims to divide into (8, 128)
    tiles or equal the array dim; the lse output block (1, 1, block_q)
    additionally needs block_q % 128 == 0 unless block_q == sq."""
    bq, bk = min(block_q, sq), min(block_k, sk)
    if sq % bq or sk % bk:
        return False
    if not (bq == sq or bq % 8 == 0) or not (bk == sk or bk % 8 == 0):
        return False
    if not (bq == sq or bq % 128 == 0):
        return False
    return sq >= 8 and sk >= 8


def kernel_tiers(sq: int, sk: int, head_dim: int,
                 block_q: Optional[int] = None,
                 block_k: Optional[int] = None) -> Tuple[bool, bool]:
    """(whether the forward of this shape takes the kernel, whether the
    backward takes the dq and dk/dv kernels): the one rule behind
    ``_fwd_dispatch``, ``_flash_bwd`` and ``flash_attention_on_mesh``.

    The forward: where kernels run at all and the shape tiles
    (``_plan_blocks``, which both block plans start from: head_dim, the
    mask and the item size move the resident major block, never whether a
    shape tiles). One TPU v5 lite measured the kernel ahead of the
    blockwise tier, whose fp32 [B,H,Sq,block_k] logits go through HBM, on
    every shape the benchmark has (the table at the top of this file).

    The backward: where the forward does (``flash_attention_on_mesh``
    puts the pair in shard_maps together) and head_dim is a multiple of
    the 128 lanes, or 64. At d 128 the pair takes 6.10 + 7.49 ms a call at
    B4-S4096-H32, 69 % and 75 % of its rooflines (PR 27). At d 64 a block
    fills half the lanes and q, k, v go heads-major (0.93 + 1.02 ms at
    B4-S2048-H16, 28 % and 34 %), and r05 read a whole d 64 step slower
    with that round's kernels than blockwise (2.74 s against 2.17 s); the
    step of LFM2's cell (B4-S8192, 32 heads of 64 on 8 K/V heads, two
    attention layers, traced on one TPU v5 lite) reads the other
    way with these kernels: 0.846 s a step with the pair (dq 21.7 ms and
    dk/dv 27.4 ms a call, 38.5 % and 40.8 % of their rooflines; the
    forward 17.7 ms, 31.5 %) against 1.401 s on the blockwise tier, whose
    float32 [B, H, Sq, block_k] logits go through HBM (attention 55.9 %
    of that step's device time against 27.6 %). Shorter keys read the same
    way on one TPU v5 lite, forward and gradient a call against
    the blockwise backward: 3.48 against 7.61 ms at B4-S2048-H16 causal,
    2.68 against 3.55 ms at ViT-B's B64-S197-H12 unmasked, and a whole
    ViT-B step at B128 152.4 against 176.2 ms. d 160 read MFU 0.300
    against 0.4045 at d 128 (r05) and no step has read it since: it
    stays blockwise."""
    fwd = kernels_on() and _plan_blocks(sq, sk, block_q, block_k) is not None
    return fwd, fwd and (head_dim % _LANES == 0 or head_dim == _LANES // 2)


def _fwd_dispatch(q, k, v, causal, sm_scale, block_q, block_k,
                  window=None):
    scale = sm_scale if sm_scale is not None else q.shape[-1] ** -0.5
    sq, sk, d = q.shape[1], k.shape[1], q.shape[-1]
    window = _effective_window(window, causal, sk)
    fwd_kernel, _ = kernel_tiers(sq, sk, d, block_q, block_k)
    if fwd_kernel:
        plan = fwd_block_plan(sq, sk, d, causal, q.dtype.itemsize, block_q,
                              block_k, window=window)
        return _pallas_fwd(q, k, v, causal, scale, plan)
    return _blockwise_fwd(q, k, v, causal, scale,
                          block_k or BLOCKWISE_BLOCK_K, window)


def _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k, window=None):
    out, lse = _fwd_dispatch(q, k, v, causal, sm_scale, block_q, block_k,
                             window)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, sm_scale, block_q, block_k, window, residuals, dout):
    q, k, v, out, lse = residuals
    scale = sm_scale if sm_scale is not None else q.shape[-1] ** -0.5
    sq, sk, d = q.shape[1], k.shape[1], q.shape[-1]
    window = _effective_window(window, causal, sk)
    _, bwd_kernels = kernel_tiers(sq, sk, d, block_q, block_k)
    if bwd_kernels:
        plan = bwd_block_plan(sq, sk, d, causal, q.dtype.itemsize, block_q,
                              block_k, window=window)
        return _pallas_bwd(q, k, v, out, lse, dout, causal, scale, plan)
    dq, dk, dv = _blockwise_bwd(q, k, v, out, lse, dout, causal, scale,
                                block_k or BLOCKWISE_BLOCK_K, window)
    return dq, dk, dv


flash_attention.defvjp(_flash_fwd, _flash_bwd)


def flash_attention_on_mesh(spec, mesh=None, axis_names=None):
    """Causal ``flash_attention`` for [batch, seq, heads, head_dim]
    arrays sharded as ``spec`` (batch and heads only: they are
    independent in attention), under jit over ``mesh`` — or, with
    ``mesh=None``, inside a shard_map whose mesh it takes and which has
    left exactly ``axis_names`` automatic. -> attention(q, k, v,
    window=None): a window passes through to the op (the sequence is
    whole on every shard).

    The blockwise tier is plain jnp that the partitioner splits itself,
    and gets the bare op. A Pallas kernel it refuses ("Mosaic kernels
    cannot be automatically partitioned", even over an axis of size 1),
    so each kernel runs per shard in a shard_map with no axis left
    automatic. Which tier a shape takes is decided when it is traced,
    by the rule the bare op dispatches on (``kernel_tiers``): the forward
    kernel with a blockwise backward (head_dim not a multiple of 128)
    puts only the forward in a shard_map.

    Forward and backward each get a shard_map of their own, joined by a
    custom VJP, so the residuals cross as ordinary arrays: autodiff
    through one nested shard_map stacks them over every mesh axis, the
    outer region's manual one included, which the partitioner rejects.
    Partial evaluation under remat does the same to any constant inside
    a nested shard_map; the blockwise tier has some, which is why it
    stays out of one."""
    # no axis_names: manual over every axis of the mesh
    smap = functools.partial(shard_map, mesh=mesh,
                             axis_names=frozenset(axis_names or ()))
    batch, _, heads, _ = spec
    residuals = (spec, spec, spec, spec, P(batch, heads, None))  # out, lse

    def kernels_of(window):
        def op(q, k, v):
            return flash_attention(q, k, v, True, None, None, None, window)

        @jax.custom_vjp
        def kernels(q, k, v):
            return smap(op, in_specs=(spec,) * 3, out_specs=spec)(q, k, v)

        def fwd(q, k, v):
            return smap(
                lambda q, k, v: _flash_fwd(q, k, v, True, None, None, None,
                                           window),
                in_specs=(spec,) * 3, out_specs=(spec, residuals))(q, k, v)

        def one_bwd(res, dout):
            return _flash_bwd(True, None, None, None, window, res, dout)

        def bwd(res, dout):
            q, k = res[:2]
            _, bwd_kernels = kernel_tiers(q.shape[1], k.shape[1],
                                          q.shape[-1])
            if not bwd_kernels:
                return one_bwd(res, dout)
            return smap(one_bwd, in_specs=(residuals, spec),
                        out_specs=(spec,) * 3)(res, dout)

        kernels.defvjp(fwd, bwd)
        return kernels, op

    def attention(q, k, v, window=None):
        kernels, op = kernels_of(window)
        fwd_kernel, _ = kernel_tiers(q.shape[1], k.shape[1], q.shape[-1])
        return kernels(q, k, v) if fwd_kernel else op(q, k, v)

    return attention


def attention_reference(q, k, v, causal: bool = True,
                        sm_scale: Optional[float] = None,
                        window: Optional[int] = None):
    """O(S^2)-memory reference implementation for tests."""
    scale = sm_scale if sm_scale is not None else q.shape[-1] ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        mask = _seen(jnp.arange(sq)[:, None], jnp.arange(sk)[None, :],
                     window)
        logits = jnp.where(mask[None, None], logits, _NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p,
                      v.astype(jnp.float32)).astype(q.dtype)
