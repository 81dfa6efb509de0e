"""Flash attention: Pallas TPU forward kernel + blockwise custom VJP.

The hot op of the model family. Three tiers behind one call:

  flash_attention(q, k, v, causal=...)
    -> Pallas kernel on TPU (K/V of a head resident in VMEM, the loop
       over the keys inside the kernel, online softmax, O(S) memory),
       selected when the default backend is TPU;
    -> blockwise lax.scan implementation elsewhere (same math, XLA-fused;
       also the correctness oracle for the kernel);
  backward: Pallas dq/dk/dv kernels on TPU (flash-attention-2 split,
  causal fetch-trim), blockwise recomputation elsewhere — both
  recompute p from the saved logsumexp, so training never materializes
  the [S, S] attention matrix regardless of tier.

Layouts: [batch, seq, heads, head_dim] throughout (matches
parallel/ring_attention.py, which wraps this per-shard). On a mesh with
batch and heads sharded, flash_attention_on_mesh gives each kernel the
shard_map the TPU compiler needs.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from ray_tpu.observability.metrics import flash_fwd_subblocks

# Per-path block defaults, resolved in fwd_block_plan/_flash_bwd when the
# caller passes None. The three paths do not share a block size: tuning
# one would move the others' memory and speed.
#
# The BACKWARD kernels keep (256, 512) over a (bh, q block, k block)
# grid: 9.64 ms (dQ) and 13.04 ms (dK/dV) a call at B4-S4096-H32-D128,
# 43 % of their compute-bound rooflines (ledger, PR 24). The BLOCKWISE
# tier keeps 128: its fp32 [B,H,Sq,block_k] logits temporary scales with
# block_k.
#
# The FORWARD kernel takes 512 q rows a grid step against K/V of a whole
# head resident in VMEM and loops over 512-key sub-blocks inside the
# kernel (fwd_block_plan). Device time of `flash_fwd` from profiler
# traces on TPU v5 lite, 30 Sep 2026 (PR 25), bf16, causal: the parent
# (256 x 512 blocks, the loop over the keys in the grid) -> this kernel,
# ms a call and share of the roofline benchmark/flops.py reckons:
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
DEFAULT_BLOCK_Q = None
DEFAULT_BLOCK_K = None
PALLAS_BLOCK_Q = 256
PALLAS_BLOCK_K = 512
BLOCKWISE_BLOCK_K = 128
# The forward kernel's own: q rows a grid step, keys a pass of its inner
# loop, the longest sequence taken as one block when no size divides it,
# and the VMEM that K and V of one head may hold (both double-buffered).
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


def _use_pallas() -> bool:
    """Whether the Pallas forward kernel dispatches. Default 'auto'
    resolves to the PALLAS KERNEL on TPU, on measured evidence (one TPU
    v5 lite, PR 24 and PR 25): the kernel alone takes 0.404 ms at
    B4-S2048-H8-D128 and 5.22 ms at B4-S4096-H32-D128 (53.5 % of its
    compute-bound roofline; device time from a trace), where the
    blockwise tier's fp32 [B,H,Sq,block_k] logits temporaries go through
    HBM; with it the Mistral-7B-width step at 4 x 4096 tokens runs
    16 928 tokens/s at `step_mfu` 55.2 % (PERF.md section 6).
    RAY_TPU_ATTN_FWD=blockwise forces the other tier for an A/B. The
    kernels stay correctness-tested in interpret mode against the
    blockwise tier, which is their oracle."""
    if _FORCE_INTERPRET:
        return True
    import os

    mode = os.environ.get("RAY_TPU_ATTN_FWD", "auto")
    if mode == "blockwise":
        return False
    if mode not in ("auto", "pallas"):
        return False
    return jax.default_backend() == "tpu"


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


def _blockwise_fwd(q, k, v, causal: bool, sm_scale: float, block_k: int):
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
            valid = valid[None, :] & (q_pos[:, None] >= k_pos[None, :])
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
                   block_k: int):
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
            valid = valid[None, :] & (q_pos[:, None] >= k_pos[None, :])
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


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
                  acc_scr, *, causal: bool, sm_scale: float, block_q: int,
                  block_k: int, num_sub: int, num_major: int):
    """One q block against one resident K/V major block of ``num_sub``
    compute sub-blocks of ``block_k`` keys. The loop over the keys is in
    here, not in the grid: its trip count ends at the diagonal, and only
    the sub-blocks the diagonal crosses build the mask."""
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
                logits = jnp.where(q_pos >= k_pos, logits, _NEG_INF)
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
        # of the sub-blocks before this major block's end, `below` lie
        # wholly at or under the diagonal of every row of the q block and
        # `upto` reach it: [below, upto) cross it, the rest are never run
        first = mi * num_sub
        below = jnp.clip((qi * block_q + 1) // block_k - first, 0, num_sub)
        upto = jnp.clip((qi * block_q + block_q - 1) // block_k + 1 - first,
                        0, num_sub)
        lax.fori_loop(0, below, sub_block(False), None)
        lax.fori_loop(below, upto, sub_block(True), None)
    else:
        lax.fori_loop(0, num_sub, sub_block(False), None)

    @pl.when(mi == num_major - 1)
    def _finalize():
        l_safe = jnp.maximum(l_scr[:], 1e-20)
        o_ref[0] = (acc_scr[:] / _lanes(l_safe, d)).astype(o_ref.dtype)
        lse = m_scr[:] + jnp.log(l_safe)
        lse_ref[0] = lse[:, 0][None, :]


def _causal_kv_index_map(block_q: int, block_k: int, num_kb: int):
    """BlockSpec index map for K/V under a (bh, qi, ki) grid with the
    causal fetch-trim: blocks strictly above the diagonal are
    compute-skipped by the kernels' ``pl.when``, so clamp their fetch
    index to the q-row's last needed block — an unchanged index between
    grid steps makes the Pallas pipeline elide the DMA (37.5% of K/V
    fetches never issued at the default blocks on S2048). The outer
    min with num_kb-1 covers sq > sk, where trailing q rows' diagonal
    lies beyond the last K block. Shared by the forward and dq kernels
    (the r05 review flagged three hand-copied variants)."""

    def index(bh, qi, ki):
        kmax = jnp.minimum((qi * block_q + block_q - 1) // block_k,
                           num_kb - 1)
        return (bh, jnp.minimum(ki, kmax), 0)

    return index


def _causal_q_min(block_q: int, block_k: int, num_qb: int, ki):
    """First q block at or below the diagonal for K row ``ki`` (the
    dk/dv kernel iterates qi innermost and skips the EARLY q blocks:
    run ⟺ qi*bq + bq - 1 >= ki*bk ⟺ qi >= (ki*bk) // bq). Min with
    num_qb-1 covers sk > sq, where trailing K rows have no computed q
    block at all."""
    return jnp.minimum((ki * block_k) // block_q, num_qb - 1)


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


def fwd_block_plan(sq: int, sk: int, head_dim: int, causal: bool,
                   itemsize: int = 2, block_q: Optional[int] = None,
                   block_k: Optional[int] = None,
                   kv_vmem_bytes: int = FWD_KV_VMEM_BYTES
                   ) -> Optional[FwdPlan]:
    """The forward kernel's tiling as a pure function of the shape, or
    None where the shape does not tile (the blockwise tier's).

    The K/V major block is as many sub-blocks as ``kv_vmem_bytes`` holds
    of K and V, each double-buffered by the pipeline: the whole sequence
    at S4096-D128 bf16, so K/V are read once a head. The counts are what
    the kernel's loops do over one head (causal: q row i sees keys <= i,
    whatever sq and sk are)."""
    auto_q, auto_k = _fwd_blocks(sq, sk)
    bq = min(block_q, sq) if block_q else auto_q
    bk = min(block_k, sk) if block_k else auto_k
    if not (bq and bk and _pallas_tileable(sq, sk, bq, bk)):
        return None
    num_kb = sk // bk
    per_sub = 2 * 2 * bk * head_dim * itemsize
    num_sub = max(n for n in range(1, num_kb + 1)
                  if num_kb % n == 0 and (n == 1 or n * per_sub
                                          <= kv_vmem_bytes))
    major = num_sub * bk
    num_qb, num_major = sq // bq, num_kb // num_sub
    unmasked = masked = fetches = 0
    held = None  # the major block the pipeline last copied for this head
    for qi in range(num_qb):
        below = min((qi * bq + 1) // bk, num_kb) if causal else num_kb
        upto = min((qi * bq + bq - 1) // bk + 1, num_kb) if causal else num_kb
        unmasked += below
        masked += upto - below
        last = min((qi * bq + bq - 1) // major, num_major - 1)
        for mi in range(num_major):
            want = min(mi, last) if causal else mi
            fetches += want != held
            held = want
    f32 = 4
    vmem = (num_sub * per_sub                       # K, V: two buffers each
            + 2 * 2 * bq * head_dim * itemsize      # q, out: the same
            + 2 * bq * f32                          # lse
            + (2 * _LANES + head_dim) * bq * f32    # max, sum, accumulator
            + 4 * bq * bk * f32)                    # logits, p and their kin
    return FwdPlan(bq, bk, major, num_qb * num_major, unmasked, masked,
                   fetches * 2 * major * head_dim * itemsize, vmem)


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
    num_major = sk // major
    flash_fwd_subblocks.inc(plan.unmasked, {"mask": "none"})
    flash_fwd_subblocks.inc(plan.masked, {"mask": "diagonal"})
    # layout: fold batch*heads into grid dim 0 with [B*H, S, D] views
    qt = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kt = k.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * h, sk, d)

    # inside a shard_map the outputs vary over the axes the inputs do
    vma = jax.typeof(qt).vma

    kernel = functools.partial(
        _flash_kernel, causal=causal, sm_scale=sm_scale, block_q=block_q,
        block_k=block_k, num_sub=major // block_k, num_major=num_major)

    if causal:
        # major blocks wholly above the diagonal run no sub-block: the
        # clamp keeps their index unchanged, so nothing is copied either
        kv_index = _causal_kv_index_map(block_q, major, num_major)
    else:
        def kv_index(bh, qi, mi):
            return (bh, mi, 0)

    out, lse = pl.pallas_call(
        kernel,
        grid=(b * h, sq // block_q, num_major),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, mi: (bh, qi, 0)),
            pl.BlockSpec((1, major, d), kv_index),
            pl.BlockSpec((1, major, d), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, mi: (bh, qi, 0)),
            pl.BlockSpec((1, 1, block_q), lambda bh, qi, mi: (bh, 0, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sq, d), q.dtype, vma=vma),
            jax.ShapeDtypeStruct((b * h, 1, sq), jnp.float32, vma=vma),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            # Mosaic's own limit (16 MiB) unless the blocks need more
            vmem_limit_bytes=(2 * plan.vmem_bytes
                              if 2 * plan.vmem_bytes > _VMEM_DEFAULT_LIMIT
                              else None)),
        interpret=_FORCE_INTERPRET,
        name="flash_fwd",
    )(qt, kt, vt)
    out = out.reshape(b, h, sq, d).transpose(0, 2, 1, 3)
    lse = lse.reshape(b, h, sq)
    return out, lse


# ===========================================================================
# Pallas TPU backward kernels (flash-attention-2 split: one kernel
# accumulates dq over KV blocks, a second accumulates dk/dv over Q blocks;
# both recompute p from the saved logsumexp so the [S, S] matrix never
# materializes — the blockwise math at _blockwise_bwd is the spec).
# ===========================================================================


def _bwd_dq_kernel(q_ref, k_ref, v_ref, lse_ref, delta_ref, do_ref, dq_ref,
                   dq_scr, *, causal: bool, sm_scale: float, block_q: int,
                   block_k: int, num_kb: int):
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    run = True
    if causal:
        run = (ki * block_k) <= (qi * block_q + block_q - 1)

    @pl.when(run)
    def _compute():
        # native-dtype operands + fp32 accumulation (see _flash_kernel)
        q = q_ref[0]                                 # [bq, d]
        k = k_ref[0]                                 # [bk, d]
        v = v_ref[0]
        do = do_ref[0]                               # [bq, d]
        lse = lse_ref[0][0]                          # [bq]
        delta = delta_ref[0][0]                      # [bq]
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        if causal:
            q_pos = qi * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = ki * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            logits = jnp.where(q_pos >= k_pos, logits, _NEG_INF)
        p = jnp.exp(logits - lse[:, None])           # [bq, bk]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)      # [bq, bk]
        ds = p * (dp - delta[:, None]) * sm_scale
        dq_scr[:] = dq_scr[:] + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == num_kb - 1)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkdv_kernel(q_ref, k_ref, v_ref, lse_ref, delta_ref, do_ref,
                     dk_ref, dv_ref, dk_scr, dv_scr, *, causal: bool,
                     sm_scale: float, block_q: int, block_k: int,
                     num_qb: int):
    from jax.experimental import pallas as pl

    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    run = True
    if causal:
        run = (ki * block_k) <= (qi * block_q + block_q - 1)

    @pl.when(run)
    def _compute():
        # native-dtype operands + fp32 accumulation (see _flash_kernel)
        q = q_ref[0]                                 # [bq, d]
        k = k_ref[0]                                 # [bk, d]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][0]
        delta = delta_ref[0][0]
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        if causal:
            q_pos = qi * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = ki * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            logits = jnp.where(q_pos >= k_pos, logits, _NEG_INF)
        p = jnp.exp(logits - lse[:, None])           # [bq, bk]
        # dv += p.T @ do
        dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * sm_scale    # [bq, bk]
        # dk += ds.T @ q
        dk_scr[:] = dk_scr[:] + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == num_qb - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _pallas_bwd(q, k, v, out, lse, dout, causal: bool, sm_scale: float,
                block_q: int, block_k: int):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, sq, h, d = q.shape
    sk = k.shape[1]
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    num_qb = sq // block_q
    num_kb = sk // block_k
    qt = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kt = k.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    dot = dout.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    lse_t = lse.reshape(b * h, 1, sq)
    delta = jnp.einsum("bqhd,bqhd->bhq", out.astype(jnp.float32),
                       dout.astype(jnp.float32)).reshape(b * h, 1, sq)

    vma = jax.typeof(qt).vma  # see _pallas_fwd
    q_spec = pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0))
    if causal:
        bwd_kv_index = _causal_kv_index_map(block_q, block_k, num_kb)
    else:
        def bwd_kv_index(bh, qi, ki):
            return (bh, ki, 0)
    k_spec = pl.BlockSpec((1, block_k, d), bwd_kv_index)
    row_spec = pl.BlockSpec((1, 1, block_q), lambda bh, qi, ki: (bh, 0, qi))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, causal=causal, sm_scale=sm_scale,
                          block_q=block_q, block_k=block_k, num_kb=num_kb),
        grid=(b * h, num_qb, num_kb),
        in_specs=[q_spec, k_spec, k_spec, row_spec, row_spec, q_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype, vma=vma),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_FORCE_INTERPRET,
        name="flash_bwd_dq",
    )(qt, kt, vt, lse_t, delta, dot)

    if causal:
        # dk/dv iterates qi innermost and skips the EARLY q blocks
        # strictly above the diagonal: clamp skipped leading fetches of
        # Q/do/lse/delta up to the first needed block (_causal_q_min)
        # so their copies are elided too
        def bwd_q_index(bh, ki, qi):
            qmin = _causal_q_min(block_q, block_k, num_qb, ki)
            return (bh, jnp.maximum(qi, qmin), 0)

        def bwd_row_index(bh, ki, qi):
            qmin = _causal_q_min(block_q, block_k, num_qb, ki)
            return (bh, 0, jnp.maximum(qi, qmin))
    else:
        def bwd_q_index(bh, ki, qi):
            return (bh, qi, 0)

        def bwd_row_index(bh, ki, qi):
            return (bh, 0, qi)
    kq_spec = pl.BlockSpec((1, block_q, d), bwd_q_index)
    kk_spec = pl.BlockSpec((1, block_k, d), lambda bh, ki, qi: (bh, ki, 0))
    krow_spec = pl.BlockSpec((1, 1, block_q), bwd_row_index)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkdv_kernel, causal=causal,
                          sm_scale=sm_scale, block_q=block_q,
                          block_k=block_k, num_qb=num_qb),
        grid=(b * h, num_kb, num_qb),
        in_specs=[kq_spec, kk_spec, kk_spec, krow_spec, krow_spec, kq_spec],
        out_specs=[kk_spec, kk_spec],
        out_shape=[jax.ShapeDtypeStruct((b * h, sk, d), k.dtype, vma=vma),
                   jax.ShapeDtypeStruct((b * h, sk, d), v.dtype, vma=vma)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_FORCE_INTERPRET,
        name="flash_bwd_dkdv",
    )(qt, kt, vt, lse_t, delta, dot)

    dq = dq.reshape(b, h, sq, d).transpose(0, 2, 1, 3)
    dk = dk.reshape(b, h, sk, d).transpose(0, 2, 1, 3)
    dv = dv.reshape(b, h, sk, d).transpose(0, 2, 1, 3)
    return dq, dk, dv


# ===========================================================================
# Public op with custom VJP.
# ===========================================================================


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal: bool = True,
                    sm_scale: Optional[float] = None,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K):
    out, _ = _fwd_dispatch(q, k, v, causal, sm_scale, block_q, block_k)
    return out


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


def _fwd_is_pallas(sq: int, sk: int, block_q=None, block_k=None) -> bool:
    """Whether the forward of these sequence lengths takes the kernel:
    what _fwd_dispatch does, for flash_attention_on_mesh and the backward
    to ask. The forward's own blocks decide (head_dim, the mask and the
    item size move the K/V major block, never whether the shape tiles)."""
    return _use_pallas() and fwd_block_plan(
        sq, sk, _LANES, True, block_q=block_q, block_k=block_k) is not None


def _fwd_dispatch(q, k, v, causal, sm_scale, block_q, block_k):
    scale = sm_scale if sm_scale is not None else q.shape[-1] ** -0.5
    plan = fwd_block_plan(q.shape[1], k.shape[1], q.shape[-1], causal,
                          q.dtype.itemsize, block_q, block_k)
    if _use_pallas() and plan is not None:
        return _pallas_fwd(q, k, v, causal, scale, plan)
    return _blockwise_fwd(q, k, v, causal, scale,
                          block_k or BLOCKWISE_BLOCK_K)


def _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k):
    out, lse = _fwd_dispatch(q, k, v, causal, sm_scale, block_q, block_k)
    return out, (q, k, v, out, lse)


def _bwd_impl() -> str:
    """Backward tier: 'auto' (default) resolves BY HEAD DIM on TPU —
    Pallas dq/dk/dv kernels at head_dim >= 128 AND head_dim % 128 == 0
    (full lane utilization), blockwise otherwise.
    Measured on live v5e (r05), the discriminator is lane utilization:
    at d=128 the trimmed kernels are the decisive flagship winner
    (632M L12-H2048-B40, head_dim 128: MFU 0.409/0.411 vs 0.319 with
    the blockwise backward, two runs each — blockwise's fp32
    [B,H,Sq,block_k] logits temporaries dominate once batch x heads
    grow), but at d=64 the two-kernel split runs blocks at half the
    128-wide lane dim and LOSES (H1024-16-head MoE step, head_dim 64:
    2.74 s vs 2.17 s blockwise; the r03 'blockwise wins' A/B was the
    same d=64 shape). RAY_TPU_ATTN_BWD=pallas|blockwise forces a
    tier; both stay correctness-tested against each other."""
    import os

    return os.environ.get("RAY_TPU_ATTN_BWD", "auto")


def _bwd_is_pallas(sq: int, sk: int, head_dim: int, block_q=None,
                   block_k=None) -> bool:
    """Whether the backward of this shape takes the dq and dk/dv kernels.
    The one predicate behind _flash_bwd and flash_attention_on_mesh."""
    impl = _bwd_impl()
    # auto requires head_dim to be a MULTIPLE of the 128-wide lane dim,
    # not merely >= 128: the measured rationale is lane utilization, and
    # a non-multiple dim (e.g. d=160, the xl 16-head shape: r05 MFU
    # 0.300 vs 0.4045 at d=128) pads blocks to partial lanes — it gets
    # the reference/blockwise path until a measurement says otherwise.
    # RAY_TPU_ATTN_BWD=pallas still forces the kernels for A/B runs.
    want_pallas = (impl == "pallas"
                   or (impl == "auto" and head_dim >= 128
                       and head_dim % 128 == 0))
    # its own blocks must tile, and the forward must be a kernel too:
    # flash_attention_on_mesh puts the pair in shard_maps together
    return (want_pallas and _fwd_is_pallas(sq, sk, block_q, block_k)
            and _pallas_tileable(sq, sk, block_q or PALLAS_BLOCK_Q,
                                 block_k or PALLAS_BLOCK_K))


def _flash_bwd(causal, sm_scale, block_q, block_k, residuals, dout):
    q, k, v, out, lse = residuals
    scale = sm_scale if sm_scale is not None else q.shape[-1] ** -0.5
    if _bwd_is_pallas(q.shape[1], k.shape[1], q.shape[-1], block_q,
                      block_k):
        return _pallas_bwd(q, k, v, out, lse, dout, causal, scale,
                           block_q or PALLAS_BLOCK_Q,
                           block_k or PALLAS_BLOCK_K)
    dq, dk, dv = _blockwise_bwd(q, k, v, out, lse, dout, causal, scale,
                                block_k or BLOCKWISE_BLOCK_K)
    return dq, dk, dv


flash_attention.defvjp(_flash_fwd, _flash_bwd)


def flash_attention_on_mesh(spec, mesh=None, axis_names=None):
    """Causal ``flash_attention`` for [batch, seq, heads, head_dim]
    arrays sharded as ``spec`` (batch and heads only: they are
    independent in attention), under jit over ``mesh`` — or, with
    ``mesh=None``, inside a shard_map whose mesh it takes and which has
    left exactly ``axis_names`` automatic.

    The blockwise tier is plain jnp that the partitioner splits itself,
    and gets the bare op. A Pallas kernel it refuses ("Mosaic kernels
    cannot be automatically partitioned", even over an axis of size 1),
    so each kernel runs per shard in a shard_map with no axis left
    automatic. Which tier a shape takes is decided when it is traced,
    by the predicates the bare op dispatches on: the forward kernel with
    a blockwise backward (head_dim not a multiple of 128) puts only the
    forward in a shard_map.

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

    @jax.custom_vjp
    def kernels(q, k, v):
        return smap(lambda q, k, v: flash_attention(q, k, v, True),
                    in_specs=(spec,) * 3, out_specs=spec)(q, k, v)

    def fwd(q, k, v):
        return smap(
            lambda q, k, v: _flash_fwd(q, k, v, True, None, None, None),
            in_specs=(spec,) * 3, out_specs=(spec, residuals))(q, k, v)

    def bwd(res, dout):
        q, k = res[:2]
        if not _bwd_is_pallas(q.shape[1], k.shape[1], q.shape[-1]):
            return _flash_bwd(True, None, None, None, res, dout)
        return smap(
            lambda res, dout: _flash_bwd(True, None, None, None, res, dout),
            in_specs=(residuals, spec), out_specs=(spec,) * 3)(res, dout)

    kernels.defvjp(fwd, bwd)

    def attention(q, k, v):
        if _fwd_is_pallas(q.shape[1], k.shape[1]):
            return kernels(q, k, v)
        return flash_attention(q, k, v, True)

    return attention


def attention_reference(q, k, v, causal: bool = True,
                        sm_scale: Optional[float] = None):
    """O(S^2)-memory reference implementation for tests."""
    scale = sm_scale if sm_scale is not None else q.shape[-1] ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        mask = jnp.arange(sq)[:, None] >= jnp.arange(sk)[None, :]
        logits = jnp.where(mask[None, None], logits, _NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p,
                      v.astype(jnp.float32)).astype(q.dtype)
