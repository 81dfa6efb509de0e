"""The choice an expert layer's router makes: for scores [T, E] float32
(the router's sigmoid or softmax) and, where the router has one, its
correction bias [E],

    top_k_route(scores, bias, k) -> (chosen [T, k] int32,
                                     gates [T, k] float32,
                                     drawn [E] int32)

each token's k experts in ``lax.top_k``'s order (descending by score plus
bias, ties to the lower expert), the scores at them (the bias chooses
and never weighs), and how many (token, choice) pairs every expert drew.
Only ``gates`` has a gradient: ``d scores[t, e]`` is ``d gates[t, j]``
where ``chosen[t, j] == e``, and nought elsewhere; the bias and the
indices get none.

Two tiers behind one custom VJP, ``router_tier`` the rule between them
(kernels on, the step not partitioned over a mesh, tokens in whole
blocks, the width whole registers of 8 sublanes):

  -> the Pallas kernel ``router_topk_fwd``: grid over blocks of
     ``BLOCK_TOKENS`` tokens, all parallel, the scores laid ``[E,
     tokens]`` (experts along the sublanes, tokens along the lanes: a
     maximum over the experts is an elementwise tree over whole
     registers and one reduce of 8 sublanes). A block is worked
     ``_lanes_at_once`` tokens at a time: the bias added in float32 into
     a VMEM copy, then k passes, each taking every token's maximum and
     the lowest expert that holds it, reading the score there and
     setting the entry to -inf; the entries at -inf at the end are what
     the tokens drew, summed along the lanes into the block's partial
     counts ``[E, 128]``, which are summed outside in int32. ``chosen``
     and ``gates`` are written lane-dense as ``[k, T]`` and turned
     outside. Every output is an exact read of the inputs, so the tier
     is the ``jnp`` tier to the bit.
  -> plain ``jnp``: ``lax.top_k`` (a sort of the whole ``[T, E]`` on a
     TPU), ``take_along_axis`` and a one-hot count. The path off the TPU,
     of a step partitioned over a mesh (whose partitioner cannot split a
     Mosaic kernel), of shapes off the blocks, and the kernel's oracle.

Both tiers share one backward, a dense expression over ``[T, E]`` with
no sort, gather or scatter; each entry of ``d scores`` receives at most
one term, so it is the transpose of ``take_along_axis`` to the bit.

The scores are finite (a sigmoid's or a softmax's, plus a finite bias):
an entry the kernel has taken is -inf, below every one it has not.

Tracing a call counts it in ``moe_router_calls{tier, pass}``; the
``pallas_call`` is traced under ``kernel_trace``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.observability.device_programs import kernel_trace
from ray_tpu.observability.metrics import moe_router_calls
from ray_tpu.ops import attention

# tokens a grid step: the scores' block is [E, BLOCK_TOKENS] float32, 1
# MiB at the widest router of a cell (512), double-buffered
BLOCK_TOKENS = 512
# the widest router the kernel takes: its block, double-buffered, 8 MiB
# of the 16 MiB of VMEM a v5e kernel may use by default
MAX_WIDTH = 2048
# the most choices a token the kernel takes: its k passes are unrolled,
# and the [k, BLOCK_TOKENS] blocks of chosen and gates, double-buffered,
# take 8 KiB a choice (512 KiB at 64)
MAX_CHOICES = 64


def router_tier(tokens: int, width: int, k: int,
                sharded: bool = False) -> bool:
    """Whether a router's choice of these shapes takes the kernel: where
    kernels run at all (``attention.kernels_on``), the step is not
    partitioned over a mesh (``sharded``), the tokens are whole blocks,
    the width whole registers of 8 sublanes (and at most ``MAX_WIDTH``)
    and there are at most as many choices as experts (and at most
    ``MAX_CHOICES``)."""
    return (attention.kernels_on() and not sharded
            and tokens % BLOCK_TOKENS == 0 and width % 8 == 0
            and width <= MAX_WIDTH and 1 <= k <= min(width, MAX_CHOICES))


def _lanes_at_once(width: int) -> int:
    """Tokens a block is worked at a time, a power of two times 128 that
    divides ``BLOCK_TOKENS``: a working array of 32 to 64 registers at the
    cells' widths (E 64: 512 tokens; 128: 256; 320 and 512: 128)."""
    fold = max(1, 256 // width)
    return min(BLOCK_TOKENS, 128 * (1 << (fold.bit_length() - 1)))


def _fwd_kernel(*refs, k: int, biased: bool):
    """One block of tokens: ``k`` passes over ``_lanes_at_once`` tokens at
    a time, each writing one row of ``chosen`` and ``gates``."""
    from jax.experimental import pallas as pl

    if biased:
        s_ref, b_ref, chosen_ref, gate_ref, drawn_ref, left_ref = refs
    else:
        s_ref, chosen_ref, gate_ref, drawn_ref, left_ref = refs
    width, wide = left_ref.shape
    drawn_ref[...] = jnp.zeros_like(drawn_ref)

    def some_tokens(piece, _):
        lanes = pl.ds(pl.multiple_of(piece * wide, wide), wide)
        # the expert's index as float32 (exact below 2 ** 24): the
        # reductions over the sublanes are float32 ones
        expert = lax.broadcasted_iota(jnp.int32, (width, wide), 0).astype(
            jnp.float32)
        scores = s_ref[:, lanes]
        left_ref[...] = scores + b_ref[...] if biased else scores
        for j in range(k):
            left = left_ref[...]
            top = jnp.max(left, axis=0, keepdims=True)
            # the lowest expert among equal maxima: lax.top_k's order
            first = jnp.min(jnp.where(left == top, expert, float(width)),
                            axis=0, keepdims=True)
            hit = expert == first
            chosen_ref[j:j + 1, lanes] = first.astype(jnp.int32)
            gate_ref[j:j + 1, lanes] = (
                jnp.max(jnp.where(hit, s_ref[:, lanes], -jnp.inf), axis=0,
                        keepdims=True) if biased else top)
            left_ref[...] = jnp.where(hit, -jnp.inf, left)
        taken = jnp.where(left_ref[...] == -jnp.inf, 1.0, 0.0)
        counted = taken[:, :128]
        for at in range(128, wide, 128):
            counted = counted + taken[:, at:at + 128]
        drawn_ref[0] += counted

    lax.fori_loop(0, s_ref.shape[1] // wide, some_tokens, None)


# jitted for the reason ``ssd._conv_call`` is: the layers of a step share
# one trace and one lowering of the kernel
@functools.partial(jax.jit, static_argnums=(2,))
def _fwd_call(scores_t, bias, k: int):
    """(chosen [k, T] int32, gates [k, T] float32, partial counts [T /
    BLOCK_TOKENS, E, 128] float32) of the scores laid [E, T] and the bias
    [E, 1] float32 or None."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    width, t = scores_t.shape
    blocks = t // BLOCK_TOKENS
    vma = jax.typeof(scores_t).vma
    biased = bias is not None
    tokens = pl.BlockSpec((width, BLOCK_TOKENS), lambda i: (0, i))
    rows = pl.BlockSpec((k, BLOCK_TOKENS), lambda i: (0, i))
    with kernel_trace("router_topk_fwd"):
        return pl.pallas_call(
            functools.partial(_fwd_kernel, k=k, biased=biased),
            grid=(blocks,),
            in_specs=[tokens] + ([pl.BlockSpec((width, 1), lambda i: (0, 0))]
                                 if biased else []),
            out_specs=[rows, rows,
                       pl.BlockSpec((1, width, 128), lambda i: (i, 0, 0))],
            out_shape=[
                jax.ShapeDtypeStruct((k, t), jnp.int32, vma=vma),
                jax.ShapeDtypeStruct((k, t), jnp.float32, vma=vma),
                jax.ShapeDtypeStruct((blocks, width, 128), jnp.float32,
                                     vma=vma),
            ],
            scratch_shapes=[pltpu.VMEM((width, _lanes_at_once(width)),
                                       jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",)),
            interpret=attention.kernels_interpreted(),
            name="router_topk_fwd",
        )(scores_t, *([bias] if biased else []))


def _kernel_top_k(scores, bias, k: int):
    chosen, gates, drawn = _fwd_call(
        scores.T, None if bias is None else bias[:, None], k)
    return chosen.T, gates.T, drawn.astype(jnp.int32).sum((0, 2))


def _jnp_top_k(scores, bias, k: int):
    _, chosen = lax.top_k(scores if bias is None else scores + bias, k)
    gates = jnp.take_along_axis(scores, chosen, axis=-1)
    drawn = (chosen[..., None] == jnp.arange(scores.shape[-1])).sum(
        (0, 1), dtype=jnp.int32)
    return chosen, gates, drawn


def _count(kernel: bool, which: str) -> None:
    moe_router_calls.inc(1, {"tier": "kernel" if kernel else "jnp",
                             "pass": which})


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _top_k(scores, bias, k: int, kernel: bool):
    _count(kernel, "fwd")
    return (_kernel_top_k if kernel else _jnp_top_k)(scores, bias, k)


def _top_k_fwd(scores, bias, k: int, kernel: bool):
    out = _top_k.fun(scores, bias, k, kernel)
    return out, (out[0], bias)


def _top_k_bwd(k: int, kernel: bool, kept, cotangents):
    _count(kernel, "bwd")
    chosen, bias = kept
    # the counts' cotangent (float0 zeros) has the router's width
    d_gates, width = cotangents[1], cotangents[2].shape[0]
    hit = chosen[..., None] == jnp.arange(width, dtype=jnp.int32)
    d_scores = jnp.sum(jnp.where(hit, d_gates[..., None], 0.0), axis=1)
    return d_scores, None if bias is None else jnp.zeros_like(bias)


_top_k.defvjp(_top_k_fwd, _top_k_bwd)


def top_k_route(scores, bias, k: int, sharded: bool = False):
    """(chosen [T, k] int32, gates [T, k] float32, drawn [E] int32) of
    ``scores`` [T, E] float32 and ``bias`` [E] float32 or None.
    ``sharded``: the step is partitioned over a mesh
    (``router_tier``)."""
    t, width = scores.shape
    return _top_k(scores, bias, k, router_tier(t, width, k, sharded))
