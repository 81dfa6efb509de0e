"""Grouped matrix product: rows sorted by group, one matrix a group.

    grouped_matmul(lhs [m, k], rhs [g, k, n], group_sizes [g]) -> [m, n]

Row i of the output is ``lhs[i] @ rhs[group of i]``, the groups laid end
to end from row 0 in the order of ``group_sizes``. Rows beyond the last
group belong to nobody: what the output holds there is not defined, and
no kernel spends time on them (models/transformer.py::routed_experts
masks its buffer's tail on the way in and on the way out).

Two tiers behind one call, chosen by the platform as ops.attention's
are (``kernels_on``): on a TPU the Pallas kernels of ``jax.experimental.pallas.ops.tpu.megablox`` (``gmm``
forward and for the rows' gradient, ``tgmm`` for the matrices'), whose
grid runs over the tiles that hold rows of a group and no further;
elsewhere ``lax.ragged_dot``. XLA's own TPU lowering of ``ragged_dot``
was measured and left: its kernels carry no ``op_name`` (15 % of the
nemotron_twotower_l9_train_s8192 step that no scope could be read for;
my chip run, PR 26).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops import attention

# rows of a tile: the row buffer's length has to be a multiple of it
TILE_M = 512
# the widest tiles of the contracted and of the output width
TILE_K, TILE_N = 896, 1024


def tiles(m: int, k: int, n: int):
    """(m, k, n) tiles of one grouped product of those sizes. The
    kernels ask it of every call, the backward's two products with their
    own sizes (the rows' gradient contracts over what the forward put
    out). Of k and n: the widest whole number of 128 lanes up to
    ``TILE_K`` / ``TILE_N`` that divides the size, so that no tile is
    partly masked (a masked part is computed all the same: at k 2304 and
    n 896 the widest tiles do 4/3 of the work); the widest where none
    divides, and the kernels mask the rest."""
    def dividing(size: int, widest: int) -> int:
        return next((t for t in range(widest, 0, -128) if size % t == 0),
                    widest)

    return TILE_M, dividing(k, TILE_K), dividing(n, TILE_N)


# what the kernels are handed: the rule, not one answer of it
TILING = tiles


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
                   out_dtype=None) -> jax.Array:
    out_dtype = out_dtype or lhs.dtype
    if attention.kernels_on() and lhs.shape[0] % TILE_M == 0:
        from jax.experimental.pallas.ops.tpu.megablox import ops

        return ops.gmm(lhs, rhs, group_sizes.astype(jnp.int32),
                       preferred_element_type=out_dtype, tiling=TILING)
    return lax.ragged_dot(lhs, rhs, group_sizes,
                          preferred_element_type=out_dtype)
