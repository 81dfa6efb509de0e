"""Grouped matrix product: rows sorted by group, one matrix a group.

    grouped_matmul(lhs [m, k], rhs [g, k, n], group_sizes [g]) -> [m, n]

Row i of the output is ``lhs[i] @ rhs[group of i]``, the groups laid end
to end from row 0 in the order of ``group_sizes``. Rows beyond the last
group belong to nobody: what the output holds there is not defined, and
no kernel spends time on them.

Two tiers behind one call, chosen by the platform as ops.attention's
are (``kernels_on``): on a TPU the Pallas kernels of ``jax.experimental.pallas.ops.tpu.megablox`` (``gmm``
forward and for the rows' gradient, ``tgmm`` for the matrices'), whose
grid runs over the tiles that hold rows of a group and no further;
elsewhere ``lax.ragged_dot``. XLA's own TPU lowering of ``ragged_dot``
was measured and left: its kernels carry no ``op_name`` (15 % of the
nemotron_twotower_l9_train_s8192 step that no scope could be read for;
my chip run, PR 26).

The rows' way into that buffer and out of it is here too
(``rows_from_tokens``, ``tokens_from_rows``: the expert layer's dispatch
and combine), because it stops where the rows stop as the products do.
On a TPU, with a buffer of whole tiles and tokens of whole blocks
(``movement_block``), work is done for the rows that
hold a (token, choice) pair and no other, forwards and in both
transposes (each the other one, by a custom VJP, so that autodiff does
not turn a trimmed gather into a scatter-add over the whole buffer):
towards the buffer a loop gathers ``MOVE_ROWS`` rows a pass, as
many passes as hold such a row; towards the tokens the kernel
``rows_added`` takes a block of tokens at a time and reads, expert by
expert, the few consecutive rows that belong to it (a held expert's
rows ascend by token: ``Places.spans``). The buffer's tail then holds
nought on the way in, is never read on the way out, and adds nothing to
any gradient, whatever a product left there; elsewhere the plain form
gathers and scatter-adds the whole buffer under a mask, and is the
trimmed form's oracle.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.observability.device_programs import kernel_trace
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

        # (the transposed products are traced by megablox's own rule,
        # where JAX names them: jitted ``gmm`` and ``tgmm``)
        with kernel_trace("gmm"):
            return ops.gmm(lhs, rhs, group_sizes.astype(jnp.int32),
                           preferred_element_type=out_dtype, tiling=TILING)
    return lax.ragged_dot(lhs, rhs, group_sizes,
                          preferred_element_type=out_dtype)


# -- the rows' way between the tokens [T, H] and the buffer [rows, H] --------

# the most rows one pass of the trimmed gather moves: whole tiles
MOVE_ROWS = 8 * TILE_M
# the kernel towards the tokens: tokens a grid step, rows a copy brings
# in, and the experts whose first copy of a block is in flight at once
TOKENS_A_BLOCK = 512
COPY_ROWS = 64
COPIES_AHEAD = 16


class Places(NamedTuple):
    """Where the buffer's rows come from and go back to."""
    token: jax.Array            # [rows] int32: the token of each row
    count: jax.Array            # the first ``count`` rows hold a pair
    # [2, held experts * blocks of tokens] int32, expert-major: the
    # first and the past-the-last row of each expert's pairs with each
    # block's tokens; None where the plain form runs
    spans: Optional[jax.Array]


def movement_block(rows: int, tokens: int) -> int:
    """Rows a pass of the trimmed gather moves in a buffer of ``rows``
    (the most whole tiles up to ``MOVE_ROWS`` that divide it); 0 where
    the plain form runs: off a TPU, a buffer that is not whole tiles, or
    tokens that are not whole blocks of the kernel's. From the platform
    and the shapes alone, as ``grouped_matmul`` picks its kernel."""
    if (not attention.kernels_on() or rows % TILE_M
            or tokens % TOKENS_A_BLOCK):
        return 0
    return next(b for b in range(MOVE_ROWS, 0, -TILE_M) if rows % b == 0)


def rows_moved(count: jax.Array, rows: int, tokens: int) -> jax.Array:
    """Rows of a buffer of ``rows`` the movement touches when the first
    ``count`` hold a pair: whole passes where it is trimmed, the whole
    buffer where it is not."""
    block = movement_block(rows, tokens)
    if not block:
        return jnp.full_like(count, rows)
    return -(-count // block) * block


def places(local: jax.Array, order: jax.Array, count: jax.Array,
           held: int, choices: int) -> Places:
    """``Places`` of the buffer whose row r holds pair ``order[r]`` of
    ``local`` [tokens * choices] (each pair's held expert, ``held`` for
    none; a stable sort of it gave ``order``, so a held expert's rows
    ascend by token)."""
    rows, tokens = order.shape[0], local.shape[0] // choices
    spans = None
    if movement_block(rows, tokens):
        blocks = tokens // TOKENS_A_BLOCK
        pairs = (local.reshape(blocks, 1, -1)
                 == jnp.arange(held)[:, None]).sum(-1, dtype=jnp.int32)
        pairs = pairs.T.reshape(-1)
        last = jnp.cumsum(pairs)
        # as the groups' ends: a pair beyond the buffer has no row
        spans = jnp.minimum(jnp.stack([last - pairs, last]), rows)
    return Places(order // choices, count, spans)


def rows_from_tokens(xt: jax.Array, where: Places,
                     readers: int = 1) -> Tuple[jax.Array, ...]:
    """Dispatch. xt [T, H] -> the buffer [rows, H] of xt's type, once a
    reader: row r < count is ``xt[token[r]]``, every other row nought.
    Its transpose adds the first count rows' cotangents into ``d xt``
    and reads no other. Each product that reads the buffer takes its own
    (they are one array): each then hands the transpose its own
    cotangent, which adds them row by row as it adds the rows to their
    tokens, where autodiff would first add them over the whole
    buffer."""
    rows, tokens = where.token.shape[0], xt.shape[0]
    block = movement_block(rows, tokens)
    if block:
        return _dispatch(xt, where, block, tokens, readers)
    live = jnp.arange(rows) < where.count
    return (jnp.where(live[:, None], xt[where.token], 0),) * readers


def tokens_from_rows(rows_out: jax.Array, weights: jax.Array,
                     where: Places, tokens: int, dtype) -> jax.Array:
    """Combine. rows_out [rows, H] float32, weights [rows] float32 ->
    [tokens, H] of ``dtype``, added up in float32: token t's sum of
    ``rows_out[r] * weights[r]`` over the r < count with ``token[r]`` t.
    Rows from count on are masked before they are weighed, or never
    read: what they hold (on a chip, NaN now and then) reaches neither
    the sum nor ``d rows_out`` nor the weights' gradient."""
    rows = where.token.shape[0]
    block = movement_block(rows, tokens)
    if block:
        return _combine(rows_out, weights, where, block, tokens, dtype)
    live = jnp.arange(rows) < where.count
    weighed = jnp.where(live[:, None], rows_out, 0.0) * weights[:, None]
    return jnp.zeros((tokens, rows_out.shape[1]), jnp.float32).at[
        where.token].add(weighed).astype(dtype)


def _pass(i, where: Places, block: int):
    """(first row, tokens [block], which rows hold a pair [block, 1]) of
    pass i of the gather."""
    start = i * block
    live = start + jnp.arange(block) < where.count
    return (start, lax.dynamic_slice(where.token, (start,), (block,)),
            live[:, None])


@functools.partial(jax.jit, static_argnames=("tokens", "dtype"))
def rows_added(rows, weights, where: Places, tokens: int, dtype):
    """[tokens, H] of ``dtype``, added up in float32: row r < count of
    each array of ``rows`` (times ``weights[r]``, where given) added to
    row ``token[r]``. A grid step takes ``TOKENS_A_BLOCK`` tokens; of
    each held expert in turn it copies in the rows ``where.spans`` gives
    it, ``COPY_ROWS`` at a time from a row that tiles (the first copy of
    ``COPIES_AHEAD`` experts in flight while the rows before them are
    added), and adds the rows of the span, one at a time, to their
    tokens' rows of the block. No row outside a span is added, whatever
    the copy brought. Jitted, so that the layers share one lowering."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    total, h = rows[0].shape
    tb, c = TOKENS_A_BLOCK, COPY_ROWS
    blocks = tokens // tb
    held = where.spans.shape[1] // blocks
    ahead = min(COPIES_AHEAD, held)
    # a copy's rows widened, and several arrays' added, all at once
    staged = rows[0].dtype != jnp.float32 or len(rows) > 1
    direct = jnp.dtype(dtype) == jnp.float32    # the block is the sum
    prefetched = (where.spans, where.token) + (
        () if weights is None else (weights,))

    def kernel(*refs):
        spans, token = refs[:2]
        weight = None if weights is None else refs[2]
        rows_hbm = refs[len(prefetched):][:len(rows)]
        out, bufs, sems, *more = refs[len(prefetched) + len(rows):]
        acc = out if direct else more[0]
        wide = more[-1] if staged else None
        b = pl.program_id(0)
        acc[...] = jnp.zeros_like(acc)

        def copies(g, first):
            start = pl.multiple_of(
                jnp.minimum(first // 16 * 16, total - c), 16)
            slot = g % ahead
            return start, [pltpu.make_async_copy(
                source.at[pl.ds(start, c)], bufs.at[i, slot],
                sems.at[i, slot]) for i, source in enumerate(rows_hbm)]

        def begin(g, first):
            for copy in copies(g, first)[1]:
                copy.start()

        def ahead_of(g, carried):
            begin(g, spans[0, g * blocks + b])
            return carried

        lax.fori_loop(0, ahead, ahead_of, 0)

        def expert(g, carried):
            first, last = spans[0, g * blocks + b], spans[1, g * blocks + b]
            slot = g % ahead
            # its first copy is in flight, whether the span is empty or not
            for copy in copies(g, first)[1]:
                copy.wait()

            def add(reached):
                """The span's rows from ``reached`` as far as the copy
                that holds them reaches; the next copy, where rows are
                left; -> the row after them."""
                start = copies(g, reached)[0]
                if staged:
                    wide[...] = sum(bufs[i, slot].astype(jnp.float32)
                                    for i in range(len(rows)))
                end = jnp.minimum(last, start + c)

                def one(r, carried):
                    j = r - start
                    row = (wide[pl.ds(j, 1), :] if staged
                           else bufs[0, slot, pl.ds(j, 1), :])
                    if weight is not None:
                        row = row * weight[r]
                    acc[pl.ds(token[r] - b * tb, 1), :] += row
                    return carried

                lax.fori_loop(reached, end, one, 0)

                @pl.when(end < last)
                def _():
                    for copy in copies(g, end)[1]:
                        copy.start()
                    for copy in copies(g, end)[1]:
                        copy.wait()

                return end

            lax.while_loop(lambda reached: reached < last, add, first)

            @pl.when(g + ahead < held)
            def _():
                begin(g + ahead, spans[0, (g + ahead) * blocks + b])

            return carried

        lax.fori_loop(0, held, expert, 0)
        if not direct:
            out[...] = acc[...].astype(out.dtype)

    scratch = [pltpu.VMEM((len(rows), ahead, c, h), rows[0].dtype),
               pltpu.SemaphoreType.DMA((len(rows), ahead))]
    if not direct:
        scratch.append(pltpu.VMEM((tb, h), jnp.float32))
    if staged:
        scratch.append(pltpu.VMEM((c, h), jnp.float32))
    with kernel_trace("rows_added"):
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(prefetched), grid=(blocks,),
                in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * len(rows),
                out_specs=pl.BlockSpec((tb, h), lambda b, *_: (b, 0)),
                scratch_shapes=scratch),
            out_shape=jax.ShapeDtypeStruct((tokens, h), dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=64 * 2**20),
            interpret=attention.kernels_interpreted(),
            name="rows_added",
        )(*prefetched, *rows)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _dispatch(xt, where: Places, block: int, tokens: int, readers: int):
    def one(i, buf):
        start, idx, live = _pass(i, where, block)
        return lax.dynamic_update_slice(
            buf, jnp.where(live, xt[idx], 0), (start, 0))

    return (lax.fori_loop(
        0, -(-where.count // block), one,
        jnp.zeros((where.token.shape[0], xt.shape[1]), xt.dtype)),
            ) * readers


def _dispatch_fwd(xt, where, block, tokens, readers):
    return _dispatch(xt, where, block, tokens, readers), where


def _dispatch_bwd(block, tokens, readers, where, d_rows):
    return rows_added(tuple(d_rows), None, where, tokens=tokens,
                      dtype=d_rows[0].dtype), None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _combine(rows_out, weights, where: Places, block: int, tokens: int,
             dtype):
    return rows_added((rows_out,), weights, where, tokens=tokens,
                      dtype=dtype)


def _combine_fwd(rows_out, weights, where, block, tokens, dtype):
    return (_combine(rows_out, weights, where, block, tokens, dtype),
            (rows_out, weights, where))


def _combine_bwd(block, tokens, dtype, kept, d_out):
    """A dispatch of the cotangent, weighed; the weights' gradient is
    each row's product with what it was weighed for. A pass reads its
    rows of ``rows_out`` and writes their cotangents in their place: no
    second [rows, H] buffer, and the tail stays what it was, which no
    grouped product reads."""
    rows_out, weights, where = kept
    rows, h = rows_out.shape

    def one(i, carried):
        buf, d_weights = carried
        start, idx, live = _pass(i, where, block)
        taken = jnp.where(live, d_out[idx].astype(buf.dtype), 0.0)
        mine = jnp.where(
            live, lax.dynamic_slice(buf, (start, 0), (block, h)), 0.0)
        weight = lax.dynamic_slice(weights, (start,), (block,))
        return (lax.dynamic_update_slice(
                    buf, taken * weight[:, None], (start, 0)),
                lax.dynamic_update_slice(
                    d_weights, (taken * mine).sum(-1), (start,)))

    d_rows, d_weights = lax.fori_loop(
        0, -(-where.count // block), one,
        (rows_out, jnp.zeros((rows,), weights.dtype)))
    return d_rows, d_weights, None


_combine.defvjp(_combine_fwd, _combine_bwd)
