"""The gated delta rule with a decay a channel (Kimi Delta Attention,
Kimi Linear, arXiv 2510.26692), a chunk at a time.

The recurrence, per head, with a state S [d, d] float32 from S_0 = 0:

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t,            alpha_t = exp(g_t) in (0, 1)^d

Mamba-2's scan (ops/ssd.py) decays its state by one number a head and
position; here the decay is a vector over the key's channels and the
state's transition a decayed Householder reflection (``beta`` up to 2
gives it eigenvalues down to -1), so a chunk needs the inverse of a unit
lower-triangular matrix (the WY / UT form) and the decay cannot be pulled
out of the keys' products as one number a position.

A chunk of C positions entering with the state S, with G_i the running
sum of g from the chunk's start through position i:

    A_ij  = sum_c k_ic k_jc exp(G_ic - G_jc)          (i > j)
    B_ij  = sum_c q_ic k_jc exp(G_ic - G_jc)          (i >= j)
    T     = (I + Diag(beta) A)^-1 Diag(beta)
    W     = T (k * exp(G)),    U~ = T v
    U     = U~ - W S                       what each position writes
    O     = (q * exp(G)) S + B U
    S'    = Diag(exp(G_C)) S + (k * exp(G_C - G))^T U

Everything but the three lines with S is a chunk's own
(``_chunk_operands``); the three lines run chunk after chunk
(``_states_fwd``). exp(G_i - G_j) as a product of one
factor a row and one a column overflows in the column's factor where the
decay is strong, whatever single reference the chunk takes: the pairs
(i, j) are therefore covered by log2(C) levels of blocks, level m the
pairs whose positions lie in the two halves (of m positions each) of one
block of 2m, with the reference between the halves, so that both
factors' exponents are at most nought for every pair the level keeps
(``_decayed_products``). No decay is floored.

The inverse is built from blocks of one position up, a block of 2m from
its two halves' (``_inverse_by_halves``: two products a level), with a
rule of its own for the backward (``-T^T dT T^T``, two products more).

One op, ``gated_delta_rule``, behind a custom VJP: the forward keeps its
inputs and the state each chunk entered with ([B, H, S/C, d, d] float32),
nothing per position; the backward rebuilds a chunk's operands, walks the
chunks from the last to the first with the state's cotangent and pulls
the operands' cotangents back through the chunk's own part.

Two tiers behind the one op, as ops/ssd.py has them for its scan;
``walk_tier`` says which a call takes, from the platform
(``attention.kernels_on``), the shapes and whether the step is
partitioned over a mesh, and nothing a user sets moves it:

  -> one Pallas kernel pair, ``kda_chunk_fwd`` / ``kda_chunk_bwd``, that
     makes and uses a chunk's operands in VMEM: nothing a position and
     channel in float32 (the running sums, a level's factors, exp(G), U~,
     W, the scores, the inverse's levels) is an array in HBM, and q, k, v,
     g, o and their cotangents are read and written as the caller holds
     them, [B, S, heads * d] with head h the block of d lanes at offset
     h * d (``attention._layout_of``'s lanes); beta alone is turned
     outside, 4 bytes a position and head. (As XLA's, with the scores and
     the walk as two kernel pairs between them, every operand of a chunk
     was a whole array in HBM, 1.3 GiB a group of 8 heads forward where
     96 MiB would do, and the delta rule 35.5 % of the Solar step: PR 39,
     PR 40.) The grid runs over (batch row, ``STEP_HEADS`` heads, block
     of ``WALK_CHUNKS`` chunks) with the chunks innermost and sequential;
     a head's state (forward) or its cotangent (backward) lives in a VMEM
     scratch [d, d] float32 from a row's first chunk to its last, as the
     ``jnp`` tier holds it ([d of k, d of v]: what is turned for a product
     is then a chunk's own operand, never the state the next chunk waits
     for). A head's inverse is ten products each of which waits for the
     one before, and its walk three a chunk: bound by the matrix unit's
     latency, not its rate, so a grid step takes four heads and every
     stage is written for all of them before the next (one head a step
     took 1.74 ms forward and 2.57 backward a call of 8 heads on a v5e,
     four 0.97 and 1.67; unrolling one head's groups moved nothing). A grid
     step works a group of 128 positions at a time (two chunks of 64):
     the chunks' [C, C] matrices on the diagonal of one [128, 128] matrix,
     which is what a product of the group's rows with the group's rows
     gives under a mask, or folded side by side along the lanes for the
     inverse's products, so that each fills the matrix unit's width; the
     levels' references are copies of rows on the vector unit, the running
     sum a product of a 0/1 triangle with three bfloat16 pieces of g
     (exact). The backward rebuilds a group's operands once and pulls the
     walk's cotangents back through the same factors.
  -> plain jnp: ``_chunk_operands`` for all the chunks at once ([B, h, n,
     C, d] copies of the inputs; what is per position and channel in
     float32 is as large as g itself, so a caller with many heads hands
     the op a group of them at a time: the model's ``kda_block`` does,
     ``HEADS_AT_ONCE``) and a ``lax.scan`` over the chunks, whose state
     goes through HBM once a chunk. The path off the TPU, of shapes off
     the kernels' tiles, of a step partitioned over a mesh, and the
     kernels' oracle.

q and k are expected normalised a head by the caller (the model's
``kda_block``), q scaled; g and beta float32. Products take the type of
``q`` and accumulate in float32; the running sums, the decays, the
inverse and the state are float32. Tracing counts a call's chunks in
``kda_chunks{tier, pass}``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.observability.device_programs import kernel_trace
from ray_tpu.observability.metrics import kda_chunks
from ray_tpu.ops import attention
from ray_tpu.ops.ssd import _NN, _NT, _TN, _dot

# heads a caller should hand the op at once: what is float32 a position
# and channel (the running sums, a level's two factors) is 32 MiB a
# quantity at 8 heads of 128 over 8192 positions, where all 64 heads'
# would be 256 MiB each
HEADS_AT_ONCE = 8
# chunks a grid step of the walk's kernels takes, one after the other in
# one block of code
WALK_CHUNKS = 8
# heads a grid step of the kernels takes, each stage of one beside the
# other's (``_group_operands``)
STEP_HEADS = 4
# the inverse's products read float32 operands: each level feeds the
# next, and roundings to bfloat16 would add up over twelve of them
_INVERSE_PRECISION = lax.Precision.HIGHEST


def _quarters(c: int, half: int):
    """[c, c] bool: the pairs (i, j) of level ``half``, i in the second
    half of a block of ``2 * half`` positions and j in its first. Over
    the levels 1, 2, ... c / 2 every pair i > j lies in exactly one."""
    i = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    j = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    return ((i // (2 * half) == j // (2 * half))
            & (i % (2 * half) >= half) & (j % (2 * half) < half))


def _mm(a, b, dims: str, dtype):
    """An einsum whose operands are rounded to ``dtype`` and whose sum is
    float32."""
    return jnp.einsum(dims, a.astype(dtype), b.astype(dtype),
                      preferred_element_type=jnp.float32)


def _inverse_by_halves(a):
    """(I + a)^-1 for a strictly lower-triangular [..., C, C] float32, C a
    power of two: from blocks of one position up, the inverse of a block
    of 2m from its two halves',

        [[P, 0], [X, Q]]^-1 = [[P^-1, 0], [-Q^-1 X P^-1, Q^-1]],

    for all the blocks of a level at once: with D the inverses of the
    blocks of m on the diagonal and X_m what ``a`` has in the lower left
    quarters of the blocks of 2m, the level is ``D - D X_m D``. Two
    products a level, twelve at C = 64, each of factors no larger than the
    inverse's own entries. (The six products ``prod_i (I + (-a)^(2^i))``
    are no use here: the powers of ``a`` reach (1 + c)^C where the keys of
    a chunk share a direction, c their common cosine times beta, and at
    c = 0.4 float32 returns noise: read on the chip as gradients of the
    mixers' projections up to 2.5 of their norm off the reference's on 4
    seeds of 14, PR 39.)"""
    c = a.shape[-1]
    mm = functools.partial(jnp.matmul, precision=_INVERSE_PRECISION)
    inv = jnp.broadcast_to(jnp.eye(c, dtype=a.dtype), a.shape)
    half = 1
    while half < c:
        inv = inv - mm(mm(inv, jnp.where(_quarters(c, half), a, 0.0)), inv)
        half *= 2
    return inv


@jax.custom_vjp
def unit_lower_inverse(a):
    return _inverse_by_halves(a)


def _inverse_fwd(a):
    inv = _inverse_by_halves(a)
    return inv, inv


def _inverse_bwd(inv, d_inv):
    mm = functools.partial(jnp.matmul, precision=_INVERSE_PRECISION)
    turned = jnp.swapaxes(inv, -1, -2)
    return (-mm(mm(turned, d_inv), turned),)


unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


def _decayed_products(q, k, gs, dtype):
    """(B, A) [..., C, C] float32: entry (i, j) is ``sum_c q_ic k_jc
    exp(G_ic - G_jc)`` and the same with k for q, for i > j and nought
    elsewhere; q, k and the running sums ``gs`` [..., C, d] float32."""
    c = k.shape[-2]
    at = jnp.arange(c)

    def level(q, k, gs, half: int):
        # the last position of a block's first half: at or after every j
        # the level keeps, before every i
        ref = jnp.take(gs, at // (2 * half) * 2 * half + half - 1, axis=-2)
        keep = _quarters(c, half)
        # the pairs the level keeps never meet the clamp; the others'
        # factors stay finite, for the products and their gradients
        rows = jnp.exp(jnp.minimum(gs - ref, 0.0))
        cols = k * jnp.exp(jnp.minimum(ref - gs, 0.0))
        # q's rows above k's: the two products share the keys' factor
        both = _mm(jnp.concatenate([q * rows, k * rows], axis=-2), cols,
                   "...id,...jd->...ij", dtype)
        return jnp.where(jnp.tile(keep, (2, 1)), both, 0.0)

    out = jnp.zeros(k.shape[:-2] + (2 * c, c), jnp.float32)
    half = 1
    while half < c:
        out = out + jax.checkpoint(level, static_argnums=(3,))(q, k, gs, half)
        half *= 2
    return out[..., :c, :], out[..., c:, :]


def _chunk_operands(q, k, v, g, beta):
    """What the chunks' walk reads, for q, k, v, g [B, h, n, C, d] and
    beta [B, h, n, C]: (U~ [.., C, d], W [.., C, d], q * exp(G), k *
    exp(G_C - G), B [.., C, C], exp(G_C) [.., d]), float32 but for what is
    a product's operand alone."""
    dtype = q.dtype
    c = q.shape[-2]
    gs = jnp.cumsum(g, axis=-2)
    qf, kf = q.astype(jnp.float32), k.astype(jnp.float32)
    qk, kk = _decayed_products(qf, kf, gs, dtype)
    # a position reads what it writes itself: no decay between the two
    qk = qk + jnp.eye(c, dtype=jnp.float32) * jnp.sum(
        qf * kf, axis=-1)[..., None]
    solve = unit_lower_inverse(beta[..., :, None] * kk) * beta[..., None, :]
    grown = jnp.exp(gs)
    last = gs[..., -1:, :]
    ut = _mm(solve, v, "...ij,...jd->...id", dtype)
    w = _mm(solve, kf * grown, "...ij,...jd->...id", dtype)
    return (ut, w.astype(dtype), (qf * grown).astype(dtype),
            (kf * jnp.exp(last - gs)).astype(dtype), qk.astype(dtype),
            jnp.exp(last[..., 0, :]))


def _chunks_first(t):
    """[B, h, n, ...] -> [n, B, h, ...]."""
    return jnp.moveaxis(t, 2, 0)


def _states_fwd(operands, dtype):
    """The chunks' walk -> (o [B, h, n, C, d] float32, the state each
    chunk entered with [B, h, n, d, d] float32)."""
    ut = operands[0]
    b, h, _, _, d = ut.shape

    def chunk(state, mine):
        ut, w, qg, kg, qk, last = mine
        u = ut - _mm(w, state, "bhck,bhkv->bhcv", dtype)
        o = _mm(qg, state, "bhck,bhkv->bhcv", dtype) \
            + _mm(qk, u, "bhij,bhjv->bhiv", dtype)
        after = last[..., None] * state + _mm(kg, u, "bhck,bhcv->bhkv",
                                              dtype)
        return after, (o, state)

    _, (o, states) = lax.scan(
        chunk, jnp.zeros((b, h, d, d), jnp.float32),
        tuple(_chunks_first(t) for t in operands))
    return jnp.moveaxis(o, 0, 2), jnp.moveaxis(states, 0, 2)


def _states_bwd(operands, states, do, dtype):
    """The walk's transpose, from the last chunk to the first -> the
    cotangents of ``operands``."""
    b, h, _, _, d = do.shape

    def chunk(d_after, mine):
        (ut, w, qg, kg, qk, last), state, do = mine
        u = ut - _mm(w, state, "bhck,bhkv->bhcv", dtype)
        du = _mm(qk, do, "bhij,bhiv->bhjv", dtype) \
            + _mm(kg, d_after, "bhck,bhkv->bhcv", dtype)
        d_state = _mm(qg, do, "bhck,bhcv->bhkv", dtype) \
            + last[..., None] * d_after \
            - _mm(w, du, "bhck,bhcv->bhkv", dtype)
        return d_state, (
            du, -_mm(du, state, "bhcv,bhkv->bhck", dtype),
            _mm(do, state, "bhcv,bhkv->bhck", dtype),
            _mm(u, d_after, "bhcv,bhkv->bhck", dtype),
            _mm(do, u, "bhiv,bhjv->bhij", dtype),
            jnp.sum(d_after * state, axis=-1))

    _, cotangents = lax.scan(
        chunk, jnp.zeros((b, h, d, d), jnp.float32),
        (tuple(_chunks_first(t) for t in operands), _chunks_first(states),
         _chunks_first(do)), reverse=True)
    return tuple(jnp.moveaxis(t, 0, 2) for t in cotangents)


# ===========================================================================
# The kernel tier. A grid step takes ``WALK_CHUNKS`` chunks of one (batch
# row, head) as groups of ``_GROUP`` = 128 consecutive positions: 128 /
# chunk chunks whose [chunk, chunk] matrices lie on the diagonal of one
# [128, 128] matrix ("diagonal form": what a product of the group's rows
# with the group's rows gives, under a mask) or side by side along the
# lanes ("folded": [chunk, 128], the diagonal form's row blocks summed),
# so that every product fills the matrix unit's 128 lanes. The state is
# [d of k, d of v] as in the ``jnp`` tier: the products with it take it as
# it lies, and what has to be turned is a chunk's own operand.
# ===========================================================================

_GROUP = 128


def walk_tier(chunk: int, head_dim: int, chunks: int,
              sharded: bool = False) -> bool:
    """Whether a call of these shapes takes the kernels: the one rule
    behind ``gated_delta_rule``, of ``ssd.scan_tier``'s form. Where
    kernels run at all (``attention.kernels_on``), the step is not
    partitioned over a mesh (``sharded``), a head is whole tiles of 128
    lanes, a chunk whole tiles of a 16-bit type's 16 rows and no more than
    a group's 128, and the chunks come in whole grid steps."""
    return (attention.kernels_on() and not sharded and head_dim % 128 == 0
            and chunk % 16 == 0 and chunk <= _GROUP
            and chunks % WALK_CHUNKS == 0)


def _thirds(t):
    """float32 [m, n] -> bfloat16 [m, 3n]: three pieces side by side whose
    sum is t to float32's last bit, so that a product with a matrix of
    noughts and ones is three bfloat16 passes and exact (``_whole``)."""
    hi = t.astype(jnp.bfloat16)
    rest = t - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    low = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return jnp.concatenate([hi, mid, low], axis=1)


def _whole(pieces):
    n = pieces.shape[1] // 3
    return pieces[:, :n] + pieces[:, n:2 * n] + pieces[:, 2 * n:]


def _levels(chunk: int) -> int:
    return chunk.bit_length() - 1


@functools.lru_cache(maxsize=None)
def _group_masks(chunk: int):
    """The noughts and ones a group's kernel code reads, made once on the
    host and held in VMEM: (bfloat16 [128, 128], the triangle of a chunk's
    running sum; float32 [levels + 2, 128, 128]: of each level the pairs
    it keeps (``_quarters``: a block of a level lies in one chunk, so no
    pair of two chunks is kept), then the diagonal, then the pairs of one
    chunk)."""
    import numpy as np

    i = np.arange(_GROUP)[:, None]
    j = np.arange(_GROUP)[None, :]
    keep = []
    for level in range(_levels(chunk)):
        half = 1 << level
        keep.append((i // (2 * half) == j // (2 * half))
                    & (i % (2 * half) >= half) & (j % (2 * half) < half))
    block = i // chunk == j // chunk
    keep += [i == j, block]
    return ((block & (j <= i)).astype(jnp.bfloat16),
            np.stack(keep).astype(np.float32))


def _dot_exact(lhs, rhs, contract):
    """A product of float32 operands as float32's (``_INVERSE_PRECISION``)."""
    return lax.dot_general(lhs, rhs, (contract, ((), ())),
                           precision=_INVERSE_PRECISION,
                           preferred_element_type=jnp.float32)


def _fold(diagonal, chunk: int):
    """[128, n] in diagonal form -> [chunk, n] folded."""
    return functools.reduce(jnp.add, (
        diagonal[at:at + chunk] for at in range(0, _GROUP, chunk)))


def _spread(folded, block):
    """[chunk, 128] folded -> [128, 128] in diagonal form."""
    return jnp.concatenate(
        [folded] * (_GROUP // folded.shape[0]), axis=0) * block


def _twice(mask):
    return jnp.concatenate([mask, mask], axis=0)


def _ends(rows, chunk: int):
    """Of [128, n], each chunk's last row: a list of [1, n]."""
    return [rows[at + chunk - 1:at + chunk]
            for at in range(0, _GROUP, chunk)]


def _in_block(rows: int, size: int):
    """[rows, 1] int32: a row's place in its block of ``size`` rows."""
    return lax.broadcasted_iota(jnp.int32, (rows, 1), 0) % size


def _by_block(t, half: int):
    return t.reshape(t.shape[0] // (2 * half), 2 * half, t.shape[1])


def _level_reference(gs, half: int):
    """[rows, d]: of each row the row of ``gs`` at the last position of
    the first half of the row's block of ``2 * half``: copies, on the
    vector unit (a block of 8 rows or more is whole registers; the two
    smallest levels turn the rows by one or two)."""
    from jax.experimental.pallas import tpu as pltpu

    n = gs.shape[0]
    if half >= 4:
        blocks = _by_block(gs, half)
        return jnp.broadcast_to(blocks[:, half - 1:half, :],
                                blocks.shape).reshape(gs.shape)
    at = _in_block(n, 2 * half)
    before = pltpu.roll(gs, 1, 0)
    if half == 1:
        return jnp.where(at == 1, before, gs)
    return jnp.where(at == 0, pltpu.roll(gs, n - 1, 0), jnp.where(
        at == 1, gs, jnp.where(at == 2, before, pltpu.roll(gs, 2, 0))))


def _onto_reference(t, half: int):
    """``_level_reference``'s transpose: [rows, d] with each block's sum
    of ``t`` in the reference's row and nought elsewhere."""
    from jax.experimental.pallas import tpu as pltpu

    n = t.shape[0]
    if half >= 4:
        blocks = _by_block(t, half)
        sums = jnp.broadcast_to(jnp.sum(blocks, axis=1, keepdims=True),
                                blocks.shape).reshape(t.shape)
    else:
        # a block's sum reaches its first row, then moves to the reference
        sums = t + pltpu.roll(t, n - 1, 0)
        if half == 2:
            sums = pltpu.roll(sums + pltpu.roll(sums, n - 2, 0), 1, 0)
    return jnp.where(_in_block(n, 2 * half) == half - 1, sums, 0.0)


def _group_operands(heads, tri_ref, kp_ref, chunk: int):
    """What the walk reads of one group of positions, for each of
    ``heads``, a list of (q, k, v [128, d] in the products' type, g
    [128, d] float32, beta [1, 128]): ``_chunk_operands`` for the group's
    chunks at once, made in VMEM. The heads share nothing, and a head's
    inverse is a chain of products each of which waits for the one
    before, so every stage is written for all the heads before the next:
    the matrix unit works one head's product while another's drains. A
    dict a head; ``factors`` is each level's (left, right, the rows' and
    the columns' factor, gs less the level's reference), which the
    backward's pull-back reads again."""
    dtype = heads[0][0].dtype
    d = heads[0][0].shape[1]
    levels = _levels(chunk)
    eye, block = kp_ref[levels], kp_ref[levels + 1]
    channels = _channels_eye(eye, d)
    out = []
    for q, k, v, g, beta in heads:
        qf, kf = q.astype(jnp.float32), k.astype(jnp.float32)
        gs = _whole(_dot(tri_ref[...], _thirds(g), _NN))
        both = jnp.zeros((2 * _GROUP, _GROUP), jnp.float32)
        factors = []
        for level in range(levels):
            ahead = gs - _level_reference(gs, 1 << level)
            # the pairs the level keeps never meet the clamp; the others'
            # factors stay finite, for the products and their gradients
            rows = jnp.exp(jnp.minimum(ahead, 0.0))
            cols = jnp.exp(jnp.minimum(-ahead, 0.0))
            # q's rows above k's: the two products share the keys' factor
            left = jnp.concatenate([qf * rows, kf * rows],
                                   axis=0).astype(dtype)
            right = (kf * cols).astype(dtype)
            both = both + _dot(left, right, _NT) * _twice(kp_ref[level])
            factors.append((left, right, rows, cols, ahead))
        # a position reads what it writes itself: no decay between the two
        qk = both[:_GROUP] + eye * jnp.sum(qf * kf, axis=1, keepdims=True)
        kk = both[_GROUP:]
        beta_col = jnp.sum(eye * beta, axis=1, keepdims=True)
        out.append(dict(qf=qf, kf=kf, v=v, gs=gs, beta=beta, factors=factors,
                        beta_col=beta_col, kk=kk, a=beta_col * kk,
                        qk=qk.astype(dtype)))
    # ``_inverse_by_halves``, folded: the first level's halves are single
    # positions, whose inverses are ones
    inv = [_fold(eye, chunk) - _fold(o["a"] * kp_ref[0], chunk) for o in out]
    for level in range(1, levels):
        held = [_dot_exact(t, o["a"] * kp_ref[level], _NN)
                for t, o in zip(inv, out)]
        inv = [t - _dot_exact(h, _spread(t, block), _NN)
               for t, h in zip(inv, held)]
    for o, t in zip(out, inv):
        qf, kf, gs = o["qf"], o["kf"], o["gs"]
        grown = jnp.exp(gs)
        shrink = jnp.exp(jnp.concatenate(
            [jnp.broadcast_to(end, (chunk, d)) for end in _ends(gs, chunk)],
            axis=0) - gs)
        k_grown = (kf * grown).astype(dtype)
        solve = _spread(t * o["beta"], block).astype(dtype)
        utw = _dot(solve, jnp.concatenate([o["v"], k_grown], axis=1), _NN)
        last = _ends(grown, chunk)
        # ``decay``: what a chunk's end leaves of the state, a column (the
        # state's rows are the key's channels)
        o.update(
            inv=t, solve=solve, grown=grown, shrink=shrink, k_grown=k_grown,
            ut=utw[:, :d], w=utw[:, d:].astype(dtype),
            qg=(qf * grown).astype(dtype), kg=(kf * shrink).astype(dtype),
            last=last, decay=[_upright(end, channels) for end in last])
    return out


def _channels_eye(eye, d: int):
    """The [d, d] diagonal of noughts and ones: the masks' own at a head
    of 128."""
    if d == eye.shape[0]:
        return eye
    return (lax.broadcasted_iota(jnp.int32, (d, d), 0)
            == lax.broadcasted_iota(jnp.int32, (d, d), 1)
            ).astype(jnp.float32)


def _upright(t, eye):
    """[1, d] -> [d, 1] and back: a row of channels as a column."""
    return jnp.sum(eye * t, axis=1 - t.shape.index(1), keepdims=True)


def _group_rows(group):
    from jax.experimental import pallas as pl

    return pl.ds(pl.multiple_of(group * _GROUP, _GROUP), _GROUP)


def _heads_of(refs, rows, betas, d: int):
    """A grid step's heads as ``_group_operands`` takes them: head i the
    lanes from i * d of the blocks ``refs`` (q, k, v, g), ``betas`` a
    head's [1, 128] each."""
    return [tuple(ref[0, rows, i * d:(i + 1) * d] for ref in refs) + (beta,)
            for i, beta in enumerate(betas)]


def _chunk_fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, tri_ref, kp_ref,
                      o_ref, *rest, chunk: int, keep: bool):
    """``WALK_CHUNKS`` chunks of one batch row and ``STEP_HEADS`` heads, a
    group of positions at a time: the group's operands, then its chunks'
    three lines with the state, which ``state_scr`` [heads, d, d] float32
    carries from a row's first chunk to its last; with
    ``keep`` the state each chunk entered with."""
    from jax.experimental import pallas as pl

    states_ref, state_scr, wrote_scr = rest if keep else (None,) + rest
    dtype = q_ref.dtype
    many, d = state_scr.shape[:2]
    groups = q_ref.shape[1] // _GROUP
    per_group = _GROUP // chunk
    first = pl.program_id(2) * groups

    @pl.when(pl.program_id(2) == 0)
    def _start():
        state_scr[...] = jnp.zeros_like(state_scr)

    def group(at, _):
        rows = _group_rows(at)
        ops = _group_operands(_heads_of(
            (q_ref, k_ref, v_ref, g_ref), rows,
            [beta_ref[0, i, pl.ds(first + at, 1), :] for i in range(many)],
            d), tri_ref, kp_ref, chunk)
        # a chunk's scores meet what the group's chunks wrote: the rows of
        # the chunks to come are multiplied by noughts
        wrote_scr[...] = jnp.zeros_like(wrote_scr)
        out = [[] for _ in ops]
        for at_chunk in range(per_group):
            mine = slice(at_chunk * chunk, (at_chunk + 1) * chunk)
            for i, o in enumerate(ops):
                state = state_scr[i]
                if keep:
                    states_ref[0, i, at * per_group + at_chunk] = state
                held = state.astype(dtype)
                u = o["ut"][mine] - _dot(o["w"][mine], held, _NN)
                wrote = u.astype(dtype)
                wrote_scr[i, mine, :] = wrote
                out[i].append(_dot(o["qg"][mine], held, _NN)
                              + _dot(o["qk"][mine], wrote_scr[i], _NN))
                state_scr[i] = state * o["decay"][at_chunk] + _dot(
                    o["kg"][mine], wrote, _TN)
        for i, mine in enumerate(out):
            o_ref[0, rows, i * d:(i + 1) * d] = jnp.concatenate(
                mine, axis=0).astype(o_ref.dtype)
        return 0

    lax.fori_loop(0, groups, group, 0)


def _chunk_bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, tri_ref, kp_ref,
                      states_ref, do_ref, dq_ref, dk_ref, dv_ref, dg_ref,
                      dbeta_ref, dstate_scr, *, chunk: int):
    """The same chunks from the last to the first, a group at a time: the
    group's operands rebuilt, its chunks walked back with the cotangent of
    the state behind a chunk in ``dstate_scr``, and the walk's cotangents
    pulled back through T's two products, the inverse, the diagonal, the
    levels (each level's factors are the rebuilt ones) and the running
    sum; every stage for all the step's heads before the next
    (``_group_operands``)."""
    from jax.experimental import pallas as pl

    dtype = q_ref.dtype
    many, d = dstate_scr.shape[:2]
    groups = q_ref.shape[1] // _GROUP
    levels = _levels(chunk)
    per_group = _GROUP // chunk
    first = (pl.num_programs(2) - 1 - pl.program_id(2)) * groups

    @pl.when(pl.program_id(2) == 0)
    def _start():
        dstate_scr[...] = jnp.zeros_like(dstate_scr)

    def group(step, _):
        at = groups - 1 - step
        rows = _group_rows(at)
        row = first + at
        ops = _group_operands(_heads_of(
            (q_ref, k_ref, v_ref, g_ref), rows,
            [beta_ref[0, i, pl.ds(row, 1), :] for i in range(many)], d),
            tri_ref, kp_ref, chunk)
        eye, block = kp_ref[levels], kp_ref[levels + 1]
        channels = _channels_eye(eye, d)
        # ---- the walk, backwards: what the scores' transpose gives a
        # chunk's writes needs no state
        for i, o in enumerate(ops):
            o["do"] = do_ref[0, rows, i * d:(i + 1) * d]
            o["du_scores"] = _dot(o["qk"], o["do"], _TN)
            o["walked"] = [[] for _ in range(6)]
        for at_chunk in reversed(range(per_group)):
            mine = slice(at_chunk * chunk, (at_chunk + 1) * chunk)
            for i, o in enumerate(ops):
                state = states_ref[0, i, at * per_group + at_chunk]
                d_after = dstate_scr[i]
                held, d_held = state.astype(dtype), d_after.astype(dtype)
                w, qg, kg = o["w"][mine], o["qg"][mine], o["kg"][mine]
                do = o["do"][mine]
                wrote = (o["ut"][mine] - _dot(w, held, _NN)).astype(dtype)
                d_wrote = (o["du_scores"][mine]
                           + _dot(kg, d_held, _NN)).astype(dtype)
                for kept, piece in zip(o["walked"], (
                        d_wrote, (-_dot(d_wrote, held, _NT)).astype(dtype),
                        _dot(do, held, _NT), _dot(wrote, d_held, _NT), wrote,
                        _upright(jnp.sum(d_after * state, axis=1,
                                         keepdims=True), channels))):
                    kept.append(piece)
                dstate_scr[i] = (_dot(qg, do, _TN)
                                 + d_after * o["decay"][at_chunk]
                                 - _dot(w, d_wrote, _TN))
        # ---- T's two products, up to the inverse
        for o in ops:
            *whole, dlast = (t[::-1] for t in o.pop("walked"))
            du, dw, dqg, dkg, wrote = (
                jnp.concatenate(t, axis=0) for t in whole)
            dutw = jnp.concatenate([du, dw], axis=1)
            d_solve = _fold(_dot(dutw, jnp.concatenate(
                [o["v"], o["k_grown"]], axis=1), _NT) * block, chunk)
            o.update(dqg=dqg, dkg=dkg, wrote=wrote, dlast=dlast,
                     dvk=_dot(o["solve"], dutw, _TN), d_solve=d_solve,
                     turned=_spread(o["inv"], block))
        # ---- the inverse's rule, -T^T dT T^T: two products a head
        held = [_dot_exact(o["turned"], _spread(o["d_solve"] * o["beta"],
                                                block), _TN) for o in ops]
        da = [-_dot_exact(h, o["turned"], _NT) for h, o in zip(held, ops)]
        for i, (o, da) in enumerate(zip(ops, da)):
            qf, kf, grown, shrink = (o[name] for name in (
                "qf", "kf", "grown", "shrink"))
            dqg, dkg = o["dqg"], o["dkg"]
            d_k_grown = o["dvk"][:, d:]
            dbeta_ref[0, i, pl.ds(row, 1), :] = jnp.sum(
                o["d_solve"] * o["inv"], axis=0, keepdims=True) + jnp.sum(
                eye * jnp.sum(da * o["kk"], axis=1, keepdims=True), axis=0,
                keepdims=True)
            # ---- the scores: the diagonal, then the levels
            dqk = _dot(o["do"], o["wrote"], _NT)
            d_diag = jnp.sum(eye * dqk, axis=1, keepdims=True)
            d_both = jnp.concatenate([dqk, da * o["beta_col"]], axis=0)
            dq = d_diag * kf + dqg * grown
            dk = d_diag * qf + d_k_grown * grown + dkg * shrink
            behind = dkg * kf * shrink
            dgs = (dqg * qf + d_k_grown * kf) * grown - behind
            for level, (left, right, rows_, cols, ahead) in enumerate(
                    o["factors"]):
                d_kept = (d_both * _twice(kp_ref[level])).astype(dtype)
                d_left = _dot(d_kept, right, _NN)
                d_right = _dot(d_kept, left, _TN)
                d_rows_q, d_rows_k = d_left[:_GROUP], d_left[_GROUP:]
                dq = dq + d_rows_q * rows_
                dk = dk + d_rows_k * rows_ + d_right * cols
                d_ahead = (jnp.where(ahead < 0.0, (d_rows_q * qf
                                                   + d_rows_k * kf) * rows_,
                                     0.0)
                           - jnp.where(ahead > 0.0, d_right * kf * cols, 0.0))
                # the reference takes the opposite, summed over its block
                dgs = dgs + d_ahead - _onto_reference(d_ahead, 1 << level)
            # ---- a chunk's last running sum: exp(G_C), and G_C - G
            dgs = dgs + jnp.where(
                _in_block(_GROUP, chunk) == chunk - 1,
                jnp.concatenate([jnp.broadcast_to(
                    jnp.sum(behind[c * chunk:(c + 1) * chunk], axis=0,
                            keepdims=True) + o["dlast"][c] * o["last"][c],
                    (chunk, d)) for c in range(per_group)], axis=0), 0.0)
            lanes = slice(i * d, (i + 1) * d)
            dq_ref[0, rows, lanes] = dq.astype(dq_ref.dtype)
            dk_ref[0, rows, lanes] = dk.astype(dk_ref.dtype)
            dv_ref[0, rows, lanes] = o["dvk"][:, :d].astype(dv_ref.dtype)
            # the running sum's transpose
            dg_ref[0, rows, lanes] = _whole(_dot(tri_ref[...], _thirds(dgs),
                                                 _TN))
        return 0

    lax.fori_loop(0, groups, group, 0)


def _lanes(t):
    """[B, S, h, d] -> [B, S, h * d]: a rename, head h the block of d
    lanes at offset h * d (``attention._layout_of``'s lanes)."""
    return t.reshape(t.shape[0], t.shape[1], -1)


def _by_group(beta):
    """[B, S, h] -> [B, h, S / 128, 128]: a group's positions on the
    lanes. The one array turned around the kernels, 4 bytes a position
    and head."""
    b, s, h = beta.shape
    return jnp.moveaxis(beta, 2, 1).reshape(b, h, s // _GROUP, _GROUP)


def _step_heads(heads: int) -> int:
    """The heads a grid step takes, at most ``STEP_HEADS``."""
    return max(n for n in range(1, STEP_HEADS + 1) if heads % n == 0)


def _chunk_specs(shape, chunk: int, block_of):
    """For arrays like q ``shape`` [B, S, h, d]: (the heads of a grid
    step, the BlockSpec of q, k, v, g and o [B, S, h * d], of beta
    [B, h, S / 128, 128], of the states [B, h, S / chunk, d, d], of the
    two arrays of masks); ``block_of`` turns the grid's third index into
    the block of chunks."""
    from jax.experimental import pallas as pl

    _, s, h, d = shape
    many = _step_heads(h)

    def whole(t):
        return pl.BlockSpec(t.shape, lambda b, h, j: (0,) * t.ndim)

    return (many,
            pl.BlockSpec((1, WALK_CHUNKS * chunk, many * d),
                         lambda b, h, j: (b, block_of(j), h)),
            pl.BlockSpec((1, many, s // _GROUP, _GROUP),
                         lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, many, WALK_CHUNKS, d, d),
                         lambda b, h, j: (b, h, block_of(j), 0, 0)),
            *(whole(t) for t in _group_masks(chunk)))


def _chunk_pallas(kernel, name: str, shape, chunk: int, scratch, **specs):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, h, _ = shape
    with kernel_trace(name):
        return pl.pallas_call(
            kernel,
            grid=(b, h // _step_heads(h), s // chunk // WALK_CHUNKS),
            scratch_shapes=[pltpu.VMEM(*t) for t in scratch],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
                vmem_limit_bytes=_VMEM_BYTES),
            interpret=attention.kernels_interpreted(), name=name, **specs)


# the backward holds a group's operands and six levels' factors across its
# walk, a head's beside another's, and the blocks of fifteen arrays: more
# than Mosaic's default
_VMEM_BYTES = 64 * 2 ** 20


# jitted so that a step's delta-rule layers and their groups of heads
# share one trace and one lowering of each kernel
@functools.partial(jax.jit, static_argnames=("chunk", "keep"))
def _chunk_call(q, k, v, g, beta, chunk: int, keep: bool):
    """The forward kernel over q, k, v, g [B, S, h, d] and beta [B, S, h]
    -> (o [B, S, h, d] like q, with ``keep`` the state each chunk entered
    with [B, h, S / chunk, d, d] float32, else None)."""
    b, s, h, d = q.shape
    vma = jax.typeof(q).vma
    many, rows, small, kept, tri, kp = _chunk_specs(q.shape, chunk,
                                                    lambda j: j)
    out_shape = [jax.ShapeDtypeStruct((b, s, h * d), q.dtype, vma=vma)]
    if keep:
        out_shape.append(jax.ShapeDtypeStruct(
            (b, h, s // chunk, d, d), jnp.float32, vma=vma))
    out = _chunk_pallas(
        functools.partial(_chunk_fwd_kernel, chunk=chunk, keep=keep),
        "kda_chunk_fwd", q.shape, chunk,
        [((many, d, d), jnp.float32), ((many, _GROUP, d), q.dtype)],
        in_specs=[rows] * 4 + [small, tri, kp],
        out_specs=[rows, kept][:len(out_shape)], out_shape=out_shape,
    )(_lanes(q), _lanes(k), _lanes(v), _lanes(g), _by_group(beta),
      *_group_masks(chunk))
    return out[0].reshape(q.shape), (out[1] if keep else None)


@functools.partial(jax.jit, static_argnames=("chunk",))
def _chunk_grad_call(q, k, v, g, beta, states, do, chunk: int):
    """The backward kernel -> the cotangents of q, k, v, g and beta."""
    b, s, h, d = q.shape
    last = s // chunk // WALK_CHUNKS - 1
    vma = jax.typeof(q).vma
    many, rows, small, kept, tri, kp = _chunk_specs(q.shape, chunk,
                                                    lambda j: last - j)
    *wide, d_beta = _chunk_pallas(
        functools.partial(_chunk_bwd_kernel, chunk=chunk), "kda_chunk_bwd",
        q.shape, chunk, [((many, d, d), jnp.float32)],
        in_specs=[rows] * 4 + [small, tri, kp, kept, rows],
        out_specs=[rows] * 4 + [small],
        out_shape=[jax.ShapeDtypeStruct((b, s, h * d), t.dtype, vma=vma)
                   for t in (q, k, v, g)] + [jax.ShapeDtypeStruct(
                       (b, h, s // _GROUP, _GROUP), jnp.float32, vma=vma)],
    )(_lanes(q), _lanes(k), _lanes(v), _lanes(g), _by_group(beta),
      *_group_masks(chunk), states, _lanes(do))
    return tuple(t.reshape(q.shape) for t in wide) + (
        jnp.moveaxis(d_beta.reshape(b, h, s), 1, 2),)


def _by_chunk(t, chunk: int):
    """[B, S, h, ...] -> [B, h, S / chunk, chunk, ...]."""
    b, s, h = t.shape[:3]
    return jnp.moveaxis(t.reshape(b, s // chunk, chunk, h, *t.shape[3:]),
                        3, 1)


def _by_position(t):
    """[B, h, n, C, ...] -> [B, n * C, h, ...]."""
    t = jnp.moveaxis(t, 1, 3)
    return t.reshape(t.shape[0], -1, *t.shape[3:])


def _count(like, chunk: int, kernel: bool, which: str) -> None:
    kda_chunks.inc(like.shape[1] // chunk,
                   {"tier": "kernel" if kernel else "jnp", "pass": which})


def _operands(q, k, v, g, beta, chunk: int):
    return _chunk_operands(*(_by_chunk(t, chunk) for t in (q, k, v, g, beta)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _rule(q, k, v, g, beta, chunk: int, kernel: bool):
    _count(q, chunk, kernel, "fwd")
    if kernel:
        return _chunk_call(q, k, v, g, beta, chunk, False)[0]
    operands = _operands(q, k, v, g, beta, chunk)
    return _by_position(_states_fwd(operands, q.dtype)[0]).astype(q.dtype)


def _rule_fwd(q, k, v, g, beta, chunk: int, kernel: bool):
    _count(q, chunk, kernel, "fwd")
    if kernel:
        o, states = _chunk_call(q, k, v, g, beta, chunk, True)
    else:
        o, states = _states_fwd(_operands(q, k, v, g, beta, chunk), q.dtype)
        o = _by_position(o).astype(q.dtype)
    return o, (q, k, v, g, beta, states)


def _rule_bwd(chunk: int, kernel: bool, kept, do):
    q, k, v, g, beta, states = kept
    _count(q, chunk, kernel, "bwd")
    if kernel:
        return _chunk_grad_call(q, k, v, g, beta, states, do, chunk)
    operands, pull = jax.vjp(
        _chunk_operands, *(_by_chunk(t, chunk) for t in (q, k, v, g, beta)))
    cotangents = _states_bwd(operands, states, _by_chunk(do, chunk), q.dtype)
    pulled = pull(tuple(c.astype(o.dtype)
                        for c, o in zip(cotangents, operands)))
    return tuple(_by_position(t) for t in pulled)


_rule.defvjp(_rule_fwd, _rule_bwd)


def gated_delta_rule(q, k, v, g, beta, chunk: int = 64,
                     sharded: bool = False):
    """q, k, v [B, S, H, d] (q and k normalised a head, q scaled), g
    [B, S, H, d] float32 (the log of the decay, at most nought), beta
    [B, S, H] float32 -> o like q. ``chunk`` is a power of two that
    divides S; the state starts at nought. ``sharded``: the step is
    partitioned over a mesh (``walk_tier``)."""
    s, d = q.shape[1], q.shape[3]
    if s % chunk or chunk & (chunk - 1):
        raise ValueError(f"gated_delta_rule: chunk {chunk} has to be a "
                         f"power of two that divides the sequence {s}")
    return _rule(q, k, v, g.astype(jnp.float32), beta.astype(jnp.float32),
                 chunk, walk_tier(chunk, d, s // chunk, sharded))
