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

Everything but the three lines with S is a chunk's own and is computed
for all the chunks at once (``_chunk_operands``); the three lines run
chunk after chunk (``_states_fwd``). exp(G_i - G_j) as a product of one
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
chunks from the last to the first with the state's cotangent
(``_states_bwd``) and pulls the operands' cotangents back through
``_chunk_operands``, a level of blocks at a time (each level's two
factors are rebuilt for its backward, so that no more than one level's
live at once). What is per position and channel in float32 (the running
sums, a level's factors) is as large as g itself: a caller with many
heads hands the op a group of them at a time (the model's ``kda_block``
does, ``HEADS_AT_ONCE``).

Two tiers behind the one op, as ops/ssd.py has them for its scan;
``walk_tier`` says which a call takes, from the platform
(``attention.kernels_on``), the shapes and whether the step is
partitioned over a mesh, and nothing a user sets moves it:

  -> two Pallas kernel pairs. ``kda_scores_fwd`` / ``kda_scores_bwd``:
     a chunk's two decayed score matrices A and B and their pull-back,
     all six levels of blocks with a chunk's q, k and running sums in
     VMEM (as XLA's, each level writes and reads its two factors in
     float32 a position and channel: 15 GB a layer and pass at 64 heads
     of 128 over 8192 positions, most of what the op cost on the chip,
     PR 39). ``kda_walk_fwd`` / ``kda_walk_bwd``: the chunks' walk and
     its transpose. The grid runs over (batch row, head, block of
     ``WALK_CHUNKS`` chunks) with the chunks innermost and sequential; a
     head's state (forward) or its cotangent (backward)
     lives in a VMEM scratch [d, d] float32 from a row's first chunk to
     its last, transposed, so that the key's channels, which the decay
     scales, are its lanes. A chunk is five products forward and ten
     backward, each [64, 128] by [128, 128] or the like. The rest of a
     chunk's operands (the running sums, the inverse, T's two products)
     and their pull-back stay XLA's (``_chunk_operands``).
  -> plain jnp: a ``lax.scan`` over the chunks, whose state goes through
     HBM once a chunk. The path off the TPU, of shapes off the kernels'
     tiles, of a step partitioned over a mesh, and the kernels' oracle.

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


def _chunk_operands(q, k, v, g, beta, kernel: bool = False):
    """What the chunks' walk reads, for q, k, v, g [B, h, n, C, d] and
    beta [B, h, n, C]: (U~ [.., C, d], W [.., C, d], q * exp(G), k *
    exp(G_C - G), B [.., C, C], exp(G_C) [.., d]), float32 but for what is
    a product's operand alone. ``kernel``: the decayed products by their
    kernel pair (``_scores``)."""
    dtype = q.dtype
    c = q.shape[-2]
    gs = jnp.cumsum(g, axis=-2)
    qf, kf = q.astype(jnp.float32), k.astype(jnp.float32)
    qk, kk = (_scores(q, k, gs) if kernel
              else _decayed_products(qf, kf, gs, dtype))
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
# The walk's kernel tier. The state is kept transposed, [d of v, d of k]:
# the decay scales the key's channels, which are then the lanes of the
# state and of ``last`` alike, and nothing is turned.
# ===========================================================================


def walk_tier(chunk: int, head_dim: int, chunks: int,
              sharded: bool = False) -> bool:
    """Whether a walk of these shapes takes the kernels: the one rule
    behind ``gated_delta_rule``, of ``ssd.scan_tier``'s form. Where
    kernels run at all (``attention.kernels_on``), the step is not
    partitioned over a mesh (``sharded``), a head is whole tiles of 128
    lanes, a chunk whole tiles of a 16-bit type's 16 rows, and the chunks
    come in whole grid steps."""
    return (attention.kernels_on() and not sharded and head_dim % 128 == 0
            and chunk % 16 == 0 and chunks % WALK_CHUNKS == 0)


def _walk_fwd_kernel(ut_ref, w_ref, qg_ref, kg_ref, qk_ref, last_ref, o_ref,
                     *rest, steps: int, keep: bool):
    """``steps`` chunks of one (batch row, head): o, the state carried in
    ``state_scr`` [d, d] float32 (transposed), and with ``keep`` the
    state each chunk entered with."""
    from jax.experimental import pallas as pl

    states_ref, state_scr = rest if keep else (None,) + rest
    dtype = w_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _start():
        state_scr[...] = jnp.zeros_like(state_scr)

    for i in range(steps):
        state = state_scr[...]
        if keep:
            states_ref[0, 0, i] = state
        held = state.astype(dtype)
        u = ut_ref[0, 0, i] - _dot(w_ref[0, 0, i], held, _NT)
        wrote = u.astype(dtype)
        o = _dot(qg_ref[0, 0, i], held, _NT) + _dot(qk_ref[0, 0, i], wrote,
                                                    _NN)
        o_ref[0, 0, i] = o.astype(o_ref.dtype)
        state_scr[...] = state * last_ref[0, 0, pl.ds(i, 1), :] + _dot(
            wrote, kg_ref[0, 0, i], _TN)


def _walk_bwd_kernel(ut_ref, w_ref, qg_ref, kg_ref, qk_ref, last_ref,
                     states_ref, do_ref, dut_ref, dw_ref, dqg_ref, dkg_ref,
                     dqk_ref, dlast_ref, dstate_scr, *, steps: int):
    """The same chunks from the last to the first: the cotangents of a
    chunk's operands, the cotangent of the state behind the chunk carried
    in ``dstate_scr`` (transposed, like the states kept)."""
    from jax.experimental import pallas as pl

    dtype = w_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _start():
        dstate_scr[...] = jnp.zeros_like(dstate_scr)

    for i in reversed(range(steps)):
        state, d_after = states_ref[0, 0, i], dstate_scr[...]
        held, d_held = state.astype(dtype), d_after.astype(dtype)
        w, qg, kg, qk = (ref[0, 0, i]
                         for ref in (w_ref, qg_ref, kg_ref, qk_ref))
        do = do_ref[0, 0, i]
        last = last_ref[0, 0, pl.ds(i, 1), :]
        wrote = (ut_ref[0, 0, i] - _dot(w, held, _NT)).astype(dtype)
        du = _dot(qk, do, _TN) + _dot(kg, d_held, _NT)
        d_wrote = du.astype(dtype)
        dut_ref[0, 0, i] = du
        dw_ref[0, 0, i] = (-_dot(d_wrote, held, _NN)).astype(dtype)
        dqg_ref[0, 0, i] = _dot(do, held, _NN).astype(dtype)
        dkg_ref[0, 0, i] = _dot(wrote, d_held, _NN).astype(dtype)
        dqk_ref[0, 0, i] = _dot(do, wrote, _NT).astype(dtype)
        dlast_ref[0, 0, pl.ds(i, 1), :] = jnp.sum(d_after * state, axis=0,
                                                  keepdims=True)
        dstate_scr[...] = (_dot(do, qg, _TN) + d_after * last
                           - _dot(d_wrote, w, _TN))


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


def _level_masks(c: int, half: int):
    """Of level ``half`` for a chunk of ``c`` positions: (sel [c, c] bfloat16
    with a one at (i, the last position of the first half of i's block),
    keep [2c, c] bool: the pairs (i in a block's second half, j in its
    first), once for q's rows and once for k's)."""
    i = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    j = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    sel = (j == i // (2 * half) * 2 * half + half - 1).astype(jnp.bfloat16)
    keep = _quarters(c, half)
    return sel, jnp.concatenate([keep, keep], axis=0)


def _level_factors(q, k, gs, gs_thirds, sel):
    """(q's and k's rows times the rows' factor, k times the columns'
    factor, the two factors, gs less the level's reference), float32."""
    ahead = gs - _whole(_dot(sel, gs_thirds, _NN))
    rows = jnp.exp(jnp.minimum(ahead, 0.0))
    cols = jnp.exp(jnp.minimum(-ahead, 0.0))
    return (jnp.concatenate([q * rows, k * rows], axis=0), k * cols, rows,
            cols, ahead)


def _scores_fwd_kernel(q_ref, k_ref, gs_ref, qk_ref, kk_ref, *, steps: int):
    """``steps`` chunks of one (batch row, head): the two decayed score
    matrices, a level of blocks at a time (``_decayed_products``)."""
    dtype = q_ref.dtype
    c = q_ref.shape[-2]

    def chunk(i, _):
        q = q_ref[0, 0, i].astype(jnp.float32)
        k = k_ref[0, 0, i].astype(jnp.float32)
        gs = gs_ref[0, 0, i]
        gs_thirds = _thirds(gs)
        out = jnp.zeros((2 * c, c), jnp.float32)
        half = 1
        while half < c:
            sel, keep = _level_masks(c, half)
            left, right, _, _, _ = _level_factors(q, k, gs, gs_thirds, sel)
            out = out + jnp.where(keep, _dot(
                left.astype(dtype), right.astype(dtype), _NT), 0.0)
            half *= 2
        qk_ref[0, 0, i] = out[:c]
        kk_ref[0, 0, i] = out[c:]
        return 0

    lax.fori_loop(0, steps, chunk, 0)


def _scores_bwd_kernel(q_ref, k_ref, gs_ref, dqk_ref, dkk_ref, dq_ref,
                       dk_ref, dgs_ref, *, steps: int):
    """The cotangents of q, k and the running sums from those of the two
    score matrices, each level's factors rebuilt."""
    dtype = q_ref.dtype
    c = q_ref.shape[-2]

    def chunk(i, _):
        q = q_ref[0, 0, i].astype(jnp.float32)
        k = k_ref[0, 0, i].astype(jnp.float32)
        gs = gs_ref[0, 0, i]
        d_both = jnp.concatenate([dqk_ref[0, 0, i], dkk_ref[0, 0, i]],
                                 axis=0)
        gs_thirds = _thirds(gs)
        dq, dk, dgs = (jnp.zeros_like(gs) for _ in range(3))
        half = 1
        while half < c:
            sel, keep = _level_masks(c, half)
            left, right, rows, cols, ahead = _level_factors(
                q, k, gs, gs_thirds, sel)
            d_kept = jnp.where(keep, d_both, 0.0).astype(dtype)
            d_left = _dot(d_kept, right.astype(dtype), _NN)     # [2c, d]
            d_right = _dot(d_kept, left.astype(dtype), _TN)     # [c, d]
            dq = dq + d_left[:c] * rows
            dk = dk + d_left[c:] * rows + d_right * cols
            d_ahead = (jnp.where(ahead < 0.0, (d_left[:c] * q
                                               + d_left[c:] * k) * rows, 0.0)
                       - jnp.where(ahead > 0.0, d_right * k * cols, 0.0))
            # the reference takes the opposite, summed over its block
            dgs = dgs + d_ahead - _whole(_dot(sel, _thirds(d_ahead), _TN))
            half *= 2
        dq_ref[0, 0, i] = dq.astype(dq_ref.dtype)
        dk_ref[0, 0, i] = dk.astype(dk_ref.dtype)
        dgs_ref[0, 0, i] = dgs
        return 0

    lax.fori_loop(0, steps, chunk, 0)


def _scores_specs(arrays, steps: int):
    from jax.experimental import pallas as pl

    return [pl.BlockSpec((1, 1, steps) + t.shape[3:],
                         lambda b, h, j: (b, h, j, 0, 0)) for t in arrays]


def _scores_pallas(kernel, name: str, inputs, outputs):
    """One of the two score kernels over ``inputs`` -> arrays like
    ``outputs`` (shapes and types), all [B, h, n, C, .]."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, n = inputs[0].shape[:3]
    steps = WALK_CHUNKS
    vma = jax.typeof(inputs[0]).vma
    with kernel_trace(name):
        return pl.pallas_call(
            functools.partial(kernel, steps=steps),
            grid=(b, h, n // steps),
            in_specs=_scores_specs(inputs, steps),
            out_specs=_scores_specs(outputs, steps),
            out_shape=[jax.ShapeDtypeStruct(t.shape, t.dtype, vma=vma)
                       for t in outputs],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel")),
            interpret=attention.kernels_interpreted(),
            name=name,
        )(*inputs)


@jax.jit
def _scores_call(q, k, gs):
    c = q.shape[-2]
    square = jax.ShapeDtypeStruct(q.shape[:-1] + (c,), jnp.float32)
    return tuple(_scores_pallas(_scores_fwd_kernel, "kda_scores_fwd",
                                (q, k, gs), (square, square)))


@jax.jit
def _scores_grad_call(q, k, gs, dqk, dkk):
    return tuple(_scores_pallas(_scores_bwd_kernel, "kda_scores_bwd",
                                (q, k, gs, dqk, dkk), (q, k, gs)))


@jax.custom_vjp
def _scores(q, k, gs):
    """``_decayed_products`` by its kernel pair: q, k [B, h, n, C, d] in
    the products' type, gs float32 -> (B, A) [B, h, n, C, C] float32."""
    return _scores_call(q, k, gs)


def _scores_fwd(q, k, gs):
    return _scores_call(q, k, gs), (q, k, gs)


def _scores_bwd(kept, cotangents):
    return _scores_grad_call(*kept, *cotangents)


_scores.defvjp(_scores_fwd, _scores_bwd)


def _walk_specs(operands, steps: int, index):
    """The BlockSpecs of the walk's six operands, ``index`` (b, h, j) ->
    the block of chunks."""
    from jax.experimental import pallas as pl

    def spec(t):
        block = (1, 1, steps) + t.shape[3:]
        return pl.BlockSpec(block, lambda b, h, j: (
            b, h, index(j)) + (0,) * (len(block) - 3))

    return [spec(t) for t in operands]


# jitted so that a step's delta-rule layers and their groups of heads
# share one trace and one lowering of each kernel
@functools.partial(jax.jit, static_argnames=("keep", "out_dtype"))
def _walk_call(operands, keep: bool, out_dtype):
    """The forward kernel -> (o [B, h, n, C, d] in ``out_dtype``, with
    ``keep`` the state each chunk entered with [B, h, n, d, d] float32,
    transposed, else None)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    ut = operands[0]
    b, h, n, _, d = ut.shape
    steps = WALK_CHUNKS
    vma = jax.typeof(ut).vma
    specs = _walk_specs(operands, steps, lambda j: j)
    out_shape = [jax.ShapeDtypeStruct(ut.shape, out_dtype, vma=vma)]
    out_specs = [specs[0]]
    if keep:
        out_shape.append(jax.ShapeDtypeStruct((b, h, n, d, d), jnp.float32,
                                              vma=vma))
        out_specs.append(pl.BlockSpec((1, 1, steps, d, d),
                                      lambda b, h, j: (b, h, j, 0, 0)))
    with kernel_trace("kda_walk_fwd"):
        out = pl.pallas_call(
            functools.partial(_walk_fwd_kernel, steps=steps, keep=keep),
            grid=(b, h, n // steps), in_specs=specs, out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=[pltpu.VMEM((d, d), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=attention.kernels_interpreted(),
            name="kda_walk_fwd",
        )(*operands)
    return out[0], (out[1] if keep else None)


@jax.jit
def _walk_grad_call(operands, states, do):
    """The backward kernel -> the cotangents of ``operands``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    ut = operands[0]
    b, h, n, _, d = ut.shape
    steps = WALK_CHUNKS
    last = n // steps - 1
    vma = jax.typeof(ut).vma
    specs = _walk_specs(operands, steps, lambda j: last - j)
    with kernel_trace("kda_walk_bwd"):
        return tuple(pl.pallas_call(
            functools.partial(_walk_bwd_kernel, steps=steps),
            grid=(b, h, n // steps),
            in_specs=specs + [
                pl.BlockSpec((1, 1, steps, d, d),
                             lambda b, h, j: (b, h, last - j, 0, 0)),
                specs[0]],
            out_specs=specs,
            out_shape=[jax.ShapeDtypeStruct(t.shape, t.dtype, vma=vma)
                       for t in operands],
            scratch_shapes=[pltpu.VMEM((d, d), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=attention.kernels_interpreted(),
            name="kda_walk_bwd",
        )(*operands, states, do))


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


def _operands(q, k, v, g, beta, chunk: int, kernel: bool):
    return _chunk_operands(
        *(_by_chunk(t, chunk) for t in (q, k, v, g, beta)), kernel)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _rule(q, k, v, g, beta, chunk: int, kernel: bool):
    _count(q, chunk, kernel, "fwd")
    operands = _operands(q, k, v, g, beta, chunk, kernel)
    if kernel:
        return _by_position(_walk_call(operands, False, q.dtype)[0])
    return _by_position(_states_fwd(operands, q.dtype)[0]).astype(q.dtype)


def _rule_fwd(q, k, v, g, beta, chunk: int, kernel: bool):
    _count(q, chunk, kernel, "fwd")
    operands = _operands(q, k, v, g, beta, chunk, kernel)
    o, states = (_walk_call(operands, True, q.dtype) if kernel
                 else _states_fwd(operands, q.dtype))
    return _by_position(o).astype(q.dtype), (q, k, v, g, beta, states)


def _rule_bwd(chunk: int, kernel: bool, kept, do):
    q, k, v, g, beta, states = kept
    _count(q, chunk, kernel, "bwd")
    operands, pull = jax.vjp(
        functools.partial(_chunk_operands, kernel=kernel),
        *(_by_chunk(t, chunk) for t in (q, k, v, g, beta)))
    do = _by_chunk(do, chunk)
    cotangents = (_walk_grad_call(operands, states, do) if kernel
                  else _states_bwd(operands, states, do, q.dtype))
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
