"""Mamba-2's state-space layer: the chunked scan (SSD), the causal
depthwise convolution in front of it and the gated grouped norm behind.

The recurrence, per head h with its group g = h // (heads / groups):

    S_t = exp(dt_t * A_h) * S_{t-1} + dt_t * x_t (outer) B_t
    y_t = S_t . C_t + D_h * x_t,                      S_0 = 0

is computed a chunk at a time (state-space duality, Dao & Gu 2024):
inside a chunk the quadratic form (C.B^T masked by the decay, times x),
between chunks the carried state [batch, heads, head_dim, state]. The
decays and the carried state are float32; the operands of the products
take the type of ``x`` (bfloat16 in training). A ``lax.scan`` over the
chunks whose body is checkpointed: differentiating it keeps the carried
state at each chunk boundary and the scan's own inputs, nothing per
position, and rebuilds a chunk's decays in its backward. Plain jnp: the
carried state goes through HBM once a chunk, which a kernel that keeps it
in VMEM would not (PERF.md, open questions).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


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


def ssd_scan(x, dt, a, bm, cm, d, chunk: int):
    """x [B,S,H,P], dt [B,S,H] float32 (after its softplus), a [H]
    float32 (negative), bm and cm [B,S,G,N], d [H] float32 -> y like x.
    ``chunk`` has to divide S; the state starts at nought."""
    b, s, h, p = x.shape
    g, n = bm.shape[2], bm.shape[3]
    if s % chunk or h % g:
        raise ValueError(f"ssd_scan: chunk {chunk} has to divide the "
                         f"sequence {s}, and groups {g} the heads {h}")

    def chunks(t):  # [B,S,...] -> [S/chunk, B, chunk, ...]
        return jnp.moveaxis(
            t.reshape(b, s // chunk, chunk, *t.shape[2:]), 1, 0)

    a = a.astype(jnp.float32)
    d = d.astype(jnp.float32)
    body = jax.checkpoint(
        lambda state, inputs: _chunk(state, inputs, a, d, h // g))
    _, y = lax.scan(body, jnp.zeros((b, h, p, n), jnp.float32),
                    (chunks(x), chunks(dt.astype(jnp.float32)), chunks(bm),
                     chunks(cm)))
    return jnp.moveaxis(y, 0, 1).reshape(b, s, h, p)


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


def gated_group_norm(y, z, weight, groups: int, eps: float):
    """RMSNorm over each of ``groups`` slices of the last axis of
    y * silu(z) (the norm comes after the gate), times ``weight``."""
    dtype = y.dtype
    gated = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    parts = gated.reshape(*gated.shape[:-1], groups, -1)
    parts = parts * lax.rsqrt(
        jnp.mean(parts * parts, axis=-1, keepdims=True) + eps)
    return (parts.reshape(gated.shape)
            * weight.astype(jnp.float32)).astype(dtype)
