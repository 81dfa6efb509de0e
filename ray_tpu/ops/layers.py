"""Elementwise / normalization building blocks.

Pure jnp: XLA fuses these into surrounding matmuls on TPU (HBM-bandwidth
friendly), so no hand kernel is needed; the hot op with real tiling needs
is attention (ops/attention.py).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * scale * weight.astype(jnp.float32)).astype(dtype)


def rope_frequencies(head_dim: int, max_seq: int, theta: float = 10000.0):
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                           / head_dim))
    t = jnp.arange(max_seq, dtype=jnp.float32)
    freqs = jnp.outer(t, inv)  # [S, D/2]
    return jnp.cos(freqs), jnp.sin(freqs)


def yarn_frequencies(head_dim: int, max_seq: int, theta: float,
                     factor: float, original_max_seq: int,
                     beta_fast: float = 32.0, beta_slow: float = 1.0,
                     attention_factor: float = 1.0):
    """``rope_frequencies`` stretched by YaRN (Peng et al. 2023, arXiv
    2309.00071) for a model trained to ``original_max_seq`` positions and
    read to ``factor`` times that: pair i turns ``theta**(-2i/d)`` a
    position; the pairs that make under ``beta_slow`` turns over the
    original length turn ``factor`` times slower (interpolated), those
    over ``beta_fast`` turns are left as they are, a linear ramp over the
    pair's index between the two (its ends rounded down and up, the
    transformers default); cos and sin both carry ``attention_factor``,
    so the logits do squared."""
    half = head_dim // 2
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                           / head_dim))

    def pair_of(turns: float) -> float:
        """The pair that makes ``turns`` turns over the original length."""
        return (head_dim * math.log(original_max_seq / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = min(max(math.floor(pair_of(beta_fast)), 0), half - 1)
    high = min(max(math.ceil(pair_of(beta_slow)), 0), half - 1)
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    inv = inv * (1.0 - ramp) + inv / factor * ramp
    freqs = jnp.outer(jnp.arange(max_seq, dtype=jnp.float32), inv)
    return (jnp.cos(freqs) * attention_factor,
            jnp.sin(freqs) * attention_factor)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array,
               position_offset: int | jax.Array = 0) -> jax.Array:
    """x: [B, S, H, D]; cos/sin: [max_seq, D/2] (sliced by position)."""
    s = x.shape[1]
    if isinstance(position_offset, int) and position_offset == 0:
        c = cos[:s]
        sn = sin[:s]
    else:
        c = jax.lax.dynamic_slice_in_dim(cos, position_offset, s, axis=0)
        sn = jax.lax.dynamic_slice_in_dim(sin, position_offset, s, axis=0)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    c = c[None, :, None, :]
    sn = sn[None, :, None, :]
    out = jnp.concatenate([x1 * c - x2 * sn, x1 * sn + x2 * c], axis=-1)
    return out.astype(x.dtype)


def swiglu(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
           w_down: jax.Array) -> jax.Array:
    """SwiGLU MLP: silu(x@w_gate) * (x@w_up) @ w_down."""
    gate = jax.nn.silu(jnp.einsum("...h,hm->...m", x, w_gate))
    up = jnp.einsum("...h,hm->...m", x, w_up)
    return jnp.einsum("...m,mh->...h", gate * up, w_down)
