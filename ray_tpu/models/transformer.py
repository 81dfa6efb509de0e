"""Flagship model family: decoder-only transformer (dense + MoE).

Pure-functional JAX: parameters are a pytree of arrays with a parallel
pytree of *logical axis names* (models/sharding rules in
parallel/mesh.py map those to mesh axes). Layers are stacked along a
leading axis and iterated with ``lax.scan`` so compile time is O(1) in
depth and the pipeline path can shard the same stack over ``pp``.

Architecture: RMSNorm, rotary embeddings, GQA attention via
ops.flash_attention, SwiGLU MLP, optional top-2 MoE layers
(GShard-style capacity-bounded einsum dispatch; experts shard over the
``dp`` mesh axis = expert parallelism).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops.attention import flash_attention
from ray_tpu.ops.layers import apply_rope, rms_norm, rope_frequencies, swiglu


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    vocab_size: int = 32000
    hidden: int = 512
    layers: int = 4
    heads: int = 8
    kv_heads: int = 8
    intermediate: int = 1408
    max_seq: int = 2048
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    # MoE: every `moe_every`-th layer is sparse when num_experts > 0
    num_experts: int = 0
    experts_per_token: int = 2
    moe_every: int = 2
    capacity_factor: float = 1.25
    # grouped dispatch: when >0 and it divides B*S, tokens route in
    # independent groups of this size with per-group capacity, scanned
    # under jax.checkpoint — the GShard [tokens, experts, capacity]
    # dispatch/combine one-hots then scale with the GROUP, not the
    # batch (at B16-S2048-E8 ungrouped they are 5 GiB each and OOM a
    # 16 GB chip; 4096-token groups bound them to ~160 MB). Per-group
    # capacity is the standard GShard/Mixtral local-group semantics.
    moe_group_size: int = 0
    remat: bool = True
    # remat granularity when ``remat`` is on: "full" recomputes the whole
    # block in the backward (lowest memory, ~+1/3 matmul FLOPs); "dots"
    # saves weight-activation matmul outputs and recomputes only the
    # cheap elementwise ops (jax.checkpoint_policies.
    # dots_with_no_batch_dims_saveable — attention logits have batch
    # dims, so the [S, S] matrix is never saved). "dots" trades HBM for
    # FLOPs: use it when the batch that fits is compute-bound anyway.
    remat_policy: str = "full"
    tie_embeddings: bool = True
    # chunked cross-entropy: when >0 and it divides the sequence, the
    # loss projects to vocab one [B, chunk, V] slab at a time under
    # jax.checkpoint, so the fp32 [B, S, V] logits never materialize
    # (the dominant HBM allocation at large batch x vocab)
    logits_chunk: int = 0

    def __post_init__(self):
        # a typo'd policy silently measuring full remat would mislabel
        # an A/B data point (r05 review finding)
        if self.remat_policy not in ("full", "dots"):
            raise ValueError(
                f"remat_policy must be 'full' or 'dots', "
                f"got {self.remat_policy!r}")
        if self.moe_group_size < 0:
            raise ValueError(
                f"moe_group_size must be >= 0, "
                f"got {self.moe_group_size}")

    @property
    def head_dim(self) -> int:
        return self.hidden // self.heads

    @classmethod
    def debug(cls, **kw) -> "ModelConfig":
        return cls(vocab_size=256, hidden=64, layers=2, heads=4, kv_heads=2,
                   intermediate=128, max_seq=128, dtype=jnp.float32, **kw)

    @classmethod
    def tiny_moe(cls, **kw) -> "ModelConfig":
        return cls(vocab_size=256, hidden=64, layers=2, heads=4, kv_heads=4,
                   intermediate=128, max_seq=128, num_experts=4,
                   dtype=jnp.float32, **kw)

    @classmethod
    def b1(cls) -> "ModelConfig":
        """~1.2B dense (llama-ish shape)."""
        return cls(vocab_size=32000, hidden=2048, layers=24, heads=16,
                   kv_heads=16, intermediate=5632, max_seq=4096)

    @classmethod
    def b7(cls) -> "ModelConfig":
        return cls(vocab_size=32000, hidden=4096, layers=32, heads=32,
                   kv_heads=32, intermediate=11008, max_seq=4096)


# -- parameter init + logical axes -----------------------------------------


def init_params(cfg: ModelConfig, key: jax.Array) -> Dict[str, Any]:
    k = jax.random.split(key, 13)
    h, hd, nl = cfg.hidden, cfg.head_dim, cfg.layers
    scale = h ** -0.5
    dt = cfg.dtype

    def norm_init(shape):
        return jnp.ones(shape, dtype=jnp.float32)

    params: Dict[str, Any] = {
        "embed": (jax.random.normal(k[0], (cfg.vocab_size, h)) * 0.02
                  ).astype(dt),
        "final_norm": norm_init((h,)),
        "layers": {
            "attn_norm": norm_init((nl, h)),
            "mlp_norm": norm_init((nl, h)),
            "wq": (jax.random.normal(k[1], (nl, h, cfg.heads * hd))
                   * scale).astype(dt),
            "wk": (jax.random.normal(k[2], (nl, h, cfg.kv_heads * hd))
                   * scale).astype(dt),
            "wv": (jax.random.normal(k[3], (nl, h, cfg.kv_heads * hd))
                   * scale).astype(dt),
            "wo": (jax.random.normal(k[4], (nl, cfg.heads * hd, h))
                   * scale).astype(dt),
        },
    }
    if not cfg.tie_embeddings:
        params["unembed"] = (jax.random.normal(k[5], (h, cfg.vocab_size))
                             * scale).astype(dt)
    dense = {
        "w_gate": (jax.random.normal(k[6], (nl, h, cfg.intermediate))
                   * scale).astype(dt),
        "w_up": (jax.random.normal(k[7], (nl, h, cfg.intermediate))
                 * scale).astype(dt),
        "w_down": (jax.random.normal(k[8], (nl, cfg.intermediate, h))
                   * (cfg.intermediate ** -0.5)).astype(dt),
    }
    params["layers"].update(dense)
    if cfg.num_experts > 0:
        e = cfg.num_experts
        params["layers"]["moe"] = {
            "router": (jax.random.normal(k[9], (nl, h, e)) * scale
                       ).astype(jnp.float32),
            "w_gate": (jax.random.normal(k[10], (nl, e, h, cfg.intermediate))
                       * scale).astype(dt),
            "w_up": (jax.random.normal(k[11], (nl, e, h, cfg.intermediate))
                     * scale).astype(dt),
            "w_down": (jax.random.normal(k[12], (nl, e, cfg.intermediate, h))
                       * (cfg.intermediate ** -0.5)).astype(dt),
        }
    return params


def logical_axes(cfg: ModelConfig) -> Dict[str, Any]:
    """Same-structure pytree of logical axis tuples, consumed by
    parallel.mesh.sharding_for."""
    axes: Dict[str, Any] = {
        "embed": ("vocab", "hidden"),
        "final_norm": ("hidden",),
        "layers": {
            "attn_norm": ("layers", "hidden"),
            "mlp_norm": ("layers", "hidden"),
            "wq": ("layers", "hidden", "heads"),
            "wk": ("layers", "hidden", "kv_heads"),
            "wv": ("layers", "hidden", "kv_heads"),
            "wo": ("layers", "heads", "hidden"),
            "w_gate": ("layers", "hidden", "mlp"),
            "w_up": ("layers", "hidden", "mlp"),
            "w_down": ("layers", "mlp", "hidden"),
        },
    }
    if not cfg.tie_embeddings:
        axes["unembed"] = ("hidden", "vocab")
    if cfg.num_experts > 0:
        axes["layers"]["moe"] = {
            "router": ("layers", "hidden", None),
            "w_gate": ("layers", "experts", "hidden", "mlp"),
            "w_up": ("layers", "experts", "hidden", "mlp"),
            "w_down": ("layers", "experts", "mlp", "hidden"),
        }
    return axes


# -- MoE ---------------------------------------------------------------------


@jax.named_scope("moe")
def moe_layer(x: jax.Array, moe_params: Dict[str, jax.Array],
              cfg: ModelConfig) -> Tuple[jax.Array, jax.Array]:
    """Top-k capacity-bounded MoE (GShard-style einsum dispatch).

    x: [B, S, H] -> ([B, S, H], aux_loss scalar). With
    ``cfg.moe_group_size`` set, tokens route in independent scanned
    groups (see the config field's memory rationale); the aux loss is
    averaged over groups."""
    b, s, h = x.shape
    t = b * s
    g = cfg.moe_group_size
    xt = x.reshape(t, h)
    if g and t > g:
        if t % g == 0:
            n_groups = t // g
            # checkpoint per group: without it, the scan (and the
            # layer remat's backward recompute) stacks every group's
            # [g, E, C] dispatch residuals and reintroduces the
            # ungrouped peak
            group_fn = jax.checkpoint(
                lambda xg: _moe_tokens(xg, moe_params, cfg))

            def body(aux_sum, xg):
                out, aux = group_fn(xg)
                return aux_sum + aux, out

            aux_sum, outs = lax.scan(body, jnp.zeros((), jnp.float32),
                                     xt.reshape(n_groups, g, h))
            return outs.reshape(b, s, h), aux_sum / n_groups
        # same discipline as the logits_chunk fallback: dropping the
        # grouping silently would reintroduce the OOM-scale ungrouped
        # [T, E, capacity] dispatch tensors this feature exists to
        # prevent
        import logging

        logging.getLogger(__name__).warning(
            "moe_group_size=%d does not divide token count %d; "
            "falling back to UNGROUPED routing (dispatch tensors "
            "scale with the full batch — may OOM at large batch)",
            g, t)
    out, aux = _moe_tokens(xt, moe_params, cfg)
    return out.reshape(b, s, h), aux


def _moe_tokens(xt: jax.Array, moe_params: Dict[str, jax.Array],
                cfg: ModelConfig) -> Tuple[jax.Array, jax.Array]:
    """Route one token set [T, H] -> ([T, H], aux)."""
    t, h = xt.shape
    e = cfg.num_experts
    k = cfg.experts_per_token
    cap = max(1, int(cfg.capacity_factor * t * k / e))
    with jax.named_scope("router"):
        logits = jnp.einsum("th,he->te", xt.astype(jnp.float32),
                            moe_params["router"])
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, gate_idx = lax.top_k(probs, k)           # [T, k]
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)
        # load-balancing auxiliary loss (Switch Transformer eq. 4)
        me = probs.mean(axis=0)
        ce = jnp.zeros((e,), jnp.float32).at[gate_idx.reshape(-1)].add(
            1.0 / (t * k))
        aux = e * jnp.sum(me * ce)
    with jax.named_scope("dispatch"):
        # position of each (token, choice) within its expert's capacity
        onehot = jax.nn.one_hot(gate_idx, e, dtype=jnp.float32)  # [T, k, E]
        flat = onehot.reshape(t * k, e)
        pos = (jnp.cumsum(flat, axis=0) - flat).reshape(t, k, e)
        within = (pos * onehot).sum(-1)                     # [T, k]
        keep = within < cap
        gate_vals = gate_vals * keep
        pos_idx = jnp.clip(within, 0, cap - 1).astype(jnp.int32)
        # dispatch tensor [T, E, C]
        dispatch = jnp.einsum(
            "tke,tkc->tec", onehot * keep[..., None],
            jax.nn.one_hot(pos_idx, cap, dtype=jnp.float32))
        combine = jnp.einsum("tke,tkc,tk->tec", onehot,
                             jax.nn.one_hot(pos_idx, cap, dtype=jnp.float32),
                             gate_vals)
        expert_in = jnp.einsum("tec,th->ech", dispatch,
                               xt.astype(jnp.float32)).astype(xt.dtype)
    with jax.named_scope("experts"):
        expert_out = jax.vmap(
            lambda xi, wg, wu, wd: swiglu(xi, wg, wu, wd))(
            expert_in, moe_params["w_gate"], moe_params["w_up"],
            moe_params["w_down"])                           # [E, C, H]
    with jax.named_scope("combine"):
        out = jnp.einsum("tec,ech->th", combine,
                         expert_out.astype(jnp.float32)).astype(xt.dtype)
    return out, aux


# -- transformer block -------------------------------------------------------


def attention_block(x, layer, cfg: ModelConfig, cos, sin,
                    attention_fn: Callable) -> jax.Array:
    b, s, h = x.shape
    hd = cfg.head_dim
    with jax.named_scope("attention"):
        with jax.named_scope("qkv_proj"):
            xn = rms_norm(x, layer["attn_norm"], cfg.norm_eps)
            q = jnp.einsum("bsh,hd->bsd", xn, layer["wq"]).reshape(
                b, s, cfg.heads, hd)
            k = jnp.einsum("bsh,hd->bsd", xn, layer["wk"]).reshape(
                b, s, cfg.kv_heads, hd)
            v = jnp.einsum("bsh,hd->bsd", xn, layer["wv"]).reshape(
                b, s, cfg.kv_heads, hd)
        with jax.named_scope("rope"):
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)
            if cfg.kv_heads != cfg.heads:
                rep = cfg.heads // cfg.kv_heads
                k = jnp.repeat(k, rep, axis=2)
                v = jnp.repeat(v, rep, axis=2)
        with jax.named_scope("flash"):
            attn = attention_fn(q, k, v)
        with jax.named_scope("out_proj"):
            attn = attn.reshape(b, s, cfg.heads * hd)
            return x + jnp.einsum("bsd,dh->bsh", attn, layer["wo"])


def mlp_block(x, layer, layer_idx, cfg: ModelConfig) -> Tuple[jax.Array,
                                                              jax.Array]:
    with jax.named_scope("mlp"):
        xn = rms_norm(x, layer["mlp_norm"], cfg.norm_eps)
        aux = jnp.zeros((), jnp.float32)
        if cfg.num_experts > 0 and "moe" in layer:
            is_moe = (layer_idx % cfg.moe_every) == (cfg.moe_every - 1)
            # lax.cond so only one branch's FLOPs run per layer (jnp.where
            # would execute both the MoE dispatch and the dense SwiGLU)
            out, aux = lax.cond(
                is_moe,
                lambda t: moe_layer(t, layer["moe"], cfg),
                lambda t: (swiglu(t, layer["w_gate"], layer["w_up"],
                                  layer["w_down"]),
                           jnp.zeros((), jnp.float32)),
                xn)
        else:
            out = swiglu(xn, layer["w_gate"], layer["w_up"],
                         layer["w_down"])
        return x + out, aux


def hidden_states(params: Dict[str, Any], tokens: jax.Array,
                  cfg: ModelConfig,
                  attention_fn: Optional[Callable] = None
                  ) -> Tuple[jax.Array, jax.Array]:
    """tokens [B, S] int32 -> (final hidden states [B, S, H], aux)."""
    if attention_fn is None:
        attention_fn = lambda q, k, v: flash_attention(q, k, v, True)  # noqa: E731
    cos, sin = rope_frequencies(cfg.head_dim, cfg.max_seq, cfg.rope_theta)
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], tokens, axis=0)

    def block(carry, scanned):
        x, aux_sum = carry
        layer, idx = scanned
        x = attention_block(x, layer, cfg, cos, sin, attention_fn)
        x, aux = mlp_block(x, layer, idx, cfg)
        return (x, aux_sum + aux), None

    if cfg.remat:
        if cfg.remat_policy == "dots":
            block_fn = jax.checkpoint(
                block,
                policy=jax.checkpoint_policies.
                dots_with_no_batch_dims_saveable)
        else:
            block_fn = jax.checkpoint(block)
    else:
        block_fn = block
    with jax.named_scope("layers"):
        (x, aux), _ = lax.scan(
            block_fn, (x, jnp.zeros((), jnp.float32)),
            (params["layers"], jnp.arange(cfg.layers)))
    with jax.named_scope("final_norm"):
        return rms_norm(x, params["final_norm"], cfg.norm_eps), aux


def _unembed(params, cfg: ModelConfig):
    return (params["embed"].T if cfg.tie_embeddings
            else params["unembed"])


def forward(params: Dict[str, Any], tokens: jax.Array, cfg: ModelConfig,
            attention_fn: Optional[Callable] = None) -> Tuple[jax.Array,
                                                              jax.Array]:
    """tokens [B, S] int32 -> (logits [B, S, V] float32, aux_loss)."""
    x, aux = hidden_states(params, tokens, cfg, attention_fn)
    with jax.named_scope("unembed"):
        logits = jnp.einsum("bsh,hv->bsv", x.astype(jnp.float32),
                            _unembed(params, cfg).astype(jnp.float32))
    return logits, aux


def loss_fn(params, tokens, cfg: ModelConfig,
            attention_fn: Optional[Callable] = None) -> jax.Array:
    """Next-token cross entropy over tokens[:, :-1] -> tokens[:, 1:].

    When ``cfg.logits_chunk`` divides the sequence, the vocab
    projection + log-softmax run per sequence chunk under
    ``jax.checkpoint`` inside a scan, so the fp32 [B, S, V] logits
    tensor never materializes — at B32-S2048-V32k that tensor is
    2 x 7.8 GiB of HBM (fwd + grad), the allocation that capped the
    bench batch size (OOM trace in the r05 A/B). Backward recomputes
    one [B, C, V] chunk at a time."""
    x, aux = hidden_states(params, tokens[:, :-1], cfg, attention_fn)
    with jax.named_scope("loss"):
        return _mean_nll(x, tokens[:, 1:], _unembed(params, cfg),
                         cfg.logits_chunk) + 0.01 * aux


def token_nll(x, targets, unembed) -> jax.Array:
    """Negative log-likelihood of each target, float32, from the final
    hidden states (the loss of both train-step builders)."""
    with jax.named_scope("unembed"):
        logits = jnp.einsum("bsh,hv->bsv", x.astype(jnp.float32),
                            unembed.astype(jnp.float32))
    with jax.named_scope("softmax_xent"):
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(
            logp, targets[..., None], axis=-1)[..., 0]


def _mean_nll(x, targets, unembed, chunk: int) -> jax.Array:
    b, s, _ = x.shape
    if chunk and (s % chunk != 0 and s > chunk):
        # a non-dividing chunk would silently reintroduce the full
        # [B,S,V] fp32 logits — the OOM this feature exists to prevent
        import logging

        logging.getLogger(__name__).warning(
            "logits_chunk=%d does not divide sequence length %d; "
            "falling back to UNCHUNKED loss (full [B,S,V] fp32 logits "
            "materialize — may OOM at large batch x vocab)", chunk, s)
    if chunk and s % chunk == 0 and s > chunk:
        n_chunks = s // chunk
        chunk_fn = jax.checkpoint(
            lambda x_c, t_c, emb: token_nll(x_c, t_c, emb).sum())
        xs = x.reshape(b, n_chunks, chunk, -1).swapaxes(0, 1)
        ts = targets.reshape(b, n_chunks, chunk).swapaxes(0, 1)

        def body(acc, inp):
            x_c, t_c = inp
            return acc + chunk_fn(x_c, t_c, unembed), None

        total, _ = lax.scan(body, jnp.zeros((), jnp.float32), (xs, ts))
        return total / (b * s)
    return token_nll(x, targets, unembed).mean()
