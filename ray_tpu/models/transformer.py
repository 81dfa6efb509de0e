"""Flagship model family: decoder-only language models whose stack is
described, not hard-wired.

Pure-functional JAX: parameters are a pytree of arrays with a parallel
pytree of *logical axis names* (models/sharding rules in
parallel/mesh.py map those to mesh axes). Layers are stacked along a
leading axis and iterated with ``lax.scan`` so compile time is O(1) in
depth and the pipeline path can shard the same stack over ``pp``.

Two stacks behind one ``ModelConfig``:

- ``stack.pattern`` empty: one dense block (``dense_block``) scanned
  ``layers`` times: RMSNorm, rotary embeddings, GQA attention via
  ops.flash_attention, SwiGLU MLP.
- ``stack.pattern`` a string of kinds, one a layer (``Stack``): ``M`` a
  Mamba-2 mixer (ops/ssd.py); ``*`` GQA attention, causal over the whole
  sequence; ``W`` the same over a sliding window of ``stack.window``
  keys; ``L`` latent attention (low-rank query and key-value paths with
  a norm on each latent, a head part rotary and part not, one rotated
  key for all the heads); ``K`` a Kimi Delta Attention mixer (the gated
  delta rule with a decay a channel, ops/kda.py); ``C`` a gated short
  convolution (LFM2's: ``C * conv(B * x)`` of one projection's three
  parts, ops/short_conv.py); ``E`` a mixture of experts that is told
  which experts it holds; ``D`` the uniform stack's SwiGLU MLP.
  Every layer is ``x + f(RMSNorm(x))``; the parameters of each kind are
  stacked on a leading axis and the scan runs over whole periods of the
  pattern. A decoder block of attention and experts is two entries
  (``WEWEWE*E``: three windowed blocks, then a full one). ``stack.lead``
  names layers that run once before the first period (a leading dense
  block ``LD``), ``stack.mtp`` the block of a multi-token-prediction
  module (``mtp_loss``) that reads the stack's output; each has its
  kinds' parameters in a tree of its own beside ``layers``.

What a kind computes beyond its widths follows from what the ``Stack``
describes, never from a switch: each attention kind takes the rotary
table the stack gives it (``rope`` of ``*``, ``window_rope`` of ``W``;
plain or stretched by YaRN) or, given none, no rotary embedding at all
(a stack whose Mamba or delta-rule layers carry the positions), and
with ``attention_gate`` the ``*`` kind weighs what attention gives by
``sigmoid(x W_g)``, element for element, before ``wo``, and with
``qk_norm`` it norms each head of q and k before the rotary embedding;
the ``E`` kind routes
by ``router_score`` (``sigmoid`` scores with a correction bias that
chooses and never weighs, or a ``softmax`` over the router's width with
no bias), its experts are ``expert_act`` (``relu2``: two matrices an
expert; ``swiglu``: three), a ``shared_width`` of 0 leaves the
shared expert out, leaves, scope and all, and an ``expert_latent`` above
0 has the routed experts read and write a latent of that width, one
linear map down in front of them and one back up behind (router and
shared expert stay on the hidden state).

The ``E`` kind is the family's one mixture of experts. Every layer
function of either stack, and of the pipeline path in models/training.py,
is wrapped by ``remat`` and nowhere else.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from ray_tpu.observability.metrics import (
    loss_unembed_calls,
    moe_latent_proj_calls,
)
from ray_tpu.ops.attention import flash_attention, repeat_kv
from ray_tpu.ops.grouped import (
    TILE_M,
    grouped_matmul,
    places,
    rows_from_tokens,
    rows_moved,
    tokens_from_rows,
)
from ray_tpu.ops.kda import HEADS_AT_ONCE, gated_delta_rule
from ray_tpu.ops.layers import (
    rms_norm,
    rope_frequencies,
    rope_lanes,
    swiglu,
    yarn_frequencies,
)
from ray_tpu.ops.router import top_k_route
from ray_tpu.ops.short_conv import gated_short_conv
from ray_tpu.ops.ssd import conv_silu, gated_group_norm, ssd_scan

# the seventh, ``K``, is the one linear-attention kind: its state's
# transition is not diagonal (ops/kda.py); the eighth, ``C``, mixes
# tokens by a convolution of a few taps between two gates and carries no
# state beyond them (ops/short_conv.py)
KINDS = {"M": "mamba", "E": "moe", "*": "attention", "W": "window",
         "L": "latent", "D": "dense", "K": "kda", "C": "short_conv"}
# the expert layer's row buffer over the rows expected under even routing,
# where the stack names no other (``Stack.rows_over_expected``)
ROWS_OVER_EXPECTED = 2
# A loop over the periods keeps every layer's weights and gradients a
# second time, stacked by layer as well as by period: 2.1 GiB of the
# 8.6 GiB workspace of two periods of WEWEWE*E at hidden 2304 with 16
# experts held (the TPU compiler's buffer assignment for a described
# v5e, PR 30), with which that step does not fit a chip. Up to this many
# periods the scan is unrolled; beyond, the program's size is what the
# loop is for.
UNROLLED_PERIODS = 2


@dataclasses.dataclass(frozen=True)
class Rope:
    """The rotary table of one attention kind: pair i of a head turns
    ``theta**(-2i/d)`` a position. ``factor`` above 1 stretches it by
    YaRN for a model trained to ``original_max_seq`` positions
    (ops.layers.yarn_frequencies)."""
    theta: float = 10000.0
    factor: float = 1.0
    original_max_seq: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0

    def table(self, head_dim: int, max_seq: int):
        """(cos, sin), each [max_seq, head_dim / 2]."""
        if self.factor == 1.0:
            return rope_frequencies(head_dim, max_seq, self.theta)
        return yarn_frequencies(
            head_dim, max_seq, self.theta, self.factor,
            self.original_max_seq, self.beta_fast, self.beta_slow,
            self.attention_factor)


@dataclasses.dataclass(frozen=True)
class Stack:
    """A stack whose layers differ in kind: the pattern (one character
    of ``KINDS`` a layer) and the widths each kind needs beyond
    ``ModelConfig``'s own. An empty pattern is the uniform dense stack."""
    pattern: str = ""
    # layers before the first period, run once (a leading dense block
    # ``LD`` before periods ``LE``): kinds as ``pattern``'s
    lead: str = ""
    head_dim: int = 0            # of ``*``, ``W``, ``L``; 0: hidden // heads
    # the rotary table of ``*`` and ``L``, and of ``W``; None: that kind
    # takes no rotary embedding
    rope: Optional[Rope] = None
    window_rope: Optional[Rope] = None
    # L: the ranks of the query's and the key-value latent; the last
    # ``rope_dim`` of a head's ``head_dim`` are rotated, the rest carry no
    # position, and the rotated part of the key is one for all the heads;
    # ``v_head_dim`` is the value heads' width as the model states it:
    # 0 or ``head_dim``, the one width that is built
    q_rank: int = 0
    kv_rank: int = 0
    rope_dim: int = 0
    v_head_dim: int = 0
    # W: the keys a query sees, its own counted (i - window < j <= i)
    window: int = 0
    # *: ``sigmoid(x W_g)``, as wide as the query heads together, weighs
    # attention's output element for element before ``wo`` (Gated
    # Attention, arXiv 2505.06708); off: no such leaf, no such scope
    attention_gate: bool = False
    # *: an RMSNorm over each head of q and of k (a weight of head_dim
    # each, shared by the heads) before the rotary embedding, scope
    # ``qk_norm``; off: no such leaves, no such scope
    qk_norm: bool = False
    # C: the taps of the gated short convolution (``[B | C | x] = u W_in``,
    # ``y = C * conv(B * x)``, causal and depthwise, no bias); 0: no C
    # layers
    short_conv_taps: int = 0
    # K: heads x head_dim is the mixer's inner width (q, k and v alike);
    # the decay a channel and the output gate each come through a rank of
    # ``kda_gate_rank``; beta lies in (0, ``kda_beta_max``): 2 lets the
    # state's transition have negative eigenvalues; the convolutions in
    # front of q, k and v take ``conv_kernel`` taps, as M's
    kda_heads: int = 0
    kda_head_dim: int = 0
    kda_gate_rank: int = 0
    kda_beta_max: float = 1.0
    kda_chunk: int = 64
    # M: heads x head_dim is the mixer's inner width
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_groups: int = 1
    ssm_state: int = 0
    conv_kernel: int = 4
    chunk: int = 128
    # E: the router's width, and the contiguous range of it held here
    # (first, count); count 0 holds them all
    routed_experts: int = 0
    experts_per_token: int = 0
    expert_width: int = 0
    shared_width: int = 0        # 0: no shared expert
    # E: the width the routed experts read and write where it is not the
    # hidden size: ``l = u W_in`` in front of them, ``W_out`` behind their
    # weighed sum, both bare linear maps; 0: they work on the hidden state
    expert_latent: int = 0
    routed_scale: float = 1.0
    experts_held: Tuple[int, int] = (0, 0)
    # E: the row buffer over the rows the held experts draw under even
    # routing; 0: ``ROWS_OVER_EXPECTED``. The smaller the share of the
    # experts held, the wider a layer's draw swings about the even one
    # (8 of 64 held passed twice on the chip: PERF.md section 6, PR 32)
    rows_over_expected: float = 0.0
    # how the router scores: "sigmoid" (each expert alone, with the
    # correction bias) or "softmax" (over the router's width, no bias);
    # the chosen experts' scores over their own sum are the weights
    router_score: str = "sigmoid"
    # an expert: "relu2" W_down relu(W_up u)^2, or "swiglu"
    # W_down (silu(W_gate u) * W_up u); the shared expert likewise
    expert_act: str = "relu2"
    # what a step adds to the router's correction bias for an expert
    # that drew no token (``routing_report``); 0 leaves the bias alone
    bias_rate: float = 0.0
    # a multi-token-prediction module of depth 1 (DeepSeek-V3 report,
    # arXiv 2412.19437, section 2.2): the kinds of its one block, and
    # what its loss weighs beside the main one (``mtp_loss``)
    mtp: str = ""
    mtp_weight: float = 0.0

    def __post_init__(self):
        if set(self.every_kind) - set(KINDS):
            raise ValueError(f"pattern {self.every_kind!r} has kinds other "
                             f"than {sorted(KINDS)}")
        if (self.lead or self.mtp) and not self.pattern:
            raise ValueError("leading layers and an MTP module belong to a "
                             "stack by pattern")
        if "W" in self.every_kind and self.window < 1:
            raise ValueError("a pattern with W layers needs their window")
        if "L" in self.every_kind and not (
                self.q_rank and self.kv_rank and self.rope
                and 0 < self.rope_dim <= self.head_dim):
            raise ValueError("a pattern with L layers needs q_rank, kv_rank, "
                             "a rope and its rope_dim within head_dim")
        if "K" in self.every_kind and not (
                self.kda_heads and self.kda_head_dim and self.kda_gate_rank):
            raise ValueError("a pattern with K layers needs kda_heads, "
                             "kda_head_dim and kda_gate_rank")
        if "C" in self.every_kind and self.short_conv_taps < 1:
            raise ValueError("a pattern with C layers needs its "
                             "short_conv_taps")
        if (self.router_score not in ("sigmoid", "softmax")
                or self.expert_act not in ("relu2", "swiglu")):
            raise ValueError(
                f"router_score {self.router_score!r} is not sigmoid or "
                f"softmax, or expert_act {self.expert_act!r} not relu2 or "
                "swiglu")
        first, count = self.held
        if self.routed_experts and not (
                0 <= first and 0 < count
                and first + count <= self.routed_experts):
            raise ValueError(f"experts_held {self.experts_held} is not a "
                             f"range of {self.routed_experts} experts")

    @property
    def held(self) -> Tuple[int, int]:
        first, count = self.experts_held
        return first, count or self.routed_experts

    @property
    def router_bias(self) -> bool:
        """Whether the router has the correction bias: the sigmoid
        form's, which scores every expert alone."""
        return self.router_score == "sigmoid"

    @property
    def period(self) -> str:
        """The shortest prefix that the pattern repeats whole."""
        n = len(self.pattern)
        for p in range(1, n + 1):
            if n % p == 0 and self.pattern[:p] * (n // p) == self.pattern:
                return self.pattern[:p]
        return ""

    @property
    def every_kind(self) -> str:
        """The kinds of every layer there is: the leading ones, the
        periods', the module's block."""
        return self.lead + self.pattern + self.mtp

    def count(self, kind: str) -> int:
        return self.pattern.count(kind)

    @property
    def ssm_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def kda_inner(self) -> int:
        return self.kda_heads * self.kda_head_dim

    @property
    def conv_width(self) -> int:
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    def row_buffer(self, tokens: int) -> int:
        """Rows of (token, choice) pairs the expert layer computes:
        ``rows_over_expected`` (or ``ROWS_OVER_EXPECTED``) times those the
        held experts draw under even routing, and never more than every
        pair there can be. Rows beyond it are counted (``moe_rows_over``),
        never dropped unseen."""
        worst = tokens * min(self.experts_per_token, self.held[1])
        over = self.rows_over_expected or ROWS_OVER_EXPECTED
        rows = int(over * tokens * self.experts_per_token * self.held[1]
                   / self.routed_experts)
        # whole tiles of the grouped product (a buffer under one tile is
        # a test's: whole sublanes)
        unit = TILE_M if rows >= TILE_M else 8
        return min(worst, -(-rows // unit) * unit)


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
    # layers of more than one kind; ``layers`` is then the pattern's length
    stack: Stack = Stack()

    def __post_init__(self):
        described = self.stack.lead + self.stack.pattern
        if described and len(described) != self.layers:
            raise ValueError(
                f"layers={self.layers} but the pattern "
                f"{described!r} has {len(described)}")
        # a typo'd policy silently measuring full remat would mislabel
        # an A/B data point (r05 review finding)
        if self.remat_policy not in ("full", "dots"):
            raise ValueError(
                f"remat_policy must be 'full' or 'dots', "
                f"got {self.remat_policy!r}")

    @property
    def head_dim(self) -> int:
        return self.stack.head_dim or self.hidden // self.heads

    def rope_of(self, char: str = "*") -> Optional[Rope]:
        """The rotary table attention of kind ``char`` takes, or None:
        the uniform stack's is the plain one at ``rope_theta``, a
        pattern's kinds take what the stack gives each."""
        if not self.stack.pattern:
            return Rope(self.rope_theta)
        return {"*": self.stack.rope, "L": self.stack.rope,
                "W": self.stack.window_rope}.get(char)

    @property
    def rotary(self) -> bool:
        """Whether any layer takes rotary embeddings."""
        return any(self.rope_of(c) for c in self.stack.every_kind or "*")

    @classmethod
    def debug(cls, **kw) -> "ModelConfig":
        return cls(vocab_size=256, hidden=64, layers=2, heads=4, kv_heads=2,
                   intermediate=128, max_seq=128, dtype=jnp.float32, **kw)

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
    if cfg.stack.pattern:
        return _init_pattern_params(cfg, key)
    # 13 ways, 0-8 used: the split a dense model's weights have always
    # been drawn from (tests/test_hybrid_model.py holds them to the bit)
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
            "w_gate": (jax.random.normal(k[6], (nl, h, cfg.intermediate))
                       * scale).astype(dt),
            "w_up": (jax.random.normal(k[7], (nl, h, cfg.intermediate))
                     * scale).astype(dt),
            "w_down": (jax.random.normal(k[8], (nl, cfg.intermediate, h))
                       * (cfg.intermediate ** -0.5)).astype(dt),
        },
    }
    if not cfg.tie_embeddings:
        params["unembed"] = (jax.random.normal(k[5], (h, cfg.vocab_size))
                             * scale).astype(dt)
    return params


def logical_axes(cfg: ModelConfig) -> Dict[str, Any]:
    """Same-structure pytree of logical axis tuples, consumed by
    parallel.mesh.sharding_for."""
    if cfg.stack.pattern:
        return _pattern_axes(cfg)
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
    return axes


# -- a stack described by its pattern ----------------------------------------
# Shape and logical axes of every leaf of one layer of each kind; a
# leaf whose axes end in "f32" stays float32 whatever the model's type
# (norms, the router, the recurrence's own parameters).


def _kind_leaves(cfg: ModelConfig) -> Dict[str, Dict[str, Tuple]]:
    st, h = cfg.stack, cfg.hidden
    q, kv = cfg.heads * cfg.head_dim, cfg.kv_heads * cfg.head_dim
    kinds = st.every_kind
    out: Dict[str, Dict[str, Tuple]] = {}
    if "M" in kinds:
        inner, heads = st.ssm_inner, st.ssm_heads
        out["mamba"] = {
            "norm": ((h,), ("hidden",), "f32"),
            # z, x, B, C, dt side by side
            "w_in": ((h, inner + st.conv_width + heads),
                     ("hidden", "ssm_heads")),
            # values near 1: bfloat16's spacing there (4e-3) would lose
            # every update of 3e-4
            "conv_w": ((st.conv_kernel, st.conv_width), (None, "ssm_heads"),
                       "f32"),
            "conv_b": ((st.conv_width,), ("ssm_heads",), "f32"),
            "dt_bias": ((heads,), ("ssm_heads",), "f32"),
            "a_log": ((heads,), ("ssm_heads",), "f32"),
            "d": ((heads,), ("ssm_heads",), "f32"),
            "gate_norm": ((inner,), ("ssm_heads",), "f32"),
            "w_out": ((inner, h), ("ssm_heads", "hidden")),
        }
    if "E" in kinds:
        held, glu = st.held[1], st.expert_act == "swiglu"
        # what the routed experts read: the latent, where the stack has one
        read = st.expert_latent or h
        up = ((held, read, st.expert_width), ("experts", "hidden", None))
        shared_up = ((h, st.shared_width), ("hidden", "mlp"))
        moe = {
            "norm": ((h,), ("hidden",), "f32"),
            "router": ((h, st.routed_experts), ("hidden", None), "f32"),
            # the correction bias: chooses, never weighs; a buffer whose
            # gradient is exactly zero, moved by ``router_bias_step``
            "router_bias": ((st.routed_experts,), (None,), "f32"),
            "w_gate": up,
            "w_up": up,
            "w_down": ((held, st.expert_width, read),
                       ("experts", None, "hidden")),
            # the two maps between the hidden state and the latent, whole
            # on every chip that shares the layer like the shared expert
            "latent_in": ((h, st.expert_latent), ("hidden", "mlp")),
            "latent_out": ((st.expert_latent, h), ("mlp", "hidden")),
            "shared_gate": shared_up,
            "shared_up": shared_up,
            "shared_down": ((st.shared_width, h), ("mlp", "hidden")),
        }
        absent = ([] if st.router_bias else ["router_bias"]) + (
            [] if glu else ["w_gate", "shared_gate"]) + (
            [] if st.shared_width else ["shared_gate", "shared_up",
                                        "shared_down"]) + (
            [] if st.expert_latent else ["latent_in", "latent_out"])
        out["moe"] = {k: v for k, v in moe.items() if k not in absent}
    for char in "*W":
        if char in kinds:
            out[KINDS[char]] = {
                "attn_norm": ((h,), ("hidden",), "f32"),
                "wq": ((h, q), ("hidden", "heads")),
                "wk": ((h, kv), ("hidden", "kv_heads")),
                "wv": ((h, kv), ("hidden", "kv_heads")),
                "wo": ((q, h), ("heads", "hidden")),
            }
    if "*" in kinds and st.attention_gate:
        out["attention"]["w_gate"] = ((h, q), ("hidden", "heads"))
    if "*" in kinds and st.qk_norm:
        head = ((cfg.head_dim,), (None,), "f32")
        out["attention"].update(q_norm=head, k_norm=head)
    if "C" in kinds:
        # whole on every chip that shares the layer: a block of the
        # in-projection's columns over tp would cut across its three parts
        out["short_conv"] = {
            "norm": ((h,), ("hidden",), "f32"),
            # B, C and x side by side
            "w_in": ((h, 3 * h), ("hidden", None)),
            # float32 for M's reason: bfloat16 would lose the updates
            "conv_w": ((st.short_conv_taps, h), (None, None), "f32"),
            "w_out": ((h, h), (None, "hidden")),
        }
    if "K" in kinds:
        inner, rank = st.kda_inner, st.kda_gate_rank
        out["kda"] = {
            "norm": ((h,), ("hidden",), "f32"),
            # q, k, v side by side, and their convolutions' taps (no bias)
            "w_qkv": ((h, 3 * inner), ("hidden", "heads")),
            "conv_w": ((st.conv_kernel, 3 * inner), (None, "heads"), "f32"),
            # the decay a channel: g = -exp(a_log) softplus(. + dt_bias)
            "w_decay_down": ((h, rank), ("hidden", None)),
            "w_decay_up": ((rank, inner), (None, "heads")),
            "dt_bias": ((inner,), ("heads",), "f32"),
            "a_log": ((st.kda_heads,), ("heads",), "f32"),
            "w_beta": ((h, st.kda_heads), ("hidden", "heads")),
            "w_gate_down": ((h, rank), ("hidden", None)),
            "w_gate_up": ((rank, inner), (None, "heads")),
            # one weight for every head's norm
            "head_norm": ((st.kda_head_dim,), (None,), "f32"),
            "w_out": ((inner, h), ("heads", "hidden")),
        }
    if "L" in kinds:
        out["latent"] = {
            "attn_norm": ((h,), ("hidden",), "f32"),
            "w_dq": ((h, st.q_rank), ("hidden", None)),
            "q_norm": ((st.q_rank,), (None,), "f32"),
            "w_uq": ((st.q_rank, q), (None, "heads")),
            # the key-value latent and the one rotated key, side by side
            "w_dkv": ((h, st.kv_rank + st.rope_dim), ("hidden", None)),
            "kv_norm": ((st.kv_rank,), (None,), "f32"),
            # a head's key without position and its value (as wide as the
            # head: ``_refuse_unbuilt`` lets no other by), side by side
            "w_ukv": ((st.kv_rank, 2 * q - cfg.heads * st.rope_dim),
                      (None, "heads")),
            "wo": ((q, h), ("heads", "hidden")),
        }
    if "D" in kinds:
        up = ((h, cfg.intermediate), ("hidden", "mlp"))
        out["dense"] = {
            "mlp_norm": ((h,), ("hidden",), "f32"),
            "w_gate": up,
            "w_up": up,
            "w_down": ((cfg.intermediate, h), ("mlp", "hidden")),
        }
    return out


def _mtp_leaves(cfg: ModelConfig) -> Dict[str, Tuple]:
    """The module's own leaves beside its block's: the norms of the two
    halves it joins, the projection that joins them, its head's norm.
    Embedding and head are the model's."""
    h = cfg.hidden
    norm = ((h,), ("hidden",), "f32")
    return {"enorm": norm, "hnorm": norm,
            "eh_proj": ((2 * h, h), (None, "hidden")), "head_norm": norm}


def _init_pattern_params(cfg: ModelConfig, key: jax.Array) -> Dict[str, Any]:
    """Norms at 1, biases at 0, matrices normal at fan_in**-0.5; the
    recurrence's own as the Mamba-2 family starts them: dt log-uniform in
    [1e-3, 1e-1] through the inverse softplus, A uniform in [1, 16], D 1."""
    st, h, dt = cfg.stack, cfg.hidden, cfg.dtype
    keys = iter(jax.random.split(key, 64))
    leaves_of = _kind_leaves(cfg)

    def leaf(name, full, f32):
        if name.endswith("norm") or name == "d":
            return jnp.ones(full, jnp.float32)
        if name in ("conv_b", "router_bias"):
            return jnp.zeros(full, jnp.float32 if f32 else dt)
        if name == "dt_bias":
            step = jnp.exp(jax.random.uniform(
                next(keys), full, minval=jnp.log(1e-3), maxval=jnp.log(1e-1)))
            step = jnp.maximum(step, 1e-4)
            return step + jnp.log(-jnp.expm1(-step))
        if name == "a_log":
            return jnp.log(jax.random.uniform(next(keys), full, minval=1.0,
                                              maxval=16.0))
        # a matrix's rows; the convolution's taps
        value = jax.random.normal(next(keys), full) * full[-2] ** -0.5
        return value.astype(jnp.float32 if f32 else dt)

    def stacked(kinds):
        """The leaves of each kind among ``kinds``, stacked over that
        kind's layers there."""
        return {kind: {name: leaf(name, (kinds.count(char),) + spec[0],
                                  len(spec) > 2)
                       for name, spec in leaves_of[kind].items()}
                for char, kind in KINDS.items() if char in kinds}

    params: Dict[str, Any] = {
        "embed": (jax.random.normal(next(keys), (cfg.vocab_size, h)) * 0.02
                  ).astype(dt),
        "final_norm": jnp.ones((h,), jnp.float32),
        "layers": stacked(st.pattern),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = (jax.random.normal(
            next(keys), (h, cfg.vocab_size)) * h ** -0.5).astype(dt)
    if st.lead:
        params["lead"] = stacked(st.lead)
    if st.mtp:
        params["mtp"] = {name: leaf(name, spec[0], len(spec) > 2)
                         for name, spec in _mtp_leaves(cfg).items()}
        params["mtp"]["block"] = stacked(st.mtp)
    return params


def _pattern_axes(cfg: ModelConfig) -> Dict[str, Any]:
    st, leaves_of = cfg.stack, _kind_leaves(cfg)

    def stacked(kinds):
        return {kind: {name: ("layers",) + spec[1]
                       for name, spec in leaves_of[kind].items()}
                for char, kind in KINDS.items() if char in kinds}

    axes: Dict[str, Any] = {
        "embed": ("vocab", "hidden"),
        "final_norm": ("hidden",),
        "layers": stacked(st.pattern),
    }
    if not cfg.tie_embeddings:
        axes["unembed"] = ("hidden", "vocab")
    if st.lead:
        axes["lead"] = stacked(st.lead)
    if st.mtp:
        axes["mtp"] = {name: spec[1]
                       for name, spec in _mtp_leaves(cfg).items()}
        axes["mtp"]["block"] = stacked(st.mtp)
    return axes


# -- transformer block -------------------------------------------------------


def _heads_view(x, heads: int):
    """[B, S, heads * D] as the [B, S, heads, D] an ``attention_fn``
    takes: a rename on the way to kernels that index the lanes and rename
    it back, nothing standing between the two."""
    return x.reshape(*x.shape[:2], heads, x.shape[-1] // heads)


def _head_norm(x, weight, heads: int, eps: float):
    """RMSNorm over each head of x [B, S, heads * D], one weight [D]."""
    return rms_norm(_heads_view(x, heads), weight, eps).reshape(x.shape)


def attention_block(x, layer, cfg: ModelConfig, cos, sin,
                    attention_fn: Callable, window: int = 0,
                    sharded: bool = False, gated: bool = False,
                    qk_norm: bool = False) -> jax.Array:
    """``cos`` / ``sin`` None: a kind without rotary embeddings, no
    ``rope`` scope. ``window``: a ``W`` layer's, handed to
    ``attention_fn``. ``sharded``: the step is partitioned over a mesh
    (``rope_lanes`` then keeps to plain jnp). ``gated``: the layer has a
    ``w_gate`` (``Stack.attention_gate``), scope ``gate``. ``qk_norm``:
    the layer has ``q_norm`` and ``k_norm`` (``Stack.qk_norm``), scope
    ``qk_norm``, before ``rope``.

    q, k and v stay [B, S, their heads * head_dim] from the projections
    to ``attention_fn``, a head a block of lanes, as the flash kernels
    index them (``_heads_view`` renames them for the call); K and V are
    copied to the query heads that share them on the way
    (``ops.attention.repeat_kv``, which like ``rope_lanes`` asks
    ``ops.attention.lane_layout`` how)."""
    b, s, h = x.shape
    rotary = cos is not None
    with jax.named_scope("attention"):
        with jax.named_scope("qkv_proj"):
            xn = rms_norm(x, layer["attn_norm"], cfg.norm_eps)
            q = jnp.einsum("bsh,hd->bsd", xn, layer["wq"])
            k = jnp.einsum("bsh,hd->bsd", xn, layer["wk"])
            v = jnp.einsum("bsh,hd->bsd", xn, layer["wv"])
        if qk_norm:
            with jax.named_scope("qk_norm"):
                q = _head_norm(q, layer["q_norm"], cfg.heads, cfg.norm_eps)
                k = _head_norm(k, layer["k_norm"], cfg.kv_heads,
                               cfg.norm_eps)
        if rotary:
            with jax.named_scope("rope"):
                q = rope_lanes(q, cos, sin, cfg.heads, sharded=sharded)
                k = rope_lanes(k, cos, sin, cfg.kv_heads, sharded=sharded)
        with jax.named_scope("flash"):
            q = _heads_view(q, cfg.heads)
            k, v = (repeat_kv(_heads_view(a, cfg.kv_heads), cfg.heads, sharded)
                    for a in (k, v))
            attn = (attention_fn(q, k, v, window=window) if window
                    else attention_fn(q, k, v))
        if gated:
            with jax.named_scope("gate"):
                gate = jnp.einsum("bsh,hd->bsd", xn, layer["w_gate"])
                attn = (attn.reshape(gate.shape).astype(jnp.float32)
                        * jax.nn.sigmoid(gate.astype(jnp.float32))
                        ).astype(attn.dtype)
        with jax.named_scope("out_proj"):
            attn = attn.reshape(b, s, cfg.heads * cfg.head_dim)
            return x + jnp.einsum("bsd,dh->bsh", attn, layer["wo"])


def _key_columns(w_uk, rope_dim: int):
    """[kv_rank, heads, nope] -> the weights [kv_rank + rope_dim,
    heads * (nope + rope_dim)] that take ``[c_kv | k_r]`` to every head's
    whole key in one product: a head's ``k_n`` columns with noughts under
    its rotary lanes, above an identity that copies ``k_r`` into every
    head's rotary lanes (a one and noughts: the copy is exact, and the
    product's transpose sums ``k_r``'s cotangent over the heads)."""
    kv_rank, heads, nope = w_uk.shape
    copy = jnp.pad(jnp.eye(rope_dim, dtype=w_uk.dtype), ((0, 0), (nope, 0)))
    return jnp.concatenate([
        jnp.pad(w_uk, ((0, 0), (0, 0), (0, rope_dim))).reshape(kv_rank, -1),
        jnp.tile(copy, (1, heads))], axis=0)


def latent_attention_block(x, layer, cfg: ModelConfig, cos, sin,
                           attention_fn: Callable,
                           sharded: bool = False) -> jax.Array:
    """Attention whose queries, keys and values come through low-rank
    latents (DeepSeek-V2's MLA as the GLM and DeepSeek-V3 families train
    it): ``c_q = norm(u W_dq)``, ``q = c_q W_uq``; ``[c_kv; k_r] = u
    W_dkv``, ``[k_n; v] = norm(c_kv) W_ukv``. The last ``rope_dim`` of a
    query head and the one ``k_r`` are rotated; every head's key is its
    own ``k_n`` beside the same rotated ``k_r``, copied to each head here,
    so its cotangent is summed over the heads. The kernel sees whole
    heads of ``head_dim``.

    q, k and v stay [B, S, heads * head_dim] from the up-projections to
    ``attention_fn``, a head a block of lanes, and nothing slices an
    activation inside a 128-lane tile (``rope_lanes`` says what that
    costs): ``W_ukv``'s columns are cut into the keys' and the values' in
    the weights, so ``v`` is a product's own output, and so is ``k``: the
    keys' product takes the rotated ``k_r`` as ``rope_dim`` more columns
    of its input (``_key_columns``)."""
    b, s, h = x.shape
    st, heads, hd = cfg.stack, cfg.heads, cfg.head_dim
    nope = hd - st.rope_dim
    with jax.named_scope("attention"):
        with jax.named_scope("q_down"):
            xn = rms_norm(x, layer["attn_norm"], cfg.norm_eps)
            cq = jnp.einsum("bsh,hr->bsr", xn, layer["w_dq"])
        with jax.named_scope("q_up"):
            q = jnp.einsum(
                "bsr,rd->bsd", rms_norm(cq, layer["q_norm"], cfg.norm_eps),
                layer["w_uq"])
        with jax.named_scope("kv_down"):
            ckv, k_rope = jnp.split(
                jnp.einsum("bsh,hr->bsr", xn, layer["w_dkv"]),
                [st.kv_rank], axis=-1)
        with jax.named_scope("rope"):
            q = rope_lanes(q, cos, sin, heads, st.rope_dim, sharded)
            k_rope = rope_lanes(k_rope, cos, sin, 1)
        with jax.named_scope("kv_up"):
            ckv = rms_norm(ckv, layer["kv_norm"], cfg.norm_eps)
            w_ukv = layer["w_ukv"].reshape(st.kv_rank, heads, nope + hd)
            v = jnp.einsum("bsr,rd->bsd", ckv, w_ukv[
                :, :, nope:].reshape(st.kv_rank, heads * hd))
            k = jnp.einsum(
                "bsr,rd->bsd", jnp.concatenate([ckv, k_rope], axis=-1),
                _key_columns(w_ukv[:, :, :nope], st.rope_dim))
        with jax.named_scope("flash"):
            attn = attention_fn(*(_heads_view(a, heads) for a in (q, k, v)))
        with jax.named_scope("out_proj"):
            return x + jnp.einsum("bsd,dh->bsh", attn.reshape(b, s, -1),
                                  layer["wo"])


def mlp_block(x, layer, cfg: ModelConfig) -> jax.Array:
    with jax.named_scope("mlp"):
        xn = rms_norm(x, layer["mlp_norm"], cfg.norm_eps)
        return x + swiglu(xn, layer["w_gate"], layer["w_up"],
                          layer["w_down"])


def dense_block(x, layer, cfg: ModelConfig, cos, sin,
                attention_fn: Callable, sharded: bool = False) -> jax.Array:
    """The one layer of the uniform stack: attention, then the MLP."""
    x = attention_block(x, layer, cfg, cos, sin, attention_fn,
                        sharded=sharded)
    return mlp_block(x, layer, cfg)


def remat(fn: Callable, cfg: ModelConfig) -> Callable:
    """A layer function under ``jax.checkpoint`` as ``cfg.remat`` and
    ``cfg.remat_policy`` say. Every stack wraps its layers here (the
    uniform one, each kind of a pattern, the pipeline's stages), so none
    can read the policy differently."""
    if not cfg.remat:
        return fn
    if cfg.remat_policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.
            dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)


def dense_layers(x, layers, cfg: ModelConfig, cos, sin,
                 attention_fn: Callable, sharded: bool = False) -> jax.Array:
    """x through ``dense_block`` for each of the stacked ``layers`` (the
    whole uniform stack, or one pipeline stage's slice of it)."""
    block = remat(lambda x, layer: dense_block(
        x, layer, cfg, cos, sin, attention_fn, sharded), cfg)
    x, _ = lax.scan(lambda x, layer: (block(x, layer), None), x, layers)
    return x


def _causal_flash(q, k, v, window=None):
    """The attention a caller that names none gets."""
    return flash_attention(q, k, v, True, None, None, None, window)


def hidden_states(params: Dict[str, Any], tokens: jax.Array,
                  cfg: ModelConfig,
                  attention_fn: Optional[Callable] = None,
                  sharded: bool = False
                  ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """tokens [B, S] int32 -> (final hidden states [B, S, H], the tokens
    each expert drew in each ``E`` layer [E layers, router width]; None
    for a stack without such layers). ``sharded``: the step is
    partitioned over a mesh of more than one device, which
    ``attention_fn`` answers for attention and ``ops.ssd.scan_tier`` for
    the Mamba layers' scan; a ``W`` layer calls it with its ``window``."""
    attention_fn = attention_fn or _causal_flash
    if cfg.stack.pattern:
        return _pattern_hidden_states(params, tokens, cfg, attention_fn,
                                      sharded)
    cos, sin = rope_frequencies(cfg.head_dim, cfg.max_seq, cfg.rope_theta)
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], tokens, axis=0)
    with jax.named_scope("layers"):
        x = dense_layers(x, params["layers"], cfg, cos, sin, attention_fn,
                         sharded)
    with jax.named_scope("final_norm"):
        return rms_norm(x, params["final_norm"], cfg.norm_eps), None


# -- the kinds of a pattern stack --------------------------------------------


def mamba_block(x, layer, cfg: ModelConfig,
                sharded: bool = False) -> jax.Array:
    """Mamba-2 mixer: x + W_out . norm(ssd(conv(xBC), dt) * silu(z))."""
    st = cfg.stack
    b, s, _ = x.shape
    inner, gn = st.ssm_inner, st.ssm_groups * st.ssm_state
    with jax.named_scope("mamba"):
        with jax.named_scope("in_proj"):
            xn = rms_norm(x, layer["norm"], cfg.norm_eps)
            proj = jnp.einsum("bsh,hd->bsd", xn, layer["w_in"])
            dt = lax.slice_in_dim(proj, inner + st.conv_width, None, axis=-1)
        with jax.named_scope("conv"):
            # the kernels read their lanes of the projection where it lies
            xs, bm, cm = conv_silu(proj, layer["conv_w"], layer["conv_b"],
                                   (inner, inner + gn), inner, sharded)
        with jax.named_scope("ssd"):
            dt = jax.nn.softplus(dt.astype(jnp.float32) + layer["dt_bias"])
            y = ssd_scan(
                xs.reshape(b, s, st.ssm_heads, st.ssm_head_dim), dt,
                -jnp.exp(layer["a_log"]),
                bm.reshape(b, s, st.ssm_groups, st.ssm_state),
                cm.reshape(b, s, st.ssm_groups, st.ssm_state),
                layer["d"], min(st.chunk, s), sharded)
        with jax.named_scope("gate_norm"):
            # the gate is the projection's first lanes, read where it lies
            y = gated_group_norm(y.reshape(b, s, inner), proj,
                                 layer["gate_norm"], st.ssm_groups,
                                 cfg.norm_eps, sharded)
        with jax.named_scope("out_proj"):
            return x + jnp.einsum("bsd,dh->bsh", y, layer["w_out"])


def kda_block(x, layer, cfg: ModelConfig,
              sharded: bool = False) -> jax.Array:
    """Kimi Delta Attention (Kimi Linear, arXiv 2510.26692): x + W_out .
    (norm_head(delta(q, k, v, g, beta)) * sigmoid(gate)), with q, k, v =
    silu(conv(x W)) (q and k then normalised a head, q scaled by
    head_dim**-0.5), the decay a channel g = -exp(a_log) softplus(x
    W_down W_up + dt_bias) a head's ``a_log`` over its channels, beta =
    ``kda_beta_max`` sigmoid(x W_beta) a head, and the gate through a
    rank of its own. No position enters but through the state.

    Between the normed input and the sum that ``W_out`` makes the heads
    share nothing, and what lies between is wide: at 64 heads of 128 over
    8192 positions every float32 quantity a channel is 256 MiB, and a step
    that holds a dozen of them beside 12 GiB of parameters and moments
    does not fit a chip (the TPU compiler's count for a described v5e,
    PR 39: 17.5 GB of 15.75). So the mixer runs a group of
    ``ops.kda.HEADS_AT_ONCE`` heads at a time, each group's columns of the
    weights in turn, each group rebuilt in its backward, and ``W_out``'s
    rows of the group add to a float32 sum."""
    st = cfg.stack
    b, s, h = x.shape
    heads, hd = st.kda_heads, st.kda_head_dim
    at_once = max(n for n in range(1, min(heads, HEADS_AT_ONCE) + 1)
                  if heads % n == 0)
    groups, width = heads // at_once, at_once * hd

    def columns(w, parts: int = 1):
        """[rows, parts * heads * hd] -> [groups, rows, parts * width]:
        each group's columns of every part side by side."""
        rows = w.shape[0]
        return w.reshape(rows, parts, groups, width).transpose(
            2, 0, 1, 3).reshape(groups, rows, parts * width)

    def unit(t):
        t = t.reshape(b, s, at_once, hd).astype(jnp.float32)
        return t * lax.rsqrt(
            jnp.sum(jnp.square(t), axis=-1, keepdims=True) + 1e-6)

    def some_heads(total, w, beta):
        """``total`` plus what one group of heads gives: ``w`` its columns
        of the weights, ``beta`` [B, S, at_once]."""
        with jax.named_scope("qkv_proj"):
            proj = jnp.einsum("bsh,hd->bsd", xn, w["w_qkv"])
        with jax.named_scope("conv"):
            # no bias in this family: a nought that is no parameter
            q, k, v = conv_silu(
                proj, w["conv_w"], jnp.zeros((3 * width,), jnp.float32),
                (width, 2 * width), 0, sharded)
        with jax.named_scope("gates"):
            # the decay's pre-activation is float32 from its rank on, both
            # ways: g is summed over the positions of a chunk and of the
            # state's life, and its cotangent back into the rank's 128
            g = -jnp.repeat(jnp.exp(w["a_log"]), hd) * jax.nn.softplus(
                jnp.einsum("bsr,rd->bsd", decay_low,
                           w["w_decay_up"].astype(jnp.float32),
                           precision=lax.Precision.HIGHEST)
                + w["dt_bias"])
            gate = jnp.einsum("bsr,rd->bsd", gate_low, w["w_gate_up"])
        with jax.named_scope("delta"):
            o = gated_delta_rule(
                (unit(q) * hd ** -0.5).astype(x.dtype),
                unit(k).astype(x.dtype), v.reshape(b, s, at_once, hd),
                g.reshape(b, s, at_once, hd), beta, min(st.kda_chunk, s),
                sharded)
        with jax.named_scope("gate_norm"):
            # the norm is a head's, before the gate, and the gate a
            # sigmoid: not ``gated_group_norm``'s form. Rounded once
            o = o.astype(jnp.float32)
            y = o * lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                              + cfg.norm_eps) * layer["head_norm"]
            y = (y.reshape(b, s, width) * jax.nn.sigmoid(
                gate.astype(jnp.float32))).astype(x.dtype)
        with jax.named_scope("out_proj"):
            return total + jnp.einsum(
                "bsd,dh->bsh", y, w["w_out"],
                preferred_element_type=jnp.float32)

    with jax.named_scope("kda"):
        with jax.named_scope("qkv_proj"):
            xn = rms_norm(x, layer["norm"], cfg.norm_eps)
        with jax.named_scope("gates"):
            # what is narrow is made once for all the heads
            decay_low = jnp.einsum("bsh,hr->bsr", xn, layer["w_decay_down"],
                                   preferred_element_type=jnp.float32)
            gate_low = jnp.einsum("bsh,hr->bsr", xn, layer["w_gate_down"])
            beta = st.kda_beta_max * jax.nn.sigmoid(jnp.einsum(
                "bsh,hn->bsn", xn, layer["w_beta"],
                preferred_element_type=jnp.float32))
            by_group = {
                "w_qkv": columns(layer["w_qkv"], 3),
                "conv_w": columns(layer["conv_w"], 3),
                "w_decay_up": columns(layer["w_decay_up"]),
                "dt_bias": layer["dt_bias"].reshape(groups, width),
                "a_log": layer["a_log"].reshape(groups, at_once),
                "w_gate_up": columns(layer["w_gate_up"]),
                "w_out": layer["w_out"].reshape(groups, width, h),
            }
            beta = jnp.moveaxis(beta.reshape(b, s, groups, at_once), 2, 0)
        one = jax.checkpoint(some_heads)
        total, _ = lax.scan(lambda total, mine: (one(total, *mine), None),
                            jnp.zeros((b, s, h), jnp.float32),
                            (by_group, beta))
        with jax.named_scope("out_proj"):
            return x + total.astype(x.dtype)


def short_conv_block(x, layer, cfg: ModelConfig,
                     sharded: bool = False) -> jax.Array:
    """LFM2's gated short convolution: ``[B | C | x~] = RMSNorm(x) W_in``,
    ``y = C * conv(B * x~)`` with ``short_conv_taps`` causal taps a
    channel and no bias, ``x + y W_out``. The three parts stay one array
    from the projection to ``ops.short_conv.gated_short_conv``, whose
    kernels read each where it lies."""
    with jax.named_scope("short_conv"):
        with jax.named_scope("in_proj"):
            xn = rms_norm(x, layer["norm"], cfg.norm_eps)
            proj = jnp.einsum("bsh,hd->bsd", xn, layer["w_in"])
        with jax.named_scope("gate_conv"):
            y = gated_short_conv(proj, layer["conv_w"], sharded)
        with jax.named_scope("out_proj"):
            return x + jnp.einsum("bsd,dh->bsh", y, layer["w_out"])


# what a step reports of its ``E`` layers' rows (token, choice): those
# whose expert is held here, summed over the layers; those of the fullest
# held expert of any layer; those beyond a layer's row buffer (computed by
# nobody: the caller has to count them as failures)
MOE_ROWS = ("moe_rows_held", "moe_rows_max_expert", "moe_rows_over")


def relu2_mlp(x, w_up, w_down):
    up = jnp.einsum("...h,hm->...m", x, w_up)
    return jnp.einsum("...m,mh->...h", jnp.square(jax.nn.relu(up)), w_down)


def route(xt, layer, st: Stack, sharded: bool = False
          ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(the chosen experts [T, k], their weights [T, k] float32, the
    tokens every expert of the router's width drew [E] int32) for normed
    tokens xt [T, H], in float32 whatever the model's type. The choice is
    ``ops/router.py``'s (``sharded``: the step is partitioned over a
    mesh)."""
    logits = jnp.einsum("th,he->te", xt.astype(jnp.float32), layer["router"],
                        precision=lax.Precision.HIGHEST)
    if st.router_score == "softmax":
        scores, bias = jax.nn.softmax(logits, axis=-1), None
    else:
        scores, bias = jax.nn.sigmoid(logits), layer["router_bias"]
    chosen, gates, drawn = top_k_route(scores, bias, st.experts_per_token,
                                       sharded)
    return (chosen, gates / gates.sum(-1, keepdims=True) * st.routed_scale,
            drawn)


def _latent_map(side: str, xt, weight):
    """One of the two maps between the hidden state and the experts'
    latent: a bare product, counted when traced."""
    moe_latent_proj_calls.inc(1, {"side": side})
    with jax.named_scope("latent_" + side):
        return jnp.einsum("ta,ab->tb", xt, weight)


def routed_experts(xt, layer, st: Stack, sharded: bool = False
                   ) -> Tuple[jax.Array, jax.Array]:
    """The held experts' part of the layer for tokens xt [T, H]: route
    over all the experts, keep the (token, choice) pairs whose expert is
    held, sort them by expert, one grouped product over the held experts,
    weigh and scatter back. -> ([T, H], the tokens every expert of the
    router's width drew [E] int32). With ``st.expert_latent`` the router
    reads xt and the rows are those of ``xt W_in``: buffer, products and
    the sum back are the latent's width, and ``W_out`` takes the held
    experts' weighed sum back to the hidden width (it is linear, so the
    shares of the chips that hold the other experts still add up).
    ``sharded``: the step is partitioned over a mesh (``route``)."""
    t = xt.shape[0]
    k = st.experts_per_token
    first, held = st.held
    rows = st.row_buffer(t)
    with jax.named_scope("router"):
        chosen, gates, drawn = route(xt, layer, st, sharded)
    if st.expert_latent:
        xt = _latent_map("in", xt, layer["latent_in"])
    with jax.named_scope("dispatch"):
        local = chosen.reshape(-1) - first
        # an expert that is not held sorts behind every one that is
        local = jnp.where((local >= 0) & (local < held), local, held)
        order = jnp.argsort(local, stable=True)[:rows]
        # each held expert's rows in the buffer, as far as it has room;
        # its tail belongs to no group: the grouped products leave it
        # alone, and the rows' way in and out stops at ``ends[-1]``
        ends = jnp.minimum(jnp.cumsum(drawn[first:first + held]), rows)
        sizes = jnp.diff(ends, prepend=0)
        where = places(local, order, ends[-1], held, k)
        # the buffer once for each product that reads it
        swiglu = st.expert_act == "swiglu"
        rows_in = rows_from_tokens(xt, where, readers=1 + swiglu)
    with jax.named_scope("experts"):
        up = grouped_matmul(rows_in[0], layer["w_up"], sizes, jnp.float32)
        if swiglu:
            inner = jax.nn.silu(grouped_matmul(
                rows_in[1], layer["w_gate"], sizes, jnp.float32)) * up
        else:
            inner = jnp.square(jax.nn.relu(up))
        rows_out = grouped_matmul(inner.astype(xt.dtype), layer["w_down"],
                                  sizes, jnp.float32)
    with jax.named_scope("combine"):
        out = tokens_from_rows(rows_out, gates.reshape(-1)[order], where, t,
                               xt.dtype)
    if st.expert_latent:
        out = _latent_map("out", out, layer["latent_out"])
    return out, drawn


def moe_block(x, layer, cfg: ModelConfig, sharded: bool = False
              ) -> Tuple[jax.Array, jax.Array]:
    """x + routed experts held here + the shared expert, where the stack
    has one. ``sharded``: the step is partitioned over a mesh."""
    b, s, h = x.shape
    st = cfg.stack
    with jax.named_scope("mlp"):
        with jax.named_scope("moe"):
            xn = rms_norm(x, layer["norm"], cfg.norm_eps)
            routed, drawn = routed_experts(xn.reshape(b * s, h), layer, st,
                                           sharded)
            if not st.shared_width:
                return x + routed.reshape(b, s, h), drawn
            with jax.named_scope("shared_expert"):
                if st.expert_act == "swiglu":
                    shared = swiglu(xn, layer["shared_gate"],
                                    layer["shared_up"], layer["shared_down"])
                else:
                    shared = relu2_mlp(xn, layer["shared_up"],
                                       layer["shared_down"])
            return x + routed.reshape(b, s, h) + shared, drawn


def _kind_fns(cfg: ModelConfig, kinds: str, attention_fn,
              sharded: bool) -> Dict[str, Callable]:
    """{kind's character: (x, one layer's weights) -> (x, the tokens each
    expert drew or None)}, each under ``remat``."""
    st = cfg.stack

    def kind_fn(char):
        if char == "M":
            fn = lambda x, w: (  # noqa: E731
                mamba_block(x, w, cfg, sharded), None)
        elif char == "K":
            fn = lambda x, w: (  # noqa: E731
                kda_block(x, w, cfg, sharded), None)
        elif char == "C":
            fn = lambda x, w: (  # noqa: E731
                short_conv_block(x, w, cfg, sharded), None)
        elif char == "E":
            fn = lambda x, w: moe_block(x, w, cfg, sharded)  # noqa: E731
        elif char == "D":
            fn = lambda x, w: (mlp_block(x, w, cfg), None)  # noqa: E731
        elif char == "L":
            cos, sin = cfg.rope_of(char).table(st.rope_dim, cfg.max_seq)
            fn = lambda x, w: (latent_attention_block(  # noqa: E731
                x, w, cfg, cos, sin, attention_fn, sharded), None)
        else:
            rope = cfg.rope_of(char)
            cos, sin = (rope.table(cfg.head_dim, cfg.max_seq) if rope
                        else (None, None))
            window = st.window if char == "W" else 0
            gated = st.attention_gate and char == "*"
            qk_norm = st.qk_norm and char == "*"
            fn = lambda x, w: (attention_block(  # noqa: E731
                x, w, cfg, cos, sin, attention_fn, window, sharded, gated,
                qk_norm), None)
        return remat(fn, cfg)

    return {char: kind_fn(char) for char in set(kinds)}


def _run_kinds(x, layers, kinds: str, fns):
    """x through the layers ``kinds`` names, the n-th of a kind taking
    the n-th of that kind's stacked ``layers`` -> (x, what the ``E``
    layers among them drew [E layers, router width], or None)."""
    seen = dict.fromkeys(KINDS, 0)
    drawn = []
    for char in kinds:
        weights = jax.tree.map(lambda a: a[seen[char]], layers[KINDS[char]])
        seen[char] += 1
        x, counted = fns[char](x, weights)
        if counted is not None:
            drawn.append(counted)
    return x, (jnp.stack(drawn) if drawn else None)


def _all_drawn(*drawn):
    """The counts of several groups of ``E`` layers as one [E layers,
    router width], or None where no group has such a layer."""
    drawn = [d.reshape(-1, d.shape[-1]) for d in drawn if d is not None]
    return jnp.concatenate(drawn) if drawn else None


def _pattern_hidden_states(params, tokens, cfg: ModelConfig, attention_fn,
                           sharded: bool):
    st = cfg.stack
    period = st.period
    periods = len(st.pattern) // len(period)
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], tokens, axis=0)
    fns = _kind_fns(cfg, st.lead + period, attention_fn, sharded)

    def one_period(x, layers):
        return _run_kinds(x, layers, period, fns)

    with jax.named_scope("layers"):
        led = None
        if st.lead:
            x, led = _run_kinds(x, params["lead"], st.lead, fns)
        # each kind's leaves [n, ...] -> [periods, n / periods, ...]
        by_period = jax.tree.map(
            lambda a: a.reshape(periods, a.shape[0] // periods,
                                *a.shape[1:]), params["layers"])
        # so few periods run as straight-line code
        x, drawn = lax.scan(
            one_period, x, by_period,
            unroll=periods if periods <= UNROLLED_PERIODS else 1)
    drawn = _all_drawn(led, drawn)
    with jax.named_scope("final_norm"):
        return rms_norm(x, params["final_norm"], cfg.norm_eps), drawn


def mtp_loss(params, x, tokens, cfg: ModelConfig, attention_fn,
             sharded: bool = False, unembed_sharding=None):
    """The multi-token-prediction module of depth 1 (DeepSeek-V3 report,
    arXiv 2412.19437, section 2.2) -> (its loss, what its ``E`` layers
    drew): position i joins the model's final hidden state ``x_i`` (after
    the final norm) with the embedding of the next token, each normed,
    by one projection; one block of the module's own weights (positions
    0 ... as the stack's) and a norm of its own lead to the model's head,
    which is asked for the token after the next. Embedding and head are
    the model's arrays. ``tokens`` [B, S + 1] as the main loss reads
    them; the block runs over all S positions so that the kernels tile,
    and the last, which has no token after the next, weighs nought in
    the mean (its routing is counted like any position's).
    ``unembed_sharding`` as ``loss_and_rows`` takes it."""
    st, module = cfg.stack, params["mtp"]
    with jax.named_scope("mtp"):
        with jax.named_scope("merge"):
            e = jnp.take(params["embed"], tokens[:, 1:], axis=0)
            z = jnp.einsum("bsh,hd->bsd", jnp.concatenate(
                [rms_norm(e, module["enorm"], cfg.norm_eps),
                 rms_norm(x, module["hnorm"], cfg.norm_eps)], -1),
                module["eh_proj"])
        z, drawn = _run_kinds(z, module["block"], st.mtp,
                              _kind_fns(cfg, st.mtp, attention_fn, sharded))
        with jax.named_scope("final_norm"):
            z = rms_norm(z, module["head_norm"], cfg.norm_eps)
        with jax.named_scope("loss"):
            targets = jnp.pad(tokens[:, 2:], ((0, 0), (0, 1)))
            asked = jnp.arange(z.shape[1]) < z.shape[1] - 1
            nll = _mean_nll(z, targets, _unembed(params, cfg),
                            cfg.logits_chunk,
                            jnp.broadcast_to(asked, targets.shape),
                            unembed_sharding)
    return nll, drawn


def routing_report(drawn, st: Stack, tokens: int) -> Dict[str, jax.Array]:
    """What a step says of its ``E`` layers from the tokens each expert
    drew [E layers, router width]: ``MOE_ROWS``; ``moe_rows_moved``, the
    buffers' rows that dispatch and combine touched (``ops.grouped``:
    whole passes up to the rows held where the movement is trimmed, the
    whole buffers where it is not); and ``router_bias_step``,
    what the step adds to the correction bias (the balancing without an
    auxiliary loss of Wang et al. 2024, arXiv 2408.15664, in the form
    that follows the size of the error and not its sign alone): the
    share by which an expert's draw fell short of an even draw, times
    ``bias_rate``. The bias chooses experts and never weighs them, so a
    loss sees none of it; a router without one (``softmax``) reports no
    such step."""
    first, held = st.held
    mine = drawn[:, first:first + held]
    rows = st.row_buffer(tokens)
    report = {
        "moe_rows_held": mine.sum(),
        "moe_rows_max_expert": mine.max(),
        "moe_rows_over": jnp.maximum(mine.sum(-1) - rows, 0).sum(),
        "moe_rows_moved": rows_moved(
            jnp.minimum(mine.sum(-1), rows), rows, tokens).sum(),
    }
    if st.router_bias:
        even = tokens * st.experts_per_token / st.routed_experts
        report["router_bias_step"] = st.bias_rate * (1.0 - drawn / even)
    return report


def add_router_bias(params: Dict[str, Any], step) -> Dict[str, Any]:
    """``params`` with ``router_bias_step`` [E layers, router width]
    added to the ``E`` layers' correction bias, in the order the layers
    are counted: the leading ones, the periods', the module's."""
    at = 0

    def moved(kinds):
        """A tree of kinds with its ``E`` layers' share of the step."""
        nonlocal at
        if "moe" not in kinds:
            return kinds
        bias = kinds["moe"]["router_bias"]
        mine, at = step[at:at + bias.shape[0]], at + bias.shape[0]
        return dict(kinds, moe=dict(kinds["moe"], router_bias=bias + mine))

    out = dict(params)
    for where in ("lead", "layers"):
        if where in out:
            out[where] = moved(out[where])
    if "mtp" in out:
        out["mtp"] = dict(out["mtp"], block=moved(out["mtp"]["block"]))
    return out


def _unembed(params, cfg: ModelConfig):
    return (params["embed"].T if cfg.tie_embeddings
            else params["unembed"])


def forward(params: Dict[str, Any], tokens: jax.Array, cfg: ModelConfig,
            attention_fn: Optional[Callable] = None, sharded: bool = False
            ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """tokens [B, S] int32 -> (logits [B, S, V] float32, what
    ``hidden_states`` counts of the ``E`` layers, or None)."""
    x, drawn = hidden_states(params, tokens, cfg, attention_fn, sharded)
    with jax.named_scope("unembed"):
        logits = jnp.einsum("bsh,hv->bsv", x.astype(jnp.float32),
                            _unembed(params, cfg).astype(jnp.float32))
    return logits, drawn


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
    return loss_and_rows(params, tokens, cfg, attention_fn)[0]


def loss_and_rows(params, tokens, cfg: ModelConfig,
                  attention_fn: Optional[Callable] = None,
                  sharded: bool = False, unembed_sharding=None
                  ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """(``loss_fn``'s loss, ``routing_report`` of the ``E`` layers; empty
    for a model without them). No stack adds an auxiliary loss; one that
    declares an MTP module adds ``mtp_weight`` times the module's loss,
    reports the two parts as ``loss_main`` and ``loss_mtp``, and counts
    the module's routing with the layers'. ``unembed_sharding``: the
    ``NamedSharding`` the step holds the unembedding in (``[hidden,
    vocab]``, the tied embedding's turned); over a mesh of more than one
    device the loss is ``_mean_nll_on_mesh``'s."""
    x, drawn = hidden_states(params, tokens[:, :-1], cfg, attention_fn,
                             sharded)
    with jax.named_scope("loss"):
        nll = _mean_nll(x, tokens[:, 1:], _unembed(params, cfg),
                        cfg.logits_chunk, None, unembed_sharding)
    counted = {}
    if cfg.stack.mtp:
        ahead, mtp_drawn = mtp_loss(params, x, tokens, cfg,
                                    attention_fn or _causal_flash, sharded,
                                    unembed_sharding)
        counted = {"loss_main": nll, "loss_mtp": ahead}
        nll = nll + cfg.stack.mtp_weight * ahead
        drawn = _all_drawn(drawn, mtp_drawn)
    if drawn is None:
        return nll, counted
    return nll, {**counted,
                 **routing_report(drawn, cfg.stack, x.shape[0] * x.shape[1])}


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


def _mean_nll(x, targets, unembed, chunk: int, weights=None,
              unembed_sharding=None) -> jax.Array:
    """Mean of ``token_nll``; ``weights`` [B, S] of 0 / 1: over the
    positions that weigh 1 alone. Handed the sharding of a mesh of more
    than one device, the loss is ``_mean_nll_on_mesh``'s."""
    if unembed_sharding is not None and unembed_sharding.mesh.size > 1:
        return _mean_nll_on_mesh(x, targets, unembed, chunk, weights,
                                 unembed_sharding)
    loss_unembed_calls.inc(1, {"layout": "plain"})
    b, s, _ = x.shape
    if chunk and (s % chunk != 0 and s > chunk):
        # a non-dividing chunk would silently reintroduce the full
        # [B,S,V] fp32 logits — the OOM this feature exists to prevent
        import logging

        logging.getLogger(__name__).warning(
            "logits_chunk=%d does not divide sequence length %d; "
            "falling back to UNCHUNKED loss (full [B,S,V] fp32 logits "
            "materialize — may OOM at large batch x vocab)", chunk, s)

    def summed(x_c, t_c, w_c, emb):
        nll = token_nll(x_c, t_c, emb)
        return (nll if w_c is None else nll * w_c).sum()

    count = b * s
    if weights is not None:
        weights = weights.astype(jnp.float32)
        count = weights.sum()
    if chunk and s % chunk == 0 and s > chunk:
        n_chunks = s // chunk
        chunk_fn = jax.checkpoint(summed)

        def chunks(a):
            return a.reshape(b, n_chunks, chunk, *a.shape[2:]).swapaxes(0, 1)

        def body(acc, inp):
            return acc + chunk_fn(*inp, unembed), None

        total, _ = lax.scan(
            body, jnp.zeros((), jnp.float32),
            (chunks(x), chunks(targets),
             None if weights is None else chunks(weights)))
        return total / count
    return summed(x, targets, weights, unembed) / count


def _mean_nll_on_mesh(x, targets, unembed, chunk: int, weights,
                      unembed_sharding) -> jax.Array:
    """``_mean_nll`` on a mesh, vocabulary-parallel: a shard_map in which
    each chip's rows (``x`` [B, S, H] over ``dp`` and ``sp``, the whole
    hidden width) meet its share of the vocabulary (the unembedding
    gathered over its hidden axis once, before the chunks: one
    reduce-scatter of its gradient after them). A chunk's float32 logits
    stay on the chip, ``[B/dp, C, V/tp]``; the log-sum-exp's max and sum
    and the target's logit, picked by the chip whose slice holds it, are
    combined over ``tp`` at ``[B/dp, C]``. Under GSPMD every chunk
    gathered the unembedding, and its backward gathered the dlogits and
    all-reduced a float32 gradient of the whole unembedding.

    The arithmetic is ``token_nll``'s: logits the float32 product of
    float32-cast operands, the softmax in float32, one
    ``jax.checkpoint`` a chunk; sums across chips come in another order.
    The chunks are the local sequence's."""
    loss_unembed_calls.inc(1, {"layout": "vocab_parallel"})
    mesh = unembed_sharding.mesh
    hidden_axis, vocab_axis = unembed_sharding.spec
    rows = ("dp", "sp")
    count = x.shape[0] * x.shape[1]
    if weights is not None:
        weights = weights.astype(jnp.float32)
        count = weights.sum()

    def vary(a, axes):
        """``a`` varying over ``axes``, where it does not already."""
        axes = tuple(ax for ax in axes if ax not in jax.typeof(a).vma)
        return lax.pcast(a, axes, to="varying") if axes else a

    def local(x, targets, w, weights=None):
        if hidden_axis:
            with jax.named_scope("unembed"):
                w = lax.all_gather(w, hidden_axis, axis=0, tiled=True)
        b, s, _ = x.shape
        # both operands vary over every axis before the loop, so that the
        # transposes (dx summed over tp, dW over the rows) follow it
        x, w = vary(x, (vocab_axis,)), vary(w, rows)
        v_local = w.shape[1]
        first = lax.axis_index(vocab_axis) * v_local

        def summed(x_c, t_c, w_c, w):
            with jax.named_scope("unembed"):
                logits = jnp.einsum("bsh,hv->bsv", x_c.astype(jnp.float32),
                                    w.astype(jnp.float32))
            with jax.named_scope("softmax_xent"):
                top = lax.pmax(lax.stop_gradient(logits.max(-1)), vocab_axis)
                shifted = logits - top[..., None]
                at = t_c - first
                picked = jnp.where(
                    (at >= 0) & (at < v_local),
                    jnp.take_along_axis(
                        shifted, jnp.clip(at, 0, v_local - 1)[..., None],
                        axis=-1)[..., 0], 0.0)
                total, picked = lax.psum((jnp.exp(shifted).sum(-1), picked),
                                         vocab_axis)
                nll = jnp.log(total) - picked
                return (nll if w_c is None else nll * w_c).sum()

        if chunk and s % chunk == 0 and s > chunk:
            n_chunks = s // chunk
            chunk_fn = jax.checkpoint(summed)

            def chunks(a):
                return a.reshape(b, n_chunks, chunk,
                                 *a.shape[2:]).swapaxes(0, 1)

            def body(acc, inp):
                return acc + chunk_fn(*inp, w), None

            total, _ = lax.scan(
                body, vary(jnp.zeros((), jnp.float32), rows),
                (chunks(x), chunks(targets),
                 None if weights is None else chunks(weights)))
        else:
            total = summed(x, targets, weights, w)
        return lax.psum(total, rows)

    by_row = P("dp", "sp")
    args = (x, targets, unembed) + (() if weights is None else (weights,))
    specs = (P("dp", "sp", None), by_row, P(hidden_axis, vocab_axis),
             by_row)[:len(args)]
    return shard_map(local, mesh=mesh, in_specs=specs,
                     out_specs=P())(*args) / count
