"""Ling-3.0-flash (`model_type: bailing_hybrid`; config.json at
huggingface.co/inclusionAI/Ling-3.0-flash) — the zoo's decoder whose
attention is a recurrence in five layers of six: a delta rule with a
channel-wise decay (KDA: Kimi Linear arXiv:2510.26692 section 3) behind
three short causal convolutions, and latent attention without a query
latent (MLA, DeepSeek-V2 arXiv:2405.04434 section 2.1) in the sixth; two
leading dense layers, then 512 sigmoid-routed experts chosen under a
group limit (DeepSeek-V3 arXiv:2412.19437 section 2.1.2, `noaux_tc`)
beside a shared one.

    layer  : h = x + Attn_kind(RMSNorm(x));  y = h + FFN(RMSNorm(h))
             kind = layer_types[l]: linear_attention | full_attention
             (published: full where (l + 1) % layer_group_size == 0)
    KDA    : q, k, v = SiLU(Conv4(x W_q)), SiLU(Conv4(x W_k)),
             SiLU(Conv4(x W_v)): causal depthwise taps, no bias; q, k
             L2-normalised over a head's features, q times D^-1/2
             (`short_conv`: for a TPU one kernel a direction,
             ops/pallas_shortconv.py);
             g = lower_bound * sigmoid(exp(A_h) (x W_f + b_f)) in float32,
             a log-decay a channel in [lower_bound, 0]; beta = sigmoid(x
             W_beta) a head; no position encoding
             S_t = (I - beta_t k_t k_t^T) Diag(e^g_t) S_{t-1} + beta_t k_t
             v_t^T, o_t = S_t^T q_t       (ops/kda.py:chunked_kda)
             y = W_o [RMSNorm_head(o) * sigmoid(x W_g)_head]
    MLA    : nn/glm_moe.py:MLA with `q_rank=None` (q = x W_q), RoPE over
             ADJACENT pairs of q_pe and the shared k_pe (`interleaved`),
             the core's output times sigmoid(x W_g), a gate a head
    Expert : nn/glm_moe.py:ExpertLayer with `n_group` / `topk_group`; a
             layer's clamps from the two `*_swiglu_limit_list`s
    logits = RMSNorm(x_L) W_head (untied); loss = next-token CE (+
             `mtp_weight` times the MTP module's, an MLA layer, where a
             weight is given: the published one is 0 and builds none)

`kda_lower_bound` is what makes the chunked form safe: the decay inside a
sub-chunk of 16 positions is split into two float32 factors, each within
`e^(16 * 5 / 2)` (ops/kda.py), and `g` cannot pass the bound: it is the
bound times a sigmoid.

What nn/glm_moe.py has is used as it is: `GlmMoe` (rematerialised layers,
final norm, head, blocked cross-entropy, `loss`, MTP), `DecoderLayer`'s
frame, `MLA`, `ExpertLayer`.

Scopes: `embed`, `l<i>/attn/{norm,qkv,conv,gates,core,gate_norm,o}` (KDA)
or `l<i>/attn/{norm,q,kv,rope,core,gate,o}` (MLA), `l<i>/mlp/...` or
`l<i>/moe/{norm,route,dispatch,experts,combine,shared}`, `norm`, `head`,
`loss`.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from parallel_cnn_tpu.nn import glm_moe
from parallel_cnn_tpu.nn.core import Module, Shape
from parallel_cnn_tpu.nn.glm_moe import (
    INIT_STD,
    MLA,
    DecoderLayer,
    ExpertLayer,
    GlmMoe,
    _norm,
    _ones,
)
from parallel_cnn_tpu.nn.layers import GatedMLP, _weight, causal_conv
from parallel_cnn_tpu.ops import kda, pallas_shortconv

LINEAR, FULL = "linear_attention", "full_attention"
L2_EPS = pallas_shortconv.L2_EPS


def layer_kinds(layers: int, group: int) -> Tuple[str, ...]:
    """The published layer-kind table: the last layer of every `group` is
    a full-attention one, the others are linear."""
    return tuple(FULL if (i + 1) % group == 0 else LINEAR
                 for i in range(layers))


def _unit(x):
    """`x` over its last axis' Euclidean norm (float32 statistics)."""
    xf = x.astype(jnp.float32)
    return (xf * jax.lax.rsqrt(
        jnp.sum(xf * xf, axis=-1, keepdims=True) + L2_EPS)).astype(x.dtype)


def _short_conv_plain(x, taps, unit: bool, scale: float):
    """`short_conv`'s plain body: the stages composed, each looked up in
    this module when the layer is traced."""
    y = jax.nn.silu(causal_conv(x, taps))
    if unit:
        y = _unit(y)
    return y if scale == 1.0 else y * scale


# The two stages ops/pallas_shortconv.py's kernels implement, as this module
# had them when it was imported.
_FUSED_STAGES = (causal_conv, _unit)


def short_conv(x, taps, unit: bool = False, scale: float = 1.0):
    """`scale * unit(SiLU(causal_conv(x, taps)))` of head-major `x (N, H,
    S, D)` and `taps (K, H, D)`: `_short_conv_plain`, or — for shapes
    `pallas_shortconv.tile` takes, where the step is lowered for a TPU —
    one kernel a direction that rounds once. The kernels are those two
    stages and no others: while `causal_conv` or `_unit` of this module is
    another function than the one they were written from (a comparison's
    planted fault replaces them), the stages compose plainly everywhere."""
    fused = (causal_conv, _unit) == _FUSED_STAGES and pallas_shortconv.tile(
        x.shape[-2], x.shape[-1], taps.shape[0]) is not None
    if not fused:
        return _short_conv_plain(x, taps, unit, scale)
    return pallas_shortconv.short_conv(x, taps, unit, scale, _short_conv_plain)


@dataclasses.dataclass(frozen=True)
class KDA(Module):
    """The linear-attention layer (module docstring): `heads` heads whose
    keys and values are `head_dim` wide, `taps` causal taps a channel, the
    log-decay's floor `lower_bound` < 0."""

    heads: int = 32
    head_dim: int = 128
    taps: int = 4
    lower_bound: float = -5.0
    eps: float = 1e-6

    def __post_init__(self):
        # float32 holds e^88.7: a sub-chunk's whole decay has to stay inside
        if not -88.0 < kda.SUBCHUNK * self.lower_bound < 0:
            raise ValueError(
                f"a log-decay down to {self.lower_bound} over sub-chunks of "
                f"{kda.SUBCHUNK} positions leaves float32's range")

    def init(self, key, in_shape: Shape):
        d, h, wide = in_shape[-1], self.heads, self.heads * self.head_dim
        shapes = {"q": (d, wide), "k": (d, wide), "v": (d, wide),
                  "f": (d, wide), "o": (wide, d), "beta": (d, h),
                  "gate": (d, h)}
        keys = jax.random.split(key, len(shapes) + 3)
        params = {n: _weight(k, s, s[0], INIT_STD)
                  for (n, s), k in zip(shapes.items(), keys)}
        for name, k in zip(("q_conv", "k_conv", "v_conv"), keys[len(shapes):]):
            # (a depthwise conv's own default: uniform within 1/sqrt(taps))
            params[name] = jax.random.uniform(
                k, (self.taps, wide), jnp.float32, -1.0, 1.0) * self.taps ** -0.5
        # e^A in [1, 2] over the heads and a bias of -1: the log-decay lies
        # well inside (lower_bound, 0) at the first step, spread by head
        params["a_log"] = jnp.log1p(jnp.arange(h, dtype=jnp.float32)
                                    / max(h - 1, 1))
        params["f_bias"] = jnp.full((wide,), -1.0, jnp.float32)
        params["o_norm"] = _ones(self.head_dim)
        return params, {}, in_shape

    def log_decay(self, params, z):
        """`g` float32 of the decay projection `z (N, H, S, D)`."""
        h, d = self.heads, self.head_dim
        z = z.astype(jnp.float32) + params["f_bias"].reshape(h, 1, d)
        return self.lower_bound * jax.nn.sigmoid(
            jnp.exp(params["a_log"])[:, None, None] * z)

    def apply(self, params, state, x, train: bool = False):
        """Heads ahead of positions throughout, as `MLA.apply`."""
        w = {k: v.astype(x.dtype) for k, v in params.items()}
        h, d = self.heads, self.head_dim
        heads_of = lambda name: jnp.einsum(  # noqa: E731
            "nsm,mhd->nhsd", x, w[name].reshape(-1, h, d))
        with jax.named_scope("qkv"):
            q, k, v = heads_of("q"), heads_of("k"), heads_of("v")
        with jax.named_scope("conv"):
            q, k, v = (short_conv(
                a, params[f"{name}_conv"].reshape(self.taps, h, d), unit, scale)
                for name, a, unit, scale in (
                    ("q", q, True, d ** -0.5), ("k", k, True, 1.0),
                    ("v", v, False, 1.0)))
        with jax.named_scope("gates"):
            g = self.log_decay(params, heads_of("f"))
            beta = jax.nn.sigmoid(jnp.einsum(
                "nsm,mh->nhs", x, w["beta"]).astype(jnp.float32))
        with jax.named_scope("core"):
            out = kda.chunked_kda(q, k, v, g, beta)
        with jax.named_scope("gate_norm"):
            out = glm_moe._head_gated(
                _norm(self.eps, w["o_norm"], out),
                jnp.einsum("nsm,mh->nhs", x, w["gate"]))
        with jax.named_scope("o"):
            return jnp.einsum("nhsd,hdm->nsm", out,
                              w["o"].reshape(h, d, -1)), state


@dataclasses.dataclass(frozen=True)
class BailingHybrid(GlmMoe):
    """The language model (module docstring): `GlmMoe` with a layer of its
    own kind at every depth (`layer_types`; `attn` is the full layers' and
    the MTP module's, `linear` the others') and a clamp a layer
    (`expert_limits`, `shared_limits`; empty: none anywhere). `in_shape`,
    `x`, `apply` and `loss` as `GlmMoe`."""

    linear: KDA = KDA()
    layer_types: Tuple[str, ...] = ()
    expert_limits: Tuple[float, ...] = ()
    shared_limits: Tuple[float, ...] = ()

    def __post_init__(self):
        super().__post_init__()
        n = self.n_layers
        for name in ("layer_types", "expert_limits", "shared_limits"):
            object.__setattr__(self, name, tuple(getattr(self, name)))
        if len(self.layer_types) != n or set(self.layer_types) - {LINEAR, FULL}:
            raise ValueError(
                f"layer_types {self.layer_types}: one of {LINEAR!r}, {FULL!r} "
                f"for each of the {n} layers")
        for limits in (self.expert_limits, self.shared_limits):
            if limits and len(limits) != n:
                raise ValueError(f"{len(limits)} limits for {n} layers")

    def attention(self, kind: str) -> Module:
        """The attention module of a layer of `kind`: the layer-kind table."""
        return self.linear if kind == LINEAR else self.attn

    def _layers(self) -> List[DecoderLayer]:
        dense = GatedMLP(self.dense_width, INIT_STD)

        def experts(i: int) -> ExpertLayer:
            return dataclasses.replace(
                self.experts,
                limit=self.expert_limits[i] if self.expert_limits else 0.0,
                shared_limit=self.shared_limits[i] if self.shared_limits
                else 0.0)

        return [
            DecoderLayer(self.attention(kind),
                         dense if i < self.first_dense else experts(i),
                         self.eps)
            for i, kind in enumerate(self.layer_types)
        ]

    def describe(self, tokens_per_step: int, seq_len: int,
                 platform: str) -> Dict[str, object]:
        """`GlmMoe.describe` (whose attention core, tile and tiles are the
        full layers') and the linear layers': the scan's chunk, sub-chunk
        and the bytes of the states one sequence's backward keeps, and
        what runs it and the short convolutions ahead of it where the step
        is lowered for `platform` (`kda_core`, `kda_short_conv`: `"pallas"`
        | `"xla"`, as `attention_core` says for the full layers)."""
        said = super().describe(tokens_per_step, seq_len, platform)
        lin = self.linear
        steps, span = kda.spans(seq_len)
        said.update(
            attention_layer_kinds=list(self.layer_types),
            attention_tile=self.attn.core(seq_len)[1] if platform == "tpu"
            else self.attn.q_block,
            attention_qk_width=self.attn.qk_width,
            kda_chunk=kda.CHUNK, kda_subchunk=kda.SUBCHUNK,
            kda_scan_steps=steps, kda_chunks_a_step=span,
            kda_core=kda.core(seq_len, lin.head_dim, lin.head_dim, platform),
            kda_short_conv=pallas_shortconv.core(
                seq_len, lin.head_dim, lin.taps, platform),
            kda_state_bytes=kda.state_bytes(
                seq_len, lin.heads, lin.head_dim, lin.head_dim))
        return said


def bailing_hybrid(
    *,
    vocab_size: int,
    hidden_size: int,
    intermediate_size: int,
    moe_intermediate_size: int,
    num_hidden_layers: int,
    num_attention_heads: int,
    head_dim: int,
    kv_lora_rank: int,
    qk_nope_head_dim: int,
    qk_rope_head_dim: int,
    v_head_dim: int,
    num_experts: int,
    num_experts_per_tok: int,
    n_group: int,
    topk_group: int,
    layer_group_size: int = 6,
    layer_types: Optional[Sequence[str]] = None,
    first_k_dense_replace: int = 2,
    q_lora_rank: Optional[int] = None,
    num_shared_experts: int = 1,
    routed_scaling_factor: float = 1.0,
    kda_lower_bound: float = -5.0,
    short_conv_kernel_size: int = 4,
    rope_theta: float = 6e6,
    rope_interleave: bool = True,
    rms_norm_eps: float = 1e-6,
    num_nextn_predict_layers: int = 0,
    mtp_loss_scaling_factor: float = 0.0,
    expert_swiglu_limit_list: Sequence[float] = (),
    share_expert_swiglu_limit_list: Sequence[float] = (),
    held_experts: Optional[Sequence[int]] = None,
    row_buffer: Optional[int] = None,
    bias_update_speed: float = 1e-3,
    balance_weight: float = 0.0,
    gate_gradient: bool = True,
    dtype: str = "bfloat16",
    q_block: int = 512,
    loss_block: int = 2048,
) -> BailingHybrid:
    """A `bailing_hybrid` decoder by its config.json's keys. `layer_types`
    (default: the table `layer_group_size` gives) names each layer's kind
    where the layers kept are not the leading ones; the two limit lists
    have an entry a layer kept. `held_experts`, `row_buffer` and
    `gate_gradient` as `glm_moe_lite` has them. The MTP module is built
    where `num_nextn_predict_layers` asks for it."""
    kinds = tuple(layer_kinds(num_hidden_layers, layer_group_size)
                  if layer_types is None else layer_types)
    held = range(num_experts) if held_experts is None else held_experts
    return BailingHybrid(
        vocab=vocab_size, hidden=hidden_size, dense_width=intermediate_size,
        n_layers=num_hidden_layers,
        attn=MLA(num_attention_heads, q_lora_rank, kv_lora_rank,
                 qk_nope_head_dim, qk_rope_head_dim, v_head_dim, rope_theta,
                 rms_norm_eps, q_block, gated=True,
                 interleaved=rope_interleave),
        experts=ExpertLayer(
            moe_intermediate_size, num_experts, num_experts_per_tok,
            tuple(held), num_shared_experts, routed_scaling_factor,
            row_buffer, bias_update_speed, balance_weight, gate_gradient,
            "sigmoid", n_group, topk_group),
        first_dense=first_k_dense_replace,
        mtp_modules=num_nextn_predict_layers,
        mtp_weight=mtp_loss_scaling_factor, eps=rms_norm_eps, dtype=dtype,
        loss_block=loss_block,
        linear=KDA(num_attention_heads, head_dim, short_conv_kernel_size,
                   kda_lower_bound, rms_norm_eps),
        layer_types=kinds, expert_limits=tuple(expert_swiglu_limit_list),
        shared_limits=tuple(share_expert_swiglu_limit_list),
    )


def ling_3_0_flash(
    layer_types: Optional[Sequence[str]] = None,
    num_dense_layers: int = 2,
    vocab_size: int = 157184,
    held_experts: Optional[Sequence[int]] = None,
    row_buffer: Optional[int] = None,
    gate_gradient: bool = True,
    **overrides,
) -> BailingHybrid:
    """Ling-3.0-flash at its published widths (about 125 B parameters
    whole, 5.5 B active a token): hidden 2,560; 32 heads; in five layers
    of six a delta rule over 128-wide keys and values behind 4-tap
    convolutions, in the sixth latent attention of 128 + 64 / 128 over a
    512-wide key/value latent and no query latent; 2 dense layers 6,144
    wide, then 512 experts 768 wide in 8 groups, 4 groups and 8 experts a
    token (gates scaled 2.5), beside 1 shared. The published MTP module
    carries weight 0 and is not built. The layers kept (`layer_types`,
    default the published 42, and how many of the leading ones are
    dense), the vocabulary's rows and the experts held are the caller's
    cut: one chip of a 64-way expert-parallel group holds
    `held_experts=range(8)` and, the 64 as eight groups of eight over the
    vocabulary, 19,648 rows."""
    kinds = tuple(layer_kinds(42, 6) if layer_types is None else layer_types)
    kwargs = dict(
        vocab_size=vocab_size, hidden_size=2560, intermediate_size=6144,
        moe_intermediate_size=768, num_hidden_layers=len(kinds),
        num_attention_heads=32, head_dim=128, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        num_experts=512, num_experts_per_tok=8, n_group=8, topk_group=4,
        layer_group_size=6, layer_types=kinds,
        first_k_dense_replace=num_dense_layers, q_lora_rank=None,
        num_shared_experts=1, routed_scaling_factor=2.5,
        kda_lower_bound=-5.0, short_conv_kernel_size=4, rope_theta=6e6,
        rope_interleave=True, rms_norm_eps=1e-6, num_nextn_predict_layers=0,
        mtp_loss_scaling_factor=0.0, held_experts=held_experts,
        row_buffer=row_buffer, gate_gradient=gate_gradient,
    )
    kwargs.update(overrides)
    return bailing_hybrid(**kwargs)
