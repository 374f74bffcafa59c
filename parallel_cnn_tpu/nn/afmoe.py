"""Trinity-Mini (`model_type: afmoe`; config.json at
huggingface.co/arcee-ai/Trinity-Mini) — the zoo's decoder whose layers
differ by kind: sliding-window and full attention 3 : 1 over grouped
key/value heads, a sigmoid gate on the attention's output, a norm on both
sides of each sub-layer, leading dense layers, then 128 experts under
sigmoid scores and a balanced selection bias beside a shared one.

    x      = Emb(t) * sqrt(hidden)                    (`mup_enabled`)
    layer  : h = x + RMSNorm(Attn_kind(RMSNorm(x)))
             y = h + RMSNorm(FFN(RMSNorm(h)))         four norms a layer
             kind = layer_types[l]: sliding_attention | full_attention
             FFN = GatedMLP(dense_width) in the first `first_dense` layers,
             the expert layer after them
    Attn   : q = x W_q -> H heads, k, v = x W_k, x W_v -> KV heads,
             g = x W_g -> H heads; q, k <- RMSNorm over the head's
             features (gains g_q, g_k); RoPE on q, k in the SLIDING layers
             only (a full layer carries no position); query head a reads
             key/value head a // (H / KV); query i sees key j iff j <= i
             (full) or i - window < j <= i (sliding: `window` keys, its
             own among them); softmax(q k^T / sqrt(D) + mask) v, times
             sigmoid(g), through W_o
    Expert : nn/glm_moe.py:ExpertLayer as it is — s = sigmoid(x W_r) in
             float32, the k largest of s + b, g_i = route_scale * s_i /
             sum_chosen s_j, the held experts' part beside the whole
             shared expert; b moves after every step by load_balance_coeff
             * sign(mean load - load) (`finish_step`)
    logits = RMSNorm(x_L) W_head (untied); loss = mean next-token CE

The attention core is the fused kernels of ops/pallas_attention.py
(`grouped_causal_attention`: the block-diffusion pair's tile bodies under
this mask's schedule, a window being a shorter schedule and no other
kernel) where the shapes tile and the step is lowered for a TPU, else
`GatedGQA._blocks`: the same mask in plain XLA, a block of queries at a
time against the keys it may see.

What nn/glm_moe.py has is used as it is: `GlmMoe` (layers and their
rematerialisation, final norm, head, the blocked cross-entropy, `loss`,
`finish_step`, `counters`), `DecoderLayer`'s frame, `ExpertLayer`.

Scopes: `embed`, `l<i>/attn/{norm,qkv,qk_norm,rope,core,gate,o,post_norm}`
(`rope` in the sliding layers only), `l<i>/mlp/{norm,post_norm,...}` or
`l<i>/moe/{norm,route,dispatch,experts,combine,shared,post_norm}`, `norm`,
`head`, `loss`.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from parallel_cnn_tpu.nn.core import Module, Shape
from parallel_cnn_tpu.nn.glm_moe import (
    INIT_STD,
    DecoderLayer,
    ExpertLayer,
    GlmMoe,
    _norm,
    _ones,
)
from parallel_cnn_tpu.nn.layers import (
    Embedding, GatedMLP, _weight, rope, row_major)
from parallel_cnn_tpu.ops import pallas_attention

SLIDING, FULL = "sliding_attention", "full_attention"


def pairs_allowed(s: int, window: Optional[int]) -> int:
    """Pairs (query, key) of `s` positions that the mask allows, one
    sequence and head: query i sees `min(i + 1, window)` keys."""
    w = s if window is None else min(window, s)
    return w * (w + 1) // 2 + (s - w) * w


@functools.partial(jax.checkpoint, static_argnums=(3, 4, 5, 6))
def _attend(q, k, v, q0: int, k0: int, window: Optional[int], scale: float):
    """Queries `q0`... of `q (N, KV, G, q, D)` against keys `k0`... of `k,
    v (N, KV, k, D)`: scores and softmax in float32. Rematerialised: the
    backward recomputes the block's scores."""
    s = jnp.einsum("ncgqd,nckd->ncgqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    qi = q0 + jnp.arange(q.shape[3])[:, None]
    kj = k0 + jnp.arange(k.shape[2])[None, :]
    seen = kj <= qi
    if window is not None:
        seen &= kj > qi - window
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    return jnp.einsum("ncgqk,nckd->ncgqd", p.astype(v.dtype), v)


def _gated(out, gate):
    """The attention's output times the sigmoid (float32) of its gate."""
    return out * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(out.dtype)


@dataclasses.dataclass(frozen=True)
class GatedGQA(Module):
    """Grouped-query attention of one layer kind in its training form:
    `kv_heads` key/value heads, each read by `heads / kv_heads` query
    heads; an RMSNorm over each head's features of q and of k (`qk_norm`);
    RoPE where `rotary`; each query over the `window` keys that end with
    its own (None: every key up to its own); the output times the sigmoid
    of a projection of the layer's own input, head for head, before `o`
    (`gated`). Without `qk_norm` or `gated` the layer has no leaf and no
    op for it (nn/ouro.py: plain multi-head attention)."""

    heads: int = 32
    kv_heads: int = 4
    head_dim: int = 128
    window: Optional[int] = 2048
    rotary: bool = True
    theta: float = 1e4
    eps: float = 1e-5
    q_block: int = 512
    qk_norm: bool = True
    gated: bool = True

    def __post_init__(self):
        if self.heads % self.kv_heads:
            raise ValueError(f"{self.heads} query heads do not divide over "
                             f"{self.kv_heads} key/value heads")
        if self.window is not None and self.window < 1:
            raise ValueError(f"a window of {self.window} keys")

    def init(self, key, in_shape: Shape):
        d, wide = in_shape[-1], self.head_dim
        shapes = {"q": (d, self.heads * wide), "k": (d, self.kv_heads * wide),
                  "v": (d, self.kv_heads * wide), "gate": (d, self.heads * wide),
                  "o": (self.heads * wide, d)}
        # (five keys whatever is asked: a leaf's draw is its place among them)
        params = {n: _weight(k, s, s[0], INIT_STD)
                  for (n, s), k in zip(shapes.items(), jax.random.split(key, 5))
                  if self.gated or n != "gate"}
        if self.qk_norm:
            params["q_norm"] = _ones(wide)
            params["k_norm"] = _ones(wide)
        return params, {}, in_shape

    @property
    def rope_dim(self) -> int:
        """RoPE turns all of a head's features (`MLA.rope_dim`)."""
        return self.head_dim

    def core(self, s: int) -> Tuple[str, int]:
        """(`"fused"` | `"blocks"`, the tile's side or the queries a turn)
        for `s` positions: what the shapes allow (`MLA.core`)."""
        t = pallas_attention.causal_tile(s, self.window, self.head_dim)
        return ("blocks", self._q_block(s)) if t is None else ("fused", t)

    def _q_block(self, s: int) -> int:
        return self.q_block if s % self.q_block == 0 else s

    def _spans(self, s: int, step: int) -> List[Tuple[int, int, int]]:
        """(first query, end, first key) of every turn of the plain path."""
        reach = s if self.window is None else self.window
        return [(a, a + step, max(a - reach + 1, 0)) for a in range(0, s, step)]

    def tiles_visited(self, s: int, platform: str) -> Tuple[int, int]:
        """(tiles of the score square one core computes, the tile's side)
        on `platform`: the kernels' schedule, or the plain path's turns
        counted in tiles of a turn's queries."""
        kind, t = self.core(s)
        if kind == "fused" and platform == "tpu":
            return pallas_attention.causal_tiles_visited(s, t, self.window), t
        t = self._q_block(s)
        return sum(-(-(b - lo) // t) for _, b, lo in self._spans(s, t)), t

    def heads_a_step(self, s: int, platform: str) -> int:
        """The query heads of one key/value head a grid step of the kernels
        carries on `platform` (the plain path has no grid: 1)."""
        kind, t = self.core(s)
        if kind == "fused" and platform == "tpu":
            return pallas_attention.heads_a_step(
                self.heads // self.kv_heads, t, self.head_dim, s)
        return 1

    def pairs_computed(self, s: int, platform: str, backward: bool) -> int:
        """The (query, key) pairs one core executes in one direction, one
        (sequence, head), on `platform`: the kernels' whole tiles and the
        sub-squares they keep of a tile the mask's edge crosses, or the
        plain path's turns, each a block of queries by the keys it reads."""
        kind, t = self.core(s)
        if kind == "fused" and platform == "tpu":
            return pallas_attention.pairs_computed(
                pallas_attention.causal_schedule(s, t, self.window), 1, t, backward)
        return sum((b - a) * (b - lo) for a, b, lo in self._spans(s, self._q_block(s)))

    def _blocks(self, q, k, v):
        """`q (N, H, S, D)`, `k, v (N, KV, S, D)` in, `(N, H, S, D)` out: a
        block of queries at a time against the keys it may see, from the
        window's far end of its first query to its last query's own."""
        n, h, s, d = q.shape
        q = q.reshape(n, self.kv_heads, h // self.kv_heads, s, d)
        return jnp.concatenate([
            _attend(q[:, :, :, a:b], k[:, :, lo:b], v[:, :, lo:b], a, lo,
                    self.window, d ** -0.5)
            for a, b, lo in self._spans(s, self._q_block(s))],
            axis=3).reshape(n, h, s, d)

    def apply(self, params, state, x, train: bool = False):
        """Heads ahead of positions throughout, as `MLA.apply`."""
        w = {k: v.astype(x.dtype) for k, v in params.items()}
        s, wide = x.shape[1], self.head_dim
        with jax.named_scope("qkv"):
            q, k, v, *gate = (
                jnp.einsum("nsm,mhd->nhsd", x, w[name].reshape(-1, heads, wide))
                for name, heads in (("q", self.heads), ("k", self.kv_heads),
                                    ("v", self.kv_heads), ("gate", self.heads))
                if name in w)
            q, k = row_major(q), row_major(k)
        if self.qk_norm:
            with jax.named_scope("qk_norm"):
                q = _norm(self.eps, w["q_norm"], q)
                k = _norm(self.eps, w["k_norm"], k)
        if self.rotary:
            with jax.named_scope("rope"):
                q, k = rope(q, self.theta), rope(k, self.theta)
        with jax.named_scope("core"):
            kind, t = self.core(s)
            if kind == "fused":
                out = pallas_attention.grouped_causal_attention(
                    q, k, v, wide ** -0.5, self.window, t, self._blocks)
            else:
                out = checkpoint_name(self._blocks(q, k, v), "attn_core")
        if self.gated:
            with jax.named_scope("gate"):
                out = _gated(out, gate[0])
        with jax.named_scope("o"):
            return jnp.einsum("nhsd,hdm->nsm", out,
                              w["o"].reshape(self.heads, wide, -1)), state


@dataclasses.dataclass(frozen=True)
class SandwichLayer(DecoderLayer):
    """`DecoderLayer` with a second norm a sub-layer, on what the sub-layer
    gives before it joins the residual stream."""

    def init(self, key, in_shape: Shape):
        params, state, out = super().init(key, in_shape)
        params["attn_post_norm"] = _ones(in_shape[-1])
        params["ffn_post_norm"] = _ones(in_shape[-1])
        return params, state, out

    def _post(self, gain, y):
        """What a sub-layer gave, normed before it joins the stream."""
        return _norm(self.eps, gain, y)

    def apply(self, params, state, x, train: bool = False):
        gain = lambda name: params[name].astype(x.dtype)  # noqa: E731
        with jax.named_scope("attn"):
            with jax.named_scope("norm"):
                y = _norm(self.eps, gain("attn_norm"), x)
            y = self.attn.apply(params["attn"], {}, y, train)[0]
            with jax.named_scope("post_norm"):
                h = x + self._post(gain("attn_post_norm"), y)
        with jax.named_scope(self.ffn_scope):
            with jax.named_scope("norm"):
                y = _norm(self.eps, gain("ffn_norm"), h)
            y, state = self.ffn.apply(params["ffn"], state, y, train)
            with jax.named_scope("post_norm"):
                return h + self._post(gain("ffn_post_norm"), y), state


@dataclasses.dataclass(frozen=True)
class ScaledEmbedding(Embedding):
    """`Embedding` whose rows leave times `scale` (the product in float32,
    rounded once)."""

    scale: float = 1.0

    def apply(self, params, state, x, train: bool = False):
        return (params["w"][x] * self.scale).astype(self.dtype), state


@dataclasses.dataclass(frozen=True)
class AfMoe(GlmMoe):
    """The language model (module docstring): `GlmMoe` with no MTP
    module, a layer of its own kind at every depth (`layer_types`; `attn`
    is the sliding layers', a full layer's is the same without the window
    and without RoPE) and the embedding scaled. `in_shape`, `x`, `apply`
    and `loss` as `GlmMoe`."""

    layer_types: Tuple[str, ...] = ()
    embed_scale: float = 1.0

    def __post_init__(self):
        super().__post_init__()
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if self.mtp_modules:
            raise ValueError("afmoe has no multi-token-prediction module")
        if (len(self.layer_types) != self.n_layers
                or set(self.layer_types) - {SLIDING, FULL}):
            raise ValueError(
                f"layer_types {self.layer_types}: one of {SLIDING!r}, "
                f"{FULL!r} for each of the {self.n_layers} layers")
        if self.attn.window is None or not self.attn.rotary:
            raise ValueError("`attn` is the sliding layers' attention: it "
                             "has the window and RoPE")

    def attention(self, kind: str) -> GatedGQA:
        """The attention module of a layer of `kind`: the layer-kind
        table — a window and RoPE in a sliding layer, neither in a full
        one."""
        return (self.attn if kind == SLIDING
                else dataclasses.replace(self.attn, window=None, rotary=False))

    def _embed(self) -> Embedding:
        return ScaledEmbedding(self.vocab, self.hidden, INIT_STD, self.dtype,
                               self.embed_scale)

    def _layers(self) -> List[DecoderLayer]:
        dense = GatedMLP(self.dense_width, INIT_STD)
        return [
            SandwichLayer(self.attention(kind), dense if i < self.first_dense
                          else self.experts, self.eps)
            for i, kind in enumerate(self.layer_types)
        ]

    def describe(self, tokens_per_step: int, seq_len: int,
                 platform: str) -> Dict[str, object]:
        """`GlmMoe.describe` (whose attention tiles are a full layer's) and
        what differs by the layer's kind: per kind, the tiles of one
        (sequence, head)'s score square the core computes and the pairs
        the mask allows."""
        said = super().describe(tokens_per_step, seq_len, platform)
        by_kind = {kind: self.attention(kind) for kind in (SLIDING, FULL)}
        visited = {kind: att.tiles_visited(seq_len, platform)
                   for kind, att in by_kind.items()}
        said.update(
            attention_layer_kinds=list(self.layer_types),
            attention_window=self.attn.window,
            attention_tile=visited[FULL][1],
            attention_heads_a_step=by_kind[FULL].heads_a_step(seq_len, platform),
            attention_heads_a_step_by_kind={
                kind: att.heads_a_step(seq_len, platform)
                for kind, att in by_kind.items()},
            attention_tiles_visited=visited[FULL][0],
            attention_tiles_visited_by_kind={
                kind: got[0] for kind, got in visited.items()},
            attention_pairs_computed=by_kind[FULL].pairs_computed(
                seq_len, platform, True),
            attention_pairs_computed_by_kind={
                kind: att.pairs_computed(seq_len, platform, True)
                for kind, att in by_kind.items()},
            attention_pairs_computed_forward_by_kind={
                kind: att.pairs_computed(seq_len, platform, False)
                for kind, att in by_kind.items()},
            attention_pairs_allowed_by_kind={
                kind: pairs_allowed(seq_len, att.window)
                for kind, att in by_kind.items()})
        return said


def afmoe(
    *,
    vocab_size: int,
    hidden_size: int,
    intermediate_size: int,
    moe_intermediate_size: int,
    num_hidden_layers: int,
    num_dense_layers: int,
    num_attention_heads: int,
    num_key_value_heads: int,
    head_dim: int,
    num_experts: int,
    num_experts_per_tok: int,
    layer_types: Sequence[str],
    sliding_window: int,
    num_shared_experts: int = 1,
    route_scale: float = 1.0,
    route_norm: bool = True,
    score_func: str = "sigmoid",
    load_balance_coeff: float = 1e-3,
    mup_enabled: bool = True,
    rope_theta: float = 1e4,
    rms_norm_eps: float = 1e-5,
    held_experts: Optional[Sequence[int]] = None,
    row_buffer: Optional[int] = None,
    balance_weight: float = 0.0,
    gate_gradient: bool = True,
    dtype: str = "bfloat16",
    q_block: int = 512,
    loss_block: int = 2048,
) -> AfMoe:
    """An `afmoe` decoder by its config.json's keys (`route_norm: true`:
    the gates are the chosen scores over their sum, times `route_scale`;
    `load_balance_coeff` is the selection bias's step, and the loss has
    no balance term unless `balance_weight` gives it one). `held_experts`,
    `row_buffer` and `gate_gradient` as `glm_moe_lite` has them."""
    if not route_norm:
        raise ValueError("the expert layer normalises the chosen scores "
                         "(route_norm: true)")
    held = range(num_experts) if held_experts is None else held_experts
    return AfMoe(
        vocab=vocab_size, hidden=hidden_size, dense_width=intermediate_size,
        n_layers=num_hidden_layers,
        attn=GatedGQA(num_attention_heads, num_key_value_heads, head_dim,
                      sliding_window, True, rope_theta, rms_norm_eps, q_block),
        experts=ExpertLayer(
            moe_intermediate_size, num_experts, num_experts_per_tok,
            tuple(held), num_shared_experts, route_scale, row_buffer,
            load_balance_coeff, balance_weight, gate_gradient, score_func),
        first_dense=num_dense_layers, mtp_modules=0, eps=rms_norm_eps,
        dtype=dtype, loss_block=loss_block, layer_types=tuple(layer_types),
        embed_scale=hidden_size ** 0.5 if mup_enabled else 1.0,
    )


def trinity_mini(
    layer_types: Optional[Sequence[str]] = None,
    num_dense_layers: int = 2,
    vocab_size: int = 200192,
    held_experts: Optional[Sequence[int]] = None,
    row_buffer: Optional[int] = None,
    gate_gradient: bool = True,
    **overrides,
) -> AfMoe:
    """Trinity-Mini at its published widths (26 B parameters whole, 3 B
    active a token): hidden 2,048, 32 query heads over 4 key/value heads
    of 128, a window of 2,048 keys in three layers of four and every key
    in the fourth, 2 dense layers 6,144 wide, then 128 experts 1,024 wide,
    8 a token (gates scaled 2.826), beside 1 shared. The layers kept
    (`layer_types`, default the published 32, and how many of the leading
    ones are dense), the vocabulary's rows and the experts held are the
    caller's cut: one chip of an eight-way expert-parallel group holds
    `held_experts=range(16)` and 25,024 rows."""
    kinds = tuple((SLIDING, SLIDING, SLIDING, FULL) * 8
                  if layer_types is None else layer_types)
    kwargs = dict(
        vocab_size=vocab_size, hidden_size=2048, intermediate_size=6144,
        moe_intermediate_size=1024, num_hidden_layers=len(kinds),
        num_dense_layers=num_dense_layers, num_attention_heads=32,
        num_key_value_heads=4, head_dim=128, num_experts=128,
        num_experts_per_tok=8, layer_types=kinds, sliding_window=2048,
        num_shared_experts=1, route_scale=2.826, route_norm=True,
        score_func="sigmoid", load_balance_coeff=1e-3, mup_enabled=True,
        rope_theta=1e4, rms_norm_eps=1e-5, held_experts=held_experts,
        row_buffer=row_buffer, gate_gradient=gate_gradient,
    )
    kwargs.update(overrides)
    return afmoe(**kwargs)
