"""SDAR-30B-A3B-Chat (`model_type: sdar_moe`; config.json at
huggingface.co/JetLM/SDAR-30B-A3B-Chat) — the zoo's block-diffusion
language model (SDAR arXiv:2510.06303; the objective is BD3-LM's,
arXiv:2503.09573): a Qwen3-MoE decoder — grouped-query attention with an
RMSNorm over each head's features of q and k, every layer 128 experts
under a softmax router, 8 a token, no shared expert — trained to fill in
masked tokens a block at a time.

    x^0    tokens (N, L), K = L / B blocks of B
    noise  t_b ~ U(eps, 1) a block; token i of block b becomes [MASK]
           with probability t_b, each on its own: x^t, mask m
    stream [x^t ; x^0] (N, 2L) at positions [0..L-1 ; 0..L-1]
    mask   beta(i) = position(i) // B. Query i sees key j iff both are
           noised and beta(i) = beta(j); or i is noised, j clean and
           beta(j) < beta(i); or both are clean and beta(j) <= beta(i).
    layer  h = x + Attn(RMSNorm(x));  y = h + Experts(RMSNorm(h))
    Attn   q = x W_q -> H heads, k, v = x W_k, x W_v -> KV heads;
           q, k <- RMSNorm over the head's features (gains g_q, g_k);
           RoPE by position; query head a reads key/value head
           a // (H / KV); softmax(q k^T / sqrt(D) + mask) v; W_o
    Expert s = softmax(x W_r) over all experts in float32, the k largest,
           g = s_chosen / sum s_chosen, y = sum_held g_i E_i(x)
           (nn/glm_moe.py:ExpertLayer, which also counts and balances)
    loss   = 1 / (N L) sum_{n,i} m / t_beta(i) * CE(z_i, x^0_i) over the
           NOISED half's logits, position i against token i (no shift),
           + the layers' balance terms

The noise key lives in the model state (`state["noise"]`, as
nn/layers.py:DropPath keeps its own): `key` never changes, `draws` counts
the training forwards made, and a forward's draws are a pure function of
`(key, draws, shapes)` — `noise`. So the step stays `(state, x, y)`, a
checkpoint carries the stream, and benchmark/reference/sdar_moe.py repeats
the draws from the state it is handed. `masked` is the last forward's
count of masked tokens.

The attention core is the fused kernels of ops/pallas_attention.py
(`block_diffusion_attention`) where the shapes tile and the step is
lowered for a TPU, else `GQA._blocks`: the same mask in plain XLA, a
block of queries at a time against the keys they may see.

What nn/glm_moe.py has is used as it is: `GlmMoe` (embedding, decoder
layers and their rematerialisation, final norm, head, the blocked
cross-entropy, `finish_step`, `counters`), `DecoderLayer`, `ExpertLayer`.

Scopes: `noise`, `embed`, `l<i>/attn/{norm,qkv,qk_norm,rope,core,o}`,
`l<i>/moe/{norm,route,dispatch,experts,combine}`, `norm`, `head`, `loss`.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from parallel_cnn_tpu.nn.core import Module, Shape
from parallel_cnn_tpu.nn.glm_moe import (
    INIT_STD,
    ExpertLayer,
    GlmMoe,
    _norm,
    _ones,
)
from parallel_cnn_tpu.nn.layers import _weight, rope, row_major
from parallel_cnn_tpu.ops import pallas_attention, pallas_rope

NOISE_EPS = 1e-3


def allowed(l: int, block: int, queries=None, keys=None):
    """The block-diffusion mask (module docstring) between stream
    positions `queries` and `keys` (default: all `2 l`), bool
    (queries, keys)."""
    every = jnp.arange(2 * l)
    q = every if queries is None else queries
    k = every if keys is None else keys
    q_clean, k_clean = (q >= l)[:, None], (k >= l)[None, :]
    qb, kb = ((q % l) // block)[:, None], ((k % l) // block)[None, :]
    return jnp.where(q_clean, k_clean & (kb <= qb),
                     jnp.where(k_clean, kb < qb, kb == qb))


@functools.partial(jax.checkpoint, static_argnums=(5, 6, 7))
def _attend(q, k, v, q_at, k_at, l: int, block: int, scale: float):
    """Stream positions `q_at` of `q (N, KV, G, q, D)` against positions
    `k_at` of `k, v (N, KV, k, D)`: scores and softmax in float32.
    Rematerialised: the backward recomputes the block's scores."""
    s = jnp.einsum("ncgqd,nckd->ncgqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(allowed(l, block, q_at, k_at), s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("ncgqk,nckd->ncgqd", p.astype(v.dtype), v)


@functools.partial(jax.checkpoint, static_argnums=(4,))
def _attend_chosen(q, k, v, bias, scale: float):
    """`q (N, KV, G, q, D)` against `k, v (N, KV, k, D)` under `bias (N, q,
    k)` (0 where a pair is allowed, a large negative number elsewhere; one
    for all heads): (out, the rows' log-sum-exp), scores and softmax in
    float32. Rematerialised, as `_attend`."""
    s = jnp.einsum("ncgqd,nckd->ncgqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    s = s + bias.astype(jnp.float32)[:, None, None]
    lse = jax.nn.logsumexp(s, axis=-1)
    p = jnp.exp(s - lse[..., None])
    return jnp.einsum("ncgqk,nckd->ncgqd", p.astype(v.dtype), v), lse


@dataclasses.dataclass(frozen=True)
class GQA(Module):
    """Grouped-query attention in its training form: `kv_heads` key/value
    heads, each read by `heads / kv_heads` query heads; an RMSNorm over
    each head's features of q and of k before RoPE. What a query may see
    is one of two things. Without `select`: the two-copy stream `(N, 2L,
    d)` of a block-diffusion model under the block-diffusion mask of blocks
    of `block` (a clean sequence alone is the stream of it twice:
    `SdarMoe.apply`). With `select` (nn/keye_vl.py:Indexer, a module of its
    own parameters under `params["indexer"]`): ONE copy of the sequence,
    each query over the keys up to its own that `select` chose for it —
    the choice is data, made from the layer's input with no gradient to or
    from the trunk — and `apply` hands back, as the layer's state, what the
    selection has to say (`Indexer.report`: its own loss term, which is the
    one thing here that carries a gradient to it, and two counters).
    `positions` (ops/pallas_rope.py:Axes, static): where RoPE's pairs take
    their positions from when it is not 0..S-1."""

    heads: int = 32
    kv_heads: int = 4
    head_dim: int = 128
    block: int = 4
    theta: float = 1e6
    eps: float = 1e-6
    q_block: int = 512
    select: Optional[Module] = None
    positions: Optional[pallas_rope.Axes] = None

    def __post_init__(self):
        if self.heads % self.kv_heads:
            raise ValueError(f"{self.heads} query heads do not divide over "
                             f"{self.kv_heads} key/value heads")

    def init(self, key, in_shape: Shape):
        d, wide = in_shape[-1], self.head_dim
        shapes = {"q": (d, self.heads * wide), "k": (d, self.kv_heads * wide),
                  "v": (d, self.kv_heads * wide), "o": (self.heads * wide, d)}
        params = {n: _weight(k, s, s[0], INIT_STD)
                  for (n, s), k in zip(shapes.items(), jax.random.split(key, 4))}
        params["q_norm"] = _ones(wide)
        params["k_norm"] = _ones(wide)
        if self.select is None:
            return params, {}, in_shape
        # (a fold of the key: the four leaves above keep their draws)
        params["indexer"], state, _ = self.select.init(
            jax.random.fold_in(key, 4), in_shape)
        return params, state, in_shape

    @property
    def rope_dim(self) -> int:
        """RoPE turns all of a head's features (`MLA.rope_dim`)."""
        return self.head_dim

    def core(self, l: int) -> Tuple[str, int]:
        """(`"fused"` | `"blocks"`, the tile's side) for a stream of `2 l`
        positions (under `select`: a sequence of `l`): what the shapes
        allow (`MLA.core`)."""
        if self.select is not None:
            t = pallas_attention.causal_tile(l, None, self.head_dim)
            return ("blocks", min(self.q_block, l)) if t is None else ("fused", t)
        t = pallas_attention.bd_tile(l, self.block, self.head_dim)
        return ("blocks", self._q_block(l)) if t is None else ("fused", t)

    def heads_a_step(self, l: int, platform: str) -> int:
        """The query heads of one key/value head a grid step of the kernels
        carries for a stream of `2 l` positions on `platform` (the plain
        path has no grid: 1)."""
        kind, t = self.core(l)
        if kind == "fused" and platform == "tpu":
            return pallas_attention.heads_a_step(
                self.heads // self.kv_heads, t, self.head_dim,
                l if self.select else 2 * l, data=self.select is not None)
        return 1

    def _q_block(self, l: int) -> int:
        """Queries a turn of the plain path: whole blocks, within a half."""
        whole = self.q_block - self.q_block % self.block
        return whole if whole and l % whole == 0 else l

    def _blocks(self, q, k, v):
        """`q (N, H, 2L, D)`, `k, v (N, KV, 2L, D)` in, `(N, H, 2L, D)`
        out: a block of queries at a time against the keys it may see —
        noised queries their own noised keys and the clean ones before
        their block's end, clean queries the clean ones up to it."""
        n, h, s, d = q.shape
        l, step = s // 2, self._q_block(s // 2)
        q = q.reshape(n, self.kv_heads, h // self.kv_heads, s, d)
        at = jnp.arange(s)
        out = []
        for a in range(0, s, step):
            b = a + step
            spans = [(l, b)] if a >= l else [(a, b), (l, l + b)]

            def seen(x, axis, spans=spans):
                return jnp.concatenate(
                    [lax.slice_in_dim(x, lo, hi, axis=axis) for lo, hi in spans],
                    axis)

            out.append(_attend(q[:, :, :, a:b], seen(k, 2), seen(v, 2), at[a:b],
                               seen(at, 0), l, self.block, d ** -0.5))
        return jnp.concatenate(out, axis=3).reshape(n, h, s, d)

    def _chosen(self, q, k, v, bias):
        """(`(N, H, S, D)`, the rows' log-sum-exp `(N, H, S)` float32) of `q
        (N, H, S, D)`, `k, v (N, KV, S, D)` under `bias (N, S, S)`: a block
        of queries at a time against the keys up to its last."""
        n, h, s, d = q.shape
        step = s if s % self.q_block else self.q_block
        q = q.reshape(n, self.kv_heads, h // self.kv_heads, s, d)
        out, lse = zip(*(
            _attend_chosen(q[:, :, :, a:a + step], k[:, :, :a + step],
                           v[:, :, :a + step], bias[:, a:a + step, :a + step],
                           d ** -0.5) for a in range(0, s, step)))
        return (jnp.concatenate(out, axis=3).reshape(n, h, s, d),
                jnp.concatenate(lse, axis=3).reshape(n, h, s))

    def _over_blocks(self, q, k, v):
        """What follows the norms of q and k under the block-diffusion mask:
        the heads' outputs `(N, H, 2L, D)`."""
        n, _, s, wide = q.shape
        l = s // 2
        with jax.named_scope("rope"):
            # both halves count their positions from 0
            q, k = (rope(a.reshape(n, -1, 2, l, wide), self.theta
                         ).reshape(a.shape) for a in (q, k))
        with jax.named_scope("core"):
            kind, t = self.core(l)
            if kind == "fused":
                return pallas_attention.block_diffusion_attention(
                    q, k, v, wide ** -0.5, l, self.block, t, self._blocks)
            return checkpoint_name(self._blocks(q, k, v), "attn_core")

    def _over_chosen(self, params, x, q, k, v):
        """What follows the norms of q and k under `select`: (the heads'
        outputs `(N, H, S, D)`, the selection's report)."""
        s, wide = x.shape[1], self.head_dim
        with jax.named_scope("rope"):
            q = rope(q, self.theta, self.positions)
            k = rope(k, self.theta, self.positions)
        with jax.named_scope("indexer"):
            index = self.select.project(
                params["indexer"], lax.stop_gradient(x), self.positions)
            kind, t = self.core(s)
            bias, counts = self.select.choose(index, t)
        with jax.named_scope("core"):
            if kind == "fused":
                out, lse = pallas_attention.selected_attention(
                    q, k, v, bias, wide ** -0.5, t, self._chosen)
            else:
                out, lse = (checkpoint_name(a, "attn_core")
                            for a in self._chosen(q, k, v, bias))
        with jax.named_scope("indexer"):
            report = self.select.report(index, q, k, lse, bias, wide ** -0.5, counts)
        return out, report

    def apply(self, params, state, x, train: bool = False):
        """Heads ahead of positions throughout, as `MLA.apply`."""
        w = {k: v.astype(x.dtype) for k, v in params.items() if k != "indexer"}
        n, s, _ = x.shape
        l, wide = s // 2, self.head_dim
        with jax.named_scope("qkv"):
            q, k, v = (
                jnp.einsum("nsm,mhd->nhsd", x, w[name].reshape(-1, heads, wide))
                for name, heads in (("q", self.heads), ("k", self.kv_heads),
                                    ("v", self.kv_heads)))
            q, k = row_major(q), row_major(k)
        with jax.named_scope("qk_norm"):
            q = _norm(self.eps, w["q_norm"], q)
            k = _norm(self.eps, w["k_norm"], k)
        if self.select is not None:
            out, state = self._over_chosen(params, x, q, k, v)
        else:
            out = self._over_blocks(q, k, v)
        with jax.named_scope("o"):
            return jnp.einsum("nhsd,hdm->nsm", out,
                              w["o"].reshape(self.heads, wide, -1)), state


@dataclasses.dataclass(frozen=True)
class SdarMoe(GlmMoe):
    """The block-diffusion model (module docstring): `GlmMoe` with no
    dense layer and no MTP module, `GQA` for its attention, and a loss
    and a state of its own. `in_shape` is `(L,)`; `x` is clean token ids
    `(N, L)`, the last id of the vocabulary being `[MASK]`; `apply`
    returns float32 logits `(N, L, vocab)` of a sequence nothing is
    masked in (small sizes only); `loss(params, state, x, y)` does not
    read `y`."""

    noise_eps: float = NOISE_EPS

    def __post_init__(self):
        super().__post_init__()
        if self.first_dense or self.mtp_modules:
            raise ValueError("every sdar_moe layer is sparse and the model "
                             "has no multi-token-prediction module")

    @property
    def mask_id(self) -> int:
        return self.vocab - 1

    def init(self, key, in_shape: Shape):
        if in_shape[-1] % self.attn.block:
            raise ValueError(f"{in_shape[-1]} tokens are no whole blocks of "
                             f"{self.attn.block}")
        nkey, key = jax.random.split(key)
        params, state, out = super().init(key, (2 * in_shape[-1],))
        state["noise"] = {"key": jax.random.key_data(nkey),
                          "draws": jnp.zeros((), jnp.int32),
                          "masked": jnp.zeros((), jnp.int32)}
        return params, state, (*in_shape, self.vocab)

    def noise(self, noise, x):
        """(x^t, m bool (N, L), t (N, L) float32 — every token's own
        block's) of clean tokens `x` under the state's `noise`."""
        n, l = x.shape
        key = jax.random.fold_in(
            jax.random.wrap_key_data(noise["key"]), noise["draws"])
        tkey, mkey = jax.random.split(key)
        t = jax.random.uniform(tkey, (n, l // self.attn.block), jnp.float32,
                               self.noise_eps, 1.0)
        t = jnp.repeat(t, self.attn.block, axis=1)
        m = jax.random.uniform(mkey, (n, l), jnp.float32) < t
        return jnp.where(m, self.mask_id, x), m, t

    def apply(self, params, state, x, train: bool = False):
        h, layers = self._trunk(params, state, jnp.concatenate([x, x], 1), train)
        return (self._logits(params, h[:, : x.shape[1]]),
                dict(state, layers=layers))

    def loss(self, params, state, x, y):
        """(loss, new state) of a training forward on clean tokens `x`
        (module docstring). `y` is not read: the targets are `x`."""
        n, l = x.shape
        with jax.named_scope("noise"):
            xt, m, t = self.noise(state["noise"], x)
            stream = jnp.concatenate([xt, x], axis=1)
        h, layers = self._trunk(params, state, stream, True)
        total = self._cross_entropy(params, h[:, :l], x, m, 1.0 / t) / (n * l)
        noise = dict(state["noise"], draws=state["noise"]["draws"] + 1,
                     masked=jnp.sum(m, dtype=jnp.int32))
        return (total + sum(st["balance"] for st in layers),
                dict(state, layers=layers, noise=noise))

    def counters(self, state) -> Dict[str, object]:
        return dict(super().counters(state),
                    bd_masked_tokens=int(state["noise"]["masked"]))

    def describe(self, tokens_per_step: int, seq_len: int,
                 platform: str) -> Dict[str, object]:
        """`GlmMoe.describe` of steps of `seq_len` CLEAN tokens a sequence:
        the stream is twice that, the row buffer is of stream rows, and
        the attention tiles are this mask's."""
        att = self.attn
        said = super().describe(2 * tokens_per_step, seq_len, platform)
        t = (att.core(seq_len)[1] if said["attention_core"] == "fused"
             else att._q_block(seq_len))
        # (the plain path's turns are tiles of `t` queries in this count, and
        # whole ones: only the kernels cut a tile a boundary crosses)
        steps = pallas_attention.schedule(seq_len, t)
        forward, backward = (
            pallas_attention.pairs_computed(steps, att.block, t, direction)
            if said["attention_core"] == "fused" else len(steps) * t * t
            for direction in (False, True))
        said.update(
            attention_tiles_visited=len(steps),
            attention_pairs_computed=backward,
            attention_pairs_computed_forward=forward,
            attention_tiles_total=(2 * seq_len // t) ** 2, attention_tile=t,
            attention_heads_a_step=att.heads_a_step(seq_len, platform),
            attention_pairs_allowed=seq_len * (seq_len + att.block),
            block_length=att.block, tokens_per_step=tokens_per_step,
            stream_rows_per_step=2 * tokens_per_step)
        return said


def sdar_moe(
    *,
    vocab_size: int,
    hidden_size: int,
    moe_intermediate_size: int,
    num_hidden_layers: int,
    num_attention_heads: int,
    num_key_value_heads: int,
    head_dim: int,
    num_experts: int,
    num_experts_per_tok: int,
    rope_theta: float = 1e6,
    rms_norm_eps: float = 1e-6,
    block_length: int = 4,
    held_experts: Optional[Sequence[int]] = None,
    row_buffer: Optional[int] = None,
    balance_weight: float = 1e-3,
    gate_gradient: bool = True,
    noise_eps: float = NOISE_EPS,
    dtype: str = "bfloat16",
    q_block: int = 512,
    loss_block: int = 2048,
) -> SdarMoe:
    """An `sdar_moe` decoder by its config.json's keys (`norm_topk_prob:
    true`, `mlp_only_layers: []`, `decoder_sparse_step: 1`: every layer
    sparse, gates renormalised over the chosen). `held_experts`,
    `row_buffer` (of STREAM rows' assignments) and `gate_gradient` as
    `glm_moe_lite` has them."""
    held = range(num_experts) if held_experts is None else held_experts
    return SdarMoe(
        vocab=vocab_size, hidden=hidden_size, dense_width=0,
        n_layers=num_hidden_layers,
        attn=GQA(num_attention_heads, num_key_value_heads, head_dim,
                 block_length, rope_theta, rms_norm_eps, q_block),
        experts=ExpertLayer(
            moe_intermediate_size, num_experts, num_experts_per_tok,
            tuple(held), n_shared=0, scaling=1.0, rows=row_buffer,
            bias_step=0.0, balance=balance_weight, gate_grad=gate_gradient,
            scoring="softmax"),
        first_dense=0, mtp_modules=0, eps=rms_norm_eps, dtype=dtype,
        loss_block=loss_block, noise_eps=noise_eps,
    )


def sdar_30b_a3b(
    num_hidden_layers: int = 48,
    vocab_size: int = 151936,
    held_experts: Optional[Sequence[int]] = None,
    row_buffer: Optional[int] = None,
    block_length: int = 4,
    gate_gradient: bool = True,
    **overrides,
) -> SdarMoe:
    """SDAR-30B-A3B-Chat at its published widths (30 B parameters whole,
    3 B active a token): hidden 2,048, 32 query heads over 4 key/value
    heads of 128, 128 experts 768 wide, 8 a token, no shared expert.
    Depth, the vocabulary's rows and the experts held are the caller's
    cut: one chip of an eight-way expert-parallel group holds
    `held_experts=range(16)` and 18,992 rows. `block_length` is not in
    config.json; 4 is the released models' default."""
    kwargs = dict(
        vocab_size=vocab_size, hidden_size=2048, moe_intermediate_size=768,
        num_hidden_layers=num_hidden_layers, num_attention_heads=32,
        num_key_value_heads=4, head_dim=128, num_experts=128,
        num_experts_per_tok=8, rope_theta=1e6, rms_norm_eps=1e-6,
        block_length=block_length, held_experts=held_experts,
        row_buffer=row_buffer, gate_gradient=gate_gradient,
    )
    kwargs.update(overrides)
    return sdar_moe(**kwargs)
