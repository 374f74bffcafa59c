"""Ouro-2.6B (`model_type: ouro`; config.json at
huggingface.co/ByteDance/Ouro-2.6B; "Scaling Latent Reasoning via Looped
Language Models", arXiv:2510.25741 section 3) — the zoo's looped decoder:
ONE stack of layers run `total_ut_steps` times on the same weights, an
exit after every pass, and a loss that mixes the exits' cross-entropies
by a learned distribution over them.

    h^(0)  = Emb(tokens)                               (no scale)
    pass t = 1..T, the SAME parameters every pass:
      x = h^(t-1)
      layer l = 1..L:  a = x + RMSNorm(Attn_l(RMSNorm(x)))
                       x = a + RMSNorm(MLP_l(RMSNorm(a)))  four norms a layer
      h^(t)  = RMSNorm_f(x)     the final norm closes EVERY pass, and pass
                                t + 1 starts from the normed state
      z^(t)  = h^(t) W_head                            float32, untied
      lam_t  = sigmoid(h^(t) . w_g + b_g)              float32, a position
    Attn   : q, k, v = x W_q, x W_k, x W_v -> H heads each (no bias, no
             norm of q or k, no output gate); RoPE on q and k, every
             layer and pass; softmax(q k^T / sqrt(D) + causal) v; W_o
    MLP    : W_down(silu(x W_gate) * (x W_up))
    exits  : S_0 = 1, S_t = prod_{j <= t} (1 - lam_j);
             p_t = lam_t S_{t-1} for t < T, p_T = S_{T-1}  (sums to 1)
    loss   = mean over positions of
             sum_t p_t CE(z^(t), next token) - beta H(p),
             H(p) = - sum_t p_t ln p_t

A weight is read in T places of one step and its gradient is the sum over
them; the gradient reaches `w_g`, `b_g` and the trunk through `p_t` (a
position's `p_t` is weighed by its own cross-entropies) and the trunk and
the head through every `CE`. `early_exit_threshold` is inference's rule
and no part of training.

What is there is used as it is: `GlmMoe`'s frame (embedding, the layers'
rematerialisation, final norm, head, the blocked cross-entropy — here
handing every position's own loss back, once a pass over the whole head,
so the T sets of logits never exist together, forward or backward),
`afmoe.SandwichLayer` (the four norms), `afmoe.GatedGQA` without its norms
of q and k and without its gate (the fused kernels of
ops/pallas_attention.py under the full-causal schedule where the shapes
tile and the step is lowered for a TPU), `layers.GatedMLP`. The T passes
are ONE traced body, a `lax.scan` over the pass index that emits the body
T times in a row: the step traces L layers, not T x L, and the program is
straight. (As a `while` loop the backward keeps every pass's residuals and
the whole of the weights' gradient sums to its end: at the benchmark's
size 12.7 GB of temporaries where the straight program has 6.6, more than
one v5e holds — compiled for a described chip, PERF.md section 6, PR 48.)

State the step writes without a gradient (`state["loop"]`, read at the end
of an epoch by `counters`): the last forward's mean `p_t`, mean exit step
`sum_t t p_t` and mean entropy.

Scopes: `embed`, `ut/l<i>/attn/{norm,qkv,rope,core,o,post_norm}`,
`ut/l<i>/mlp/{norm,post_norm}` (the MLP's matmuls under `ut/l<i>/mlp`),
`ut/exit/{norm,head,loss,gate}`, `mix`.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, ClassVar, Dict, List

import jax
import jax.numpy as jnp
from jax import lax

from parallel_cnn_tpu.nn.afmoe import GatedGQA, SandwichLayer, pairs_allowed
from parallel_cnn_tpu.nn.core import Shape
from parallel_cnn_tpu.nn.glm_moe import INIT_STD, DecoderLayer, GlmMoe, _norm
from parallel_cnn_tpu.nn.layers import GatedMLP, _weight
from parallel_cnn_tpu.ops import pallas_rope


def exit_distribution(a):
    """(p, ln p) `(T, ...)` float32 of the exit gates' logits `a (T, ...)`,
    `lam = sigmoid(a)`: `p_t = lam_t prod_{j < t} (1 - lam_j)` before the
    last pass and `p_T = prod_{j < T} (1 - lam_j)` (the last gate is not
    read). Products as sums of logs: a gate that closes (lam -> 1) leaves
    the later `ln p` large and negative, never a NaN."""
    a = a.astype(jnp.float32)
    stay = jax.nn.log_sigmoid(-a)            # ln (1 - lam_t)
    before = jnp.cumsum(stay, axis=0) - stay  # ln S_{t-1}
    log_p = jnp.concatenate(
        [(jax.nn.log_sigmoid(a) + before)[:-1], before[-1:]], axis=0)
    return jnp.exp(log_p), log_p


@dataclasses.dataclass(frozen=True)
class Ouro(GlmMoe):
    """The looped language model (module docstring): `GlmMoe`'s frame with
    no expert layer and no MTP module, every layer a `SandwichLayer`
    around `attn` and a `dense_width` MLP, the stack applied `passes`
    times, and a loss and a state of its own. `in_shape`, `x` and `y` as
    `GlmMoe`; `apply` returns the LAST exit's float32 logits (what
    inference gives at `early_exit_threshold: 1`; small sizes only)."""

    passes: int = 4
    entropy_weight: float = 0.05

    # what `zoo.train` calls the journal event that carries `describe`
    setup_event: ClassVar[str] = "zoo_loop"

    def __post_init__(self):
        super().__post_init__()
        if self.passes < 1:
            raise ValueError(f"{self.passes} passes over the stack")
        if (self.experts is not None or self.mtp_modules
                or self.first_dense != self.n_layers):
            raise ValueError("every ouro layer is dense and the model has "
                             "no multi-token-prediction module")

    def _layers(self) -> List[DecoderLayer]:
        mlp = GatedMLP(self.dense_width, INIT_STD)
        return [SandwichLayer(self.attn, mlp, self.eps)] * self.n_layers

    def init(self, key, in_shape: Shape):
        gkey, key = jax.random.split(key)
        params, state, out = super().init(key, in_shape)
        d = self.hidden
        # a Linear(hidden, 1): the weight of rank 2 (decayed), `b_g` = 0
        params["exit_gate"] = {"w": _weight(gkey, (d, 1), d, INIT_STD),
                               "b": jnp.zeros((1,), jnp.float32)}
        state["loop"] = {"exit_p": jnp.zeros((self.passes,), jnp.float32),
                         "exit_step_mean": jnp.zeros((), jnp.float32),
                         "exit_entropy": jnp.zeros((), jnp.float32)}
        return params, state, out

    def _closed(self, params, x):
        """`h^(t)`: the final norm on what the stack gave."""
        with jax.named_scope("norm"):
            return _norm(self.eps, params["norm"].astype(x.dtype), x)

    def _logits(self, params, h):
        """Of a state the final norm has closed (`GlmMoe._logits` norms)."""
        with jax.named_scope("head"):
            return jnp.matmul(h, params["head"].astype(h.dtype),
                              preferred_element_type=jnp.float32)

    def _gate(self, params, h):
        """The exit gate's logit, a float32 a position: `h . w_g + b_g` as
        a float32 sum of products (no matmul unit rounds it)."""
        @jax.checkpoint  # (else the backward keeps `h` again, in float32)
        def logit(gate, h):
            return jnp.sum(h.astype(jnp.float32) * gate["w"][:, 0],
                           axis=-1) + gate["b"][0]

        with jax.named_scope("gate"):
            return logit(params["exit_gate"], h)

    def _passes(self, params, x, train: bool, at_exit: Callable):
        """(`h^(T)`, `at_exit(h^(t))` of every pass stacked along a new
        first axis): the embedding, then `passes` turns of the one stack
        and the final norm, which closes every pass: the next starts from
        the normed state, gradient and all."""
        with jax.named_scope("embed"):
            h = self._embed().apply(params["embed"], {}, x)[0]

        def turn(h, _):
            for i, (layer, p) in enumerate(zip(
                    self._layers(), params["layers"], strict=True)):
                with jax.named_scope(f"l{i}"):
                    h, _ = self._run(layer, p, {}, h, train)
            with jax.named_scope("exit"):
                h = self._closed(params, h)
                return h, at_exit(h)

        with jax.named_scope("ut"):
            return lax.scan(turn, h, None, length=self.passes,
                            unroll=self.passes)

    def hidden_states(self, params, state, x, train: bool = False):
        """(`h^(1)` ... `h^(T)`, the closed state after every pass; the
        state as it came)."""
        _, after = self._passes(params, x, train, lambda h: h)
        return list(after), state["layers"]

    def exits(self, params, state, x):
        """(float32 logits `(T, N, S, vocab)` and gates `lam (T, N, S)` of
        every exit): small sizes only."""
        _, (z, a) = self._passes(
            params, x, False,
            lambda h: (self._logits(params, h), self._gate(params, h)))
        return z, jax.nn.sigmoid(a)

    def apply(self, params, state, x, train: bool = False):
        h, _ = self._passes(params, x, train, lambda h: ())
        return self._logits(params, h), state

    def mixture(self, nll, a):
        """(the loss, its parts `expected` and `entropy` — means over the
        positions — and the mean `p (T,)`) of every exit's per-position
        cross-entropy `nll (T, N, S)` and gate logit `a (T, N, S)`."""
        with jax.named_scope("mix"):
            p, log_p = exit_distribution(a)
            expected = jnp.mean(jnp.sum(p * nll, axis=0))
            entropy = jnp.mean(-jnp.sum(p * log_p, axis=0))
            return (expected - self.entropy_weight * entropy, expected,
                    entropy, jnp.mean(p, axis=(1, 2)))

    def loss_parts(self, params, state, x, y):
        """(loss, {expected, entropy, exit_p}) of a training forward."""
        every = jnp.ones(x.shape, bool)
        _, (nll, a) = self._passes(
            params, x, True,
            lambda h: (self._cross_entropy(params, h, y, every, summed=False),
                       self._gate(params, h)))
        total, expected, entropy, exit_p = self.mixture(nll, a)
        return total, {"expected": expected, "entropy": entropy,
                       "exit_p": exit_p}

    def loss(self, params, state, x, y):
        """(loss, new state) of a training forward: the expected
        cross-entropy under the exit distribution less `entropy_weight`
        times its entropy; the state carries what `counters` reads."""
        total, parts = self.loss_parts(params, state, x, y)
        p = lax.stop_gradient(parts["exit_p"])
        loop = {"exit_p": p,
                "exit_step_mean": jnp.sum(
                    p * jnp.arange(1, self.passes + 1, dtype=jnp.float32)),
                "exit_entropy": lax.stop_gradient(parts["entropy"])}
        return total, dict(state, loop=loop)

    def finish_step(self, state):
        """Nothing to settle: no layer counts a step's tokens. (The model
        still has the method, so the step factories that average the
        model state over shards refuse it as they refuse its siblings:
        `zoo.refuse_step_state`.)"""
        return state

    def counters(self, state) -> Dict[str, object]:
        """`GlmMoe.counters` (no expert layer: every `moe_*` an empty
        list) and the loop's own, of the last training forward."""
        loop = jax.device_get(state["loop"])
        return dict(super().counters(state),
                    loop_exit_p=[float(v) for v in loop["exit_p"]],
                    loop_exit_step_mean=float(loop["exit_step_mean"]),
                    loop_exit_entropy=float(loop["exit_entropy"]))

    def describe(self, tokens_per_step: int, seq_len: int,
                 platform: str) -> Dict[str, object]:
        """What the `zoo_loop` journal event says once at set-up, of steps
        of `seq_len`-token sequences on `platform` (`GlmMoe.describe`'s
        contract): the loop, and what runs the attention core and RoPE's
        turn. The tiles are one core's, one (sequence, head)'s."""
        kind = self.attn.core(seq_len)[0] if platform == "tpu" else "blocks"
        visited, tile = self.attn.tiles_visited(seq_len, platform)
        turn = pallas_rope.tile(seq_len, self.attn.rope_dim)
        return dict(
            passes=self.passes, layers=self.n_layers, exits=self.passes,
            layer_applications_per_token=self.passes * self.n_layers,
            entropy_weight=self.entropy_weight,
            attention_core=kind, attention_tile=tile,
            attention_heads_a_step=self.attn.heads_a_step(seq_len, platform),
            attention_tiles_visited=visited,
            attention_pairs_computed=self.attn.pairs_computed(
                seq_len, platform, True),
            attention_pairs_computed_forward=self.attn.pairs_computed(
                seq_len, platform, False),
            attention_tiles_total=(-(-seq_len // tile)) ** 2,
            attention_pairs_allowed=pairs_allowed(seq_len, None),
            rope_turn="kernel" if turn is not None and platform == "tpu"
            else "plain",
            tokens_per_step=tokens_per_step)


def ouro(
    *,
    vocab_size: int,
    hidden_size: int,
    intermediate_size: int,
    num_hidden_layers: int,
    num_attention_heads: int,
    num_key_value_heads: int,
    head_dim: int,
    total_ut_steps: int = 4,
    rope_theta: float = 1e6,
    rms_norm_eps: float = 1e-6,
    entropy_weight: float = 0.05,
    dtype: str = "bfloat16",
    q_block: int = 512,
    loss_block: int = 2048,
) -> Ouro:
    """An `ouro` decoder by its config.json's keys (`layer_types` all
    `full_attention`, `use_sliding_window: false`, `tie_word_embeddings:
    false`). `entropy_weight` is the loss's beta, which config.json does
    not hold."""
    return Ouro(
        vocab=vocab_size, hidden=hidden_size, dense_width=intermediate_size,
        n_layers=num_hidden_layers,
        attn=GatedGQA(num_attention_heads, num_key_value_heads, head_dim,
                      None, True, rope_theta, rms_norm_eps, q_block,
                      qk_norm=False, gated=False),
        experts=None, first_dense=num_hidden_layers, mtp_modules=0,
        eps=rms_norm_eps, dtype=dtype, loss_block=loss_block,
        passes=total_ut_steps, entropy_weight=entropy_weight,
    )


def ouro_2_6b(num_hidden_layers: int = 48, **overrides) -> Ouro:
    """Ouro-2.6B at its published widths (2.67 B parameters whole, each
    read four times a token): hidden 2,048, 16 heads of 128 over 16
    key/value heads, an MLP 5,632 wide, 49,152 tokens, untied, 4 passes.
    Depth is the caller's cut: one chip of a six-stage pipeline holds
    `num_hidden_layers=8` with the embedding and the exits."""
    kwargs = dict(
        vocab_size=49152, hidden_size=2048, intermediate_size=5632,
        num_hidden_layers=num_hidden_layers, num_attention_heads=16,
        num_key_value_heads=16, head_dim=128, total_ut_steps=4,
        rope_theta=1e6, rms_norm_eps=1e-6,
    )
    kwargs.update(overrides)
    return ouro(**kwargs)
