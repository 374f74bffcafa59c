"""nn/sdar_moe.py (SDAR-30B-A3B-Chat's mechanisms: the block-diffusion
noise, stream and mask, grouped-query attention with q/k norms, softmax-
routed experts, the weighted loss) at toy widths on the CPU, seeded random
weights, against the plain float32 reference the benchmark keeps
(benchmark/reference/sdar_moe.py): the pieces, the share, the whole
model's loss, gradients, three AdamW steps and held-row counts; the noise
as a pure function of the state; which step factories run the model; and
that GLM-4.7-Flash's step is the program it was."""

import dataclasses
import hashlib
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.reference import sdar_moe as ref  # noqa: E402
from benchmark.tools import compare_sdar_moe  # noqa: E402
from benchmark.tools.compare_reference import leaf_gaps  # noqa: E402
from parallel_cnn_tpu import config as config_lib, plan as plan_lib  # noqa: E402
from parallel_cnn_tpu.nn import glm_moe, sdar_moe  # noqa: E402
from parallel_cnn_tpu.train import zoo  # noqa: E402
from token_family import (HYPER, highest, jitted, loss as loss_of, pulled,  # noqa: E402
                          stepped, steps, system, toy)

L, B, VOCAB = 16, 4, 96
ARCH = {
    "hidden_size": 32, "moe_intermediate_size": 16, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 8, "num_experts_per_tok": 2,
    "num_hidden_layers": 2, "rms_norm_eps": 1e-6, "rope_theta": 1e6,
    "vocab_size": VOCAB, "router_experts": 8, "held_experts": [0, 1, 2],
    "row_buffer": None, "balance_weight": 1e-3, "gate_gradient": True,
    "block_length": B, "noise_eps": 1e-3, "mask_token_id": VOCAB - 1,
}
# float32 on both sides at the highest matmul precision: what differs is the
# order of float32 sums. Seen: 1e-7 on the loss, 1.5e-6 on the worst leaf's
# gradient; every fault below moves 100 x TOL.
TOL = 2e-5


def build(**over):
    arch = dict(ARCH, **over)
    return sdar_moe.sdar_moe(
        vocab_size=arch["vocab_size"], hidden_size=arch["hidden_size"],
        moe_intermediate_size=arch["moe_intermediate_size"],
        num_hidden_layers=arch["num_hidden_layers"],
        num_attention_heads=arch["num_attention_heads"],
        num_key_value_heads=arch["num_key_value_heads"],
        head_dim=arch["head_dim"], num_experts=arch["router_experts"],
        num_experts_per_tok=arch["num_experts_per_tok"],
        rms_norm_eps=arch["rms_norm_eps"], block_length=arch["block_length"],
        held_experts=arch["held_experts"], row_buffer=arch["row_buffer"],
        balance_weight=arch["balance_weight"],
        gate_gradient=arch["gate_gradient"], noise_eps=arch["noise_eps"],
        dtype="float32", q_block=8, loss_block=16), arch


@pytest.fixture(scope="module")
def small():
    """The toy model with every PARAMETER leaf drawn at random (weights of
    std 1 / sqrt(fan_in), gains 1 + 0.1 n); its state as `init` made it."""
    return toy(*build(), seq=L, draw_state=False)


# ------------------------------------------------------------ the mask

def _by_hand(l, b):
    """The three rules, a pair at a time."""
    table = np.zeros((2 * l, 2 * l), bool)
    for i in range(2 * l):
        for j in range(2 * l):
            bi, bj = (i % l) // b, (j % l) // b
            if i < l and j < l:
                table[i, j] = bi == bj
            elif i < l <= j:
                table[i, j] = bj < bi
            elif i >= l and j >= l:
                table[i, j] = bj <= bi
    return table


@pytest.mark.parametrize("which", ["program", "reference"])
def test_the_mask_is_the_three_rules_written_by_hand(which):
    got = sdar_moe.allowed(16, 4) if which == "program" else ref.stream_mask(16, 4)
    want = _by_hand(16, 4)
    np.testing.assert_array_equal(np.asarray(got), want)
    # L (L + B) pairs: B noised and block-start clean keys for a noised
    # query, the clean keys up to its block's end for a clean one
    assert want.sum() == 16 * (16 + 4)
    assert not want[16:, :16].any()  # a clean query never sees a noised key
    assert want[5, 4:8].all() and want[5, 16:20].all() and not want[5, 20:].any()
    # a part of the square, as the blocked path asks for it
    np.testing.assert_array_equal(
        np.asarray(sdar_moe.allowed(16, 4, jnp.arange(8, 12), jnp.arange(12, 30))),
        want[8:12, 12:30])


# ---------------------------------------------------------- the pieces

def _attention(seed=0):
    att = sdar_moe.GQA(4, 2, 8, B, q_block=8)
    shapes, _, _ = att.init(jax.random.key(0), (2 * L, 32))
    p = {n: (jax.random.normal(jax.random.key(seed + i), a.shape)
             * (a.shape[0] ** -0.5 if a.ndim == 2 else 0.1)
             + (1.0 if a.ndim == 1 else 0.0))
         for i, (n, a) in enumerate(sorted(shapes.items()))}
    return att, p, jax.random.normal(jax.random.key(9), (2, 2 * L, 32))


def test_grouped_query_attention_agrees_with_the_reference():
    att, p, x = _attention()
    d_out = jax.random.normal(jax.random.key(10), x.shape)
    got, grads = pulled(lambda p, x: att.apply(p, {}, x)[0], d_out, p, x)
    want, want_grads = pulled(lambda p, x: ref.attention(ARCH, p, x), d_out, p, x)
    assert float(jnp.max(jnp.abs(want))) > 0.1
    np.testing.assert_allclose(got, want, atol=TOL)
    gaps = leaf_gaps(grads, want_grads)
    assert len(gaps) == 7 and max(gaps.values()) < TOL, max(gaps, key=gaps.get)
    assert set(p) == {"q", "k", "v", "o", "q_norm", "k_norm"}
    assert p["k"].shape == (32, 2 * 8) and p["q"].shape == (32, 4 * 8)


def test_what_a_position_may_not_see_does_not_move_it():
    att, p, x = _attention()
    run = jax.jit(lambda x: att.apply(p, {}, x)[0])
    base = highest(run, x)
    # the noised half moved: no clean position moves
    moved = highest(run, x.at[:, :L].add(1.0))
    np.testing.assert_allclose(moved[:, L:], base[:, L:], atol=1e-6)
    assert float(jnp.max(jnp.abs(moved[:, :L] - base[:, :L]))) > 0.01
    # clean block 2 moved: noised blocks 0..2 and clean blocks 0, 1 stay —
    # a noised block never sees its OWN clean block
    at = slice(L + 2 * B, L + 3 * B)
    moved = highest(run, x.at[:, at].add(1.0))
    np.testing.assert_allclose(moved[:, : 3 * B], base[:, : 3 * B], atol=1e-6)
    np.testing.assert_allclose(moved[:, L: L + 2 * B], base[:, L: L + 2 * B],
                               atol=1e-6)
    assert float(jnp.max(jnp.abs(moved[:, 3 * B: L] - base[:, 3 * B: L]))) > 1e-3


def test_both_halves_count_their_positions_from_zero():
    """A clean sequence alone is the stream of it twice: with the same
    tokens in both halves, noised position i and clean position i hold the
    same q and k, which a RoPE running on to 2L - 1 would turn apart."""
    att, p, x = _attention()
    twice = jnp.concatenate([x[:, :L], x[:, :L]], axis=1)
    out = jitted(lambda p, x: att.apply(p, {}, x)[0], p, twice)
    # block 0: noised sees noised block 0, clean sees clean block 0 — the same
    np.testing.assert_allclose(out[:, :B], out[:, L: L + B], atol=1e-5)


def test_the_expert_layer_under_a_softmax_router_agrees_with_the_reference(small):
    layer = small.model.experts
    p, st = small.params["layers"][1]["ffn"], small.state["layers"][1]
    assert set(p) == {"router", "experts"}  # no shared expert, no leaf for one
    x = jax.random.normal(jax.random.key(6), (4, 2 * L, 32))
    got, new = jitted(lambda p, st, x: layer.apply(p, st, x, train=True), p, st, x)
    want, balance, load = jitted(lambda p, x: ref.experts(small.arch, p, x), p, x)
    np.testing.assert_allclose(got, want, atol=TOL)
    np.testing.assert_allclose(new["load"], load)
    assert float(new["balance"]) == pytest.approx(float(balance), rel=1e-5)
    assert float(load.sum()) == 4 * 2 * L * 2 and int(new["overflow_rows"]) == 0
    # the gates of a token sum to one over its chosen, held or not
    ids, gates, _, _ = jitted(lambda r, b, x: layer.route(r, b, x, 4),
                              p["router"], st["bias"], x.reshape(-1, 32))
    np.testing.assert_allclose(gates.sum(axis=1), 1.0, atol=1e-6)
    # and no selection bias ever moves under this router
    done = layer.finish_step(new)
    np.testing.assert_array_equal(done["bias"], jnp.zeros(8))


def test_the_eight_shares_routed_parts_add_up_to_the_uncut_layer():
    """One layer of 16 experts cut eight ways: each share routes over all
    16, normalises the gates over all the chosen, and adds only its own two
    experts' part. The eight parts are what the uncut reference gives."""
    whole = glm_moe.ExpertLayer(width=16, n_routed=16, per_token=4,
                                held=tuple(range(16)), n_shared=0, scaling=1.0,
                                bias_step=0.0, balance=1e-3, scoring="softmax")
    shape, key = (2 * L, 32), jax.random.key(7)
    p, st, _ = whole.init(key, shape)
    x = jax.random.normal(jax.random.key(9), (2, 2 * L, 32)) * 4.0
    arch = dict(ARCH, router_experts=16, num_experts_per_tok=4,
                held_experts=list(range(16)))
    want, _, _ = jitted(lambda p, x: ref.experts(arch, p, x), p, x)
    total = jnp.zeros_like(want)
    for i in range(8):
        share = dataclasses.replace(whole, held=(2 * i, 2 * i + 1))
        sp, _, _ = share.init(key, shape)  # an expert's weights come from its id
        for m in ("gate", "up", "down"):
            np.testing.assert_array_equal(sp["experts"][m], p["experts"][m][2 * i: 2 * i + 2])
        np.testing.assert_array_equal(sp["router"], p["router"])
        total = total + jitted(share.apply, sp, st, x)[0]
    # (at the published init of std 0.02 a 16-wide expert gives 0.04 at most)
    assert float(jnp.max(jnp.abs(want))) > 0.02
    np.testing.assert_allclose(total, want, atol=1e-7)
    uncut, _ = jitted(whole.apply, p, st, x)
    np.testing.assert_allclose(uncut, want, atol=1e-7)


# ------------------------------------------------------------ the noise

def test_the_noise_is_a_pure_function_of_the_key_and_the_forwards_made(small):
    model, noise = small.model, small.state["noise"]
    assert noise["key"].dtype == jnp.uint32 and int(noise["draws"]) == 0
    xt, m, t = model.noise(noise, small.x)
    again = model.noise(jax.tree_util.tree_map(lambda a: a + 0, noise), small.x)
    for a, b in zip((xt, m, t), again):
        np.testing.assert_array_equal(a, b)
    later = model.noise(dict(noise, draws=noise["draws"] + 1), small.x)
    assert not np.array_equal(later[1], m) and not np.array_equal(later[2], t)
    other = model.noise(dict(noise, key=noise["key"] + 1), small.x)
    assert not np.array_equal(other[2], t)
    # one t a block, in [eps, 1); [MASK] exactly where m says
    blocks = np.asarray(t).reshape(4, L // B, B)
    assert (blocks == blocks[..., :1]).all() and len(np.unique(blocks)) == 4 * L // B
    assert 1e-3 <= float(t.min()) and float(t.max()) < 1.0
    np.testing.assert_array_equal(xt, jnp.where(m, VOCAB - 1, small.x))
    # the reference repeats the draws from the state it is handed
    for forward in (0, 3):
        want = ref.noise(small.arch, noise, forward, small.x)
        got = model.noise(dict(noise, draws=noise["draws"] + forward), small.x)
        for a, b in zip(got, want):
            np.testing.assert_array_equal(a, b)


def test_a_block_is_masked_at_its_own_rate():
    model, _ = build()
    _, state, _ = model.init(jax.random.key(0), (4096,))
    x = jnp.zeros((8, 4096), jnp.int32)
    _, m, t = model.noise(state["noise"], x)
    assert abs(float(m.mean()) - float(t.mean())) < 0.01  # E[m] = t
    assert abs(float(t.mean()) - 0.5) < 0.02
    assert abs(float((m / t).mean()) - 1.0) < 0.1  # E[m / t] = 1 a token


def test_the_state_advances_once_a_forward_and_counts_the_masked(small):
    _, _, new = system(small)
    assert int(new["noise"]["draws"]) == 1
    np.testing.assert_array_equal(new["noise"]["key"], small.state["noise"]["key"])
    _, m, _ = small.model.noise(small.state["noise"], small.x)
    assert int(new["noise"]["masked"]) == int(m.sum()) > 0
    done = small.model.finish_step(new)
    assert small.model.counters(done)["bd_masked_tokens"] == int(m.sum())
    # two microbatches: two draws, the second's are not the first's
    state, step = stepped(small, accum=2)
    state, _ = highest(step, state, small.x, small.y)
    assert int(state.model_state["noise"]["draws"]) == 2


def test_the_targets_argument_is_not_read(small):
    assert loss_of(small, y=(small.y + 7) % VOCAB) == loss_of(small)
    assert loss_of(small) == system(small)[0]


# --------------------------------------------------------- the whole model

def test_loss_and_every_leafs_gradient_agree_with_the_reference(small):
    loss, grads, _ = system(small)
    want, want_grads = ref.loss_and_grads(
        small.arch, small.params, small.state, small.x, small.y)
    assert loss == pytest.approx(float(want), rel=TOL)
    gaps = leaf_gaps(grads, want_grads)
    assert len(gaps) == 27 and max(gaps.values()) < TOL, max(gaps, key=gaps.get)
    assert min(float(jnp.linalg.norm(g)) for g in
               jax.tree_util.tree_leaves(want_grads)) > 0  # every leaf is used


def test_the_loss_is_the_weighted_cross_entropy_of_the_noised_half(small):
    """Written once more from the model's own logits: the stream's noised
    half against the clean tokens, masked positions over t, all N L tokens
    in the denominator; plus the layers' balance terms."""
    model = small.model
    xt, m, t = model.noise(small.state["noise"], small.x)
    stream = jnp.concatenate([xt, small.x], axis=1)
    hidden, layers = jitted(lambda p, st, x: model.hidden_states(p, st, x, True),
                            small.params, small.state, stream)
    z = jitted(model._logits, small.params, hidden[-1][:, :L])
    ce = jax.nn.logsumexp(z, -1) - jnp.take_along_axis(
        z, small.x[..., None], -1)[..., 0]
    want = jnp.sum(jnp.where(m, ce / t, 0.0)) / (4 * L) + sum(
        s["balance"] for s in layers)
    assert system(small)[0] == pytest.approx(float(want), rel=1e-5)
    assert all(float(s["balance"]) > 0 for s in layers)


def test_a_share_that_leaves_the_gates_gradient_out_agrees_with_the_reference(small):
    model, arch = build(gate_gradient=False)
    loss, grads, _ = system(small, model)
    whole, whole_grads, _ = system(small)
    assert loss == whole
    want, want_grads = ref.loss_and_grads(
        arch, small.params, small.state, small.x, small.y)
    assert loss == pytest.approx(float(want), rel=TOL)
    gaps = leaf_gaps(grads, want_grads)
    assert len(gaps) == 27 and max(gaps.values()) < TOL, max(gaps, key=gaps.get)
    assert max(leaf_gaps(grads, whole_grads).values()) > 100 * TOL


def test_logits_and_hidden_states_agree_with_the_reference(small):
    want = ref.eval_logits(small.arch, small.params, small.state, small.x)
    got, _ = jitted(small.model.apply, small.params, small.state, small.x)
    assert got.shape == (4, L, VOCAB) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, atol=TOL * float(jnp.max(jnp.abs(want))))
    stream = jnp.concatenate(
        [small.model.noise(small.state["noise"], small.x)[0], small.x], axis=1)
    hidden, _ = jitted(small.model.hidden_states, small.params, small.state,
                       stream)
    for a, b in zip(hidden, ref.hidden_states(
            small.arch, small.params, small.state, stream), strict=True):
        assert a.shape == (4, 2 * L, 32)
        np.testing.assert_allclose(a, b, atol=TOL * float(jnp.max(jnp.abs(b))))


def test_three_steps_losses_held_rows_and_noise_agree_with_the_reference(small):
    want = ref.train_report(small.arch, small.params, small.state, small.x,
                            small.y, steps=3, **HYPER)
    losses, seen, state = steps(small)
    rows = [c["moe_rows_held"] for c in seen]
    masked = [c["bd_masked_tokens"] for c in seen]
    assert losses == pytest.approx(want["losses"], rel=TOL)
    assert rows == want["rows_held"] and masked == want["masked"]
    assert len(set(masked)) == 3  # every step under its own noise
    two = ref.train_losses(small.arch, small.params, small.state, small.x,
                           small.y, steps=2, **HYPER)
    assert two == pytest.approx(want["losses"][:2], rel=1e-6)
    assert sum(seen[-1]["moe_overflow_rows"]) == 0
    assert int(state.model_state["noise"]["draws"]) == 3


FAULTS = [*compare_sdar_moe.FAULTS, "rope_runs_on", "mask_id_off_by_one",
          "sigmoid_for_softmax"]


@pytest.mark.parametrize("fault", FAULTS)
def test_a_fault_in_the_system_fails_the_comparison(small, fault, monkeypatch):
    """The faults the chip's controls plant (benchmark/tools/
    compare_sdar_moe.py:control, the very context the tool uses) and three
    more, each against the reference's loss and gradients."""
    want, want_grads = ref.loss_and_grads(
        small.arch, small.params, small.state, small.x, small.y)
    if fault in compare_sdar_moe.FAULTS:
        with _planted(fault):
            loss, grads, _ = system(small, fresh=True)
    else:
        if fault == "rope_runs_on":
            monkeypatch.setattr(
                sdar_moe, "rope", lambda x, theta: glm_moe.rope(
                    x.reshape(*x.shape[:2], -1, x.shape[-1]), theta).reshape(x.shape))
        elif fault == "mask_id_off_by_one":
            monkeypatch.setattr(sdar_moe.SdarMoe, "mask_id",
                                property(lambda self: self.vocab - 2))
        elif fault == "sigmoid_for_softmax":
            monkeypatch.setattr(jax.nn, "softmax",
                                lambda a, axis=-1: jax.nn.sigmoid(a)
                                if a.shape[-1] == 8 else _softmax(a, axis))
        loss, grads, _ = system(small, fresh=True)
    loss_gap = abs(loss / float(want) - 1)
    grad_gap = max(leaf_gaps(grads, want_grads).values())
    assert max(loss_gap, grad_gap) > 100 * TOL, (loss_gap, grad_gap)


_softmax = jax.nn.softmax


def _planted(fault):
    """`compare_sdar_moe.control` for a model the test has already built:
    the patches are the module's, the model it yields is not used."""
    cfg = {"factory": {"module": "parallel_cnn_tpu.nn.sdar_moe",
                       "name": "sdar_30b_a3b",
                       "kwargs": {"num_hidden_layers": 1, "vocab_size": 8}}}
    return compare_sdar_moe.control(cfg, ref, fault)


def test_the_control_puts_everything_back(small):
    before = system(small, fresh=True)[0]
    for fault in compare_sdar_moe.FAULTS:
        with _planted(fault):
            pass
    assert system(small, fresh=True)[0] == before


def test_a_float8_reference_fails_the_comparison(small):
    want = ref.train_losses(small.arch, small.params, small.state, small.x,
                            small.y, steps=2, **HYPER)
    with _planted("float8_e4m3fn"):
        low = ref.train_losses(small.arch, small.params, small.state, small.x,
                               small.y, steps=2, **HYPER)
    assert max(abs(a / b - 1) for a, b in zip(low, want)) > 100 * TOL
    again = ref.train_losses(small.arch, small.params, small.state, small.x,
                             small.y, steps=2, **HYPER)
    assert again == want


# ------------------------------------------------- bf16, the step factories

def test_bfloat16_activations_change_rounding_only(small):
    loss, _, _ = system(small)
    half = dataclasses.replace(small.model, dtype="bfloat16")
    loss16, grads16, _ = system(small, half)
    assert abs(loss16 / loss - 1) < 1e-2
    assert all(g.dtype == jnp.float32 for g in jax.tree_util.tree_leaves(grads16))


def test_the_published_model_has_the_counted_parameters():
    model = sdar_moe.sdar_30b_a3b(num_hidden_layers=6, vocab_size=18992,
                                  held_experts=range(16), row_buffer=65536,
                                  gate_gradient=False)
    params, state = jax.eval_shape(
        lambda k: model.init(k, (4096,))[:2], jax.random.key(0))
    count = lambda t: sum(l.size for l in jax.tree_util.tree_leaves(t))  # noqa: E731
    attn = 2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048
    assert count(params["layers"][0]["attn"]) == attn + 2 * 128 == 18_874_624
    assert count(params["layers"][0]["ffn"]["experts"]) == 16 * 3 * 2048 * 768
    assert count(params["layers"][3]) == 94_638_336  # 16 of 128 experts
    assert count(params) == 645_623_296
    assert 16 * 645_623_296 / 1e9 == pytest.approx(10.33, abs=0.01)  # GB
    said = dict(attention_tiles_visited=80, attention_tiles_total=256,
                attention_tile=512, attention_pairs_allowed=4096 * 4100,
                block_length=4, experts_held=16, experts_published=128,
                experts_per_token=8, expert_layers=6, row_buffer=65536,
                tokens_per_step=16384, stream_rows_per_step=32768)
    # each half's 4,096 positions of 128-wide heads tile: the kernel turns them,
    # and its backward computes 68 tile areas of its 80 steps (PR 49: 56 whole,
    # 8 SAME tiles 4 sub-squares of 16, 16 triangular ones 10), its forward 74
    # (the triangular ones whole); the plain path whole turns
    # a grid step of the kernels carries the 8 query heads of a key/value head
    assert model.describe(4 * 4096, 4096, "tpu") == dict(
        said, attention_core="fused", rope_turn="kernel", attention_heads_a_step=8,
        attention_pairs_computed=68 * 512 * 512,
        attention_pairs_computed_forward=74 * 512 * 512)
    assert model.describe(4 * 4096, 4096, "cpu") == dict(
        said, attention_core="blocks", rope_turn="plain", attention_heads_a_step=1,
        attention_pairs_computed=80 * 512 * 512,
        attention_pairs_computed_forward=80 * 512 * 512)
    assert 68 * 512 * 512 / (4096 * 4100) == pytest.approx(1.0615, abs=1e-4)
    assert 80 * 512 * 512 / (4096 * 4100) == pytest.approx(1.249, abs=1e-3)
    whole = sdar_moe.sdar_30b_a3b()
    assert (whole.n_layers, whole.vocab, len(whole.experts.held)) == (48, 151936, 128)
    assert whole.experts.scoring == "softmax" and whole.experts.n_shared == 0
    with pytest.raises(ValueError, match="whole blocks"):
        whole.init(jax.random.key(0), (4098,))
    with pytest.raises(ValueError, match="do not divide"):
        sdar_moe.GQA(heads=32, kv_heads=5)
    with pytest.raises(ValueError, match="sigmoid or softmax"):
        glm_moe.ExpertLayer(scoring="tanh")


@pytest.mark.parametrize("factory", ["comm_psum", "comm_ring", "fused_update",
                                     "zero3", "pipeline"])
def test_the_other_step_factories_refuse_the_model_by_name(host_devices, factory):
    """By the two refusals they have: the model keeps per-step counts
    (`finish_step`) and a key (`has_random_state`)."""
    model, _ = build()
    _, state, _ = model.init(jax.random.key(0), (L,))
    from parallel_cnn_tpu.nn.layers import has_random_state

    assert has_random_state(state)
    opt = zoo.make_optimizer(**HYPER)
    mesh = plan_lib.ExecutionPlan(data=2).validate().make_mesh(
        devices=host_devices[:2])
    fused = config_lib.FusedStepConfig(update=True)
    comm = config_lib.CommConfig(impl="ring")
    with pytest.raises((zoo.StepStateUnsupported, zoo.RandomLayerUnsupported),
                       match="SdarMoe|random in training"):
        if factory.startswith("comm"):
            zoo.make_train_step(model, opt, 1, mesh, comm=config_lib.CommConfig(
                impl=factory.split("_")[1]))
        elif factory == "fused_update":
            zoo.make_fused_train_step(
                model, lr=0.1, momentum=0.9, accum_steps=1, mesh=mesh,
                augment=None, comm=comm, fused=fused, n_buckets=1)
        elif factory == "zero3":
            zoo.make_zero3_train_step(
                model, lr=0.1, momentum=0.9, accum_steps=1, mesh=mesh,
                augment=None, comm=comm, fused=fused, plan=None)
        else:
            from parallel_cnn_tpu.train.pipeline_schedule import make_pipeline_step

            make_pipeline_step(model, opt, accum_steps=2, mesh=mesh,
                               pipeline=config_lib.PipelineConfig(stages=2),
                               in_shape=(L,))


def test_the_gspmd_step_runs_the_model_on_a_mesh_and_zoo_train_records_it(
        host_devices):
    model, _ = build()
    mesh = plan_lib.ExecutionPlan(data=2).validate().make_mesh(
        devices=host_devices[:2])
    tokens = np.asarray(jax.random.randint(jax.random.key(3), (8, L), 0, VOCAB))

    class Rec:
        epochs = []

        def record(self, **rec):
            self.epochs.append(rec)

    from parallel_cnn_tpu import obs as obs_lib

    class Journal:
        enabled = True
        events = []

        def emit(self, kind, **fields):
            self.events.append((kind, fields))

        def flush(self):
            pass

    obs = obs_lib.Obs(obs_lib.Tracer(), obs_lib.MetricsRegistry(), Journal(),
                      enabled=True)
    state, losses = zoo.train(
        model, tokens, tokens, in_shape=(L,), epochs=2, batch_size=4, mesh=mesh,
        **HYPER, seed=3, verbose=False, metrics=Rec(), obs=obs)
    assert all(math.isfinite(v) for v in losses)
    last = Rec.epochs[-1]
    assert len(last["moe_rows_held"]) == 2 and sum(last["moe_overflow_rows"]) == 0
    assert all(m >= 1.0 for m in last["moe_load_max_over_mean"])
    assert 0 < last["bd_masked_tokens"] < 4 * L
    assert int(state.model_state["noise"]["draws"]) == 4  # two steps an epoch
    (event,) = [f for k, f in Journal.events if k == "zoo_moe"]
    assert (event["experts_held"], event["experts_published"],
            event["tokens_per_step"], event["stream_rows_per_step"],
            event["row_buffer"]) == (3, 8, 4 * L, 8 * L, 8 * L * 2)
    # 16 clean tokens in turns of 8 queries, on the CPU: 2 a half, 8 of 16
    assert (event["attention_core"], event["attention_tiles_visited"],
            event["attention_tiles_total"], event["attention_pairs_allowed"],
            event["block_length"]) == ("blocks", 8, 16, L * (L + B), B)
    assert event["rope_turn"] == "plain"  # a CPU, and a toy head besides


def test_the_scopes_are_the_ones_the_catalog_reads():
    import re

    from parallel_cnn_tpu.obs import programs

    model, _ = build()
    opt = zoo.make_optimizer(**HYPER)
    state = jax.eval_shape(lambda k: zoo.init_state(model, k, (L,), opt),
                           jax.random.key(0))
    x = jax.ShapeDtypeStruct((4, L), jnp.int32)
    text = zoo.make_train_step(model, opt, 1, None).lower(state, x, x).as_text(
        debug_info=True)
    scopes = {programs.scope_of(name)[0]
              for name in re.findall(r'loc\("([^"]*)"', text)}
    for want in ("noise", "embed", "l0/attn/norm", "l0/attn/qkv",
                 "l0/attn/qk_norm", "l0/attn/rope", "l0/attn/core", "l0/attn/o",
                 "l1/moe/norm", "l1/moe/route", "l1/moe/dispatch",
                 "l1/moe/experts", "l1/moe/combine", "norm", "head", "loss",
                 "optimizer"):
        assert want in scopes, (want, sorted(scopes))
    assert not any("shared" in s or "mtp" in s or "mlp" in s for s in scopes)
    of = programs.scope_of
    assert of("jit(step)/grad/jvp(l1)/attn/core/cond/branch_0_fun/"
              "block_diffusion_attention_fwd/pallas_call") == ("l1/attn/core", "fwd")
    assert of("jit(step)/grad/transpose(jvp(l1))/grad/jvp(l1)/checkpoint/attn/core/"
              "cond/branch_0_fun/block_diffusion_attention_bwd/pallas_call") == (
        "l1/attn/core", "bwd")


# --------------------------- what was there lowers to the program it was

# sha256 of GLM-4.7-Flash's toy step, `make_train_step(...).lower(...)
# .as_text()` (tests/test_convnext.py's LOWERED, for the one model whose
# module PR 34 edited without meaning to move its program). Read by this
# very function; moved by design by PR 35 (from edcf1db8..., PR 34's
# parent and PR 34 alike): the expert layer's plan is counted and kept
# through the layer's rematerialisation under the name "moe_plan", and
# the way back out of the buffer has a backward rule of its own
# (`glm_moe._combine`). A PR that does not touch the expert layer, the
# attention or the loss leaves it where PR 35 put it. Moved by design
# again by PR 37 (from 5af67e9f...): the sum of a token's rows out of the
# buffer, `_combine` forward and the dispatch's backward, is made from
# the buffer's rows (at these widths a float32 scatter-add), no longer
# from a gather of every assignment slot, and the layers' state has one
# more counter.
GLM_LOWERED = "236a7bdc9a325c4474534d8afdbffb657c307bfd1afa00f7ad17417c4c2e916c"


def test_the_lowered_step_of_the_glm_model_is_unchanged():
    model = glm_moe.glm_moe_lite(
        vocab_size=96, hidden_size=32, intermediate_size=64,
        moe_intermediate_size=16, num_hidden_layers=3, num_attention_heads=2,
        q_lora_rank=12, kv_lora_rank=8, qk_nope_head_dim=6, qk_rope_head_dim=4,
        v_head_dim=8, n_routed_experts=8, num_experts_per_tok=2,
        routed_scaling_factor=1.8, held_experts=[0, 1, 2], q_block=8,
        loss_block=16, gate_gradient=False)
    opt = zoo.make_optimizer(**HYPER)
    state = jax.eval_shape(lambda k: zoo.init_state(model, k, (16,), opt),
                           jax.random.key(0))
    x = jax.ShapeDtypeStruct((4, 16), jnp.int32)
    text = zoo.make_train_step(model, opt, 1, None).lower(state, x, x).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == GLM_LOWERED


# sha256 of this file's own toy step (`build()`, four sequences of L tokens),
# lowered the same way: pinned by PR 43, whose new family added arguments to
# `ExpertLayer` (a group limit, two clamps) and put `layers.gated_unit`
# into the experts block that this model runs; read on PR 43's parent and
# on its change, the same text. Moves with the expert layer, the grouped
# attention, the noise or the loss, as GLM_LOWERED does with its own.
SDAR_LOWERED = "9bb46a24da9804125f52158f24c6ef2cacd2fab84b265ad3b86d2a3e61f899a1"


def test_the_lowered_step_of_this_model_is_unchanged():
    model, _ = build()
    opt = zoo.make_optimizer(**HYPER)
    state = jax.eval_shape(lambda k: zoo.init_state(model, k, (L,), opt),
                           jax.random.key(0))
    x = jax.ShapeDtypeStruct((4, L), jnp.int32)
    text = zoo.make_train_step(model, opt, 1, None).lower(state, x, x).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == SDAR_LOWERED
