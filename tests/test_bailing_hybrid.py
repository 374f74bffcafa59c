"""nn/bailing_hybrid.py (Ling-3.0-flash's mechanisms: the delta rule with a
channel-wise decay behind short convolutions in five layers of six, latent
attention without a query latent in the sixth, sigmoid-routed experts
under a group limit, a clamp on the late layers' gated MLPs, an MTP module
that is an MLA layer) at toy widths on the CPU, seeded random weights,
against the plain float32 reference the benchmark keeps
(benchmark/reference/bailing_hybrid.py): the pieces, the share, the
layer-kind table, the whole model's logits, loss, gradients, AdamW steps
and held-row counts; the faults the chip's controls plant; which step
factories run the model; the scopes, the counters and the `zoo_moe`
event."""

import dataclasses
import math
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_pallas_shortconv import interpret

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.reference import bailing_hybrid as ref  # noqa: E402
from benchmark.tools import compare_bailing_hybrid as tool  # noqa: E402
from benchmark.tools.compare_reference import leaf_gaps  # noqa: E402
from parallel_cnn_tpu import config as config_lib, plan as plan_lib  # noqa: E402
from parallel_cnn_tpu.nn import bailing_hybrid as bh, glm_moe, layers  # noqa: E402
from parallel_cnn_tpu.train import zoo  # noqa: E402
from token_family import (HYPER, jitted, logits, loss as loss_of, pulled,  # noqa: E402
                          steps, system, toy)

S, VOCAB, D = 128, 96, 32
LINEAR, FULL = bh.LINEAR, bh.FULL
ARCH = {
    "hidden_size": D, "intermediate_size": 48, "moe_intermediate_size": 16,
    "num_attention_heads": 2, "head_dim": 16, "kv_lora_rank": 8,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "num_experts_per_tok": 4, "n_group": 4, "topk_group": 2,
    "num_shared_experts": 1, "routed_scaling_factor": 2.5,
    "num_hidden_layers": 3, "first_k_dense_replace": 1, "layer_group_size": 3,
    "layer_types": [LINEAR, LINEAR, FULL],
    "expert_swiglu_limit_list": [0, 0, 0],
    "share_expert_swiglu_limit_list": [0, 0, 0],
    "kda_lower_bound": -5.0, "short_conv_kernel_size": 4, "kda_chunk": 64,
    "rms_norm_eps": 1e-6, "rope_theta": 6e6, "bias_update_speed": 1e-3,
    "num_nextn_predict_layers": 0, "mtp_weight": 0.0, "vocab_size": VOCAB,
    "router_experts": 16, "held_experts": [0, 1, 5, 9], "row_buffer": None,
    "balance_weight": 0.0, "gate_gradient": True,
}
# float32 on both sides at the highest matmul precision: what differs is the
# order of float32 sums (the chunked scan's against a position at a time).
# Every fault below moves the loss by 10 x TOL or a gradient by 100 x TOL
# (at toy widths and random weights the loss stays near log(vocabulary)).
TOL = 3e-5


def build(**over):
    arch = dict(ARCH, **over)
    return bh.bailing_hybrid(
        vocab_size=arch["vocab_size"], hidden_size=arch["hidden_size"],
        intermediate_size=arch["intermediate_size"],
        moe_intermediate_size=arch["moe_intermediate_size"],
        num_hidden_layers=arch["num_hidden_layers"],
        num_attention_heads=arch["num_attention_heads"],
        head_dim=arch["head_dim"], kv_lora_rank=arch["kv_lora_rank"],
        qk_nope_head_dim=arch["qk_nope_head_dim"],
        qk_rope_head_dim=arch["qk_rope_head_dim"],
        v_head_dim=arch["v_head_dim"], num_experts=arch["router_experts"],
        num_experts_per_tok=arch["num_experts_per_tok"],
        n_group=arch["n_group"], topk_group=arch["topk_group"],
        layer_types=arch["layer_types"],
        layer_group_size=arch["layer_group_size"],
        first_k_dense_replace=arch["first_k_dense_replace"],
        routed_scaling_factor=arch["routed_scaling_factor"],
        kda_lower_bound=arch["kda_lower_bound"],
        short_conv_kernel_size=arch["short_conv_kernel_size"],
        rope_theta=arch["rope_theta"], rms_norm_eps=arch["rms_norm_eps"],
        num_nextn_predict_layers=arch["num_nextn_predict_layers"],
        mtp_loss_scaling_factor=arch["mtp_weight"],
        expert_swiglu_limit_list=arch["expert_swiglu_limit_list"],
        share_expert_swiglu_limit_list=arch["share_expert_swiglu_limit_list"],
        held_experts=arch["held_experts"], row_buffer=arch["row_buffer"],
        bias_update_speed=arch["bias_update_speed"],
        balance_weight=arch["balance_weight"],
        gate_gradient=arch["gate_gradient"], dtype="float32", q_block=32,
        loss_block=64), arch


def _drawn(model, arch):
    """Every PARAMETER leaf drawn at random (`random_leaves`: weights of std
    1 / sqrt(fan_in), gains 1 + 0.1 n, selection biases 0.01 n); the
    decay's own two leaves back inside their range (A around 0, b around
    -1), so that the log-decay covers (-5, 0) and is pinned at neither end."""
    def inside(path, leaf):
        name = getattr(path[-1], "key", None)
        if name == "a_log":
            return 3.0 * (leaf - 1.0)
        return leaf - 2.0 if name == "f_bias" else leaf

    return toy(model, arch, seq=S, batch=2, adjust=lambda params:
               jax.tree_util.tree_map_with_path(inside, params))


@pytest.fixture(scope="module")
def small():
    s = _drawn(*build())
    s.want_loss = float(ref.loss_and_grads(s.arch, s.params, s.state, s.x, s.y)[0])
    return s


def _close(got, want, tol=TOL):
    np.testing.assert_allclose(got, want, atol=tol * float(jnp.max(jnp.abs(want))))


# ------------------------------------------------------------ the pieces

def test_the_linear_layer_agrees_with_the_reference_and_its_decay_is_inside_the_bound(small):
    att = small.model.attention(LINEAR)
    p = small.params["layers"][0]["attn"]
    assert set(p) == {"q", "k", "v", "f", "o", "beta", "gate", "q_conv", "k_conv",
                      "v_conv", "a_log", "f_bias", "o_norm"}
    x = jax.random.normal(jax.random.key(5), (2, S, D))
    got, state = jitted(lambda p, x: att.apply(p, {}, x), p, x)
    assert state == {}
    _close(got, jitted(lambda p, x: ref.linear_attention(small.arch, p, x), p, x))
    g = jitted(lambda p, x: ref.log_decay(small.arch, p, x), p, x)
    z = jnp.einsum("nsm,mhd->nhsd", x, p["f"].reshape(D, att.heads, att.head_dim))
    _close(jitted(att.log_decay, p, z), jnp.swapaxes(g, 1, 2))
    # the bound times a sigmoid: no projection takes the decay past it
    far = jitted(att.log_decay, p, 1e4 * jnp.sign(z))
    assert float(jnp.min(far)) == -5.0 and float(jnp.max(far)) <= 0.0
    # the gate is exercised: neither pinned at the floor nor at none
    assert -5.0 < float(jnp.min(g)) < -3.0 and -1.0 < float(jnp.max(g)) < 0.0


@pytest.fixture(scope="module")
def wide():
    """`small` with the linear layers' heads 128 wide: shapes
    `pallas_shortconv.tile` takes (one tile of 128 positions)."""
    s = _drawn(*build(head_dim=128))
    s.want_loss = float(ref.loss_and_grads(s.arch, s.params, s.state, s.x, s.y)[0])
    return s


@pytest.mark.parametrize("path", ["lowered_here", "kernels"])
def test_the_linear_layer_agrees_with_the_reference_around_either_short_conv(
        small, wide, path, monkeypatch):
    """`KDA.apply` at heads that tile lowers to a program that holds both
    forms of the short convolutions (`lax.platform_dependent`: the only
    place the layer forks at 128 positions, and at 16-wide heads it does
    not fork at all); this host takes the composition, and with the
    kernels put where a TPU would take them (interpret mode) the layer is
    the reference's as well: values and every gradient."""
    att = wide.model.attention(LINEAR)
    p = wide.params["layers"][0]["attn"]
    x = jax.random.normal(jax.random.key(5), (2, S, D))
    d_out = jax.random.normal(jax.random.key(6), (2, S, D))
    run = lambda p, x: att.apply(p, {}, x)[0]  # noqa: E731
    if path == "kernels":
        ran = interpret(monkeypatch)
    else:
        assert "stablehlo.case" in jax.jit(run).lower(p, x).as_text()
        narrow = small.model.attention(LINEAR)
        assert "stablehlo.case" not in jax.jit(
            lambda p, x: narrow.apply(p, {}, x)[0]).lower(
                small.params["layers"][0]["attn"], x).as_text()
    got, grads = pulled(run, d_out, p, x)
    want, want_grads = pulled(
        lambda p, x: ref.linear_attention(wide.arch, p, x), d_out, p, x)
    if path == "kernels":
        shape = (2, 2, S, 128)
        assert sorted(ran) == sorted(
            (shape, unit, back) for unit in (True, True, False)
            for back in (False, True))
    assert float(jnp.max(jnp.abs(want))) > 0.1
    _close(got, want)
    for a, b in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(want_grads), strict=True):
        _close(a, b)


def test_the_full_layer_agrees_with_the_reference(small):
    att = small.model.attention(FULL)
    p = small.params["layers"][2]["attn"]
    assert set(p) == {"q", "kv_a", "kv_b", "kv_norm", "o", "gate"}
    assert (att.q_rank, att.gated, att.interleaved, att.qk_width) == (
        None, True, True, 128)
    x = jax.random.normal(jax.random.key(5), (2, S, D))
    got, seen = jitted(lambda p, x: att.apply(p, {}, x), p, x)
    assert seen == {}
    _close(got, jitted(lambda p, x: ref.full_attention(small.arch, p, x), p, x))


def test_no_query_latent_is_glm_moes_algebra_with_the_latent_folded(small):
    """`q_rank=None` against `glm_moe.MLA` with a query latent of the
    hidden size, `q_a` the identity and a unit norm gain: the same q but
    for the latent's RMSNorm, which an input of unit mean square passes
    through; everything after q is the one code."""
    att = dataclasses.replace(small.model.attn, gated=False, interleaved=False)
    p = {k: v for k, v in small.params["layers"][2]["attn"].items() if k != "gate"}
    x = jax.random.normal(jax.random.key(6), (2, S, D))
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + att.eps)
    with_latent = dataclasses.replace(att, q_rank=D)
    q = dict(p, q_a=jnp.eye(D), q_norm=jnp.ones((D,)), q_b=p["q"])
    del q["q"]
    got = jitted(lambda p, x: att.apply(p, {}, x)[0], p, x)
    want = jitted(lambda q, x: with_latent.apply(q, {}, x)[0], q, x)
    _close(got, want, 1e-5)
    # the published GLM layer draws what it drew before this family came
    glm = glm_moe.MLA(4, 12, 8, 8, 4, 8)
    drawn = glm.init(jax.random.key(0), (S, D))[0]
    assert set(drawn) == {"q_a", "q_b", "kv_a", "kv_b", "o", "q_norm", "kv_norm"}
    keys = jax.random.split(jax.random.key(0), 5)
    np.testing.assert_array_equal(
        drawn["o"], layers._weight(keys[4], (32, D), 32, glm_moe.INIT_STD))
    assert (glm.qk_width, glm_moe.MLA().qk_width) == (128, 256)
    assert dataclasses.replace(small.model.attn, nope=128, rope_dim=64).qk_width == 256


def test_interleaved_rope_is_rotate_half_over_a_fixed_order_of_the_features(small):
    """Adjacent pairs (the reference) against the program's turn of the
    even features then the odd ones: q and k each differ by that order, and
    every score q . k is the same."""
    theta, r = 6e6, 8
    q = jax.random.normal(jax.random.key(7), (1, S, 2, r))
    k = jax.random.normal(jax.random.key(8), (1, S, 2, r))
    order = jnp.concatenate([jnp.arange(0, r, 2), jnp.arange(1, r, 2)])
    heads_first = lambda a: jnp.moveaxis(a, 2, 1)  # noqa: E731
    turn = jax.jit(lambda a: layers.rope(a, theta))
    a, b = turn(heads_first(q[..., order])), turn(heads_first(k[..., order]))
    pairs = jax.jit(lambda a: ref.rotary_pairs(a, theta))
    want_q, want_k = pairs(q), pairs(k)
    np.testing.assert_allclose(a, heads_first(want_q[..., order]), atol=1e-5)
    np.testing.assert_allclose(
        jnp.einsum("nhqd,nhkd->nhqk", a, b),
        jnp.einsum("nqhd,nkhd->nhqk", want_q, want_k), atol=1e-4)
    # the order is taken from the weights' columns
    w = jnp.arange(2 * 3 * 12, dtype=jnp.float32).reshape(2, 3, 12)
    got = small.model.attn._pe_order(w)
    np.testing.assert_array_equal(got[..., :4], w[..., :4])
    np.testing.assert_array_equal(got[..., 4:], w[..., 4:][..., order])
    plain = dataclasses.replace(small.model.attn, interleaved=False)
    assert plain._pe_order(w) is w


def _brute_force(biased, n_group, topk_group, k):
    out = []
    for row in np.asarray(biased):
        groups = row.reshape(n_group, -1)
        best = np.sort(groups, axis=1)[:, -2:].sum(axis=1)
        kept = sorted(range(n_group), key=lambda g: (-best[g], g))[:topk_group]
        allowed = [i for i in range(row.size) if i // groups.shape[1] in kept]
        out.append(sorted(allowed, key=lambda i: (-row[i], i))[:k])
    return np.asarray(out)


def test_the_group_limited_top_k_is_the_brute_force(small):
    layer = small.model.experts
    assert (layer.n_group, layer.topk_group, layer.per_token) == (4, 2, 4)
    xt = jax.random.normal(jax.random.key(9), (64, D)) * 3.0
    router = small.params["layers"][1]["ffn"]["router"]
    bias = small.state["layers"][1]["bias"]
    ids, gates, load, _ = jitted(lambda r, b, x: layer.route(r, b, x, 1),
                                 router, bias, xt)
    score = jax.nn.sigmoid(jnp.dot(xt, router, precision="highest"))
    want = _brute_force(score + bias, 4, 2, 4)
    np.testing.assert_array_equal(np.sort(np.asarray(ids), axis=1),
                                  np.sort(want, axis=1))
    np.testing.assert_array_equal(
        np.asarray(ref.choose(small.arch, score + bias)), want)
    # the limit binds: the 4 largest of all 16 are another set for some token
    free = _brute_force(score + bias, 1, 1, 4)
    assert (np.sort(free, axis=1) != np.sort(want, axis=1)).any()
    # every token's experts lie in two groups of four neighbours
    assert all(len({i // 4 for i in row}) <= 2 for row in np.asarray(ids))
    np.testing.assert_allclose(gates.sum(axis=1), 2.5, rtol=1e-6)
    assert float(load.sum()) == 64 * 4
    with pytest.raises(ValueError, match="groups divide the experts"):
        dataclasses.replace(layer, n_group=3)
    with pytest.raises(ValueError, match="groups divide the experts"):
        dataclasses.replace(layer, n_group=16, topk_group=2)


def test_one_group_lowers_to_todays_routing_bit_for_bit():
    """`n_group=1` (GLM's, SDAR's and Trinity's layers) is the program
    text those models had before the key came: the step's lowering has no
    operation for a limit, and neither has a limit of 0 on the gated MLPs."""
    kw = dict(width=16, n_routed=8, per_token=2, held=(0, 1, 2), scaling=1.8)
    then = glm_moe.ExpertLayer(**kw)
    now = glm_moe.ExpertLayer(**kw, n_group=1, topk_group=1, limit=0.0,
                              shared_limit=0.0)
    assert then == now
    p, st, _ = now.init(jax.random.key(0), (S, D))
    x = jax.ShapeDtypeStruct((2, S, D), jnp.float32)
    text = jax.jit(lambda p, st, x: now.apply(p, st, x, True)).lower(
        p, st, x).as_text()
    grouped = dataclasses.replace(now, n_group=2)
    other = jax.jit(lambda p, st, x: grouped.apply(p, st, x, True)).lower(
        p, st, x).as_text()
    assert (text.count("top_k"), other.count("top_k")) == (1, 3)
    clamped = jax.jit(lambda p, st, x: dataclasses.replace(
        now, limit=4.0).apply(p, st, x, True)).lower(p, st, x).as_text()
    # min(a, L) and clip(u, -L, L): two minima and a maximum more
    assert clamped.count("stablehlo.minimum") == text.count("stablehlo.minimum") + 2
    assert clamped.count("stablehlo.maximum") == text.count("stablehlo.maximum") + 1
    # and the routing itself: the same ids and gates, bit for bit
    xt = jax.random.normal(jax.random.key(1), (64, D))
    a = jitted(lambda r, b, x: then.route(r, b, x, 1), p["router"], st["bias"], xt)
    b = jitted(lambda r, b, x: now.route(r, b, x, 1), p["router"], st["bias"], xt)
    for u, v in zip(a, b, strict=True):
        np.testing.assert_array_equal(u, v)


@pytest.mark.parametrize("limit", [0.0, 0.05])
def test_the_clamp_on_a_gated_mlp(limit):
    mlp = layers.GatedMLP(16, 0.5, limit)
    p = mlp.init(jax.random.key(0), (D,))[0]
    x = jax.random.normal(jax.random.key(1), (8, D))
    got = jitted(lambda p, x: mlp.apply(p, {}, x)[0], p, x)
    a, u = x @ p["gate"], x @ p["up"]
    if limit:
        assert float(jnp.max(a)) > limit and float(jnp.max(jnp.abs(u))) > limit
        a, u = jnp.minimum(a, limit), jnp.clip(u, -limit, limit)
    _close(got, jitted(lambda a, u, w: (jax.nn.silu(a) * u) @ w, a, u, p["down"]),
           1e-5)
    _close(got, jitted(lambda p, x: ref.gated_mlp(p, x, limit), p, x), 1e-5)
    assert layers.GatedMLP(16, 0.5) == layers.GatedMLP(16, 0.5, 0.0)


def test_a_late_layers_clamps_reach_its_experts_and_its_shared_expert(small):
    s = _drawn(*build(expert_swiglu_limit_list=[0, 0.05, 0],
                      share_expert_swiglu_limit_list=[0, 0, 0.07]))
    made = s.model._layers()
    assert [(l.ffn.limit, l.ffn.shared_limit) for l in made[1:]] == [
        (0.05, 0.0), (0.0, 0.07)]
    assert made[0].ffn == layers.GatedMLP(48, glm_moe.INIT_STD)
    loss, grads, _ = system(s)
    want, want_grads = ref.loss_and_grads(s.arch, s.params, s.state, s.x, s.y)
    assert loss == pytest.approx(float(want), rel=TOL)
    assert max(leaf_gaps(grads, want_grads).values()) < 3 * TOL
    assert abs(loss_of(small) / loss - 1) > 10 * TOL  # the same draws, no clamp
    with pytest.raises(ValueError, match="limits for 3 layers"):
        build(expert_swiglu_limit_list=[0, 4])


def test_the_layer_kind_table_comes_from_the_group_size():
    assert bh.layer_kinds(6, 6) == (LINEAR,) * 5 + (FULL,)
    whole = bh.ling_3_0_flash()
    kinds = whole.layer_types
    assert len(kinds) == 42 and [i for i, k in enumerate(kinds) if k == FULL] == [
        5, 11, 17, 23, 29, 35, 41]
    assert kinds[:2] == (LINEAR, LINEAR) and whole.first_dense == 2
    made = whole._layers()
    assert [type(l.attn).__name__ for l in made[4:7]] == ["KDA", "MLA", "KDA"]
    assert [l.ffn_scope for l in made[:3]] == ["mlp", "mlp", "moe"]
    assert [i for i, l in enumerate(made) if l.attn is whole.linear] == [
        i for i in range(42) if (i + 1) % 6]
    assert all(type(l) is glm_moe.DecoderLayer for l in made)
    model, _ = build(layer_types=None, num_hidden_layers=3)
    assert model.layer_types == (LINEAR, LINEAR, FULL)
    with pytest.raises(ValueError, match="one of"):
        build(layer_types=[LINEAR, FULL])
    with pytest.raises(ValueError, match="one of"):
        build(layer_types=[LINEAR, "sliding_attention", FULL])
    with pytest.raises(ValueError, match="leaves float32's range"):
        bh.KDA(lower_bound=-6.0)
    assert bh.KDA(lower_bound=-5.25).lower_bound == -5.25


def test_the_sixty_four_shares_parts_and_the_shared_expert_once_add_up():
    """One layer of 16 experts in 4 groups cut eight ways: each share
    routes over all 16 under the group limit, normalises the gates over all
    the chosen, and adds its own two experts' part to the whole shared
    expert. The eight routed parts plus the shared expert ONCE are what the
    uncut reference gives."""
    whole = glm_moe.ExpertLayer(width=16, n_routed=16, per_token=4,
                                held=tuple(range(16)), n_shared=1, scaling=2.5,
                                bias_step=1e-3, balance=0.0, scoring="sigmoid",
                                n_group=4, topk_group=2)
    shape, key = (S, D), jax.random.key(7)
    p, st, _ = whole.init(key, shape)
    st = dict(st, bias=0.01 * jax.random.normal(jax.random.key(8), (16,)))
    x = jax.random.normal(jax.random.key(9), (2, S, D)) * 4.0
    arch = dict(ARCH, held_experts=list(range(16)))
    want, _, _ = jitted(lambda p, b, x: ref.experts(arch, p, b, x, 0.0, 0.0),
                        p, st["bias"], x)
    shared = jitted(ref.gated_mlp, p["shared"], x)
    total = shared
    for i in range(8):
        share = dataclasses.replace(whole, held=(2 * i, 2 * i + 1))
        sp, _, _ = share.init(key, shape)  # an expert's weights come from its id
        for m in ("gate", "up", "down"):
            np.testing.assert_array_equal(
                sp["experts"][m], p["experts"][m][2 * i: 2 * i + 2])
            np.testing.assert_array_equal(sp["shared"][m], p["shared"][m])
        np.testing.assert_array_equal(sp["router"], p["router"])
        total = total + jitted(share.apply, sp, st, x)[0] - shared
    assert float(jnp.max(jnp.abs(want - shared))) > 0.01
    np.testing.assert_allclose(total, want, atol=2e-7)
    uncut, _ = jitted(whole.apply, p, st, x)
    np.testing.assert_allclose(uncut, want, atol=2e-7)


# ------------------------------------------------------- the whole model

def test_loss_and_every_leafs_gradient_agree_with_the_reference(small):
    loss, grads, new = system(small)
    want, want_grads = ref.loss_and_grads(
        small.arch, small.params, small.state, small.x, small.y)
    assert loss == pytest.approx(float(want), rel=TOL)
    gaps = leaf_gaps(grads, want_grads)
    # two linear layers' 13 and the full one's 6, two norms a layer, a dense
    # MLP's 3 and two expert layers' 7, embedding, final norm, head
    assert len(gaps) == 2 * 13 + 6 + 3 * 2 + 3 + 2 * 7 + 3
    assert max(gaps.values()) < TOL, max(gaps, key=gaps.get)
    # the mean next-token cross-entropy and nothing else
    z = logits(small)
    nll = jax.nn.logsumexp(z, -1) - jnp.take_along_axis(z, small.y[..., None], -1)[..., 0]
    assert loss == pytest.approx(float(jnp.mean(nll)), rel=1e-6)
    # the state is the expert layers' alone, as the siblings'
    assert set(new) == {"layers"} and new["layers"][0] == {}


def test_a_share_that_leaves_the_gates_gradient_out_agrees_with_the_reference():
    s = _drawn(*build(gate_gradient=False))
    loss, grads, _ = system(s)
    want, want_grads = ref.loss_and_grads(s.arch, s.params, s.state, s.x, s.y)
    assert loss == pytest.approx(float(want), rel=TOL)
    assert max(leaf_gaps(grads, want_grads).values()) < TOL
    for layer in grads["layers"][1:]:
        assert float(jnp.max(jnp.abs(layer["ffn"]["router"]))) == 0.0


def test_logits_and_hidden_states_agree_with_the_reference(small):
    want = ref.eval_logits(small.arch, small.params, small.state, small.x)
    got, new = jitted(small.model.apply, small.params, small.state, small.x)
    assert got.shape == (2, S, VOCAB) and got.dtype == jnp.float32
    _close(got, want)
    assert set(new) == set(small.state)  # no step: the lows stay
    hidden, _ = jitted(small.model.hidden_states, small.params, small.state,
                       small.x)
    for a, b in zip(hidden, ref.hidden_states(
            small.arch, small.params, small.state, small.x), strict=True):
        assert a.shape == (2, S, D)
        _close(a, b)


def test_the_mtp_module_at_a_weight_is_an_mla_layer_and_agrees_with_the_reference():
    s = _drawn(*build(num_nextn_predict_layers=1, mtp_weight=0.3))
    assert isinstance(s.model._mtp_layer().attn, glm_moe.MLA)
    assert set(s.params["mtp"]["layer"]["attn"]) == {
        "q", "kv_a", "kv_b", "kv_norm", "o", "gate"}
    loss, grads, new = system(s)
    (want, (terms, loads)), want_grads = jitted(jax.value_and_grad(
        lambda p, st, x, y: ref.loss_fn(s.arch, p, st, x, y), has_aux=True),
        s.params, s.state, s.x, s.y)
    assert loss == pytest.approx(float(want), rel=TOL)
    assert float(terms["mtp"]) > 1.0 and len(loads) == 3
    assert max(leaf_gaps(grads, want_grads).values()) < 2 * TOL
    assert float(jnp.max(jnp.abs(grads["mtp"]["proj"]))) > 0
    off = _drawn(*build(num_nextn_predict_layers=1, mtp_weight=0.0))
    assert loss_of(off) == pytest.approx(float(terms["main"]), rel=TOL)
    assert "mtp" not in build()[0].init(jax.random.key(0), (S,))[0]
    assert bh.ling_3_0_flash().mtp_modules == 0


def test_three_steps_losses_and_held_rows_agree_with_the_reference(small):
    """Two AdamW updates and two moves of the selection bias between three
    losses, through `zoo.make_train_step` (the GSPMD step)."""
    want = ref.train_report(small.arch, small.params, small.state, small.x,
                            small.y, steps=3, first_grads=True, **HYPER)
    losses, seen, state = steps(small)
    assert losses == pytest.approx(want["losses"], rel=TOL)
    first = want.pop("first_grads")
    assert jax.tree_util.tree_structure(first) == jax.tree_util.tree_structure(
        small.params)
    assert all(g.dtype == jnp.bfloat16 for g in jax.tree_util.tree_leaves(first))
    _, exact = ref.loss_and_grads(small.arch, small.params, small.state,
                                  small.x, small.y)
    assert max(leaf_gaps(first, exact).values()) < 1e-2
    assert set(want) == {"losses", "rows_held", "terms"}
    assert [r["moe_rows_held"] for r in seen] == want["rows_held"]
    assert len(seen[0]["moe_rows_held"]) == 2
    assert losses[2] < losses[1] < losses[0]
    two = ref.train_losses(small.arch, small.params, small.state, small.x,
                           small.y, steps=2, **HYPER)
    assert two == pytest.approx(want["losses"][:2], rel=1e-6)
    assert sum(seen[-1]["moe_overflow_rows"]) == 0
    assert set(seen[0]) == set(glm_moe.GlmMoe.counters(
        small.model, state.model_state))


FAULTS = [*tool.FAULTS, "tap_order_reversed", "decay_on_values"]
# what moves the loss too little at toy size moves the gradients
BY_GRADIENT = ("absent_gates", "group_limit_dropped", "rope_off")


def _planted(fault):
    """`compare_bailing_hybrid.control` for a model the test builds itself:
    the patches are the module's, the model it yields is not used (but for
    the one fault that is an argument of the factory)."""
    cfg = {"factory": {"module": "parallel_cnn_tpu.nn.bailing_hybrid",
                       "name": "ling_3_0_flash",
                       "kwargs": {"layer_types": [LINEAR], "num_dense_layers": 1,
                                  "vocab_size": 8}}}
    return tool.control(cfg, ref, fault)


@pytest.mark.parametrize("fault", FAULTS)
def test_a_fault_in_the_system_fails_the_comparison(small, fault, monkeypatch):
    """The faults the chip's controls plant (benchmark/tools/
    compare_bailing_hybrid.py:control, the very context the tool uses) and
    two more, each against the reference's loss (three against its
    gradients)."""
    want = small.want_loss
    if fault == "scaling_dropped":
        with _planted(fault) as faulty:
            assert faulty.experts.scaling == 1.0
        loss = loss_of(small, dataclasses.replace(
            small.model, experts=dataclasses.replace(small.model.experts,
                                                     scaling=1.0)))
    elif fault in tool.FAULTS:
        with _planted(fault):
            if fault in BY_GRADIENT:
                loss, grads, _ = system(small, build()[0], fresh=True)
                _, want_grads = ref.loss_and_grads(
                    small.arch, small.params, small.state, small.x, small.y)
                assert max(leaf_gaps(grads, want_grads).values()) > 100 * TOL
                return
            loss = loss_of(small, build()[0], fresh=True)
    elif fault == "tap_order_reversed":
        monkeypatch.setattr(bh, "causal_conv",
                            lambda x, taps: layers.causal_conv(x, taps[::-1]))
        loss = loss_of(small, build()[0], fresh=True)
    else:  # the decay applied to the values' side of the state
        from parallel_cnn_tpu.ops import kda

        scan = kda.chunked_kda
        monkeypatch.setattr(kda, "chunked_kda", lambda q, k, v, g, b, *a: scan(
            q, k, v, jnp.swapaxes(jnp.swapaxes(g, -1, -2)[..., ::-1, :], -1, -2),
            b, *a))
        loss = loss_of(small, build()[0], fresh=True)
    assert abs(loss / want - 1) > 10 * TOL, (fault, loss, want)


@pytest.mark.parametrize("fault", ["conv_dropped", "l2_dropped",
                                   "tap_order_reversed"])
def test_a_fault_in_a_fused_stage_fails_the_comparison_where_the_shapes_tile(
        wide, fault, monkeypatch):
    """At heads 128 wide, with ops/pallas_shortconv.py's kernels put where
    a TPU would take them (interpret mode): the clean model runs them and
    is the reference's; a fault planted by replacing `bh.causal_conv` or
    `bh._unit` — the tool's two and the reversed taps — still runs, because
    the layer then composes its stages plainly, and fails the comparison."""
    ran = interpret(monkeypatch)
    # a new model a call, and every trace under the patches of its moment
    want, model = wide.want_loss, lambda: build(head_dim=128)[0]  # noqa: E731
    assert loss_of(wide, model(), fresh=True) == pytest.approx(want, rel=TOL)
    assert [(unit, back) for _, unit, back in ran] == 2 * [
        (True, False), (True, False), (False, False)]
    del ran[:]
    if fault == "tap_order_reversed":
        monkeypatch.setattr(bh, "causal_conv",
                            lambda x, taps: layers.causal_conv(x, taps[::-1]))
        loss = loss_of(wide, model(), fresh=True)
    else:
        with _planted(fault):
            loss = loss_of(wide, model(), fresh=True)
    assert ran == []
    assert abs(loss / want - 1) > 10 * TOL, (fault, loss, want)


def test_the_control_puts_everything_back(small):
    before = loss_of(small, build()[0], fresh=True)
    for fault in tool.FAULTS:
        with _planted(fault):
            pass
    assert loss_of(small, build()[0], fresh=True) == before
    assert before == pytest.approx(small.want_loss, rel=TOL)
    from benchmark.reference import glm_moe as rounded

    with _planted("float8_e4m3fn"):
        assert rounded.ROUND == jnp.dtype("float8_e4m3fn")
    assert rounded.ROUND is None


def test_a_float8_reference_fails_the_comparison(small):
    with _planted("float8_e4m3fn"):
        low = float(ref.loss_and_grads(
            small.arch, small.params, small.state, small.x, small.y)[0])
    assert abs(low / small.want_loss - 1) > 10 * TOL


def test_bfloat16_activations_change_rounding_only(small):
    loss = loss_of(small, dataclasses.replace(small.model, dtype="bfloat16"))
    assert 1e-7 < abs(loss / small.want_loss - 1) < 2e-2


# ----------------------------------------------- the published model's size

def test_the_published_model_and_the_share_have_the_counted_parameters():
    def count(model, s):
        params = jax.eval_shape(lambda k: model.init(k, (s,))[0], jax.random.key(0))
        return sum(l.size for l in jax.tree_util.tree_leaves(params))

    d, wide = 2560, 4096
    linear = 5 * d * wide + 2 * d * 32 + 3 * 4 * wide + 32 + wide + 128
    full = d * 6144 + d * 576 + 512 + 512 * 8192 + wide * d + d * 32
    assert (linear, full) == (52_646_048, 31_965_696)
    norms = 2 * d
    dense = norms + 3 * d * 6144
    sparse = lambda held: norms + d * 512 + (held + 1) * 3 * d * 768  # noqa: E731
    share = bh.ling_3_0_flash(
        layer_types=[LINEAR] * 6 + [FULL], num_dense_layers=1, vocab_size=19648,
        held_experts=range(8), row_buffer=8192, gate_gradient=False)
    assert count(share, 8192) == (
        6 * linear + full + dense + 6 * sparse(8) + 2 * 19648 * d + d) \
        == 822_033_344
    period = bh.ling_3_0_flash(layer_types=bh.layer_kinds(6, 6), vocab_size=8)
    assert count(period, 4096) == (5 * linear + full + 2 * dense
                                   + 4 * sparse(512) + 2 * 8 * d + d)
    total = (35 * linear + 7 * full + 2 * dense + 40 * sparse(512)
             + 2 * 157184 * d + d)
    assert 1.20e11 < total < 1.30e11  # "~125B"
    whole = bh.ling_3_0_flash()
    assert (whole.first_dense, whole.vocab, len(whole.experts.held)) == (
        2, 157184, 512)
    assert (whole.attn.theta, whole.eps, whole.linear.lower_bound,
            whole.experts.scaling, whole.experts.n_group,
            whole.experts.topk_group) == (6e6, 1e-6, -5.0, 2.5, 8, 4)
    assert whole.attn.core(8192) == ("fused", 512)  # 192 carried as 256


# ----------------------------------------------------------- step factories

@pytest.mark.parametrize("factory", ["comm_psum", "comm_ring", "fused_update",
                                     "zero3", "pipeline"])
def test_the_other_step_factories_refuse_the_model_by_name(host_devices, factory):
    model, _ = build()
    _, state, _ = model.init(jax.random.key(0), (S,))
    assert not layers.has_random_state(state) and hasattr(model, "finish_step")
    opt = zoo.make_optimizer(**HYPER)
    mesh = plan_lib.ExecutionPlan(data=2).validate().make_mesh(
        devices=host_devices[:2])
    fused = config_lib.FusedStepConfig(update=True)
    comm = config_lib.CommConfig(impl="ring")
    with pytest.raises((zoo.StepStateUnsupported, zoo.RandomLayerUnsupported),
                       match="BailingHybrid"):
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
                               in_shape=(S,))


def test_the_gspmd_step_runs_the_model_on_a_mesh_and_zoo_train_records_it(
        host_devices):
    model, _ = build()
    mesh = plan_lib.ExecutionPlan(data=2).validate().make_mesh(
        devices=host_devices[:2])
    tokens = np.asarray(jax.random.randint(jax.random.key(3), (8, S + 1), 0, VOCAB))

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
        model, tokens[:, :-1], tokens[:, 1:], in_shape=(S,), epochs=2,
        batch_size=4, accum_steps=2, mesh=mesh, **HYPER, seed=3, verbose=False,
        metrics=Rec(), obs=obs)
    assert all(math.isfinite(v) for v in losses)
    last = Rec.epochs[-1]
    assert len(last["moe_rows_held"]) == 2 and sum(last["moe_overflow_rows"]) == 0
    for layer in state.model_state["layers"][1:]:
        assert 0 < float(jnp.max(jnp.abs(layer["bias"]))) <= 4e-3 + 1e-9
    assert state.model_state["layers"][0] == {}
    (event,) = [f for k, f in Journal.events if k == "zoo_moe"]
    assert (event["experts_held"], event["experts_published"],
            event["tokens_per_step"], event["row_buffer"],
            event["expert_layers"]) == (4, 16, 4 * S, 4 * S * 4, 2)
    assert event["attention_layer_kinds"] == [LINEAR, LINEAR, FULL]
    assert (event["attention_core"], event["attention_tile"],
            event["attention_qk_width"], event["rope_turn"]) == (
        "blocks", 32, 128, "plain")
    assert (event["attention_tiles_visited"], event["attention_tiles_total"]) == (
        10, 16)
    assert (event["kda_chunk"], event["kda_subchunk"], event["kda_scan_steps"],
            event["kda_chunks_a_step"], event["kda_state_bytes"]) == (
        64, 16, 1, 2, 2 * 16 * 16 * 4)
    assert event["kda_core"] == event["kda_short_conv"] == "xla"  # a CPU, and heads 16 wide
    at_size_model = bh.ling_3_0_flash(
        layer_types=[LINEAR, FULL], num_dense_layers=1, vocab_size=8,
        held_experts=range(8), row_buffer=8192)
    at_size = at_size_model.describe(8192, 8192, "tpu")
    assert (at_size["attention_core"], at_size["attention_tile"],
            at_size["attention_tiles_visited"], at_size["kda_scan_steps"],
            at_size["kda_state_bytes"]) == ("fused", 512, 136, 32, 64 << 20)
    # the scan's kernels where the step is lowered for a TPU and the shapes
    # tile (ops/pallas_kda.py), the plain body on a CPU or at 64-wide heads
    assert at_size["kda_core"] == "pallas"
    narrow = bh.ling_3_0_flash(layer_types=[LINEAR, FULL], num_dense_layers=1,
                               vocab_size=8, held_experts=range(8),
                               row_buffer=8192, head_dim=64)
    assert [m.describe(8192, 8192, p)["kda_core"] for m, p in (
        (at_size_model, "cpu"), (narrow, "tpu"))] == ["xla", "xla"]
    # and the short convolutions ahead of it (ops/pallas_shortconv.py)
    assert [m.describe(8192, s, p)["kda_short_conv"] for m, s, p in (
        (at_size_model, 8192, "tpu"), (at_size_model, 8192, "cpu"),
        (narrow, 8192, "tpu"), (at_size_model, 8192 + 64, "tpu"))] == [
            "pallas", "xla", "xla", "xla"]


def test_the_scopes_are_the_ones_the_catalog_reads():
    from parallel_cnn_tpu.obs import programs

    model, _ = build()
    opt = zoo.make_optimizer(**HYPER)
    state = jax.eval_shape(lambda k: zoo.init_state(model, k, (S,), opt),
                           jax.random.key(0))
    x = jax.ShapeDtypeStruct((2, S), jnp.int32)
    text = zoo.make_train_step(model, opt, 1, None).lower(state, x, x).as_text(
        debug_info=True)
    scopes = {programs.scope_of(name)[0]
              for name in re.findall(r'loc\("([^"]*)"', text)}
    for want in ("embed", *(f"l0/attn/{s}" for s in (
            "norm", "qkv", "conv", "gates", "core", "gate_norm", "o")),
            "l0/mlp/norm", "l1/attn/core", "l1/moe/norm", "l1/moe/route",
            "l1/moe/dispatch", "l1/moe/experts", "l1/moe/combine",
            "l1/moe/shared", *(f"l2/attn/{s}" for s in (
                "norm", "q", "kv", "rope", "core", "gate", "o")),
            "norm", "head", "loss", "optimizer"):
        assert want in scopes, (want, sorted(scopes))
    # no position in a linear layer, no conv in the full one, no MTP module
    assert not any(re.match(r"l[01]/attn/rope", s) for s in scopes)
    assert not any(s.startswith(("l2/attn/conv", "l2/attn/gates", "mtp"))
                   for s in scopes)
