"""nn/glm_moe.py (GLM-4.7-Flash's mechanisms) at toy widths on the CPU,
seeded random weights, against the plain float32 reference the benchmark
keeps (benchmark/reference/glm_moe.py): latent attention, the expert layer
and its share, the multi-token-prediction module, the whole model's loss,
gradients, two AdamW steps and held-row counts; what the step settles
without a gradient; and which step factories run the model."""

import dataclasses
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.reference import glm_moe as ref  # noqa: E402
from benchmark.tools.compare_reference import leaf_gaps  # noqa: E402
from parallel_cnn_tpu import config as config_lib, nn, plan as plan_lib  # noqa: E402
from parallel_cnn_tpu.nn import glm_moe  # noqa: E402
from parallel_cnn_tpu.train import zoo  # noqa: E402
from token_family import (HYPER, highest, jitted, logits as logits_of,  # noqa: E402
                          loss as loss_of, pulled, stepped, steps, system, toy)

S, VOCAB = 16, 96
ARCH = {
    "hidden_size": 32, "intermediate_size": 64, "moe_intermediate_size": 16,
    "num_attention_heads": 2, "n_shared_experts": 1,
    "routed_scaling_factor": 1.8, "num_experts_per_tok": 2,
    "first_k_dense_replace": 1, "num_hidden_layers": 3,
    "num_nextn_predict_layers": 1, "rms_norm_eps": 1e-5, "rope_theta": 1e6,
    "q_lora_rank": 12, "kv_lora_rank": 8, "qk_nope_head_dim": 6,
    "qk_rope_head_dim": 4, "v_head_dim": 8, "vocab_size": VOCAB,
    "router_experts": 8, "held_experts": [0, 1, 2], "row_buffer": None,
    "bias_update_speed": 1e-3, "balance_weight": 1e-4, "mtp_weight": 0.3,
}
# float32 on both sides, the highest matmul precision: what differs is the
# order of float32 sums (a grouped matmul over sorted rows against a loop
# over experts, blocked against whole softmax). Seen: 1e-7 on the loss,
# 6e-7 on the worst leaf's gradient; every fault below moves 100 x TOL.
TOL = 2e-5


def build(**over):
    arch = dict(ARCH, **over)
    keys = [k for k in arch if k not in ("router_experts", "held_experts")]
    return glm_moe.glm_moe_lite(
        **{k: arch[k] for k in keys}, n_routed_experts=arch["router_experts"],
        held_experts=arch["held_experts"], dtype="float32", q_block=8,
        loss_block=16), arch


@pytest.fixture(scope="module")
def small():
    """The toy model with EVERY leaf drawn at random (weights of std
    1 / sqrt(fan_in), gains 1 + 0.1 n, selection biases 0.01 n): at the
    published init of std 0.02 a 32-wide model is all embedding."""
    return toy(*build(), seq=S, shifted=True)


def _reference_terms(s):
    """The reference's loss, term by term, on the toy: read by two tests."""
    if "terms" not in s.memo:
        s.memo["terms"] = jitted(
            lambda p, st, x, y: ref.loss_fn(s.arch, p, st, x, y)[1][0],
            s.params, s.state, s.x, s.y)
    return s.memo["terms"]


# ------------------------------------------------------------- the pieces

def test_latent_attention_agrees_with_the_reference(small):
    attn = small.model.attn
    p = small.params["layers"][0]["attn"]
    x = jax.random.normal(jax.random.key(5), (2, S, 32))
    run = jax.jit(lambda p, x: attn.apply(p, {}, x)[0])
    got = highest(run, p, x)
    want = jitted(lambda p, x: ref.attention(small.arch, p, x), p, x)
    assert float(jnp.max(jnp.abs(want))) > 0.1
    np.testing.assert_allclose(got, want, atol=TOL)
    # causal: position i does not see what follows it
    later = x.at[:, S // 2:].add(1.0)
    moved = highest(run, p, later)
    np.testing.assert_allclose(moved[:, : S // 2], got[:, : S // 2], atol=1e-6)


@pytest.mark.parametrize("shape", [(2, 3, S, 8), (2, 1, S, 8), (S, 4)],
                         ids=["heads", "the-shared-key", "one-sequence"])
def test_rope_turns_the_positions_of_the_axis_before_the_last(shape):
    """`nn.layers.rope` of head-major `(N, heads, S, d)` (and of anything
    `(..., S, d)`) against the reference's position-major `rotary`."""
    x = jax.random.normal(jax.random.key(8), shape)
    x4 = x.reshape((1,) * (4 - x.ndim) + shape)
    want = jnp.swapaxes(ref.rotary(jnp.swapaxes(x4, 1, 2), jnp.float32(1e6)), 1, 2)
    got = nn.layers.rope(x, 1e6)
    assert got.shape == shape and float(jnp.max(jnp.abs(got - x))) > 0.1
    np.testing.assert_allclose(got.reshape(x4.shape), want, atol=1e-6)
    # position 0 is not turned, position 1 is
    np.testing.assert_array_equal(got[..., 0, :], x[..., 0, :])


def test_the_expert_layer_agrees_with_the_reference(small):
    layer = small.model.experts
    p, st = small.params["layers"][1]["ffn"], small.state["layers"][1]
    x = jax.random.normal(jax.random.key(6), (4, S, 32))
    got, new = jitted(lambda p, st, x: layer.apply(p, st, x, train=True), p, st, x)
    want, balance, load = jitted(
        lambda p, b, x: ref.experts(small.arch, p, b, x), p, st["bias"], x)
    np.testing.assert_allclose(got, want, atol=TOL)
    np.testing.assert_allclose(new["load"], load)
    assert float(new["balance"]) == pytest.approx(float(balance), rel=1e-5)
    assert float(load.sum()) == 4 * S * 2 and int(new["overflow_rows"]) == 0


def test_the_eight_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """One layer cut eight ways: each share routes over all 8 experts and
    adds only its own expert's part, the shared expert is computed by
    every share alike. The shares' routed parts plus the shared expert
    ONCE are what the uncut reference gives for the whole layer."""
    whole = glm_moe.ExpertLayer(width=16, n_routed=8, per_token=2,
                                held=tuple(range(8)), scaling=1.8)
    shape = (S, 32)
    key = jax.random.key(7)
    p, st, _ = whole.init(key, shape)
    st = dict(st, bias=0.01 * jax.random.normal(jax.random.key(8), (8,)))
    x = jax.random.normal(jax.random.key(9), (2, S, 32)) * 4.0
    arch = dict(ARCH, held_experts=list(range(8)))
    want, _, _ = jitted(lambda p, b, x: ref.experts(arch, p, b, x), p, st["bias"], x)
    shared = jitted(ref.gated_mlp, p["shared"], x)
    total = shared
    for i in range(8):
        share = dataclasses.replace(whole, held=(i,))
        sp, _, _ = share.init(key, shape)  # an expert's weights come from its id
        for m in ("gate", "up", "down"):
            np.testing.assert_array_equal(sp["experts"][m][0], p["experts"][m][i])
        np.testing.assert_array_equal(sp["router"], p["router"])
        y, _ = jitted(share.apply, sp, st, x)
        total = total + (y - shared)
    assert float(jnp.max(jnp.abs(want - shared))) > 0.05  # the experts matter
    np.testing.assert_allclose(total, want, atol=TOL)


def test_all_tokens_routed_to_one_held_expert_lose_none():
    """The default row buffer is every assignment: overflow 0 however the
    tokens fall. A buffer that cannot take them counts what it left out."""
    layer = glm_moe.ExpertLayer(width=16, n_routed=8, per_token=2,
                                held=(0, 5), scaling=1.8)
    p, st, _ = layer.init(jax.random.key(1), (S, 32))
    st = dict(st, bias=st["bias"].at[5].set(10.0).at[3].set(9.0))
    x = jax.random.normal(jax.random.key(2), (2, S, 32))
    train = lambda layer: jitted(  # noqa: E731
        lambda p, st, x: layer.apply(p, st, x, train=True), p, st, x)
    y, new = train(layer)
    assert float(new["load"][5]) == 2 * S and int(new["overflow_rows"]) == 0
    arch = dict(ARCH, held_experts=[0, 5])
    want, _, _ = jitted(lambda p, b, x: ref.experts(arch, p, b, x), p, st["bias"], x)
    np.testing.assert_allclose(y, want, atol=1e-4)
    _, new = train(dataclasses.replace(layer, rows=20))
    assert int(new["overflow_rows"]) == 2 * S - 20


def _sorted_plan(layer, ids, rows):
    """The plan by a stable sort of all assignments, the plain reference
    of `ExpertLayer.plan` (and what the layer ran until PR 35): held
    assignments first, expert by expert, the absent ones last."""
    t, k = ids.shape
    e, a = len(layer.held), t * k
    lut = np.full((layer.n_routed,), e, np.int32)
    lut[list(layer.held)] = np.arange(e)
    local = lut[np.asarray(ids)].reshape(a)
    order = np.argsort(local, kind="stable").astype(np.int32)
    rank = np.zeros((a,), np.int32)
    rank[order] = np.arange(a)
    counts = (local[:, None] == np.arange(e)[None, :]).sum(axis=0)
    ends = np.minimum(np.cumsum(counts), rows)
    return dict(
        rank=rank.reshape(t, k), in_buffer=((local < e) & (rank < rows)).reshape(t, k),
        row_of=order[:rows], row_live=np.arange(rows) < ends[-1],
        sizes=np.diff(ends, prepend=0), overflow=counts.sum() - ends[-1])


def _routed(case, t, k, layer):
    """Assignments (T, k), an expert at most once a token."""
    draw = lambda key, among: jnp.asarray(among)[jax.lax.top_k(  # noqa: E731
        jax.random.normal(jax.random.key(key), (t, len(among))), k)[1]]
    absent = [i for i in range(layer.n_routed) if i not in layer.held]
    if case == "none_held":
        return draw(3, absent)
    ids = draw(4, list(range(layer.n_routed)))
    if case == "all_on_one":  # every token's second choice is held expert 5
        others = draw(5, [i for i in range(layer.n_routed) if i != 5])
        ids = others.at[:, 1].set(5)
    return ids


@pytest.mark.parametrize("case,rows", [
    ("balanced", None), ("all_on_one", None), ("none_held", None),
    ("balanced", 20), ("all_on_one", 37), ("balanced", 1)])
def test_the_counted_plan_is_the_stable_sorts(case, rows):
    """`ExpertLayer.plan` counts where the sort it replaced sorted: the row
    of every slot in the buffer, the slot in every live row, the experts'
    sizes and the overflow are the stable argsort's, whether the buffer
    takes every held assignment or not; rows past the live ones and slots
    outside the buffer are marked, and carry a 0."""
    t, k = 48, 3
    layer = glm_moe.ExpertLayer(width=16, n_routed=12, per_token=k,
                                held=(7, 0, 5, 9), scaling=1.8)
    ids = _routed(case, t, k, layer)
    rows = t * k if rows is None else rows
    plan = jax.jit(layer.plan, static_argnums=1)(ids, rows)
    assert plan.sums is None  # no tiles asked for: tests/test_pallas_rowsum.py
    rank, in_buffer, row_of, row_live, sizes, overflow = (
        plan.rank, plan.in_buffer, plan.row_of, plan.row_live, plan.sizes,
        plan.overflow)
    want = _sorted_plan(layer, ids, rows)
    np.testing.assert_array_equal(in_buffer, want["in_buffer"])
    np.testing.assert_array_equal(rank, np.where(want["in_buffer"], want["rank"], 0))
    np.testing.assert_array_equal(row_live, want["row_live"])
    np.testing.assert_array_equal(row_of, np.where(want["row_live"], want["row_of"], 0))
    np.testing.assert_array_equal(sizes, want["sizes"])
    assert int(overflow) == want["overflow"]
    held = int(np.isin(np.asarray(ids), layer.held).sum())
    assert int(sizes.sum()) == int(row_live.sum()) == min(held, rows)
    assert int(overflow) == held - min(held, rows)
    assert {"balanced": held not in (0, t), "all_on_one": held >= t,
            "none_held": held == 0}[case]
    if case == "all_on_one":
        assert int(sizes[2]) == min(t, max(0, rows - int(sizes[:2].sum())))


def _plain_combine(ys, gates, plan, gate_grad=True):
    """What `_combine` computes, as autodiff sees it (and as the layer ran
    it until PR 37): a gather of every assignment's row and a sum over a
    token's slots."""
    back = jnp.where(plan.in_buffer[..., None], ys[plan.rank], 0)
    return jnp.einsum("tk,tkd->td", gates, back)


def one_rounding(dtype):
    """Two float32 sums of the same few terms in two orders, each rounded
    once to `dtype`: a last place of `dtype` apart at the most."""
    return dict(atol=1e-6, rtol=2.0 ** -7 if dtype == "bfloat16" else 1e-6)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("rows", [30, 100], ids=["overflowed", "dead_rows"])
def test_the_combines_gradients_are_autodiffs_made_in_buffer_space(dtype, rows):
    """`_combine` (the buffer's rows summed onto their tokens, and its own
    backward: a gather of `rows` rows of `dy`) gives the output and the
    rows' and the gates' gradients that `jax.vjp` of the plain expression
    gives: a buffer that overflows, and one with rows past the live ones,
    which hold NaN here (nothing reads them). The output to the order of a
    float32 sum rounded once (a token's rows as they lie in the buffer
    here, its slots in order there); the rows' gradient to the bit (one
    product either way); the gates' to the bit in bfloat16 (a float32 sum
    rounded once either way) and to the order of a float32 sum over `d` in
    float32 (a row's product with its token's `dy` here, a token's with
    its k rows there: this backend's two dot loops)."""
    t, k, d = 48, 3, 16
    layer = glm_moe.ExpertLayer(width=16, n_routed=12, per_token=k,
                                held=(7, 0, 5, 9), scaling=1.8)
    ids = _routed("balanced", t, k, layer)
    plan = layer.plan(ids, rows)
    assert (int(plan.overflow) > 0) == (rows == 30)
    assert bool(plan.row_live.all()) == (rows == 30)
    ys = jax.random.normal(jax.random.key(1), (rows, d)).astype(dtype)
    ys = jnp.where(plan.row_live[:, None], ys, jnp.nan)
    gates = jax.random.uniform(jax.random.key(2), (t, k)).astype(dtype)
    dy = jax.random.normal(jax.random.key(3), (t, d)).astype(dtype)
    y, (d_ys, d_gates) = pulled(
        lambda ys, g: glm_moe._combine(ys, g, plan), dy, ys, gates)
    want_y, (want_ys, want_gates) = pulled(
        lambda ys, g: _plain_combine(ys, g, plan), dy, ys, gates)
    assert y.dtype == want_y.dtype and not bool(jnp.isnan(y).any())
    np.testing.assert_allclose(y.astype(jnp.float32), want_y.astype(jnp.float32),
                               **one_rounding(dtype))
    assert d_ys.dtype == want_ys.dtype == ys.dtype and d_gates.dtype == gates.dtype
    np.testing.assert_array_equal(d_ys, want_ys)
    np.testing.assert_array_equal(d_gates == 0, want_gates == 0)
    np.testing.assert_allclose(d_gates, want_gates, atol=0,
                               rtol=0 if dtype == "bfloat16" else 1e-5)
    assert not bool(jnp.isnan(d_ys).any()) and float(jnp.abs(d_gates).max()) > 0
    assert float(jnp.abs(d_ys[~plan.row_live]).sum()) == 0


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("gate_grad", [True, False], ids=["gates_in", "gates_out"])
def test_the_layers_gradients_are_those_of_the_plain_combine(
        dtype, gate_grad, monkeypatch):
    """Through the whole expert layer, the gates' gradient in or out: every
    leaf's gradient and the input's are what autodiff of the plain
    gather-and-sum gives, with a buffer that overflows: to the bit, but in
    float32 with the gates' gradient in, where the router's and the
    input's stand the order of a float32 sum apart (the test above)."""
    layer = glm_moe.ExpertLayer(width=16, n_routed=8, per_token=2, held=(0, 5, 6),
                                scaling=1.8, rows=40, gate_grad=gate_grad)
    p, st, _ = layer.init(jax.random.key(1), (S, 32))
    p = jax.tree_util.tree_map(lambda a: a * 8, p)
    x = jax.random.normal(jax.random.key(2), (4, S, 32)).astype(dtype)
    cot = jax.random.normal(jax.random.key(3), (4, S, 32)).astype(dtype)

    # float32 as one compiled program; bfloat16 an operation at a time, as
    # the claim is made: a compiled program keeps float32 where two
    # operations round to bfloat16 between them, and "to the bit" is 4e-3 off
    run = jitted if dtype == "float32" else highest

    def grads():  # a new function a call: traced under what is patched now
        def loss(p, x):
            y, new = layer.apply(p, st, x, train=True)
            return jnp.sum((y * cot).astype(jnp.float32)) + new["balance"], new
        return run(jax.grad(loss, argnums=(0, 1), has_aux=True), p, x)

    got, new = grads()
    assert int(new["overflow_rows"]) > 0
    monkeypatch.setattr(glm_moe, "_combine", _plain_combine)
    want, _ = grads()
    gaps = jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))),
        got, want)
    router = float(jnp.linalg.norm(got[0]["router"]))
    exact = dtype == "bfloat16" or not gate_grad
    assert max(jax.tree_util.tree_leaves(gaps)) <= (0 if exact else 1e-6 * router), gaps
    assert router > 0 and all(
        float(jnp.linalg.norm(g.astype(jnp.float32))) > 0
        for g in jax.tree_util.tree_leaves(got))


def _equations(jaxpr, path=""):
    """Every equation under `jaxpr`, with the name stack that leads to it."""
    for eqn in jaxpr.eqns:
        here = "/".join(filter(None, [path, str(eqn.source_info.name_stack)]))
        yield here, eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _equations(sub, here)


@pytest.mark.parametrize("gate_gradient", [True, False], ids=["gates_in", "gates_out"])
def test_a_rematerialised_layer_plans_once_and_combines_back_in_buffer_space(
        gate_gradient):
    """The jaxpr of a training gradient of one rematerialised expert
    decoder layer: the plan is named and kept, so `top_k` and the plan's
    sort appear once, the backward's `route` has the scores' matmul and
    no one-hot; what is named is what the backward reads (without the
    gates' gradient the slots' own rows are not); and under no `moe/` scope
    does the backward make anything with a row an assignment — no `(T * k,
    d)`, no `(T, k, d)`: the sum of a token's rows, `moe/combine` forward
    and `moe/dispatch` backward, is one scatter-add of the buffer's rows
    each (at these widths; the fused kernel where the shapes tile:
    tests/test_pallas_rowsum.py, tests/test_compiled_glm_sdar_programs.py)."""
    model, _ = build(gate_gradient=gate_gradient, row_buffer=48)
    layer = model._mtp_layer()
    p, st, _ = layer.init(jax.random.key(0), (S, 32))
    x = jax.random.normal(jax.random.key(1), (4, S, 32))
    t, k, d = 4 * S, 2, 32

    def loss(p, x):
        y, new = model._run(layer, p, st, x, True)
        return jnp.sum(y * y) + new["balance"]

    eqns = list(_equations(jax.make_jaxpr(jax.grad(loss))(p, x).jaxpr))
    count = lambda name: sum(e.primitive.name == name for _, e in eqns)  # noqa: E731
    assert count("top_k") == 1 and count("cumsum") == 2 and count("scatter") == 0
    assert count("sort") == 1  # the plan's inverse, once
    kept = [e.params["name"] for _, e in eqns if e.primitive.name == "name"]
    # ids, gates, the balance term's f, row_of, row_live, sizes; with the
    # gates' gradient rank and in_buffer too
    assert kept.count("moe_plan") == (8 if gate_gradient else 6)
    assert kept.count("attn_core") == 1
    backward = [(where, e) for where, e in eqns if where.startswith("transpose(")
                and "rematted_computation" not in where]
    recomputed = [(where, e) for where, e in eqns if "rematted_computation" in where]
    assert {e.primitive.name for where, e in recomputed
            if where.endswith("moe/route")} >= {"dot_general"}
    assert not any(e.primitive.name in ("top_k", "eq", "sort", "cumsum", "scatter-add")
                   for where, e in recomputed)
    per_assignment = {(t * k, d), (t, k, d)}
    shapes = lambda found: {tuple(v.aval.shape) for _, e in found  # noqa: E731
                            for v in e.outvars}
    combine = [(w, e) for w, e in backward if "moe/combine" in w]
    assert len(combine) >= 4 and (48, d) in shapes(combine)  # rows of dy
    dispatch = [(w, e) for w, e in backward if "moe/dispatch" in w]
    assert (t, d) in shapes(dispatch)  # the rows of d xs, summed by token
    assert not shapes([(w, e) for w, e in backward if "/moe/" in w]) & per_assignment
    sums = [w for w, e in eqns if e.primitive.name == "scatter-add" and "moe" in w
            and any(v.aval.shape == (t, d) for v in e.outvars)]
    assert [w.rsplit("/", 1)[1] for w in sums] == ["combine", "dispatch"]
    assert sums[0].startswith("jvp(") and sums[1].startswith("transpose(")


def test_the_bias_moves_by_u_toward_balance_and_takes_no_gradient(small):
    model = small.model
    loss, grads, new = system(small)
    assert set(small.params["layers"][1]["ffn"]) == {"router", "experts", "shared"}
    done = model.finish_step(new)
    for before, mid, after in zip(
            model._expert_states(small.state), model._expert_states(new),
            model._expert_states(done)):
        load = mid["load"]
        assert float(load.sum()) == 4 * S * 2
        np.testing.assert_array_equal(mid["bias"], before["bias"])
        np.testing.assert_allclose(
            after["bias"] - before["bias"],
            1e-3 * np.sign(float(load.mean()) - np.asarray(load)), atol=1e-9)
        assert float(after["load"].sum()) == 0
        assert int(after["rows_held"]) == int(load[jnp.asarray([0, 1, 2])].sum())
        assert float(after["load_max_over_mean"]) == pytest.approx(
            float(load.max() / load.mean()))
    # the loss does not move with the bias except through the routing: a
    # bias too small to flip a choice leaves it where it was
    nudged = jax.tree_util.tree_map_with_path(
        lambda path, a: a + 1e-9 if path[-1].key == "bias" else a, small.state)
    assert loss_of(small, state=nudged) == pytest.approx(loss, rel=1e-6)


def test_a_sliced_vocabulary_is_a_smaller_vocabulary(small):
    """The head and the embedding hold `vocab_size` rows and nothing else:
    the loss of a model built with the slice is the cross-entropy over the
    slice's logits alone."""
    logits = logits_of(small)
    assert logits.shape == (4, S, VOCAB) and logits.dtype == jnp.float32
    assert small.params["embed"]["w"].shape == (VOCAB, 32)
    assert small.params["head"].shape == (32, VOCAB)
    main = float(jnp.mean(ref.nll(logits, small.y)))
    terms = _reference_terms(small)
    assert main == pytest.approx(float(terms["main"]), rel=TOL)


def test_the_mtp_module_scores_position_i_against_token_i_plus_two(small):
    terms = _reference_terms(small)
    off, _ = build(mtp_weight=0.0)
    with_mtp = system(small)[0]
    without = system(small, off)[0]
    assert with_mtp - without == pytest.approx(0.3 * float(terms["mtp"]), rel=1e-4)
    # the last position has no target: its token changes nothing of the term
    y2 = small.y.at[:, 0].set((small.y[:, 0] + 1) % VOCAB)  # token 1: an input
    assert abs(loss_of(small, y=y2) - with_mtp) > 1e-4


# --------------------------------------------------------- the whole model

def test_loss_and_every_leafs_gradient_agree_with_the_reference(small):
    loss, grads, _ = system(small)
    want, want_grads = ref.loss_and_grads(
        small.arch, small.params, small.state, small.x, small.y)
    assert loss == pytest.approx(float(want), rel=TOL)
    gaps = leaf_gaps(grads, want_grads)
    assert len(gaps) == 66 and max(gaps.values()) < TOL, max(gaps, key=gaps.get)
    assert min(float(jnp.linalg.norm(g)) for g in
               jax.tree_util.tree_leaves(want_grads)) > 0  # every leaf is used


def test_a_share_that_leaves_the_gates_gradient_out_agrees_with_the_reference(small):
    """`gate_gradient=False` (a share that trains without the exchange):
    the forward is the published one, the loss the same number, and every
    leaf's gradient the reference's under the same key of `arch`; what the
    router keeps is the balance term's gradient alone."""
    model, arch = build(gate_gradient=False)
    loss, grads, _ = system(small, model)
    whole, whole_grads, _ = system(small)
    assert loss == whole
    want, want_grads = ref.loss_and_grads(
        arch, small.params, small.state, small.x, small.y)
    assert loss == pytest.approx(float(want), rel=TOL)
    gaps = leaf_gaps(grads, want_grads)
    assert len(gaps) == 66 and max(gaps.values()) < TOL, max(gaps, key=gaps.get)
    router = lambda g: float(jnp.linalg.norm(g["layers"][1]["ffn"]["router"]))  # noqa: E731
    assert router(grads) < 0.05 * router(whole_grads)  # alpha = 1e-4 is left
    # and the reference with the gradient in stands 100 x TOL off
    assert max(leaf_gaps(grads, whole_grads).values()) > 100 * TOL


def _held_share(gate_gradient, steps=50):
    """Mean over the expert layers and over steps 21..`steps` of rows held
    over the balanced share, training the toy at its published init."""
    model, _ = build(gate_gradient=gate_gradient)
    optimizer = zoo.make_optimizer(**HYPER)
    state = zoo.init_state(model, jax.random.key(5), (S,), optimizer)
    tokens = jax.random.randint(jax.random.key(6), (8, S + 1), 0, VOCAB)
    step = zoo.make_train_step(model, optimizer, 1, None)
    balanced = 4 * S * 2 / 8 * 3
    seen = []
    for i in range(steps):
        at = 4 * (i % 2)
        state, _ = step(state, tokens[at: at + 4, :-1], tokens[at: at + 4, 1:])
        if i >= 20:
            rows = model.counters(state.model_state)["moe_rows_held"]
            seen.append(sum(rows) / len(rows) / balanced)
    return sum(seen) / len(seen)


def test_a_share_that_trains_its_gates_pulls_the_tokens_onto_what_it_holds():
    """Why `gate_gradient=False` exists (PERF.md section 6, PR 32): only a
    held expert's gate has a gradient in a share, so the router learns
    that only those lower the loss, and the held experts' rows rise above
    the balanced share while it trains; with the gates' gradient left out
    they stay at it."""
    with_gradient, without = _held_share(True), _held_share(False)
    assert with_gradient > 1.1 and with_gradient > without + 0.08
    assert abs(without - 1) < 0.08


def test_logits_and_hidden_states_agree_with_the_reference(small):
    want = ref.eval_logits(small.arch, small.params, small.state, small.x)
    got = logits_of(small)
    np.testing.assert_allclose(got, want, atol=TOL * float(jnp.max(jnp.abs(want))))
    hidden, _ = jitted(small.model.hidden_states, small.params, small.state,
                       small.x)
    for a, b in zip(hidden, ref.hidden_states(
            small.arch, small.params, small.state, small.x), strict=True):
        np.testing.assert_allclose(a, b, atol=TOL * float(jnp.max(jnp.abs(b))))


def test_three_steps_losses_and_held_rows_agree_with_the_reference(small):
    want = ref.train_report(small.arch, small.params, small.state, small.x,
                            small.y, steps=3, **HYPER)
    losses, seen, state = steps(small)
    assert losses == pytest.approx(want["losses"], rel=TOL)
    assert [c["moe_rows_held"] for c in seen] == want["rows_held"]
    assert want["losses"][2] < want["losses"][1] < want["losses"][0]
    # two steps alone keep no moment: the same first two losses
    two = ref.train_losses(small.arch, small.params, small.state, small.x,
                           small.y, steps=2, **HYPER)
    assert two == pytest.approx(want["losses"][:2], rel=1e-6)
    assert model_overflow(small.model, state) == 0


def model_overflow(model, state):
    return sum(model.counters(state.model_state)["moe_overflow_rows"])


def test_accumulation_settles_the_bias_once_a_step_over_all_its_tokens(small):
    """Two microbatches of two sequences: the same loads as one batch of
    four, one move of the bias."""
    out = []
    for accum in (1, 2):
        state, step = stepped(small, accum=accum)
        state, _ = highest(step, state, small.x, small.y)
        out.append(state.model_state)
    for a, b in zip(small.model._expert_states(out[0]),
                    small.model._expert_states(out[1])):
        np.testing.assert_array_equal(a["bias"], b["bias"])
        assert int(a["rows_held"]) == int(b["rows_held"])


FAULTS = ["shared_dropped", "scaling_skipped", "softmax_for_sigmoid",
          "q_norm_missing", "rope_off", "mtp_off", "gates_from_biased_scores"]


@pytest.mark.parametrize("fault", FAULTS)
def test_a_fault_in_the_system_fails_the_comparison(small, fault, monkeypatch):
    # a patch is seen by a new trace alone; another model is another program
    model, fresh = small.model, fault not in ("scaling_skipped", "mtp_off")
    # before any patch: some of them reach the reference's `jax.numpy` too
    want, want_grads = ref.loss_and_grads(
        small.arch, small.params, small.state, small.x, small.y)
    if fault == "shared_dropped":
        class Nothing(nn.GatedMLP):
            def apply(self, params, state, x, train=False):
                return jnp.zeros_like(x), state

        monkeypatch.setattr(glm_moe.ExpertLayer, "_shared",
                            lambda self: Nothing(self.width))
    elif fault == "scaling_skipped":
        model, _ = build(routed_scaling_factor=1.0)
    elif fault == "softmax_for_sigmoid":
        monkeypatch.setattr(jax.nn, "sigmoid",
                            lambda a: jax.nn.softmax(a, axis=-1))
    elif fault == "q_norm_missing":
        real = glm_moe._norm
        monkeypatch.setattr(
            glm_moe, "_norm",
            lambda eps, scale, x: x if scale.shape == (12,) else real(eps, scale, x))
    elif fault == "rope_off":
        monkeypatch.setattr(glm_moe, "rope", lambda x, theta: x)
    elif fault == "mtp_off":
        model, _ = build(mtp_weight=0.0)
    elif fault == "gates_from_biased_scores":
        real_take = jnp.take_along_axis
        monkeypatch.setattr(
            glm_moe.jnp, "take_along_axis",
            lambda a, i, axis: real_take(a, i, axis) + 0.01 if a.shape[-1] == 8
            else real_take(a, i, axis))
    loss, grads, _ = system(small, model, fresh=fresh)
    loss_gap = abs(loss / float(want) - 1)
    grad_gap = max(leaf_gaps(grads, want_grads).values())
    assert max(loss_gap, grad_gap) > 100 * TOL, (loss_gap, grad_gap)


def test_a_float8_reference_fails_the_comparison(small, monkeypatch):
    want = ref.train_losses(small.arch, small.params, small.state, small.x,
                            small.y, steps=2, **HYPER)
    monkeypatch.setattr(ref, "ROUND", jnp.float8_e4m3fn)
    ref._programs.cache_clear()
    try:
        low = ref.train_losses(small.arch, small.params, small.state, small.x,
                               small.y, steps=2, **HYPER)
    finally:
        monkeypatch.undo()
        ref._programs.cache_clear()
    assert max(abs(a / b - 1) for a, b in zip(low, want)) > 100 * TOL


# ------------------------------------------------- bf16, the step factories

def test_bfloat16_activations_change_rounding_only(small):
    """The cell's precision: bf16 activations over float32 masters, whose
    gradients stay float32."""
    loss, _, _ = system(small)
    half = dataclasses.replace(small.model, dtype="bfloat16")
    loss3, grads3, _ = system(small, half)
    assert abs(loss3 / loss - 1) < 5e-3
    assert all(g.dtype == jnp.float32 for g in jax.tree_util.tree_leaves(grads3))


def test_the_published_model_has_the_counted_parameters():
    model = glm_moe.glm_4_7_flash(num_hidden_layers=5, vocab_size=19360,
                                  held_experts=range(8), row_buffer=16384)
    params, state = jax.eval_shape(
        lambda k: model.init(k, (4096,))[:2], jax.random.key(0))
    count = lambda t: sum(l.size for l in jax.tree_util.tree_leaves(t))  # noqa: E731
    attn = 2048 * 768 + 768 * 5120 + 2048 * 576 + 512 * 8960 + 5120 * 2048
    assert count(params["layers"][1]["attn"]) == attn + 768 + 512 == 21_759_232
    assert count(params["layers"][0]) == 84_677_888  # the dense layer
    assert count(params["layers"][1]) == 106_829_056  # 8 of 64 experts + shared
    assert count(params["mtp"]) == 115_221_760
    assert count(params) == 706_516_480
    moe = dict(experts_held=8, experts_published=64, experts_per_token=4,
               expert_layers=5, row_buffer=16384, tokens_per_step=16384)
    # 4,096 positions of 256-wide heads tile at 512: the kernels visit the
    # 36 tiles at or below the diagonal of 64; off the chip the 512-query
    # blocks compute as many; RoPE's 64 features are half a register, so the
    # plain body turns them on either platform
    assert model.describe(4 * 4096, 4096, "tpu") == dict(
        moe, attention_core="fused", attention_tiles_visited=36,
        attention_tiles_total=64, rope_turn="plain")
    assert model.describe(4 * 4096, 4096, "cpu") == dict(
        moe, attention_core="blocks", attention_tiles_visited=36,
        attention_tiles_total=64, rope_turn="plain")
    whole = glm_moe.glm_4_7_flash()
    assert (whole.n_layers, whole.vocab, len(whole.experts.held)) == (47, 154880, 64)
    with pytest.raises(ValueError, match="distinct ids"):
        glm_moe.glm_4_7_flash(held_experts=[0, 64])


@pytest.mark.parametrize("factory", ["comm_psum", "comm_ring", "fused_update",
                                     "zero3", "pipeline"])
def test_the_other_step_factories_refuse_the_model_by_name(host_devices, factory):
    model, _ = build()
    opt = zoo.make_optimizer(**HYPER)
    mesh = plan_lib.ExecutionPlan(data=2).validate().make_mesh(
        devices=host_devices[:2])
    fused = config_lib.FusedStepConfig(update=True)
    comm = config_lib.CommConfig(impl="ring")
    with pytest.raises(zoo.StepStateUnsupported, match="GlmMoe"):
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
        host_devices, tmp_path):
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
    _, losses = zoo.train(
        model, tokens[:, :-1], tokens[:, 1:], in_shape=(S,), epochs=2,
        batch_size=4, mesh=mesh, **HYPER, seed=3, verbose=False,
        metrics=Rec(), obs=obs)
    assert all(math.isfinite(v) for v in losses) and losses[1] < losses[0]
    last = Rec.epochs[-1]
    assert len(last["moe_rows_held"]) == 3 and sum(last["moe_overflow_rows"]) == 0
    assert all(m >= 1.0 for m in last["moe_load_max_over_mean"])
    # every row of the buffer on the plain path (this CPU), a value a layer;
    # and the program's own copy of the record is the recorder's
    assert last["moe_sum_rows_visited"] == [4 * S * 2] * 3
    assert obs_lib.epochs.newest() == [last]
    (event,) = [f for k, f in Journal.events if k == "zoo_moe"]
    assert (event["experts_held"], event["experts_published"],
            event["tokens_per_step"], event["row_buffer"]) == (3, 8, 4 * S, 4 * S * 2)
    # 16 positions in blocks of 8 queries, on the CPU: 3 of 4 tiles
    assert (event["attention_core"], event["attention_tiles_visited"],
            event["attention_tiles_total"]) == ("blocks", 3, 4)
    assert event["rope_turn"] == "plain"  # a CPU, and a toy head besides


def test_the_scopes_are_the_ones_the_catalog_reads_through_rematerialisation():
    """Layer scopes survive `jax.checkpoint` (which names a layer twice in
    its backward and adds scopes of its own) and `jnp.einsum` (which opens
    one with its subscripts)."""
    from parallel_cnn_tpu.obs import programs

    of = programs.scope_of
    assert of("jit(step)/grad/transpose(jvp(l1))/grad/jvp(l1)/checkpoint/"
              "rematted_computation/moe/dispatch/gather") == ("l1/moe/dispatch", "bwd")
    assert of("jit(step)/grad/transpose(jvp(mtp))/l0/grad/jvp(mtp)/l0/checkpoint/"
              "attn/core/checkpoint/nhqk,nkhd->nqhd/dot_general") == (
        "mtp/l0/attn/core", "bwd")
    assert of("jit(step)/grad/jvp(l0)/attn/core/checkpoint/jit(_where)/select_n") == (
        "l0/attn/core", "fwd")
    assert of("jit(step)/grad/transpose(jvp(grad))/jvp()/checkpoint/"
              "rematted_computation/norm/mul") == ("norm", "bwd")
    # the fused core's kernels (ops/pallas_attention.py), as the step compiled
    # for a v5e names them: inside the `lax.platform_dependent`'s branch, under
    # the kernel's own name; the backward through the rematerialised layer
    assert of("jit(step)/grad/jvp(l1)/attn/core/cond/branch_0_fun/"
              "causal_attention_fwd/pallas_call") == ("l1/attn/core", "fwd")
    assert of("jit(step)/grad/transpose(jvp(l1))/grad/jvp(l1)/checkpoint/attn/core/"
              "cond/branch_0_fun/causal_attention_bwd/pallas_call") == (
        "l1/attn/core", "bwd")
    assert of("jit(step)/grad/transpose(jvp(mtp))/l0/grad/jvp(mtp)/l0/checkpoint/"
              "attn/core/cond/branch_0_fun/causal_attention_bwd/pallas_call") == (
        "mtp/l0/attn/core", "bwd")
    assert of("jit(step)/grad/transpose(jvp(l1))/grad/jvp(l1)/checkpoint/attn/core/"
              "cond/branch_0_fun/reshape") == ("l1/attn/core", "bwd")
    # only the pair a branch opens names no layer: a scope somebody called
    # `cond` stays, and so does a `branch_0_fun` that no `cond` precedes
    assert of("jit(step)/grad/jvp(cond)/conv/dot_general") == ("cond/conv", "fwd")
    assert of("jit(step)/grad/jvp(l1)/branch_0_fun/mul") == (
        "l1/branch_0_fun", "fwd")
    model, _ = build()
    opt = zoo.make_optimizer(**HYPER)
    state = jax.eval_shape(lambda k: zoo.init_state(model, k, (S,), opt),
                           jax.random.key(0))
    x = jax.ShapeDtypeStruct((4, S), jnp.int32)
    text = zoo.make_train_step(model, opt, 1, None).lower(state, x, x).as_text(
        debug_info=True)
    import re

    scopes = {of(name)[0] for name in re.findall(r'loc\("([^"]*)"', text)}
    for want in ("embed", "l0/attn/q", "l0/attn/kv", "l0/attn/rope",
                 "l0/attn/core", "l0/attn/o", "l0/mlp", "l1/moe/route",
                 "l1/moe/dispatch", "l1/moe/experts", "l1/moe/combine",
                 "l1/moe/shared", "mtp/proj", "mtp/l0/moe/experts", "mtp/head",
                 "norm", "head", "loss", "optimizer"):
        assert want in scopes, (want, sorted(scopes))
    assert not any("checkpoint" in s or "->" in s for s in scopes)
