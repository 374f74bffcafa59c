"""nn/afmoe.py (Trinity-Mini's mechanisms: sliding-window and full
attention mixed over grouped key/value heads, the gated attention output,
four norms a layer, the scaled embedding, sigmoid-routed experts beside a
shared one under a balanced selection bias) at toy widths on the CPU,
seeded random weights, against the plain float32 reference the benchmark
keeps (benchmark/reference/afmoe.py): the pieces, the share, the layer-kind
table, the whole model's logits, loss, gradients, AdamW steps and held-row
counts; the faults the chip's controls plant; which step factories run the
model; the scopes and the `zoo_moe` event."""

import dataclasses
import math
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.reference import afmoe as ref  # noqa: E402
from benchmark.tools import compare_afmoe  # noqa: E402
from benchmark.tools.compare_reference import leaf_gaps  # noqa: E402
from parallel_cnn_tpu import config as config_lib, plan as plan_lib  # noqa: E402
from parallel_cnn_tpu.nn import afmoe, glm_moe  # noqa: E402
from parallel_cnn_tpu.train import zoo  # noqa: E402
from token_family import (HYPER, highest, jitted, logits, loss as loss_of,  # noqa: E402
                          steps, system, toy)

S, VOCAB, WINDOW = 32, 96, 8
KINDS = [afmoe.SLIDING, afmoe.SLIDING, afmoe.FULL, afmoe.SLIDING]
ARCH = {
    "hidden_size": 32, "intermediate_size": 48, "moe_intermediate_size": 16,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
    "num_experts_per_tok": 2, "num_shared_experts": 1, "num_hidden_layers": 4,
    "num_dense_layers": 1, "layer_types": KINDS, "sliding_window": WINDOW,
    "rms_norm_eps": 1e-5, "rope_theta": 1e4, "route_scale": 2.826,
    "load_balance_coeff": 1e-3, "vocab_size": VOCAB, "router_experts": 8,
    "held_experts": [0, 1, 2], "row_buffer": None, "balance_weight": 0.0,
    "gate_gradient": True, "embed_scale": 32 ** 0.5,
}
# float32 on both sides at the highest matmul precision: what differs is the
# order of float32 sums. Every fault below moves 100 x TOL.
TOL = 2e-5


def build(**over):
    arch = dict(ARCH, **over)
    return afmoe.afmoe(
        vocab_size=arch["vocab_size"], hidden_size=arch["hidden_size"],
        intermediate_size=arch["intermediate_size"],
        moe_intermediate_size=arch["moe_intermediate_size"],
        num_hidden_layers=arch["num_hidden_layers"],
        num_dense_layers=arch["num_dense_layers"],
        num_attention_heads=arch["num_attention_heads"],
        num_key_value_heads=arch["num_key_value_heads"],
        head_dim=arch["head_dim"], num_experts=arch["router_experts"],
        num_experts_per_tok=arch["num_experts_per_tok"],
        layer_types=arch["layer_types"], sliding_window=arch["sliding_window"],
        route_scale=arch["route_scale"],
        load_balance_coeff=arch["load_balance_coeff"],
        rope_theta=arch["rope_theta"], rms_norm_eps=arch["rms_norm_eps"],
        held_experts=arch["held_experts"], row_buffer=arch["row_buffer"],
        balance_weight=arch["balance_weight"],
        gate_gradient=arch["gate_gradient"], dtype="float32", q_block=8,
        loss_block=16), arch


@pytest.fixture(scope="module")
def small():
    """The toy model with every PARAMETER leaf drawn at random (weights of
    std 1 / sqrt(fan_in), gains 1 + 0.1 n) and every selection bias 0.01 n."""
    s = toy(*build(), seq=S)
    s.want_loss = float(ref.loss_and_grads(s.arch, s.params, s.state, s.x, s.y)[0])
    return s


# ------------------------------------------------------------ the pieces

@pytest.mark.parametrize("kind", [afmoe.SLIDING, afmoe.FULL])
def test_attention_of_either_kind_agrees_with_the_reference(small, kind):
    att = small.model.attention(kind)
    p = small.params["layers"][KINDS.index(kind)]["attn"]
    assert set(p) == {"q", "k", "v", "gate", "o", "q_norm", "k_norm"}
    x = jax.random.normal(jax.random.key(5), (2, S, 32))
    got = jitted(lambda p, x: att.apply(p, {}, x)[0], p, x)
    want = jitted(lambda p, x: ref.attention(small.arch, kind, p, x), p, x)
    np.testing.assert_allclose(got, want, atol=TOL * float(jnp.max(jnp.abs(want))))


def test_the_layer_kind_table_rope_and_a_window_only_where_sliding(small):
    """A sliding layer has the window and RoPE, a full layer neither: its
    last position's output does not move when the earlier positions are
    shuffled (no position anywhere: a set of keys), a sliding layer's does."""
    model = small.model
    local, full = model.attention(afmoe.SLIDING), model.attention(afmoe.FULL)
    assert (local.window, local.rotary) == (WINDOW, True)
    assert (full.window, full.rotary) == (None, False)
    assert [(l.attn.window, l.attn.rotary) for l in model._layers()] == [
        (WINDOW, True), (WINDOW, True), (None, False), (WINDOW, True)]
    assert [l.ffn_scope for l in model._layers()] == ["mlp", "moe", "moe", "moe"]
    p = small.params["layers"][2]["attn"]
    x = jax.random.normal(jax.random.key(6), (1, S, 32))
    order = jnp.concatenate([jax.random.permutation(jax.random.key(7), S - 1),
                             jnp.array([S - 1])])
    last = lambda att: jax.jit(lambda p, x: att.apply(p, {}, x)[0][:, -1])  # noqa: E731
    for att, moves in ((full, False), (dataclasses.replace(full, rotary=True), True),
                       (dataclasses.replace(local, window=None), True)):
        a, b = highest(last(att), p, x), highest(last(att), p, x[:, order])
        assert (float(jnp.max(jnp.abs(a - b))) > 1e-3) == moves
    # under the window the last position does not see the first ones at all
    far = x.at[:, : S - WINDOW].set(0.0)
    a, b = highest(last(local), p, x), highest(last(local), p, far)
    np.testing.assert_allclose(a, b, atol=1e-6)
    with pytest.raises(ValueError, match="one of"):
        build(layer_types=KINDS[:3])
    with pytest.raises(ValueError, match="one of"):
        build(layer_types=[*KINDS[:3], "linear_attention"])


def test_the_expert_layer_under_this_router_agrees_with_the_reference(small):
    layer = small.model.experts
    p, st = small.params["layers"][1]["ffn"], small.state["layers"][1]
    assert set(p) == {"router", "experts", "shared"}
    assert (layer.scoring, layer.scaling, layer.n_shared, layer.balance,
            layer.bias_step) == ("sigmoid", 2.826, 1, 0.0, 1e-3)
    x = jax.random.normal(jax.random.key(6), (4, S, 32))
    got, new = jitted(lambda p, st, x: layer.apply(p, st, x, train=True), p, st, x)
    want, balance, load = jitted(
        lambda p, b, x: ref.experts(small.arch, p, b, x), p, st["bias"], x)
    np.testing.assert_allclose(got, want, atol=TOL * float(jnp.max(jnp.abs(want))))
    np.testing.assert_allclose(new["load"], load)
    assert float(new["balance"]) == float(balance) == 0.0
    # the gates of a token sum to route_scale over its chosen, held or not
    _, gates, _, _ = jitted(lambda r, b, x: layer.route(r, b, x, 4),
                            p["router"], st["bias"], x.reshape(-1, 32))
    np.testing.assert_allclose(gates.sum(axis=1), 2.826, rtol=1e-6)
    # the bias moves by the step's load, as the reference moves it
    done = layer.finish_step(new)
    moved = ref.moved_bias(small.arch, {"layers": [st]}, [load])["layers"][0]
    np.testing.assert_allclose(done["bias"], moved["bias"], atol=1e-7)
    assert float(jnp.max(jnp.abs(done["bias"] - st["bias"]))) == pytest.approx(1e-3)


def test_the_eight_shares_routed_parts_and_the_shared_expert_once_add_up():
    """One layer of 16 experts cut eight ways: each share routes over all
    16, normalises the gates over all the chosen, and adds its own two
    experts' part to the whole shared expert. The eight routed parts plus
    the shared expert ONCE are what the uncut reference gives."""
    whole = glm_moe.ExpertLayer(width=16, n_routed=16, per_token=4,
                                held=tuple(range(16)), n_shared=1, scaling=2.826,
                                bias_step=1e-3, balance=0.0, scoring="sigmoid")
    shape, key = (S, 32), jax.random.key(7)
    p, st, _ = whole.init(key, shape)
    st = dict(st, bias=0.01 * jax.random.normal(jax.random.key(8), (16,)))
    x = jax.random.normal(jax.random.key(9), (2, S, 32)) * 4.0
    arch = dict(ARCH, router_experts=16, num_experts_per_tok=4,
                held_experts=list(range(16)))
    want, _, _ = jitted(lambda p, b, x: ref.experts(arch, p, b, x), p, st["bias"], x)
    shared = jitted(ref.gated_mlp, p["shared"], x)
    total = shared
    for i in range(8):
        share = dataclasses.replace(whole, held=(2 * i, 2 * i + 1))
        sp, _, _ = share.init(key, shape)  # an expert's weights come from its id
        for m in ("gate", "up", "down"):
            np.testing.assert_array_equal(sp["experts"][m], p["experts"][m][2 * i: 2 * i + 2])
        np.testing.assert_array_equal(sp["router"], p["router"])
        for m in ("gate", "up", "down"):
            np.testing.assert_array_equal(sp["shared"][m], p["shared"][m])
        total = total + jitted(share.apply, sp, st, x)[0] - shared
    assert float(jnp.max(jnp.abs(want - shared))) > 0.01
    np.testing.assert_allclose(total, want, atol=2e-7)
    uncut, _ = jitted(whole.apply, p, st, x)
    np.testing.assert_allclose(uncut, want, atol=2e-7)


def test_the_embedding_leaves_times_the_square_root_of_the_width(small):
    emb = small.model._embed()
    assert emb.scale == pytest.approx(32 ** 0.5)
    got = jitted(emb.apply, small.params["embed"], {}, small.x)[0]
    np.testing.assert_allclose(
        got, small.params["embed"]["w"][small.x] * 32 ** 0.5, rtol=1e-6)
    assert build()[0].embed_scale == pytest.approx(5.656854)


# ------------------------------------------------------- the whole model

def test_loss_and_every_leafs_gradient_agree_with_the_reference(small):
    loss, grads, new = system(small)
    want, want_grads = ref.loss_and_grads(
        small.arch, small.params, small.state, small.x, small.y)
    assert loss == pytest.approx(float(want), rel=TOL)
    gaps = leaf_gaps(grads, want_grads)
    assert len(gaps) == 4 * 11 + 3 + 3 * 7 + 3  # every leaf has a gradient
    assert max(gaps.values()) < TOL, max(gaps, key=gaps.get)
    # the mean next-token cross-entropy and nothing else
    z = logits(small)
    nll = jax.nn.logsumexp(z, -1) - jnp.take_along_axis(z, small.y[..., None], -1)[..., 0]
    assert loss == pytest.approx(float(jnp.mean(nll)), rel=1e-6)


def test_a_share_that_leaves_the_gates_gradient_out_agrees_with_the_reference():
    s = toy(*build(gate_gradient=False), seq=S)
    loss, grads, _ = system(s)
    want, want_grads = ref.loss_and_grads(s.arch, s.params, s.state, s.x, s.y)
    assert loss == pytest.approx(float(want), rel=TOL)
    assert max(leaf_gaps(grads, want_grads).values()) < TOL
    # no gates' gradient and no balance term: the routers take none at all
    for layer in grads["layers"][1:]:
        assert float(jnp.max(jnp.abs(layer["ffn"]["router"]))) == 0.0


def test_logits_and_hidden_states_agree_with_the_reference(small):
    want = ref.eval_logits(small.arch, small.params, small.state, small.x)
    got = logits(small)
    assert got.shape == (4, S, VOCAB) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, atol=TOL * float(jnp.max(jnp.abs(want))))
    hidden, _ = jitted(small.model.hidden_states, small.params, small.state,
                       small.x)
    for a, b in zip(hidden, ref.hidden_states(
            small.arch, small.params, small.state, small.x), strict=True):
        assert a.shape == (4, S, 32)
        np.testing.assert_allclose(a, b, atol=TOL * float(jnp.max(jnp.abs(b))))


def test_three_steps_losses_and_held_rows_agree_with_the_reference(small):
    """Two AdamW updates and two moves of the selection bias between three
    losses, through `zoo.make_train_step` (the GSPMD step)."""
    want = ref.train_report(small.arch, small.params, small.state, small.x,
                            small.y, steps=3, first_grads=True, **HYPER)
    losses, seen, state = steps(small)
    rows = [c["moe_rows_held"] for c in seen]
    assert losses == pytest.approx(want["losses"], rel=TOL)
    # step 1's gradient, handed over as a direction (bfloat16) on request
    first = want.pop("first_grads")
    assert jax.tree_util.tree_structure(first) == jax.tree_util.tree_structure(
        small.params)
    assert all(g.dtype == jnp.bfloat16 for g in jax.tree_util.tree_leaves(first))
    _, exact = ref.loss_and_grads(small.arch, small.params, small.state,
                                  small.x, small.y)
    assert max(leaf_gaps(first, exact).values()) < 1e-2
    assert set(want) == {"losses", "rows_held", "terms"}
    assert rows == want["rows_held"] and len(rows[0]) == 3
    assert losses[2] < losses[1] < losses[0]
    two = ref.train_losses(small.arch, small.params, small.state, small.x,
                           small.y, steps=2, **HYPER)
    assert two == pytest.approx(want["losses"][:2], rel=1e-6)
    assert sum(seen[-1]["moe_overflow_rows"]) == 0
    assert [t["balance"] for t in want["terms"]] == [0.0] * 3


FAULTS = [*compare_afmoe.FAULTS, "window_off_by_one", "rope_off"]


def _planted(fault):
    """`compare_afmoe.control` for a model the test builds itself: the
    patches are the module's, the model it yields is not used (but for the
    one fault that is an argument of the factory)."""
    cfg = {"factory": {"module": "parallel_cnn_tpu.nn.afmoe", "name": "trinity_mini",
                       "kwargs": {"layer_types": KINDS[:1], "num_dense_layers": 1,
                                  "vocab_size": 8}}}
    return compare_afmoe.control(cfg, ref, fault)


@pytest.mark.parametrize("fault", FAULTS)
def test_a_fault_in_the_system_fails_the_comparison(small, fault, monkeypatch):
    """The faults the chip's controls plant (benchmark/tools/
    compare_afmoe.py:control, the very context the tool uses) and two more,
    each against the reference's loss (one against its gradients)."""
    want = small.want_loss
    if fault == "embed_scale_off":
        with _planted(fault) as faulty:
            assert faulty.embed_scale == 1.0
        loss = loss_of(small, dataclasses.replace(small.model, embed_scale=1.0))
    elif fault in compare_afmoe.FAULTS:
        with _planted(fault):
            if fault == "absent_gates":  # moves the loss by 9e-4: the gradients
                loss, grads, _ = system(small, build()[0], fresh=True)
                _, want_grads = ref.loss_and_grads(
                    small.arch, small.params, small.state, small.x, small.y)
                assert max(leaf_gaps(grads, want_grads).values()) > 100 * TOL
                return
            loss = loss_of(small, build()[0], fresh=True)
    elif fault == "window_off_by_one":
        loss = loss_of(small, dataclasses.replace(
            small.model, attn=dataclasses.replace(small.model.attn,
                                                  window=WINDOW + 1)))
    else:
        monkeypatch.setattr(afmoe, "rope", lambda x, theta: x)
        loss = loss_of(small, build()[0], fresh=True)
    assert abs(loss / want - 1) > 100 * TOL, (fault, loss, want)


def test_the_control_puts_everything_back(small):
    before = loss_of(small, build()[0], fresh=True)
    for fault in compare_afmoe.FAULTS:
        with _planted(fault):
            pass
    assert loss_of(small, build()[0], fresh=True) == before
    assert before == pytest.approx(small.want_loss, rel=TOL)
    from benchmark.reference import glm_moe as rounded

    with _planted("float8_e4m3fn"):
        assert rounded.ROUND == jnp.dtype("float8_e4m3fn")
    assert rounded.ROUND is None


def test_a_float8_reference_fails_the_comparison(small):
    want = small.want_loss
    with _planted("float8_e4m3fn"):
        low = float(ref.loss_and_grads(
            small.arch, small.params, small.state, small.x, small.y)[0])
    assert abs(low / want - 1) > 100 * TOL


def test_bfloat16_activations_change_rounding_only(small):
    loss = loss_of(small, dataclasses.replace(small.model, dtype="bfloat16"))
    assert 1e-7 < abs(loss / small.want_loss - 1) < 2e-2


# ----------------------------------------------- the published model's size

def test_the_published_model_and_the_share_have_the_counted_parameters():
    def count(model, s):
        params = jax.eval_shape(lambda k: model.init(k, (s,))[0], jax.random.key(0))
        return sum(l.size for l in jax.tree_util.tree_leaves(params))

    attention = 3 * 2048 * 4096 + 2 * 2048 * 512 + 2 * 128  # 27.26 M
    norms = 4 * 2048
    dense = attention + norms + 3 * 2048 * 6144
    sparse = lambda held: (attention + norms + 2048 * 128  # noqa: E731
                           + (held + 1) * 3 * 2048 * 1024)
    share = afmoe.trinity_mini(
        layer_types=[afmoe.SLIDING] * 4 + [afmoe.FULL], num_dense_layers=1,
        vocab_size=25024, held_experts=range(16), row_buffer=32768,
        gate_gradient=False)
    assert count(share, 16384) == dense + 4 * sparse(16) + 2 * 25024 * 2048 + 2048 \
        == 705_473_792
    whole = afmoe.trinity_mini()
    period = afmoe.trinity_mini(layer_types=whole.layer_types[:4], vocab_size=8)
    assert count(period, 4096) == 2 * dense + 2 * sparse(128) + 2 * 8 * 2048 + 2048
    total = 2 * dense + 30 * sparse(128) + 2 * 200192 * 2048 + 2048
    assert 26.0e9 < total < 26.3e9  # "26B-A3B"
    assert whole.layer_types == (afmoe.SLIDING,) * 3 + (afmoe.FULL,) \
        + whole.layer_types[4:] and len(whole.layer_types) == 32
    assert (whole.first_dense, whole.vocab, len(whole.experts.held)) == (2, 200192, 128)
    assert (whole.attn.window, whole.attn.theta, whole.eps) == (2048, 1e4, 1e-5)
    with pytest.raises(ValueError, match="do not divide"):
        afmoe.GatedGQA(heads=32, kv_heads=5)
    with pytest.raises(ValueError, match="route_norm"):
        afmoe.trinity_mini(route_norm=False)
    with pytest.raises(ValueError, match="multi-token"):
        dataclasses.replace(whole, mtp_modules=1)


# ----------------------------------------------------------- step factories

@pytest.mark.parametrize("factory", ["comm_psum", "comm_ring", "fused_update",
                                     "zero3", "pipeline"])
def test_the_other_step_factories_refuse_the_model_by_name(host_devices, factory):
    """By the refusal they have for a model whose state a step settles
    (`finish_step`); the model has no random layer."""
    model, _ = build()
    _, state, _ = model.init(jax.random.key(0), (S,))
    from parallel_cnn_tpu.nn.layers import has_random_state

    assert not has_random_state(state) and hasattr(model, "finish_step")
    opt = zoo.make_optimizer(**HYPER)
    mesh = plan_lib.ExecutionPlan(data=2).validate().make_mesh(
        devices=host_devices[:2])
    fused = config_lib.FusedStepConfig(update=True)
    comm = config_lib.CommConfig(impl="ring")
    with pytest.raises((zoo.StepStateUnsupported, zoo.RandomLayerUnsupported),
                       match="AfMoe"):
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
        batch_size=4, mesh=mesh, **HYPER, seed=3, verbose=False, metrics=Rec(),
        obs=obs)
    assert all(math.isfinite(v) for v in losses)
    last = Rec.epochs[-1]
    assert len(last["moe_rows_held"]) == 3 and sum(last["moe_overflow_rows"]) == 0
    assert all(m >= 1.0 for m in last["moe_load_max_over_mean"])
    assert len(last["moe_sum_rows_visited"]) == 3
    # every expert layer's bias has moved by two epochs of two steps
    for layer in state.model_state["layers"][1:]:
        assert 0 < float(jnp.max(jnp.abs(layer["bias"]))) <= 4e-3 + 1e-9
    assert state.model_state["layers"][0] == {}
    (event,) = [f for k, f in Journal.events if k == "zoo_moe"]
    assert (event["experts_held"], event["experts_published"],
            event["tokens_per_step"], event["row_buffer"],
            event["expert_layers"]) == (3, 8, 4 * S, 4 * S * 2, 3)
    assert event["attention_layer_kinds"] == KINDS
    assert (event["attention_core"], event["attention_window"],
            event["attention_tile"]) == ("blocks", WINDOW, 8)
    assert event["rope_turn"] == "plain"  # a CPU, and a toy head besides
    # 32 positions in turns of 8 queries, on the CPU: a turn of a full layer
    # reads up to its end (1 + 2 + 3 + 4 tiles), a window of 8 two tiles but
    # the first turn's one
    assert event["attention_tiles_visited_by_kind"] == {
        afmoe.SLIDING: 7, afmoe.FULL: 10}
    assert event["attention_tiles_visited"] == 10 and event["attention_tiles_total"] == 16
    assert event["attention_pairs_allowed_by_kind"] == {
        afmoe.SLIDING: 8 * 9 // 2 + 24 * 8, afmoe.FULL: 32 * 33 // 2}
    # what the turns execute: 8 queries by the keys from the window's far end
    # of the first to the last one's own (8, then 15 three times; 8 + 16 + 24 + 32)
    assert event["attention_pairs_computed_by_kind"] == {
        afmoe.SLIDING: 8 * (8 + 3 * 15), afmoe.FULL: 8 * 80}
    assert event["attention_pairs_computed"] == 8 * 80
    assert (event["attention_pairs_computed_forward_by_kind"]
            == event["attention_pairs_computed_by_kind"])
    at_size = afmoe.trinity_mini(
        layer_types=[afmoe.SLIDING, afmoe.FULL], num_dense_layers=1,
        vocab_size=25024, held_experts=range(16), row_buffer=32768,
        gate_gradient=False).describe(16384, 16384, "tpu")
    # the kernels at the cell's shape (PR 49): 127.5 tile areas of 150 steps
    # under the window, 516 of 528 without
    assert at_size["attention_tiles_visited_by_kind"] == {
        afmoe.SLIDING: 150, afmoe.FULL: 528}
    assert at_size["attention_pairs_computed_by_kind"] == {
        afmoe.SLIDING: int(127.5 * 512 * 512), afmoe.FULL: 516 * 512 * 512}
    assert at_size["attention_pairs_computed"] == 516 * 512 * 512
    # ... backward; forward every step's tile whole
    assert at_size["attention_pairs_computed_forward_by_kind"] == {
        afmoe.SLIDING: 150 * 512 * 512, afmoe.FULL: 528 * 512 * 512}


def test_the_scopes_are_the_ones_the_catalog_reads():
    from parallel_cnn_tpu.obs import programs

    model, _ = build()
    opt = zoo.make_optimizer(**HYPER)
    state = jax.eval_shape(lambda k: zoo.init_state(model, k, (S,), opt),
                           jax.random.key(0))
    x = jax.ShapeDtypeStruct((4, S), jnp.int32)
    text = zoo.make_train_step(model, opt, 1, None).lower(state, x, x).as_text(
        debug_info=True)
    scopes = {programs.scope_of(name)[0]
              for name in re.findall(r'loc\("([^"]*)"', text)}
    for want in ("embed", "l0/attn/norm", "l0/attn/qkv", "l0/attn/qk_norm",
                 "l0/attn/rope", "l0/attn/core", "l0/attn/gate", "l0/attn/o",
                 "l0/attn/post_norm", "l0/mlp/norm", "l0/mlp/post_norm",
                 "l1/moe/norm", "l1/moe/route", "l1/moe/dispatch",
                 "l1/moe/experts", "l1/moe/combine", "l1/moe/shared",
                 "l1/moe/post_norm", "l2/attn/core", "l2/attn/gate", "norm",
                 "head", "loss", "optimizer"):
        assert want in scopes, (want, sorted(scopes))
    # the full layer carries no position; the sliding ones do
    assert "l2/attn/rope" not in scopes and "l3/attn/rope" in scopes
    assert not any("mtp" in s or "noise" in s for s in scopes)
    of = programs.scope_of
    assert of("jit(step)/grad/jvp(l4)/attn/core/cond/branch_0_fun/"
              "grouped_causal_attention_fwd/pallas_call") == ("l4/attn/core", "fwd")
    assert of("jit(step)/grad/transpose(jvp(l4))/grad/jvp(l4)/checkpoint/attn/core/"
              "cond/branch_0_fun/grouped_causal_attention_bwd/pallas_call") == (
        "l4/attn/core", "bwd")


# --------------------------- what was there lowers to the program it was

# sha256 of this file's own toy step (`build()`, four sequences of S tokens),
# `make_train_step(...).lower(...).as_text()` as tests/test_sdar_moe.py pins
# GLM's: pinned by PR 43, whose new family added arguments to `ExpertLayer`
# and `GatedMLP` (a group limit, clamps) and a head-wise gate beside this
# model's; read on PR 43's parent and on its change, the same text. Moves
# with the expert layer, the grouped attention, the sandwich norms or the
# loss.
AFMOE_LOWERED = "6e2b82a8794d67b5bf77c73de5391244bcb828978bcd6b96e1ea31a3831463e4"


def test_the_lowered_step_of_this_model_is_unchanged():
    import hashlib

    model, _ = build()
    opt = zoo.make_optimizer(**HYPER)
    state = jax.eval_shape(lambda k: zoo.init_state(model, k, (S,), opt),
                           jax.random.key(0))
    x = jax.ShapeDtypeStruct((4, S), jnp.int32)
    text = zoo.make_train_step(model, opt, 1, None).lower(state, x, x).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == AFMOE_LOWERED
