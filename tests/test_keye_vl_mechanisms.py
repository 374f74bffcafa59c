"""nn/keye_vl.py's mechanisms one at a time (tests/test_keye_vl.py holds the
whole model to its reference and has the two gradient isolations): the model with every key kept against plain causal attention; M-RoPE's reduction to `rope`
and its layout; the share; the data-mask kind of the scheduled kernels
against the plain body in interpret mode; which step factories run the
model, and what `zoo.train` records of it."""

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

from benchmark.reference import keye_vl as ref  # noqa: E402
from parallel_cnn_tpu import config as config_lib, plan as plan_lib  # noqa: E402
from parallel_cnn_tpu.nn import afmoe, glm_moe, keye_vl  # noqa: E402
from parallel_cnn_tpu.nn.layers import rope  # noqa: E402
from parallel_cnn_tpu.ops import pallas_attention as pa, pallas_rope  # noqa: E402
from parallel_cnn_tpu.train import zoo  # noqa: E402
from test_keye_vl import K, S, SPANS, VOCAB, build  # noqa: E402
from token_family import HYPER, jitted  # noqa: E402

# ------------------------------------------------- without an indexer

def test_with_every_key_kept_the_model_is_the_same_model_without_an_indexer():
    """`topk >= S`: the selection is the causal mask, and the trunk is a
    Qwen3-MoE decoder with plain causal grouped attention — `afmoe.GatedGQA`
    without its gate and its window, handed the same leaves."""
    model, _ = build(32, topk=S, mrope_layout=[])
    plain = afmoe.GatedGQA(4, 2, 16, None, True, 1e7, 1e-6, 32, qk_norm=True,
                           gated=False)
    p = jitted(lambda key: jax.tree_util.tree_map(
        # (gains and the key's LayerNorm bias move too)
        lambda a: a + 0.3 * jax.random.normal(jax.random.key(a.size), a.shape),
        model.attn.init(key, (S, 32))[0]), jax.random.key(4))
    x = jax.random.normal(jax.random.key(5), (2, S, 32))
    got, report = jitted(lambda p, x: model.attn.apply(p, {}, x), p, x)
    want, _ = jitted(lambda p, x: plain.apply(p, {}, x),
                     {k: v for k, v in p.items() if k != "indexer"}, x)
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert float(report["keys_selected_mean"]) == (S + 1) / 2
    assert float(report["kl"]) > 0  # softmax(I) is not the attention


# ------------------------------------------------------------ positions

def test_mrope_on_three_equal_rows_is_rope_bit_for_bit_and_an_image_moves_it():
    x = jax.random.normal(jax.random.key(0), (2, 3, 256, 128), jnp.bfloat16)
    text = pallas_rope.Axes((16, 24, 24))
    np.testing.assert_array_equal(
        np.asarray(jitted(lambda x: rope(x, 1e7), x), np.float32),
        np.asarray(jitted(lambda x: rope(x, 1e7, text), x), np.float32))
    image = pallas_rope.Axes((16, 24, 24), ((16, 1, 8, 8),))
    moved = np.asarray(jitted(lambda x: rope(x, 1e7, image), x), np.float32)
    same = np.asarray(jitted(lambda x: rope(x, 1e7), x), np.float32)
    np.testing.assert_array_equal(moved[:, :, :17], same[:, :, :17])
    assert np.abs(moved[:, :, 17:] - same[:, :, 17:]).max() > 0.1
    # the program's literal is the three rows of integers, widened on the
    # device: pair by pair in float32 it is 4 MB a turn at 16,384 positions
    text = jax.jit(lambda x: rope(x, 1e7, image)).lower(x).as_text()
    assert "dense<" in text and "tensor<3x256xi32>" in text
    assert "x64xf32>" not in "".join(
        line for line in text.splitlines() if "stablehlo.constant dense<" in line)


def test_an_images_cells_sit_at_their_three_coordinates_and_the_text_resumes():
    rows = pallas_rope.Axes((16, 24, 24), ((4, 2, 2, 3),)).rows(20)
    np.testing.assert_array_equal(rows[:, :4], np.tile(np.arange(4), (3, 1)))
    cells = rows[:, 4:16] - 4
    np.testing.assert_array_equal(cells[0], np.repeat([0, 1], 6))
    np.testing.assert_array_equal(cells[1], np.tile(np.repeat([0, 1], 3), 2))
    np.testing.assert_array_equal(cells[2], np.tile([0, 1, 2], 4))
    # the largest position so far is 4 + 2 (the width's): the text resumes at 7
    np.testing.assert_array_equal(rows[:, 16:], np.tile(7 + np.arange(4), (3, 1)))
    np.testing.assert_array_equal(
        rows, ref.positions([[4, 2, 2, 3]], 20))
    with pytest.raises(ValueError, match="pairs"):
        pallas_rope.Axes((16, 24, 24)).of_pairs(8, 64)
    with pytest.raises(ValueError, match="overlap or pass"):
        pallas_rope.Axes((1,), ((4, 1, 4, 4), (8, 1, 2, 2))).rows(32)


# ------------------------------------------------------------ the share

def test_the_eight_shares_expert_parts_add_up_to_the_uncut_layer():
    """One layer of 16 experts cut eight ways: every share holds the WHOLE
    attention and indexer, leaf for leaf (counted once), and its own two
    experts' part of what follows it."""
    whole, arch = build(32, router_experts=16, num_experts_per_tok=4,
                        held_experts=list(range(16)), num_hidden_layers=1)
    layer = whole._layers()[0]
    shape, key = (S, 32), jax.random.key(7)
    p, st, _ = layer.init(key, shape)
    x = jax.random.normal(jax.random.key(9), (2, S, 32)) * 4.0
    want = jitted(lambda p, x: ref.decoder_layer(arch, p, x)[0], p, x)

    def attended(p, st, x):
        """(x + attention, the expert layer's input)."""
        zero = jax.tree_util.tree_map(jnp.zeros_like, p["ffn"]["experts"])
        h, _ = layer.apply(dict(p, ffn=dict(p["ffn"], experts=zero)), st, x)
        return h, glm_moe._norm(layer.eps, p["ffn_norm"], h)

    h, u = jitted(attended, p, st, x)
    total = jnp.zeros_like(want)
    for i in range(8):
        share = dataclasses.replace(layer, ffn=dataclasses.replace(
            layer.ffn, held=(2 * i, 2 * i + 1)))
        # an expert's weights come from its id: a share's leaves ARE the uncut
        # layer's, its two experts' their slices (initialised for the first
        # and the last share, a second of leaf-by-leaf draws each; cut out of
        # the uncut layer's for the six between)
        ffn = dict(p["ffn"], experts=jax.tree_util.tree_map(
            lambda a: a[2 * i:2 * i + 2], p["ffn"]["experts"]))
        if i in (0, 7):
            for a, b in zip(jax.tree_util.tree_leaves(share.init(key, shape)[0]),
                            jax.tree_util.tree_leaves(dict(p, ffn=ffn)), strict=True):
                np.testing.assert_array_equal(a, b)
        total = total + jitted(share.ffn.apply, ffn, st, u)[0]
    np.testing.assert_allclose(h + total, want, atol=2e-5)
    uncut, _ = jitted(layer.apply, p, st, x)
    np.testing.assert_allclose(uncut, want, atol=2e-5)


# --------------------------------------------- the kernels' data-mask kind

def _masked_case(s=256, d=128, heads=4, kv=2):
    ks = jax.random.split(jax.random.key(0), 5)
    q = jax.random.normal(ks[0], (1, heads, s, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, kv, s, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, kv, s, d), jnp.bfloat16)
    do = jax.random.normal(ks[3], (1, heads, s, d), jnp.bfloat16)
    take = jnp.tril(jnp.ones((s, s), bool)) & (
        jax.random.uniform(ks[4], (1, s, s)) < 0.3)
    # a query's own key is not always chosen; every query has some key
    take = take.at[:, jnp.arange(s), jnp.maximum(jnp.arange(s) - 130, 0)].set(True)
    return q, k, v, do, jnp.where(take, 0.0, pa.MASKED).astype(jnp.bfloat16)


@pytest.mark.parametrize("heads", [1, 2])
def test_the_data_mask_kind_is_the_plain_body_in_interpret_mode(heads):
    """A query tile whose first (its own) tile holds none of a row's keys,
    rows whose keys lie two tiles back, `heads` query heads a grid step."""
    q, k, v, do, bias = _masked_case()
    t, d = 128, q.shape[-1]
    plain = keye_vl.GQA(4, 2, d, 1, q_block=128, select=keye_vl.Indexer())._chosen
    call = dict(scale=d ** -0.5, block=1, t=t, kinds=(pa.DATA,), heads=heads,
                interpret=True)
    steps_ = pa.selected_schedule(q.shape[2], t)
    assert len(steps_) == 3 and {kind for _, _, kind in steps_} == {pa.DATA}
    out, lse = pa.scheduled_forward(q, k, v, steps_, bias=bias, **call)
    want, want_lse = jitted(plain, q, k, v, bias)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=0.02)
    np.testing.assert_allclose(lse, want_lse, atol=2e-5)
    got = pa.scheduled_backward(q, k, v, out, lse, do, steps_,
                                bias_t=jnp.swapaxes(bias, 1, 2), **call)
    wanted = jitted(lambda q, k, v, do: jax.vjp(
        lambda q, k, v: plain(q, k, v, bias)[0], q, k, v)[1](do), q, k, v, do)
    for a, b in zip(got, wanted):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.abs(a - b).max() < 0.01 * np.abs(b).max()


def test_the_data_kind_takes_its_place_in_the_pairs_steps_and_heads():
    assert pa.sub_squares(pa.DATA, 1, 512, True) == (512, {(0, 0): False})
    steps_ = pa.selected_schedule(16384, 512)
    assert len(steps_) == 528 and steps_[1] == (1, 1, pa.DATA)
    assert pa.pairs_computed(steps_, 1, 512, True) == 528 * 512 * 512
    # the mask's tile beside each head's scores: four heads a step, not eight
    assert pa.heads_a_step(8, 512, 128, 16384) == 8
    assert pa.heads_a_step(8, 512, 128, 16384, data=True) == 4


# ------------------------------------------------- through the factories

@pytest.mark.parametrize("factory", ["comm_psum", "comm_ring", "fused_update",
                                     "zero3", "pipeline"])
def test_the_other_step_factories_refuse_the_model_by_name(host_devices, factory):
    """By the refusal they have: the model keeps per-step counts
    (`finish_step`)."""
    model, _ = build()
    opt = zoo.make_optimizer(**HYPER)
    mesh = plan_lib.ExecutionPlan(data=2).validate().make_mesh(
        devices=host_devices[:2])
    fused = config_lib.FusedStepConfig(update=True)
    comm = config_lib.CommConfig(impl="ring")
    with pytest.raises(zoo.StepStateUnsupported, match="KeyeVL"):
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


def test_zoo_train_lowers_both_loss_parts_and_records_the_counters():
    model, _ = build(32)
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
    hyper = dict(HYPER, lr=3e-3)
    _, losses = zoo.train(
        model, tokens[:, :-1], tokens[:, 1:], in_shape=(S,), epochs=3,
        batch_size=4, **hyper, seed=3, verbose=False, metrics=Rec(), obs=obs)
    assert all(math.isfinite(v) for v in losses) and losses[-1] < losses[0]
    first, last = Rec.epochs[0], Rec.epochs[-1]
    assert len(last["moe_rows_held"]) == 2 and sum(last["moe_overflow_rows"]) == 0
    assert last["dsa_keys_selected_mean"] == [keye_vl.pairs_allowed(S, K) / S] * 2
    assert all(0 < r <= 1 for r in last["dsa_tiles_touched_ratio"])
    # the second part of the loss is in the step: the objective is read, and
    # moves (that it trains the indexer, and the indexer alone: above)
    assert all(v > 0 for v in first["dsa_index_kl"] + last["dsa_index_kl"])
    assert last["dsa_index_kl"] != first["dsa_index_kl"]
    (event,) = [f for k, f in Journal.events if k == "zoo_dsa"]
    assert not [k for k, _ in Journal.events if k == "zoo_moe"]
    assert (event["layers"], event["topk"], event["attention_core"],
            event["attention_pairs_allowed"], event["attention_pairs_causal"],
            event["rope_axes"], event["image_spans"]) == (
        2, K, "blocks", keye_vl.pairs_allowed(S, K), S * (S + 1) // 2, 3, SPANS)
    # two turns of 32 queries against the keys up to their end
    assert event["attention_pairs_computed"] == 32 * 32 + 32 * 64
    assert "a bit a pair" in event["selection_saved"]
    # the toy's index heads are 8 wide and its blocks 32 queries: the plain
    # form of the scores, on any platform
    assert (event["index_scores_core"], event["index_scores_tile"]) == ("xla", None)


@pytest.mark.parametrize("platform,seq,core,tile", [
    ("tpu", 16384, "pallas", 1024),  # the cell: bands of 4,096 ... 16,384 keys
    ("tpu", 2048, "pallas", 512),    # bands of 512 ... 2,048
    ("tpu", 1024, "pallas", 256),    # bands of 256 ... 1,024
    ("tpu", 320, "xla", None),       # one band, 320 keys: no tile divides them
    ("cpu", 16384, "xla", None),
])
def test_describe_says_what_makes_the_index_scores(platform, seq, core, tile):
    """At published widths (sixteen index heads of 64, blocks of 256
    queries), beside what it says of the attention's core: the kernels
    where the program is lowered for a TPU and every band's keys tile, the
    narrowest band's tile."""
    model = keye_vl.keye_vl2_30b_a3b(num_hidden_layers=1, vocab_size=64)
    said = model.describe(seq, seq, platform)
    assert (said["index_scores_core"], said["index_scores_tile"]) == (core, tile)
    fused = platform == "tpu" and seq % 128 == 0
    assert said["attention_core"] == ("fused" if fused else "blocks")
