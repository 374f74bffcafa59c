"""nn/keye_vl.py (Keye-VL-2.0-30B-A3B's language model: a learned indexer
scores every earlier key, each query attends to its best `topk`, the
indexer learns from the attention it steered, positions turn on three axes)
at toy widths on the CPU, seeded random weights, against the plain float32
reference the benchmark keeps (benchmark/reference/keye_vl.py): logits, the
loss's parts, the selected sets and their tie rule, every leaf's gradient,
the two gradient isolations
(tests/test_keye_vl_mechanisms.py has
the mechanisms one at a time, tests/test_keye_vl_faults.py the planted
faults)."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.reference import keye_vl as ref  # noqa: E402
from benchmark.tools.compare_reference import leaf_gaps  # noqa: E402
from parallel_cnn_tpu.nn import glm_moe, keye_vl  # noqa: E402
from token_family import jitted, logits as logits_of, system, toy  # noqa: E402

S, K, VOCAB = 64, 16, 96
SPANS = [[8, 1, 4, 4]]
ARCH = {
    "family": "keye_vl", "hidden_size": 32, "moe_intermediate_size": 16,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "num_experts_per_tok": 2, "num_hidden_layers": 2, "rms_norm_eps": 1e-6,
    "rope_theta": 1e7, "vocab_size": VOCAB, "router_experts": 8,
    "held_experts": [0, 1, 2], "row_buffer": None, "balance_weight": 1e-3,
    "gate_gradient": True, "index_weight": 1.0, "indexer_num_heads": 4,
    "indexer_head_dim": 8, "topk": K, "mrope_section": [2, 2, 4],
    "mrope_layout": SPANS,
}
# float32 on both sides at the highest matmul precision: what differs is the
# order of float32 sums. Seen: 1e-7 on the loss, 2e-6 on the worst leaf's
# gradient.
TOL = 2e-5


def build(index_block=16, **over):
    """(`keye_vl.keye_vl` of `ARCH` with `over`, that architecture).
    `index_block` 16 scores the 64 positions in four bands of one block, as
    the cell's 16,384 are scored in four of sixteen; a test that is not
    about the bands asks for 32, one band of two blocks, and a program a
    quarter the size."""
    arch = dict(ARCH, **over)
    return keye_vl.keye_vl(
        vocab_size=arch["vocab_size"], hidden_size=arch["hidden_size"],
        moe_intermediate_size=arch["moe_intermediate_size"],
        num_hidden_layers=arch["num_hidden_layers"],
        num_attention_heads=arch["num_attention_heads"],
        num_key_value_heads=arch["num_key_value_heads"],
        head_dim=arch["head_dim"], num_experts=arch["router_experts"],
        num_experts_per_tok=arch["num_experts_per_tok"],
        indexer_num_heads=arch["indexer_num_heads"],
        indexer_head_dim=arch["indexer_head_dim"], topk=arch["topk"],
        mrope_section=arch["mrope_section"], image_spans=arch["mrope_layout"],
        rope_theta=arch["rope_theta"], rms_norm_eps=arch["rms_norm_eps"],
        held_experts=arch["held_experts"], row_buffer=arch["row_buffer"],
        balance_weight=arch["balance_weight"], index_weight=arch["index_weight"],
        gate_gradient=arch["gate_gradient"], dtype="float32", q_block=32,
        index_block=index_block, loss_block=32), arch


@pytest.fixture(scope="module")
def small():
    """The toy model with every PARAMETER leaf drawn at random (weights of
    std 1 / sqrt(fan_in), gains 1 + 0.1 n): 64 positions, 16 keys a query,
    so 48 queries of 64 select."""
    return toy(*build(), seq=S, draw_state=False, compiled=True)


def _is_indexer(path) -> bool:
    return "indexer" in jax.tree_util.keystr(path)


# ------------------------------------------------- against the reference

def test_loss_parts_and_every_leafs_gradient_agree_with_the_reference(small):
    value, grads, new = system(small)
    want, want_grads = ref.loss_and_grads(small.arch, small.params, small.state,
                                          small.x, small.y)
    assert abs(value / float(want) - 1) < TOL
    gaps = leaf_gaps(grads, want_grads)
    assert max(gaps.values()) < TOL, max(gaps, key=gaps.get)
    terms = ref.loss_terms(small.arch, small.params, small.state, small.x, small.y)
    np.testing.assert_allclose([float(st["dsa"]["kl"]) for st in new["layers"]],
                               terms["index_by_layer"], rtol=TOL)
    assert all(0.05 < float(v) for v in terms["index_by_layer"])
    np.testing.assert_allclose(
        sum(float(st["balance"]) for st in new["layers"]), terms["balance"], rtol=TOL)
    # every query keeps min(t + 1, K) keys, on both sides
    kept = keye_vl.pairs_allowed(S, K) / S
    assert [float(st["dsa"]["keys_selected_mean"]) for st in new["layers"]] == [kept] * 2
    np.testing.assert_array_equal(terms["keys_selected_mean"], [kept] * 2)


def test_logits_agree_with_the_reference(small):
    want = ref.eval_logits(small.arch, small.params, small.state, small.x)
    np.testing.assert_allclose(logits_of(small), want, atol=2e-4)


def _bias_of_layers(s):
    """Every layer's selection as the program makes it: bool (L, N, S, S)."""
    model = s.model

    def run(params, state, x):
        hidden, _ = model.hidden_states(params, state, x)
        h = [model._embed().apply(params["embed"], {}, x)[0]] + hidden[:-1]
        out = []
        for p, u in zip(params["layers"], h):
            u = glm_moe._norm(model.eps, p["attn_norm"], u)
            index = model.attn.select.project(
                p["attn"]["indexer"], u, model.attn.positions)
            out.append(model.attn.select.choose(index, 32)[0] == 0)
        return jnp.stack(out)

    return jitted(run, s.params, s.state, s.x)


def test_the_selected_sets_are_the_references(small):
    """Exact top-k, ties to the lower index: the same keys, pair for pair,
    and `min(t + 1, K)` of them a query."""
    got = np.asarray(_bias_of_layers(small))
    want = np.asarray(ref.selected_sets(small.arch, small.params, small.x))
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(
        got.sum(-1), np.broadcast_to(np.minimum(np.arange(S) + 1, K), got.shape[:3]))
    assert not got[..., np.triu_indices(S, 1)[0], np.triu_indices(S, 1)[1]].any()
    # not a window: some query past K keeps a key further back than K
    t, s_ = np.nonzero(got[0, 0])
    assert (t - s_).max() >= K


def test_equal_scores_go_to_the_lower_position():
    ix = keye_vl.Indexer(heads=1, head_dim=8, topk=3, rows=8)
    q = jnp.zeros((1, 1, 8, 8), jnp.float32)  # every score 0: all ties
    bias, (kept, touched) = jitted(
        lambda q: ix.choose((q, q[:, 0], jnp.ones((1, 8, 1))), 8), q)
    want = np.tril(np.ones((8, 8), bool)) & (np.arange(8)[None, :] < 3)
    np.testing.assert_array_equal(np.asarray(bias[0] == 0), want)
    assert float(kept) == (1 + 2 + 3 * 6) / 8 and float(touched) == 1.0


@pytest.mark.parametrize("keep", [1, 5, 17, 64])
def test_the_bisection_takes_what_a_stable_top_k_takes(keep):
    """Small integers (many equal scores), signed zeros and -inf: entry for
    entry `lax.top_k`'s choice, without its sort."""
    from jax import lax

    r = np.random.default_rng(keep).integers(-3, 4, (2, 16, 64)).astype(np.float32)
    r[0, :, ::7] = -0.0
    r[1, :, ::5] = -np.inf
    got = np.asarray(jitted(
        lambda r: keye_vl._best(r, jnp.full((16, 1), keep)), jnp.asarray(r)))
    want = np.zeros_like(got)
    np.put_along_axis(want, np.asarray(lax.top_k(jnp.asarray(r), keep)[1]), True, -1)
    np.testing.assert_array_equal(got, want)
    assert (got.sum(-1) == keep).all()


def test_the_trunk_learns_nothing_from_the_indexers_objective_and_the_indexer_nothing_else(small):
    """The gradient of `sum_layers L^I` alone is zero on every trunk leaf,
    and on the indexer's leaves it IS the whole loss's gradient: the
    cross-entropy and the balance terms add nothing there."""
    model = small.model
    index = jitted(jax.grad(
        lambda p, st, x, y: model.loss_parts(p, st, x, y)[0][2]),
        small.params, small.state, small.x, small.y)
    _, whole, _ = system(small)
    flat = jax.tree_util.tree_flatten_with_path
    for (path, a), (_, b) in zip(flat(index)[0], flat(whole)[0]):
        if _is_indexer(path):
            assert np.any(a), jax.tree_util.keystr(path)
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-9)
        else:
            assert not np.any(a) and np.any(b), jax.tree_util.keystr(path)
    assert sum(_is_indexer(p) for p, _ in flat(index)[0]) == 2 * 5
