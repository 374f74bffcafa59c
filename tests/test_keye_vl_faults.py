"""The faults the chip's controls plant in nn/keye_vl.py (benchmark/tools/
compare_keye_vl.py:control, the very context the tool uses), each against
the reference's attention layer at toy widths on the CPU, float32 on both
sides: the layer's output and its `L^I` where a forward shows the fault,
their gradients where only a backward does. Every fault moves the
comparison by far more than the order of float32 sums does, and the context
puts everything back. (One attention layer, not the model: a fault's trace
is a tenth of the two-layer toy's step.)"""

import os
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import common  # noqa: E402
from benchmark.reference import keye_vl as ref  # noqa: E402
from benchmark.tools import compare_keye_vl  # noqa: E402
from benchmark.tools.compare_reference import leaf_gaps  # noqa: E402
from test_keye_vl import ARCH, TOL  # noqa: E402
from token_family import jitted  # noqa: E402

S = 32
# 32 positions, 8 keys a query, one 4 x 6 image
ONE = dict(ARCH, num_hidden_layers=1, topk=8, mrope_layout=[[4, 1, 4, 6]])
CFG = {"arch": ONE, "factory": {
    "module": "parallel_cnn_tpu.nn.keye_vl", "name": "keye_vl", "kwargs": dict(
        {k: ONE[k] for k in (
            "vocab_size", "hidden_size", "moe_intermediate_size",
            "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
            "head_dim", "num_experts_per_tok", "indexer_num_heads",
            "indexer_head_dim", "topk", "mrope_section", "rope_theta",
            "rms_norm_eps", "held_experts", "row_buffer", "balance_weight",
            "index_weight", "gate_gradient")},
        num_experts=ONE["router_experts"], image_spans=ONE["mrope_layout"],
        dtype="float32", q_block=16, index_block=16, loss_block=32)}}
# the output's weight beside `L^I` in the number that is differentiated: both
# paths' gradients of a size
SHARE = 0.01
# only a backward shows these: the forward is the clean model's
BACKWARD_ONLY = ("u_attached", "p_attached")
# a change of the configuration's own numbers, not a patch: read off the model
BY_CONFIGURATION = {"kl_dropped": lambda model: model.index_weight == 0.0}


def _both(att):
    """`(the heads' outputs summed against a fixed direction + L^I, L^I)` of
    an attention layer on `x`: one number whose gradient reaches every leaf
    and the input by both of the layer's paths."""
    def run(p, x, direction):
        out, report = att.apply(p, {}, x, True)
        return SHARE * jnp.sum(out * direction) + report["kl"], report["kl"]
    return run


def _reference(p, x, direction):
    out, kl, _ = ref.attention(ONE, p, x)
    return SHARE * jnp.sum(out * direction) + kl, kl


@pytest.fixture(scope="module")
def layer():
    """The attention of the one-layer toy with every leaf drawn at random, a
    random input, and the reference's readings of it."""
    att = common.build_model(CFG).attn
    def drawn(keys):
        flat, tree = jax.tree_util.tree_flatten(att.init(keys[0], (S, 32))[0])
        p = tree.unflatten([a + 0.3 * jax.random.normal(k, a.shape) for a, k in zip(
            flat, jax.random.split(keys[1], len(flat)))])
        return (p, *(2.0 * jax.random.normal(keys[2], (2, 2, S, 32))))

    p, x, direction = jitted(drawn, jax.random.split(jax.random.key(11), 3))
    (value, kl), grads = jitted(
        jax.value_and_grad(_reference, argnums=(0, 1), has_aux=True), p, x, direction)
    return dict(att=att, args=(p, x, direction), value=float(value), kl=float(kl),
                grads=grads)


def _gap(layer, att, backward: bool):
    if not backward:
        value, kl = jitted(_both(att), *layer["args"])
        return max(abs(float(value) / layer["value"] - 1),
                   abs(float(kl) / layer["kl"] - 1))
    _, grads = jitted(jax.value_and_grad(_both(att), argnums=(0, 1), has_aux=True),
                      *layer["args"])
    return max(leaf_gaps(grads, layer["grads"]).values())


def test_the_clean_layer_agrees_with_the_reference_forward_and_backward(layer):
    assert _gap(layer, layer["att"], False) < TOL
    assert _gap(layer, layer["att"], True) < TOL
    assert layer["kl"] > 0.05


@pytest.mark.parametrize("fault", compare_keye_vl.FAULTS)
def test_a_fault_in_the_system_fails_the_comparison(layer, fault):
    with compare_keye_vl.control(CFG, ref, fault) as faulty:
        if fault in BY_CONFIGURATION:
            assert BY_CONFIGURATION[fault](faulty) and not BY_CONFIGURATION[fault](
                common.build_model(CFG))
            return
        gap = _gap(layer, faulty.attn, fault in BACKWARD_ONLY)
        if fault in BACKWARD_ONLY:  # and the forward is indeed the clean one
            assert _gap(layer, faulty.attn, False) < TOL
    assert gap > 100 * TOL, gap


def _seams():
    from parallel_cnn_tpu.nn import keye_vl, sdar_moe
    from parallel_cnn_tpu.ops import pallas_attention, pallas_rope

    ix = keye_vl.Indexer
    return (ix.scores, ix.choose, keye_vl.rope, keye_vl.lax, keye_vl._index_kl,
            sdar_moe.lax, sdar_moe.GQA._chosen,
            pallas_attention.selected_attention, pallas_rope._rows)


def test_the_control_puts_everything_back():
    before = _seams()
    for fault in compare_keye_vl.FAULTS:
        with compare_keye_vl.control(CFG, ref, fault):
            assert fault in ("k_halved", "kl_dropped") or any(
                a is not b for a, b in zip(_seams(), before)), fault
        assert all(a is b for a, b in zip(_seams(), before)), fault


def test_a_float8_reference_fails_the_comparison(layer):
    with compare_keye_vl.control(CFG, ref, "float8_e4m3fn"):
        # (a function of its own each time: jit keeps its traces by function,
        # and the rounding is a global the trace reads)
        low = float(jitted(lambda *a: _reference(*a), *layer["args"])[0])
    assert abs(low / layer["value"] - 1) > 100 * TOL
    assert float(jitted(lambda *a: _reference(*a), *layer["args"])[0]) == pytest.approx(
        layer["value"], rel=1e-6)
