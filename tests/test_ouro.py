"""nn/ouro.py (Ouro-2.6B's mechanisms: one stack of layers run several
times on the same weights, the final norm closing every pass, an exit after
every pass — the whole head and a learned gate — and the loss the expected
cross-entropy under the exit distribution less its entropy) at toy widths
on the CPU, seeded random weights, against the plain float32 reference the
benchmark keeps (benchmark/reference/ouro.py): every exit's logits and
gate, the loss's parts, every leaf's gradient, AdamW steps; the exit
distribution's algebra; the tied weights' gradient as the sum over the
passes of an untied copy's; the faults the chip's controls plant; which
step factories run the model; the scopes, the counters and the `zoo_loop`
event."""

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

from benchmark.reference import ouro as ref  # noqa: E402
from benchmark.tools import compare_ouro as tool  # noqa: E402
from benchmark.tools.compare_reference import leaf_gaps  # noqa: E402
from parallel_cnn_tpu import config as config_lib, plan as plan_lib  # noqa: E402
from parallel_cnn_tpu.nn import afmoe, glm_moe, layers, ouro  # noqa: E402
from parallel_cnn_tpu.train import zoo  # noqa: E402
from token_family import (HYPER, jitted, loss as loss_of, steps, system,  # noqa: E402
                          toy)

S, VOCAB, D, T = 32, 96, 32, 3
ARCH = {
    "family": "ouro", "hidden_size": D, "intermediate_size": 48,
    "num_attention_heads": 2, "num_key_value_heads": 2, "head_dim": 16,
    "num_hidden_layers": 2, "total_ut_steps": T, "rms_norm_eps": 1e-6,
    "rope_theta": 1e6, "vocab_size": VOCAB, "entropy_weight": 0.05,
}
# float32 on both sides at the highest matmul precision: what differs is the
# order of float32 sums. Every fault below moves the loss by 10 x TOL or a
# gradient by 100 x TOL.
TOL = 2e-5


def build(**over):
    arch = dict(ARCH, **over)
    model = ouro.ouro(
        vocab_size=arch["vocab_size"], hidden_size=arch["hidden_size"],
        intermediate_size=arch["intermediate_size"],
        num_hidden_layers=arch["num_hidden_layers"],
        num_attention_heads=arch["num_attention_heads"],
        num_key_value_heads=arch["num_key_value_heads"],
        head_dim=arch["head_dim"], total_ut_steps=arch["total_ut_steps"],
        rope_theta=arch["rope_theta"], rms_norm_eps=arch["rms_norm_eps"],
        entropy_weight=arch["entropy_weight"], dtype=over.get("dtype", "float32"),
        q_block=8, loss_block=16)
    return model, {k: v for k, v in arch.items() if k != "dtype"}


def _adjust(params):
    """The exit gate away from its initialisation: a bias beside the
    redrawn weight (order 1 / sqrt(d), as the other matrices), so that the
    `p_t` differ by position and their mean is no round number."""
    gate = dict(params["exit_gate"], b=jnp.asarray([0.3], jnp.float32))
    return dict(params, exit_gate=gate)


@pytest.fixture(scope="module")
def small():
    model, arch = build()
    return toy(model, arch, seq=S, batch=2, adjust=_adjust)


def _close(got, want, tol=TOL):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=tol,
                               atol=tol)


# ------------------------------------------------------ the pieces' algebra

def test_the_exit_distribution_sums_to_one_and_is_the_papers_product():
    a = jax.random.normal(jax.random.key(0), (4, 5, 7)) * 3
    p, log_p = jitted(ouro.exit_distribution, a)
    _close(jnp.sum(p, axis=0), jnp.ones((5, 7)), 1e-6)
    lam = jax.nn.sigmoid(a)
    want = ref.exit_distribution(list(lam))
    _close(p, jnp.stack(want), 1e-6)
    _close(jnp.exp(log_p), p, 1e-6)
    # a gate that closes leaves the later exits at 0, never a NaN
    shut = jnp.full((3, 2), 1e4)
    p, log_p = jitted(ouro.exit_distribution, shut)
    assert np.all(np.isfinite(np.asarray(log_p)))
    _close(p[:, 0], jnp.asarray([1.0, 0.0, 0.0]), 1e-6)


def test_one_pass_is_the_plain_cross_entropy_of_a_sandwich_decoder(small):
    model, arch = build(total_ut_steps=1)
    # (the one pass's gate is not read: the loss is the mean CE, no entropy)
    got = loss_of(small, model)
    z, lam = jitted(model.exits, small.params, small.state, small.x)
    assert z.shape == (1, 2, S, VOCAB) and lam.shape == (1, 2, S)
    ce = -jnp.take_along_axis(jax.nn.log_softmax(z[0]), small.y[..., None], -1)
    _close(got, jnp.mean(ce))
    _close(got, ref.loss_terms(arch, small.params, small.state, small.x,
                               small.y)["loss"])


# ------------------------------------------------- against the reference

def test_every_exits_logits_and_gate_agree_with_the_reference(small):
    z, lam = jitted(small.model.exits, small.params, small.state, small.x)
    want_z, want_lam = ref.eval_exits(ARCH, small.params, small.state, small.x)
    assert z.shape == (T, 2, S, VOCAB)
    _close(z, want_z)
    _close(lam, want_lam)
    # `apply` is the last exit; the closed state after every pass
    logits = jitted(small.model.apply, small.params, small.state, small.x)[0]
    _close(logits, want_z[-1])
    hidden = jitted(lambda p, s, x: small.model.hidden_states(p, s, x)[0],
                    small.params, small.state, small.x)
    for got, want in zip(hidden, ref.hidden_states(
            ARCH, small.params, small.state, small.x), strict=True):
        _close(got, want)


def test_the_losss_parts_and_every_leafs_gradient_agree_with_the_reference(small):
    value, grads, new = system(small)
    terms = ref.loss_terms(ARCH, small.params, small.state, small.x, small.y)
    _close(value, terms["loss"])
    got = jitted(lambda *a: small.model.loss_parts(*a)[1], small.params,
                 small.state, small.x, small.y)
    _close(got["expected"], terms["expected"])
    _close(got["entropy"], terms["entropy"])
    _close(got["exit_p"], terms["exit_p"])
    _close(value, terms["expected"] - 0.05 * terms["entropy"])
    # the exits differ and the distribution is no constant: the test has teeth
    assert np.ptp(np.asarray(terms["ce"])) > 1e-3
    assert 0.02 < float(terms["exit_p"][-1]) < 0.9
    _, want = ref.loss_and_grads(ARCH, small.params, small.state, small.x, small.y)
    gaps = leaf_gaps(grads, want)
    assert set(gaps) >= {"['exit_gate']['w']", "['exit_gate']['b']",
                         "['layers'][1]['attn']['q']", "['head']"}
    assert max(gaps.values()) < 100 * TOL, max(gaps.items(), key=lambda kv: kv[1])
    # the state carries what the counters read
    seen = small.model.counters(new)
    _close(seen["loop_exit_p"], terms["exit_p"])
    _close(seen["loop_exit_entropy"], terms["entropy"])
    _close(seen["loop_exit_step_mean"],
           float(jnp.sum(terms["exit_p"] * jnp.arange(1, T + 1))))


def test_three_steps_losses_agree_with_the_reference(small):
    losses, seen, _ = steps(small)
    want = ref.train_report(ARCH, small.params, small.state, small.x, small.y,
                            steps=3, **HYPER)
    _close(losses, want["losses"], 10 * TOL)
    assert want["rows_held"] == [[], [], []]
    assert losses[2] < losses[0]
    for s in seen:  # what the benchmark's runner zips over
        assert [s[k] for k in ("moe_rows_held", "moe_load_max_over_mean",
                               "moe_overflow_rows")] == [[], [], []]
        assert len(s["loop_exit_p"]) == T and 1 <= s["loop_exit_step_mean"] <= T
        assert math.isclose(sum(s["loop_exit_p"]), 1.0, abs_tol=1e-5)


# ----------------------------------- the share-to-model test: tied weights

def test_a_layers_gradient_is_the_sum_over_the_passes_of_an_untied_copys(small):
    """The model reads a layer's weights in T places; written out with a
    copy of the stack a pass (benchmark/reference/ouro.py's equations over
    T x L separate leaves), the gradient of every copy, summed over the
    passes, is the tied leaf's."""
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), small.params)

    def untied_loss(stacks, rest):
        h = rest["embed"]["w"][small.x]
        ce, lam = [], []
        for stack in stacks:  # a pass: its OWN copy of the layers
            for p in stack:
                h = ref.decoder_layer(ARCH, p, h)
            h = ref.rms_norm(h, rest["norm"], ARCH["rms_norm_eps"])
            ce.append(ref.token_losses(rest, h, small.y))
            lam.append(ref.gate_of(rest, h))
        p = ref.exit_distribution(lam)
        expected = jnp.mean(sum(a * b for a, b in zip(p, ce)))
        entropy = jnp.mean(-sum(a * jnp.log(a) for a in p))
        return expected - ARCH["entropy_weight"] * entropy

    rest = {k: v for k, v in params.items() if k != "layers"}
    value, by_pass = jitted(jax.value_and_grad(untied_loss),
                            [params["layers"]] * T, rest)
    tied_value, tied, _ = system(small)
    _close(value, tied_value)
    summed = jax.tree_util.tree_map(lambda *g: sum(g), *by_pass)
    gaps = leaf_gaps(tied["layers"], summed)
    assert max(gaps.values()) < 100 * TOL, gaps
    # and no single pass's gradient is it: every use of the weights learns
    for one in by_pass:
        assert min(leaf_gaps(one, tied["layers"]).values()) > 0.05


# --------------------------------------------------- the planted faults

CFG = {"arch": ARCH, "factory": {
    "module": "parallel_cnn_tpu.nn.ouro", "name": "ouro",
    "kwargs": dict({k: v for k, v in ARCH.items() if k != "family"},
                   dtype="float32", q_block=8, loss_block=16)}}


@pytest.mark.parametrize("fault", tool.FAULTS)
def test_a_fault_in_the_system_fails_the_comparison(small, fault):
    terms = ref.loss_terms(ARCH, small.params, small.state, small.x, small.y)
    _, want = ref.loss_and_grads(ARCH, small.params, small.state, small.x, small.y)
    with tool.control(CFG, ref, fault) as faulty:
        value, grads, _ = system(small, faulty, fresh=True)
    if fault == "three_passes":  # other leaves: a state of T - 1 exits
        assert abs(value - float(terms["loss"])) > 10 * TOL
        return
    worst = max(leaf_gaps(grads, want).values())
    assert (abs(value - float(terms["loss"])) > 10 * TOL or worst > 100 * TOL), (
        fault, value, float(terms["loss"]), worst)
    # what the fault does NOT move says where to look for it
    if fault in ("grad_stopped", "p_detached"):
        _close(value, terms["loss"])  # the forward is the clean one


def test_the_control_puts_everything_back(small):
    before = (ouro.Ouro._passes, ouro.exit_distribution,
              afmoe.SandwichLayer._post, afmoe.rope, ouro.Ouro._gate)
    for fault in (*tool.FAULTS, "float8_e4m3fn"):
        with tool.control(CFG, ref, fault):
            pass
    assert before == (ouro.Ouro._passes, ouro.exit_distribution,
                      afmoe.SandwichLayer._post, afmoe.rope, ouro.Ouro._gate)
    from benchmark.reference import glm_moe as rounded

    assert rounded.ROUND is None


def test_the_tools_copy_of_the_passes_is_the_models(small, monkeypatch):
    """`passes_handing` with the closed state handed on is `Ouro._passes`:
    the same loss, every leaf's gradient to the last bit."""
    value, grads, _ = system(small)
    monkeypatch.setattr(ouro.Ouro, "_passes", tool.passes_handing(lambda x, h: h))
    copy_value, copy_grads, _ = system(small, build()[0], fresh=True)
    assert copy_value == value
    assert max(leaf_gaps(copy_grads, grads).values()) == 0.0


def test_the_gates_bias_read_apart_is_both_sides_own_gradient():
    """`bias_reader` on the check's draws: the reference's reading (one
    forward pass and the mixture's gradient of the gates' logits) is its
    autodiff's, the system's is the system's, the terms are of either sign
    and their mean is the gradient; a planted fault of the bias shows in
    the system's reading alone."""
    from benchmark import token_data

    model, seed = build()[0], 4801000021
    traffic = {"sequence_length": S, "check": {"batch": 2}}
    got = tool.bias_reader(CFG, traffic, model, ref)(seed)
    x, y = token_data.synthetic_tokens(
        jax.random.fold_in(jax.random.key(seed), 1), n=2, length=S, vocab=VOCAB)
    params, state = model.init(jax.random.key(seed), (S,))[:2]
    _, want = ref.loss_and_grads(ARCH, params, state, x, y)
    _close(got["g_ref"], want["exit_gate"]["b"][0], 1e-3)
    _close(got["gate_weight_rms"],
           jnp.sqrt(jnp.mean(want["exit_gate"]["w"] ** 2)), 1e-3)
    _close(got["g_sys"], got["g_ref"], 1e-3)
    assert got["positions"] == 2 * S
    assert abs(got["g_ref"]) < got["term_mean_abs"] <= got["term_rms"]
    for fault, times in (("bias_dead", 0.0), ("bias_doubled", 2.0)):
        with tool.control(CFG, ref, fault) as faulty:
            planted = tool.bias_reader(CFG, traffic, faulty, ref)(seed)
        _close(planted["g_sys"], times * got["g_sys"], 1e-6)
        assert planted["g_ref"] == got["g_ref"]


def test_a_float8_reference_fails_the_comparison(small):
    value, _, _ = system(small)
    with tool.control(CFG, ref, "float8_e4m3fn"):
        rounded = ref.loss_terms(ARCH, small.params, small.state, small.x, small.y)
    assert abs(value - float(rounded["loss"])) > 10 * TOL


def test_bfloat16_activations_change_rounding_only(small):
    model, _ = build(dtype="bfloat16")
    value, _, _ = system(small)
    assert abs(loss_of(small, model) - value) < 0.05


# ------------------------------------------------- what the factory builds

def test_the_published_model_and_the_stage_have_the_counted_parameters():
    def count(model):
        shapes = jax.eval_shape(lambda k: model.init(k, (8,))[0], jax.random.key(0))
        return sum(math.prod(a.shape) for a in jax.tree_util.tree_leaves(shapes))

    layer = 4 * 2048 ** 2 + 3 * 2048 * 5632 + 4 * 2048
    ends = 2 * 49152 * 2048 + 2048 + 2048 + 1
    assert count(ouro.ouro_2_6b(num_hidden_layers=8)) == 8 * layer + ends == 612_438_017
    whole = ouro.ouro_2_6b()
    assert count(whole) == 48 * layer + ends  # 2.67 B
    assert (whole.n_layers, whole.passes, whole.vocab, whole.eps,
            whole.attn.theta, whole.entropy_weight) == (48, 4, 49152, 1e-6, 1e6, 0.05)
    att = whole.attn
    assert (att.heads, att.kv_heads, att.head_dim, att.window, att.rotary,
            att.qk_norm, att.gated) == (16, 16, 128, None, True, False, False)
    assert att.core(4096) == ("fused", 512)
    leaves = jax.eval_shape(lambda k: att.init(k, (8, 2048))[0], jax.random.key(0))
    assert set(leaves) == {"q", "k", "v", "o"}
    with pytest.raises(ValueError, match="passes"):
        ouro.ouro_2_6b(total_ut_steps=0)
    with pytest.raises(ValueError, match="dense"):
        ouro.Ouro(vocab=8, hidden=8, dense_width=8, n_layers=2, attn=att,
                  experts=glm_moe.ExpertLayer(), first_dense=2, mtp_modules=0)


def test_an_attention_without_norms_or_gate_draws_the_leaves_the_gated_one_draws():
    both = afmoe.GatedGQA(2, 2, 16, None, True)
    bare = afmoe.GatedGQA(2, 2, 16, None, True, qk_norm=False, gated=False)
    full = both.init(jax.random.key(5), (8, 32))[0]
    cut = bare.init(jax.random.key(5), (8, 32))[0]
    assert set(full) - set(cut) == {"gate", "q_norm", "k_norm"}
    for name in cut:
        assert np.array_equal(np.asarray(cut[name]), np.asarray(full[name])), name


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
                       match="Ouro"):
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


def test_zoo_train_lowers_the_loss_and_records_the_loop(host_devices):
    model, _ = build(dtype="bfloat16")
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
        model, tokens[:, :-1], tokens[:, 1:], in_shape=(S,), epochs=6,
        batch_size=4, accum_steps=2, mesh=mesh, **dict(HYPER, lr=3e-3), seed=3,
        verbose=False, metrics=Rec(), obs=obs)
    assert all(math.isfinite(v) for v in losses) and losses[-1] < losses[0] - 0.05
    last = Rec.epochs[-1]
    assert (last["moe_rows_held"], last["moe_overflow_rows"],
            last["moe_load_max_over_mean"]) == ([], [], [])
    assert len(last["loop_exit_p"]) == T
    assert math.isclose(sum(last["loop_exit_p"]), 1.0, abs_tol=1e-3)
    assert 1.0 <= last["loop_exit_step_mean"] <= T
    assert 0.0 < last["loop_exit_entropy"] <= math.log(T) + 1e-3
    assert set(state.model_state) == {"layers", "loop"}
    kinds = [k for k, _ in Journal.events]
    assert "zoo_moe" not in kinds  # the model names its event itself
    (event,) = [f for k, f in Journal.events if k == "zoo_loop"]
    assert (event["passes"], event["layers"], event["exits"],
            event["layer_applications_per_token"],
            event["tokens_per_step"]) == (T, 2, T, 2 * T, 4 * S)
    assert (event["attention_core"], event["attention_tile"],
            event["attention_tiles_visited"], event["attention_tiles_total"],
            event["attention_pairs_allowed"], event["rope_turn"]) == (
        "blocks", 8, 10, 16, S * (S + 1) // 2, "plain")
    at_size = ouro.ouro_2_6b(num_hidden_layers=8).describe(8192, 4096, "tpu")
    assert (at_size["attention_core"], at_size["attention_tile"],
            at_size["attention_tiles_visited"], at_size["attention_tiles_total"],
            at_size["rope_turn"], at_size["layer_applications_per_token"]) == (
        "fused", 512, 36, 64, "kernel", 32)
    # 28 whole tiles and 10 sub-squares of 16 of each of the diagonal's 8 (PR 49)
    # backward; forward every step's tile whole
    assert (at_size["attention_pairs_computed"],
            at_size["attention_pairs_computed_forward"]) == (
        33 * 512 * 512, 36 * 512 * 512)
    assert (event["attention_pairs_computed"]
            == event["attention_pairs_computed_forward"] == 8 * (8 + 16 + 24 + 32))


def test_a_model_without_a_name_for_it_keeps_the_zoo_moe_event():
    assert not hasattr(glm_moe.GlmMoe, "setup_event")
    assert ouro.Ouro.setup_event == "zoo_loop"


def test_the_scopes_are_the_ones_the_catalog_reads():
    from parallel_cnn_tpu.obs import programs

    model, _ = build()
    opt = zoo.make_optimizer(**HYPER)
    state = jax.eval_shape(lambda k: zoo.init_state(model, k, (S,), opt),
                           jax.random.key(0))
    x = jax.ShapeDtypeStruct((2, S), jnp.int32)
    # the compiled program's own text, as `zoo.train` records it
    compiled = zoo.make_train_step(model, opt, 1, None).lower(state, x, x).compile()
    catalog = programs.parse(compiled.as_text())
    scopes = {e.scope for e in catalog.values()}
    for want in ("embed", *(f"ut/l{i}/attn/{s}" for i in (0, 1) for s in (
            "norm", "qkv", "rope", "core", "o", "post_norm")),
            "ut/l0/mlp/norm", "ut/l1/mlp/post_norm", "ut/l0/mlp",
            "ut/exit/norm", "ut/exit/head", "ut/exit/loss", "ut/exit/gate",
            "mix", "optimizer"):
        assert want in scopes, (want, sorted(scopes))
    # the passes are a straight program, and the body they call and a
    # rematerialised layer's backward name the layer and nothing else
    assert not any(e.opcode == "while" for e in catalog.values())
    assert not any(part in s.split("/") for s in scopes
                   for part in ("closed_call", "checkpoint", "while"))
    assert not any(re.search(r"qk_norm|attn/gate|l\d+/l\d+|exit/exit", s)
                   for s in scopes)


# (the names of nn/ouro.py's step compiled for a described v5e at the cell's
# size, PR 48, then names the four older families' steps have)
@pytest.mark.parametrize("op_name,want", [
    ("jit(step)/grad/jvp(ut)/closed_call/l3/mlp/jit(silu)/div",
     ("ut/l3/mlp", "fwd")),
    ("jit(step)/grad/transpose(jvp(ut))/closed_call/l1/l1/checkpoint/"
     "rematted_computation/attn/qkv/nsm,mhd->nhsd/dot_general",
     ("ut/l1/attn/qkv", "bwd")),
    ("jit(step)/grad/transpose(jvp(ut))/closed_call/l0/l0/remat2",
     ("ut/l0", "bwd")),
    ("jit(step)/grad/jvp(ut)/closed_call/l0/attn/core/cond/l0/attn/core/cond/"
     "branch_0_fun/grouped_causal_attention_fwd/pallas_call",
     ("ut/l0/attn/core", "fwd")),
    ("jit(step)/grad/jvp(ut)/closed_call/l0/attn/rope/jit(either)/cond/l0/attn/"
     "rope/jit(either)/l0/attn/rope/jit(either)/cond/branch_0_fun/jit(rotate)/"
     "rope_turn/pallas_call", ("ut/l0/attn/rope", "fwd")),
    ("jit(step)/grad/transpose(jvp(ut))/closed_call/l0/l0/checkpoint/attn/core/"
     "cond/branch_0_fun/grouped_causal_attention_bwd/pallas_call",
     ("ut/l0/attn/core", "bwd")),
    ("jit(step)/grad/transpose(jvp(ut))/closed_call/exit/exit/checkpoint/"
     "rematted_computation/loss/select_n", ("ut/exit/loss", "bwd")),
    ("jit(step)/grad/transpose(jvp(ut))/closed_call/exit/gate/exit/gate/"
     "checkpoint/rematted_computation/convert_element_type",
     ("ut/exit/gate", "bwd")),
    ("jit(step)/grad/transpose(jvp(ut))/closed_call/exit/add_any",
     ("ut/exit", "bwd")),
    # what was there reads as it read: a path that holds no called body goes
    # the way it went, a loop's pair and a `cond` without a branch included
    ("jit(step)/grad/jvp(l1)/attn/core/while/body/checkpoint/dot_general",
     ("l1/attn/core/while/body", "fwd")),
    ("jit(step)/grad/transpose(jvp(l1))/grad/jvp(l1)/checkpoint/moe/route/"
     "dot_general", ("l1/moe/route", "bwd")),
    ("jit(step)/grad/transpose(jvp(mtp))/l0/grad/jvp(mtp)/l0/checkpoint/moe/"
     "experts/mul", ("mtp/l0/moe/experts", "bwd")),
    ("jit(step)/grad/jvp(l4)/attn/core/cond/branch_0_fun/"
     "grouped_causal_attention_fwd/pallas_call", ("l4/attn/core", "fwd")),
    ("jit(step)/grad/jvp(s2b1)/cond/relu", ("s2b1/cond", "fwd")),
])
def test_an_op_of_a_called_body_is_its_layers(op_name, want):
    from parallel_cnn_tpu.obs import programs

    assert programs.scope_of(op_name) == want
