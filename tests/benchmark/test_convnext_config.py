"""The ConvNeXt-B configuration as the benchmark holds it: the manifest's
appended entries, the shape counter against the published counts and the
program's own parameter tree, the plain reference against the system at a
small size (logits, loss, every leaf's gradient, two AdamW steps — and six
faults that must fail the comparison), the three per-layer readers on a
hand-made trace, and the whole command on the CPU through the real files
(`tiny_convnext_train`, tests/benchmark/cells)."""

import importlib
import json
import os
import re
import subprocess
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import common, flops, scope_time, trace_reduce as tr  # noqa: E402
from benchmark.tools import compare_reference  # noqa: E402

MAN = common.manifest()
CFG = common.find_config("convnext_b_imagenet", False)
NEW_METRICS = ["dwconv_device_ms", "dwconv_roofline", "norm_act_device_ms"]
SOURCE = ("Liu et al. 2022, A ConvNet for the 2020s, arXiv:2201.03545, "
          "Sec. 2 and Appendix A Table 5, ConvNeXt-B column")


# ------------------------------------------------------------ the manifest

def test_the_manifest_gained_one_configuration_one_cell_and_three_metrics():
    assert [c["name"] for c in MAN["configs"]] == [
        "resnet50_imagenet", "resnet18_imagenet", "convnext_b_imagenet"]
    assert MAN["configs"][-1] == {
        "name": "convnext_b_imagenet", "source": SOURCE,
        "file": "benchmark/configs/convnext_b_imagenet.json", "reduced": [],
        "why": MAN["configs"][-1]["why"]}
    assert [w["name"] for w in MAN["workloads"]] == [
        "r50_train", "r18_train", "r50_train_dp4", "convnext_b_train"]
    cell = MAN["workloads"][-1]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "convnext_b_imagenet", "train_b128_resident", 1)
    assert [m["name"] for m in MAN["per_layer"][20:]] == NEW_METRICS
    for m in MAN["per_layer"][20:]:
        assert m["workloads"] == ["convnext_b_train"]
        assert (m["layer"], m["moves"], m["source"]) == (
            "layers and kernels", "train_img_s_chip", "device_trace")
    assert MAN["per_layer"][21]["unit"] == "%"  # <kernel>_roofline
    assert MAN["run_seconds"] == 10 and len(MAN["end_to_end"]) == 2


def test_the_cell_is_the_published_recipe_on_one_chip():
    cell = common.find_workload("convnext_b_train")
    traffic = common.find_traffic(cell["traffic"], False)
    assert (traffic["runner"], traffic["global_batch"], traffic["images"],
            traffic["loader"], traffic["mesh_data"]) == (
        "train_zoo", 128, 1024, "device", None)
    assert traffic["check"]["batch"] == 8 and len(traffic["check"]["loss_rtol"]) == 2
    assert cell["accum_steps"] in (1, 2) and "who" in cell
    opt = CFG["optimizer"]
    from benchmark.runners import train_zoo

    assert train_zoo.optimizer_args(opt, opt["lr_per_256"] * 128 / 256) == {
        "lr": 1.25e-4, "kind": "adamw", "b1": 0.9, "b2": 0.999, "eps": 1e-8,
        "weight_decay": 0.05}
    assert 4096 * opt["lr_per_256"] / 256 == pytest.approx(4e-3)  # Table 5


def test_the_configuration_is_the_published_one_and_cuts_nothing():
    assert CFG["reduced"] == [] and CFG["source"] == SOURCE
    arch = CFG["arch"]
    assert (arch["family"], CFG["reference"]) == ("convnext", "convnext")
    assert arch["depths"] == [3, 3, 27, 3] and arch["dims"] == [128, 256, 512, 1024]
    assert (arch["dw_kernel"], arch["expansion"], arch["ln_eps"],
            arch["drop_path_rate"], arch["layer_scale_init"]) == (7, 4, 1e-6, 0.5, 1e-6)
    assert CFG["input"] == [224, 224, 3] and CFG["num_classes"] == 1000
    assert CFG["factory"] == {
        "module": "parallel_cnn_tpu.nn.convnext", "name": "convnext_b",
        "kwargs": {"num_classes": 1000, "drop_path_rate": 0.5,
                   "layer_scale_init": 1e-6}}
    for key in ("data", "deployment", "weight_decay_on", "left_out", "accum_steps"):
        assert key in CFG["assumed"]


# ------------------------------------------------------ the shape counter

def test_the_counter_gives_the_published_macs_and_the_training_flops():
    ls = flops.layers(CFG)
    assert flops.forward_macs(CFG) == 15_354_729_472  # 15.35 GMACs at 224x224
    depthwise = [l for l in ls if l.get("groups", 1) > 1]
    assert sum(map(flops.macs, depthwise)) == 228_652_032 and len(depthwise) == 36
    assert all(l["groups"] == l["cin"] == l["cout"] and l["k"] == 7
               for l in depthwise)
    assert flops.train_flops_per_image(CFG) == 92_089_841_664  # 92.09 GFLOP
    assert ls[0] == dict(name="stem", kind="conv", k=4, stride=4, h_in=224,
                         w_in=224, cin=3, h_out=56, w_out=56, cout=128)
    assert ls[-1] == dict(name="fc", kind="dense", cin=1024, cout=1000)


def test_the_pointwise_layers_are_listed_as_convs_so_the_roofline_sees_them():
    ls = flops.layers(CFG)
    pointwise = [l for l in ls if l["kind"] == "conv" and l["k"] == 1]
    assert len(pointwise) == 72 and [l["kind"] for l in ls].count("dense") == 1
    by = {l["name"]: l for l in ls}
    assert (by["s3b27.expand"]["cin"], by["s3b27.expand"]["cout"],
            by["s3b27.expand"]["h_out"]) == (512, 2048, 14)
    assert (by["s4b1.reduce"]["cin"], by["s4b1.reduce"]["cout"]) == (4096, 1024)
    assert (by["down3"]["k"], by["down3"]["stride"], by["down3"]["h_in"],
            by["down3"]["h_out"], by["down3"]["cout"]) == (2, 2, 28, 14, 512)
    # every conv has three passes but the stem, which needs no data gradient
    assert len(flops.conv_passes(CFG, 128)) == 3 * (len(ls) - 1) - 1
    share = sum(map(flops.macs, pointwise)) / flops.forward_macs(CFG)
    assert 0.96 < share < 0.97


def test_the_counter_counts_the_parameters_of_the_programs_own_model():
    import jax

    model = common.build_model(CFG)
    params = jax.eval_shape(lambda k: model.init(k, tuple(CFG["input"])),
                            jax.random.key(0))[0]
    assert sum(l.size for l in jax.tree_util.tree_leaves(params)) == 88_591_464
    weights = sum(l["k"] ** 2 * l["cin"] // l.get("groups", 1) * l["cout"]
                  if l["kind"] == "conv" else l["cin"] * l["cout"]
                  for l in flops.layers(CFG))
    rank2 = sum(l.size for l in jax.tree_util.tree_leaves(params) if l.ndim >= 2)
    assert weights == rank2  # each listed layer is a weight of the model


def test_the_listers_names_are_the_scopes_the_model_opens():
    model = common.build_model(CFG)
    scopes = set()
    for name, layer in zip(model.scope_names(), model.layers):
        if hasattr(layer, "_branch"):
            scopes.update(f"{name}.{inner}" for inner in layer._branch().scope_names())
        scopes.add(name)
    assert {l["name"] for l in flops.layers(CFG)} <= scopes


# ------------------------------------ the reference against the system

TINY = common.find_config("convnext_tiny", True)
# The paper's AdamW but for eps: 1e-6, not 1e-8. Adam's first steps move a
# weight by lr * g / (|g| + eps); where a gradient element is itself
# rounding noise (|g| ~ 5e-9 in a float32 sum of order 1e-2: the reduction
# matmul's weights under a tiny net) eps = 1e-8 turns that noise into a
# full-size step of either sign (seen: 1.2e-3 on one element), which says
# nothing about either implementation. With 1e-6 such an element moves by
# lr * 5e-3 at most, and every fault below still shows.
HYPER = dict(lr=1e-3, kind="adamw", b1=0.9, b2=0.999, eps=1e-6, weight_decay=0.05)


@pytest.fixture(scope="module")
def small():
    """A tiny ConvNeXt (depths 1-1-2-1, widths 8-16-32-64, 32x32, batch 4,
    float32 inputs) with EVERY leaf drawn at random: `gamma` of order 1
    and biases non-zero, so that the loss depends on the inside of every
    block (at the published init it does not, to better than 1e-6)."""
    import jax
    import jax.numpy as jnp

    model = common.build_model(TINY)
    params, state, _ = model.init(jax.random.key(11), (32, 32, 3))
    params = compare_reference.random_leaves(params, jax.random.key(12))
    x = jax.random.normal(jax.random.key(13), (4, 32, 32, 3), jnp.float32)
    y = jnp.array([3, 1, 4, 1])
    reference = common.find_reference(TINY)
    return types.SimpleNamespace(model=model, params=params, state=state, x=x,
                                 y=y, ref=reference, arch=TINY["arch"])


def _fresh(s, opt):
    """A ZooState of copies: the train step donates what it is given."""
    import jax
    from parallel_cnn_tpu.train import zoo

    copy = lambda tree: jax.tree_util.tree_map(lambda a: a + 0, tree)  # noqa: E731
    return zoo.ZooState(copy(s.params), copy(s.state), opt.init(s.params))


def _system(s, model=None):
    """The system's evaluation logits, training loss and gradients."""
    import jax
    from parallel_cnn_tpu.train import zoo

    model = model or s.model
    logits = model.apply(s.params, s.state, s.x, train=False)[0]
    (loss, _), grads = jax.value_and_grad(
        zoo._build_loss_fn(model, None), has_aux=True)(s.params, s.state, s.x, s.y)
    return logits, float(loss), grads


def _two_steps(s, opt):
    """The system's parameters after two steps of its own train step."""
    from parallel_cnn_tpu.train import zoo

    state = _fresh(s, opt)
    step = zoo.make_train_step(s.model, opt, 1, None)
    for _ in range(2):
        state, _ = step(state, s.x, s.y)
    return state.params


# float32 on the CPU at the highest matmul precision on both sides: what
# differs is the order of float32 sums (XLA's grouped conv against 49
# shifted slices, a fused LayerNorm against the written-out one). Seen:
# 1.3e-6 on logits of size 2.6, 1.2e-6 on the worst leaf's gradient,
# 1.8e-5 on a parameter after two steps; 1e-4 is five times the widest and
# three orders under what any of the six faults below moves.
TOL = 1e-4


@pytest.fixture(scope="module")
def healthy(small):
    return _system(small)


def _worst_grad_gap(grads, ref_grads):
    return max(compare_reference.leaf_gaps(grads, ref_grads).values())


def test_evaluation_logits_agree_with_the_reference(small, healthy):
    want = small.ref.eval_logits(small.arch, small.params, small.state, small.x)
    assert float(np.max(np.abs(want))) > 1.0  # the blocks carry the signal
    np.testing.assert_allclose(healthy[0], want, atol=TOL * float(np.max(np.abs(want))))


def test_training_loss_and_every_leafs_gradient_agree_with_the_reference(
        small, healthy):
    loss, grads = small.ref.loss_and_grads(
        small.arch, small.params, small.state, small.x, small.y)
    assert healthy[1] == pytest.approx(float(loss), rel=TOL)
    gaps = compare_reference.leaf_gaps(healthy[2], grads)
    assert len(gaps) == 65 and max(gaps.values()) < TOL, max(gaps, key=gaps.get)


def test_two_adamw_steps_agree_with_the_reference(small, healthy):
    want = small.ref.train_params(small.arch, small.params, small.state,
                                  small.x, small.y, steps=2, **HYPER)
    moved = compare_reference.leaf_gaps(small.params, want)
    assert min(moved.values()) > 1e-4  # every leaf was updated
    import jax
    from parallel_cnn_tpu.train import zoo

    got = _two_steps(small, zoo.make_optimizer(**HYPER))
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, atol=TOL, rtol=TOL, err_msg=str(path))


def test_the_check_the_harness_runs_agrees_too(small):
    """`train_losses`, as benchmark/runners/train_zoo.py:check calls it."""
    import jax
    from parallel_cnn_tpu.train import zoo

    opt = zoo.make_optimizer(**HYPER)
    state = _fresh(small, opt)
    want = small.ref.train_losses(small.arch, small.params, small.state,
                                  small.x, small.y, steps=2, **HYPER)
    step = zoo.make_train_step(small.model, opt, 1, None)
    for ref_loss in want:
        state, loss = step(state, small.x, small.y)
        assert float(loss) == pytest.approx(ref_loss, rel=TOL)
    assert want[1] < want[0]


def _faulty(small, fault):
    """The tiny model with one fault built in, from the program's own
    layers; the parameter tree is the same."""
    import dataclasses

    import jax
    from parallel_cnn_tpu.nn import convnext, layers
    from parallel_cnn_tpu.nn.core import Sequential

    class Fault(convnext.Block):
        def _branch(self):
            branch = super()._branch()
            parts = list(branch.layers)
            if fault == "dropped_droppath":
                parts[6] = layers.DropPath(0.0)
            elif fault == "no_rescale":
                rate = self.drop_rate

                class Unscaled(layers.DropPath):
                    def apply(self, params, state, x, train=False):
                        y, s = super().apply(params, state, x, train)
                        return (y * (1.0 - rate) if train and rate else y), s

                parts[6] = Unscaled(rate)
            elif fault == "layernorm_axis":
                # the statistics of a row of positions, not of the channels
                class OverRow(layers.LayerNorm):
                    def apply(self, params, state, x, train=False):
                        xf = x.astype(jax.numpy.float32)
                        mean = xf.mean(axis=2, keepdims=True)
                        var = ((xf - mean) ** 2).mean(axis=2, keepdims=True)
                        y = (xf - mean) * jax.lax.rsqrt(var + self.eps)
                        return (y * params["scale"] + params["bias"]).astype(x.dtype), state

                parts[1] = OverRow(convnext.LN_EPS)
            elif fault == "tanh_gelu":
                class Tanh(layers.GELU):
                    def apply(self, params, state, x, train=False):
                        return jax.nn.gelu(x, approximate=True), state

                parts[3] = Tanh()
            return Sequential(parts, branch.names)

    model = small.model
    swapped = [Fault(l.features, l.drop_rate, l.layer_scale_init)
               if isinstance(l, convnext.Block) else l for l in model.layers]
    return dataclasses.replace(model, layers=swapped)


@pytest.mark.parametrize("fault", [
    "dropped_droppath", "no_rescale", "layernorm_axis", "tanh_gelu",
    "coupled_decay", "decay_on_rank1"])
def test_a_fault_in_the_system_fails_the_comparison(small, fault):
    """Each of these passes a check at the published initialisation
    (`gamma` = 1e-6 hides the blocks, one step hides the decay) and must
    not pass this one."""
    import jax
    import optax
    from parallel_cnn_tpu.train import zoo

    loss, grads = small.ref.loss_and_grads(
        small.arch, small.params, small.state, small.x, small.y)
    if fault in ("coupled_decay", "decay_on_rank1"):
        # The optimizer's faults show in the parameters after two steps. At
        # ten times the paper's decay, so that two steps of it stand clear
        # of TOL: the healthy system is held to the same reference first.
        hyper = dict(HYPER, weight_decay=0.5)
        decayed = lambda p: jax.tree_util.tree_map(lambda a: a.ndim >= 2, p)  # noqa: E731
        opts = {
            "healthy": zoo.make_optimizer(**hyper),
            # L2 in the gradient, then Adam
            "coupled_decay": optax.chain(
                optax.add_decayed_weights(hyper["weight_decay"], mask=decayed),
                optax.adam(hyper["lr"])),
            "decay_on_rank1": optax.adamw(
                hyper["lr"], weight_decay=hyper["weight_decay"]),
        }
        want = jax.tree_util.tree_leaves(small.ref.train_params(
            small.arch, small.params, small.state, small.x, small.y, steps=2,
            **hyper))
        gap = {}
        for name in ("healthy", fault):
            got = jax.tree_util.tree_leaves(_two_steps(small, opts[name]))
            gap[name] = max(float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
                            for a, b in zip(got, want))
        assert gap["healthy"] < TOL < 5 * TOL < gap[fault], gap
        return
    _, got_loss, got_grads = _system(small, _faulty(small, fault))
    loss_gap = abs(got_loss - float(loss)) / float(loss)
    grad_gap = _worst_grad_gap(got_grads, grads)
    # tanh-GELU is within 3e-4 of the erf form everywhere: it moves the loss
    # by about TOL and is caught by the gradients (17 x TOL)
    assert max(loss_gap, grad_gap) > 10 * TOL, (loss_gap, grad_gap)


def test_the_reference_leans_on_nothing_of_the_program_or_of_optax():
    path = os.path.join(ROOT, "benchmark", "reference", "convnext.py")
    src = open(path).read()
    imports = re.findall(r"^\s*(?:from|import)\s+([\w.]+)", src, re.M)
    assert set(imports) == {"__future__", "functools", "json", "typing", "jax",
                            "jax.numpy"}
    assert src.count('default_matmul_precision("highest")') >= 3
    assert "conv_general_dilated" not in src and "jax.nn" not in src
    for fn in ("train_losses", "eval_logits", "loss_and_grads"):
        assert callable(getattr(common.find_reference(CFG), fn))


def test_the_reference_refuses_another_optimizer_than_the_one_it_writes_out(small):
    with pytest.raises(ValueError, match="AdamW"):
        small.ref.train_losses(small.arch, small.params, small.state, small.x,
                               small.y, steps=1, **dict(HYPER, kind="sgd"))


# ------------------------------------------------- the three new readers

def _read(name, run):
    return importlib.import_module(f"benchmark.layer_metrics.{name}").read(run)


CATALOG = """HloModule jit_step

ENTRY %main (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %dw.f = f32[8]{0} negate(%p), metadata={op_name="jit(step)/grad/s1b1/dw/conv_general_dilated"}
  %ln.f = f32[8]{0} negate(%dw.f), metadata={op_name="jit(step)/grad/s1b1/norm/mul"}
  %pw.f = f32[8]{0} negate(%ln.f), metadata={op_name="jit(step)/grad/s1b1/expand/dot_general"}
  %act.b = f32[8]{0} negate(%pw.f), metadata={op_name="jit(step)/grad/transpose(jvp(s1b1))/act/mul"}
  %dw.b = f32[8]{0} negate(%act.b), metadata={op_name="jit(step)/grad/transpose(jvp(s1b1))/dw/conv_general_dilated"}
  %add.f = f32[8]{0} negate(%dw.b), metadata={op_name="jit(step)/grad/s1b1/add/add"}
  %head.f = f32[8]{0} negate(%add.f), metadata={op_name="jit(step)/grad/norm/mul"}
  ROOT %o.1 = f32[8]{0} negate(%head.f), metadata={op_name="jit(step)/optimizer/neg"}
}
"""


def _hand_made(peak=None, config=None):
    ms = 1e6
    spans = {"dw.f": (0, 4), "ln.f": (4, 6), "pw.f": (6, 20), "act.b": (20, 23),
             "dw.b": (23, 31), "add.f": (31, 32), "head.f": (32, 33), "o.1": (33, 36)}
    ops = [tr.Op(n, "other", base * ms + a * ms, base * ms + b * ms)
           for base in (0, 100) for n, (a, b) in spans.items()]
    trace = tr.Trace(ops={0: ops}, async_ops={},
                     modules={0: [("jit_step(7)", 0.0, 40 * ms),
                                  ("jit_step(7)", 100 * ms, 140 * ms)]}, host={})
    return types.SimpleNamespace(
        trace=trace, spans={}, counters={"batch_per_chip": 128}, e2e={},
        window_s=0.2, program=r"^jit_step\b", device={},
        ctx=types.SimpleNamespace(peak=peak, config=config or {}))


@pytest.fixture
def catalog():
    from parallel_cnn_tpu.obs import programs

    programs.record("jit_step", CATALOG)
    yield
    programs.clear()


def test_the_readers_on_a_hand_made_trace_give_hand_computed_numbers(catalog):
    run = _hand_made()
    assert _read("dwconv_device_ms", run) == pytest.approx(4.0 + 8.0)
    # norm + act + add + the head's norm; not the matmul, not the optimizer
    assert _read("norm_act_device_ms", run) == pytest.approx(2.0 + 3.0 + 1.0 + 1.0)
    assert _read("dwconv_roofline", run) is None  # no published peak (a CPU)
    assert scope_time.phase_ms(run, "opt") == pytest.approx(3.0)


def test_the_depthwise_roofline_is_the_grouped_passes_least_time_over_measured(catalog):
    peak = common.load_json(os.path.join(ROOT, "benchmark", "peaks.json"))["TPU v5 lite"]
    roofline = importlib.import_module("benchmark.layer_metrics.dwconv_roofline")
    least = roofline.least_seconds(CFG, 128, peak)
    # by hand: a depthwise pass moves its input and output once (bf16) and
    # 49 weights a channel; 2 * 49 FLOPs an output element never bind
    by_hand = 0.0
    for depth, dim, side in zip((3, 3, 27, 3), (128, 256, 512, 1024), (56, 28, 14, 7)):
        acts = 2 * 128 * side * side * dim * 2
        fl = 2 * 49 * 128 * side * side * dim
        for wbytes in (2, 2, 4):  # forward, data gradient, weight gradient
            by_hand += depth * max(fl / 197e12, (acts + 49 * dim * wbytes) / 819e9)
    assert least == pytest.approx(by_hand, rel=1e-12)
    assert least == pytest.approx(8.76e-3, rel=2e-3)  # 8.8 ms a step, HBM-bound
    run = _hand_made(peak=peak, config=CFG)
    assert _read("dwconv_roofline", run) == pytest.approx(100 * least / 12e-3)
    assert _read("dwconv_roofline", run) < 100


def test_the_readers_find_nothing_in_a_program_that_has_no_such_scope():
    """The ResNets' step, or the parent's: no `dw` scope, nothing named."""
    from parallel_cnn_tpu.obs import programs

    programs.record("jit_step", CATALOG.replace("/dw/", "/mid/").replace(
        "/norm/", "/bn/").replace("/act/", "/relu/").replace("/add/", "/sum/"))
    try:
        run = _hand_made(peak={"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0},
                         config=CFG)
        assert all(_read(m, run) is None for m in NEW_METRICS)
    finally:
        programs.clear()


# ------------------------------ the whole command on the CPU, real files

@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    return tmp_path_factory.mktemp("convnext-cache")


def _env(cache):
    return dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="",
                JAX_COMPILATION_CACHE_DIR=str(cache))


@pytest.mark.parametrize("trace", [0, 1])
def test_the_tiny_convnext_cell_runs_to_a_correct_result_line(cache, trace):
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "tiny_convnext_train",
         "--seed", "2701000123", "--seconds", "0.3", "--trace", str(trace),
         "--notes", "1"],
        cwd=ROOT, env=_env(cache), capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["platform"] == "cpu" and line["attempted"] >= 4
    notes = json.loads([l for l in out.stderr.splitlines() if l.startswith("{")][-1])
    assert notes["counters"]["compiles_in_window"] == 0
    if not trace:
        assert set(line["metrics"]) == {"train_img_s_chip", "setup_s"}
        got, ref = (notes["notes"]["check_losses"][k] for k in ("system", "reference"))
        assert got[1] < got[0] and ref[1] < ref[0]  # the AdamW step was taken
        return
    m = {k: v["value"] for k, v in line["metrics"].items()}
    # CPU numbers, never device numbers: only that each reader found its ops
    assert m["dwconv_device_ms"] > 0 and m["norm_act_device_ms"] > 0
    assert m["opt_device_ms"] > 0 and m["stem_device_ms"] > 0
    assert m["scope_named_pct"] > 50
    assert m["dwconv_device_ms"] + m["norm_act_device_ms"] < m["step_device_ms"]
    # no published peak for a CPU: nothing is reported against one
    assert not set(m) & {"mfu_pct", "conv_roofline", "dwconv_roofline"}


@pytest.mark.parametrize("mode,args", [
    ("random", ["--act", "float32"]), ("init", ["--lr", "1e-4", "1e-3"])])
def test_the_comparison_tool_runs_by_name_of_a_configuration(cache, mode, args):
    out = subprocess.run(
        [sys.executable, "benchmark/tools/compare_reference.py", "--config",
         "convnext_tiny", "--rehearsal", "1", "--seeds", "1", "--mode", mode, *args],
        cwd=ROOT, env=_env(cache), capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["platform"] == "cpu" and got["config"] == "convnext_tiny"
    if mode == "random":
        # float32 activations against the float32 reference: rounding only
        assert max(got["widest"].values()) < 1e-4, got["widest"]
    else:
        small_lr, large_lr = got["widest"]["lr=0.0001"], got["widest"]["lr=0.001"]
        assert large_lr["step_moves_loss_by"] > 3 * small_lr["step_moves_loss_by"]
        assert max(small_lr["gaps"] + large_lr["gaps"]) < 0.01  # bf16 inputs
