"""The `ouro_2_6b_stage` configuration and its cell `ouro_loop_train`
(PR 48): the manifest's appended entries (closed indices: what a later PR
appends is that PR's to hold), the file's keys against the catalog row of
the published config.json, the parameter count against the issue's
arithmetic, the lister's operations against a hand count and T times a
one-pass count, the six readers on a hand-made trace, and the cell's whole
command on the CPU."""

import importlib
import json
import os
import subprocess
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import common, flops, ouro_scopes as scopes  # noqa: E402
from benchmark import trace_reduce as tr  # noqa: E402
from benchmark.runners import train_zoo_tokens_gradnorm as runner  # noqa: E402
from benchmark.shapes import ouro as shapes  # noqa: E402

MAN = common.manifest()
CFG = common.find_config("ouro_2_6b_stage", False)
TRAFFIC = common.find_traffic("train_s4096_b2_loop_fixedjob", False)
SOURCE = "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json"
NEW_METRICS = ["loop_stack_device_ms", "loop_exits_device_ms",
               "loop_attn_core_device_ms", "loop_attn_core_roofline",
               "loop_core_calls_ratio", "loop_exit_step_mean"]
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# the catalog row's `config` (model-configs guide, architectures.jsonl)
PUBLISHED = {
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
    "max_position_embeddings": 65536, "max_window_layers": 48,
    "model_type": "ouro", "num_attention_heads": 16, "num_hidden_layers": 48,
    "num_key_value_heads": 16, "rms_norm_eps": 1e-06, "rope_scaling": None,
    "rope_theta": 1000000, "sliding_window": None, "tie_word_embeddings": False,
    "total_ut_steps": 4, "early_exit_threshold": 1, "use_sliding_window": False,
    "vocab_size": 49152,
}


# ----------------------------------------------------------- the manifest

def test_this_prs_entries_are_appended_one_configuration_one_cell_six_metrics():
    assert len(MAN["configs"]) >= 8 and len(MAN["workloads"]) >= 9
    assert [c["name"] for c in MAN["configs"][:7]] == [
        "resnet50_imagenet", "resnet18_imagenet", "convnext_b_imagenet",
        "glm_4_7_flash_ep8", "sdar_30b_a3b_ep8", "trinity_mini_ep8",
        "ling_3_0_flash_ep64"]
    assert [w["name"] for w in MAN["workloads"][:8]] == [
        "r50_train", "r18_train", "r50_train_dp4", "convnext_b_train",
        "glm47f_train", "sdar_bd_train", "trinity_mini_train", "ling3f_train"]
    assert MAN["run_seconds"] == 10 and len(MAN["end_to_end"]) == 2
    entry, cell = MAN["configs"][7], MAN["workloads"][8]
    assert entry == {
        "name": "ouro_2_6b_stage", "source": SOURCE,
        "file": "benchmark/configs/ouro_2_6b_stage.json",
        "reduced": ["num_hidden_layers"], "why": entry["why"]}
    assert len(entry["why"]) <= 200 and "one pipeline stage" in entry["why"]
    assert [c["file"] for c in MAN["configs"]].count(entry["file"]) == 1
    assert cell == {"name": "ouro_loop_train", "config": "ouro_2_6b_stage",
                    "traffic": "train_s4096_b2_loop_fixedjob", "chips": 1,
                    "why": cell["why"]}
    assert len(cell["why"]) <= 200
    for said in ("8 of 48", "4,096", "4 passes", "17 %", "3.4 %"):
        assert said in cell["why"], said
    assert common.find_workload("ouro_loop_train")["why"] == cell["why"]
    # one cell in nine asks for four chips: 25 % rounded down is two
    assert [w["chips"] for w in MAN["workloads"]].count(4) == 1
    six = MAN["per_layer"][66:72]
    assert [m["name"] for m in six] == NEW_METRICS
    for m in six:
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert m["workloads"] == ["ouro_loop_train"]
        assert (m["layer"], m["moves"]) == ("layers and kernels", "train_img_s_chip")
        assert (m["unit"] == "%") == m["name"].endswith("_roofline")
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", m["name"] + ".py"))
    assert [m["source"] for m in six] == ["device_trace"] * 5 + ["program_counter"]
    # no older list grew, and the cell reports the unlisted metrics too
    assert not any("ouro_loop_train" in m.get("workloads", [])
                   for m in MAN["per_layer"][:66])
    assert [m["name"] for m in MAN["per_layer"][57:66]] == [
        "kda_core_device_ms", "kda_core_roofline", "kda_conv_gates_device_ms",
        "bh_mla_core_device_ms", "bh_mla_core_roofline", "bh_experts_device_ms",
        "bh_experts_roofline", "bh_route_device_ms", "bh_load_max_over_mean"]
    got = [m["name"] for m in common.cell_metrics(MAN, "ouro_loop_train",
                                                  "per_layer")]
    assert set(NEW_METRICS) <= set(got)
    for shared in ("mfu_pct", "scope_named_pct", "step_device_ms",
                   "opt_device_ms", "peak_hbm_gb", "conv_roofline"):
        assert shared in got
    assert not any(m.startswith(("moe_", "kda_", "bh_", "win_", "full_", "bd_",
                                 "sdar_", "afmoe_", "rope_", "attn_core"))
                   for m in got)
    for w in MAN["workloads"][:8]:
        assert not set(NEW_METRICS) & {m["name"] for m in common.cell_metrics(
            MAN, w["name"], "per_layer")}


# --------------------------------------------------------------- the file

def test_every_published_key_is_there_and_only_the_depth_differs():
    assert (CFG["name"], CFG["source"]) == ("ouro_2_6b_stage", SOURCE)
    assert CFG["reduced"] == ["num_hidden_layers"]
    assert CFG["published"] == {"num_hidden_layers": 48}
    differ = sorted(k for k, v in PUBLISHED.items() if CFG[k] != v)
    assert differ == ["num_hidden_layers"] and CFG["num_hidden_layers"] == 8
    # no width is among the cuts, and the vocabulary is whole
    assert (CFG["hidden_size"], CFG["intermediate_size"], CFG["head_dim"],
            CFG["num_attention_heads"], CFG["vocab_size"],
            CFG["total_ut_steps"]) == (2048, 5632, 128, 16, 49152, 4)
    for said in ("six chips", "pipeline stages of eight", "four times a step",
                 "612.44 M", "9.80 GB"):
        assert said in CFG["deployment"], said
    for key in ("norms", "attention", "exit_gate", "exit_distribution", "loss",
                "early_exit_threshold", "init", "lr", "left_out"):
        assert key in CFG["assumed"], key
    for said in ("stage II", "early exit", "packing", "clipping", "warm-up"):
        assert said in CFG["assumed"]["left_out"], said
    assert "0.05" in CFG["assumed"]["loss"] and "0.1" in CFG["assumed"]["loss"]


def test_the_arch_group_repeats_the_files_own_keys_and_the_factory_builds_it():
    arch = CFG["arch"]
    for key in ("hidden_size", "intermediate_size", "num_attention_heads",
                "num_key_value_heads", "head_dim", "num_hidden_layers",
                "total_ut_steps", "rms_norm_eps", "rope_theta", "vocab_size"):
        assert arch[key] == CFG[key], key
    assert (arch["family"], CFG["reference"], arch["entropy_weight"]) == (
        "ouro", "ouro", 0.05)
    assert CFG["factory"] == {
        "module": "parallel_cnn_tpu.nn.ouro", "name": "ouro_2_6b",
        "kwargs": {"num_hidden_layers": 8, "entropy_weight": 0.05}}
    model = common.build_model(CFG)
    assert (model.n_layers, model.passes, model.vocab, model.hidden,
            model.dense_width, model.entropy_weight) == (
        8, 4, 49152, 2048, 5632, 0.05)
    assert CFG["input"] == [TRAFFIC["sequence_length"]] == [4096]
    opt = CFG["optimizer"]
    assert (opt["kind"], opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"]) == (
        "adamw", 0.9, 0.95, 1e-8, 0.1)
    assert 2e-4 <= runner.cell_lr(CFG, TRAFFIC) <= 3e-4


def test_what_the_three_deselected_cases_held_holds_for_this_entry():
    """tests/conftest.py deselects `test_config_entries`, `test_the_listed_
    configurations_name_the_resnet_reference` and `test_optimizer_args_of_
    the_listed_configurations_are_sgds_three` for `ouro_2_6b_stage` (no
    ResNet, AdamW, a cut): everything else they hold, held here."""
    import re

    from benchmark.runners import train_zoo

    entry = MAN["configs"][7]
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert re.fullmatch(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}", entry["name"])
    for text in (entry["source"], entry["why"]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    assert entry["file"].startswith("benchmark/") and len(entry["reduced"]) <= 16
    assert CFG["name"] == entry["name"] and CFG["source"] == entry["source"]
    assert CFG["reduced"] == entry["reduced"] == ["num_hidden_layers"]
    assert "assumed" in CFG and "arch" in CFG and "factory" in CFG
    assert any(w["config"] == entry["name"] for w in MAN["workloads"])
    ref = common.find_reference(CFG)
    assert ref.__name__ == "benchmark.reference.ouro"
    assert callable(ref.train_losses) and callable(ref.eval_logits)
    assert callable(ref.train_report)
    assert train_zoo.optimizer_args(CFG["optimizer"], 2e-4) == {
        "lr": 2e-4, "kind": "adamw", "b1": 0.9, "b2": 0.95, "eps": 1e-8,
        "weight_decay": 0.1}


def test_the_cell_is_one_fixed_job_of_two_sequences_a_step():
    assert (TRAFFIC["runner"], TRAFFIC["sequence_length"], TRAFFIC["global_batch"],
            TRAFFIC["sequences"], TRAFFIC["loader"], TRAFFIC["warmup_epochs"],
            TRAFFIC["trace_seconds"]) == (
        "train_zoo_tokens_gradnorm", 4096, 2, 4, "device", 4, 3.0)
    assert isinstance(TRAFFIC["job_seed"], int)
    chk = TRAFFIC["check"]
    assert chk["batch"] == 1 and chk["rows_tol"] == 0 and len(chk["loss_rtol"]) == 2
    assert [t["leaves"] for t in chk["grad_tols"]] == [
        "['exit_gate']['b']", "['exit_gate']", "['head']", "['embed']",
        "['layers']", ""]
    # the gate's bias is one number, the mean over the positions of terms of
    # either sign (7 % of their mean size at the median, read on the chip):
    # its direction is a sign and its length swings with the sum, so it is
    # judged for being alive alone (a dead leaf reads a length > 60)
    assert chk["grad_tols"][0] == {"leaves": "['exit_gate']['b']", "gap": 2.5,
                                   "norm": 30.0}
    for t in chk["grad_tols"][1:]:
        assert 0 < t["gap"] <= 0.015 and 0 < t["norm"] <= 0.1
    assert chk["loss_rtol"] == [0.0003, 0.004]
    for said in ("float8_e4m3fn", "three_passes", "grad_stopped", "p_detached",
                 "entropy_dropped", "norm_outside_loop", "post_norms_dropped",
                 "rope_dropped", "bias_dead", "bias_doubled"):
        assert said in chk["note"], said


@pytest.mark.parametrize("path,want", [
    ("['exit_gate']['b']", 0), ("['exit_gate']['w']", 1), ("['head']", 2),
    ("['embed']['w']", 3), ("['layers'][3]['ffn']['gate']", 4),
    ("['layers'][0]['attn_norm']", 4), ("['norm']", 5)])
def test_a_leaf_belongs_to_the_first_class_its_path_holds(path, want):
    assert runner.class_of(path, TRAFFIC["check"]["grad_tols"]) == want


# ------------------------------------------------------------- the lister

def test_the_counter_gives_the_hand_counted_macs_and_t_times_one_pass():
    s, d, f, v, pairs = 4096, 2048, 5632, 49152, 4096 * 4097 // 2
    layer = s * (4 * d * d + 3 * d * f) + 2 * pairs * 16 * 128
    one_pass = 8 * layer + s * d * v
    assert flops.forward_macs(CFG) == 4 * one_pass
    assert sum(flops.macs(l) for l in shapes.one_pass(CFG, "ut0")) == one_pass
    ls = shapes.layers(CFG)
    assert ls[0] == dict(name="embed", kind="dense", rows=0, cin=v, cout=d)
    assert len(ls) == 1 + 4 * (8 * 9 + 1)
    assert [l["name"] for l in ls if l["name"].endswith("exit.head")] == [
        f"ut{t}.exit.head" for t in range(4)]
    # the issue's arithmetic: 2,316 M MACs a token, 113.8 TFLOP a step
    assert round(flops.forward_macs(CFG) / s / 1e6) == 2315
    step = 2 * flops.train_flops_per_image(CFG)
    assert round(step / 1e12, 1) == 113.8
    # the exits' share of the matmul work, here and in the whole model (the
    # issue's 3.9 % leaves the attention cores out of the whole; its 17 % has them)
    exits = 4 * s * d * v
    assert round(100 * exits / flops.forward_macs(CFG), 1) == 17.4
    whole = 4 * (48 * layer + s * d * v)
    assert round(100 * exits / whole, 1) == 3.4
    assert round(100 * exits / (whole - 4 * 48 * 2 * pairs * 16 * 128), 1) == 3.9


def test_the_counter_counts_the_parameters_of_the_programs_own_model():
    import jax

    weights = {}
    for l in shapes.one_pass(CFG, "ut0"):
        if l.get("weights", True):
            weights[l["name"]] = l["cin"] * l["cout"]
    listed = sum(weights.values()) + 49152 * 2048  # the embedding's rows
    model = common.build_model(CFG)
    shapes_of = jax.eval_shape(lambda k: model.init(k, (8,))[0], jax.random.key(0))
    counted = sum(a.size for a in jax.tree_util.tree_leaves(shapes_of))
    gains_and_gate = 8 * 4 * 2048 + 2048 + 2048 + 1
    assert counted == listed + gains_and_gate == 612_438_017
    assert round(counted * 16 / 1e9, 2) == 9.80


def test_the_kernels_operations_and_bytes_are_the_hand_counted_ones():
    passes = shapes.attention_core_passes(CFG, 2)
    assert len(passes) == 2 * 4 * 8 and shapes.core_calls(CFG) == 32
    pairs = 4096 * 4097 // 2
    fwd = 2 * 2 * 16 * pairs * 2 * 128
    tensor = 2 * 4096 * 16 * 128 * 2  # q (or k, v, the output) of a step
    assert passes[0] == dict(name="ut0.core0", kind="fwd", flops=fwd,
                             bytes=4 * tensor)
    assert passes[1] == dict(name="ut0.core0", kind="bwd", flops=2 * fwd,
                             bytes=8 * tensor)
    assert sum(p["flops"] for p in passes) == 32 * 3 * fwd


# ------------------------------------------------------------ the readers

def _read(name, run):
    return importlib.import_module(f"benchmark.layer_metrics.{name}").read(run)


def _op(name, stack, call=False):
    kind = ('custom-call(%p), custom_call_target="tpu_custom_call"'
            if call else "negate(%p)")
    return ('  %%%s = bf16[8,8]{1,0} %s, metadata={op_name="jit(step)/grad/%s"}'
            % (name, kind, stack))


FWD = "jvp(ut)/closed_call/"
BWD = "transpose(jvp(ut))/closed_call/"
CATALOG = "\n".join([
    "HloModule jit_step", "",
    "ENTRY %main (p: bf16[8,8]) -> bf16[8,8] {",
    "  %p = bf16[8,8]{1,0} parameter(0)",
    _op("emb.f", "jvp(embed)/gather"),
    _op("qkv.f", FWD + "l0/attn/qkv/dot_general"),
    *(_op(f"core{i}.f", FWD + "l0/attn/core/cond/l0/attn/core/cond/branch_0_fun/"
          "grouped_causal_attention_fwd/pallas_call", call=True) for i in range(4)),
    _op("mlp.f", FWD + "l1/mlp/dot_general"),
    _op("norm.f", FWD + "exit/norm/mul"),
    _op("head.f", FWD + "exit/head/dot_general"),
    _op("gate.f", FWD + "exit/gate/reduce_sum"),
    _op("mix.f", "jvp(mix)/exp"),
    _op("sum.f", FWD + "add"),
    _op("head.b", BWD + "exit/checkpoint/rematted_computation/head/dot_general"),
    _op("mlp.b", BWD + "l1/l1/checkpoint/rematted_computation/mlp/dot_general"),
    *(_op(f"core{i}.b", BWD + "l0/l0/checkpoint/attn/core/cond/branch_0_fun/"
          "grouped_causal_attention_bwd/pallas_call", call=True) for i in range(4)),
    '  ROOT %o.1 = bf16[8,8]{1,0} negate(%p), '
    'metadata={op_name="jit(step)/optimizer/neg"}', "}", ""])
SPANS = {"emb.f": (0, 1), "qkv.f": (1, 5), "core0.f": (5, 7), "core1.f": (7, 9),
         "core2.f": (9, 11), "core3.f": (11, 13), "mlp.f": (13, 20),
         "norm.f": (20, 21), "head.f": (21, 25), "gate.f": (25, 26),
         "mix.f": (26, 27), "sum.f": (27, 28), "head.b": (28, 36),
         "mlp.b": (36, 50), "core0.b": (50, 54), "core1.b": (54, 58),
         "core2.b": (58, 62), "core3.b": (62, 66), "o.1": (66, 70)}
# a model of one layer and four passes needs 4 forward cores a step
ONE_LAYER = dict(CFG, arch=dict(CFG["arch"], num_hidden_layers=1))


def _hand_made(peak=None, config=ONE_LAYER, spans=SPANS):
    ms = 1e6
    ops = [tr.Op(n, "other", base * ms + a * ms, base * ms + b * ms)
           for base in (0, 100) for n, (a, b) in spans.items()]
    trace = tr.Trace(ops={0: ops}, async_ops={},
                     modules={0: [("jit_step(7)", 0.0, 71 * ms),
                                  ("jit_step(7)", 100 * ms, 171 * ms)]}, host={})
    return types.SimpleNamespace(
        trace=trace, spans={}, counters={"batch_per_chip": 2}, e2e={},
        window_s=0.2, program=r"^jit_step\b", device={"platform": "tpu"},
        ctx=types.SimpleNamespace(peak=peak, config=config))


@pytest.fixture
def catalog():
    from parallel_cnn_tpu.obs import programs

    programs.record("jit_step", CATALOG)
    yield programs.lookup("jit_step")
    programs.clear()


def test_the_readers_on_a_hand_made_trace_give_hand_computed_numbers(catalog):
    assert (catalog["core2.f"].scope, catalog["core2.f"].phase,
            catalog["core2.f"].opcode) == ("ut/l0/attn/core", "fwd", "custom-call")
    assert (catalog["mlp.b"].scope, catalog["mlp.b"].phase) == ("ut/l1/mlp", "bwd")
    assert (catalog["head.b"].scope, catalog["sum.f"].scope) == ("ut/exit/head", "ut")
    kinds = {n: scopes.mechanisms(catalog[n]) for n in catalog}
    assert kinds["core0.b"] == ("stack", "core") and kinds["qkv.f"] == ("stack",)
    assert kinds["gate.f"] == kinds["mix.f"] == kinds["head.b"] == ("exits",)
    assert kinds["emb.f"] == kinds["sum.f"] == kinds["o.1"] == ()
    run = _hand_made()
    assert _read("loop_stack_device_ms", run) == pytest.approx(4 + 8 + 7 + 14 + 16)
    assert _read("loop_attn_core_device_ms", run) == pytest.approx(8 + 16)
    assert _read("loop_exits_device_ms", run) == pytest.approx(1 + 4 + 1 + 1 + 8)
    assert _read("loop_attn_core_roofline", run) is None  # no published peak
    assert scopes.core_kernel_calls(run) == {"fwd": 4.0, "bwd": 4.0}
    assert _read("loop_core_calls_ratio", run) == pytest.approx(1.0)
    # a step that left a pass out; a step whose backward ran a core again
    fewer = {k: v for k, v in SPANS.items() if k not in ("core3.f", "core3.b")}
    assert _read("loop_core_calls_ratio", _hand_made(spans=fewer)) == 0.75
    twice = dict(SPANS, **{"core0.b": (50, 52), "core1.b": (52, 54)})
    twice_cat = CATALOG.replace(
        "  ROOT %o.1", _op(
            "again.b", BWD + "l0/l0/checkpoint/rematted_computation/attn/core/"
            "cond/branch_0_fun/grouped_causal_attention_fwd/pallas_call",
            call=True) + "\n  ROOT %o.1")
    from parallel_cnn_tpu.obs import programs

    programs.record("jit_step", twice_cat)
    assert _read("loop_core_calls_ratio", _hand_made(
        spans=dict(twice, **{"again.b": (66, 66.5)}))) == 1.25


def test_the_roofline_share_is_least_time_over_measured(catalog):
    run = _hand_made(peak=PEAK)
    want = 100 * shapes.least_seconds(
        shapes.attention_core_passes(ONE_LAYER, 2), PEAK) / 24e-3
    assert _read("loop_attn_core_roofline", run) == pytest.approx(want)
    # compute-bound at 4,096 positions: the flops set every pass's least time
    for p in shapes.attention_core_passes(CFG, 2):
        assert p["flops"] / PEAK["bf16_flops_per_s"] > p["bytes"] / PEAK["hbm_bytes_per_s"]


def test_the_exit_step_reader_reads_the_programs_newest_epoch_record():
    from parallel_cnn_tpu.obs import epochs

    epochs.clear()
    try:
        assert _read("loop_exit_step_mean", _hand_made()) is None
        epochs.record({"epoch": 1, "loop_exit_step_mean": 1.9})
        epochs.record({"epoch": 2, "loop_exit_step_mean": 2.25})
        assert _read("loop_exit_step_mean", _hand_made()) == 2.25
        epochs.record({"epoch": 3, "moe_rows_held": [7]})  # another model's
        assert _read("loop_exit_step_mean", _hand_made()) is None
    finally:
        epochs.clear()


def test_the_readers_find_nothing_where_the_program_has_no_such_thing():
    """A conv net's step, a configuration of another family, no trace:
    nothing named, nothing counted, nothing raised."""
    from parallel_cnn_tpu.obs import epochs, programs

    epochs.clear()
    programs.record("jit_step", CATALOG.replace("(ut)", "(s1b1)").replace(
        "jvp(mix)", "jvp(pool)"))
    try:
        run = _hand_made(peak=PEAK)
        assert [m for m in NEW_METRICS if _read(m, run) is not None] == []
        glm = common.find_config("glm_4_7_flash_ep8", False)
        programs.record("jit_step", CATALOG)
        assert _read("loop_core_calls_ratio", _hand_made(config=glm)) is None
        no_trace = _hand_made(peak=PEAK)
        no_trace.trace = None
        assert [m for m in NEW_METRICS if _read(m, no_trace) is not None] == []
        programs.clear()  # a program that records no catalog
        assert [m for m in NEW_METRICS if _read(m, _hand_made(peak=PEAK))
                is not None] == []
    finally:
        programs.clear()


# ------------------------------ the whole command on the CPU, real files

@pytest.fixture(scope="module")
def two_runs(tmp_path_factory):
    """Two seeds, the second more than 32 signed bits hold and traced."""
    cache = tmp_path_factory.mktemp("ouro-cache")
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="",
               JAX_COMPILATION_CACHE_DIR=str(cache))

    def run_cell(seed, trace):
        out = subprocess.run(
            [sys.executable, "benchmark/run.py", "--workload", "tiny_ouro_train",
             "--seed", str(seed), "--seconds", "0.3", "--trace", str(trace),
             "--notes", "1"],
            cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
        assert out.returncode == 0, out.stderr[-3000:]
        line = json.loads(out.stdout.strip().splitlines()[-1])
        notes = json.loads([l for l in out.stderr.splitlines()
                            if l.startswith("{")][-1])
        return line, notes

    return run_cell(4823000017, 0), run_cell(2147483659, 1)


def test_two_seeds_are_one_job_and_the_check_reads_every_class(two_runs):
    (a, na), (b, nb) = two_runs
    for line, notes in two_runs:
        assert line["correct"] is True and line["failed"] == 0
        assert line["device"]["platform"] == "cpu"
        assert notes["counters"]["compiles_in_window"] == 0
        assert notes["counters"]["moe_overflow_rows"] == []
        assert notes["notes"]["check_rows_held"] == {
            "system": [[], []], "reference": [[], []]}
        gate, stack, rest = notes["notes"]["check_grad_classes"]
        assert (gate["leaves_read"], stack["leaves_read"], rest["leaves_read"]) == (
            2, 2 * 11, 3)
        for c in (gate, stack, rest):
            assert 0 < c["gap_widest"] < c["gap"] and 0 < c["norm_widest"] < c["norm"]
    assert na["counters"]["losses"][:4] == nb["counters"]["losses"][:4]
    assert (na["notes"]["check_losses"]["reference"]
            != nb["notes"]["check_losses"]["reference"])
    assert set(a["metrics"]) == {"train_img_s_chip", "setup_s"}


def test_the_traced_tiny_cell_reports_the_new_metrics_and_the_unlisted_ones(two_runs):
    (_, _), (line, notes) = two_runs
    got = line["metrics"]
    # no kernel and no published peak on a CPU: the share and the calls'
    # ratio are left out, never 0
    assert {m for m in NEW_METRICS if m in got} == set(NEW_METRICS) - {
        "loop_attn_core_roofline", "loop_core_calls_ratio"}
    for name in ("loop_stack_device_ms", "loop_exits_device_ms",
                 "loop_attn_core_device_ms"):
        assert got[name]["value"] > 0 and got[name]["unit"] == "ms"
    assert got["loop_attn_core_device_ms"]["value"] < got["loop_stack_device_ms"]["value"]
    assert 1.0 <= got["loop_exit_step_mean"]["value"] <= 3.0
    assert got["scope_named_pct"]["value"] > 85
    assert not any(m.startswith(("moe_", "kda_", "bh_", "afmoe_")) for m in got)
    assert got["stem_device_ms"]["value"] == 0.0
