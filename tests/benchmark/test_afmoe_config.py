"""The Trinity-Mini configuration as the benchmark holds it: the manifest's
appended entries (and what the cases deselected in tests/conftest.py for it
held of the older entries), the cut written down against the published
config.json, the shape counter against the program's own parameter tree
and the issue's arithmetic, the ten new readers on a hand-made trace, and
the whole command on the CPU through the real files (`tiny_afmoe_train`,
tests/benchmark/cells): two seeds, one job."""

import importlib
import json
import os
import subprocess
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import afmoe_scopes, common, flops, trace_reduce as tr  # noqa: E402
from benchmark.runners import train_zoo, train_zoo_tokens  # noqa: E402
from benchmark.shapes import afmoe as shapes  # noqa: E402

MAN = common.manifest()
CFG = common.find_config("trinity_mini_ep8", False)
SOURCE = "https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json"
SLIDING, FULL = "sliding_attention", "full_attention"
KINDS = [SLIDING] * 4 + [FULL]
GLM_METRICS = ["attn_core_device_ms", "attn_core_roofline",
               "moe_experts_device_ms", "moe_experts_roofline",
               "moe_route_device_ms", "mtp_device_ms", "moe_held_load_ratio",
               "moe_load_max_over_mean"]
SDAR_METRICS = ["bd_attn_core_device_ms", "bd_attn_core_roofline",
                "bd_attn_pairs_computed_ratio", "bd_noise_device_ms",
                "sdar_experts_device_ms", "sdar_experts_roofline",
                "sdar_route_device_ms", "sdar_load_max_over_mean"]
SETUP_METRICS = ["setup_trace_lower_s", "setup_compile_s", "setup_cache_load_s",
                 "setup_programs", "setup_cache_misses", "setup_step_s"]
NEW_METRICS = ["win_attn_core_device_ms", "win_attn_core_roofline",
               "full_attn_core_device_ms", "full_attn_core_roofline",
               "win_attn_pairs_computed_ratio", "afmoe_experts_device_ms",
               "afmoe_experts_roofline", "afmoe_route_device_ms",
               "afmoe_load_max_over_mean", "afmoe_gate_norm_device_ms"]
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
TINY = common.find_traffic("tiny_train_tokens_grad", True)


# ------------------------------------------------------------ the manifest

def test_what_pr37_left_is_a_prefix_and_this_prs_entries_come_after_it():
    """What tests/benchmark/test_rowsum_metric.py's `test_what_pr36_left_is_
    a_prefix_and_the_one_comes_after_it` held, with `[45:46]` where it read
    to the end and the lists of configurations and cells as they were, and
    this PR's entries after them."""
    assert [c["name"] for c in MAN["configs"]] == [
        "resnet50_imagenet", "resnet18_imagenet", "convnext_b_imagenet",
        "glm_4_7_flash_ep8", "sdar_30b_a3b_ep8", "trinity_mini_ep8"]
    assert [w["name"] for w in MAN["workloads"]] == [
        "r50_train", "r18_train", "r50_train_dp4", "convnext_b_train",
        "glm47f_train", "sdar_bd_train", "trinity_mini_train"]
    assert all(c["reduced"] == [] for c in MAN["configs"][:3])
    assert [m["name"] for m in MAN["per_layer"][20:23]] == [
        "dwconv_device_ms", "dwconv_roofline", "norm_act_device_ms"]
    assert [m["name"] for m in MAN["per_layer"][23:31]] == GLM_METRICS
    for m in MAN["per_layer"][23:31]:
        assert m["workloads"] == ["glm47f_train"]  # no older list grew
    assert [m["name"] for m in MAN["per_layer"][31:39]] == SDAR_METRICS
    for m in MAN["per_layer"][31:39]:
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert m["workloads"] == ["sdar_bd_train"]
        assert (m["layer"], m["moves"]) == ("layers and kernels", "train_img_s_chip")
        assert (m["unit"] == "%") == m["name"].endswith("_roofline")
    assert [m["source"] for m in MAN["per_layer"][31:39]] == [
        "device_trace", "device_trace", "program_counter", "device_trace",
        "device_trace", "device_trace", "device_trace", "program_counter"]
    assert not any("sdar_bd_train" in m.get("workloads", [])
                   for m in MAN["per_layer"][:31])
    assert MAN["run_seconds"] == 10 and len(MAN["end_to_end"]) == 2
    glm, sdar, cell = MAN["workloads"][-3:]
    assert (glm["config"], glm["traffic"], glm["chips"]) == (
        "glm_4_7_flash_ep8", "train_s4096_b4_fixedjob", 1)
    assert (sdar["config"], sdar["traffic"], sdar["chips"]) == (
        "sdar_30b_a3b_ep8", "train_s4096_b4_bd_fixedjob", 1)
    assert set(sdar) == {"name", "config", "traffic", "chips", "why"}
    assert len(sdar["why"]) <= 200 and "8x" in sdar["why"]
    # one cell in seven asks for four chips, as before: 25 % rounded down
    assert [w["chips"] for w in MAN["workloads"]].count(4) == 1
    six = MAN["per_layer"][39:45]
    assert [m["name"] for m in six] == SETUP_METRICS
    for m in six:
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves"}
        assert (m["moves"], m["better"]) == ("setup_s", "lower")
    assert [m["unit"] for m in six] == ["s", "s", "s", "programs", "programs", "s"]
    assert [m["layer"] for m in six] == (
        ["entry point and compile cache"] * 5 + ["step factories"])
    (one,) = MAN["per_layer"][45:46]
    assert one == {
        "name": "moe_sum_rows_visited_ratio", "unit": "rows/row",
        "better": "lower", "source": "program_counter",
        "layer": "layers and kernels", "moves": "train_img_s_chip",
        "workloads": ["glm47f_train", "sdar_bd_train"]}
    # this PR: one cell on one chip and ten entries after the 46
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "trinity_mini_ep8", "train_s16384_b1_fixedjob", 1)
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert len(cell["why"]) <= 200 and "1/8 of deployed" in cell["why"]
    ten = MAN["per_layer"][46:]
    assert [m["name"] for m in ten] == NEW_METRICS
    for m in ten:
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert m["workloads"] == ["trinity_mini_train"]
        assert (m["layer"], m["moves"]) == ("layers and kernels", "train_img_s_chip")
        assert (m["unit"] == "%") == m["name"].endswith("_roofline")
        assert m["better"] == ("higher" if m["unit"] == "%" else "lower")
    assert [m["source"] for m in ten] == [
        "device_trace", "device_trace", "device_trace", "device_trace",
        "program_counter", "device_trace", "device_trace", "device_trace",
        "program_counter", "device_trace"]
    assert not any("trinity_mini_train" in m.get("workloads", [])
                   for m in MAN["per_layer"][:46])
    # every cell reports the six of set-up; the new cell its ten after them
    for w in MAN["workloads"]:
        got = [m["name"] for m in common.cell_metrics(MAN, w["name"], "per_layer")]
        if w["name"] == "trinity_mini_train":
            assert got[-16:] == SETUP_METRICS + NEW_METRICS
            assert not set(got) & set(GLM_METRICS + SDAR_METRICS)
        else:
            assert not set(got) & set(NEW_METRICS)


def test_the_cut_configurations_reduced_keys_are_its_files():
    """What `test_config_entries[trinity_mini_ep8]` held but for `reduced
    == []`."""
    (entry,) = [c for c in MAN["configs"] if c["name"] == "trinity_mini_ep8"]
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert entry["file"] == "benchmark/configs/trinity_mini_ep8.json"
    assert entry["source"] == CFG["source"] == SOURCE and CFG["name"] == entry["name"]
    assert entry["reduced"] == CFG["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert len(entry["why"]) <= 200 and "\n" not in entry["why"]
    assert "assumed" in CFG and "arch" in CFG and "factory" in CFG
    assert any(w["config"] == entry["name"] for w in MAN["workloads"])
    assert [c["file"] for c in MAN["configs"]].count(entry["file"]) == 1


def test_the_new_configuration_names_its_own_reference_and_adamw():
    """The two `[trinity_mini_ep8]` cases of the ResNet-only tests, turned
    round."""
    assert (CFG["reference"], CFG["arch"]["family"]) == ("afmoe", "afmoe")
    ref = common.find_reference(CFG)
    assert ref.__name__ == "benchmark.reference.afmoe"
    assert all(callable(getattr(ref, f)) for f in (
        "train_losses", "eval_logits", "train_report", "loss_and_grads",
        "hidden_states", "seen", "moved_bias"))
    assert common.find_module("shapes", "afmoe") is shapes
    opt = CFG["optimizer"]
    assert train_zoo.optimizer_args(opt, opt["lr_per_256"] * 1 / 256) == {
        "lr": pytest.approx(train_zoo_tokens.cell_lr(CFG, {"global_batch": 1})),
        "kind": "adamw", "b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1}
    assert train_zoo_tokens.cell_lr(CFG, {"global_batch": 1}) == pytest.approx(2e-4)


# ------------------------------------------- the cut, written down

# the catalog's `config` of Trinity-Mini
# (/opt/skills/guides/model-configs/architectures.jsonl), which is the
# published config.json's numbers
PUBLISHED = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144,
    "layer_types": [SLIDING, SLIDING, SLIDING, FULL] * 8,
    "load_balance_coeff": 0.001, "max_position_embeddings": 131072,
    "model_type": "afmoe", "moe_intermediate_size": 1024, "mup_enabled": True,
    "n_group": 1, "num_attention_heads": 32, "num_dense_layers": 2,
    "num_expert_groups": 1, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 32, "num_key_value_heads": 4, "num_limited_groups": 1,
    "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "route_norm": True, "route_scale": 2.826,
    "score_func": "sigmoid", "sliding_window": 2048,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True,
    "vocab_size": 200192,
}


def test_every_published_key_is_there_and_only_the_three_cuts_differ():
    differs = {k for k, v in PUBLISHED.items() if CFG.get(k, "absent") != v}
    assert differs == set(CFG["reduced"])
    assert CFG["published"] == {k: PUBLISHED[k] for k in CFG["reduced"]}
    assert (CFG["num_hidden_layers"], CFG["num_experts"], CFG["vocab_size"]) == (
        5, 16, 25024)
    assert CFG["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert CFG["num_experts"] * 8 == PUBLISHED["num_experts"]
    # no width is cut: heads, head size, hidden, the three feed-forward
    # widths, experts a token, the window
    for key in ("hidden_size", "head_dim", "num_attention_heads",
                "num_key_value_heads", "moe_intermediate_size",
                "num_experts_per_tok", "intermediate_size", "sliding_window",
                "num_shared_experts"):
        assert CFG[key] == PUBLISHED[key]
    assert "eight chips share every layer" in CFG["deployment"]
    assert "4 key/value heads do not divide by 8" in CFG["deployment"]
    assert "layers 4-7" in CFG["deployment"]
    for key in ("attention", "mask", "softmax_scale", "norms", "embedding_scale",
                "router", "bias_update", "balance_weight", "loss", "init",
                "row_buffer", "data", "lr", "gate_gradient", "left_out"):
        assert key in CFG["assumed"], key
    assert "Muon" in CFG["assumed"]["left_out"]
    assert "sliding_attention layers only" in CFG["assumed"]["attention"]
    assert CFG["assumed"]["balance_weight"].startswith("0:")


def test_the_arch_group_repeats_the_files_own_keys_and_names_the_share():
    arch = CFG["arch"]
    same = [k for k in arch if k in PUBLISHED
            and k not in ("layer_types", "num_dense_layers")]
    assert len(same) == 15 and all(arch[k] == CFG[k] for k in same)
    # the depth's cut: one of the two dense layers, then layers 4-7
    assert arch["layer_types"] == KINDS == (
        PUBLISHED["layer_types"][1:2] + PUBLISHED["layer_types"][4:8])
    assert (arch["num_dense_layers"], arch["num_hidden_layers"]) == (1, 5)
    assert "num_experts" not in arch  # 128 to route over, 16 held: two keys
    assert arch["router_experts"] == PUBLISHED["num_experts"]
    assert arch["held_experts"] == list(range(16)) and arch["row_buffer"] == 32768
    assert (arch["balance_weight"], arch["gate_gradient"]) == (0.0, False)
    assert arch["embed_scale"] == pytest.approx(2048 ** 0.5)
    assert CFG["factory"] == {
        "module": "parallel_cnn_tpu.nn.afmoe", "name": "trinity_mini",
        "kwargs": {"layer_types": KINDS, "num_dense_layers": 1,
                   "vocab_size": 25024, "held_experts": list(range(16)),
                   "row_buffer": 32768, "gate_gradient": False}}
    assert CFG["input"] == [16384]
    model = common.build_model(CFG)
    assert (list(model.layer_types), model.first_dense, model.attn.window,
            model.experts.balance, model.experts.scaling) == (
        KINDS, 1, 2048, 0.0, 2.826)
    assert model.embed_scale == pytest.approx(arch["embed_scale"])
    said = model.describe(16384, 16384, "tpu")
    assert said["attention_tiles_visited_by_kind"] == {SLIDING: 150, FULL: 528}
    assert said["attention_pairs_allowed_by_kind"] == {
        SLIDING: shapes.pairs_allowed(CFG, SLIDING),
        FULL: shapes.pairs_allowed(CFG, FULL)}


def test_the_cell_is_one_job_for_every_seed_at_one_sequence_a_step():
    cell = common.find_workload("trinity_mini_train")
    t = common.find_traffic(cell["traffic"], False)
    assert (t["runner"], t["sequence_length"], t["global_batch"], t["sequences"],
            t["loader"]) == ("train_zoo_tokens_grad", 16384, 1, 4, "device")
    assert isinstance(t["job_seed"], int) and 1 <= t["job_seed"] <= 10
    # a held expert's rows: 16,384 x 8 / 128 = 1,024 here, an eighth of the
    # 8 chips x 16,384 tokens x 8 / 128 of a deployment step
    assert shapes.held_rows(CFG) * t["global_batch"] == 16384 == 16 * 1024
    assert 8 * 16384 * 8 // 128 == 8 * 1024
    assert CFG["arch"]["row_buffer"] == 2 * 16384
    chk = t["check"]
    assert chk["batch"] == 1 and len(chk["loss_rtol"]) == 2 and chk["rows_tol"] >= 1
    assert 0 < chk["grad_gap_tol"] < 0.5
    assert "lr" not in chk and t["warmup_epochs"] == 4 and t["trace_seconds"] == 3.0
    assert "float8" in chk["note"] and "embed" in chk["note"]
    assert cell["accum_steps"] == 1 and "16k" in cell["who"]


# ------------------------------------------------------ the shape counter

def test_the_counter_gives_the_issues_macs_and_the_training_flops():
    ls = flops.layers(CFG)
    by = {l["name"]: l for l in ls}
    s = 16384
    win, full = 31_458_304, 134_225_920  # pairs a head: allowed, not visited
    assert (shapes.pairs_allowed(CFG, SLIDING), shapes.pairs_allowed(CFG, FULL)) \
        == (win, full)
    assert win / full == pytest.approx(0.2344, abs=1e-4)
    projections = s * (3 * 2048 * 4096 + 2 * 2048 * 512 + 0)  # q, gate, o, k, v
    dense = s * 3 * 2048 * 6144
    experts = s * 3 * 2048 * 1024  # S x 8 x 16 / 128 rows over the held 16
    shared, router = s * 3 * 2048 * 1024, s * 2048 * 128
    core = lambda pairs: pairs * 32 * 2 * 128  # noqa: E731
    head = s * 2048 * 25024
    assert [round(v / 1e12, 3) for v in (projections, dense, experts, core(win),
                                         core(full), head)] == [
        0.447, 0.618, 0.103, 0.258, 1.1, 0.84]
    # the full layer's core costs what the four window layers' cost together
    assert 1.0 < core(full) / (4 * core(win)) < 1.1
    want = (5 * projections + dense + 4 * (experts + shared + router)
            + 4 * core(win) + core(full) + head)
    assert flops.forward_macs(CFG) == want == 6_663_742_423_040
    assert flops.train_flops_per_image(CFG) == 6 * want
    assert flops.train_flops_per_image(CFG) / 1e12 == pytest.approx(39.98, abs=0.01)
    assert ls[0] == dict(name="embed", kind="dense", rows=0, cin=25024, cout=2048)
    assert flops.macs(ls[0]) == 0 and all(l["kind"] == "dense" for l in ls)
    assert by["l3.attn.core.qk"] == dict(
        name="l3.attn.core.qk", kind="dense", rows=win, cin=128, cout=32,
        weights=False)
    assert by["l4.attn.core.pv"]["rows"] == full
    assert by["l2.moe.experts.gate"] == dict(
        name="l2.moe.experts.gate", kind="dense", rows=16384, cin=2048, cout=1024,
        copies=16)
    assert by["l2.attn.qkv.gate"]["cout"] == 4096 and by["l2.attn.qkv.k"]["cout"] == 512
    assert by["l0.mlp.up"]["cout"] == 6144 and "l0.moe.route" not in by
    assert by["l1.moe.shared.down"]["cin"] == 1024 and "l1.mlp.up" not in by
    share = sum(flops.macs(l) for l in ls if ".core." in l["name"]) \
        / flops.forward_macs(CFG)
    assert 0.31 < share < 0.33  # about a third of the step


def test_the_counter_counts_the_parameters_of_the_programs_own_model():
    import jax

    model = common.build_model(CFG)
    params = jax.eval_shape(lambda k: model.init(k, tuple(CFG["input"]))[0],
                            jax.random.key(0))
    leaves = jax.tree_util.tree_leaves(params)
    assert sum(l.size for l in leaves) == 705_473_792
    assert 16 * 705_473_792 / 1e9 == pytest.approx(11.29, abs=0.01)  # GB
    weights = sum(l["cin"] * l["cout"] * l.get("copies", 1)
                  for l in flops.layers(CFG) if l.get("weights", True))
    assert weights == sum(l.size for l in leaves if l.ndim >= 2) == 705_429_504


def test_the_kernels_operations_and_bytes_are_the_hand_counted_ones():
    passes = shapes.attention_core_passes(CFG, 1)
    assert len(passes) == 10
    assert [p["layer_kind"] for p in passes[::2]] == KINDS
    assert [p["layer"] for p in passes] == [0, 0, 1, 1, 2, 2, 3, 3, 4, 4]
    fwd = lambda pairs: 2 * 32 * pairs * (128 + 128)  # noqa: E731
    assert passes[0]["flops"] == fwd(31_458_304) and passes[1]["flops"] == 2 * fwd(31_458_304)
    assert passes[8]["flops"] == fwd(134_225_920)
    # q and out over 32 heads, k and v over 4, 16,384 positions, bf16
    assert passes[0]["bytes"] == 16384 * 128 * 2 * (32 + 32 + 4 + 4)
    assert passes[9]["bytes"] == 16384 * 128 * 2 * (4 * 32 + 4 * 4)
    by_kind = lambda kind: shapes.least_seconds(  # noqa: E731
        [p for p in passes if p["layer_kind"] == kind], PEAK)
    assert by_kind(SLIDING) == pytest.approx(4 * 3 * fwd(31_458_304) / 197e12)
    assert by_kind(FULL) == pytest.approx(3 * fwd(134_225_920) / 197e12)
    assert by_kind(SLIDING) == pytest.approx(31.4e-3, rel=1e-2)
    assert by_kind(FULL) == pytest.approx(33.5e-3, rel=1e-2)
    rows = [16384, 15000, 17200, 16384]
    ex = shapes.expert_passes(CFG, rows)
    assert len(ex) == 4 * 3 * 3
    assert sum(p["flops"] for p in ex) == 3 * 3 * 2 * sum(rows) * 2048 * 1024
    gate_fwd = ex[0]
    assert gate_fwd["bytes"] == 16384 * (2048 + 1024) * 2 + 16 * 2048 * 1024 * 2
    # 1,024 rows an expert, an eighth of a deployment's and half of
    # `sdar_bd_train`'s: still bound by the operations, by less
    assert 1.5 < (gate_fwd["flops"] / 197e12) / (gate_fwd["bytes"] / 819e9) < 2.0


# ---------------------------------------------------------- the ten readers

def _read(name, run):
    return importlib.import_module(f"benchmark.layer_metrics.{name}").read(run)


CATALOG = """HloModule jit_step

ENTRY %main (p: bf16[8,8]) -> bf16[8,8] {
  %p = bf16[8,8]{1,0} parameter(0)
  %grouped_causal_attention_fwd.1 = bf16[8,8]{1,0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/grad/jvp(l1)/attn/core/cond/branch_0_fun/grouped_causal_attention_fwd/pallas_call"}
  %grouped_causal_attention_fwd.2 = bf16[8,8]{1,0} custom-call(%grouped_causal_attention_fwd.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/grad/jvp(l4)/attn/core/cond/branch_0_fun/grouped_causal_attention_fwd/pallas_call"}
  %grouped_causal_attention_bwd.2 = bf16[8,8]{1,0} custom-call(%grouped_causal_attention_fwd.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/grad/transpose(jvp(l4))/grad/jvp(l4)/checkpoint/attn/core/cond/branch_0_fun/grouped_causal_attention_bwd/pallas_call"}
  %grouped_causal_attention_bwd.1 = bf16[8,8]{1,0} custom-call(%grouped_causal_attention_bwd.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/grad/transpose(jvp(l1))/grad/jvp(l1)/checkpoint/attn/core/cond/branch_0_fun/grouped_causal_attention_bwd/pallas_call"}
  %norm.f = bf16[8,8]{1,0} negate(%p), metadata={op_name="jit(step)/grad/jvp(l1)/attn/qk_norm/mul"}
  %gate.f = bf16[8,8]{1,0} negate(%norm.f), metadata={op_name="jit(step)/grad/jvp(l1)/attn/gate/logistic"}
  %post.f = bf16[8,8]{1,0} negate(%gate.f), metadata={op_name="jit(step)/grad/jvp(l1)/moe/post_norm/mul"}
  %pre.f = bf16[8,8]{1,0} negate(%post.f), metadata={op_name="jit(step)/grad/jvp(l1)/moe/norm/mul"}
  %top.f = bf16[8,8]{1,0} negate(%pre.f), metadata={op_name="jit(step)/grad/jvp(l1)/moe/route/top_k"}
  %rows.f = bf16[8,8]{1,0} negate(%top.f), metadata={op_name="jit(step)/grad/jvp(l1)/moe/dispatch/gather"}
  %w.f = bf16[8,8]{1,0} negate(%p), metadata={op_name="jit(step)/grad/jvp(l1)/moe/experts/convert_element_type"}
  %ragged-dot-none.1 = bf16[8,8]{1,0} custom-call(%rows.f, %w.f), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %back.f = bf16[8,8]{1,0} negate(%ragged-dot-none.1), metadata={op_name="jit(step)/grad/jvp(l1)/moe/combine/gather"}
  %h.f = bf16[8,8]{1,0} negate(%back.f), metadata={op_name="jit(step)/grad/jvp(grad)/head/dot_general"}
  ROOT %o.1 = bf16[8,8]{1,0} negate(%h.f), metadata={op_name="jit(step)/optimizer/neg"}
}
"""
SPANS = {"grouped_causal_attention_fwd.1": (0, 4),
         "grouped_causal_attention_fwd.2": (4, 14),
         "grouped_causal_attention_bwd.2": (14, 36),
         "grouped_causal_attention_bwd.1": (36, 45), "norm.f": (45, 47),
         "gate.f": (47, 48), "post.f": (48, 51), "pre.f": (51, 52),
         "top.f": (52, 54), "rows.f": (54, 57), "w.f": (57, 58),
         "ragged-dot-none.1": (58, 66), "back.f": (66, 69), "h.f": (69, 74),
         "o.1": (74, 80)}


def _hand_made(peak=None, counters=None, platform="tpu", config=CFG):
    ms = 1e6
    ops = [tr.Op(n, "other", base * ms + a * ms, base * ms + b * ms)
           for base in (0, 100) for n, (a, b) in SPANS.items()]
    trace = tr.Trace(ops={0: ops}, async_ops={},
                     modules={0: [("jit_step(7)", 0.0, 90 * ms),
                                  ("jit_step(7)", 100 * ms, 190 * ms)]}, host={})
    counters = dict({"batch_per_chip": 1}, **(counters or {}))
    return types.SimpleNamespace(
        trace=trace, spans={}, counters=counters, e2e={}, window_s=0.2,
        program=r"^jit_step\b", device={"platform": platform},
        ctx=types.SimpleNamespace(peak=peak, config=config))


@pytest.fixture
def catalog():
    from parallel_cnn_tpu.obs import programs

    programs.record("jit_step", CATALOG)
    yield programs.lookup("jit_step")
    programs.clear()


def test_the_readers_on_a_hand_made_trace_give_hand_computed_numbers(catalog):
    assert (catalog["grouped_causal_attention_bwd.2"].scope,
            catalog["grouped_causal_attention_bwd.2"].phase) == ("l4/attn/core", "bwd")
    assert afmoe_scopes.core_kind(catalog["grouped_causal_attention_fwd.1"],
                                  KINDS) == SLIDING
    assert afmoe_scopes.core_kind(catalog["grouped_causal_attention_fwd.2"],
                                  KINDS) == FULL
    assert afmoe_scopes.core_kind(catalog["gate.f"], KINDS) is None
    run = _hand_made(counters={
        "moe_rows_held": [[1] * 4, [16384, 15000, 17200, 16384]],
        "moe_load_max_over_mean": [[9.0] * 4, [1.5, 2.25, 1.1, 1.2]]})
    assert _read("win_attn_core_device_ms", run) == pytest.approx(4 + 9)
    assert _read("full_attn_core_device_ms", run) == pytest.approx(10 + 22)
    assert _read("afmoe_experts_device_ms", run) == pytest.approx(1 + 8)
    assert _read("afmoe_route_device_ms", run) == pytest.approx(2 + 3 + 3)
    # qk_norm, gate and post_norm; the sub-layer's FIRST norm is not of them
    assert _read("afmoe_gate_norm_device_ms", run) == pytest.approx(2 + 1 + 3)
    assert _read("afmoe_load_max_over_mean", run) == 2.25  # the newest epoch's worst
    # the program's own statement: 150 tiles of 512 x 512 over the window's
    # 31,458,304 pairs on a TPU; off it, turns of 512 queries hold as many
    assert _read("win_attn_pairs_computed_ratio", run) == pytest.approx(
        150 * 512 * 512 / 31_458_304)
    assert _read("win_attn_pairs_computed_ratio", _hand_made(platform="cpu")) \
        == pytest.approx(1.25, abs=1e-3)
    for name in ("win_attn_core_roofline", "full_attn_core_roofline",
                 "afmoe_experts_roofline"):
        assert _read(name, run) is None  # no published peak


def test_the_roofline_shares_are_least_time_over_measured_by_layer_kind(catalog):
    rows = [16384, 15000, 17200, 16384]
    run = _hand_made(peak=PEAK, counters={"moe_rows_held": [rows]})
    passes = shapes.attention_core_passes(CFG, 1)
    for name, kind, took in (("win_attn_core_roofline", SLIDING, 13e-3),
                             ("full_attn_core_roofline", FULL, 32e-3)):
        least = shapes.least_seconds(
            [p for p in passes if p["layer_kind"] == kind], PEAK)
        assert _read(name, run) == pytest.approx(100 * least / took)
    ex = shapes.least_seconds(shapes.expert_passes(CFG, rows), PEAK)
    assert _read("afmoe_experts_roofline", run) == pytest.approx(100 * ex / 9e-3)
    fewer = _hand_made(peak=PEAK, counters={"moe_rows_held": [[r // 2 for r in rows]]})
    assert _read("afmoe_experts_roofline", fewer) < _read("afmoe_experts_roofline", run)


def test_the_readers_find_nothing_where_the_program_has_no_such_thing():
    """A conv net's step, or the parent's (which has neither the scopes nor
    the model): nothing named, nothing counted, nothing raised."""
    from parallel_cnn_tpu.obs import programs

    programs.record("jit_step", CATALOG.replace("/attn/", "/s1b1/").replace(
        "/moe/", "/mid/").replace("custom-call(", "negate(").replace(
        "ragged-dot-none", "conv").replace("/post_norm", "/bn").replace(
        "/gate/", "/relu/").replace("/qk_norm", "/bn"))
    try:
        run = _hand_made(peak=PEAK)
        named = [m for m in NEW_METRICS if m != "win_attn_pairs_computed_ratio"]
        assert [m for m in named if _read(m, run) is not None] == []
        # a configuration of another family: no layer kinds to split by
        glm = common.find_config("glm_4_7_flash_ep8", False)
        assert _read("win_attn_core_device_ms", _hand_made(config=glm)) is None
        assert _read("win_attn_pairs_computed_ratio", _hand_made(config=glm)) is None
        # the parent: the configuration names a factory its program lacks
        run.ctx.config = dict(CFG, factory=dict(CFG["factory"], name="no_such_factory"))
        assert _read("win_attn_pairs_computed_ratio", run) is None
        run.ctx.config = dict(CFG, factory=dict(
            CFG["factory"], module="parallel_cnn_tpu.nn.no_such_module"))
        assert _read("win_attn_pairs_computed_ratio", run) is None
    finally:
        programs.clear()


# ------------------------------ the whole command on the CPU, real files

@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    return tmp_path_factory.mktemp("afmoe-cache")


def _env(cache):
    return dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="",
                JAX_COMPILATION_CACHE_DIR=str(cache))


def _run_cell(cache, seed, trace):
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "tiny_afmoe_train",
         "--seed", str(seed), "--seconds", "0.3", "--trace", str(trace),
         "--notes", "1"],
        cwd=ROOT, env=_env(cache), capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    notes = json.loads([l for l in out.stderr.splitlines() if l.startswith("{")][-1])
    return line, notes


@pytest.fixture(scope="module")
def two_runs(cache):
    """Two seeds, the second more than 32 signed bits hold and traced."""
    return _run_cell(cache, 4123000017, 0), _run_cell(cache, 2147483659, 1)


def test_two_seeds_are_one_job_the_same_rows_held_and_the_same_losses(two_runs):
    """`--seed` draws the check; the timed job — weights, sequences and
    shuffles — is drawn from the traffic file's `job_seed`."""
    (a, na), (b, nb) = two_runs
    for line, notes in two_runs:
        assert line["correct"] is True and line["failed"] == 0
        assert line["device"]["platform"] == "cpu"
        assert notes["counters"]["compiles_in_window"] == 0
        assert notes["counters"]["moe_overflow_rows"] == [0, 0]
    assert set(a["metrics"]) == {"train_img_s_chip", "setup_s"}
    n = min(na["counters"]["epochs"], nb["counters"]["epochs"]) + 1
    assert n >= 3
    assert na["counters"]["moe_rows_held"][:n] == nb["counters"]["moe_rows_held"][:n]
    assert na["counters"]["losses"][:n] == nb["counters"]["losses"][:n]
    assert len(set(map(tuple, na["counters"]["moe_rows_held"][:n]))) > 1  # it trains
    ca, cb = na["notes"]["check_losses"], nb["notes"]["check_losses"]
    assert ca["reference"] != cb["reference"]  # another seed, another check
    rows = na["notes"]["check_rows_held"]
    assert len(rows["system"]) == 2 and len(rows["system"][0]) == 2


def test_the_traced_tiny_cell_reports_the_new_metrics_and_the_unlisted_ones(two_runs):
    line, _ = two_runs[1]
    m = {k: v["value"] for k, v in line["metrics"].items()}
    # CPU numbers, never device numbers: only that each reader found its ops
    for name in ("win_attn_core_device_ms", "full_attn_core_device_ms",
                 "afmoe_experts_device_ms", "afmoe_route_device_ms",
                 "afmoe_gate_norm_device_ms", "opt_device_ms", "step_device_ms",
                 "fwd_device_ms", "bwd_device_ms"):
        assert m[name] > 0, name
    assert m["win_attn_core_device_ms"] + m["full_attn_core_device_ms"] \
        + m["afmoe_experts_device_ms"] + m["afmoe_route_device_ms"] \
        + m["afmoe_gate_norm_device_ms"] < m["step_device_ms"]
    # 32 positions in turns of 8 queries under a window of 8: 7 turns' tiles
    # over 8 x 9 / 2 + 24 x 8 pairs
    assert m["win_attn_pairs_computed_ratio"] == pytest.approx(7 * 64 / 228)
    assert m["afmoe_load_max_over_mean"] >= 1
    assert m["scope_named_pct"] > 90 and m["stem_device_ms"] == 0
    assert not set(m) & {"mfu_pct", "win_attn_core_roofline",
                         "full_attn_core_roofline", "afmoe_experts_roofline"}
    assert not set(m) & set(GLM_METRICS + SDAR_METRICS)


def test_the_comparison_tool_runs_by_name_of_a_cell(cache):
    out = subprocess.run(
        [sys.executable, "benchmark/tools/compare_afmoe.py", "--workload",
         "tiny_afmoe_train", "--seeds", "1", "--seed-list", "4117000003",
         "--controls", "gate_off,rope_in_full"],
        cwd=ROOT, env=_env(cache), capture_output=True, text=True, timeout=900)
    rows = [json.loads(l) for l in out.stdout.strip().splitlines()]
    assert out.returncode == 0, out.stderr[-3000:]
    clean, listed, ungated, turned = rows
    assert clean["correct"] is True and "control" not in clean
    assert (clean["seed"], listed["seed"]) == (2701000000, 4117000003)
    assert listed["correct"] is True
    got = clean["check_rows_held"]["system"]
    assert len(got) == 2 and len(got[0]) == 2
    assert max(clean["loss_gaps"]) < 1e-3 and clean["rows_gap"] <= 4
    assert clean["check_grad_gap"]["widest"] < 0.05 == TINY["check"]["grad_gap_tol"]
    assert clean["check_grad_gap"]["leaves"] == 3 * 11 + 3 + 2 * 7 + 3
    assert (ungated["control"], turned["control"]) == ("gate_off", "rope_in_full")
    # the gate dropped: the two losses stay inside their limits, a gate's
    # weights take no gradient at all
    assert ungated["correct"] is False and max(ungated["loss_gaps"]) < 0.01
    assert ungated["check_grad_gap"]["widest"] == 1.0
    assert ungated["check_grad_gap"]["leaf"].endswith("['attn']['gate']")
    # RoPE in the full layer too: its q and k point elsewhere
    assert turned["correct"] is False
    assert turned["check_grad_gap"]["widest"] > 0.3
    assert turned["check_grad_gap"]["leaf"].startswith("['layers'][2]['attn']")


def test_the_cells_runner_is_the_token_runner_with_one_more_reading():
    from benchmark.runners import train_zoo_tokens_grad as grad

    assert grad.run is not train_zoo_tokens.run
    assert grad.cell_lr is train_zoo_tokens.cell_lr
    t = common.find_traffic("train_s16384_b1_fixedjob", False)
    assert "direction" in t["check"]["note"] and "rope" in t["check"]["note"].lower()
    theirs = train_zoo_tokens.checker
    ctx = types.SimpleNamespace(config={"factory": {"module": "no.such", "name": "x",
                                                    "kwargs": {}}},
                                traffic={"sequence_length": 8, "global_batch": 4,
                                         "sequences": 8})
    with pytest.raises(ModuleNotFoundError):
        grad.run(ctx)
    assert train_zoo_tokens.checker is theirs  # the swap is undone whatever happens
