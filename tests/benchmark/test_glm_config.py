"""The GLM-4.7-Flash configuration as the benchmark holds it: the
manifest's appended entries (and what four older cases, which pin the
manifest as PR 27 left it and are deselected in tests/conftest.py, held of
the older entries), the cut written down, the shape counter against the
program's own parameter tree, the eight new readers on a hand-made trace,
and the whole command on the CPU through the real files
(`tiny_glm_train`, tests/benchmark/cells): two seeds, one job."""

import importlib
import json
import os
import subprocess
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import common, flops, glm_scopes, trace_reduce as tr  # noqa: E402
from benchmark.runners import train_zoo, train_zoo_tokens  # noqa: E402
from benchmark.shapes import glm_moe as shapes  # noqa: E402

MAN = common.manifest()
CFG = common.find_config("glm_4_7_flash_ep8", False)
SOURCE = "https://huggingface.co/zai-org/GLM-4.7-Flash/blob/main/config.json"
NEW_METRICS = ["attn_core_device_ms", "attn_core_roofline",
               "moe_experts_device_ms", "moe_experts_roofline",
               "moe_route_device_ms", "mtp_device_ms", "moe_held_load_ratio",
               "moe_load_max_over_mean"]
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


# ------------------------------------------------------------ the manifest

def test_the_older_entries_are_a_prefix_and_the_new_ones_are_appended():
    """What `test_the_manifest_gained_one_configuration_one_cell_and_three_
    metrics` held, with this PR's entries after them."""
    assert [c["name"] for c in MAN["configs"]] == [
        "resnet50_imagenet", "resnet18_imagenet", "convnext_b_imagenet",
        "glm_4_7_flash_ep8"]
    assert [w["name"] for w in MAN["workloads"]] == [
        "r50_train", "r18_train", "r50_train_dp4", "convnext_b_train",
        "glm47f_train"]
    assert all(c["reduced"] == [] for c in MAN["configs"][:3])
    convnext = MAN["workloads"][3]
    assert (convnext["config"], convnext["traffic"], convnext["chips"]) == (
        "convnext_b_imagenet", "train_b128_resident", 1)
    assert [m["name"] for m in MAN["per_layer"][20:23]] == [
        "dwconv_device_ms", "dwconv_roofline", "norm_act_device_ms"]
    for m in MAN["per_layer"][20:23]:
        assert m["workloads"] == ["convnext_b_train"]
    assert [m["name"] for m in MAN["per_layer"][23:]] == NEW_METRICS
    for m in MAN["per_layer"][23:]:
        assert m["workloads"] == ["glm47f_train"]
        assert (m["layer"], m["moves"]) == ("layers and kernels", "train_img_s_chip")
        assert (m["unit"] == "%") == m["name"].endswith("_roofline")
    assert [m["source"] for m in MAN["per_layer"][23:]] == (
        ["device_trace"] * 6 + ["program_counter"] * 2)
    # no older metric's list of cells grew: the new cell reports the unlisted ones
    assert not any("glm47f_train" in m.get("workloads", [])
                   for m in MAN["per_layer"][:23])
    assert MAN["run_seconds"] == 10 and len(MAN["end_to_end"]) == 2
    cell = MAN["workloads"][-1]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "glm_4_7_flash_ep8", "train_s4096_b4_fixedjob", 1)


def test_the_new_entrys_reduced_keys_are_its_files():
    """What `test_config_entries[glm_4_7_flash_ep8]` held, but for
    `reduced == []`: the entry's list equals the file's."""
    entry = MAN["configs"][-1]
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert entry["file"] == "benchmark/configs/glm_4_7_flash_ep8.json"
    assert entry["source"] == CFG["source"] == SOURCE and CFG["name"] == entry["name"]
    assert entry["reduced"] == CFG["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert len(entry["why"]) <= 200 and "\n" not in entry["why"]
    assert "assumed" in CFG and "arch" in CFG and "factory" in CFG
    assert [c["file"] for c in MAN["configs"]].count(entry["file"]) == 1


def test_the_new_configuration_names_its_own_reference_and_adamw():
    """The two `[glm_4_7_flash_ep8]` cases of the ResNet-only tests, turned
    round: this one names its family, and its optimizer is AdamW's five."""
    assert (CFG["reference"], CFG["arch"]["family"]) == ("glm_moe", "glm_moe")
    ref = common.find_reference(CFG)
    assert ref.__name__ == "benchmark.reference.glm_moe"
    assert all(callable(getattr(ref, f)) for f in (
        "train_losses", "eval_logits", "train_report", "loss_and_grads"))
    for name in ("resnet50_imagenet", "resnet18_imagenet"):
        assert common.find_reference(common.find_config(name, False)).__name__ \
            == "benchmark.reference.resnet"
    opt = CFG["optimizer"]
    assert train_zoo.optimizer_args(opt, opt["lr_per_256"] * 4 / 256) == {
        "lr": pytest.approx(2e-4), "kind": "adamw", "b1": 0.9, "b2": 0.95,
        "eps": 1e-8, "weight_decay": 0.1}


# ------------------------------------------- the cut, written down

PUBLISHED = {
    "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 10240, "max_position_embeddings": 202752,
    "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
    "topk_method": "noaux_tc", "norm_topk_prob": True,
    "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
    "n_routed_experts": 64, "n_shared_experts": 1,
    "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
    "first_k_dense_replace": 1, "num_hidden_layers": 47,
    "num_key_value_heads": 20, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 1000000, "tie_word_embeddings": False, "q_lora_rank": 768,
    "kv_lora_rank": 512, "qk_nope_head_dim": 192, "qk_rope_head_dim": 64,
    "v_head_dim": 256, "vocab_size": 154880,
}


def test_every_published_key_is_there_and_only_the_three_cuts_differ():
    differs = {k for k, v in PUBLISHED.items() if CFG.get(k, "absent") != v}
    assert differs == set(CFG["reduced"])
    assert CFG["published"] == {k: PUBLISHED[k] for k in CFG["reduced"]}
    assert (CFG["num_hidden_layers"], CFG["n_routed_experts"],
            CFG["vocab_size"]) == (5, 8, 19360)
    assert CFG["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    # the floors: the dense layer and four sparse ones, 8 experts, 1/8 of the rows
    assert CFG["num_hidden_layers"] - CFG["first_k_dense_replace"] >= 4
    assert "eight chips share every layer" in CFG["deployment"]
    for key in ("row_buffer", "norms", "rope", "init", "lr", "gate_gradient",
                "bias_update_speed", "balance_weight", "mtp_weight", "data",
                "left_out"):
        assert key in CFG["assumed"]
    assert "Muon" in CFG["assumed"]["left_out"]


def test_the_arch_group_repeats_the_files_own_keys_and_names_the_share():
    arch = CFG["arch"]
    shared = [k for k in arch if k in PUBLISHED]
    assert len(shared) == 18 and all(arch[k] == CFG[k] for k in shared)
    assert "n_routed_experts" not in arch  # 64 to route over, 8 held: two keys
    assert arch["router_experts"] == PUBLISHED["n_routed_experts"]
    assert arch["held_experts"] == list(range(8)) and arch["row_buffer"] == 16384
    assert (arch["bias_update_speed"], arch["balance_weight"],
            arch["mtp_weight"]) == (1e-3, 1e-4, 0.3)
    assert CFG["factory"] == {
        "module": "parallel_cnn_tpu.nn.glm_moe", "name": "glm_4_7_flash",
        "kwargs": {"num_hidden_layers": 5, "vocab_size": 19360,
                   "held_experts": list(range(8)), "row_buffer": 16384,
                   "gate_gradient": False}}
    assert arch["gate_gradient"] is False  # the reference leaves it out too
    assert CFG["input"] == [4096]


def test_the_cell_is_one_job_for_every_seed_at_the_deployments_rows():
    cell = common.find_workload("glm47f_train")
    t = common.find_traffic(cell["traffic"], False)
    assert (t["runner"], t["sequence_length"], t["global_batch"], t["sequences"],
            t["loader"]) == ("train_zoo_tokens", 4096, 4, 16, "device")
    assert isinstance(t["job_seed"], int) and 1 <= t["job_seed"] <= 16
    # each held expert's rows: 8 chips x 2,048 tokens x 4 / 64 in the
    # deployment, 16,384 tokens x 4 / 64 here
    assert 8 * 2048 * 4 // 64 == t["global_batch"] * 4096 * 4 // 64 == 1024
    assert shapes.held_rows(CFG) * t["global_batch"] == 8192
    assert CFG["arch"]["row_buffer"] == 2 * 8192
    chk = t["check"]
    assert chk["batch"] == 1 and len(chk["loss_rtol"]) == 2 and chk["rows_tol"] >= 1
    # the check runs the configuration's optimizer at the cell's own rate,
    # which lies inside the issue's range; the warm-up is set-up's
    assert "lr" not in chk and t["warmup_epochs"] >= 1
    assert 1e-4 <= train_zoo_tokens.cell_lr(CFG, t) <= 3e-4
    assert cell["accum_steps"] in (1, 2) and "who" in cell
    assert "8x" in cell["why"] and "job_seed" in cell["why"]


# ------------------------------------------------------ the shape counter

def test_the_counter_gives_the_issues_macs_and_the_training_flops():
    ls = flops.layers(CFG)
    assert flops.forward_macs(CFG) == 1_959_704_657_920  # 1.96 TMAC a sequence
    assert flops.train_flops_per_image(CFG) == 6 * 1_959_704_657_920
    assert ls[0] == dict(name="embed", kind="dense", rows=0, cin=19360, cout=2048)
    assert flops.macs(ls[0]) == 0 and all(l["kind"] == "dense" for l in ls)
    by = {l["name"]: l for l in ls}
    pairs = 4096 * 4097 // 2  # the causal half
    assert by["l3.attn.core.qk"] == dict(
        name="l3.attn.core.qk", kind="dense", rows=pairs, cin=256, cout=20,
        weights=False)
    assert by["mtp.l0.attn.core.pv"]["rows"] == pairs
    assert by["l2.moe.experts.gate"] == dict(
        name="l2.moe.experts.gate", kind="dense", rows=2048, cin=2048,
        cout=1536, copies=8)  # 4,096 x 4 x 8 / 64 rows over the held eight
    assert by["l2.moe.shared.down"]["rows"] == 4096
    assert by["l2.moe.route"]["cout"] == 64 and "l0.moe.route" not in by
    assert by["l0.mlp.up"]["cout"] == 10240 and by["head"]["cout"] == 19360
    core = sum(flops.macs(l) for l in ls if ".core." in l["name"])
    assert 0.26 < core / flops.forward_macs(CFG) < 0.27


def test_the_counter_counts_the_parameters_of_the_programs_own_model():
    import jax

    model = common.build_model(CFG)
    params = jax.eval_shape(lambda k: model.init(k, tuple(CFG["input"]))[0],
                            jax.random.key(0))
    leaves = jax.tree_util.tree_leaves(params)
    assert sum(l.size for l in leaves) == 706_516_480
    weights = sum(l["cin"] * l["cout"] * l.get("copies", 1)
                  for l in flops.layers(CFG) if l.get("weights", True))
    assert weights == sum(l.size for l in leaves if l.ndim >= 2)
    assert 16 * 706_516_480 / 1e9 == pytest.approx(11.3, abs=0.01)  # GB


def test_the_kernels_operations_and_bytes_are_the_hand_counted_ones():
    passes = shapes.attention_core_passes(CFG, 4)
    assert len(passes) == 12 and shapes.attention_cores(CFG) == 6
    fwd = 2 * 4 * 20 * (4096 * 4097 // 2) * (256 + 256)
    assert passes[0]["flops"] == fwd and passes[1]["flops"] == 2 * fwd
    assert passes[0]["bytes"] == 4 * 4096 * 20 * 2 * 4 * 256
    least = shapes.least_seconds(passes, PEAK)
    assert least == pytest.approx(6 * 3 * fwd / 197e12)  # compute-bound
    assert least == pytest.approx(62.8e-3, rel=1e-2)
    rows = [8192, 8000, 8400, 8192, 8192]
    ex = shapes.expert_passes(CFG, rows)
    assert len(ex) == 5 * 3 * 3
    assert sum(p["flops"] for p in ex) == 3 * 3 * 2 * sum(rows) * 2048 * 1536
    gate_fwd = ex[0]
    assert gate_fwd["bytes"] == 8192 * (2048 + 1536) * 2 + 8 * 2048 * 1536 * 2
    # 1,024 rows an expert: the weights' bytes and the FLOPs are of one order
    assert 0.5 < (gate_fwd["flops"] / 197e12) / (gate_fwd["bytes"] / 819e9) < 2


# ------------------------------------------------------- the eight readers

def _read(name, run):
    return importlib.import_module(f"benchmark.layer_metrics.{name}").read(run)


CATALOG = """HloModule jit_step

ENTRY %main (p: bf16[8,8]) -> bf16[8,8] {
  %p = bf16[8,8]{1,0} parameter(0)
  %qk.f = bf16[8,8]{1,0} negate(%p), metadata={op_name="jit(step)/grad/jvp(l1)/attn/core/checkpoint/nqhd,nkhd->nhqk/dot_general"}
  %qk.b = bf16[8,8]{1,0} negate(%qk.f), metadata={op_name="jit(step)/grad/transpose(jvp(l1))/grad/jvp(l1)/checkpoint/attn/core/checkpoint/rematted_computation/nqhd,nkhd->nhqk/dot_general"}
  %top.f = bf16[8,8]{1,0} negate(%qk.b), metadata={op_name="jit(step)/grad/jvp(l1)/moe/route/top_k"}
  %rows.f = bf16[8,8]{1,0} negate(%top.f), metadata={op_name="jit(step)/grad/jvp(l1)/moe/dispatch/gather"}
  %w.f = bf16[8,8]{1,0} negate(%p), metadata={op_name="jit(step)/grad/jvp(l1)/moe/experts/convert_element_type"}
  %ragged-dot-none.1 = bf16[8,8]{1,0} custom-call(%rows.f, %w.f), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %back.f = bf16[8,8]{1,0} negate(%ragged-dot-none.1), metadata={op_name="jit(step)/grad/jvp(l1)/moe/combine/gather"}
  %sh.f = bf16[8,8]{1,0} negate(%back.f), metadata={op_name="jit(step)/grad/jvp(l1)/moe/shared/dot_general"}
  %m.f = bf16[8,8]{1,0} negate(%sh.f), metadata={op_name="jit(step)/grad/jvp(mtp)/l0/attn/core/checkpoint/dot_general"}
  %mw.f = bf16[8,8]{1,0} negate(%p), metadata={op_name="jit(step)/grad/transpose(jvp(mtp))/l0/grad/jvp(mtp)/l0/checkpoint/rematted_computation/moe/experts/convert_element_type"}
  %ragged-dot-none.2 = bf16[8,8]{1,0} custom-call(%m.f, %mw.f), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %mh.f = bf16[8,8]{1,0} negate(%ragged-dot-none.2), metadata={op_name="jit(step)/grad/jvp(mtp)/head/dot_general"}
  ROOT %o.1 = bf16[8,8]{1,0} negate(%mh.f), metadata={op_name="jit(step)/optimizer/neg"}
}
"""
SPANS = {"qk.f": (0, 10), "qk.b": (10, 30), "top.f": (30, 32), "rows.f": (32, 35),
         "w.f": (35, 36), "ragged-dot-none.1": (36, 44), "back.f": (44, 47),
         "sh.f": (47, 52), "m.f": (52, 57), "mw.f": (57, 58),
         "ragged-dot-none.2": (58, 62), "mh.f": (62, 64), "o.1": (64, 70)}


def _hand_made(peak=None, config=None, counters=None):
    ms = 1e6
    ops = [tr.Op(n, "other", base * ms + a * ms, base * ms + b * ms)
           for base in (0, 100) for n, (a, b) in SPANS.items()]
    trace = tr.Trace(ops={0: ops}, async_ops={},
                     modules={0: [("jit_step(7)", 0.0, 80 * ms),
                                  ("jit_step(7)", 100 * ms, 180 * ms)]}, host={})
    counters = dict({"batch_per_chip": 4}, **(counters or {}))
    return types.SimpleNamespace(
        trace=trace, spans={}, counters=counters, e2e={}, window_s=0.2,
        program=r"^jit_step\b", device={},
        ctx=types.SimpleNamespace(peak=peak, config=config or {}))


@pytest.fixture
def catalog():
    from parallel_cnn_tpu.obs import programs

    programs.record("jit_step", CATALOG)
    yield programs.lookup("jit_step")
    programs.clear()


def test_the_catalog_gives_a_compiler_made_kernel_its_operands_layer(catalog):
    assert (catalog["ragged-dot-none.1"].scope, catalog["ragged-dot-none.1"].phase) \
        == ("l1/moe/experts/ragged-dot-none", "fwd")
    assert (catalog["ragged-dot-none.2"].scope, catalog["ragged-dot-none.2"].phase) \
        == ("mtp/l0/moe/experts/ragged-dot-none", "bwd")
    assert catalog["qk.b"].scope == "l1/attn/core" and catalog["qk.b"].phase == "bwd"


def test_only_the_grouped_matmul_is_the_experts_among_the_custom_calls():
    """A custom-call is judged by what it is: the compiler's grouped matmul
    by its own name wherever its operands came from, a custom-call the
    program scoped (a fused kernel in the MTP head, say) by that scope, and
    one that carries no name at all stays unnamed, as before PR 32."""
    from parallel_cnn_tpu.obs import programs

    head = 'metadata={op_name="jit(step)/grad/jvp(mtp)/head/dot_general"}'
    cat = programs.parse(f"""HloModule jit_step

ENTRY %main (p: bf16[8,8]) -> bf16[8,8] {{
  %p = bf16[8,8]{{1,0}} parameter(0)
  %rows.f = bf16[8,8]{{1,0}} negate(%p), metadata={{op_name="jit(step)/grad/jvp(l2)/moe/dispatch/gather"}}
  %ragged-dot-none.7 = bf16[8,8]{{1,0}} custom-call(%p, %rows.f), custom_call_target="tpu_custom_call", metadata={{op_name="ragged-dot-none"}}
  %kernel.1 = bf16[8,8]{{1,0}} custom-call(%ragged-dot-none.7), custom_call_target="tpu_custom_call", {head}
  %bare.1 = bf16[8,8]{{1,0}} custom-call(%kernel.1), custom_call_target="AllocateBuffer"
  ROOT %o.1 = bf16[8,8]{{1,0}} negate(%bare.1), metadata={{op_name="jit(step)/optimizer/neg"}}
}}
""")
    assert cat["ragged-dot-none.7"].scope == "l2/moe/dispatch/ragged-dot-none"
    assert glm_scopes.mechanism(cat["ragged-dot-none.7"]) == "moe_experts"
    assert cat["kernel.1"].scope == "mtp/head"
    assert glm_scopes.mechanism(cat["kernel.1"]) is None
    assert (cat["bare.1"].scope, cat["bare.1"].phase) == ("", "")
    assert glm_scopes.mechanism(cat["rows.f"]) == "moe_route"


def test_the_readers_on_a_hand_made_trace_give_hand_computed_numbers(catalog):
    run = _hand_made(config=CFG, counters={
        "moe_rows_held": [[1, 2], [8192, 9011]],
        "moe_load_max_over_mean": [[9.0, 9.0], [1.5, 2.25]]})
    assert _read("attn_core_device_ms", run) == pytest.approx(10 + 20 + 5)
    # both grouped matmuls and the two casts of the experts' weights
    assert _read("moe_experts_device_ms", run) == pytest.approx(1 + 8 + 1 + 4)
    assert _read("moe_route_device_ms", run) == pytest.approx(2 + 3 + 3)
    # the module's attention, experts and head; not the trunk's, not the optimizer
    assert _read("mtp_device_ms", run) == pytest.approx(5 + 1 + 4 + 2)
    assert _read("moe_held_load_ratio", run) == pytest.approx(9011 / 8192)
    assert _read("moe_load_max_over_mean", run) == 2.25  # the newest epoch's worst
    assert _read("attn_core_roofline", run) is None  # no published peak (a CPU)
    assert _read("moe_experts_roofline", run) is None


def test_the_roofline_shares_are_least_time_over_measured_and_follow_the_rows(catalog):
    rows = [8192, 8100, 8300, 8192, 9011]
    run = _hand_made(peak=PEAK, config=CFG, counters={"moe_rows_held": [rows]})
    least = shapes.least_seconds(shapes.attention_core_passes(CFG, 4), PEAK)
    assert _read("attn_core_roofline", run) == pytest.approx(100 * least / 35e-3)
    ex = shapes.least_seconds(shapes.expert_passes(CFG, rows), PEAK)
    assert _read("moe_experts_roofline", run) == pytest.approx(100 * ex / 14e-3)
    fewer = _hand_made(peak=PEAK, config=CFG,
                       counters={"moe_rows_held": [[r // 2 for r in rows]]})
    assert _read("moe_experts_roofline", fewer) < _read("moe_experts_roofline", run)
    assert _read("moe_held_load_ratio", run) == pytest.approx(9011 / 8192)


def test_the_readers_find_nothing_in_a_program_that_has_no_such_scope():
    """A conv net's step, or the parent's: nothing named, nothing counted."""
    from parallel_cnn_tpu.obs import programs

    programs.record("jit_step", CATALOG.replace("/attn/core", "/s1b1/conv").replace(
        "/moe/", "/mid/").replace("(mtp)", "(s4b1)").replace(
        "custom-call(", "negate(").replace("ragged-dot-none", "conv"))
    try:
        run = _hand_made(peak=PEAK, config=CFG)
        assert all(_read(m, run) is None for m in NEW_METRICS)
    finally:
        programs.clear()


# ------------------------------ the whole command on the CPU, real files

@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    return tmp_path_factory.mktemp("glm-cache")


def _env(cache):
    return dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="",
                JAX_COMPILATION_CACHE_DIR=str(cache))


def _run_cell(cache, seed, trace):
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "tiny_glm_train",
         "--seed", str(seed), "--seconds", "0.3", "--trace", str(trace),
         "--notes", "1"],
        cwd=ROOT, env=_env(cache), capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    notes = json.loads([l for l in out.stderr.splitlines() if l.startswith("{")][-1])
    return line, notes


def test_two_seeds_are_one_job_the_same_rows_held_and_the_same_losses(cache):
    """`--seed` draws the check; the timed job is drawn from the traffic
    file's `job_seed`. Two runs with different seeds hold the same rows in
    every expert layer, epoch for epoch, and read the same losses — the
    same work — while their checks differ."""
    (a, na), (b, nb) = (_run_cell(cache, seed, 0) for seed in (2701000123, 7))
    for line, notes in ((a, na), (b, nb)):
        assert line["correct"] is True and line["failed"] == 0
        assert line["device"]["platform"] == "cpu"
        assert set(line["metrics"]) == {"train_img_s_chip", "setup_s"}
        assert notes["counters"]["compiles_in_window"] == 0
        assert notes["counters"]["moe_overflow_rows"] == [0, 0, 0]
    n = min(na["counters"]["epochs"], nb["counters"]["epochs"]) + 1
    assert n >= 3
    assert na["counters"]["moe_rows_held"][:n] == nb["counters"]["moe_rows_held"][:n]
    assert na["counters"]["losses"][:n] == nb["counters"]["losses"][:n]
    assert len(set(map(tuple, na["counters"]["moe_rows_held"][:n]))) > 1  # it trains
    ca, cb = na["notes"]["check_losses"], nb["notes"]["check_losses"]
    assert ca["reference"] != cb["reference"]  # another seed, another check
    for chk in (ca, cb):
        assert chk["system"][1] < chk["system"][0]
        assert chk["reference"][1] < chk["reference"][0]
    rows = na["notes"]["check_rows_held"]
    assert len(rows["system"]) == 2 and len(rows["system"][0]) == 3


def test_the_traced_tiny_cell_reports_the_new_metrics_and_the_unlisted_ones(cache):
    line, notes = _run_cell(cache, 2147483659, 1)  # more than 32 signed bits hold
    assert line["correct"] is True
    m = {k: v["value"] for k, v in line["metrics"].items()}
    # CPU numbers, never device numbers: only that each reader found its ops
    for name in ("attn_core_device_ms", "moe_experts_device_ms",
                 "moe_route_device_ms", "mtp_device_ms", "opt_device_ms",
                 "step_device_ms", "fwd_device_ms", "bwd_device_ms"):
        assert m[name] > 0, name
    assert m["mtp_device_ms"] < m["step_device_ms"]
    assert m["attn_core_device_ms"] + m["moe_experts_device_ms"] \
        + m["moe_route_device_ms"] < m["step_device_ms"]
    assert m["moe_held_load_ratio"] > 0 and m["moe_load_max_over_mean"] >= 1
    assert m["scope_named_pct"] > 50 and m["stem_device_ms"] == 0
    # no published peak for a CPU: nothing is reported against one
    assert not set(m) & {"mfu_pct", "attn_core_roofline", "moe_experts_roofline"}


@pytest.mark.parametrize("mode,args", [
    ("init", ["--controls", "float8_e4m3fn,mtp_off"]), ("layers", [])])
def test_the_comparison_tool_runs_by_name_of_a_cell(cache, mode, args):
    out = subprocess.run(
        [sys.executable, "benchmark/tools/compare_glm_moe.py", "--workload",
         "tiny_glm_train", "--seeds", "1", "--mode", mode, *args],
        cwd=ROOT, env=_env(cache), capture_output=True, text=True, timeout=900)
    rows = [json.loads(l) for l in out.stdout.strip().splitlines()]
    if mode == "layers":
        assert out.returncode == 0, out.stderr[-3000:]
        (row,) = rows
        assert len(row["layer_gaps"]) == 3 and row["leaves"] == 66
        assert row["loss_gap"] < 0.01
        return
    clean, low, faulty = rows
    # the cell's own check, with the cell's bounds
    assert clean["correct"] is True and "control" not in clean
    ref = clean["check_losses"]["reference"]
    assert ref[1] < ref[0]
    got = clean["check_rows_held"]["system"]
    assert len(got) == 2 and len(got[0]) == 3
    assert max(clean["loss_gaps"]) < 1e-3 and clean["rows_gap"] <= 4
    # a dropped term fails it; the toy's bounds are too wide for float8,
    # which stands further off all the same, and the tool says so by exit 1
    assert (low["control"], faulty["control"]) == ("float8_e4m3fn", "mtp_off")
    assert faulty["correct"] is False and faulty["loss_gaps"][0] > 0.1
    assert max(low["loss_gaps"]) > 1e-3
    assert out.returncode == (1 if low["correct"] else 0), out.stderr[-3000:]

