"""The `keye_vl2_30b_a3b_ep8` configuration and its cell `keye_dsa_train`
(PR 51): the manifest's appended entries (closed indices: what a later PR
appends is that PR's to hold), the file's keys against the catalog row of
the published config.json, the parameter count against the issue's
arithmetic, the lister's pairs and operations against a hand count, the
eleven readers on a hand-made trace, and the cell's whole command on the
CPU."""

import importlib
import json
import os
import subprocess
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import common, flops, keye_scopes as scopes  # noqa: E402
from benchmark import trace_reduce as tr  # noqa: E402
from benchmark.runners import train_zoo_tokens_gradnorm as runner  # noqa: E402
from benchmark.shapes import keye_vl as shapes  # noqa: E402

MAN = common.manifest()
CFG = common.find_config("keye_vl2_30b_a3b_ep8", False)
TRAFFIC = common.find_traffic("train_s16384_b1_dsa_fixedjob", False)
SOURCE = "https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/config.json"
NEW_METRICS = ["dsa_core_device_ms", "dsa_core_roofline",
               "dsa_pairs_computed_ratio", "dsa_indexer_device_ms",
               "dsa_select_device_ms", "dsa_kl_device_ms", "dsa_index_kl",
               "keye_experts_device_ms", "keye_experts_roofline",
               "keye_route_device_ms", "keye_load_max_over_mean"]
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# the catalog row's `config` (model-configs guide, architectures.jsonl)
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 262144, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "KeyeVL2", "moe_intermediate_size": 768,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 48, "num_key_value_heads": 4,
    "num_local_experts": 128, "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                     "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936,
}
S, TOPK = 16384, 2048
ALLOWED = sum(min(t + 1, TOPK) for t in range(S))


# ----------------------------------------------------------- the manifest

def test_this_prs_entries_are_appended_one_configuration_one_cell_eleven_metrics():
    assert len(MAN["configs"]) >= 9 and len(MAN["workloads"]) >= 10
    assert [c["name"] for c in MAN["configs"][:8]] == [
        "resnet50_imagenet", "resnet18_imagenet", "convnext_b_imagenet",
        "glm_4_7_flash_ep8", "sdar_30b_a3b_ep8", "trinity_mini_ep8",
        "ling_3_0_flash_ep64", "ouro_2_6b_stage"]
    assert [w["name"] for w in MAN["workloads"][:9]] == [
        "r50_train", "r18_train", "r50_train_dp4", "convnext_b_train",
        "glm47f_train", "sdar_bd_train", "trinity_mini_train", "ling3f_train",
        "ouro_loop_train"]
    assert MAN["run_seconds"] == 10 and len(MAN["end_to_end"]) == 2
    entry, cell = MAN["configs"][8], MAN["workloads"][9]
    assert entry == {
        "name": "keye_vl2_30b_a3b_ep8", "source": SOURCE,
        "file": "benchmark/configs/keye_vl2_30b_a3b_ep8.json",
        "reduced": ["num_hidden_layers", "num_experts", "vocab_size"],
        "why": entry["why"]}
    assert len(entry["why"]) <= 200 and "one chip of eight" in entry["why"]
    assert [c["file"] for c in MAN["configs"]].count(entry["file"]) == 1
    assert cell == {"name": "keye_dsa_train", "config": "keye_vl2_30b_a3b_ep8",
                    "traffic": "train_s16384_b1_dsa_fixedjob", "chips": 1,
                    "why": cell["why"]}
    assert len(cell["why"]) <= 200
    # the regime the cell measures, not the balanced one: at the published
    # initialisation the router sends every token to the same experts
    for said in ("16,384", "2,048 keys", "router collapsed at init",
                 "all 16,384 rows or none", "1/8 of deployed", "no tower"):
        assert said in cell["why"], said
    assert "collapsed" in TRAFFIC["describes"] and "131,072" in TRAFFIC["describes"]
    assert "1,024 rows, an eighth" not in TRAFFIC["describes"]
    assert common.find_workload("keye_dsa_train")["why"] == cell["why"]
    # one cell in ten asks for four chips: 25 % rounded down is two
    assert [w["chips"] for w in MAN["workloads"]].count(4) == 1
    eleven = MAN["per_layer"][72:83]
    assert [m["name"] for m in eleven] == NEW_METRICS
    for m in eleven:
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert m["workloads"] == ["keye_dsa_train"]
        assert (m["layer"], m["moves"]) == ("layers and kernels", "train_img_s_chip")
        assert (m["unit"] == "%") == m["name"].endswith("_roofline")
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", m["name"] + ".py"))
    assert [m["source"] for m in eleven] == [
        "device_trace", "device_trace", "program_counter", "device_trace",
        "device_trace", "device_trace", "program_counter", "device_trace",
        "device_trace", "device_trace", "program_counter"]
    # no older list grew, and the cell reports the unlisted metrics too
    assert not any("keye_dsa_train" in m.get("workloads", [])
                   for m in MAN["per_layer"][:72])
    assert [m["name"] for m in MAN["per_layer"][66:72]] == [
        "loop_stack_device_ms", "loop_exits_device_ms",
        "loop_attn_core_device_ms", "loop_attn_core_roofline",
        "loop_core_calls_ratio", "loop_exit_step_mean"]
    got = [m["name"] for m in common.cell_metrics(MAN, "keye_dsa_train",
                                                  "per_layer")]
    assert set(NEW_METRICS) <= set(got)
    for shared in ("mfu_pct", "scope_named_pct", "step_device_ms",
                   "opt_device_ms", "peak_hbm_gb", "conv_roofline"):
        assert shared in got
    assert not any(m.startswith(("moe_", "kda_", "bh_", "win_", "full_", "bd_",
                                 "sdar_", "afmoe_", "rope_", "attn_core", "loop_"))
                   for m in got)
    for w in MAN["workloads"][:9]:
        assert not set(NEW_METRICS) & {m["name"] for m in common.cell_metrics(
            MAN, w["name"], "per_layer")}


# --------------------------------------------------------------- the file

def test_every_published_key_is_there_and_only_the_cut_differs():
    assert CFG["source"] == SOURCE
    assert CFG["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert CFG["published"] == {k: PUBLISHED[k] for k in CFG["reduced"]}
    for key, value in PUBLISHED.items():
        assert key in CFG, key
        if key not in CFG["reduced"]:
            assert CFG[key] == value, key
    assert (CFG["num_hidden_layers"], CFG["num_experts"], CFG["vocab_size"]) == (
        6, 16, 18992)
    # no width is cut, sa_config and rope_scaling are whole
    assert CFG["sa_config"] == PUBLISHED["sa_config"]
    assert CFG["rope_scaling"] == PUBLISHED["rope_scaling"]
    for said in ("indexer", "indexer_key_norm", "indexer_scale", "indexer_rope",
                 "chunks", "ties", "mrope", "sequence_layout", "index_objective",
                 "balance_weight", "gate_gradient", "init", "lr", "left_out"):
        assert said in CFG["assumed"], said
    left = CFG["assumed"]["left_out"]
    assert left.startswith("the vision tower and its merger")
    for said in ("dense warm-up stage", "FP8", "packing", "clipping", "decoding"):
        assert said in left, said
    for said in ("16 of the 128 experts", "1/8 of the vocabulary",
                 "indexer whole on every chip", "659,190,016", "10.55 GB"):
        assert said in CFG["deployment"], said


def test_the_arch_group_repeats_the_files_own_keys_and_the_factory_builds_it():
    arch, sa = CFG["arch"], CFG["sa_config"]
    for key in ("hidden_size", "moe_intermediate_size", "num_attention_heads",
                "num_key_value_heads", "head_dim", "num_experts_per_tok",
                "num_hidden_layers", "rms_norm_eps", "rope_theta", "vocab_size"):
        assert arch[key] == CFG[key], key
    assert (arch["router_experts"], len(arch["held_experts"])) == (
        CFG["num_local_experts"], CFG["num_experts"])
    assert (arch["indexer_num_heads"], arch["indexer_head_dim"], arch["topk"]) == (
        sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"])
    assert arch["mrope_section"] == CFG["rope_scaling"]["mrope_section"]
    kwargs = CFG["factory"]["kwargs"]
    assert kwargs["image_spans"] == arch["mrope_layout"] == [
        [512, 1, 32, 32], [4608, 1, 32, 32], [8704, 1, 32, 32], [12800, 1, 32, 32]]
    model = common.build_model(CFG)
    att, pick, ex = model.attn, model.attn.select, model.experts
    assert (model.n_layers, model.vocab, model.hidden, model.index_weight) == (
        6, 18992, 2048, arch["index_weight"])
    assert (att.heads, att.kv_heads, att.head_dim, att.theta) == (32, 4, 128, 1e7)
    assert (pick.heads, pick.head_dim, pick.topk, pick.theta) == (16, 64, 2048, 1e7)
    assert att.positions.sections == (16, 24, 24)
    assert (ex.width, ex.n_routed, ex.per_token, ex.held, ex.rows, ex.gate_grad,
            ex.scoring, ex.n_shared, ex.balance) == (
        768, 128, 8, tuple(range(16)), None, False, "softmax", 0, 1e-3)
    # the text resumes 32 positions after an image's first: 4 x 992 are skipped
    assert att.positions.rows(S)[:, -1].tolist() == [S - 1 - 4 * 992] * 3
    said = model.describe(S, S, "tpu")
    # every assignment of a step has a row: nothing can overflow (the issue's
    # 32,768 did, on the first seed run on the chip: PERF.md section 6)
    assert said["row_buffer"] == S * 8 and "overflows" in CFG["assumed"]["row_buffer"]
    assert (said["attention_core"], said["attention_tile"],
            said["attention_heads_a_step"], said["rope_turn"]) == (
        "fused", 512, 4, "kernel")
    assert said["attention_pairs_allowed"] == ALLOWED == 31458304
    assert said["attention_pairs_computed"] == 528 * 512 * 512
    assert said["attention_pairs_computed"] / ALLOWED == pytest.approx(4.4, abs=0.01)


def test_what_the_three_deselected_cases_held_holds_for_this_entry():
    """`tests/conftest.py` deselects, for this configuration, the three
    cases that read every listed configuration as a ResNet under SGD with
    nothing reduced."""
    assert CFG["reference"] == "keye_vl"
    assert common.find_reference(CFG).__name__ == "benchmark.reference.keye_vl"
    from benchmark.runners.train_zoo_tokens import cell_lr, optimizer_args

    lr = cell_lr(CFG, TRAFFIC)
    assert lr == pytest.approx(2e-4)
    assert optimizer_args(CFG["optimizer"], lr) == dict(
        lr=lr, kind="adamw", b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.1)
    entry = MAN["configs"][8]
    assert entry["reduced"] == CFG["reduced"] and entry["file"].endswith(
        CFG["name"] + ".json")


def test_the_cell_is_one_fixed_job_of_one_sequence_a_step():
    assert TRAFFIC["runner"] == "train_zoo_tokens_gradnorm"
    assert (TRAFFIC["sequence_length"], TRAFFIC["global_batch"],
            TRAFFIC["sequences"], TRAFFIC["loader"], TRAFFIC["warmup_epochs"],
            TRAFFIC["trace_seconds"]) == (S, 1, 4, "device", 4, 3.0)
    assert CFG["input"] == [S]
    chk = TRAFFIC["check"]
    assert chk["batch"] == 1 and len(chk["loss_rtol"]) == 2
    assert [t["leaves"] for t in chk["grad_tols"]] == [
        "['indexer']", "['router']", "['experts']", "['head']", "['embed']", ""]
    assert runner.class_of("['layers'][2]['attn']['indexer']['k_norm']['bias']",
                           chk["grad_tols"]) == 0
    assert runner.class_of("['layers'][2]['attn']['k_norm']", chk["grad_tols"]) == 5
    assert runner.class_of("['layers'][0]['ffn']['experts']['up']",
                           chk["grad_tols"]) == 2


# ------------------------------------------------------------- the counts

def test_the_listers_pairs_are_the_sum_written_by_hand():
    assert shapes.pairs_allowed(CFG) == ALLOWED
    assert shapes.pairs_causal(CFG) == S * (S + 1) // 2 == 134225920
    assert ALLOWED / shapes.pairs_causal(CFG) == pytest.approx(0.234, abs=5e-4)
    short = dict(CFG, input=[1024])  # every query keeps all its keys
    assert shapes.pairs_allowed(short) == shapes.pairs_causal(short)
    from parallel_cnn_tpu.nn import keye_vl

    assert keye_vl.pairs_allowed(S, TOPK) == ALLOWED


def test_the_counter_gives_the_hand_counted_macs():
    by_name = {l["name"]: l for l in shapes.layers(CFG)}
    macs = lambda n: flops.macs(by_name[n])  # noqa: E731
    assert macs("embed") == 0 and macs("head") == S * 2048 * 18992
    assert macs("l0.attn.qkv.q") == S * 2048 * 4096
    assert (macs("l3.attn.indexer.proj.q"), macs("l3.attn.indexer.proj.k"),
            macs("l3.attn.indexer.proj.w")) == (
        S * 2048 * 1024, S * 2048 * 64, S * 2048 * 16)
    # the cores over the allowed pairs, whatever computes them
    assert macs("l5.attn.core.qk") == macs("l5.attn.core.pv") == ALLOWED * 32 * 128
    # the scores: causal pairs forward, selected pairs twice backward, as the
    # third of the three passes `flops.py` counts of every record
    causal = S * (S + 1) // 2
    assert 3 * macs("l1.attn.indexer.scores") == (causal + 2 * ALLOWED) * 16 * 64
    assert macs("l2.moe.route") == S * 2048 * 128
    assert macs("l2.moe.experts.gate") == 16384 * 2048 * 768
    total = flops.train_flops_per_image(CFG)
    assert total == 2 * 3 * sum(flops.macs(l) for l in shapes.layers(CFG))
    assert 30.5e12 < total < 31.5e12


def test_the_counter_counts_the_parameters_of_the_programs_own_model():
    import jax

    model = common.build_model(CFG)
    made = jax.eval_shape(lambda k: model.init(k, (S,))[0], jax.random.key(0))
    count = lambda tree: sum(a.size for a in jax.tree_util.tree_leaves(tree))  # noqa: E731
    layer = made["layers"][0]
    assert count(layer["attn"]) - count(layer["attn"]["indexer"]) == 18874624
    assert count(layer["attn"]["indexer"]) == 2261120
    assert count(layer["ffn"]["router"]) == 262144
    assert count(layer["ffn"]["experts"]) == 75497472
    assert count(layer) == 96899456
    assert count(made) == 659190016
    # what a rematerialised layer keeps of a selection: a bit a pair
    assert -(-S // 32) * 4 * S == S * S // 8


def test_the_kernels_operations_and_bytes_are_the_hand_counted_ones():
    passes = shapes.attention_core_passes(CFG, 1)
    assert len(passes) == 2 * 6
    fwd = 2 * 32 * ALLOWED * 2 * 128
    tensor = S * 128 * 2  # one head's q (or k, v, output) of a step
    assert passes[0] == dict(name="core0", kind="fwd", layer=0, flops=fwd,
                             bytes=(2 * 32 + 2 * 4) * tensor)
    assert passes[1] == dict(name="core0", kind="bwd", layer=0, flops=2 * fwd,
                             bytes=(4 * 32 + 4 * 4) * tensor)
    assert sum(p["flops"] for p in passes) == 6 * 3 * fwd == pytest.approx(
        9.28e12, rel=1e-3)
    # compute-bound: the flops set every pass's least time
    for p in passes:
        assert p["flops"] / PEAK["bf16_flops_per_s"] > p["bytes"] / PEAK["hbm_bytes_per_s"]


# ------------------------------------------------------------ the readers

def _read(name, run):
    return importlib.import_module(f"benchmark.layer_metrics.{name}").read(run)


def _op(name, stack, call=False):
    kind = ('custom-call(%p), custom_call_target="tpu_custom_call"'
            if call else "negate(%p)")
    return ('  %%%s = bf16[8,8]{1,0} %s, metadata={op_name="jit(step)/grad/%s"}'
            % (name, kind, stack))


FWD, BWD = "jvp(l0)/", "transpose(jvp(l0))/grad/jvp(l0)/checkpoint/"
CATALOG = "\n".join([
    "HloModule jit_step", "",
    "ENTRY %main (p: bf16[8,8]) -> bf16[8,8] {",
    "  %p = bf16[8,8]{1,0} parameter(0)",
    _op("emb.f", "jvp(embed)/gather"),
    _op("qkv.f", FWD + "attn/qkv/dot_general"),
    _op("proj.f", FWD + "attn/indexer/proj/dot_general"),
    _op("irope.f", FWD + "attn/indexer/rope/mul"),
    _op("scores.f", FWD + "attn/indexer/while/body/scores/dot_general"),
    _op("topk.f", FWD + "attn/indexer/while/body/select/sort"),
    _op("bias.f", FWD + "attn/indexer/select/select_n"),
    _op("core.f", FWD + "attn/core/cond/branch_0_fun/selected_attention_fwd/"
        "pallas_call", call=True),
    _op("kl.f", FWD + "attn/indexer/kl/while/body/while/body/exp"),
    _op("klscores.f", FWD + "attn/indexer/kl/while/body/scores/dot_general"),
    _op("route.f", FWD + "moe/route/dot_general"),
    _op("experts.f", FWD + "moe/experts/mul"),
    _op("bias.b", BWD + "rematted_computation/attn/indexer/select/select_n"),
    _op("core.b", BWD + "attn/core/cond/branch_0_fun/selected_attention_bwd/"
        "pallas_call", call=True),
    _op("kl.b", BWD + "attn/indexer/kl/while/body/while/body/exp"),
    _op("klscores.b", BWD + "attn/indexer/kl/while/body/scores/transpose"),
    _op("proj.b", BWD + "attn/indexer/proj/dot_general"),
    _op("experts.b", BWD + "moe/experts/mul"),
    _op("combine.b", BWD + "moe/combine/mul"),
    '  ROOT %o.1 = bf16[8,8]{1,0} negate(%p), '
    'metadata={op_name="jit(step)/optimizer/neg"}', "}", ""])
SPANS = {"emb.f": (0, 1), "qkv.f": (1, 5), "proj.f": (5, 6), "irope.f": (6, 7),
         "scores.f": (7, 10), "topk.f": (10, 18), "bias.f": (18, 19),
         "core.f": (19, 29), "kl.f": (29, 34), "klscores.f": (34, 35),
         "route.f": (35, 37), "experts.f": (37, 41), "bias.b": (41, 42),
         "core.b": (42, 62), "kl.b": (62, 68), "klscores.b": (68, 71),
         "proj.b": (71, 73), "experts.b": (73, 81), "combine.b": (81, 82),
         "o.1": (82, 86)}
ONE_LAYER = dict(CFG, arch=dict(CFG["arch"], num_hidden_layers=1))


def _hand_made(peak=None, config=ONE_LAYER, spans=SPANS, rows=((16000,),)):
    ms = 1e6
    ops = [tr.Op(n, "other", base * ms + a * ms, base * ms + b * ms)
           for base in (0, 100) for n, (a, b) in spans.items()]
    trace = tr.Trace(ops={0: ops}, async_ops={},
                     modules={0: [("jit_step(7)", 0.0, 87 * ms),
                                  ("jit_step(7)", 100 * ms, 187 * ms)]}, host={})
    return types.SimpleNamespace(
        trace=trace, spans={}, e2e={}, window_s=0.2, program=r"^jit_step\b",
        counters={"batch_per_chip": 1, "moe_rows_held": [list(r) for r in rows],
                  "moe_load_max_over_mean": [[1.5], [1.75]]},
        device={"platform": "tpu"},
        ctx=types.SimpleNamespace(peak=peak, config=config))


@pytest.fixture
def catalog():
    from parallel_cnn_tpu.obs import programs

    programs.record("jit_step", CATALOG)
    yield programs.lookup("jit_step")
    programs.clear()


def test_the_readers_on_a_hand_made_trace_give_hand_computed_numbers(catalog):
    assert (catalog["core.f"].scope, catalog["core.f"].phase,
            catalog["core.f"].opcode) == ("l0/attn/core", "fwd", "custom-call")
    kinds = {n: scopes.mechanism(catalog[n]) for n in catalog}
    assert kinds["core.b"] == "core" and kinds["qkv.f"] is None
    assert kinds["proj.f"] == kinds["irope.f"] == kinds["scores.f"] == "indexer"
    assert kinds["klscores.f"] == kinds["klscores.b"] == "indexer"
    assert kinds["topk.f"] == kinds["bias.f"] == kinds["bias.b"] == "select"
    assert kinds["kl.f"] == kinds["kl.b"] == "kl"
    assert kinds["experts.b"] == "experts" and kinds["combine.b"] == "route"
    assert kinds["emb.f"] is None and kinds["o.1"] is None
    run = _hand_made()
    assert _read("dsa_core_device_ms", run) == pytest.approx(10 + 20)
    assert _read("dsa_indexer_device_ms", run) == pytest.approx(1 + 1 + 3 + 1 + 3 + 2)
    assert _read("dsa_select_device_ms", run) == pytest.approx(8 + 1 + 1)
    assert _read("dsa_kl_device_ms", run) == pytest.approx(5 + 6)
    assert _read("keye_experts_device_ms", run) == pytest.approx(4 + 8)
    assert _read("keye_route_device_ms", run) == pytest.approx(2 + 1)
    assert _read("keye_load_max_over_mean", run) == 1.75
    assert _read("dsa_pairs_computed_ratio", run) == pytest.approx(
        528 * 512 * 512 / ALLOWED)
    # no published peak: the shares are left out
    assert _read("dsa_core_roofline", run) is None
    assert _read("keye_experts_roofline", run) is None


def test_the_roofline_shares_are_least_time_over_measured(catalog):
    run = _hand_made(peak=PEAK)
    want = 100 * shapes.least_seconds(
        shapes.attention_core_passes(ONE_LAYER, 1), PEAK) / 30e-3
    assert _read("dsa_core_roofline", run) == pytest.approx(want)
    # a masked-dense core cannot pass allowed / computed of its share
    assert want == pytest.approx(100 * 3 * 2 * 32 * ALLOWED * 2 * 128 / 197e12 / 30e-3)
    held = 100 * shapes.least_seconds(
        shapes.expert_passes(ONE_LAYER, [16000]), PEAK) / 12e-3
    assert _read("keye_experts_roofline", run) == pytest.approx(held)


def test_the_objectives_reader_reads_the_programs_newest_epoch_record():
    from parallel_cnn_tpu.obs import epochs

    epochs.clear()
    try:
        assert _read("dsa_index_kl", _hand_made()) is None
        epochs.record({"epoch": 1, "dsa_index_kl": [0.5, 0.7]})
        epochs.record({"epoch": 2, "dsa_index_kl": [0.25, 0.5]})
        assert _read("dsa_index_kl", _hand_made()) == 0.375
        epochs.record({"epoch": 3, "moe_rows_held": [7]})  # another model's
        assert _read("dsa_index_kl", _hand_made()) is None
    finally:
        epochs.clear()


def test_the_readers_find_nothing_where_the_program_has_no_such_thing():
    """A conv net's step, a configuration of another family, no trace:
    nothing named, nothing counted, nothing raised."""
    from parallel_cnn_tpu.obs import epochs, programs

    epochs.clear()
    programs.record("jit_step", CATALOG.replace("(l0)", "(s1b1)").replace(
        "attn/", "conv/").replace("moe/", "bn/").replace("indexer/", "act/"))
    try:
        r18 = common.find_config("resnet18_imagenet", False)
        run = _hand_made(peak=PEAK, config=r18, rows=())
        run.counters = {"batch_per_chip": 1}
        assert [m for m in NEW_METRICS if _read(m, run) is not None] == []
        programs.record("jit_step", CATALOG)
        no_trace = _hand_made(peak=PEAK)
        no_trace.trace = None
        assert [m for m in NEW_METRICS if _read(m, no_trace) is not None] == [
            "dsa_pairs_computed_ratio", "keye_load_max_over_mean"]
        programs.clear()  # a program that records no catalog
        assert [m for m in NEW_METRICS if _read(m, _hand_made(peak=PEAK))
                is not None] == ["dsa_pairs_computed_ratio",
                                 "keye_load_max_over_mean"]
    finally:
        programs.clear()


# ------------------------------ the whole command on the CPU, real files

@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    """One seed, more than 32 signed bits hold, traced."""
    cache = tmp_path_factory.mktemp("keye-cache")
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="",
               JAX_COMPILATION_CACHE_DIR=str(cache))
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "tiny_keye_train",
         "--seed", "2147483659", "--seconds", "0.3", "--trace", "1",
         "--notes", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    notes = json.loads([l for l in out.stderr.splitlines()
                        if l.startswith("{")][-1])
    return line, notes


def test_the_tiny_cell_is_correct_and_the_check_reads_every_class(traced_run):
    line, notes = traced_run
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    assert notes["counters"]["compiles_in_window"] == 0
    assert not any(notes["counters"]["moe_overflow_rows"])
    index, held, rest = notes["notes"]["check_grad_classes"]
    assert (index["leaves_read"], held["leaves_read"], rest["leaves_read"]) == (
        5, 3, 9 + 3)
    for c in (index, held, rest):
        assert 0 < c["gap_widest"] < c["gap"] and 0 < c["norm_widest"] < c["norm"]


def test_the_traced_tiny_cell_reports_the_new_metrics_and_the_unlisted_ones(traced_run):
    got = traced_run[0]["metrics"]
    # no published peak on a CPU: the shares are left out, never 0
    assert {m for m in NEW_METRICS if m in got} == set(NEW_METRICS) - {
        "dsa_core_roofline", "keye_experts_roofline"}
    for name in ("dsa_core_device_ms", "dsa_indexer_device_ms",
                 "dsa_select_device_ms", "dsa_kl_device_ms",
                 "keye_experts_device_ms", "keye_route_device_ms"):
        assert got[name]["value"] > 0 and got[name]["unit"] == "ms"
    assert got["dsa_pairs_computed_ratio"]["value"] == pytest.approx(
        (32 * 32 + 32 * 64) / sum(min(t + 1, 16) for t in range(64)))
    assert 0 < got["dsa_index_kl"]["value"] < 2
    assert got["keye_load_max_over_mean"]["value"] >= 1.0
    assert got["scope_named_pct"]["value"] > 85
    assert not any(m.startswith(("moe_", "kda_", "bh_", "afmoe_", "loop_", "sdar_"))
                   for m in got)
    assert got["stem_device_ms"]["value"] == 0.0
