"""The SDAR-30B-A3B-Chat configuration as the benchmark holds it: the
manifest's appended entries (and what the cases deselected in
tests/conftest.py for it held of the older entries), the cut written down
against the published config.json, the shape counter against the program's
own parameter tree and the issue's arithmetic, the eight new readers on a
hand-made trace, and the whole command on the CPU through the real files
(`tiny_sdar_train`, tests/benchmark/cells): two seeds, one job, one noise
stream."""

import importlib
import json
import os
import subprocess
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import common, flops, trace_reduce as tr  # noqa: E402
from benchmark.runners import train_zoo, train_zoo_tokens  # noqa: E402
from benchmark.shapes import sdar_moe as shapes  # noqa: E402

MAN = common.manifest()
CFG = common.find_config("sdar_30b_a3b_ep8", False)
GLM = common.find_config("glm_4_7_flash_ep8", False)
SOURCE = "https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json"
GLM_METRICS = ["attn_core_device_ms", "attn_core_roofline",
               "moe_experts_device_ms", "moe_experts_roofline",
               "moe_route_device_ms", "mtp_device_ms", "moe_held_load_ratio",
               "moe_load_max_over_mean"]
NEW_METRICS = ["bd_attn_core_device_ms", "bd_attn_core_roofline",
               "bd_attn_pairs_computed_ratio", "bd_noise_device_ms",
               "sdar_experts_device_ms", "sdar_experts_roofline",
               "sdar_route_device_ms", "sdar_load_max_over_mean"]
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


# ------------------------------------------------------------ the manifest

def test_what_pr32_left_is_a_prefix_and_this_prs_entries_come_after_it():
    """What tests/benchmark/test_glm_config.py's `test_the_older_entries_
    are_a_prefix_and_the_new_ones_are_appended` held, with this PR's
    entries after them."""
    assert [c["name"] for c in MAN["configs"]] == [
        "resnet50_imagenet", "resnet18_imagenet", "convnext_b_imagenet",
        "glm_4_7_flash_ep8", "sdar_30b_a3b_ep8"]
    assert [w["name"] for w in MAN["workloads"]] == [
        "r50_train", "r18_train", "r50_train_dp4", "convnext_b_train",
        "glm47f_train", "sdar_bd_train"]
    assert all(c["reduced"] == [] for c in MAN["configs"][:3])
    assert [m["name"] for m in MAN["per_layer"][20:23]] == [
        "dwconv_device_ms", "dwconv_roofline", "norm_act_device_ms"]
    assert [m["name"] for m in MAN["per_layer"][23:31]] == GLM_METRICS
    for m in MAN["per_layer"][23:31]:
        assert m["workloads"] == ["glm47f_train"]  # no older list grew
    assert [m["name"] for m in MAN["per_layer"][31:]] == NEW_METRICS
    for m in MAN["per_layer"][31:]:
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert m["workloads"] == ["sdar_bd_train"]
        assert (m["layer"], m["moves"]) == ("layers and kernels", "train_img_s_chip")
        assert (m["unit"] == "%") == m["name"].endswith("_roofline")
    assert [m["source"] for m in MAN["per_layer"][31:]] == [
        "device_trace", "device_trace", "program_counter", "device_trace",
        "device_trace", "device_trace", "device_trace", "program_counter"]
    assert not any("sdar_bd_train" in m.get("workloads", [])
                   for m in MAN["per_layer"][:31])
    assert MAN["run_seconds"] == 10 and len(MAN["end_to_end"]) == 2
    glm, cell = MAN["workloads"][-2:]
    assert (glm["config"], glm["traffic"], glm["chips"]) == (
        "glm_4_7_flash_ep8", "train_s4096_b4_fixedjob", 1)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "sdar_30b_a3b_ep8", "train_s4096_b4_bd_fixedjob", 1)
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert len(cell["why"]) <= 200 and "8x" in cell["why"]
    # one cell in six asks for four chips, as before
    assert [w["chips"] for w in MAN["workloads"]].count(4) == 1


@pytest.mark.parametrize("name,cfg,reduced", [
    ("glm_4_7_flash_ep8", GLM,
     ["num_hidden_layers", "n_routed_experts", "vocab_size"]),
    ("sdar_30b_a3b_ep8", CFG, ["num_hidden_layers", "num_experts", "vocab_size"]),
])
def test_a_cut_configurations_reduced_keys_are_its_files(name, cfg, reduced):
    """What `test_config_entries[<name>]` held but for `reduced == []`, and
    what `test_the_new_entrys_reduced_keys_are_its_files` held of PR 32's
    entry when it was the manifest's last."""
    (entry,) = [c for c in MAN["configs"] if c["name"] == name]
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert entry["file"] == f"benchmark/configs/{name}.json"
    assert entry["source"] == cfg["source"] and cfg["name"] == name
    assert entry["reduced"] == cfg["reduced"] == reduced
    assert len(entry["why"]) <= 200 and "\n" not in entry["why"]
    assert "assumed" in cfg and "arch" in cfg and "factory" in cfg
    assert [c["file"] for c in MAN["configs"]].count(entry["file"]) == 1


def test_the_new_configuration_names_its_own_reference_and_adamw():
    """The two `[sdar_30b_a3b_ep8]` cases of the ResNet-only tests, turned
    round."""
    assert CFG["source"] == SOURCE
    assert (CFG["reference"], CFG["arch"]["family"]) == ("sdar_moe", "sdar_moe")
    ref = common.find_reference(CFG)
    assert ref.__name__ == "benchmark.reference.sdar_moe"
    assert all(callable(getattr(ref, f)) for f in (
        "train_losses", "eval_logits", "train_report", "loss_and_grads",
        "hidden_states", "noise", "stream_mask"))
    assert common.find_module("shapes", "sdar_moe") is shapes
    opt = CFG["optimizer"]
    assert train_zoo.optimizer_args(opt, opt["lr_per_256"] * 4 / 256) == {
        "lr": pytest.approx(train_zoo_tokens.cell_lr(CFG, {"global_batch": 4})),
        "kind": "adamw", "b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1}


# ------------------------------------------- the cut, written down

# the catalog's `config` of SDAR-30B-A3B-Chat
# (/opt/skills/guides/model-configs/architectures.jsonl), which is the
# published config.json's numbers
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936,
}


def test_every_published_key_is_there_and_only_the_three_cuts_differ():
    differs = {k for k, v in PUBLISHED.items() if CFG.get(k, "absent") != v}
    assert differs == set(CFG["reduced"])
    assert CFG["published"] == {k: PUBLISHED[k] for k in CFG["reduced"]}
    assert (CFG["num_experts"], CFG["vocab_size"]) == (16, 18992)
    assert CFG["num_hidden_layers"] in (5, 6)
    assert CFG["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert CFG["num_experts"] * 8 == PUBLISHED["num_experts"]
    # no width is cut: heads, head size, hidden, expert width, experts a token
    for key in ("hidden_size", "head_dim", "num_attention_heads",
                "num_key_value_heads", "moe_intermediate_size",
                "num_experts_per_tok", "intermediate_size"):
        assert CFG[key] == PUBLISHED[key]
    assert "eight chips share every layer" in CFG["deployment"]
    assert "4 key/value heads do not divide by 8" in CFG["deployment"]
    for key in ("block_length", "noise", "no_logit_shift", "mask_token", "norms",
                "rope", "router", "balance_weight", "init", "row_buffer", "data",
                "lr", "gate_gradient", "left_out"):
        assert key in CFG["assumed"], key
    assert "sampler" in CFG["assumed"]["left_out"]
    assert "2503.09573" in CFG["assumed"]["noise"]


def test_the_arch_group_repeats_the_files_own_keys_and_names_the_share():
    arch = CFG["arch"]
    shared = [k for k in arch if k in PUBLISHED]
    assert len(shared) == 10 and all(arch[k] == CFG[k] for k in shared)
    assert "num_experts" not in arch  # 128 to route over, 16 held: two keys
    assert arch["router_experts"] == PUBLISHED["num_experts"]
    assert arch["held_experts"] == list(range(16)) and arch["row_buffer"] == 65536
    assert (arch["block_length"], arch["noise_eps"], arch["balance_weight"],
            arch["mask_token_id"]) == (4, 1e-3, 1e-3, CFG["vocab_size"] - 1)
    assert CFG["factory"] == {
        "module": "parallel_cnn_tpu.nn.sdar_moe", "name": "sdar_30b_a3b",
        "kwargs": {"num_hidden_layers": CFG["num_hidden_layers"],
                   "vocab_size": 18992, "held_experts": list(range(16)),
                   "row_buffer": 65536, "block_length": 4,
                   "gate_gradient": False}}
    assert arch["gate_gradient"] is False and CFG["input"] == [4096]
    model = common.build_model(CFG)
    assert (model.attn.block, model.noise_eps, model.experts.balance,
            model.mask_id) == (4, 1e-3, 1e-3, arch["mask_token_id"])


def test_the_cell_is_one_job_for_every_seed_at_the_deployments_rows():
    cell = common.find_workload("sdar_bd_train")
    t = common.find_traffic(cell["traffic"], False)
    assert (t["runner"], t["sequence_length"], t["global_batch"], t["sequences"],
            t["loader"]) == ("train_zoo_tokens_bd", 4096, 4, 16, "device")
    assert isinstance(t["job_seed"], int) and 1 <= t["job_seed"] <= 16
    # each held expert's rows: 8 chips x 2,048 tokens, twice over as stream
    # rows, x 8 / 128 in the deployment; 32,768 stream rows x 8 / 128 here
    assert 8 * 2048 * 2 * 8 // 128 == t["global_batch"] * 4096 * 2 * 8 // 128 == 2048
    assert shapes.held_rows(CFG) * t["global_batch"] == 32768
    assert CFG["arch"]["row_buffer"] == 2 * 32768
    chk = t["check"]
    assert chk["batch"] == 1 and len(chk["loss_rtol"]) == 2 and chk["rows_tol"] >= 1
    assert "lr" not in chk and t["warmup_epochs"] == 4
    assert 5e-5 <= train_zoo_tokens.cell_lr(CFG, t) <= 3e-4
    assert cell["accum_steps"] == 1 and "who" in cell
    assert "noise key" in t["describes"]


# ------------------------------------------------------ the shape counter

def test_the_counter_gives_the_issues_macs_and_the_training_flops():
    n = CFG["num_hidden_layers"]
    ls = flops.layers(CFG)
    by = {l["name"]: l for l in ls}
    pairs = 4096 * 4100  # L (L + B): what the mask allows, not what tiles hold
    projections = 8192 * (2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048)
    experts = 8192 * 3 * 2048 * 768  # 2 L x 8 x 16 / 128 rows over the held 16
    router = 8192 * 2048 * 128
    core = pairs * 32 * 2 * 128
    layer = projections + experts + router + core
    head = 4096 * 2048 * 18992
    assert [round(v / 1e9, 1) for v in (projections, experts, router, core, head)] \
        == [154.6, 38.7, 2.1, 137.6, 159.3]
    assert flops.forward_macs(CFG) == n * layer + head
    assert flops.train_flops_per_image(CFG) == 6 * (n * layer + head)
    if n == 6:
        assert flops.forward_macs(CFG) == 2_157_281_542_144  # 2.16 TMAC a sequence
        assert flops.train_flops_per_image(CFG) / 1e12 == pytest.approx(12.94, abs=0.01)
    assert ls[0] == dict(name="embed", kind="dense", rows=0, cin=18992, cout=2048)
    assert flops.macs(ls[0]) == 0 and all(l["kind"] == "dense" for l in ls)
    assert by["l3.attn.core.qk"] == dict(
        name="l3.attn.core.qk", kind="dense", rows=pairs, cin=128, cout=32,
        weights=False)
    assert by["l0.attn.core.pv"]["rows"] == pairs
    assert by["l2.moe.experts.gate"] == dict(
        name="l2.moe.experts.gate", kind="dense", rows=8192, cin=2048, cout=768,
        copies=16)
    assert by["l2.attn.qkv.k"]["cout"] == 512 and by["l2.attn.qkv.q"]["rows"] == 8192
    assert by["l2.moe.route"]["cout"] == 128 and by["head"]["rows"] == 4096
    assert not any("shared" in name or "mlp" in name for name in by)
    share = sum(flops.macs(l) for l in ls if ".core." in l["name"]) \
        / flops.forward_macs(CFG)
    assert 0.37 < share < 0.39  # the issue's 38 %


def test_the_counter_counts_the_parameters_of_the_programs_own_model():
    import jax

    model = common.build_model(CFG)
    params = jax.eval_shape(lambda k: model.init(k, tuple(CFG["input"]))[0],
                            jax.random.key(0))
    leaves = jax.tree_util.tree_leaves(params)
    n = CFG["num_hidden_layers"]
    assert sum(l.size for l in leaves) == n * 94_638_336 + 77_793_280
    if n == 6:
        assert sum(l.size for l in leaves) == 645_623_296
        assert 16 * 645_623_296 / 1e9 == pytest.approx(10.33, abs=0.01)  # GB
    weights = sum(l["cin"] * l["cout"] * l.get("copies", 1)
                  for l in flops.layers(CFG) if l.get("weights", True))
    assert weights == sum(l.size for l in leaves if l.ndim >= 2)


def test_the_kernels_operations_and_bytes_are_the_hand_counted_ones():
    n = CFG["num_hidden_layers"]
    passes = shapes.attention_core_passes(CFG, 4)
    assert len(passes) == 2 * n
    fwd = 2 * 4 * 32 * (4096 * 4100) * (128 + 128)
    assert passes[0]["flops"] == fwd and passes[1]["flops"] == 2 * fwd
    # q and out over 32 heads, k and v over 4, 8,192 positions, bf16
    assert passes[0]["bytes"] == 4 * 8192 * 128 * 2 * (32 + 32 + 4 + 4)
    assert passes[1]["bytes"] == 4 * 8192 * 128 * 2 * (4 * 32 + 4 * 4)
    least = shapes.least_seconds(passes, PEAK)
    assert least == pytest.approx(n * 3 * fwd / 197e12)  # compute-bound
    if n == 6:
        assert least == pytest.approx(100.5e-3, rel=1e-2)
    rows = [32768, 32000, 33400, 32768, 32768, 31000][:n]
    ex = shapes.expert_passes(CFG, rows)
    assert len(ex) == n * 3 * 3
    assert sum(p["flops"] for p in ex) == 3 * 3 * 2 * sum(rows) * 2048 * 768
    gate_fwd = ex[0]
    assert gate_fwd["bytes"] == 32768 * (2048 + 768) * 2 + 16 * 2048 * 768 * 2
    # 2,048 rows an expert: compute-bound, twice GLM's rows an expert
    assert (gate_fwd["flops"] / 197e12) / (gate_fwd["bytes"] / 819e9) > 1.5
    assert shapes.pairs_allowed(CFG) == 4096 * 4100


# ------------------------------------------------------- the eight readers

def _read(name, run):
    return importlib.import_module(f"benchmark.layer_metrics.{name}").read(run)


CATALOG = """HloModule jit_step

ENTRY %main (p: bf16[8,8]) -> bf16[8,8] {
  %p = bf16[8,8]{1,0} parameter(0)
  %draw.f = bf16[8,8]{1,0} negate(%p), metadata={op_name="jit(step)/grad/jvp(noise)/jit(_uniform)/threefry2x32"}
  %block_diffusion_attention_fwd.1 = bf16[8,8]{1,0} custom-call(%draw.f), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/grad/jvp(l1)/attn/core/cond/branch_0_fun/block_diffusion_attention_fwd/pallas_call"}
  %block_diffusion_attention_bwd.1 = bf16[8,8]{1,0} custom-call(%block_diffusion_attention_fwd.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/grad/transpose(jvp(l1))/grad/jvp(l1)/checkpoint/attn/core/cond/branch_0_fun/block_diffusion_attention_bwd/pallas_call"}
  %norm.f = bf16[8,8]{1,0} negate(%p), metadata={op_name="jit(step)/grad/jvp(l1)/attn/qk_norm/mul"}
  %top.f = bf16[8,8]{1,0} negate(%norm.f), metadata={op_name="jit(step)/grad/jvp(l1)/moe/route/top_k"}
  %rows.f = bf16[8,8]{1,0} negate(%top.f), metadata={op_name="jit(step)/grad/jvp(l1)/moe/dispatch/gather"}
  %w.f = bf16[8,8]{1,0} negate(%p), metadata={op_name="jit(step)/grad/jvp(l1)/moe/experts/convert_element_type"}
  %ragged-dot-none.1 = bf16[8,8]{1,0} custom-call(%rows.f, %w.f), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %back.f = bf16[8,8]{1,0} negate(%ragged-dot-none.1), metadata={op_name="jit(step)/grad/jvp(l1)/moe/combine/gather"}
  %h.f = bf16[8,8]{1,0} negate(%back.f), metadata={op_name="jit(step)/grad/jvp(grad)/head/dot_general"}
  ROOT %o.1 = bf16[8,8]{1,0} negate(%h.f), metadata={op_name="jit(step)/optimizer/neg"}
}
"""
SPANS = {"draw.f": (0, 1), "block_diffusion_attention_fwd.1": (1, 11),
         "block_diffusion_attention_bwd.1": (11, 36), "norm.f": (36, 38),
         "top.f": (38, 40), "rows.f": (40, 43), "w.f": (43, 44),
         "ragged-dot-none.1": (44, 52), "back.f": (52, 55), "h.f": (55, 60),
         "o.1": (60, 66)}


def _hand_made(peak=None, counters=None, platform="tpu"):
    ms = 1e6
    ops = [tr.Op(n, "other", base * ms + a * ms, base * ms + b * ms)
           for base in (0, 100) for n, (a, b) in SPANS.items()]
    trace = tr.Trace(ops={0: ops}, async_ops={},
                     modules={0: [("jit_step(7)", 0.0, 80 * ms),
                                  ("jit_step(7)", 100 * ms, 180 * ms)]}, host={})
    counters = dict({"batch_per_chip": 4}, **(counters or {}))
    return types.SimpleNamespace(
        trace=trace, spans={}, counters=counters, e2e={}, window_s=0.2,
        program=r"^jit_step\b", device={"platform": platform},
        ctx=types.SimpleNamespace(peak=peak, config=CFG))


@pytest.fixture
def catalog():
    from parallel_cnn_tpu.obs import programs

    programs.record("jit_step", CATALOG)
    yield programs.lookup("jit_step")
    programs.clear()


def test_the_readers_on_a_hand_made_trace_give_hand_computed_numbers(catalog):
    assert (catalog["block_diffusion_attention_bwd.1"].scope,
            catalog["block_diffusion_attention_bwd.1"].phase) == ("l1/attn/core", "bwd")
    assert catalog["draw.f"].scope == "noise"
    run = _hand_made(counters={
        "moe_rows_held": [[1] * 6, [32768, 30000, 33000, 32768, 32768, 35011]],
        "moe_load_max_over_mean": [[9.0] * 6, [1.5, 2.25, 1.1, 1.2, 1.3, 1.4]]})
    assert _read("bd_attn_core_device_ms", run) == pytest.approx(10 + 25)
    assert _read("bd_noise_device_ms", run) == pytest.approx(1)
    assert _read("sdar_experts_device_ms", run) == pytest.approx(1 + 8)
    assert _read("sdar_route_device_ms", run) == pytest.approx(2 + 3 + 3)
    assert _read("sdar_load_max_over_mean", run) == 2.25  # the newest epoch's worst
    # the program's own statement: 80 tiles of 512 x 512 over L (L + B) on a
    # TPU; the plain path's turns of 512 queries off it hold as many
    assert _read("bd_attn_pairs_computed_ratio", run) == pytest.approx(
        80 * 512 * 512 / (4096 * 4100))
    assert _read("bd_attn_pairs_computed_ratio", _hand_made(platform="cpu")) \
        == pytest.approx(1.2488, abs=1e-4)
    assert _read("bd_attn_core_roofline", run) is None  # no published peak
    assert _read("sdar_experts_roofline", run) is None


def test_the_roofline_shares_are_least_time_over_measured_and_follow_the_rows(catalog):
    n = CFG["num_hidden_layers"]
    rows = [32768, 30000, 33000, 32768, 32768, 35011][:n]
    run = _hand_made(peak=PEAK, counters={"moe_rows_held": [rows]})
    least = shapes.least_seconds(shapes.attention_core_passes(CFG, 4), PEAK)
    assert _read("bd_attn_core_roofline", run) == pytest.approx(100 * least / 35e-3)
    ex = shapes.least_seconds(shapes.expert_passes(CFG, rows), PEAK)
    assert _read("sdar_experts_roofline", run) == pytest.approx(100 * ex / 9e-3)
    fewer = _hand_made(peak=PEAK, counters={"moe_rows_held": [[r // 2 for r in rows]]})
    assert _read("sdar_experts_roofline", fewer) < _read("sdar_experts_roofline", run)


def test_the_readers_find_nothing_where_the_program_has_no_such_thing(monkeypatch):
    """A conv net's step, or the parent's (which has neither the scopes nor
    the model): nothing named, nothing counted, nothing raised."""
    from parallel_cnn_tpu.obs import programs

    programs.record("jit_step", CATALOG.replace("/attn/core", "/s1b1/conv").replace(
        "/moe/", "/mid/").replace("(noise)", "(s4b1)").replace(
        "custom-call(", "negate(").replace("ragged-dot-none", "conv"))
    try:
        run = _hand_made(peak=PEAK)
        named = [m for m in NEW_METRICS if m != "bd_attn_pairs_computed_ratio"]
        assert all(_read(m, run) is None for m in named)
        # the parent: the configuration names a factory its program lacks
        missing = dict(CFG, factory=dict(CFG["factory"], name="no_such_factory"))
        run.ctx.config = missing
        assert _read("bd_attn_pairs_computed_ratio", run) is None
        run.ctx.config = dict(CFG, factory=dict(
            CFG["factory"], module="parallel_cnn_tpu.nn.no_such_module"))
        assert _read("bd_attn_pairs_computed_ratio", run) is None
    finally:
        programs.clear()


# ------------------------------ the whole command on the CPU, real files

@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    return tmp_path_factory.mktemp("sdar-cache")


def _env(cache):
    return dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="",
                JAX_COMPILATION_CACHE_DIR=str(cache))


def _run_cell(cache, seed, trace):
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "tiny_sdar_train",
         "--seed", str(seed), "--seconds", "0.3", "--trace", str(trace),
         "--notes", "1"],
        cwd=ROOT, env=_env(cache), capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    notes = json.loads([l for l in out.stderr.splitlines() if l.startswith("{")][-1])
    return line, notes


def test_two_seeds_are_one_job_the_same_rows_held_and_the_same_losses(cache):
    """`--seed` draws the check; the timed job — weights, sequences,
    shuffles AND the noise key — is drawn from the traffic file's
    `job_seed`: two runs with different seeds hold the same rows in every
    layer, epoch for epoch, and read the same losses."""
    (a, na), (b, nb) = (_run_cell(cache, seed, 0) for seed in (2701000123, 7))
    for line, notes in ((a, na), (b, nb)):
        assert line["correct"] is True and line["failed"] == 0
        assert line["device"]["platform"] == "cpu"
        assert set(line["metrics"]) == {"train_img_s_chip", "setup_s"}
        assert notes["counters"]["compiles_in_window"] == 0
        assert notes["counters"]["moe_overflow_rows"] == [0, 0]
    n = min(na["counters"]["epochs"], nb["counters"]["epochs"]) + 1
    assert n >= 3
    assert na["counters"]["moe_rows_held"][:n] == nb["counters"]["moe_rows_held"][:n]
    assert na["counters"]["losses"][:n] == nb["counters"]["losses"][:n]
    assert len(set(map(tuple, na["counters"]["moe_rows_held"][:n]))) > 1  # it trains
    ca, cb = na["notes"]["check_losses"], nb["notes"]["check_losses"]
    assert ca["reference"] != cb["reference"]  # another seed, another check
    rows = na["notes"]["check_rows_held"]
    assert len(rows["system"]) == 2 and len(rows["system"][0]) == 2


def test_the_traced_tiny_cell_reports_the_new_metrics_and_the_unlisted_ones(cache):
    line, notes = _run_cell(cache, 2147483659, 1)  # more than 32 signed bits hold
    assert line["correct"] is True
    m = {k: v["value"] for k, v in line["metrics"].items()}
    # CPU numbers, never device numbers: only that each reader found its ops
    for name in ("bd_attn_core_device_ms", "bd_noise_device_ms",
                 "sdar_experts_device_ms", "sdar_route_device_ms",
                 "opt_device_ms", "step_device_ms", "fwd_device_ms",
                 "bwd_device_ms"):
        assert m[name] > 0, name
    assert m["bd_attn_core_device_ms"] + m["sdar_experts_device_ms"] \
        + m["sdar_route_device_ms"] + m["bd_noise_device_ms"] < m["step_device_ms"]
    # 32 clean tokens in turns of 16 queries: 8 turns' worth over 32 x 36 pairs
    assert m["bd_attn_pairs_computed_ratio"] == pytest.approx(8 * 256 / (32 * 36))
    assert m["sdar_load_max_over_mean"] >= 1
    assert m["scope_named_pct"] > 50 and m["stem_device_ms"] == 0
    assert not set(m) & {"mfu_pct", "bd_attn_core_roofline", "sdar_experts_roofline"}
    assert not set(m) & set(GLM_METRICS)  # the other family's readers stay with it


@pytest.mark.parametrize("mode,args", [
    ("init", ["--controls", "weight_dropped,qk_norm_off"]), ("layers", [])])
def test_the_comparison_tool_runs_by_name_of_a_cell(cache, mode, args):
    out = subprocess.run(
        [sys.executable, "benchmark/tools/compare_sdar_moe.py", "--workload",
         "tiny_sdar_train", "--seeds", "1", "--mode", mode, *args],
        cwd=ROOT, env=_env(cache), capture_output=True, text=True, timeout=900)
    rows = [json.loads(l) for l in out.stdout.strip().splitlines()]
    if mode == "layers":
        assert out.returncode == 0, out.stderr[-3000:]
        (row,) = rows
        assert len(row["layer_gaps"]) == 2 and row["leaves"] == 27
        assert row["loss_gap"] < 0.02
        return
    clean, dropped, bare = rows
    assert out.returncode == 0, out.stderr[-3000:]
    assert clean["correct"] is True and "control" not in clean
    got = clean["check_rows_held"]["system"]
    assert len(got) == 2 and len(got[0]) == 2
    assert max(clean["loss_gaps"]) < 2e-3 and clean["rows_gap"] <= 6
    assert clean["check_unused_leaves"] == {"system": [], "reference": []}
    assert (dropped["control"], bare["control"]) == ("weight_dropped", "qk_norm_off")
    assert dropped["correct"] is False and dropped["loss_gaps"][0] > 0.1
    assert dropped["check_unused_leaves"]["system"] == []
    # the norms left out: gains that take no gradient, whatever the losses say
    assert bare["correct"] is False
    assert {"['layers'][0]['attn']['q_norm']", "['layers'][1]['attn']['k_norm']"} \
        <= set(bare["check_unused_leaves"]["system"])
    assert bare["check_unused_leaves"]["reference"] == []


def test_the_cells_runner_is_the_token_runner_with_one_more_reading():
    from benchmark.runners import train_zoo_tokens_bd as bd

    assert bd.run is not train_zoo_tokens.run and bd.cell_lr is train_zoo_tokens.cell_lr
    t = common.find_traffic("train_s4096_b4_bd_fixedjob", False)
    assert t["check"]["rows_tol"] == 2500 and t["check"]["loss_rtol"] == [2e-4, 2e-2]
    assert "unused" in t["check"]["note"] and "float8" in t["check"]["note"]
    # the swap is undone whatever the run does
    class Boom(Exception):
        pass

    def boom(*a):
        raise Boom

    theirs = train_zoo_tokens.checker
    ctx = types.SimpleNamespace(config={"factory": {"module": "no.such", "name": "x",
                                                    "kwargs": {}}},
                                traffic={"sequence_length": 8, "global_batch": 4,
                                         "sequences": 8})
    with pytest.raises(ModuleNotFoundError):
        bd.run(ctx)
    assert train_zoo_tokens.checker is theirs
