"""`rope_device_ms` (PR 42): the manifest's appended entry (and what the
case deselected in tests/conftest.py for it held of the older entries), and
the reader on hand-made runs — the `rope` scope of the three token models'
attention, forward and backward; a run with no trace, or a program that
records no catalog, reads None, never 0."""

import types

import pytest

from benchmark import common, trace_reduce as tr
from benchmark.layer_metrics import rope_device_ms as reader
from parallel_cnn_tpu.obs import programs

MAN = common.manifest()
TOKEN_CELLS = ["glm47f_train", "sdar_bd_train", "trinity_mini_train"]
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
AFMOE_METRICS = ["win_attn_core_device_ms", "win_attn_core_roofline",
                 "full_attn_core_device_ms", "full_attn_core_roofline",
                 "win_attn_pairs_computed_ratio", "afmoe_experts_device_ms",
                 "afmoe_experts_roofline", "afmoe_route_device_ms",
                 "afmoe_load_max_over_mean", "afmoe_gate_norm_device_ms"]
# instruction -> (scope, phase), and its (start, end) in ms of a 90 ms step
OPS = {
    "qkv.f": ("l0/attn/qkv", "fwd", 0, 5),
    "norm.f": ("l0/attn/qk_norm", "fwd", 5, 7),
    "rope_turn.1": ("l0/attn/rope", "fwd", 7, 8),
    "rope_turn.2": ("l0/attn/rope", "fwd", 8, 8.25),
    "core.f": ("l0/attn/core", "fwd", 8.25, 20),
    "pe.f": ("mtp/l0/attn/rope", "fwd", 20, 22.5),       # GLM's MTP module's
    "rope_turn.3": ("l0/attn/rope", "bwd", 30, 31),      # rematerialised
    "rope_turn.4": ("l0/attn/rope", "bwd", 40, 41),
    "copy.9": ("l0/attn/rope", "bwd", 41, 42.5),         # its re-layout
    "europe.f": ("l0/europe", "fwd", 50, 60),            # no `rope` scope
    "rope.f": ("l0/mlp/rope", "fwd", 60, 70),            # under no `attn`
}


def _run(trace=True):
    ms = 1e6
    ops = [tr.Op(n, "other", base * ms + a * ms, base * ms + b * ms)
           for base in (0, 100) for n, (_, _, a, b) in OPS.items()]
    made = tr.Trace(ops={0: ops}, async_ops={},
                    modules={0: [("jit_step(7)", 0.0, 90 * ms),
                                 ("jit_step(7)", 100 * ms, 190 * ms)]}, host={})
    return types.SimpleNamespace(trace=made if trace else None,
                                 program=r"^jit_step\b")


def _text(ops):
    """A step's HLO text whose instructions carry the name stacks the
    program gives them: a layer under `grad`, rematerialised backward."""
    lines = ["HloModule jit_step", "",
             "ENTRY %main (p: bf16[8,8]) -> bf16[8,8] {",
             "  %p = bf16[8,8]{1,0} parameter(0)"]
    for name, (scope, phase, _, _) in ops.items():
        layer, rest = scope.rsplit("/", 2)[0], "/".join(scope.rsplit("/", 2)[1:])
        if "/" not in rest:  # `l0/europe`: a scope right under the layer
            layer, rest = scope.split("/", 1)
        stack = (f"jvp({layer})/{rest}" if phase == "fwd" else
                 f"transpose(jvp({layer}))/grad/jvp({layer})/checkpoint/{rest}")
        lines.append(f"  %{name} = bf16[8,8]{{1,0}} negate(%p), "
                     f'metadata={{op_name="jit(step)/grad/{stack}/mul"}}')
    return "\n".join(lines + ["  ROOT %o = bf16[8,8]{1,0} negate(%p)", "}", ""])


@pytest.fixture
def catalog():
    programs.record("jit_step", _text(OPS))
    yield programs.lookup("jit_step")
    programs.clear()


def test_what_pr41_left_is_a_prefix_with_closed_slices():
    """What tests/benchmark/test_afmoe_config.py's `test_what_pr37_left_is_
    a_prefix_and_this_prs_entries_come_after_it` held, with `[46:56]` where
    it read to the end and the new cell's sixteen found where they start,
    not counted from the end."""
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
    # PR 41: one cell on one chip and ten entries after the 46
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "trinity_mini_ep8", "train_s16384_b1_fixedjob", 1)
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert len(cell["why"]) <= 200 and "1/8 of deployed" in cell["why"]
    ten = MAN["per_layer"][46:56]
    assert [m["name"] for m in ten] == AFMOE_METRICS
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
            at = got.index(SETUP_METRICS[0])
            assert got[at: at + 16] == SETUP_METRICS + AFMOE_METRICS
            assert not set(got) & set(GLM_METRICS + SDAR_METRICS)
        else:
            assert not set(got) & set(AFMOE_METRICS)


def test_the_entry_is_appended_and_names_the_three_token_cells():
    """The 57th entry, after PR 41's ten (a closed index: what a later PR
    appends is that PR's to hold)."""
    assert len(MAN["per_layer"]) >= 57
    assert MAN["per_layer"][56] == {
        "name": "rope_device_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "layers and kernels",
        "moves": "train_img_s_chip", "workloads": TOKEN_CELLS}
    assert MAN["per_layer"][56]["layer"] in {
        m["layer"] for m in MAN["per_layer"][:56]}
    assert [m["name"] for m in MAN["per_layer"]].count("rope_device_ms") == 1
    for cell in MAN["workloads"]:
        got = [m["name"] for m in common.cell_metrics(MAN, cell["name"], "per_layer")]
        assert ("rope_device_ms" in got) == (cell["name"] in TOKEN_CELLS)


def test_the_reader_sums_the_rope_scopes_under_an_attention_both_phases(catalog):
    assert {n: (e.scope, e.phase) for n, e in catalog.items() if n in OPS} == {
        n: op[:2] for n, op in OPS.items()}
    assert reader.read(_run()) == pytest.approx(1 + 0.25 + 2.5 + 1 + 1 + 1.5)


@pytest.mark.parametrize("parent", ["no_trace", "no_catalog", "no_rope_scope"])
def test_a_run_with_nothing_to_read_reads_none_not_zero(parent):
    if parent == "no_rope_scope":
        programs.record("jit_step", _text(
            {n: ("l0/attn/core", "fwd", 0, 0) for n in OPS}))
    try:
        assert reader.read(_run(trace=parent != "no_trace")) is None
    finally:
        programs.clear()
