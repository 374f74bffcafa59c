"""`moe_sum_rows_visited_ratio` (PR 37): the manifest's appended entry (and
what the case deselected in tests/conftest.py for it held of the older
entries), the reader on hand-made runs — a
parent of PR 37 (no copy of the epoch record in the program, or no such
counter in it) reads None, never 0 — and the counter's way from an expert
layer's state through `GlmMoe.counters` and `zoo.train`'s epoch record
into the program's copy that the reader finds."""

import sys
import types

import jax
import jax.numpy as jnp
import pytest

from benchmark import common
from benchmark.layer_metrics import moe_sum_rows_visited_ratio as reader
from parallel_cnn_tpu.nn import glm_moe
from parallel_cnn_tpu.obs import epochs

HELD = [[8192, 8100, 9011], [24177, 11142, 33996]]
MAN = common.manifest()
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
TOKEN_CELLS = ["glm47f_train", "sdar_bd_train"]


def test_what_pr36_left_is_a_prefix_and_the_one_comes_after_it():
    """What tests/benchmark/test_setup_metrics.py's `test_what_pr34_left_is_
    a_prefix_and_the_six_come_after_it` held, with `[39:45]` where it read
    to the end, and this PR's one entry after them."""
    assert [c["name"] for c in MAN["configs"]] == [
        "resnet50_imagenet", "resnet18_imagenet", "convnext_b_imagenet",
        "glm_4_7_flash_ep8", "sdar_30b_a3b_ep8"]
    assert [w["name"] for w in MAN["workloads"]] == [
        "r50_train", "r18_train", "r50_train_dp4", "convnext_b_train",
        *TOKEN_CELLS]
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
    glm, cell = MAN["workloads"][-2:]
    assert (glm["config"], glm["traffic"], glm["chips"]) == (
        "glm_4_7_flash_ep8", "train_s4096_b4_fixedjob", 1)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "sdar_30b_a3b_ep8", "train_s4096_b4_bd_fixedjob", 1)
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert len(cell["why"]) <= 200 and "8x" in cell["why"]
    assert [w["chips"] for w in MAN["workloads"]].count(4) == 1
    # PR 36's six entries after the 39
    six = MAN["per_layer"][39:45]
    assert [m["name"] for m in six] == SETUP_METRICS
    for m in six:
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves"}
        assert (m["moves"], m["better"]) == ("setup_s", "lower")
    assert [m["unit"] for m in six] == ["s", "s", "s", "programs", "programs", "s"]
    assert [m["source"] for m in six] == [
        "program_span", "program_span", "program_span", "program_counter",
        "program_counter", "program_span"]
    assert [m["layer"] for m in six] == (
        ["entry point and compile cache"] * 5 + ["step factories"])
    assert MAN["per_layer"][0]["layer"] == six[0]["layer"]  # warmup_s's
    assert MAN["per_layer"][4]["layer"] == six[5]["layer"]  # step_device_ms's
    # this PR: one entry after the 45, nothing else; its layer is one the
    # manifest already names, letter for letter (`conv_time_pct`'s)
    (one,) = MAN["per_layer"][45:]
    assert one == {
        "name": "moe_sum_rows_visited_ratio", "unit": "rows/row",
        "better": "lower", "source": "program_counter",
        "layer": "layers and kernels", "moves": "train_img_s_chip",
        "workloads": TOKEN_CELLS}
    assert one["layer"] in {m["layer"] for m in MAN["per_layer"][:45]}
    # every cell reports `setup_s`, so every cell reports the six; the two
    # token cells report the one after them, the conv cells do not
    for cell in MAN["workloads"]:
        got = [m["name"] for m in common.cell_metrics(MAN, cell["name"], "per_layer")]
        if cell["name"] in TOKEN_CELLS:
            assert got[-7:] == SETUP_METRICS + [one["name"]]
        else:
            assert got[-6:] == SETUP_METRICS and one["name"] not in got


def _run(held=HELD):
    return types.SimpleNamespace(counters={"moe_rows_held": held})


@pytest.fixture(autouse=True)
def no_records():
    epochs.clear()
    yield
    epochs.clear()


def test_the_ratio_is_the_mean_over_the_layers_of_the_newest_epoch():
    epochs.record({"epoch": 3, "moe_rows_held": HELD[0],
                   "moe_sum_rows_visited": [1, 2, 3]})
    epochs.record({"epoch": 4, "moe_rows_held": HELD[1],
                   "moe_sum_rows_visited": [36272, 33424, 40800]})
    want = (36272 / 24177 + 33424 / 11142 + 40800 / 33996) / 3
    assert reader.read(_run()) == pytest.approx(want)
    # a layer that held nothing has no ratio and does not enter the mean
    epochs.record({"moe_rows_held": [0, 100], "moe_sum_rows_visited": [0, 160]})
    assert reader.read(_run([[0, 100]])) == pytest.approx(1.6)


@pytest.mark.parametrize("parent", ["no_module", "no_counter", "no_record",
                                    "another_epoch", "no_rows_in_the_run"])
def test_a_parent_shaped_run_reads_none_not_zero(parent, monkeypatch):
    run = _run()
    if parent == "no_module":  # the program as it was: nothing to import
        monkeypatch.setitem(sys.modules, "parallel_cnn_tpu.obs.epochs", None)
        import parallel_cnn_tpu.obs as obs
        monkeypatch.delattr(obs, "epochs")
    elif parent == "no_counter":
        epochs.record({"moe_rows_held": HELD[1]})
    elif parent == "another_epoch":  # the record is not the epoch the runner read
        epochs.record({"moe_rows_held": HELD[0],
                       "moe_sum_rows_visited": [1, 2, 3]})
    elif parent == "no_rows_in_the_run":
        epochs.record({"moe_rows_held": HELD[1],
                       "moe_sum_rows_visited": [1, 2, 3]})
        run = types.SimpleNamespace(counters={})
    assert reader.read(run) is None


def test_the_counter_goes_from_the_layers_state_to_the_programs_newest_record():
    """One value a layer, the MTP module's last: every row of the buffer
    where the plain sum ran (this CPU), set by the last training forward."""
    model = glm_moe.glm_moe_lite(
        vocab_size=64, hidden_size=32, intermediate_size=64,
        moe_intermediate_size=16, num_hidden_layers=3, num_attention_heads=2,
        q_lora_rank=12, kv_lora_rank=8, qk_nope_head_dim=6, qk_rope_head_dim=4,
        v_head_dim=8, n_routed_experts=8, num_experts_per_tok=2,
        held_experts=[0, 1, 2], row_buffer=40, dtype="float32", q_block=8,
        loss_block=16)
    params, state, _ = model.init(jax.random.key(0), (16,))
    assert model.counters(state)["moe_sum_rows_visited"] == [0, 0, 0]
    x = jax.random.randint(jax.random.key(1), (2, 16), 0, 64)
    _, new = model.loss(params, state, x, jnp.roll(x, -1, axis=1))
    got = model.counters(model.finish_step(new))
    assert got["moe_sum_rows_visited"] == [40, 40, 40]
    assert len(got["moe_rows_held"]) == 3 and all(
        0 < r <= 40 for r in got["moe_rows_held"])
    epochs.record(dict(event="zoo_epoch", **got))
    want = sum(40 / r for r in got["moe_rows_held"]) / 3
    assert reader.read(_run([got["moe_rows_held"]])) == pytest.approx(want)


def test_the_programs_copy_keeps_the_newest_records_only():
    for i in range(epochs.KEEP + 5):
        epochs.record({"epoch": i})
    kept = epochs.newest(epochs.KEEP + 5)
    assert len(kept) == epochs.KEEP and kept[-1] == {"epoch": epochs.KEEP + 4}
    assert epochs.newest() == [{"epoch": epochs.KEEP + 4}] and epochs.newest(0) == []
    rec = {"epoch": -1, "moe_rows_held": [1]}
    epochs.record(rec)
    rec["moe_rows_held"].append(2)  # a copy of the record's keys, not the caller's dict
    rec["epoch"] = 7
    assert epochs.newest()[0]["epoch"] == -1
