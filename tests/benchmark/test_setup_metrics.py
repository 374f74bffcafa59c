"""The six per-layer metrics of set-up (`setup_trace_lower_s`,
`setup_compile_s`, `setup_cache_load_s`, `setup_programs`,
`setup_cache_misses`, `setup_step_s`) that read the program's own compile
log (`parallel_cnn_tpu/obs/compiles.py`) through `benchmark/setup_time.py`:
the manifest's appended entries (and what the case deselected in
tests/conftest.py for them held of the older entries), the readers on a
hand-made log with hand-computed numbers, what they do where there is
nothing to read, and the whole command on the CPU through the real files
(`tiny_r18_train`, traced and through `benchmark/tools/setup_table.py`)."""

import importlib
import json
import os
import subprocess
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import common, setup_time  # noqa: E402
from parallel_cnn_tpu.obs import compiles  # noqa: E402

MAN = common.manifest()
GLM_METRICS = ["attn_core_device_ms", "attn_core_roofline",
               "moe_experts_device_ms", "moe_experts_roofline",
               "moe_route_device_ms", "mtp_device_ms", "moe_held_load_ratio",
               "moe_load_max_over_mean"]
SDAR_METRICS = ["bd_attn_core_device_ms", "bd_attn_core_roofline",
                "bd_attn_pairs_computed_ratio", "bd_noise_device_ms",
                "sdar_experts_device_ms", "sdar_experts_roofline",
                "sdar_route_device_ms", "sdar_load_max_over_mean"]
NEW = ["setup_trace_lower_s", "setup_compile_s", "setup_cache_load_s",
       "setup_programs", "setup_cache_misses", "setup_step_s"]


def _read(name, run):
    return importlib.import_module(f"benchmark.layer_metrics.{name}").read(run)


# ------------------------------------------------------------ the manifest

def test_what_pr34_left_is_a_prefix_and_the_six_come_after_it():
    """What tests/benchmark/test_sdar_config.py's `test_what_pr32_left_is_
    a_prefix_and_this_prs_entries_come_after_it` held, with `[31:39]` where
    it read to the end, and this PR's six entries after them."""
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
    # one cell in six asks for four chips, as before
    assert [w["chips"] for w in MAN["workloads"]].count(4) == 1
    # this PR: six entries after the 39, nothing else
    six = MAN["per_layer"][39:]
    assert [m["name"] for m in six] == NEW
    for m in six:
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves"}
        assert (m["moves"], m["better"]) == ("setup_s", "lower")
    assert [m["unit"] for m in six] == ["s", "s", "s", "programs", "programs", "s"]
    assert [m["source"] for m in six] == [
        "program_span", "program_span", "program_span", "program_counter",
        "program_counter", "program_span"]
    assert [m["layer"] for m in six] == (
        ["entry point and compile cache"] * 5 + ["step factories"])
    # the layers are ones the manifest already names, letter for letter
    assert MAN["per_layer"][0]["layer"] == six[0]["layer"]  # warmup_s's
    assert MAN["per_layer"][4]["layer"] == six[5]["layer"]  # step_device_ms's
    # every cell reports `setup_s`, so every cell reports the six
    for cell in MAN["workloads"]:
        got = [m["name"] for m in common.cell_metrics(MAN, cell["name"], "per_layer")]
        assert got[-6:] == NEW


# ---------------------------------------------------------- a hand-made log

T0 = 1000.0  # the process's start on the run's clock
R = compiles.Record


def _log():
    """A run whose set-up is [1000, 1040] s. In it: the check's step (a
    trace, a lowering, a compile that missed), an init program the cache
    served, the loop's step served from the cache inside epoch 1's first
    dispatch, one compile with the cache off; the tracing's own catalog
    load; and a recompile in the window, after set-up's end."""
    d = {"step": 0, "epoch": 1}
    return [
        R("trace", "step", T0 + 1.0, 2.0, 2.5),
        R("lower", "jit(step)", T0 + 4.0, 1.0, 1.0),
        R("compile", "jit(step)", T0 + 5.0, 20.0, 20.0, "miss"),
        R("trace", "_normal", T0 + 26.0, 0.25, 0.25, within="zoo.init", ids={}),
        R("lower", "jit(_normal)", T0 + 26.5, 0.5, 0.5, within="zoo.init", ids={}),
        R("compile", "jit(_normal)", T0 + 27.0, 0.75, 0.75, "hit", 0.5,
          "zoo.init", {}),
        R("trace", "step", T0 + 30.0, 1.5, 1.5, within="zoo.dispatch", ids=d),
        R("lower", "jit(step)", T0 + 32.0, 1.0, 1.0, within="zoo.dispatch", ids=d),
        R("compile", "jit(step)", T0 + 33.0, 3.0, 3.0, "hit", 2.5,
          "zoo.dispatch", d),
        R("compile", "jit(select_batch)", T0 + 36.5, 0.125, 0.125, "off",
          None, "zoo.data", d),
        # tracing's own: left out
        R("trace", "step", T0 + 37.0, 0.5, 0.5, within="zoo.catalog", ids={}),
        R("compile", "jit(step)", T0 + 37.5, 2.0, 2.0, "hit", 1.5,
          "zoo.catalog", {}),
        # starts on the boundary: counted; a hair after it: not
        R("compile", "jit(add)", T0 + 40.0, 0.25, 0.25, "miss"),
        R("compile", "jit(step)", T0 + 40.001, 9.0, 9.0, "miss",
          None, "zoo.dispatch", {"step": 16, "epoch": 2}),
    ]


@pytest.fixture
def run(monkeypatch):
    monkeypatch.setattr(compiles, "records", _log)
    monkeypatch.setattr(compiles, "installed", lambda: True)
    return types.SimpleNamespace(
        trace=None, spans={}, counters={}, e2e={"setup_s": 40.0}, window_s=10.0,
        program=r"^jit_step\b", device={},
        ctx=types.SimpleNamespace(t_process=T0, peak=None, config={}))


def test_the_readers_on_a_hand_made_log_give_hand_computed_numbers(run):
    assert _read("setup_trace_lower_s", run) == 2.0 + 1.0 + 0.25 + 0.5 + 1.5 + 1.0
    assert _read("setup_compile_s", run) == 20.0 + 0.125 + 0.25  # misses, and off
    assert _read("setup_cache_load_s", run) == 0.75 + 3.0
    assert _read("setup_programs", run) == 5
    assert _read("setup_cache_misses", run) == 2
    assert _read("setup_step_s", run) == 2.0 + 1.0 + 20.0 + 1.5 + 1.0 + 3.0
    parts = sum(_read(n, run) for n in NEW[:3])
    assert parts <= run.e2e["setup_s"]
    assert _read("setup_cache_misses", run) <= _read("setup_programs", run)
    # the records behind them: in the window's past, not the catalog's
    kept = setup_time.records(run)
    assert len(kept) == 11 and kept[-1].fun_name == "jit(add)"
    assert {r.within for r in kept} == {None, "zoo.init", "zoo.dispatch", "zoo.data"}


def test_a_shorter_set_up_counts_fewer_records(run):
    run.e2e["setup_s"] = 26.25  # ends inside the init's trace
    assert _read("setup_trace_lower_s", run) == 2.0 + 1.0 + 0.25
    assert _read("setup_cache_load_s", run) == 0.0
    assert (_read("setup_programs", run), _read("setup_cache_misses", run)) == (1, 1)
    run.ctx.t_process = T0 + 100.0  # another process's log: nothing of it
    assert [_read(n, run) for n in NEW] == [0, 0, 0, 0, 0, 0]


@pytest.mark.parametrize("fun_name,module", [
    ("jit(step)", "jit_step"), ("step", "jit_step"),
    ("jit(select_batch)", "jit_select_batch"), ("pmap(step)", "pmap_step"),
    ("jit(<lambda>)", "jit_<lambda>")])
def test_a_records_program_is_named_as_the_device_trace_names_it(fun_name, module):
    assert setup_time.module_of(R("lower", fun_name, 0.0, 0.0, 0.0)) == module


def test_the_step_reader_follows_the_runs_program(run):
    run.program = r"^jit_select_batch\b"
    assert _read("setup_step_s", run) == 0.125
    run.program = r"^jit_predict\b"
    assert _read("setup_step_s", run) == 0.0


@pytest.mark.parametrize("name", NEW)
def test_the_readers_find_nothing_where_there_is_nothing_to_read(
        name, run, monkeypatch):
    assert _read(name, run) is not None
    # a run whose line has no `setup_s`
    run.e2e = {}
    assert _read(name, run) is None
    run.e2e = {"setup_s": 40.0}
    # a host whose two clocks differ
    monkeypatch.setattr(setup_time, "clocks_agree", lambda: False)
    assert _read(name, run) is None
    monkeypatch.setattr(setup_time, "clocks_agree", lambda: True)
    # an entry point that installed no log
    monkeypatch.setattr(compiles, "installed", lambda: False)
    assert _read(name, run) is None
    monkeypatch.setattr(compiles, "installed", lambda: True)
    # a program from before the log (the parent commit under these files)
    import parallel_cnn_tpu.obs

    monkeypatch.setitem(sys.modules, "parallel_cnn_tpu.obs.compiles", None)
    monkeypatch.delattr(parallel_cnn_tpu.obs, "compiles")
    assert _read(name, run) is None


def test_the_two_clocks_agree_here():
    assert setup_time.clocks_agree() is True


# ----------------------------------------------------- rehearsals on the CPU

@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    return tmp_path_factory.mktemp("jax_cache")


def _run(cache, args, devices=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               JAX_COMPILATION_CACHE_DIR=str(cache))
    out = subprocess.run([sys.executable, *args], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return [json.loads(l) for l in out.stdout.splitlines() if l.startswith("{")]


@pytest.fixture(scope="module")
def lines(cache, tmp_path_factory):
    """The cell untraced (which fills the cache), then traced, then through
    the table tool: (untraced line, traced line, tool's line, its report)."""
    cell = ["--workload", "tiny_r18_train", "--seconds", "0.3"]
    (plain,) = _run(cache, ["benchmark/run.py", *cell, "--seed", "5", "--trace", "0"])
    (traced,) = _run(cache, ["benchmark/run.py", *cell, "--seed", "2147483659",
                             "--trace", "1"])
    out = tmp_path_factory.mktemp("table") / "t" / "setup.json"
    line, table = _run(cache, ["benchmark/tools/setup_table.py", *cell,
                               "--seed", "7", "--out", str(out)])
    with open(out) as f:
        assert json.load(f) == table
    return plain, traced, line, table


def test_the_traced_tiny_cell_reports_the_six_as_parts_of_its_set_up(lines):
    plain, traced, _, _ = lines
    assert plain["correct"] is True and traced["correct"] is True
    assert set(plain["metrics"]) == {"train_img_s_chip", "setup_s"}
    m = {k: v["value"] for k, v in traced["metrics"].items()}
    assert set(NEW) <= set(m) and "setup_s" not in m
    assert [traced["metrics"][n]["unit"] for n in NEW] == [
        "s", "s", "s", "programs", "programs", "s"]
    for name in NEW:
        assert 0 <= m[name] < float("inf"), name
    # CPU numbers, never device numbers: only that the parts are parts
    assert m["setup_trace_lower_s"] > 0 and m["setup_step_s"] > 0
    assert m["setup_programs"] >= 10 and m["setup_programs"] == int(m["setup_programs"])
    assert m["setup_cache_misses"] <= m["setup_programs"]
    # the untraced run before it left every program in the cache
    assert m["setup_cache_misses"] == 0 and m["setup_compile_s"] == 0
    assert m["setup_cache_load_s"] > 0
    assert m["warmup_s"] > 0  # the harness's own reading stays


def test_the_table_tool_prints_set_up_by_phase_and_by_function(lines):
    _, _, line, table = lines
    assert line["correct"] is True
    m = table["metrics"]
    assert set(m) == set(NEW) | {"warmup_s"}
    assert {k: line["metrics"][k]["value"] for k in NEW} == {k: m[k] for k in NEW}
    parts = m["setup_trace_lower_s"] + m["setup_compile_s"] + m["setup_cache_load_s"]
    assert parts <= table["setup_s"] == table["traced_e2e"]["setup_s"]
    assert table["remainder_s"] == pytest.approx(table["setup_s"] - parts)
    assert table["remainder_s"] > 0  # imports, the reference, data, execution
    phases = table["phases_s"]
    assert {"zoo.init", "zoo.build_step", "zoo.store", "zoo.catalog",
            "zoo.data", "zoo.dispatch", "zoo.readback"} <= set(phases)
    assert "zoo.restore" not in phases
    assert all(v >= 0 for v in phases.values())
    assert sum(phases.values()) < table["setup_s"]
    rows = table["functions"]
    assert 1 <= len(rows) <= 20 and all(len(r) == 9 for r in rows)
    by_name = {r[0]: r for r in rows}
    step = by_name["jit_step"]
    # the check's program and the loop's: two requests, both served warm
    assert step[1] == 2 and step[6] == 2 and step[7] == 0
    assert "zoo.dispatch" in step[8]
    assert m["setup_step_s"] == pytest.approx(step[2] + step[3] + step[4])
    assert step[5] <= step[4]  # the load is part of the compile request
    totals = [r[2] + r[3] + r[4] for r in rows]
    assert totals == sorted(totals, reverse=True)
    assert table["records_in_setup"] <= table["records_kept"] <= compiles.KEEP
    assert table["records_in_setup"] == 3 * m["setup_programs"]
    assert table["catalog_s"] >= 0 and table["catalog_requests"] == 0


def test_on_four_devices_the_six_count_both_of_the_loops_step_programs(cache):
    """Under a mesh the loop's step compiles twice (its first call's state
    is laid out otherwise than what it returns). `zoo.train` catalogs the
    step for the trace only when epoch 1's steps are out, so the loop has
    asked for both programs itself, as an untraced run does: nothing of
    the step lies within `zoo.catalog`, where the readers would leave it
    out, and `setup_step_s` and `setup_programs` hold both requests."""
    line, table = _run(cache, ["benchmark/tools/setup_table.py", "--workload",
                               "tiny_r18_train_dp4", "--seconds", "0.3",
                               "--seed", "2147483777"], devices=4)
    assert line["correct"] is True and line["device"]["count"] == 4
    m = table["metrics"]
    assert set(NEW) <= set(m)
    assert table["catalog_requests"] == 0
    assert "zoo.catalog" in table["phases_s"] and "zoo.shard" in table["phases_s"]
    (step,) = [r for r in table["functions"] if r[0] == "jit_step"]
    # the check's programs, then the loop's first step and its second
    assert step[1] >= 4 and step[6] + step[7] == step[1]
    assert "zoo.dispatch" in step[8]
    assert m["setup_step_s"] == pytest.approx(step[2] + step[3] + step[4])
    assert m["setup_cache_misses"] <= m["setup_programs"]
    parts = m["setup_trace_lower_s"] + m["setup_compile_s"] + m["setup_cache_load_s"]
    assert parts <= table["setup_s"]
