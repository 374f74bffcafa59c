"""BENCHMARK.json against the contract it was written to, and the harness
against its promise to be driven by data: every name resolves to a file,
and a cell or a metric added as a new file is found without an edit to
any file that is there."""

import importlib
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import common  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
MAN = common.manifest()
CELLS = [w["name"] for w in MAN["workloads"]]
METRICS = MAN["end_to_end"] + MAN["per_layer"]


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level_keys_and_sizes():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(MAN["run_seconds"], int) and 1 <= MAN["run_seconds"] <= 51
    assert 1 <= len(MAN["command"]) <= 32 and all(_line(c) for c in MAN["command"])
    assert MAN["paths"] == ["benchmark", "tests/benchmark"]
    assert 2 <= len(MAN["workloads"]) <= 24 and 1 <= len(MAN["configs"]) <= 24
    assert 1 <= len(MAN["end_to_end"]) <= 16 and 1 <= len(MAN["per_layer"]) <= 128


def test_command_names_only_files_under_paths():
    for word in MAN["command"]:
        assert not word.startswith("/") and ".." not in word
        if os.path.exists(os.path.join(ROOT, word)):
            assert any(word.startswith(p + "/") for p in MAN["paths"])


def test_a_full_check_fits_its_budget_with_24_cells():
    runs = 2 + 14 * 24
    assert runs * (MAN["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("entry", MAN["configs"], ids=lambda e: e["name"])
def test_config_entries(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(entry["name"]) and _line(entry["source"]) and _line(entry["why"])
    assert entry["file"].startswith("benchmark/") and len(entry["reduced"]) <= 16
    cfg = common.load_json(os.path.join(ROOT, entry["file"]))
    assert cfg["name"] == entry["name"] and cfg["source"] == entry["source"]
    assert cfg["reduced"] == entry["reduced"] == []
    assert "assumed" in cfg and "arch" in cfg and "factory" in cfg
    assert any(w["config"] == entry["name"] for w in MAN["workloads"])
    assert [c["file"] for c in MAN["configs"]].count(entry["file"]) == 1


@pytest.mark.parametrize("entry", MAN["workloads"], ids=lambda e: e["name"])
def test_workload_entries_resolve_to_files(entry):
    assert set(entry) == {"name", "config", "traffic", "chips", "why"}
    assert all(NAME.match(entry[k]) for k in ("name", "config", "traffic"))
    assert entry["chips"] in (1, 4) and _line(entry["why"])
    w = common.find_workload(entry["name"])
    assert not w["rehearsal"]
    assert {k: w[k] for k in ("config", "traffic", "chips", "why")} == \
        {k: entry[k] for k in ("config", "traffic", "chips", "why")}
    assert "who" in w
    assert entry["config"] in [c["name"] for c in MAN["configs"]]
    common.find_config(entry["config"], False)
    traffic = common.find_traffic(entry["traffic"], False)
    runner = importlib.import_module(f"benchmark.runners.{traffic['runner']}")
    assert callable(runner.run)


def test_cells_are_unique_and_at_most_one_takes_four_chips():
    assert len(set(CELLS)) == len(CELLS)
    pairs = [(w["config"], w["traffic"]) for w in MAN["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = [w["name"] for w in MAN["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(CELLS) // 4) and len(four) <= 1


@pytest.mark.parametrize("m", METRICS, ids=lambda m: m["name"])
def test_metric_entries(m):
    per_layer = m in MAN["per_layer"]
    want = {"name", "unit", "better", "source"} | (
        {"layer", "moves"} if per_layer else {"bound"})
    assert set(m) - {"workloads"} == want
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    assert all(c in CELLS for c in m.get("workloads", []))
    if per_layer:
        assert _line(m["layer"])
        moved = [e for e in MAN["end_to_end"] if e["name"] == m["moves"]]
        assert len(moved) == 1
        # reported only where the metric it moves is
        cells = m.get("workloads", CELLS)
        assert set(cells) <= set(moved[0].get("workloads", CELLS))
        reader = importlib.import_module(f"benchmark.layer_metrics.{m['name']}")
        assert callable(reader.read)
    else:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1


def test_metric_names_are_unique_and_setup_s_is_there():
    names = [m["name"] for m in METRICS]
    assert len(set(names)) == len(names)
    setup = [m for m in MAN["end_to_end"] if m["name"] == "setup_s"]
    assert len(setup) == 1 and setup[0]["bound"] <= 0.1 and "workloads" not in setup[0]


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_setup_another_end_to_end_and_a_layer_metric(cell):
    e2e = [m["name"] for m in common.cell_metrics(MAN, cell, "end_to_end")]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert len(common.cell_metrics(MAN, cell, "per_layer")) >= 1


def test_files_under_paths_are_named_from_allowed_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for p in MAN["paths"]:
        for base, dirs, files in os.walk(os.path.join(ROOT, p)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(base, f), ROOT)
                assert ok.match(rel) and len(rel) <= 200, rel


READERS = sorted(f[:-3] for f in os.listdir(os.path.join(ROOT, "benchmark", "layer_metrics"))
                 if f.endswith(".py") and f != "__init__.py")


@pytest.mark.parametrize("name", READERS)
def test_a_reader_that_finds_nothing_to_read_returns_nothing(name):
    import types

    reader = importlib.import_module(f"benchmark.layer_metrics.{name}")
    empty = types.SimpleNamespace(
        trace=None, spans={}, counters={}, e2e={}, window_s=0.0, program="^x",
        device={}, ctx=types.SimpleNamespace(peak=None, config={}))
    assert reader.read(empty) is None
    assert reader.__doc__ and NAME.match(name)


def test_serve_readers_read_the_batchers_own_counters():
    import types

    run = types.SimpleNamespace(
        counters={"batches": 4, "requests_in_batches": 10, "padded_slots": 6,
                  "gen_late_p99_ms": 0.7},
        spans={"serve.batch": [0.010, 0.030, 0.020]})
    read = lambda n: importlib.import_module(  # noqa: E731
        f"benchmark.layer_metrics.{n}").read(run)
    assert read("mean_batch_size") == 2.5
    assert read("pad_waste_pct") == pytest.approx(37.5)
    assert read("batch_service_ms") == pytest.approx(20.0)
    assert read("gen_late_p99_ms") == 0.7


def test_peaks_table_names_its_source_and_refuses_nothing_silently():
    peaks = common.load_json(os.path.join(ROOT, "benchmark", "peaks.json"))
    assert "Google Cloud" in peaks["source"]
    v5e = peaks["TPU v5 lite"]
    assert (v5e["bf16_flops_per_s"], v5e["hbm_bytes_per_s"]) == (197e12, 819e9)
    assert "cpu" not in peaks


@pytest.fixture(scope="module")
def scratch_copy(tmp_path_factory):
    """The benchmark's files in a directory of their own, the program
    beside them, plus one new cell and one new per-layer metric added as
    new files (and one appended manifest entry), nothing edited."""
    root = tmp_path_factory.mktemp("copy")
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copytree(os.path.join(ROOT, "tests", "benchmark", "cells"),
                    root / "tests" / "benchmark" / "cells")
    os.symlink(os.path.join(ROOT, "parallel_cnn_tpu"), root / "parallel_cnn_tpu")
    cells = root / "tests" / "benchmark" / "cells" / "workloads"
    new = common.load_json(str(cells / "tiny_r18_train.json"))
    new["why"] = "a fifth cell, added as a file"
    (cells / "dummy_fifth.json").write_text(json.dumps(new))
    (root / "benchmark" / "layer_metrics" / "dummy_epochs.py").write_text(
        '"""A metric added as a file: whole epochs in the window."""\n\n\n'
        'def read(run):\n    return run.counters["epochs"]\n')
    man = json.loads(json.dumps(MAN))
    man["per_layer"].append({
        "name": "dummy_epochs", "unit": "epochs", "better": "higher",
        "source": "program_counter", "layer": "zoo trainer loop",
        "moves": "train_img_s_chip", "workloads": ["r18_train"]})
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    return root


def test_a_new_cell_and_a_new_metric_are_found_without_editing_a_file(scratch_copy):
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="",
               JAX_COMPILATION_CACHE_DIR=str(scratch_copy / "cache"))
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "dummy_fifth",
         "--seed", "3", "--seconds", "0.2", "--trace", "1"],
        cwd=scratch_copy, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["device"]["platform"] == "cpu" and line["correct"] is True
    assert line["metrics"]["dummy_epochs"] == {
        "value": pytest.approx(line["metrics"]["dummy_epochs"]["value"]),
        "unit": "epochs"}
    assert line["metrics"]["dummy_epochs"]["value"] >= 1
    assert "step_device_ms" in line["metrics"]
