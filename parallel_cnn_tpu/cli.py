"""CLI driver (≙ main(), Sequential/Main.cpp:44-57 — which accepts
argc/argv and ignores them; here the flags actually work).

    python -m parallel_cnn_tpu [--loader …] [--epochs N] [--batch-size B] …

Drives the same flow as every reference backend: load data → learn →
test, printing the reference's lines ("Learning", per-epoch error, final
error rate), plus the subsystems the reference lacks: checkpoint/resume,
structured metrics, and the per-phase profile table (paper Tables 4-8).
"""

from __future__ import annotations

import argparse
import dataclasses
import os
from typing import List, Optional

from parallel_cnn_tpu import obs as obs_lib
from parallel_cnn_tpu.config import (
    AsyncConfig,
    AutotuneConfig,
    CommConfig,
    Config,
    DataConfig,
    ElasticConfig,
    FusedStepConfig,
    MeshConfig,
    NetConfig,
    ObsConfig,
    PipelineConfig,
    ResilienceConfig,
    ServeConfig,
    TrainConfig,
    plan_path_from_env,
)


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="parallel_cnn_tpu",
        description="TPU-native trainer with the reference's capabilities",
    )
    d, t = DataConfig(), TrainConfig()
    p.add_argument("--model", default="lenet_ref",
                   choices=["lenet_ref", "cifar_cnn", "resnet18", "resnet34",
                            "resnet50", "vgg16", "convnext_b"],
                   help="lenet_ref = the reference-parity trainer; the rest "
                        "route to the model-zoo trainer (train/zoo.py, "
                        "synthetic CIFAR-shape data, SGD+momentum)")
    p.add_argument("--conv-backend", default="xla",
                   choices=["xla", "pallas"],
                   help="zoo models only: conv kernel library — XLA convs "
                        "or the hand-written Pallas tapped-matmul kernels "
                        "(ops/pallas_conv.py)")
    p.add_argument("--lr", type=float, default=0.1,
                   help="zoo models only: SGD learning rate")
    p.add_argument("--lr-schedule", default="constant",
                   choices=["constant", "cosine"],
                   help="zoo models only: cosine decays over the full run "
                        "(epochs x steps); both honor --warmup-steps")
    p.add_argument("--warmup-steps", type=int, default=0,
                   help="zoo models only: linear LR warmup steps")
    p.add_argument("--augment", action="store_true",
                   help="zoo models only: on-device random crop + "
                        "horizontal flip (CIFAR recipe), traced into the "
                        "train step")
    # None sentinel: the autotuner's chosen plan may fill it; unset and
    # untuned resolves to 1 (the historical no-accumulation default).
    p.add_argument("--accum-steps", type=int, default=None,
                   help="zoo models only: gradient-accumulation "
                        "microbatches (default 1; --autotune may set it)")
    p.add_argument("--zoo-loader", default="device",
                   choices=["device", "native"],
                   help="zoo models only: batch source — on-device gathers "
                        "over the HBM-resident dataset, or the native C++ "
                        "prefetch ring (data/native.py; NumPy-twin fallback "
                        "without a toolchain)")
    p.add_argument("--loader", default=d.loader,
                   choices=["auto", "native", "numpy", "synthetic"])
    p.add_argument("--data-dir", default=None,
                   help="directory holding the four idx files "
                        "(defaults to the DataConfig paths)")
    p.add_argument("--epochs", type=int, default=t.epochs)
    # None sentinel: lenet_ref defaults to the strict-parity batch_size=1,
    # zoo models to minibatch 128 — an EXPLICIT value is never reinterpreted.
    p.add_argument("--batch-size", type=int, default=None)
    p.add_argument("--dt", type=float, default=t.dt,
                   help="SGD step (dt at Sequential/layer.h:12)")
    p.add_argument("--threshold", type=float, default=t.threshold,
                   help="early-stop err threshold (layer.h:13)")
    p.add_argument("--seed", type=int, default=t.seed)
    p.add_argument("--shuffle", action="store_true")
    p.add_argument("--prefetch", default=t.prefetch,
                   choices=["auto", "native", "off"])
    p.add_argument("--dtype", default=t.dtype,
                   choices=["float32", "bfloat16"],
                   help="compute dtype; bfloat16 = MXU-native mixed "
                        "precision (batch_size>1 only)")
    p.add_argument("--ops", default=t.ops,
                   choices=["reference", "pallas"],
                   help="kernel library: path A (jnp/lax, XLA-fused) or "
                        "path B (hand-written Pallas/Mosaic kernels ≙ the "
                        "CUDA backend; batch_size>1 only)")
    p.add_argument("--synthetic-train-count", type=int,
                   default=d.synthetic_train_count)
    p.add_argument("--synthetic-test-count", type=int,
                   default=d.synthetic_test_count)
    p.add_argument("--mesh-data", type=int, default=None, metavar="N",
                   help="data(-parallel) mesh axis size; setting either "
                        "mesh flag routes minibatch training over the "
                        "device mesh (≙ mpirun -np N, MPI/Main.cpp:44)")
    p.add_argument("--mesh-model", type=int, default=None, metavar="N",
                   help="model (intra-op) mesh axis size. lenet_ref: must "
                        "divide the 6 conv filters (legal: 1, 2, 3, 6). "
                        "zoo models: filter/channel GSPMD sharding "
                        "(parallel/zoo_sharding.py) composed with "
                        "--mesh-data DP on the 2-D mesh")
    p.add_argument("--comm-impl", default=None,
                   choices=["psum", "ring", "hierarchical"],
                   help="mesh runs: gradient-collective algorithm "
                        "(parallel/collectives.py) — monolithic psum, "
                        "bucketed ring reduce-scatter/all-gather over the "
                        "data axis, or the two-level hierarchical ring "
                        "over a (host, device) mesh (inter-host links "
                        "carry 1/n_dev of the payload; docs/collectives.md)"
                        ". Default: PCNN_COMM_IMPL, else the "
                        "historical implicit psum/GSPMD path")
    p.add_argument("--comm-bucket-mb", type=float, default=None, metavar="MB",
                   help="ring collective bucket size in MiB "
                        "(PCNN_COMM_BUCKET_BYTES; default 4)")
    p.add_argument("--comm-wire-dtype", default=None,
                   choices=["float32", "bfloat16"],
                   help="collective payload dtype on the wire; bfloat16 "
                        "halves ICI bytes, accumulation stays f32 "
                        "(PCNN_COMM_WIRE_DTYPE)")
    p.add_argument("--comm-hosts", type=int, default=None, metavar="N",
                   help="--comm-impl hierarchical: host-axis size of the "
                        "(host, device) mesh. Default (PCNN_COMM_HOSTS "
                        "unset): derive one host row per jax.distributed "
                        "process; an explicit N splits one process's "
                        "devices into N emulated hosts (CPU testing)")
    p.add_argument("--autotune", action="store_true",
                   help="zoo mesh runs: apply the cost report's chosen "
                        "parallelism plan (analysis/autotune.py; run "
                        "`python -m parallel_cnn_tpu tune` first) as the "
                        "base layer — explicit --comm-*/--fused-step/"
                        "--pipeline-*/--accum-steps knobs still win "
                        "[PCNN_AUTOTUNE]")
    p.add_argument("--autotune-report", default=None, metavar="PATH",
                   help="cost report the chosen plan is read from "
                        "(default analysis/cost_report.json) "
                        "[PCNN_AUTOTUNE_REPORT]")
    p.add_argument("--pipeline-stages", type=int, default=None, metavar="S",
                   help="zoo mesh runs: pipeline parallelism — partition "
                        "the model's layers over S stages of a (stage, "
                        "data) mesh and run the 1F1B microbatch schedule "
                        "(train/pipeline_schedule.py; --accum-steps is "
                        "the microbatch count M). Builds its own mesh "
                        "over all devices; drop --mesh-data/--mesh-model."
                        " S=1 is the degenerate single-stage pipeline "
                        "(bit-exact vs the flat data mesh) "
                        "[PCNN_PIPELINE_STAGES]")
    p.add_argument("--pipeline-split", default=None, metavar="B1,B2,..",
                   help="manual stage boundaries (layer indices, "
                        "stages-1 of them); default: balanced split from "
                        "the analysis/cost_model.py per-layer flops "
                        "tables [PCNN_PIPELINE_SPLIT]")
    p.add_argument("--pipeline-wire-dtype", default=None,
                   choices=["float32", "bfloat16"],
                   help="dtype of the inter-stage activation/cotangent "
                        "ppermute payload; accumulation stays f32 "
                        "[PCNN_PIPELINE_WIRE_DTYPE]")
    p.add_argument("--pipeline-act-dtype", default=None,
                   choices=["float32", "bfloat16"],
                   help="stage-compute activation dtype (params cast "
                        "per-layer, grads/loss stay f32) "
                        "[PCNN_PIPELINE_ACT_DTYPE]")
    p.add_argument("--fused-step", action="store_true",
                   help="fused training step (PCNN_FUSED_STEP): fused "
                        "pool→FC→softmax-CE loss tail, bf16 activations "
                        "over f32 masters with loss scaling, and — on "
                        "zoo mesh runs with --comm-impl ring — the "
                        "update-on-arrival fused optimizer "
                        "(ops/pallas_update.py)")
    p.add_argument("--act-dtype", default=None,
                   choices=["float32", "bfloat16"],
                   help="fused-step activation dtype (PCNN_ACT_DTYPE; "
                        "default bfloat16). Refines --fused-step only — "
                        "it never enables the fused path by itself")
    p.add_argument("--plan", default=None, metavar="PATH",
                   help="execution-plan file (docs/execution_plan.md; "
                        "written by `tune --report` or `plan show --save`): "
                        "fills every parallelism knob the env and explicit "
                        "flags left unset — flag beats env beats plan "
                        "[PCNN_PLAN]")
    p.add_argument("--replan", action="store_true",
                   help="allow resuming from a checkpoint whose recorded "
                        "plan fingerprint mismatches the live plan "
                        "(re-shard under the live plan instead of refusing "
                        "with PlanMismatchError)")
    p.add_argument("--checkpoint-dir", default=None,
                   help="save ckpt_<epoch>.npz per epoch; --resume restarts "
                        "from the latest")
    p.add_argument("--resume", action="store_true")
    r = ResilienceConfig()
    p.add_argument("--sentinel", default=r.policy,
                   choices=["off", "raise", "skip", "rollback"],
                   help="health-sentinel policy on a non-finite "
                        "loss/param: fail fast, discard the epoch, or "
                        "auto-rollback to the last-good state "
                        "(resilience/)")
    p.add_argument("--max-rollbacks", type=int, default=r.max_rollbacks,
                   help="bounded retry budget for --sentinel rollback")
    p.add_argument("--lr-backoff", type=float, default=r.lr_backoff,
                   help="LR multiplier applied per rollback "
                        "(lenet_ref path; 1.0 keeps the LR)")
    p.add_argument("--sentinel-every", type=int, default=r.check_every_steps,
                   metavar="N",
                   help="zoo models: also run the sentinel every N "
                        "optimizer steps (0 = epoch boundaries only; "
                        "each check is a host sync)")
    p.add_argument("--keep-checkpoints", type=int, default=r.ring_size,
                   metavar="N",
                   help="prune --checkpoint-dir to the newest N "
                        "checkpoints (0 = keep all)")
    p.add_argument("--chaos", default=None, metavar="SPEC",
                   help="fault injection for resilience testing: "
                        "nan@STEP poisons the update at optimizer step "
                        "STEP; kill@EPOCH / kill9@EPOCH delivers "
                        "SIGTERM / SIGKILL after epoch EPOCH's "
                        "checkpoint; resize@STEP:±K loses/adds K devices "
                        "at optimizer step STEP (needs --elastic); "
                        "kill-replica@SEQ kills the serving replica "
                        "holding dispatch batch SEQ (serve path); "
                        "slow-replica@SEQ:MS stalls it MS ms instead "
                        "(serve path); slow-worker@STEP:MS stalls the "
                        "training worker dispatching gradient step STEP "
                        "for MS ms — the async-training straggler; "
                        "slow-stage@STEP:MS stalls the pipeline trainer "
                        "MS ms at the step-STEP dispatch boundary — the "
                        "1F1B straggler (needs --pipeline-stages) "
                        "(resilience/chaos.py has the full grammar)")
    p.add_argument("--elastic", action="store_true",
                   help="elastic training (PCNN_ELASTIC): on a preemption "
                        "resize request, a chaos resize@, or a schedule "
                        "entry, quiesce at the microbatch boundary, "
                        "snapshot the ZeRO-3 state to a world-size-"
                        "independent view, re-mesh over the surviving "
                        "devices, reshard, and continue — no disk round "
                        "trip, no restart (resilience/elastic.py). "
                        "Requires the ZeRO-3 step (--fused-step path "
                        "with zero=3 + --comm-impl ring/hierarchical)")
    p.add_argument("--elastic-schedule", default=None, metavar="SPEC",
                   help="planned resizes 'STEP:WORLD[,STEP:WORLD…]' — "
                        "before optimizer step STEP resize the data "
                        "world to WORLD (implies --elastic) "
                        "[PCNN_ELASTIC_SCHEDULE]")
    p.add_argument("--elastic-scaling", default=None,
                   choices=["global", "per-device"],
                   help="batch/LR response to a resize: global keeps the "
                        "global batch + LR fixed (parity mode), "
                        "per-device keeps the per-device batch and "
                        "scales global batch + LR with the world "
                        "(throughput mode) [PCNN_ELASTIC_SCALING]")
    p.add_argument("--elastic-min-world", type=int, default=None,
                   metavar="N",
                   help="never shrink the data world below N devices; "
                        "deeper chaos losses are clamped and journaled "
                        "[PCNN_ELASTIC_MIN_WORLD]")
    p.add_argument("--async-mode", default=None,
                   choices=["off", "stale", "easgd"],
                   help="straggler-tolerant async data parallelism "
                        "(train/async_dp.py): stale = bounded-staleness "
                        "gradients with a hard barrier only at the bound, "
                        "easgd = independent local SGD with a periodic "
                        "elastic ρ-pull toward a bucket-sharded center; "
                        "off / unset = the bulk-synchronous ring. Async "
                        "modes trade bitwise sync parity for a bounded "
                        "loss delta [PCNN_ASYNC_MODE]")
    p.add_argument("--staleness-bound", type=int, default=None, metavar="S",
                   help="max optimizer-step age of the params a gradient "
                        "may be computed against (--async-mode stale; "
                        "0 = bit-exact with the sync ring) "
                        "[PCNN_ASYNC_STALENESS]")
    p.add_argument("--easgd-period", type=int, default=None, metavar="N",
                   help="local SGD steps between elastic-averaging rounds "
                        "(--async-mode easgd) [PCNN_ASYNC_EASGD_PERIOD]")
    p.add_argument("--easgd-rho", type=float, default=None, metavar="RHO",
                   help="elastic-averaging pull strength in (0, 1]: worker "
                        "and center each move ρ toward the other per round "
                        "(--async-mode easgd) [PCNN_ASYNC_EASGD_RHO]")
    p.add_argument("--metrics", default=None, metavar="PATH",
                   help="append JSONL metrics records to PATH")
    _add_obs_flags(p)
    p.add_argument("--profile", action="store_true",
                   help="lenet_ref: print the per-phase table (paper "
                        "Tables 4-8 shape); zoo models: write a "
                        "jax.profiler trace of 3 steady-state train steps "
                        "to zoo_xla_trace/ under --checkpoint-dir (or cwd)")
    return p


def _add_obs_flags(p: argparse.ArgumentParser) -> None:
    """The shared observability flag surface (train, zoo, serve, loadgen).

    Defaults keep observability fully OFF (the zero-cost no-op bundle);
    PCNN_OBS_* env sets the base and these flags override field-by-field
    (the comm-config layering)."""
    p.add_argument("--trace", action="store_true",
                   help="record host-side spans and the event journal; "
                        "writes a Perfetto-loadable Chrome trace JSON and "
                        "a JSONL journal under --trace-dir on exit "
                        "[PCNN_OBS_TRACE]")
    p.add_argument("--trace-dir", default=None, metavar="DIR",
                   help="artifact directory for the trace + journal "
                        "(implies --trace) [PCNN_OBS_DIR]")
    p.add_argument("--metrics-json", default=None, metavar="PATH",
                   help="write the metrics-registry JSON snapshot to PATH "
                        "on exit (works without --trace: metrics-only "
                        "mode) [PCNN_OBS_METRICS_JSON]")


def _obs_config_from_args(args: argparse.Namespace):
    """Optional[ObsConfig]: env first, flags override field-by-field;
    everything unset → None (observability off, Config.obs default)."""
    obs_cfg = ObsConfig.from_env()
    if args.trace or args.trace_dir or args.metrics_json:
        base = obs_cfg if obs_cfg is not None else ObsConfig(
            trace=bool(args.trace or args.trace_dir)
        )
        obs_cfg = dataclasses.replace(
            base,
            trace=base.trace or bool(args.trace or args.trace_dir),
            dir=args.trace_dir or base.dir,
            metrics_json=args.metrics_json or base.metrics_json,
        )
    return obs_cfg


def config_from_args(args: argparse.Namespace) -> Config:
    data = DataConfig(
        loader=args.loader,
        synthetic_train_count=args.synthetic_train_count,
        synthetic_test_count=args.synthetic_test_count,
    )
    if args.data_dir:
        data = DataConfig(
            train_images=os.path.join(args.data_dir, "train-images.idx3-ubyte"),
            train_labels=os.path.join(args.data_dir, "train-labels.idx1-ubyte"),
            test_images=os.path.join(args.data_dir, "t10k-images.idx3-ubyte"),
            test_labels=os.path.join(args.data_dir, "t10k-labels.idx1-ubyte"),
            loader=args.loader,
            synthetic_train_count=args.synthetic_train_count,
            synthetic_test_count=args.synthetic_test_count,
        )
    train = TrainConfig(
        dt=args.dt,
        threshold=args.threshold,
        epochs=args.epochs,
        batch_size=args.batch_size if args.batch_size is not None else 1,
        seed=args.seed,
        shuffle=args.shuffle,
        prefetch=args.prefetch,
        dtype=args.dtype,
        ops=args.ops,
    )
    # Either flag opts into mesh training; data=None means "all devices
    # not claimed by model" (resolved at mesh build, after the platform
    # override — no jax import may happen here). A bare `--mesh-model 1`
    # is the single-device default and does not activate the mesh.
    mesh = MeshConfig(data=args.mesh_data, model=args.mesh_model or 1)
    resilience = ResilienceConfig(
        policy=args.sentinel,
        max_rollbacks=args.max_rollbacks,
        lr_backoff=args.lr_backoff,
        ring_size=args.keep_checkpoints,
        check_every_steps=args.sentinel_every,
    )
    # Env first (PCNN_COMM_*), explicit flags override field-by-field;
    # all-defaults → comm=None, the historical implicit-collective path.
    comm = CommConfig.from_env()
    if (args.comm_impl is not None or args.comm_bucket_mb is not None
            or args.comm_wire_dtype is not None
            or args.comm_hosts is not None):
        base = comm or CommConfig()
        comm = dataclasses.replace(
            base,
            impl=args.comm_impl or base.impl,
            bucket_bytes=(int(args.comm_bucket_mb * 1024 * 1024)
                          if args.comm_bucket_mb is not None
                          else base.bucket_bytes),
            wire_dtype=args.comm_wire_dtype or base.wire_dtype,
            hosts=(args.comm_hosts if args.comm_hosts is not None
                   else base.hosts),
        )
    # Same env-then-flags layering for the fused step. --act-dtype only
    # REFINES an enabled fused path (acceptance: nothing but
    # --fused-step / PCNN_FUSED_STEP changes the default behavior).
    fused = FusedStepConfig.from_env()
    if args.fused_step:
        fused = fused or FusedStepConfig()
    if args.act_dtype is not None:
        if fused is None:
            raise SystemExit(
                "--act-dtype refines the fused step; enable it with "
                "--fused-step (or PCNN_FUSED_STEP=1) first"
            )
        fused = dataclasses.replace(fused, act_dtype=args.act_dtype)
    # Same layering for the pipeline: PCNN_PIPELINE_* env sets the base,
    # any --pipeline-* flag overrides field-by-field (and opts in).
    pipeline = PipelineConfig.from_env()
    if (args.pipeline_stages is not None
            or args.pipeline_split is not None
            or args.pipeline_wire_dtype is not None
            or args.pipeline_act_dtype is not None):
        base = pipeline or PipelineConfig()
        pipeline = dataclasses.replace(
            base,
            stages=(args.pipeline_stages
                    if args.pipeline_stages is not None else base.stages),
            split=(args.pipeline_split
                   if args.pipeline_split is not None else base.split),
            wire_dtype=args.pipeline_wire_dtype or base.wire_dtype,
            act_dtype=args.pipeline_act_dtype or base.act_dtype,
        )
    # Same layering for the elastic runtime: PCNN_ELASTIC* env sets the
    # base, any --elastic* flag overrides field-by-field (and opts in).
    elastic = ElasticConfig.from_env()
    if (args.elastic or args.elastic_schedule is not None
            or args.elastic_scaling is not None
            or args.elastic_min_world is not None):
        base = elastic or ElasticConfig()
        elastic = dataclasses.replace(
            base,
            enabled=True,
            schedule=(args.elastic_schedule
                      if args.elastic_schedule is not None
                      else base.schedule),
            scaling=args.elastic_scaling or base.scaling,
            min_world=(args.elastic_min_world
                       if args.elastic_min_world is not None
                       else base.min_world),
        )
    # And for the async data-parallel modes: PCNN_ASYNC_* env sets the
    # base, any --async*/--staleness*/--easgd* flag overrides (and opts
    # in).  --async-mode off explicitly pins the sync ring even when env
    # vars are set.
    async_dp = AsyncConfig.from_env()
    if (args.async_mode is not None
            or args.staleness_bound is not None
            or args.easgd_period is not None
            or args.easgd_rho is not None):
        base = async_dp or AsyncConfig()
        async_dp = dataclasses.replace(
            base,
            mode=args.async_mode or base.mode,
            staleness_bound=(args.staleness_bound
                             if args.staleness_bound is not None
                             else base.staleness_bound),
            easgd_period=(args.easgd_period
                          if args.easgd_period is not None
                          else base.easgd_period),
            easgd_rho=(args.easgd_rho
                       if args.easgd_rho is not None
                       else base.easgd_rho),
        )
    # --plan / PCNN_PLAN: a serialized ExecutionPlan (written by `tune
    # --report` or `plan show --save`) fills every parallelism knob the
    # env and flags left unset — the same precedence slot as the
    # autotuner's chosen plan (flag > env > plan > default), and knobs it
    # fills are provenance-labeled "autotune" by plan.build_plan.
    args._autotune_filled = set()
    plan_path = getattr(args, "plan", None) or plan_path_from_env()
    if plan_path:
        from parallel_cnn_tpu import plan as plan_lib

        try:
            eplan = plan_lib.load_plan(plan_path)
        except plan_lib.PlanError as exc:
            raise SystemExit(f"--plan: {exc}")
        if comm is None and eplan.comm_impl is not None:
            comm = eplan.comm_config()
            args._autotune_filled |= {
                "comm_impl", "bucket_bytes", "wire_dtype", "overlap",
                "hosts",
            }
        if fused is None and eplan.fused:
            fused = eplan.fused_config()
            args._autotune_filled |= {
                "fused", "fused_update", "fused_tail", "act_dtype", "zero",
            }
        if pipeline is None and (ppc := eplan.pipeline_config()) is not None:
            pipeline = ppc
            args._autotune_filled |= {
                "pipelined", "stages", "split", "pipe_wire_dtype",
                "pipe_act_dtype",
            }
        if args.accum_steps is None and eplan.accum > 1:
            args.accum_steps = eplan.accum
            args._autotune_filled.add("accum")
        if args.mesh_data is None and eplan.data is not None \
                and not (eplan.pipelined or eplan.stages > 1
                         or eplan.comm_impl == "hierarchical"):
            args.mesh_data = eplan.data
            mesh = dataclasses.replace(mesh, data=eplan.data)
            args._autotune_filled.add("data")
        if (args.mesh_model or 1) == 1 and eplan.model > 1:
            args.mesh_model = eplan.model
            mesh = dataclasses.replace(mesh, model=eplan.model)
            args._autotune_filled.add("model")
    # --autotune / PCNN_AUTOTUNE*: env sets the base, flags override —
    # then the report's chosen plan becomes the LOWEST layer: it fills
    # every parallelism subsystem (comm / fused / pipeline /
    # --accum-steps) the env and flags left untouched, so the tuner
    # proposes and explicit knobs always win (plan < env < flags).
    autotune = AutotuneConfig.from_env()
    if args.autotune or args.autotune_report is not None:
        base = autotune or AutotuneConfig()
        autotune = dataclasses.replace(
            base,
            enabled=True,
            report=args.autotune_report or base.report,
        )
    if autotune is not None and autotune.enabled:
        # analysis.autotune is import-light (no jax at module scope), so
        # this stays safe before the backend bootstrap.
        from parallel_cnn_tpu.analysis import autotune as autotune_lib

        try:
            plan, section = autotune_lib.load_chosen_plan(autotune.report)
        except ValueError as exc:  # NoFeasiblePlan / CostSchemaError
            raise SystemExit(f"--autotune: {exc}")
        n_host = int(section.get("n_host", 1) or 1)
        plan_comm, plan_fused, plan_pipe, plan_accum = \
            autotune_lib.plan_to_configs(plan, n_host=n_host)
        if comm is None and plan_comm is not None:
            comm = plan_comm
            args._autotune_filled |= {
                "comm_impl", "bucket_bytes", "wire_dtype", "overlap",
                "hosts",
            }
        if fused is None and plan_fused is not None:
            fused = plan_fused
            args._autotune_filled |= {
                "fused", "fused_update", "fused_tail", "act_dtype", "zero",
            }
        if pipeline is None and plan_pipe is not None:
            pipeline = plan_pipe
            args._autotune_filled |= {
                "pipelined", "stages", "split", "pipe_wire_dtype",
                "pipe_act_dtype",
            }
        if args.accum_steps is None:
            args.accum_steps = plan_accum
            if plan_accum and plan_accum > 1:
                args._autotune_filled.add("accum")
        # The (n_dev, n_host) shape the tuner scored is part of the plan,
        # so the mesh is filled like any other unset knob: a flat
        # single-stage plan activates pure DP over the scored device
        # count. Pipeline and hierarchical plans build their own meshes
        # in the zoo driver (which reads args.mesh_data), and the lenet
        # reference path has no mesh to activate.
        if (args.mesh_data is None and (args.mesh_model or 1) == 1
                and args.model != "lenet_ref"
                and (pipeline is None or pipeline.stages == 1)
                and (comm is None or comm.impl != "hierarchical")):
            plan_dev = int(section.get("n_dev", 0) or 0)
            if plan_dev > 1:
                args.mesh_data = plan_dev
                mesh = dataclasses.replace(mesh, data=plan_dev)
                args._autotune_filled.add("data")
    return Config(data=data, train=train, mesh=mesh,
                  resilience=resilience, comm=comm, fused=fused,
                  obs=_obs_config_from_args(args), elastic=elastic,
                  async_dp=async_dp, pipeline=pipeline,
                  autotune=autotune, model=args.model)


def build_serve_parser(cmd: str) -> argparse.ArgumentParser:
    """Shared flag surface for the `serve` and `loadgen` subcommands.

    Defaults come from ServeConfig.from_env() (the PCNN_SERVE_* table in
    the README), flags override field-by-field — same env-then-flags
    layering as the comm config."""
    sc = ServeConfig.from_env()
    p = argparse.ArgumentParser(
        prog=f"parallel_cnn_tpu {cmd}",
        description=(
            "inference serving (serve/): checkpoint → AOT-compiled, "
            "shape-bucketed, dynamically batched predict"
            if cmd == "serve"
            else "drive the serving stack with seeded traffic and report "
                 "latency percentiles / shed rate"
        ),
    )
    p.add_argument("--model", default=sc.model,
                   choices=["lenet_ref", "cifar_cnn", "resnet18", "resnet34",
                            "resnet50", "vgg16", "convnext_b"],
                   help="registry name (serve/registry.py); must match the "
                        "checkpoint's model [PCNN_SERVE_MODEL]")
    p.add_argument("--checkpoint", default=sc.checkpoint,
                   help="restore params (+ BN stats) from this .npz; both "
                        "lenet params-only and zoo full-state checkpoints "
                        "load (optimizer state ignored) "
                        "[PCNN_SERVE_CHECKPOINT]")
    p.add_argument("--conv-backend", default=sc.conv_backend,
                   choices=["xla", "pallas"],
                   help="resnet/vgg only: conv kernel library; pallas takes "
                        "the fused eval epilogues [PCNN_SERVE_CONV_BACKEND]")
    p.add_argument("--max-batch", type=int, default=sc.max_batch,
                   help="top shape bucket (power of two) "
                        "[PCNN_SERVE_MAX_BATCH]")
    p.add_argument("--max-wait-ms", type=float, default=sc.max_wait_ms,
                   help="batch coalescing window [PCNN_SERVE_MAX_WAIT_MS]")
    p.add_argument("--queue-depth", type=int, default=sc.queue_depth,
                   help="bounded request queue; full → typed Overloaded "
                        "shed [PCNN_SERVE_QUEUE_DEPTH]")
    p.add_argument("--replicas", type=int, default=sc.n_replicas,
                   help="engine replicas pinned round-robin across local "
                        "devices [PCNN_SERVE_REPLICAS]")
    p.add_argument("--deadline-ms", type=float, default=sc.deadline_ms,
                   help="per-request deadline budget (0 = none) "
                        "[PCNN_SERVE_DEADLINE_MS]")
    p.add_argument("--no-precompile", action="store_true",
                   help="compile buckets lazily on first use instead of at "
                        "startup [PCNN_SERVE_PRECOMPILE=0]")
    p.add_argument("--admission", action="store_true",
                   help="SLO admission control in front of the queue: "
                        "EWMA reject-early shedding + the graceful-"
                        "degradation ladder (serve/admission.py) "
                        "[PCNN_SERVE_ADMISSION]")
    p.add_argument("--slo-ms", type=float, default=sc.slo_ms,
                   help="completion-time objective: admission budget for "
                        "deadline-less requests, autoscaler p99 target, "
                        "default scenario p99 gate [PCNN_SERVE_SLO_MS]")
    p.add_argument("--autoscale", action="store_true",
                   help="replica autoscaler: grow/drain the pool between "
                        "--replicas and --max-replicas from windowed "
                        "telemetry (serve/autoscaler.py) "
                        "[PCNN_SERVE_AUTOSCALE]")
    p.add_argument("--max-replicas", type=int, default=sc.max_replicas,
                   help="autoscaler ceiling (0 = --replicas: no growth) "
                        "[PCNN_SERVE_MAX_REPLICAS]")
    p.add_argument("--window-s", type=float, default=sc.window_s,
                   help="decay time constant of the windowed telemetry "
                        "the autoscaler reads [PCNN_SERVE_WINDOW_S]")
    p.add_argument("--scenario", default=None,
                   choices=["diurnal", "flash-crowd", "slow-client",
                            "chaos-kill", "chaos-slow", "net-steady",
                            "net-slow-loris", "net-kill-endpoint",
                            "net-hot-swap-diurnal"],
                   help="drive a seeded SLO-gated traffic scenario "
                        "(serve/scenarios.py) instead of plain loadgen; "
                        "exit code reflects the p99/shed/conservation "
                        "gates (chaos-* scenarios need --chaos; net-* "
                        "scenarios need --listen and judge the wire tier "
                        "too)")
    p.add_argument("--chaos", default=None, metavar="SPEC",
                   help="serving fault injection: kill-replica@SEQ kills "
                        "the replica holding dispatch batch SEQ, "
                        "slow-replica@SEQ:MS stalls it MS ms, "
                        "kill-endpoint@SEQ kills the network endpoint at "
                        "wire request SEQ, slow-loris@SEQ:MS stalls a "
                        "client mid-request for MS ms "
                        "(resilience/chaos.py)")
    nc = NetConfig.from_env()
    g = p.add_argument_group(
        "network front door (serve/net.py; PCNN_SERVE_* in docs/api.md)")
    g.add_argument("--listen", action="store_true", default=nc.listen,
                   help="serve over a real TCP socket (NDJSON protocol) "
                        "instead of in-process submit; traffic/scenarios "
                        "are driven through the socket transport "
                        "[PCNN_SERVE_LISTEN]")
    g.add_argument("--listen-host", default=nc.host,
                   help="bind address for --listen [PCNN_SERVE_HOST]")
    g.add_argument("--listen-port", type=int, default=nc.port,
                   help="bind port for --listen; 0 = ephemeral (the "
                        "supervisor respawns on whatever was bound) "
                        "[PCNN_SERVE_PORT]")
    g.add_argument("--conn-deadline-ms", type=float,
                   default=nc.conn_deadline_ms,
                   help="per-connection read/write deadline: a socket "
                        "stalling mid-request past it is reaped as "
                        "expired (slow-loris defense); also the budget "
                        "of deadline-less wire requests "
                        "[PCNN_SERVE_CONN_DEADLINE_MS]")
    g.add_argument("--aot-cache-dir", default=nc.aot_cache_dir,
                   help="persistent on-disk AOT-executable cache: warm "
                        "cold-starts skip every bucket compile; torn or "
                        "fingerprint-mismatched entries recompile with a "
                        "typed AotCacheWarning "
                        "[PCNN_SERVE_AOT_CACHE_DIR]")
    g.add_argument("--supervise", action="store_true", default=nc.supervise,
                   help="respawn a killed endpoint on the same port with "
                        "bounded exponential backoff "
                        "(serve/supervisor.py) [PCNN_SERVE_SUPERVISE]")
    g.add_argument("--swap-checkpoint", default=None, metavar="PATH",
                   help="net-hot-swap-diurnal: checkpoint to hot-swap in "
                        "mid-peak (default: fresh seed+1 init)")
    p.add_argument("--requests", type=int,
                   default=64 if cmd == "serve" else 512,
                   help="traffic volume to drive through the stack")
    p.add_argument("--pattern", default="closed",
                   choices=["closed", "open"],
                   help="arrival pattern (serve/loadgen.py): closed-loop "
                        "concurrency or open-loop Poisson")
    p.add_argument("--concurrency", type=int, default=8,
                   help="closed loop: synchronous client count")
    p.add_argument("--rate", type=float, default=500.0,
                   help="open loop: offered Poisson rate, req/s")
    p.add_argument("--seed", type=int, default=0,
                   help="payload + arrival-process seed (replayable)")
    p.add_argument("--json", default=None, metavar="PATH",
                   help="write the report/telemetry snapshot as JSON")
    _add_obs_flags(p)
    return p


def _serve_config_from_args(args: argparse.Namespace) -> ServeConfig:
    env = ServeConfig.from_env()
    return ServeConfig(
        model=args.model,
        checkpoint=args.checkpoint,
        max_batch=args.max_batch,
        max_wait_ms=args.max_wait_ms,
        queue_depth=args.queue_depth,
        n_replicas=args.replicas,
        deadline_ms=args.deadline_ms,
        conv_backend=args.conv_backend,
        precompile=not args.no_precompile,
        admission=args.admission or env.admission,
        slo_ms=args.slo_ms,
        autoscale=args.autoscale or env.autoscale,
        max_replicas=args.max_replicas,
        window_s=args.window_s,
    )


def _net_config_from_args(args: argparse.Namespace) -> NetConfig:
    env = NetConfig.from_env()
    return NetConfig(
        listen=args.listen or env.listen,
        host=args.listen_host,
        port=args.listen_port,
        conn_deadline_ms=args.conn_deadline_ms,
        aot_cache_dir=args.aot_cache_dir,
        supervise=args.supervise or env.supervise,
        respawn_attempts=env.respawn_attempts,
        respawn_base_delay_s=env.respawn_base_delay_s,
        respawn_max_delay_s=env.respawn_max_delay_s,
    )


def _padded_bucket_parity(engine, handle, max_batch: int, seed: int) -> dict:
    """The padding contract on one padded bucket: n < b requests through
    the engine's AOT bucket executable must equal the same-bucket jit
    forward on the zero-padded batch.
    Returns {"n", "bucket", "max_abs_diff"}; 0.0 means bit-identical."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from parallel_cnn_tpu.serve import loadgen

    b = min(4, max_batch)
    n = max(b - 1, 1)
    xs = loadgen.make_samples(n, handle.in_shape, seed=seed)
    got = engine.predict(xs)
    pad = np.zeros((b - n, *handle.in_shape), np.float32)
    ref = np.asarray(jax.jit(
        lambda v: handle.forward(engine._params, engine._state, v)
    )(jnp.concatenate([jnp.asarray(xs), jnp.asarray(pad)])))[:n]
    return {"n": n, "bucket": b,
            "max_abs_diff": float(np.max(np.abs(got - ref)))}


def _failed_requests_rc(cmd: str, report, chaos) -> int:
    """1 when plain (non-scenario) traffic lost requests to errors: the
    batcher turns a device exception into failed futures, and a run whose
    requests failed must not exit 0. Under ``--chaos`` failures are the
    injected experiment, judged by the scenario gates instead."""
    if report.errors and chaos is None:
        print(f"[{cmd}] FAILED: {report.errors}/{report.requests} requests "
              f"raised (device or engine error)")
        return 1
    return 0


def _run_serve(cmd: str, argv: List[str]) -> int:
    """`serve` and `loadgen` subcommands.

    `serve` is the operator's view: restore the checkpoint, AOT-compile
    the bucket ladder (printing the compile-cache table), prove the
    padding/parity contract on one padded bucket, drive a short smoke of
    traffic, and print the telemetry snapshot. `loadgen` is the
    benchmarker's view: the same stack under a chosen arrival pattern,
    reporting client-side p50/p90/p99 and shed rate (optionally as JSON).
    By default the surface is in-process (batcher.submit); `--listen`
    puts the network front door (serve/net.py: NDJSON over TCP,
    per-connection deadlines, wire-tier conservation) in front of it
    and drives the same traffic through real sockets — optionally
    supervised (`--supervise`: crash-fast respawn on a stable port) and
    with the persistent AOT-executable cache (`--aot-cache-dir`)
    warming cold starts.
    """
    args = build_serve_parser(cmd).parse_args(argv)
    cfg = _serve_config_from_args(args)
    ncfg = _net_config_from_args(args)

    import json as json_mod
    import time

    from parallel_cnn_tpu.serve import (
        AutoScaler,
        get,
        loadgen,
        scenarios,
        serve_stack,
    )

    handle = get(cfg.model, conv_backend=cfg.conv_backend)
    obs_bundle = obs_lib.from_config(_obs_config_from_args(args), run=cmd)
    chaos = None
    if args.chaos:
        from parallel_cnn_tpu.resilience.chaos import ChaosMonkey

        chaos = ChaosMonkey.from_spec(args.chaos)
    t0 = time.perf_counter()
    pool, batcher = serve_stack(handle, cfg, obs=obs_bundle, chaos=chaos,
                                cache_dir=ncfg.aot_cache_dir)
    startup = time.perf_counter() - t0
    if obs_bundle.enabled:
        # Exposition parity: the ServeStats counters feed the registry's
        # Prometheus/JSON snapshots without changing their semantics.
        batcher.stats.attach_registry(obs_bundle.registry)
        if batcher.admission is not None:
            batcher.admission.attach_registry(obs_bundle.registry)
    src = cfg.checkpoint or "fresh init (no --checkpoint)"
    print(f"[serve] model={cfg.model} params from {src}")
    print(f"[serve] replicas={cfg.n_replicas} on "
          f"{[str(e.device) for e in pool.engines]}")
    if cfg.admission:
        print(f"[serve] admission control on (SLO {cfg.slo_ms:g} ms)")
    scaler = None
    if cfg.autoscale:
        scaler = AutoScaler(
            pool, batcher,
            min_replicas=1,
            max_replicas=cfg.effective_max_replicas,
            slo_ms=cfg.slo_ms,
            obs=obs_bundle,
        )
        if obs_bundle.enabled:
            scaler.attach_registry(obs_bundle.registry)
        scaler.start()
        print(f"[serve] autoscaler on "
              f"(1..{cfg.effective_max_replicas} replicas, "
              f"p99 target {cfg.slo_ms:g} ms)")
    if cfg.precompile:
        buckets = pool.engines[0].stats.compile_seconds
        table = ", ".join(f"b{b}: {s * 1e3:.0f} ms"
                          for b, s in sorted(buckets.items()))
        print(f"[serve] AOT bucket ladder compiled in {startup:.2f}s "
              f"({table})")
    if ncfg.aot_cache_dir:
        hits = sum(e.stats.aot_cache_hits for e in pool.engines)
        misses = sum(e.stats.aot_cache_misses for e in pool.engines)
        corrupt = sum(e.stats.aot_cache_corrupt for e in pool.engines)
        print(f"[serve] AOT disk cache {ncfg.aot_cache_dir}: "
              f"{hits} hits, {misses} misses, {corrupt} corrupt "
              f"(warm start = zero compiles)")

    with batcher:
        rc = 0
        parity = None
        if cmd == "serve":
            parity = _padded_bucket_parity(
                pool.engines[0], handle, cfg.max_batch, args.seed
            )
            # `!= 0.0` (not `> 0`) so a NaN diff is a mismatch too.
            mismatch = parity["max_abs_diff"] != 0.0
            verdict = (f"MISMATCH (max |Δ| {parity['max_abs_diff']:.2e})"
                       if mismatch else "bit-identical")
            print(f"[serve] padded-bucket parity "
                  f"(n={parity['n']}→b{parity['bucket']}): {verdict}")
            rc = int(mismatch)

        sup = None
        endpoint = None
        wire = None
        if args.scenario and args.scenario.startswith("net-") \
                and not ncfg.listen:
            print(f"[{cmd}] scenario {args.scenario} needs --listen "
                  f"(it judges the wire tier)")
            return 2
        if ncfg.listen:
            from parallel_cnn_tpu.resilience.retry import RetryPolicy
            from parallel_cnn_tpu.serve.net import NetServer
            from parallel_cnn_tpu.serve.supervisor import Supervisor
            from parallel_cnn_tpu.serve.telemetry import WireStats

            wire = WireStats()
            if obs_bundle.enabled:
                wire.attach_registry(obs_bundle.registry)
            # A kill-endpoint monkey arms the SERVER (first incarnation
            # only — a respawn must not replay the death); a slow-loris
            # monkey arms the CLIENT side of the socket transport.
            server_chaos = (
                chaos if chaos is not None
                and chaos.kill_endpoint_seq is not None else None
            )
            client_chaos = (
                chaos if chaos is not None
                and chaos.slow_loris is not None else None
            )
            armed = [server_chaos]

            def _factory(port: int, seq_start: int):
                m = armed.pop(0) if armed else None
                return NetServer(
                    batcher, host=ncfg.host, port=port,
                    conn_deadline_ms=ncfg.conn_deadline_ms, wire=wire,
                    chaos=m, obs=obs_bundle, seq_start=seq_start,
                ).start()

            if ncfg.supervise:
                sup = Supervisor(
                    _factory,
                    policy=RetryPolicy(
                        attempts=ncfg.respawn_attempts,
                        base_delay=ncfg.respawn_base_delay_s,
                        max_delay=ncfg.respawn_max_delay_s,
                        seed=args.seed,
                    ),
                    obs=obs_bundle, port=ncfg.port,
                ).start()
                endpoint = sup.server
            else:
                endpoint = _factory(ncfg.port, 0)
            print(f"[{cmd}] listening on "
                  f"{endpoint.host}:{endpoint.port} "
                  f"(conn deadline {ncfg.conn_deadline_ms:g} ms"
                  + (", supervised" if sup is not None else "") + ")")
        if args.scenario and args.scenario.startswith("net-"):
            swap_params = swap_state = None
            if args.scenario == "net-hot-swap-diurnal":
                from parallel_cnn_tpu.serve.engine import load_or_init

                swap_params, swap_state = load_or_init(
                    handle, args.swap_checkpoint, seed=args.seed + 1,
                )
            report = scenarios.run_net(
                args.scenario, batcher, wire=wire,
                supervisor=sup, server=endpoint, chaos=client_chaos,
                swap_params=swap_params, swap_state=swap_state,
                obs=obs_bundle, seed=args.seed,
            )
            gates = report.gates()
            verdict = "PASS" if report.passed else "FAIL"
            p99 = report.p99_ms
            print(f"[{cmd}] scenario {report.name}: "
                  f"{report.completed}/{report.requests} ok, "
                  f"shed rate {report.shed_rate:.3f}, "
                  f"p99 {p99:.1f} ms" if p99 is not None else
                  f"[{cmd}] scenario {report.name}: no completions")
            print(f"[{cmd}] gates {verdict}: " + ", ".join(
                f"{k}={'ok' if v else 'TRIPPED'}"
                for k, v in gates.items()
            ))
            rc = max(rc, 0 if report.passed else 1)
        elif args.scenario:
            report = scenarios.run(
                args.scenario, batcher,
                seed=args.seed,
                deadline_ms=args.deadline_ms or None,
            )
            gates = report.gates()
            verdict = "PASS" if report.passed else "FAIL"
            p99 = report.p99_ms
            print(f"[{cmd}] scenario {report.name}: "
                  f"{report.completed}/{report.requests} ok, "
                  f"shed rate {report.shed_rate:.3f}, "
                  f"p99 {p99:.1f} ms" if p99 is not None else
                  f"[{cmd}] scenario {report.name}: no completions")
            print(f"[{cmd}] gates {verdict}: " + ", ".join(
                f"{k}={'ok' if v else 'TRIPPED'}"
                for k, v in gates.items()
            ))
            rc = max(rc, 0 if report.passed else 1)
        elif ncfg.listen:
            report = loadgen.run_closed_loop_net(
                endpoint.address,
                loadgen.make_samples(
                    min(args.requests, 64), handle.in_shape,
                    seed=args.seed,
                ),
                n_requests=args.requests,
                concurrency=args.concurrency,
                deadline_ms=args.deadline_ms or None,
                seed=args.seed,
                chaos=client_chaos,
            )
            print(f"[{cmd}] closed-net-loop: "
                  f"{report.completed}/{report.requests} ok, "
                  f"{report.throughput:.1f} req/s over the wire, "
                  f"shed rate {report.shed_rate:.3f}")
            lat = report.latency.summary(scale=1e3)
            if lat.get("count"):
                print(f"[{cmd}] latency p50 {lat['p50']:.2f} ms, "
                      f"p90 {lat['p90']:.2f} ms, p99 {lat['p99']:.2f} ms")
            rc = max(rc, _failed_requests_rc(cmd, report, chaos))
        else:
            report = loadgen.run(
                batcher,
                pattern=args.pattern,
                n_requests=args.requests,
                concurrency=args.concurrency,
                rate=args.rate,
                deadline_ms=args.deadline_ms or None,
                seed=args.seed,
            )
            print(f"[{cmd}] {args.pattern}-loop: "
                  f"{report.completed}/{report.requests} ok, "
                  f"{report.throughput:.1f} req/s, "
                  f"shed rate {report.shed_rate:.3f}")
            lat = report.latency.summary(scale=1e3)
            if lat.get("count"):
                print(f"[{cmd}] latency p50 {lat['p50']:.2f} ms, "
                      f"p90 {lat['p90']:.2f} ms, p99 {lat['p99']:.2f} ms")
            rc = max(rc, _failed_requests_rc(cmd, report, chaos))
        if ncfg.listen:
            (sup if sup is not None else endpoint).close()
            w = wire.snapshot()
            print(f"[{cmd}] wire: {w['submitted']} submitted = "
                  f"{w['completed']} completed + {w['shed']} shed + "
                  f"{w['expired']} expired + {w['failed']} failed "
                  f"({'balanced' if wire.balanced() else 'IMBALANCED'}; "
                  f"{w['reaped']} reaped, "
                  f"{w['endpoint_deaths']} endpoint deaths"
                  + (f", {sup.respawns} respawns" if sup is not None
                     else "") + ")")
        if scaler is not None:
            scaler.close()
            snap = scaler.snapshot()
            print(f"[{cmd}] autoscaler: {snap['scale_ups']} up, "
                  f"{snap['scale_downs']} down, "
                  f"{snap['routable']} replicas routable")
        print(batcher.stats.render())
        if args.json:
            out = {"config": dataclasses.asdict(cfg),
                   "report": report.to_dict(),
                   "telemetry": batcher.stats.snapshot(),
                   "window": batcher.stats.window_snapshot()}
            if parity is not None:
                out["parity"] = parity
            out["replicas"] = [
                {"device": str(e.device), "device_id": int(e.device.id),
                 "platform": e.device.platform}
                for e in pool.engines
            ]
            if batcher.admission is not None:
                out["admission"] = batcher.admission.snapshot()
            if scaler is not None:
                out["autoscaler"] = scaler.snapshot()
            if wire is not None:
                out["wire"] = wire.snapshot()
            with open(args.json, "w") as f:
                json_mod.dump(out, f, indent=2)
            print(f"[{cmd}] report written to {args.json}")
    for kind, path in obs_bundle.finish().items():
        print(f"[{cmd}] {kind} written to {path}")
    return rc


def _run_check(argv: List[str]) -> int:
    """`python -m parallel_cnn_tpu check` — graftcheck static analysis.

    A host-side lint pass: it never needs (or touches) an accelerator,
    so CPU is forced unconditionally, with 8 virtual devices so the
    mesh-shaped jaxpr analyzers can trace the real collective schedules.
    Both knobs must land before jax initializes a backend — hence the
    env write here, first thing, mirroring tests/conftest.py (the
    ambient plugin snapshots XLA_FLAGS at import)."""
    flags = os.environ.get("XLA_FLAGS", "")  # graftcheck: disable=env-outside-config -- backend bootstrap, must precede jax import; not a tunable knob
    if "xla_force_host_platform_device_count" not in flags:
        # graftcheck: disable=env-outside-config -- backend bootstrap, must precede jax import; not a tunable knob
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()

    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
    except RuntimeError:
        pass  # backend already initialized (embedded call): analyze as-is

    # PCNN_CHECK_COST=1 turns on the cost/sharding families for every
    # check invocation — the CI spelling of `check --cost` (docs/api.md).
    # graftcheck: disable=env-outside-config -- check-dispatch knob: must act before checker argparse, config.py is not imported on this path
    if os.environ.get("PCNN_CHECK_COST", "").lower() in ("1", "true") \
            and "--cost" not in argv:
        argv = ["--cost"] + argv

    from parallel_cnn_tpu.analysis import checker

    return checker.main(argv)


def _run_tune(argv: List[str]) -> int:
    """`python -m parallel_cnn_tpu tune` — rank the parallelism-plan
    space against the analytic roofline and write the chosen plan into
    the cost report (docs/autotuning.md).

    Search is pure closed-form arithmetic; jax is needed only to profile
    the model (param/flop/activation tables), so CPU is forced with 8
    virtual devices exactly like `check` — the tuner must run on a
    devbox, not burn accelerator time."""
    flags = os.environ.get("XLA_FLAGS", "")  # graftcheck: disable=env-outside-config -- backend bootstrap, must precede jax import; not a tunable knob
    if "xla_force_host_platform_device_count" not in flags:
        # graftcheck: disable=env-outside-config -- backend bootstrap, must precede jax import; not a tunable knob
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()

    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
    except RuntimeError:
        pass  # backend already initialized (embedded call): profile as-is

    from parallel_cnn_tpu.analysis import autotune as autotune_lib
    from parallel_cnn_tpu.analysis import hw_profiles

    at = AutotuneConfig.from_env() or AutotuneConfig()
    p = argparse.ArgumentParser(
        prog="parallel_cnn_tpu tune",
        description="cost-model plan autotuner (analysis/autotune.py)",
    )
    p.add_argument("--model", default="cifar_cnn",
                   choices=["cifar_cnn", "resnet18", "resnet34", "resnet50",
                            "vgg16", "convnext_b"],
                   help="zoo model the plan space is profiled for")
    p.add_argument("--global-batch", type=int, default=128, metavar="B",
                   help="global batch size every plan must serve")
    p.add_argument("--devices", type=int, default=None, metavar="N",
                   help="device count the plans are laid out over "
                        "(default: all local devices)")
    p.add_argument("--hosts", type=int, default=1, metavar="H",
                   help="emulated host count (hierarchical plans need "
                        ">= 2; flat rings spanning hosts are charged at "
                        "DCN speed)")
    p.add_argument("--hw", default=at.hw, metavar="NAME",
                   help="hardware profile scored against "
                        f"({', '.join(sorted(hw_profiles.PROFILES))}) "
                        "[PCNN_HW_PROFILE]")
    p.add_argument("--hbm-budget-mb", type=float, default=None, metavar="MB",
                   help="peak-HBM budget per device; default: the "
                        "profile's capacity [PCNN_AUTOTUNE_HBM_BUDGET]")
    p.add_argument("--top-k", type=int, default=at.top_k,
                   help="ranked plans kept in the report "
                        "[PCNN_AUTOTUNE_TOPK]")
    p.add_argument("--report", default=at.report, metavar="PATH",
                   help="cost report the autotune section is merged into; "
                        "default: the shipped analysis/cost_report.json "
                        "[PCNN_AUTOTUNE_REPORT]")
    p.add_argument("--no-prune", action="store_true",
                   help="score every feasible plan (disable the "
                        "admissible compute-lower-bound prune; results "
                        "are identical by construction — debug only)")
    args = p.parse_args(argv)

    from parallel_cnn_tpu.nn import cifar, convnext, resnet, vgg

    factories = {
        "cifar_cnn": lambda: cifar.cifar_cnn(),
        "resnet18": lambda: resnet.resnet18(10, cifar_stem=True),
        "resnet34": lambda: resnet.resnet34(10, cifar_stem=True),
        "resnet50": lambda: resnet.resnet50(10, cifar_stem=True),
        "vgg16": lambda: vgg.vgg16(10),
        "convnext_b": lambda: convnext.convnext_b(10),
    }
    model = factories[args.model]()
    mp = autotune_lib.profile_module(model, cifar.IN_SHAPE, name=args.model)
    hw = hw_profiles.get_profile(args.hw)
    n_dev = args.devices or jax.local_device_count()
    budget = (int(args.hbm_budget_mb * 1024 * 1024)
              if args.hbm_budget_mb is not None else at.hbm_budget)
    try:
        result = autotune_lib.search(
            mp, hw=hw, global_batch=args.global_batch, n_dev=n_dev,
            n_host=args.hosts, hbm_budget=budget, top_k=args.top_k,
            prune=not args.no_prune,
        )
    except autotune_lib.NoFeasiblePlan as exc:
        print(f"tune: {exc}")
        return 1
    print(autotune_lib.format_table(result))
    written = autotune_lib.write_section(
        args.report, autotune_lib.build_section(result))
    # Embed the chosen plan as a first-class ExecutionPlan document so
    # the report itself is a --plan file — the lossless tune → train
    # artifact hand-off (docs/execution_plan.md).
    import json as json_mod

    from parallel_cnn_tpu import plan as plan_lib

    chosen, section = autotune_lib.load_chosen_plan(written)
    eplan = chosen.to_execution_plan(
        n_host=int(section.get("n_host", 1) or 1),
        n_dev=int(section.get("n_dev", 0) or 0) or None,
    )
    with open(written) as f:
        doc = json_mod.load(f)
    doc["plan"] = eplan.to_json_dict()
    with open(written, "w") as f:
        json_mod.dump(doc, f, sort_keys=True, indent=2)
        f.write("\n")
    print(f"tune: chosen plan written to {written} "
          f"(plan {eplan.fingerprint()}; run with --plan {written})")
    return 0


def _run_plan(argv: List[str]) -> int:
    """`python -m parallel_cnn_tpu plan show|diff` — the resolved
    ExecutionPlan as a first-class object (docs/execution_plan.md).

    `plan show [train flags] [--save PATH]` resolves exactly the plan a
    train run with those flags would execute (flag > env > plan-file >
    default) and prints it one knob per line with per-knob provenance;
    `plan diff A B` prints a field-by-field diff of two plan files.
    Both are pure host-side paths: no jax, no backend, no devices."""
    from parallel_cnn_tpu import plan as plan_lib

    if not argv or argv[0] not in ("show", "diff"):
        print("usage: parallel_cnn_tpu plan show [train flags] "
              "[--save PATH]\n"
              "       parallel_cnn_tpu plan diff PLAN_A PLAN_B")
        return 2
    if argv[0] == "diff":
        if len(argv) != 3:
            print("usage: parallel_cnn_tpu plan diff PLAN_A PLAN_B")
            return 2
        try:
            a = plan_lib.load_plan(argv[1])
            b = plan_lib.load_plan(argv[2])
        except plan_lib.PlanError as exc:
            print(f"plan diff: {exc}")
            return 2
        out = plan_lib.diff_plans(a, b)
        if not out:
            print(f"plans identical ({a.fingerprint()})")
            return 0
        print(out)
        return 1
    p = build_parser()
    p.add_argument("--save", default=None, metavar="PATH",
                   help="also write the resolved plan as a --plan-loadable "
                        "plan.json")
    args = p.parse_args(argv[1:])
    cfg = config_from_args(args)
    plan = plan_lib.build_plan(cfg, args)
    verdict = ""
    try:
        plan.validate()
    except plan_lib.PlanError as exc:
        verdict = f"\nILLEGAL: {exc}"
    if args.save:
        plan_lib.save_plan(args.save, plan)
    print(plan_lib.format_plan(plan, title=f"resolved plan ({cfg.model})")
          + verdict)
    if args.save:
        print(f"plan written to {args.save}")
    return 1 if verdict else 0


def main(argv: Optional[List[str]] = None) -> int:
    import sys

    raw = list(sys.argv[1:] if argv is None else argv)
    # Subcommand dispatch rides in front of the historical flat trainer
    # CLI: `python -m parallel_cnn_tpu serve|loadgen …` routes to the
    # serving stack, anything else keeps the original flag surface
    # unchanged (no retrofit of subparsers onto existing automation).
    if raw and raw[0] == "check":
        return _run_check(raw[1:])
    if raw and raw[0] == "tune":
        return _run_tune(raw[1:])
    if raw and raw[0] == "plan":
        return _run_plan(raw[1:])
    # Everything below compiles for the device: place the persistent
    # compile cache before anything can trigger a compilation.
    from parallel_cnn_tpu.utils.backend import enable_compile_cache

    enable_compile_cache()
    if raw and raw[0] in ("serve", "loadgen"):
        return _run_serve(raw[0], raw[1:])
    args = build_parser().parse_args(raw)
    cfg = config_from_args(args)

    # Surface the data pipeline's INFO-level evidence (e.g. the real-MNIST
    # integrity report) in the driver; library embedders keep their own
    # logging policy and a clean stdout.
    import logging

    logging.getLogger("parallel_cnn_tpu").setLevel(logging.INFO)
    if not logging.getLogger().handlers:
        logging.basicConfig(
            level=logging.INFO, format="%(levelname)s %(name)s: %(message)s"
        )

    import jax
    import jax.numpy as jnp

    from parallel_cnn_tpu.data import pipeline
    from parallel_cnn_tpu.models import lenet_ref
    from parallel_cnn_tpu.parallel import distributed
    from parallel_cnn_tpu.resilience import ChaosMonkey, CheckpointRing
    from parallel_cnn_tpu.resilience import preempt
    from parallel_cnn_tpu.train import checkpoint, trainer
    from parallel_cnn_tpu.utils.metrics import MetricsLogger, throughput
    from parallel_cnn_tpu.utils import profiling

    distributed.initialize()  # env-configured multi-host; no-op otherwise

    if cfg.model != "lenet_ref":
        if cfg.async_dp is not None and cfg.async_dp.enabled:
            raise SystemExit(
                "--async-mode drives the lenet_ref virtual-clock harness "
                "(train/async_dp.py); zoo models stay bulk-synchronous — "
                "drop --async-mode or use --model lenet_ref"
            )
        return _run_zoo(args, cfg)
    if cfg.elastic is not None and cfg.elastic.enabled:
        # The flat per-sample trainer has no sharded optimizer state to
        # re-lay-out; only the zoo ZeRO-3 step can resize in flight.
        raise SystemExit(
            "--elastic needs the zoo ZeRO-3 trainer: pick a zoo --model "
            "(e.g. cifar_cnn) with --mesh-data, --comm-impl ring and "
            "--fused-step"
        )
    train_ds, test_ds = pipeline.load_train_test(cfg.data)

    chaos = ChaosMonkey.from_spec(args.chaos) if args.chaos else None
    if cfg.async_dp is not None and cfg.async_dp.enabled:
        return _run_async_lenet(args, cfg, train_ds, test_ds, chaos)
    ring = None
    if args.checkpoint_dir:
        ring = CheckpointRing(
            args.checkpoint_dir, keep=cfg.resilience.ring_size
        )

    params = None
    start_epoch = 0
    error_history: List[float] = []
    if args.checkpoint_dir and args.resume:
        path = checkpoint.latest(args.checkpoint_dir)
        if path:
            like = lenet_ref.init(jax.random.key(cfg.train.seed))
            params, state = checkpoint.restore(path, like)
            start_epoch = state.epoch
            error_history = list(state.epoch_errors)
            print(f"resumed from {path} (epoch {start_epoch})")

    metrics = MetricsLogger(path=args.metrics) if args.metrics else None
    obs_bundle = obs_lib.from_config(cfg.obs, run="train")
    remaining = max(cfg.train.epochs - start_epoch, 0)
    run_cfg = cfg.replace(
        train=dataclasses.replace(cfg.train, epochs=remaining)
    )

    def on_epoch(epoch: int, epoch_params, err: float) -> None:
        """Mid-training persistence: fires after every epoch, so a killed
        run resumes from its last finished epoch, not from nothing."""
        error_history.append(err)
        if metrics:
            metrics.record(event="epoch", epoch=epoch, error=err)
        if ring is not None:
            ring.save(
                epoch,
                epoch_params,
                checkpoint.TrainState(
                    epoch=epoch, epoch_errors=list(error_history)
                ),
            )

    # SIGTERM/SIGINT stop training at the next epoch boundary with the
    # checkpoint already flushed (resilience/preempt) — the cloud
    # preemption contract the reference lacks.
    with preempt.PreemptionGuard() as guard:
        result = trainer.learn(
            run_cfg,
            train_ds,
            params=params,
            epoch_offset=start_epoch,
            epoch_callback=on_epoch,
            chaos=chaos,
            ring=ring,
            obs=obs_bundle,
        )

    for kind, path in obs_bundle.finish().items():
        print(f"[obs] {kind} written to {path}")
    if result.preempted or guard.preempted:
        if metrics:
            metrics.record(
                event="preempted",
                epoch=start_epoch + len(result.epoch_errors),
            )
            metrics.close()
        print("preempted: checkpoint flushed; continue with --resume")
        return 0

    rate = trainer.test(result.params, test_ds)
    if metrics:
        n_images = len(train_ds) * max(len(result.epoch_errors), 1)
        metrics.record(
            event="final",
            error_rate=rate,
            seconds=result.seconds,
            images_per_sec=throughput(n_images, result.seconds),
        )
        metrics.close()

    if args.profile:
        bsz = max(cfg.train.batch_size, 256)
        xs = jnp.asarray(train_ds.images[:bsz])
        ys = jnp.asarray(train_ds.labels[:bsz])
        phases = profiling.profile_phases(result.params, xs, ys)
        print(profiling.report(phases, n_images=xs.shape[0]))

    return 0


def _run_async_lenet(args, cfg: Config, train_ds, test_ds, chaos) -> int:
    """Async data-parallel driver branch (--async-mode stale|easgd).

    Runs the deterministic virtual-clock harness (train/async_dp.py):
    N logical workers, each resident on its own shard of the training
    set, real jitted gradients, virtual step durations — so throughput
    and straggler tolerance replay exactly, chaos ``slow-worker@`` and
    all.  One optimizer step consumes every worker's resident microbatch
    once, so ``--epochs`` counts server steps (stale) / per-worker local
    steps (easgd)."""
    import jax
    import jax.numpy as jnp

    from parallel_cnn_tpu.models import lenet_ref
    from parallel_cnn_tpu.resilience.sentinel import Sentinel
    from parallel_cnn_tpu.train import async_dp, trainer

    acfg = cfg.async_dp
    w, b = acfg.workers, cfg.train.batch_size
    if len(train_ds) < w * b:
        raise SystemExit(
            f"async harness wants {w} workers x {b} images, dataset has "
            f"{len(train_ds)}"
        )
    xs = jnp.asarray(train_ds.images[: w * b]).reshape(w, b, 28, 28)
    ys = jnp.asarray(train_ds.labels[: w * b]).reshape(w, b)
    params = lenet_ref.init(jax.random.key(cfg.train.seed))
    obs_bundle = obs_lib.from_config(cfg.obs, run="train_async")

    result = async_dp.run_async(
        params, xs, ys, cfg=acfg, dt=cfg.train.dt,
        max_server_steps=cfg.train.epochs, chaos=chaos,
        sentinel=Sentinel(), obs=obs_bundle,
    )
    for kind, path in obs_bundle.finish().items():
        print(f"[obs] {kind} written to {path}")
    print(
        f"async mode={acfg.mode} steps={result.server_steps} "
        f"microbatches={result.microbatches} "
        f"virtual_ms={result.virtual_ms:.0f} "
        f"max_staleness={result.ledger.max_staleness()} "
        f"stragglers={result.stragglers} dropped={result.dropped} "
        f"easgd_rounds={result.easgd_rounds}"
    )
    rate = trainer.test(result.params, test_ds)
    print(f"async test error rate: {rate:.4f}")
    return 0


def _run_zoo(args: argparse.Namespace, cfg: Config) -> int:
    """Zoo-model driver branch (--model {cifar_cnn,resnet18,34,50,vgg16,
    convnext_b}).

    Trains on the deterministic synthetic CIFAR-shape stand-in (this
    environment cannot fetch CIFAR/ImageNet — BASELINE.md), with the
    production surface zoo.train provides: per-epoch eval, atomic
    checkpoint/resume of the FULL state, JSONL metrics, GSPMD DP over a
    --mesh-data mesh (plus filter sharding with --mesh-model N>1), and
    --conv-backend pallas for the native kernels.
    """
    from parallel_cnn_tpu import plan as plan_lib
    from parallel_cnn_tpu.data import synthetic
    from parallel_cnn_tpu.nn import cifar, convnext, resnet, vgg
    from parallel_cnn_tpu.resilience import ChaosMonkey
    from parallel_cnn_tpu.resilience import preempt
    from parallel_cnn_tpu.train import zoo
    from parallel_cnn_tpu.utils.metrics import MetricsLogger

    factories = {
        "cifar_cnn": lambda: cifar.cifar_cnn(),
        "resnet18": lambda: resnet.resnet18(
            10, cifar_stem=True, conv_backend=args.conv_backend
        ),
        "resnet34": lambda: resnet.resnet34(
            10, cifar_stem=True, conv_backend=args.conv_backend
        ),
        "resnet50": lambda: resnet.resnet50(
            10, cifar_stem=True, conv_backend=args.conv_backend
        ),
        "vgg16": lambda: vgg.vgg16(10, conv_backend=args.conv_backend),
        "convnext_b": lambda: convnext.convnext_b(10),
    }
    if cfg.model in ("cifar_cnn", "convnext_b") and args.conv_backend != "xla":
        raise SystemExit(
            "--conv-backend pallas applies to the resnet/vgg models"
        )
    model = factories[cfg.model]()

    imgs, labels = synthetic.make_image_dataset(
        args.synthetic_train_count, seed=cfg.data.synthetic_seed
    )
    ev_imgs, ev_labels = synthetic.make_image_dataset(
        args.synthetic_test_count, seed=cfg.data.synthetic_seed + 1
    )

    # ONE resolution + legality + mesh-construction site: the three
    # historical mesh branches (flat ring / hierarchical / pipeline) and
    # their ad-hoc knob guards all live in plan.build_plan / validate /
    # make_mesh now (docs/execution_plan.md has the legality matrix).
    try:
        eplan = plan_lib.build_plan(cfg, args).validate()
    except plan_lib.PlanError as exc:
        raise SystemExit(str(exc))
    mesh = eplan.make_mesh()
    model_axis = eplan.model > 1
    if mesh is not None:
        kind = ("pipeline" if eplan.pipelined or eplan.stages > 1
                else "hierarchical" if eplan.comm_impl == "hierarchical"
                else None)
        print(f"mesh: {dict(mesh.shape)}" + (f" ({kind})" if kind else ""))

    metrics = MetricsLogger(path=args.metrics) if args.metrics else None
    # batch-size sentinel: zoo default is minibatch 128; an explicit 1 is
    # a config error (per-sample SGD is the lenet_ref parity mode).
    if args.batch_size is None:
        batch = 128
    elif args.batch_size == 1:
        raise SystemExit("zoo models train minibatch; use --batch-size > 1")
    else:
        batch = args.batch_size
    chaos = ChaosMonkey.from_spec(args.chaos) if args.chaos else None
    obs_bundle = obs_lib.from_config(cfg.obs, run="zoo")
    with preempt.PreemptionGuard() as guard:
        zoo.train(
            model,
            imgs,
            labels,
            in_shape=cifar.IN_SHAPE,
            epochs=args.epochs,
            batch_size=batch,
            lr=args.lr,
            lr_schedule=args.lr_schedule,
            warmup_steps=args.warmup_steps,
            augment=args.augment,
            accum_steps=args.accum_steps or 1,
            mesh=mesh,
            model_axis=model_axis,
            comm=cfg.comm,
            fused=cfg.fused,
            plan=eplan,
            replan=args.replan,
            seed=args.seed,
            eval_data=(ev_imgs, ev_labels),
            checkpoint_dir=args.checkpoint_dir,
            resume=args.resume,
            metrics=metrics,
            loader=args.zoo_loader,
            resilience=cfg.resilience,
            chaos=chaos,
            obs=obs_bundle,
            elastic=cfg.elastic,
            pipeline=cfg.pipeline,
            # Zoo --profile = a jax.profiler trace of 3 steady-state steps
            # of THE run's own jitted step (augment/schedule/accum/mesh
            # included; compile excluded) — the single-chip MFU attribution
            # tool. The lenet path's --profile prints the per-phase table.
            profile_trace_dir=(
                os.path.abspath(
                    os.path.join(args.checkpoint_dir or ".", "zoo_xla_trace")
                )
                if args.profile
                else None
            ),
        )
    for kind, path in obs_bundle.finish().items():
        print(f"[obs] {kind} written to {path}")
    if guard.preempted:
        print("preempted: checkpoint flushed; continue with --resume")
    if metrics:
        metrics.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
