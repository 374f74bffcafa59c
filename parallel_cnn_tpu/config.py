"""Configuration layer.

The reference has no config system: every tunable is a hardcoded constant —
``dt = 0.1`` and ``threshold = 0.01`` (Sequential/layer.h:12-13), epochs via
``iter = 1`` (Sequential/Main.cpp:148), data paths (Sequential/Main.cpp:38-41),
and layer shapes baked into global ctor args (Sequential/Main.cpp:17-20).
``argc/argv`` are accepted and ignored (Sequential/Main.cpp:44).

Here every one of those constants becomes a config field, plus the TPU-native
knobs the reference couldn't have (mesh shape, batching, dtype policy).
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional


@dataclasses.dataclass(frozen=True)
class DataConfig:
    """Where training data comes from (≙ Sequential/Main.cpp:36-42)."""

    train_images: str = "data/train-images.idx3-ubyte"
    train_labels: str = "data/train-labels.idx1-ubyte"
    test_images: str = "data/t10k-images.idx3-ubyte"
    test_labels: str = "data/t10k-labels.idx1-ubyte"
    # The reference snapshot ships labels but not images (SURVEY.md B15);
    # when files are missing we synthesize a deterministic MNIST stand-in.
    synthetic_fallback: bool = True
    synthetic_train_count: int = 60_000
    synthetic_test_count: int = 10_000
    synthetic_seed: int = 1234
    loader: str = "auto"  # "auto" | "native" | "numpy" | "synthetic"


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    """Optimization contract of the reference (SURVEY.md §2.1)."""

    # `dt` at Sequential/layer.h:12 — SGD step applied as `w += dt * g`.
    dt: float = 0.1
    # `threshold` at Sequential/layer.h:13 — stop when mean ‖y−ŷ‖₂ < threshold.
    threshold: float = 0.01
    # `iter` at Sequential/Main.cpp:148. The reference's while-loop caps at one
    # epoch (bug B12); we honor the *intent*: run up to `epochs`, stop early
    # at `threshold`.
    epochs: int = 1
    # batch_size=1 reproduces the reference's per-sample SGD trajectory
    # (Sequential/Main.cpp:157-171). Larger batches are the TPU throughput
    # mode (minibatch SGD; a deliberate, documented equivalence gap).
    batch_size: int = 1
    seed: int = 0
    # dtype for the compute path. The reference is float32 throughout;
    # bfloat16 is the MXU-native option for throughput runs.
    dtype: str = "float32"
    # Epoch shuffling. The reference replays file order every epoch
    # (Sequential/Main.cpp:157), so parity default is False.
    shuffle: bool = False
    # Host-side batch assembly for batch_size > 1:
    #   "auto"   — use the native C++ prefetching batcher (data/native.py)
    #              when the extension builds, else a NumPy fallback with
    #              IDENTICAL semantics (drop-tail, xorshift shuffle via
    #              pipeline.xorshift_permutation) — the same config+seed
    #              trains bit-identically with or without a toolchain;
    #   "native" — require the native batcher (error if unavailable);
    #   "off"    — plain NumPy slicing (keep-tail, NumPy PCG shuffle).
    prefetch: str = "auto"

    # Which kernel library executes the FLOPs (SURVEY.md §7 stages 3-4):
    #   "reference" — path A, jnp/lax ops (XLA-fused; the parity surface);
    #   "pallas"    — path B, the hand-written Mosaic kernels
    #                 (ops/pallas.py ≙ the CUDA backend's kernel library,
    #                 CUDA/layer.cu:80-368). Batched mode only.
    ops: str = "reference"

    def __post_init__(self):
        if self.batch_size == 1 and self.dtype != "float32":
            raise ValueError(
                "batch_size=1 is the strict-parity mode and is float32-only "
                f"(got dtype={self.dtype!r}); use batch_size>1 for bf16 "
                "throughput"
            )
        if self.ops not in ("reference", "pallas"):
            raise ValueError(f"unknown ops path {self.ops!r}")
        if self.ops == "pallas" and self.batch_size == 1:
            raise ValueError(
                "ops='pallas' is the batched kernel path (its grids tile the "
                "batch dimension); use batch_size>1, or ops='reference' for "
                "strict per-sample parity"
            )
        if self.ops == "pallas" and self.dtype != "float32":
            raise ValueError(
                "ops='pallas' computes f32 (the fused megakernel casts its "
                "inputs; a bf16 run would be silently mislabeled) — use "
                "ops='reference' for bf16 throughput"
            )


@dataclasses.dataclass(frozen=True)
class ResilienceConfig:
    """Fault-tolerance policy (resilience/ subsystem — a capability class
    the reference lacks entirely: a NaN loss compares false against the
    stop threshold and trains a dead model forever, SURVEY.md §5)."""

    # What the health sentinel does on a non-finite loss/grad/param:
    #   "off"      — no checks (the reference's behavior);
    #   "raise"    — fail fast with resilience.DivergenceError;
    #   "skip"     — discard the poisoned update, continue from last-good;
    #   "rollback" — restore the newest healthy state and retry, LR scaled
    #                by lr_backoff per retry, at most max_rollbacks times.
    policy: str = "raise"
    max_rollbacks: int = 3
    # LR multiplier applied per rollback (1.0 = keep the LR).
    lr_backoff: float = 0.5
    # Checkpoint ring size: keep the newest N on-disk checkpoints
    # (0 = unbounded, the historical per-epoch behavior).
    ring_size: int = 0
    # Zoo trainer: also check loss/param finiteness every N optimizer
    # steps (0 = epoch boundaries only). Each check is a host sync, so
    # per-step checking trades dispatch asynchrony for detection latency.
    check_every_steps: int = 0

    def __post_init__(self):
        if self.policy not in ("off", "raise", "skip", "rollback"):
            raise ValueError(f"unknown sentinel policy {self.policy!r}")
        if self.max_rollbacks < 0:
            raise ValueError("max_rollbacks must be >= 0")
        if not 0.0 < self.lr_backoff <= 1.0:
            raise ValueError(
                f"lr_backoff must be in (0, 1], got {self.lr_backoff}"
            )
        if self.ring_size < 0 or self.check_every_steps < 0:
            raise ValueError("ring_size/check_every_steps must be >= 0")


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Device-mesh layout (the TPU-native replacement for `mpirun -np N` +
    per-kernel MPI_Reduce, MPI/Main.cpp:44 / MPI/layer.h). Axis names are
    fixed ("data", "model") — every collective in parallel/ binds them."""

    # Axis sizes; None = use all available devices on that axis.
    data: Optional[int] = None  # batch (DP) axis
    model: int = 1  # intra-op / tensor axis


@dataclasses.dataclass(frozen=True)
class CommConfig:
    """Gradient-collective policy (parallel/collectives.py).

    The default (no CommConfig at all — Config.comm is None) keeps the
    historical behavior: one monolithic psum/GSPMD all-reduce per step.
    Constructing one opts the mesh trainers into the explicit-comm path,
    where the reduce algorithm, bucket granularity, and wire precision
    become knobs (docs/collectives.md has the cost model)."""

    # "psum"         — monolithic lax.psum, XLA picks the algorithm
    #                  (baseline);
    # "ring"         — bucketed ring reduce-scatter + all-gather
    #                  (lax.ppermute), 2(n−1)/n wire payload and an
    #                  explicit schedule XLA can overlap with microbatch
    #                  compute;
    # "hierarchical" — two-level ring over a (host, device) mesh
    #                  (parallel/mesh.py make_hier_mesh): intra-host ring
    #                  reduce-scatter → inter-host shard exchange over the
    #                  host axis → intra-host all-gather (arXiv:1810.11112)
    #                  — the multi-host topology-aware path, where the slow
    #                  inter-host links carry only 1/n_dev of the payload.
    impl: str = "psum"
    # Bucket payload budget for impl="ring" (bytes). Small buckets pay the
    # per-hop latency many times; huge buckets lose overlap granularity.
    bucket_bytes: int = 4 * 1024 * 1024
    # Payload dtype on the wire: "float32" (exact) or "bfloat16" (half the
    # ICI bytes; accumulation stays f32 master precision).
    wire_dtype: str = "float32"
    # impl="ring" × grad accumulation: reduce-scatter each microbatch's
    # buckets as soon as its grads are final (overlapping the reduce with
    # the next microbatch's compute), one all-gather at the end. False
    # reduces once after the full accumulation loop.
    overlap: bool = True
    # impl="hierarchical": host-axis size of the (host, device) mesh.
    # None = derive from jax.distributed process topology (one host row
    # per process); an explicit value splits a single process's devices
    # into that many emulated hosts — the 2-process-per-host CPU
    # emulation path the tests exercise.
    hosts: Optional[int] = None

    def __post_init__(self):
        if self.impl not in ("psum", "ring", "hierarchical"):
            raise ValueError(f"unknown comm impl {self.impl!r}")
        if self.hosts is not None and self.hosts < 1:
            raise ValueError(f"hosts must be >= 1, got {self.hosts}")
        if self.bucket_bytes <= 0:
            raise ValueError(
                f"bucket_bytes must be > 0, got {self.bucket_bytes}"
            )
        if self.wire_dtype not in ("float32", "bfloat16"):
            raise ValueError(
                f"unknown wire dtype {self.wire_dtype!r} "
                "(float32 or bfloat16)"
            )

    @staticmethod
    def from_env() -> Optional["CommConfig"]:
        """CommConfig from PCNN_COMM_IMPL / PCNN_COMM_BUCKET_BYTES /
        PCNN_COMM_WIRE_DTYPE / PCNN_COMM_OVERLAP / PCNN_COMM_HOSTS, or
        None when none of them is set (→ the historical implicit-psum
        path)."""
        impl = os.environ.get("PCNN_COMM_IMPL")
        bucket = os.environ.get("PCNN_COMM_BUCKET_BYTES")
        wire = os.environ.get("PCNN_COMM_WIRE_DTYPE")
        overlap = os.environ.get("PCNN_COMM_OVERLAP")
        hosts = os.environ.get("PCNN_COMM_HOSTS")
        if (impl is None and bucket is None and wire is None
                and overlap is None and hosts is None):
            return None
        return CommConfig(
            impl=impl or "psum",
            bucket_bytes=int(bucket) if bucket else 4 * 1024 * 1024,
            wire_dtype=wire or "float32",
            overlap=overlap != "0" if overlap is not None else True,
            hosts=int(hosts) if hosts else None,
        )


@dataclasses.dataclass(frozen=True)
class FusedStepConfig:
    """Fused end-to-end train-step policy (round 7).

    The default (no FusedStepConfig at all — Config.fused is None) keeps
    every historical code path byte-for-byte: the optimizer stays a
    tree-wide post-collective optax pass, the loss tail stays the unfused
    pool→flatten→dense→softmax-CE composition, activations stay f32.
    Constructing one (--fused-step / PCNN_FUSED_STEP=1) opts a run into
    the fused step, whose three pieces are individually gated:

    - ``update`` — update-on-arrival bucketed SGD/momentum
      (ops/pallas_update.py): each gradient bucket's param+momentum
      update launches as soon as its ring reduce-scatter sum is final,
      and the final all-gather ships already-updated parameter shards.
      Requires the explicit ring collective path (CommConfig impl="ring"
      on a mesh) and constant-LR SGD+momentum without weight decay — the
      update math is baked into the kernel, not an optax chain.
    - ``tail`` — the fused pool→flatten→FC→softmax-CE kernel with a
      custom VJP that emits dlogits from the forward
      (ops/pallas_tail.py); models whose head doesn't match a supported
      tail pattern degrade to the unfused composition with a log line.
    - ``act_dtype`` — activation/compute dtype for the fused path.
      Defaults to bfloat16 (f32 master weights; grads/updates stay f32).
      bf16 runs carry a dynamic loss scale: the scaled loss keeps bf16
      backprop cotangents in range, gradient overflow SKIPS the update
      in-step and multiplies the scale by ``backoff`` (the resilience
      sentinel reports it as a handled overflow instead of rolling
      back), and ``growth_interval`` consecutive good steps double it.
      act_dtype="float32" keeps exact numerics (scale pinned to 1).
    """

    update: bool = True
    tail: bool = True
    act_dtype: str = "bfloat16"
    loss_scale: float = 2.0 ** 15
    growth_interval: int = 200
    backoff: float = 0.5
    # Optimizer-state partitioning level (requires ``update``):
    #   2 — ZeRO-2: momentum lives as 1/n bucket shards, params stay
    #       replicated (the round-7 behavior);
    #   3 — ZeRO-3: params AND momentum live permanently as 1/n bucket
    #       shards; each step all-gathers the weights just-in-time at the
    #       head of the microbatch schedule (always f32 on the wire) and
    #       the end-of-step update writes shards back with NO trailing
    #       all-gather. Per-step wire volume equals ZeRO-2 — the gather
    #       moves from the tail to the head — but resident param memory
    #       drops to 1/n.
    zero: int = 2

    def __post_init__(self):
        if self.zero not in (2, 3):
            raise ValueError(f"zero level must be 2 or 3, got {self.zero}")
        if self.zero == 3 and not self.update:
            raise ValueError(
                "zero=3 shards params into the update-on-arrival path and "
                "requires update=True"
            )
        if self.act_dtype not in ("float32", "bfloat16"):
            raise ValueError(
                f"unknown act dtype {self.act_dtype!r} "
                "(float32 or bfloat16)"
            )
        if self.loss_scale < 1.0:
            raise ValueError(
                f"loss_scale must be >= 1, got {self.loss_scale}"
            )
        if self.growth_interval < 1:
            raise ValueError(
                f"growth_interval must be >= 1, got {self.growth_interval}"
            )
        if not 0.0 < self.backoff < 1.0:
            raise ValueError(
                f"backoff must be in (0, 1), got {self.backoff}"
            )

    @staticmethod
    def from_env() -> Optional["FusedStepConfig"]:
        """FusedStepConfig when PCNN_FUSED_STEP is set truthy, else None
        (→ every historical path unchanged). PCNN_ACT_DTYPE refines the
        activation dtype but does not by itself opt in — the acceptance
        contract is that ONLY --fused-step/PCNN_FUSED_STEP changes
        behavior."""
        enabled = os.environ.get("PCNN_FUSED_STEP")
        if enabled is None or enabled == "0":
            return None
        return FusedStepConfig(
            act_dtype=os.environ.get("PCNN_ACT_DTYPE", "bfloat16"),
            zero=int(os.environ.get("PCNN_ZERO_LEVEL", "2")),
        )


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Inference-serving policy (serve/ subsystem — the layer that turns
    training checkpoints into a request-serving surface; docs/serving.md
    has the queueing model and the bucket/padding cost math)."""

    # Registry name (serve/registry.py): lenet_ref, cifar_cnn,
    # resnet18/34/50, vgg16, convnext_b.
    model: str = "cifar_cnn"
    # Checkpoint to restore params (+ BN stats) from; None serves
    # seed-initialized weights (bench/smoke mode).
    checkpoint: Optional[str] = None
    # Largest batch the engine compiles; must be a power of two — it is
    # the top of the shape-bucket ladder 1, 2, 4, …, max_batch, and a
    # non-pow2 cap would silently never be used.
    max_batch: int = 64
    # Batcher coalescing window: a batch dispatches at max_batch OR when
    # this many ms have passed since its first request, whichever first.
    max_wait_ms: float = 2.0
    # Bounded request queue; a full queue sheds new requests with the
    # typed serve.Overloaded error (backpressure, not OOM).
    queue_depth: int = 256
    # Engine replicas pinned round-robin across local devices.
    n_replicas: int = 1
    # Default per-request deadline budget (ms); 0 = no deadline. Requests
    # already past their deadline at dispatch time are dropped with
    # serve.DeadlineExceeded instead of wasting a device slot.
    deadline_ms: float = 0.0
    # Conv kernel library for zoo models (resnet/vgg): "xla" or "pallas"
    # (fused eval epilogues, ops/pallas_conv.py).
    conv_backend: str = "xla"
    # AOT-compile every bucket at startup so steady-state requests never
    # trigger a trace; False compiles lazily on first use per bucket.
    precompile: bool = True
    # SLO admission control (serve/admission.py): EWMA reject-early
    # shedding + the graceful-degradation ladder in front of the queue.
    # Off by default — the historical admit-until-full behavior.
    admission: bool = False
    # Completion-time objective (ms): the admission predictor's budget
    # for deadline-less requests, the autoscaler's p99 target, and the
    # default scenario p99 gate.
    slo_ms: float = 100.0
    # Replica autoscaler (serve/autoscaler.py): grow/drain the pool from
    # windowed telemetry between n_replicas and max_replicas.
    autoscale: bool = False
    # Autoscaler ceiling; 0 = n_replicas (growth disabled even with
    # autoscale on — scale-down/scale-back-up only).
    max_replicas: int = 0
    # Exponential-decay time constant (seconds) of the windowed
    # telemetry views the autoscaler reads (serve/telemetry.py).
    window_s: float = 10.0

    def __post_init__(self):
        if self.max_batch < 1 or (self.max_batch & (self.max_batch - 1)):
            raise ValueError(
                f"max_batch must be a power of two >= 1, got {self.max_batch}"
            )
        if self.max_wait_ms < 0 or self.deadline_ms < 0:
            raise ValueError("max_wait_ms/deadline_ms must be >= 0")
        if self.queue_depth < 1:
            raise ValueError(f"queue_depth must be >= 1, got {self.queue_depth}")
        if self.n_replicas < 1:
            raise ValueError(f"n_replicas must be >= 1, got {self.n_replicas}")
        if self.conv_backend not in ("xla", "pallas"):
            raise ValueError(f"unknown conv backend {self.conv_backend!r}")
        if self.slo_ms <= 0:
            raise ValueError(f"slo_ms must be > 0, got {self.slo_ms}")
        if self.window_s <= 0:
            raise ValueError(f"window_s must be > 0, got {self.window_s}")
        if self.max_replicas < 0:
            raise ValueError(
                f"max_replicas must be >= 0, got {self.max_replicas}"
            )
        if self.max_replicas and self.max_replicas < self.n_replicas:
            raise ValueError(
                f"max_replicas ({self.max_replicas}) must be >= "
                f"n_replicas ({self.n_replicas})"
            )

    @property
    def effective_max_replicas(self) -> int:
        """The autoscaler ceiling: max_replicas, or n_replicas when 0."""
        return self.max_replicas or self.n_replicas

    @staticmethod
    def from_env() -> "ServeConfig":
        """ServeConfig with PCNN_SERVE_* environment overrides applied
        over the defaults (README has the full table). Unlike
        CommConfig.from_env there is no None sentinel — serving has no
        historical implicit path to preserve, so the env vars simply
        re-default the config the CLI flags then override."""
        e = os.environ.get
        return ServeConfig(
            model=e("PCNN_SERVE_MODEL", "cifar_cnn"),
            checkpoint=e("PCNN_SERVE_CHECKPOINT") or None,
            max_batch=int(e("PCNN_SERVE_MAX_BATCH", "64")),
            max_wait_ms=float(e("PCNN_SERVE_MAX_WAIT_MS", "2.0")),
            queue_depth=int(e("PCNN_SERVE_QUEUE_DEPTH", "256")),
            n_replicas=int(e("PCNN_SERVE_REPLICAS", "1")),
            deadline_ms=float(e("PCNN_SERVE_DEADLINE_MS", "0")),
            conv_backend=e("PCNN_SERVE_CONV_BACKEND", "xla"),
            precompile=e("PCNN_SERVE_PRECOMPILE", "1") != "0",
            admission=e("PCNN_SERVE_ADMISSION", "0") != "0",
            slo_ms=float(e("PCNN_SERVE_SLO_MS", "100")),
            autoscale=e("PCNN_SERVE_AUTOSCALE", "0") != "0",
            max_replicas=int(e("PCNN_SERVE_MAX_REPLICAS", "0")),
            window_s=float(e("PCNN_SERVE_WINDOW_S", "10")),
        )


@dataclasses.dataclass(frozen=True)
class NetConfig:
    """Network front-door policy (serve/net.py + serve/supervisor.py —
    the out-of-process serving tier in front of the DynamicBatcher;
    docs/serving.md §network tier has the deadline mapping and the
    supervisor state machine)."""

    # Serve over a real TCP listener (serve/net.py) instead of the
    # historical in-process-only surface.
    listen: bool = False
    # Bind address for the listener. Loopback by default — the front
    # door is an experiment harness, not a hardened public ingress.
    host: str = "127.0.0.1"
    # TCP port; 0 binds an ephemeral port (the bound port is reported
    # on NetServer.port and kept stable across supervisor respawns).
    port: int = 0
    # Per-connection read/write deadline (ms): a socket that stalls
    # mid-request past this budget is reaped as `expired` (the
    # slow-loris defense), and a blocked response write is abandoned
    # the same way. Also the submit() budget inherited by requests
    # that do not carry their own deadline_ms.
    conn_deadline_ms: float = 2000.0
    # Persistent on-disk AOT-executable cache directory (engine.py):
    # a cold-started / respawned / autoscaler-grown replica loads its
    # per-bucket executables instead of recompiling. None = off.
    aot_cache_dir: Optional[str] = None
    # Supervise the endpoint: respawn a killed listener with bounded
    # exponential backoff (resilience/retry.py) and reconcile the
    # journal across the restart.
    supervise: bool = False
    # Supervisor respawn backoff envelope (RetryPolicy fields).
    respawn_attempts: int = 4
    respawn_base_delay_s: float = 0.05
    respawn_max_delay_s: float = 1.0

    def __post_init__(self):
        if not 0 <= self.port <= 65535:
            raise ValueError(f"port must be in [0, 65535], got {self.port}")
        if self.conn_deadline_ms <= 0:
            raise ValueError(
                f"conn_deadline_ms must be > 0, got {self.conn_deadline_ms}"
            )
        if self.respawn_attempts < 1:
            raise ValueError(
                f"respawn_attempts must be >= 1, got {self.respawn_attempts}"
            )
        if self.respawn_base_delay_s < 0 or self.respawn_max_delay_s < 0:
            raise ValueError("respawn delays must be >= 0")

    @staticmethod
    def from_env() -> "NetConfig":
        """NetConfig with PCNN_SERVE_* environment overrides applied over
        the defaults (docs/api.md has the table). Same no-sentinel idiom
        as ServeConfig.from_env: env re-defaults, CLI flags override."""
        e = os.environ.get
        return NetConfig(
            listen=e("PCNN_SERVE_LISTEN", "0") != "0",
            host=e("PCNN_SERVE_HOST", "127.0.0.1"),
            port=int(e("PCNN_SERVE_PORT", "0")),
            conn_deadline_ms=float(e("PCNN_SERVE_CONN_DEADLINE_MS", "2000")),
            aot_cache_dir=e("PCNN_SERVE_AOT_CACHE_DIR") or None,
            supervise=e("PCNN_SERVE_SUPERVISE", "0") != "0",
            respawn_attempts=int(e("PCNN_SERVE_RESPAWN_ATTEMPTS", "4")),
            respawn_base_delay_s=float(
                e("PCNN_SERVE_RESPAWN_BASE_DELAY_S", "0.05")
            ),
            respawn_max_delay_s=float(
                e("PCNN_SERVE_RESPAWN_MAX_DELAY_S", "1.0")
            ),
        )


@dataclasses.dataclass(frozen=True)
class ElasticConfig:
    """Elastic-training policy (resilience/elastic.py — in-flight re-mesh
    + ZeRO-3 reshard on preemption, chaos-injected device loss, or device
    add; docs/fault_tolerance.md has the state machine).

    The default (no ElasticConfig at all — Config.elastic is None) keeps
    the historical fixed-mesh behavior: a preemption stops the run at the
    next boundary, a lost device kills it.  Constructing one (--elastic /
    PCNN_ELASTIC=1) opts the ZeRO-3 zoo trainer into resize-and-continue.
    Requires the ZeRO-3 step (FusedStepConfig zero=3) — only there are
    params/momentum resident as world-size-independent bucket-row shards
    that zero3_full_view/zero3_from_view can re-lay-out without a disk
    round-trip.
    """

    enabled: bool = True
    # Deterministic resize schedule: "STEP:WORLD[,STEP:WORLD...]" —
    # before optimizer step STEP (0-based, global across epochs), resize
    # the data-parallel world to WORLD devices.  The planned test
    # surface; preemption signals and chaos `resize@` triggers feed the
    # same controller at runtime.  Empty = no planned resizes.
    schedule: str = ""
    # How batch/LR respond to a world-size change:
    #   "global"     — global batch and LR stay fixed; per-device batch
    #                  changes implicitly with the mesh (the parity mode:
    #                  the loss trajectory matches a fixed-mesh run up to
    #                  reduction-order roundoff);
    #   "per-device" — per-device batch stays fixed; global batch and LR
    #                  scale linearly with the new world size (the
    #                  throughput mode for genuine capacity changes).
    scaling: str = "global"
    # Never shrink below this many devices; a chaos `resize@N:-k` that
    # would go under is clamped (and the clamp journaled).
    min_world: int = 1

    def __post_init__(self):
        if self.scaling not in ("global", "per-device"):
            raise ValueError(
                f"unknown elastic scaling {self.scaling!r} "
                "(global or per-device)"
            )
        if self.min_world < 1:
            raise ValueError(
                f"min_world must be >= 1, got {self.min_world}"
            )
        self.plan()  # validate the schedule grammar eagerly

    def plan(self) -> tuple:
        """The parsed schedule: ((step, world), ...) sorted by step."""
        out = []
        for part in filter(None, self.schedule.split(",")):
            step, sep, world = part.partition(":")
            if not sep or not step.strip().isdigit() \
                    or not world.strip().isdigit():
                raise ValueError(
                    f"bad elastic schedule entry {part!r} "
                    "(want STEP:WORLD, e.g. '40:4,80:8')"
                )
            out.append((int(step), int(world)))
        return tuple(sorted(out))

    @staticmethod
    def from_env() -> Optional["ElasticConfig"]:
        """ElasticConfig from PCNN_ELASTIC / PCNN_ELASTIC_SCHEDULE /
        PCNN_ELASTIC_SCALING / PCNN_ELASTIC_MIN_WORLD, or None when none
        of them is set (→ the historical fixed-mesh path)."""
        enabled = os.environ.get("PCNN_ELASTIC")
        schedule = os.environ.get("PCNN_ELASTIC_SCHEDULE")
        scaling = os.environ.get("PCNN_ELASTIC_SCALING")
        min_world = os.environ.get("PCNN_ELASTIC_MIN_WORLD")
        if (enabled is None and schedule is None and scaling is None
                and min_world is None):
            return None
        return ElasticConfig(
            enabled=(enabled if enabled is not None else "1")
            not in ("0", ""),
            schedule=schedule or "",
            scaling=scaling or "global",
            min_world=int(min_world) if min_world else 1,
        )


@dataclasses.dataclass(frozen=True)
class AsyncConfig:
    """Asynchronous data-parallel policy (train/async_dp.py — bounded
    staleness per arXiv:1711.00705, EASGD elastic averaging per
    arXiv:1605.08325; docs/fault_tolerance.md has the straggler state
    machine).

    The default (no AsyncConfig at all — Config.async_dp is None) keeps
    every trainer bulk-synchronous: one slow worker stalls the whole
    ring.  Constructing one (--async-mode / PCNN_ASYNC_MODE) opts into a
    straggler-tolerant mode.  Async modes do NOT preserve bitwise parity
    with the sync ring (except mode="stale" with staleness_bound=0,
    which degenerates to the synchronous schedule) — the contract is a
    bounded loss delta instead.
    """

    # "off"   — sync ring (same as Config.async_dp is None),
    # "stale" — bounded-staleness SSP: a worker may apply gradients
    #           computed against params up to `staleness_bound`
    #           optimizer steps old; a hard barrier fires only when the
    #           bound would be violated,
    # "easgd" — elastic averaging: independent local SGD per worker plus
    #           a periodic ρ-pull toward a shared center variable.
    mode: str = "stale"
    # Max optimizer-step age S of the params a gradient may be computed
    # against (mode="stale").  0 = fully synchronous (bit-exact with the
    # sync ring by construction).
    staleness_bound: int = 2
    # Local SGD steps between elastic-averaging rounds (mode="easgd").
    easgd_period: int = 4
    # Elastic-averaging pull strength ρ in (0, 1]: both the worker and
    # the center move ρ of the way toward each other each round.
    easgd_rho: float = 0.5
    # Logical async workers the single-process scheduler simulates; in a
    # multi-process run this is the process count instead.
    workers: int = 4
    # A completion later than this multiple of the nominal step duration
    # journals a `straggler_detected` event.
    straggler_factor: float = 2.0

    def __post_init__(self):
        if self.mode not in ("off", "stale", "easgd"):
            raise ValueError(
                f"unknown async mode {self.mode!r} (off, stale or easgd)"
            )
        if self.staleness_bound < 0:
            raise ValueError(
                f"staleness_bound must be >= 0, got {self.staleness_bound}"
            )
        if self.easgd_period < 1:
            raise ValueError(
                f"easgd_period must be >= 1, got {self.easgd_period}"
            )
        if not (0.0 < self.easgd_rho <= 1.0):
            raise ValueError(
                f"easgd_rho must be in (0, 1], got {self.easgd_rho}"
            )
        if self.workers < 1:
            raise ValueError(f"workers must be >= 1, got {self.workers}")
        if self.straggler_factor <= 1.0:
            raise ValueError(
                f"straggler_factor must be > 1, got {self.straggler_factor}"
            )

    @property
    def enabled(self) -> bool:
        return self.mode != "off"

    @staticmethod
    def from_env() -> Optional["AsyncConfig"]:
        """AsyncConfig from PCNN_ASYNC_MODE / PCNN_ASYNC_STALENESS /
        PCNN_ASYNC_EASGD_PERIOD / PCNN_ASYNC_EASGD_RHO /
        PCNN_ASYNC_WORKERS, or None when none of them is set (→ the
        historical bulk-synchronous path)."""
        mode = os.environ.get("PCNN_ASYNC_MODE")
        bound = os.environ.get("PCNN_ASYNC_STALENESS")
        period = os.environ.get("PCNN_ASYNC_EASGD_PERIOD")
        rho = os.environ.get("PCNN_ASYNC_EASGD_RHO")
        workers = os.environ.get("PCNN_ASYNC_WORKERS")
        if (mode is None and bound is None and period is None
                and rho is None and workers is None):
            return None
        return AsyncConfig(
            mode=mode or "stale",
            staleness_bound=int(bound) if bound else 2,
            easgd_period=int(period) if period else 4,
            easgd_rho=float(rho) if rho else 0.5,
            workers=int(workers) if workers else 4,
        )


@dataclasses.dataclass(frozen=True)
class ObsConfig:
    """Observability policy (obs/ subsystem — span tracing with Perfetto
    export, the process-wide metrics registry, and the JSONL event
    journal; docs/observability.md has the artifact formats).

    The default (no ObsConfig at all — Config.obs is None) keeps every
    hot path on the zero-cost no-op bundle: no spans, no journal, no
    files.  Constructing one (--trace / PCNN_OBS_* env) opts a run in.
    """

    # Emit host-side spans + the event journal and export the Chrome
    # trace at the end of the run.
    trace: bool = True
    # Directory all trace/journal artifacts are written under.
    dir: str = "obs_out"
    # Path for a MetricsRegistry JSON snapshot at the end of the run;
    # None = no snapshot file.  Setting only this (trace off) still
    # enables the registry without any span/journal cost.
    metrics_json: Optional[str] = None
    # Mirror every span into jax.profiler.TraceAnnotation so XLA device
    # profiles carry the same semantic names as the host timeline.
    jax_annotations: bool = True

    def __post_init__(self):
        if not self.dir:
            raise ValueError("ObsConfig.dir must be a non-empty path")

    @property
    def enabled(self) -> bool:
        return self.trace or self.metrics_json is not None

    @staticmethod
    def from_env() -> Optional["ObsConfig"]:
        """ObsConfig from PCNN_OBS_TRACE / PCNN_OBS_DIR /
        PCNN_OBS_METRICS_JSON / PCNN_OBS_JAX, or None when none of them
        is set (→ the no-op bundle everywhere)."""
        trace = os.environ.get("PCNN_OBS_TRACE")
        d = os.environ.get("PCNN_OBS_DIR")
        mj = os.environ.get("PCNN_OBS_METRICS_JSON")
        jx = os.environ.get("PCNN_OBS_JAX")
        if trace is None and d is None and mj is None and jx is None:
            return None
        return ObsConfig(
            trace=(trace if trace is not None else "1") not in ("0", ""),
            dir=d or "obs_out",
            metrics_json=mj or None,
            jax_annotations=(jx or "1") != "0",
        )


@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    """Pipeline-parallelism policy (parallel/pipeline.py +
    train/pipeline_schedule.py — GPipe-style 1F1B microbatch pipelining
    over a ``(stage, data)`` mesh; docs/pipeline.md has the schedule
    diagram and the bubble/byte cost model).

    The default (no PipelineConfig at all — Config.pipeline is None)
    keeps every trainer on the existing data-parallel paths.
    Constructing one (--pipeline-stages / PCNN_PIPELINE_STAGES) opts the
    zoo trainer into the pipelined step.  stages=1 is the degenerate
    pipeline: it delegates structurally to the explicit-ring
    data-parallel step and is bit-exact with it by construction.
    """

    # Number of pipeline stages S — the size of the mesh's ``stage``
    # axis.  Device count must be divisible by S; the remaining devices
    # form the data axis (n_devices // S data-parallel replicas per
    # stage).
    stages: int = 1
    # Manual stage boundaries: comma-separated layer indices at which a
    # new stage STARTS (e.g. "8,15" for 3 stages of a 23-layer model).
    # Empty = automatic flops-balanced split from the cost model's
    # per-layer tables (parallel/pipeline.py split_layers).
    split: str = ""
    # Inter-stage activation payload dtype on the wire: "float32"
    # (exact) or "bfloat16" (half the stage-boundary ICI bytes; the
    # backward cotangent wire narrows identically).
    wire_dtype: str = "float32"
    # Stage-compute activation dtype: "float32", or "bfloat16" for
    # MXU-native stage math over f32 master params (grads come back
    # f32; same cast discipline as the fused step's bf16 path).
    act_dtype: str = "float32"

    def __post_init__(self):
        if self.stages < 1:
            raise ValueError(f"stages must be >= 1, got {self.stages}")
        if self.wire_dtype not in ("float32", "bfloat16"):
            raise ValueError(
                f"unknown pipeline wire dtype {self.wire_dtype!r} "
                "(float32 or bfloat16)"
            )
        if self.act_dtype not in ("float32", "bfloat16"):
            raise ValueError(
                f"unknown pipeline act dtype {self.act_dtype!r} "
                "(float32 or bfloat16)"
            )
        self.boundaries()  # validate the split grammar eagerly

    def boundaries(self) -> tuple:
        """The parsed manual split: sorted stage-start layer indices,
        () when split is empty (→ automatic balancing)."""
        out = []
        for part in filter(None, self.split.split(",")):
            if not part.strip().isdigit() or int(part) < 1:
                raise ValueError(
                    f"bad pipeline split entry {part!r} (want positive "
                    "layer indices, e.g. '8,15' for 3 stages)"
                )
            out.append(int(part))
        if len(set(out)) != len(out):
            raise ValueError(
                f"pipeline split {self.split!r} repeats a boundary"
            )
        if out and len(out) != self.stages - 1:
            raise ValueError(
                f"pipeline split {self.split!r} names {len(out)} "
                f"boundaries but stages={self.stages} needs "
                f"{self.stages - 1}"
            )
        return tuple(sorted(out))

    @staticmethod
    def from_env() -> Optional["PipelineConfig"]:
        """PipelineConfig from PCNN_PIPELINE_STAGES /
        PCNN_PIPELINE_SPLIT / PCNN_PIPELINE_WIRE_DTYPE /
        PCNN_PIPELINE_ACT_DTYPE, or None when none of them is set
        (→ the historical data-parallel paths)."""
        stages = os.environ.get("PCNN_PIPELINE_STAGES")
        split = os.environ.get("PCNN_PIPELINE_SPLIT")
        wire = os.environ.get("PCNN_PIPELINE_WIRE_DTYPE")
        act = os.environ.get("PCNN_PIPELINE_ACT_DTYPE")
        if (stages is None and split is None and wire is None
                and act is None):
            return None
        return PipelineConfig(
            stages=int(stages) if stages else 1,
            split=split or "",
            wire_dtype=wire or "float32",
            act_dtype=act or "float32",
        )


@dataclasses.dataclass(frozen=True)
class AutotuneConfig:
    """Cost-model autotuner policy (analysis/autotune.py — enumerate the
    legal parallelism-plan space, score every plan against the analytic
    roofline under a hard peak-HBM budget, and apply the winner;
    docs/autotuning.md has the search space and the scoring formula).

    The default (no AutotuneConfig at all — Config.autotune is None)
    keeps plan selection fully manual: every --comm-impl/--zero/
    --pipeline-stages flag means exactly what the operator typed.
    Constructing one (--autotune / PCNN_AUTOTUNE=1) layers the report's
    chosen plan UNDER the env and CLI flags — the tuner proposes,
    explicit knobs still win.
    """

    enabled: bool = True
    # Cost report the chosen plan is read from (``tune`` writes it; see
    # analysis/autotune.py load_chosen_plan). None = the shipped report,
    # cost_model.DEFAULT_COST_REPORT — resolved at use, not here, so the
    # dataclass stays importable without the analysis package.
    report: Optional[str] = None
    # Hardware profile name (analysis/hw_profiles.py) the tuner scores
    # against; None = the PCNN_HW_PROFILE env var, then the default.
    hw: Optional[str] = None
    # Ranked plans kept in the report table.
    top_k: int = 8
    # Peak-HBM budget in bytes a plan must fit under; None = the
    # profile's full HBM capacity.
    hbm_budget: Optional[int] = None

    def __post_init__(self):
        if self.top_k < 1:
            raise ValueError(f"top_k must be >= 1, got {self.top_k}")
        if self.hbm_budget is not None and self.hbm_budget <= 0:
            raise ValueError(
                f"hbm_budget must be > 0, got {self.hbm_budget}"
            )
        if self.hw is not None:
            # Fail at config time, not mid-search; hw_profiles is
            # import-light (no jax) so this stays cheap.
            from parallel_cnn_tpu.analysis import hw_profiles
            hw_profiles.get_profile(self.hw)

    @staticmethod
    def from_env() -> Optional["AutotuneConfig"]:
        """AutotuneConfig from PCNN_AUTOTUNE / PCNN_AUTOTUNE_REPORT /
        PCNN_AUTOTUNE_TOPK / PCNN_AUTOTUNE_HBM_BUDGET, or None when none
        of them is set (→ fully manual plan selection). The hardware
        profile is NOT duplicated here — PCNN_HW_PROFILE is resolved by
        analysis/hw_profiles.get_profile for every consumer."""
        enabled = os.environ.get("PCNN_AUTOTUNE")
        report = os.environ.get("PCNN_AUTOTUNE_REPORT")
        top_k = os.environ.get("PCNN_AUTOTUNE_TOPK")
        budget = os.environ.get("PCNN_AUTOTUNE_HBM_BUDGET")
        if (enabled is None and report is None and top_k is None
                and budget is None):
            return None
        return AutotuneConfig(
            enabled=(enabled if enabled is not None else "1")
            not in ("0", ""),
            report=report or None,
            top_k=int(top_k) if top_k else 8,
            hbm_budget=int(budget) if budget else None,
        )


@dataclasses.dataclass(frozen=True)
class Config:
    data: DataConfig = dataclasses.field(default_factory=DataConfig)
    train: TrainConfig = dataclasses.field(default_factory=TrainConfig)
    mesh: MeshConfig = dataclasses.field(default_factory=MeshConfig)
    resilience: ResilienceConfig = dataclasses.field(
        default_factory=ResilienceConfig
    )
    # None = historical implicit collectives (monolithic psum / GSPMD);
    # a CommConfig opts mesh training into parallel/collectives.py.
    comm: Optional[CommConfig] = None
    # None = the historical unfused step; a FusedStepConfig opts into the
    # round-7 fused path (update-on-arrival optimizer, fused loss tail,
    # bf16 activations with dynamic loss scaling).
    fused: Optional[FusedStepConfig] = None
    # None = the zero-cost no-op observability bundle; an ObsConfig opts
    # the run into span tracing / journal / metrics artifacts (obs/).
    obs: Optional[ObsConfig] = None
    # None = fixed-mesh training (preemption stops, device loss kills);
    # an ElasticConfig opts the ZeRO-3 zoo trainer into in-flight
    # re-mesh + reshard-and-continue (resilience/elastic.py).
    elastic: Optional[ElasticConfig] = None
    # None = bulk-synchronous training everywhere; an AsyncConfig opts
    # into the straggler-tolerant bounded-staleness / EASGD data-parallel
    # modes (train/async_dp.py).
    async_dp: Optional[AsyncConfig] = None
    # None = data-parallel only; a PipelineConfig opts the zoo trainer
    # into 1F1B microbatch pipelining over a (stage, data) mesh
    # (parallel/pipeline.py + train/pipeline_schedule.py).
    pipeline: Optional[PipelineConfig] = None
    # None = in-process serving only; a NetConfig opts the serve stack
    # into the supervised TCP front door (serve/net.py + supervisor.py).
    net: Optional[NetConfig] = None
    # None = manual plan selection; an AutotuneConfig layers the cost
    # report's chosen plan under the env/CLI knobs (analysis/autotune.py).
    autotune: Optional[AutotuneConfig] = None
    model: str = "lenet_ref"

    def replace(self, **kw) -> "Config":
        return dataclasses.replace(self, **kw)


#: Every PCNN_* variable that feeds an ExecutionPlan knob — the set
#: plan.build_plan consults to label a knob's provenance "env".  Kept
#: here (not in plan/) because environment reads live in config.py only
#: (the env-outside-config graftcheck rule pins that).
_PLAN_ENV_VARS = (
    "PCNN_COMM_IMPL",
    "PCNN_COMM_BUCKET_BYTES",
    "PCNN_COMM_WIRE_DTYPE",
    "PCNN_COMM_OVERLAP",
    "PCNN_COMM_HOSTS",
    "PCNN_FUSED_STEP",
    "PCNN_ACT_DTYPE",
    "PCNN_ZERO_LEVEL",
    "PCNN_PIPELINE_STAGES",
    "PCNN_PIPELINE_SPLIT",
    "PCNN_PIPELINE_WIRE_DTYPE",
    "PCNN_PIPELINE_ACT_DTYPE",
    "PCNN_SERVE_PRECOMPILE",
    "PCNN_SERVE_AOT_CACHE_DIR",
)


def present_plan_env() -> frozenset:
    """The plan-feeding PCNN_* vars actually set in this environment."""
    return frozenset(v for v in _PLAN_ENV_VARS if os.environ.get(v))


def plan_path_from_env() -> Optional[str]:
    """PCNN_PLAN: path to a plan.json applied under CLI flags (same
    precedence slot as --plan; an explicit --plan flag wins), or None."""
    return os.environ.get("PCNN_PLAN") or None
