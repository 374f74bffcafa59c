"""Device-mesh abstraction — the TPU-native substrate replacing the
reference's two distribution runtimes (SURVEY.md §2.4):

- `mpirun -np N` + per-kernel `MPI_Reduce(root=0)` (MPI/Main.cpp:44,
  MPI/layer.h — 16 reduce sites), and
- CUDA's single-device launch geometry (CUDA/main.cu:75-156).

Here a single `jax.sharding.Mesh` with named axes carries both roles:
the ``data`` axis is batch/data parallelism (what the MPI backend *wanted*
to be), the ``model`` axis is intra-op decomposition (what it actually was,
per kernel). Collectives compile onto ICI; nothing is root-biased, so the
reference's "non-root ranks silently diverge" defect (SURVEY.md B7) cannot
exist by construction.
"""

from __future__ import annotations

import math
import os
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from parallel_cnn_tpu.config import MeshConfig

DATA_AXIS = "data"
MODEL_AXIS = "model"
# Hierarchical (host, device) meshes: the outer axis over which only the
# slow inter-host links exist. Built by make_hier_mesh; the hierarchical
# collective (collectives.hier_all_reduce) rings each axis separately so
# inter-host wires carry only 1/n_dev of the payload.
HOST_AXIS = "host"
# Pipeline-parallel (stage, data) meshes: the outer axis over which model
# layers are partitioned into stages. Built by make_pipeline_mesh; the
# 1F1B schedule (train/pipeline_schedule.py) moves activations stage→stage
# and cotangents stage←stage with full-ring ppermutes, while gradients
# still reduce over the inner data axis with the existing collectives.
STAGE_AXIS = "stage"


def make_mesh(cfg: Optional[MeshConfig] = None, devices: Optional[Sequence] = None) -> Mesh:
    """Build the (data, model) mesh from config.

    ``cfg.data=None`` means "all devices not claimed by the model axis" —
    the moral equivalent of mpirun's -np defaulting to world size.
    """
    cfg = cfg or MeshConfig()
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    model = cfg.model
    if cfg.data is None:
        if n % model != 0:
            raise ValueError(f"model axis {model} does not divide device count {n}")
        data = n // model
    else:
        data = cfg.data
        if data * model > n:
            raise ValueError(
                f"requested mesh {data}x{model} needs {data * model} devices "
                f"but only {n} available"
            )
    dev_array = np.array(devices[: data * model]).reshape(data, model)
    return Mesh(dev_array, (DATA_AXIS, MODEL_AXIS))


def make_hier_mesh(n_hosts: Optional[int] = None,
                   devices: Optional[Sequence] = None) -> Mesh:
    """Build the 2-level (host, device) mesh for hierarchical collectives.

    ``n_hosts=None`` derives the host axis from jax.distributed process
    topology: one host row per process, each row that process's devices
    (the TPU-pod case, where a row's devices share fast ICI and rows talk
    over DCN). An explicit ``n_hosts`` instead splits the device list into
    that many equal contiguous rows — fake hosts within one process, the
    CPU-emulation path that lets the whole hierarchical stack run and be
    tested on the 8-device virtual host platform.

    Device order is normalized to (process_index, id) so the same mesh is
    constructed on every participating process.
    """
    devices = list(devices if devices is not None else jax.devices())
    devices.sort(key=lambda d: (d.process_index, getattr(d, "id", 0)))
    if n_hosts is None:
        n_hosts = len({d.process_index for d in devices})
    n = len(devices)
    if n_hosts < 1 or n % n_hosts != 0:
        raise ValueError(
            f"host axis {n_hosts} does not divide device count {n}"
        )
    dev_array = np.array(devices).reshape(n_hosts, n // n_hosts)
    return Mesh(dev_array, (HOST_AXIS, DATA_AXIS))


def make_pipeline_mesh(n_stages: int,
                       devices: Optional[Sequence] = None) -> Mesh:
    """Build the 2-level (stage, data) mesh for pipeline parallelism.

    The device list splits into ``n_stages`` equal contiguous rows; row s
    holds stage s's layers replicated over the row (the inner ``data``
    axis — n // n_stages data-parallel replicas per stage). Inter-stage
    activation/cotangent wires are ppermutes over the stage axis between
    same-data-index devices; gradient reduction stays on the data axis.

    Device order is normalized to (process_index, id) — the same
    normalization make_hier_mesh applies — so the same mesh is
    constructed on every participating process.
    """
    devices = list(devices if devices is not None else jax.devices())
    devices.sort(key=lambda d: (d.process_index, getattr(d, "id", 0)))
    n = len(devices)
    if n_stages < 1 or n % n_stages != 0:
        raise ValueError(
            f"stage axis {n_stages} does not divide device count {n}"
        )
    dev_array = np.array(devices).reshape(n_stages, n // n_stages)
    return Mesh(dev_array, (STAGE_AXIS, DATA_AXIS))


def pipeline_axis_sizes(mesh: Mesh):
    """(n_stages, n_data) of a make_pipeline_mesh mesh."""
    if STAGE_AXIS not in mesh.axis_names:
        raise ValueError(
            f"mesh {mesh.axis_names} has no {STAGE_AXIS!r} axis — build "
            "it with make_pipeline_mesh"
        )
    return mesh.shape[STAGE_AXIS], mesh.shape[DATA_AXIS]


def make_elastic_mesh(world: int, *, n_hosts: int = 1,
                      devices: Optional[Sequence] = None) -> Mesh:
    """Rebuild the training mesh over the first ``world`` surviving
    devices (resilience/elastic.py's re-mesh step).

    The survivor set is deterministic: devices sort by
    (process_index, id) — the same normalization make_hier_mesh applies —
    and the first ``world`` are kept, so every process of a resizing run
    rebuilds the identical mesh without coordination beyond agreeing on
    ``world``. ``n_hosts > 1`` rebuilds hierarchically (host rows over
    the survivors, so the two-level collectives keep working after a
    host-count change); when ``world`` is no longer divisible by
    ``n_hosts`` — e.g. a host lost some but not all of its devices — the
    topology degrades to a flat data ring rather than refusing to
    continue (the elastic contract is "keep training on what's left").
    """
    if world < 1:
        raise ValueError(f"elastic world must be >= 1, got {world}")
    devices = list(devices if devices is not None else jax.devices())
    if world > len(devices):
        raise ValueError(
            f"elastic world {world} exceeds the {len(devices)} "
            "reachable devices"
        )
    devices.sort(key=lambda d: (d.process_index, getattr(d, "id", 0)))
    survivors = devices[:world]
    if n_hosts > 1 and world % n_hosts == 0:
        return make_hier_mesh(n_hosts=n_hosts, devices=survivors)
    return make_mesh(MeshConfig(data=world, model=1), survivors)


def hier_axis_sizes(mesh: Mesh):
    """(n_hosts, n_devices_per_host) of a make_hier_mesh mesh."""
    if HOST_AXIS not in mesh.axis_names:
        raise ValueError(
            f"mesh {mesh.axis_names} has no {HOST_AXIS!r} axis — build it "
            "with make_hier_mesh"
        )
    return mesh.shape[HOST_AXIS], mesh.shape[DATA_AXIS]


def single_device_mesh(device=None) -> Mesh:
    """A 1×1 mesh: lets every code path be written mesh-first and still run
    on one chip (≙ the Sequential/CUDA single-process backends)."""
    device = device or jax.devices()[0]
    return Mesh(np.array([device]).reshape(1, 1), (DATA_AXIS, MODEL_AXIS))


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Leading-axis sharding over the batch-parallel axes — how epoch
    tensors land in HBM (contrast: the CUDA reference's 60k per-sample
    H2D memcpys, SURVEY.md §3.2). On a hierarchical (host, device) mesh
    the batch splits over BOTH axes, host-major."""
    if HOST_AXIS in mesh.axis_names:
        return NamedSharding(mesh, P((HOST_AXIS, DATA_AXIS)))
    return NamedSharding(mesh, P(DATA_AXIS))


def replicated(mesh: Mesh) -> NamedSharding:
    """Fully-replicated sharding (params in pure-DP training)."""
    return NamedSharding(mesh, P())


def shard_batch(mesh: Mesh, batch):
    """Place a host batch in HBM sharded over the data axis."""
    s = batch_sharding(mesh)
    return jax.tree_util.tree_map(lambda x: jax.device_put(x, s), batch)


def replicate(mesh: Mesh, tree):
    """Place a pytree in HBM replicated over the whole mesh.

    Always copies: device_put may alias the source buffer when it already
    lives on a mesh device, and the train steps donate their params — an
    aliased replica would silently delete the caller's pytree.
    """
    s = replicated(mesh)
    return jax.tree_util.tree_map(lambda x: jax.device_put(jnp.array(x), s), tree)


def pad_to_multiple(n: int, k: int) -> int:
    """Smallest multiple of k ≥ n (batch padding for even data-axis shards)."""
    return k * math.ceil(n / k)


def distributed_init(coordinator: Optional[str] = None, num_processes: Optional[int] = None,
                     process_id: Optional[int] = None,
                     retry: Optional["object"] = None) -> None:
    """Multi-host bring-up (≙ MPI_Init, MPI/Main.cpp:44).

    On a TPU pod slice all arguments are auto-detected from the environment;
    explicit args support manual bring-up. Safe to call when already
    initialized (unlike MPI_Init). The reference's MPI_Finalize is dead code
    after `return` (bug B8); JAX needs no finalize at all.

    Transient bring-up failures — the coordinator not yet listening,
    barrier timeouts while other hosts boot — are retried with jittered
    exponential backoff (``retry`` is a resilience.RetryPolicy; default
    PCNN_INIT_RETRIES attempts, 3). Once the budget is exhausted the last
    error propagates — still failing fast like MPI_Init, just not on the
    very first race with the coordinator.
    """
    if jax.distributed.is_initialized():
        return  # already initialized — idempotent by design

    from parallel_cnn_tpu.resilience.retry import RetryPolicy, retry_call

    if retry is None:
        retry = RetryPolicy(
            attempts=int(os.environ.get("PCNN_INIT_RETRIES", "3")),  # graftcheck: disable=env-outside-config -- bootstrap retry knob read at call time, shared contract with parallel.distributed
            base_delay=0.5,
        )
        # Decorrelate the jitter stream per rank: after a straggler-
        # induced timeout every worker rebuilds this same default policy,
        # and identical delay sequences would re-stampede the coordinator
        # in lockstep.  Deterministic per (seed, rank); the max_delay cap
        # is unchanged.  An explicitly-passed policy is used verbatim.
        retry = retry.decorrelated(rank=process_id or 0)
    retry_call(
        jax.distributed.initialize,
        coordinator,
        num_processes,
        process_id,
        policy=retry,
        # The realistic transient failures surface as these; anything else
        # (bad arguments, TypeError) is a programming error and propagates
        # on the first attempt.
        retry_on=(RuntimeError, ConnectionError, OSError, TimeoutError),
        describe="jax.distributed.initialize",
    )
