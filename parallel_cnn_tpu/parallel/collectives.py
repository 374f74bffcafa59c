"""Bucketed gradient collectives: explicit ring reduce-scatter/all-gather
over the mesh's ``data`` axis, with optional bf16-on-the-wire compression.

Both trainers reduce the whole grad pytree in ONE ``psum`` at the end of
backward (parallel/data_parallel.py, train/zoo.py) — semantically a true
allreduce (the corrected version of the reference MPI backend's 16
root-only reduces, SURVEY.md B7), but a single monolithic collective gives
the scheduler nothing to overlap: ICI idles during compute and compute
idles during the reduce. This module provides the standard latency-hiding
decomposition (arXiv:1810.11112, arXiv:1605.08325, Horovod-style):

- **bucketization** — the grad pytree is flattened into fixed-byte 1-D
  buckets (`plan_buckets` / `flatten_buckets` / `unflatten_buckets`) with
  an exact round-trip: leaves grouped by dtype (so concatenation is
  bit-preserving for float AND integer leaves), scalars and odd shapes
  raveled in, zero-size leaves carried in metadata only, and each bucket
  zero-padded to a multiple of the axis size so ring chunks stay even;
- **ring collectives** — `ring_reduce_scatter` + `ring_all_gather` built
  from `lax.ppermute` (run inside shard_map), the bandwidth-optimal
  2(n−1)/n-payload alternative to monolithic psum (docs/collectives.md);
- **wire-dtype compression** — float payloads optionally cast to bf16 for
  the hop transfers while every accumulation stays f32 master precision;
- **selection** — `tree_all_reduce` dispatches on a config.CommConfig
  (impl "psum" keeps the monolithic collective; "ring" goes bucketed),
  so callers hold one code path and the choice rides PCNN_COMM_IMPL /
  --comm-impl.

The overlap schedule itself lives in the grad-accumulation consumer
(train/zoo.py): each microbatch's buckets are reduce-scattered as soon as
its grads are final — kept OUT of the inter-microbatch optimization
barrier — and one all-gather at the end rematerializes the full grads.

Numerics contract: ring reduction reassociates the f32 sum (n partial
orders instead of psum's fixed tree), so results match psum to roundoff
(~1e-5 relative for zoo-scale grads), not bit-exactly; bf16 wire adds a
per-hop requantization, keeping loss parity to ~1e-2. Both bounds are
pinned by tests/test_collectives.py.
"""

from __future__ import annotations

import dataclasses
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

DEFAULT_BUCKET_BYTES = 4 * 1024 * 1024  # PCNN_COMM_BUCKET_BYTES default


# --------------------------------------------------------------------------
# Bucketization: pytree <-> list of fixed-byte 1-D buffers, exact round-trip
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class LeafSlot:
    """Where one pytree leaf lives inside the bucket list.

    ``bucket == -1`` marks a zero-size leaf: it occupies no bucket space
    and is rebuilt from (shape, dtype) alone at unflatten time.
    """

    bucket: int
    offset: int  # element offset within the bucket
    size: int    # element count (product of shape)
    shape: Tuple[int, ...]
    dtype: str   # numpy dtype name — hashable/pickleable plan metadata


@dataclasses.dataclass(frozen=True)
class BucketPlan:
    """Static flattening recipe for one pytree structure.

    Built once per (tree structure, bucket_bytes, shards) at trace time;
    `flatten_buckets`/`unflatten_buckets` are pure array reshuffles driven
    by this metadata, so the round-trip is exact by construction.
    """

    treedef: Any
    slots: Tuple[LeafSlot, ...]
    bucket_sizes: Tuple[int, ...]   # padded element counts, per bucket
    bucket_dtypes: Tuple[str, ...]  # one dtype per bucket (grouped fill)
    shards: int                     # every bucket_size is a multiple of this

    @property
    def n_buckets(self) -> int:
        return len(self.bucket_sizes)


def _ceil_to(n: int, k: int) -> int:
    return k * ((n + k - 1) // k)


def plan_buckets(tree: Any, bucket_bytes: int = DEFAULT_BUCKET_BYTES,
                 shards: int = 1) -> BucketPlan:
    """Greedy fixed-byte bucket assignment for a pytree's leaves.

    Leaves are grouped by dtype (a bucket never mixes dtypes — the
    concatenation round-trips bit-exactly with no casts) and packed in
    flatten order into buckets of at most ``bucket_bytes`` payload; a
    single leaf larger than the budget gets a bucket of its own rather
    than being split (keeps slots contiguous; the tail bucket per dtype
    is simply short). Each bucket's element count is padded up to a
    multiple of ``shards`` so a ring reduce-scatter divides it evenly.
    """
    if bucket_bytes <= 0:
        raise ValueError(f"bucket_bytes must be > 0, got {bucket_bytes}")
    if shards <= 0:
        raise ValueError(f"shards must be > 0, got {shards}")
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    slots: List[LeafSlot] = []
    sizes: List[int] = []      # unpadded fill, per open/closed bucket
    dtypes: List[str] = []
    open_bucket: dict = {}     # dtype name -> bucket index still accepting
    for leaf in leaves:
        shape = tuple(getattr(leaf, "shape", ()))
        dt = jnp.asarray(leaf).dtype
        size = int(np.prod(shape, dtype=np.int64)) if shape else 1
        if size == 0:
            slots.append(LeafSlot(-1, 0, 0, shape, dt.name))
            continue
        cap = max(1, bucket_bytes // dt.itemsize)
        b = open_bucket.get(dt.name)
        if b is None or sizes[b] + size > cap:
            b = len(sizes)
            sizes.append(0)
            dtypes.append(dt.name)
            # An oversized leaf fills (and closes) its own bucket.
            open_bucket[dt.name] = b if size < cap else None
        slots.append(LeafSlot(b, sizes[b], size, shape, dt.name))
        sizes[b] += size
        if sizes[b] >= cap:
            open_bucket[dt.name] = None
    return BucketPlan(
        treedef=treedef,
        slots=tuple(slots),
        bucket_sizes=tuple(_ceil_to(s, shards) for s in sizes),
        bucket_dtypes=tuple(dtypes),
        shards=shards,
    )


def flatten_buckets(tree: Any, plan: BucketPlan) -> List[jax.Array]:
    """Pack a pytree (matching the plan's structure) into its buckets."""
    leaves = jax.tree_util.tree_leaves(tree)
    if len(leaves) != len(plan.slots):
        raise ValueError(
            f"tree has {len(leaves)} leaves but plan was built for "
            f"{len(plan.slots)}"
        )
    parts: List[List[jax.Array]] = [[] for _ in plan.bucket_sizes]
    fill = [0] * plan.n_buckets
    for leaf, slot in zip(leaves, plan.slots):
        if slot.bucket < 0:
            continue
        parts[slot.bucket].append(jnp.ravel(jnp.asarray(leaf)))
        fill[slot.bucket] += slot.size
    out: List[jax.Array] = []
    for b, chunks in enumerate(parts):
        pad = plan.bucket_sizes[b] - fill[b]
        if pad:
            chunks = chunks + [jnp.zeros((pad,), plan.bucket_dtypes[b])]
        out.append(chunks[0] if len(chunks) == 1 else jnp.concatenate(chunks))
    return out


def unflatten_buckets(buckets: Sequence[jax.Array], plan: BucketPlan) -> Any:
    """Exact inverse of `flatten_buckets` (padding discarded)."""
    if len(buckets) != plan.n_buckets:
        raise ValueError(
            f"{len(buckets)} buckets given, plan has {plan.n_buckets}"
        )
    leaves = []
    for slot in plan.slots:
        if slot.bucket < 0:
            leaves.append(jnp.zeros(slot.shape, slot.dtype))
            continue
        flat = lax.slice(buckets[slot.bucket], (slot.offset,),
                         (slot.offset + slot.size,))
        leaves.append(flat.reshape(slot.shape).astype(slot.dtype))
    return jax.tree_util.tree_unflatten(plan.treedef, leaves)


# --------------------------------------------------------------------------
# Ring collectives (call inside shard_map over the named axis)
# --------------------------------------------------------------------------


def _wire(x_dtype, wire_dtype):
    """Resolve the on-wire dtype: only floats compress, and casting to the
    native dtype is a no-op we skip entirely."""
    if wire_dtype is None or not jnp.issubdtype(x_dtype, jnp.floating):
        return None
    w = jnp.dtype(wire_dtype)
    return None if w == jnp.dtype(x_dtype) else w


def _acc(x_dtype):
    """Accumulation dtype: f32 master precision for floats (the wire may
    be bf16; sums never are), native dtype for exact integer addition."""
    return jnp.float32 if jnp.issubdtype(x_dtype, jnp.floating) else x_dtype


def ring_reduce_scatter(x: jax.Array, axis_name: str, axis_size: int,
                        wire_dtype=None) -> jax.Array:
    """Ring reduce-scatter of a 1-D buffer: device ``d`` returns the fully
    summed chunk ``d`` of ``x.reshape(axis_size, -1)``.

    n−1 `ppermute` hops, each carrying 1/n of the payload: at step s a
    device forwards the partial sum for chunk (idx−s−1) mod n and adds its
    local copy of the chunk arriving next — after n−1 hops it holds the
    complete sum of exactly one chunk. `axis_size` is an explicit argument
    (not read back from the axis) so the chunking is static at trace time.
    """
    n = axis_size
    if x.ndim != 1:
        raise ValueError(f"expected a 1-D bucket, got shape {x.shape}")
    if x.shape[0] % n:
        raise ValueError(
            f"bucket of {x.shape[0]} elements does not divide over "
            f"{n} shards (plan_buckets pads for this)"
        )
    acc = _acc(x.dtype)
    chunks = x.reshape(n, -1).astype(acc)
    if n == 1:
        return chunks[0].astype(x.dtype)
    wire = _wire(x.dtype, wire_dtype)
    send_cast = (lambda v: v.astype(wire)) if wire is not None else (lambda v: v)
    perm = [(i, (i + 1) % n) for i in range(n)]
    idx = lax.axis_index(axis_name)
    send = jnp.take(chunks, (idx - 1) % n, axis=0)
    for s in range(n - 1):
        recvd = lax.ppermute(send_cast(send), axis_name, perm).astype(acc)
        send = recvd + jnp.take(chunks, (idx - s - 2) % n, axis=0)
    return send.astype(x.dtype)  # the fully-reduced chunk `idx`


def ring_all_gather(shard: jax.Array, axis_name: str, axis_size: int,
                    wire_dtype=None) -> jax.Array:
    """Ring all-gather: device ``d`` contributes chunk ``d``; every device
    returns the concatenation of all chunks (n−1 forwarding hops)."""
    n = axis_size
    if n == 1:
        return shard
    wire = _wire(shard.dtype, wire_dtype)
    send_cast = (lambda v: v.astype(wire)) if wire is not None else (lambda v: v)
    perm = [(i, (i + 1) % n) for i in range(n)]
    idx = lax.axis_index(axis_name)
    out = jnp.zeros((n,) + shard.shape, shard.dtype).at[idx].set(shard)
    send = shard
    for s in range(n - 1):
        recvd = lax.ppermute(send_cast(send), axis_name, perm).astype(shard.dtype)
        out = out.at[(idx - s - 1) % n].set(recvd)
        send = recvd
    return out.reshape((n * shard.shape[0],) + shard.shape[1:])


def ring_all_reduce(x: jax.Array, axis_name: str, axis_size: int,
                    wire_dtype=None) -> jax.Array:
    """Bandwidth-optimal allreduce of a 1-D buffer: reduce-scatter then
    all-gather — 2(n−1)/n of the payload per device on the wire vs the
    naive n× (docs/collectives.md)."""
    shard = ring_reduce_scatter(x, axis_name, axis_size, wire_dtype)
    return ring_all_gather(shard, axis_name, axis_size, wire_dtype)


# --------------------------------------------------------------------------
# Hierarchical (two-level) collectives over a (host, device) mesh
# --------------------------------------------------------------------------
#
# The multi-host decomposition of arXiv:1810.11112: ring each mesh axis
# separately instead of one flat ring over every device. The intra-host
# ring moves (n_dev−1)/n_dev of the bucket over fast ICI; the inter-host
# exchange then rings only the 1/n_dev-sized chunks over the slow links —
# (n_host−1)/(n_host·n_dev) of the bucket per device on DCN, vs a flat
# global ring's (N−1)/N of it. docs/collectives.md has the cost model.
#
# Shard indexing: after hier_reduce_scatter, device (h, d) holds the fully
# reduced row ``d*n_host + h`` of ``x.reshape(n_host*n_dev, -1)`` — chunk d
# from the device-axis ring, sub-chunk h from the host-axis ring.
# hier_all_gather inverts exactly that placement, and hier_shard_rows /
# hier_unshard_rows lay a bucket out as (n_host*n_dev, L) rows in
# shard_map's P((host, data)) row order so ZeRO-3 resident shards line up
# with what the rings deliver.


def hier_reduce_scatter(x: jax.Array, host_axis: str, n_host: int,
                        dev_axis: str, n_dev: int,
                        wire_dtype=None) -> jax.Array:
    """Two-level reduce-scatter: intra-host ring RS over the device axis,
    then the inter-host shard exchange — a ring RS of the surviving chunk
    over the host axis. Device (h, d) returns the globally summed row
    ``d*n_host + h`` of ``x.reshape(n_host*n_dev, -1)``."""
    local = ring_reduce_scatter(x, dev_axis, n_dev, wire_dtype)
    return ring_reduce_scatter(local, host_axis, n_host, wire_dtype)


def hier_all_gather(shard: jax.Array, host_axis: str, n_host: int,
                    dev_axis: str, n_dev: int, wire_dtype=None) -> jax.Array:
    """Exact inverse of `hier_reduce_scatter`: all-gather over the host
    axis rebuilds each device's chunk, then the intra-host all-gather
    rebuilds the full bucket."""
    chunk = ring_all_gather(shard, host_axis, n_host, wire_dtype)
    return ring_all_gather(chunk, dev_axis, n_dev, wire_dtype)


def hier_all_reduce(x: jax.Array, host_axis: str, n_host: int,
                    dev_axis: str, n_dev: int, wire_dtype=None) -> jax.Array:
    """Hierarchical allreduce of a 1-D bucket (RS then AG, per level)."""
    shard = hier_reduce_scatter(x, host_axis, n_host, dev_axis, n_dev,
                                wire_dtype)
    return hier_all_gather(shard, host_axis, n_host, dev_axis, n_dev,
                           wire_dtype)


def hier_shard_rows(bucket: jax.Array, n_host: int, n_dev: int) -> jax.Array:
    """Lay a 1-D bucket out as (n_host*n_dev, L) resident-shard rows in
    shard_map's P((host, data)) row order: row ``h*n_dev + d`` carries the
    sub-chunk the hierarchical rings place on device (h, d) — i.e. row
    ``d*n_host + h`` of the natural reshape. With n_host=1 this is just
    ``bucket.reshape(n_dev, -1)`` (the flat-ring layout)."""
    if bucket.shape[0] % (n_host * n_dev):
        raise ValueError(
            f"bucket of {bucket.shape[0]} elements does not divide over "
            f"{n_host}x{n_dev} shards"
        )
    if n_host == 1:
        return bucket.reshape(n_dev, -1)
    return (bucket.reshape(n_dev, n_host, -1)
            .transpose(1, 0, 2)
            .reshape(n_host * n_dev, -1))


def hier_unshard_rows(rows: jax.Array, n_host: int, n_dev: int) -> jax.Array:
    """Exact inverse of `hier_shard_rows`: rows back to the 1-D bucket."""
    if n_host == 1:
        return rows.reshape(-1)
    return (rows.reshape(n_host, n_dev, -1)
            .transpose(1, 0, 2)
            .reshape(-1))


# --------------------------------------------------------------------------
# Tree-level API (what the trainers call)
# --------------------------------------------------------------------------


def wire_dtype_arg(comm) -> Optional[str]:
    """The wire_dtype argument the ring primitives expect, from a
    config.CommConfig ("float32" means no compression → None)."""
    if comm is None or comm.wire_dtype in (None, "float32"):
        return None
    return comm.wire_dtype


def tree_all_reduce(tree: Any, axis_name: str, axis_size: int,
                    comm=None, *, host_axis: Optional[str] = None,
                    host_size: int = 1) -> Any:
    """SUM-allreduce a pytree over the batch-parallel axes, per the comm
    config.

    comm=None or impl="psum": one monolithic `lax.psum` (the historical
    behavior — XLA picks the algorithm; on a hierarchical mesh it reduces
    over both axes at once). impl="ring": the pytree is bucketed
    (comm.bucket_bytes) and each bucket goes through the explicit ring,
    optionally bf16-on-the-wire. impl="hierarchical": each bucket goes
    through the two-level (host, device) ring; callers pass the host axis
    name/size alongside the device axis. Call inside shard_map; ring and
    hierarchical callers must build the enclosing shard_map with the
    replication checker off (mesh.shard_map(check_vma=False)) — ppermute
    outputs are per-device values the checker cannot prove replicated,
    even though RS+AG leaves every device with identical sums.
    """
    if comm is None or comm.impl == "psum":
        axes = (host_axis, axis_name) if host_axis is not None else axis_name
        return lax.psum(tree, axes)
    wire = wire_dtype_arg(comm)
    if comm.impl == "hierarchical":
        if host_axis is None:
            raise ValueError(
                "impl='hierarchical' needs a (host, device) mesh — pass "
                "host_axis/host_size (mesh.make_hier_mesh builds the mesh)"
            )
        plan = plan_buckets(tree, comm.bucket_bytes,
                            shards=host_size * axis_size)
        buckets = [
            hier_all_reduce(b, host_axis, host_size, axis_name, axis_size,
                            wire)
            for b in flatten_buckets(tree, plan)
        ]
        return unflatten_buckets(buckets, plan)
    if comm.impl != "ring":
        raise ValueError(f"unknown comm impl {comm.impl!r}")
    plan = plan_buckets(tree, comm.bucket_bytes, shards=axis_size)
    buckets = [
        ring_all_reduce(b, axis_name, axis_size, wire)
        for b in flatten_buckets(tree, plan)
    ]
    return unflatten_buckets(buckets, plan)


def reduce_scatter_buckets(buckets: Sequence[jax.Array], axis_name: str,
                           axis_size: int, wire_dtype=None, *,
                           host_axis: Optional[str] = None,
                           host_size: int = 1) -> List[jax.Array]:
    """Reduce-scatter each bucket → per-device shard list. The overlap
    building block: train/zoo.py calls this per microbatch (the shards
    accumulate sharded, 1/n the memory of full grads) and defers the
    single `all_gather_buckets` to after the last microbatch. With a
    host axis the two-level hierarchical ring runs instead of the flat
    one (buckets must be planned with shards=host_size*axis_size)."""
    if host_axis is not None:
        return [
            hier_reduce_scatter(b, host_axis, host_size, axis_name,
                                axis_size, wire_dtype)
            for b in buckets
        ]
    return [
        ring_reduce_scatter(b, axis_name, axis_size, wire_dtype)
        for b in buckets
    ]


def all_gather_buckets(shards: Sequence[jax.Array], axis_name: str,
                       axis_size: int, wire_dtype=None, *,
                       host_axis: Optional[str] = None,
                       host_size: int = 1) -> List[jax.Array]:
    """Inverse of `reduce_scatter_buckets`: rematerialize full buckets."""
    if host_axis is not None:
        return [
            hier_all_gather(s, host_axis, host_size, axis_name, axis_size,
                            wire_dtype)
            for s in shards
        ]
    return [
        ring_all_gather(s, axis_name, axis_size, wire_dtype)
        for s in shards
    ]
