"""Multi-host distribution: process topology, replica groups, and the
global-mesh staging path.

The reference scales across machines with HTTP scatter/gather plus
synchronous replica write fan-out and anti-entropy repair (SURVEY §2.10,
executor.go:1444-1535, fragment.go:1703). The TPU-native equivalents:

- **inside one pod** — slices shard over chips; map/reduce is a single
  XLA program with ``psum`` over ICI (parallel/mesh.py).
- **across hosts of one pod** — ``jax.distributed.initialize`` forms one
  global device set; arrays are assembled from per-process local shards
  (:func:`stage_process_local`), and the same shard_map kernels run SPMD
  with collectives routed over ICI within the pod slice owned by each
  host.
- **across pods / replica sets (DCN)** — a second, outer mesh axis
  carries ReplicaN copies of every slice block. Queries psum only over
  the slice axis (replicas hold identical data, so each replica computes
  the full answer redundantly — the fault-tolerance trade the reference
  makes with its successor-node replicas, cluster.go:250-271);
  :meth:`ReplicaMeshEngine.replica_digest` is the on-device anti-entropy
  probe: per-replica content digests compared host-side to trigger the
  block-level repair pass (cluster/syncer.py).

Process-level *ownership* (which host's storage holds which slice)
stays on the jump-hash placement in cluster/cluster.py so host HTTP
ownership and device sharding agree (SURVEY §7 "mesh distribution").
"""
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

REPLICA_AXIS = "replica"
SLICE_AXIS = "slice"


def init_distributed(coordinator=None, num_processes=None, process_id=None):
    """Join the JAX distributed runtime (multi-host pods).

    No-op for single-process runs (the common dev / single-VM case).
    Reads ``PILOSA_COORDINATOR`` / ``PILOSA_NUM_PROCESSES`` /
    ``PILOSA_PROCESS_ID`` when args are omitted — the TPU-native analog
    of the reference's gossip seed-join config (config.go gossip.seed).
    """
    coordinator = coordinator or os.environ.get("PILOSA_COORDINATOR")
    if not coordinator:
        return False
    if num_processes is None:
        num_processes = int(os.environ.get("PILOSA_NUM_PROCESSES", "1"))
    if process_id is None:  # NOT `or`: 0 is a valid explicit id
        process_id = int(os.environ.get("PILOSA_PROCESS_ID", "0"))
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=num_processes,
                               process_id=process_id)
    return True


def make_replica_mesh(replica_n=1, n_devices=None):
    """2-D mesh ``(replica, slice)``: the outer axis carries ReplicaN
    data copies (across pods → DCN), the inner axis shards slices
    (within a pod → ICI). With replica_n=1 this degenerates to the
    1-D slice mesh."""
    devices = np.asarray(jax.devices())
    if n_devices is not None:
        devices = devices[:n_devices]
    if devices.size % replica_n:
        raise ValueError(
            f"{devices.size} devices not divisible by replica_n={replica_n}")
    grid = devices.reshape(replica_n, devices.size // replica_n)
    return Mesh(grid, (REPLICA_AXIS, SLICE_AXIS))


def process_slice_range(n_slices, mesh):
    """[lo, hi) of the global slice-stack rows this process's local
    devices own under ``P(slice)`` sharding — what the storage layer
    must stage locally. Contiguous because mesh device order is
    process-major within each replica row."""
    axis = mesh.shape[SLICE_AXIS] if SLICE_AXIS in mesh.shape else mesh.devices.size
    per_dev = (n_slices + axis - 1) // axis
    local_ids = [d.id for d in mesh.local_devices]
    cols = []
    flat = mesh.devices.reshape(-1, axis)
    for r in range(flat.shape[0]):
        for c in range(axis):
            if flat[r, c].id in local_ids:
                cols.append(c)
    if not cols:
        return 0, 0
    return min(cols) * per_dev, min((max(cols) + 1) * per_dev, n_slices)


def stage_process_local(local_rows, global_shape, mesh,
                        spec=P(SLICE_AXIS)):
    """Assemble a global sharded array from this process's local shard
    data (np.uint32). Single-process: a plain device_put. Multi-host:
    ``jax.make_array_from_process_local_data`` — each host contributes
    only the slices it owns; no host ever materializes the global
    array (the analog of each node mmapping only its own fragments).
    """
    sharding = NamedSharding(mesh, spec)
    if jax.process_count() == 1:
        return jax.device_put(np.ascontiguousarray(local_rows), sharding)
    return jax.make_array_from_process_local_data(
        sharding, np.ascontiguousarray(local_rows), global_shape)


class ReplicaMeshEngine:
    """Sharded kernels over a ``(replica, slice)`` mesh.

    Data layout: every replica row of the mesh holds an identical copy
    of the slice-sharded stack (``P(None, 'slice')`` on the slice axis
    of the array — replicas are *not* a sharded array dimension, they
    are redundant copies, matching the reference where each replica
    node stores full fragments, not halves).
    """

    def __init__(self, mesh):
        if mesh.axis_names != (REPLICA_AXIS, SLICE_AXIS):
            raise ValueError(f"want (replica, slice) mesh, got {mesh.axis_names}")
        self.mesh = mesh
        self.replica_n = mesh.shape[REPLICA_AXIS]
        self.slice_devices = mesh.shape[SLICE_AXIS]

    def pad_slices(self, n):
        d = self.slice_devices
        return (n + d - 1) // d * d

    def shard_rows(self, host_rows):
        """np.uint32[S, W] -> sharded on slice axis, replicated over the
        replica axis (each replica group gets a full copy over DCN)."""
        s = self.pad_slices(host_rows.shape[0])
        if s != host_rows.shape[0]:
            pad = np.zeros((s - host_rows.shape[0],) + host_rows.shape[1:],
                           dtype=host_rows.dtype)
            host_rows = np.concatenate([host_rows, pad])
        return jax.device_put(
            host_rows, NamedSharding(self.mesh, P(SLICE_AXIS)))

    # ----------------------------------------------------------- kernels

    @partial(jax.jit, static_argnums=0)
    def count_and(self, a, b):
        """|A ∩ B|: psum over the slice axis only — every replica group
        computes the full count independently (redundant execution =
        failure tolerance; the first replica's answer is returned)."""

        def kernel(a_blk, b_blk):
            part = jnp.sum(
                lax.population_count(lax.bitwise_and(a_blk, b_blk))
                .astype(jnp.int32))
            return lax.psum(part, SLICE_AXIS)

        return shard_map(
            kernel, mesh=self.mesh,
            in_specs=(P(SLICE_AXIS), P(SLICE_AXIS)),
            out_specs=P())(a, b)

    @partial(jax.jit, static_argnums=0)
    def topn_counts(self, matrix):
        def kernel(blk):
            part = jnp.sum(
                lax.population_count(blk).astype(jnp.int32), axis=(0, 2))
            return lax.psum(part, SLICE_AXIS)

        return shard_map(kernel, mesh=self.mesh,
                         in_specs=(P(SLICE_AXIS),), out_specs=P())(matrix)

    @partial(jax.jit, static_argnums=0)
    def replica_digest(self, rows):
        """Anti-entropy probe: per-replica 64-bit-ish content digest of
        the full slice stack, all_gathered over the replica axis so the
        host can compare copies without pulling data (the on-device
        analog of FragmentSyncer's block-checksum exchange,
        fragment.go:1703-1771). Digest = psum over slices of a
        position-salted word mix — associative, order-independent."""

        def kernel(blk):
            # Position-salted mix summed with uint32 wrap-around: mod-2^32
            # sums are associative, so the digest is independent of the
            # psum reduction order. Salting by global position makes
            # "same words, different slice" collisions unlikely.
            idx = jnp.arange(blk.size, dtype=jnp.uint32).reshape(blk.shape)
            base = lax.axis_index(SLICE_AXIS).astype(jnp.uint32)
            mixed = blk ^ ((idx + base * jnp.uint32(blk.size))
                           * jnp.uint32(2654435761))
            local = lax.psum(jnp.sum(mixed), SLICE_AXIS)
            return lax.all_gather(local, REPLICA_AXIS)

        # Replication checking off: after the all_gather every device
        # holds the same [replica_n] vector, but varying-mesh-axis
        # inference can't prove replica-invariance statically.
        return shard_map(kernel, mesh=self.mesh,
                         in_specs=(P(SLICE_AXIS),),
                         out_specs=P(), check_vma=False)(rows)

    def replicas_consistent(self, rows):
        """Host-side check: True when all replica copies digest equal."""
        d = np.asarray(self.replica_digest(rows))
        return bool((d == d[0]).all())
