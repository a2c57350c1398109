"""Child process for the multi-host (multi-process JAX) proof test.

Each process joins the JAX distributed runtime as one "host" with 2
virtual CPU devices, stages ONLY the slice rows it owns
(stage_process_local → jax.make_array_from_process_local_data), and
runs the sharded Count(Intersect) kernel — the cross-host path of
parallel/distributed.py that single-process tests cannot reach.

Spawned by tests/test_multihost.py; writes the line "COUNT <n>" on
success.
Exits 77 (the autotools skip convention) when the pinned jaxlib's CPU
backend refuses multiprocess computations at this topology — a
platform capability gap, not a code failure; the parent skips.
"""
import os
import sys

SKIP_RC = 77


def main():
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    dev_per_proc = int(sys.argv[4]) if len(sys.argv) > 4 else 2
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={dev_per_proc}").strip()

    import jax

    jax.config.update("jax_platforms", "cpu")

    import numpy as np

    coordinator = sys.argv[1]
    process_id = int(sys.argv[2])
    n_proc = int(sys.argv[3]) if len(sys.argv) > 3 else 2

    from pilosa_tpu.parallel.distributed import (
        ReplicaMeshEngine,
        init_distributed,
        make_replica_mesh,
        process_slice_range,
        stage_process_local,
    )

    assert init_distributed(coordinator=coordinator, num_processes=n_proc,
                            process_id=process_id)
    assert jax.process_count() == n_proc, jax.process_count()
    assert len(jax.devices()) == dev_per_proc * n_proc, jax.devices()
    assert len(jax.local_devices()) == dev_per_proc

    S, W = 8, 64
    rng = np.random.default_rng(42)  # same stream in both processes
    a_full = rng.integers(0, 1 << 32, size=(S, W)).astype(np.uint32)
    b_full = rng.integers(0, 1 << 32, size=(S, W)).astype(np.uint32)
    expect = int(np.bitwise_count(a_full & b_full).sum())

    mesh = make_replica_mesh(replica_n=1)
    lo, hi = process_slice_range(S, mesh)
    assert hi - lo == S // n_proc, (lo, hi)  # equal slice ownership

    from jax.sharding import PartitionSpec as P

    spec = P("slice")
    a = stage_process_local(a_full[lo:hi], (S, W), mesh, spec=spec)
    b = stage_process_local(b_full[lo:hi], (S, W), mesh, spec=spec)

    engine = ReplicaMeshEngine(mesh)
    count = int(engine.count_and(a, b))
    assert count == expect, (count, expect)

    # Cross-host TopN phase-1 kernel: per-row candidate counts psum'd
    # over a slice axis that spans processes.
    R = 4
    m_full = rng.integers(0, 1 << 32, size=(S, R, W)).astype(np.uint32)
    m = stage_process_local(m_full[lo:hi], (S, R, W), mesh,
                            spec=P("slice"))
    rc = np.asarray(engine.topn_counts(m))
    assert rc.shape == (R,)
    assert rc.tolist() == np.bitwise_count(m_full).sum(
        axis=(0, 2)).tolist(), rc

    # replica_n=2 mesh: the replica axis spans processes (at 2 hosts
    # each host IS one replica row; at 4 hosts each row spans two),
    # so the replica digest's all_gather over the replica axis is a
    # collective that actually crosses hosts — the DCN-analog path
    # this proof exists to exercise.
    mesh2 = make_replica_mesh(replica_n=2)
    lo2, hi2 = process_slice_range(S, mesh2)
    rows2 = stage_process_local(a_full[lo2:hi2], (S, W), mesh2,
                                spec=P("slice"))
    eng2 = ReplicaMeshEngine(mesh2)
    count2 = int(eng2.count_and(
        rows2, stage_process_local(b_full[lo2:hi2], (S, W), mesh2,
                                   spec=P("slice"))))
    assert count2 == expect, (count2, expect)
    assert eng2.replicas_consistent(rows2)  # cross-host all_gather

    # One write, starting a line of its own: Gloo reports every
    # context it connects ("[Gloo] Rank 0 is connected to ...") on this
    # same stdout, from the device threads and a piece at a time, and
    # print() under PYTHONUNBUFFERED is two writes. A pipe keeps one
    # small write whole.
    os.write(1, f"\nCOUNT {count}\n".encode())


if __name__ == "__main__":
    try:
        main()
    except Exception as e:  # noqa: BLE001 — jaxlib error classes vary
        if "Multiprocess computations aren't implemented" in str(e):
            print(f"SKIP: {e}", file=sys.stderr)
            sys.exit(SKIP_RC)
        raise
