"""Multi-host proof (VERDICT r1 item 5): real JAX processes (2- and
4-host clusters) join via jax.distributed.initialize, each stages only
its own slice shards (stage_process_local), and the sharded Count
kernel returns the global answer — exercising the cross-process half
of parallel/distributed.py that in-process tests cannot reach."""
import os
import socket
import subprocess
import sys

import pytest

CHILD = os.path.join(os.path.dirname(__file__), "_multihost_child.py")


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _run_cluster(n_proc, dev_per_proc=2):
    coordinator = f"127.0.0.1:{_free_port()}"
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS")
           and not k.startswith("PILOSA_")}
    procs = [
        subprocess.Popen(
            [sys.executable, CHILD, coordinator, str(i), str(n_proc),
             str(dev_per_proc)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env, cwd=os.path.dirname(os.path.dirname(CHILD)))
        for i in range(n_proc)
    ]
    outs = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=300)
            outs.append((p.returncode, out, err))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for rc, out, err in outs:
        if rc == 77:
            # Child hit the pinned jaxlib's "Multiprocess computations
            # aren't implemented on the CPU backend" at this topology —
            # a backend capability gap (the 2×2 shape does run), not a
            # regression in the code under test.
            pytest.skip(f"CPU backend refuses this topology: {err[-200:]}")
        assert rc == 0, f"child failed rc={rc}\nstdout:{out}\nstderr:{err}"
    # Every host reports one count, and it is the same global count.
    # Whole lines only: the children share their stdout with Gloo's
    # chatter, and a COUNT glued into a "[Gloo] Rank ..." line once
    # failed this test in one run and hid a host's answer in the next.
    counts = [[ln for ln in out.splitlines() if ln.startswith("COUNT ")]
              for _, out, _ in outs]
    assert all(len(c) == 1 for c in counts), [out for _, out, _ in outs]
    assert len({c[0] for c in counts}) == 1, counts


def test_two_process_sharded_count():
    _run_cluster(2)


def test_two_process_four_device_sharded_count():
    """2 processes × 4 devices each (8 total): the dryrun's device
    count with a REAL process boundary through the middle of the slice
    axis — every collective (count psum, TopN phase-1 psum, replica
    digest all_gather) crosses both ICI-analog (intra-process) and
    DCN-analog (cross-process) edges in one program (VERDICT r3 #5)."""
    _run_cluster(2, dev_per_proc=4)


def test_four_process_sharded_count():
    """Four real JAX processes (8 devices total, 2 per host): the same
    slice-ownership staging and cross-host collectives at a topology
    where the coordinator, non-zero processes, and the replica axis
    all span multiple peers — the multi-host scaling shape the 2-proc
    proof can't distinguish from point-to-point."""
    _run_cluster(4)
