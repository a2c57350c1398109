"""Device kernels for the streaming bulk-ingest pipeline.

The FPGA bitmap-index-creation line (arXiv:1803.11207) shows that
index *construction* — sort, bit-pack, popcount — is the same kernel
family the read path already offloads; the AVX2 popcount paper
(arXiv:1611.07612) is the word-level batching playbook for the pack
step. This module is that offload on XLA: ONE fused jitted pass per
slice batch that

- **scatter/packs** a sorted, deduplicated (row, position) column
  batch into dense ``uint32[n_rows, width32]`` words (positions are
  distinct after dedup, so per-word mask ADDs equal ORs — the
  ``_array_to_dense`` construction from ops/containers.py, batched
  over every row of the slice at once), and
- **classifies** every packed row in the same program: per-row
  popcount (cardinality) and per-row run-start count (a run starts at
  a set bit whose predecessor is clear; carries cross word
  boundaries) — the two density stats the roaring thresholds
  (containers.choose_format) need to pick ARRAY/RUN/DENSE.

The ingest pipeline (ingest/pipeline.py) reaches these through the
``bitops`` ingest dispatch registry (the write-path analog of the
count-kernel table): ``pack_classify`` is the fused pass, and the
``build.<fmt>`` cells turn one classified row's sorted positions into
its compressed Container — ARRAY and RUN containers are built from
the positions the batch already holds (NO dense host intermediate is
ever materialized for them), and the DENSE cell returns None so the
storage tier serves such rows from the fragment's existing device
mirrors.

Shapes are bucketed (rows and nnz pad to powers of two) so jit
compilation stays bounded, the bitops/containers discipline.
"""

import numpy as np

from pilosa_tpu.observe import kerneltime as _kt
from pilosa_tpu.ops import bitops, containers

# Shape buckets: the nnz axis floors at 1024 (small batches share one
# executable), the row axis at 8 (the fragment's own capacity floor).
_NNZ_FLOOR = 1024
_ROWS_FLOOR = 8


def _pad_pow2(n, floor):
    p = floor
    while p < n:
        p *= 2
    return p


_kernel_cache = {}


def _pack_classify_impl(n_rows_pad, width32):
    import jax.numpy as jnp
    from jax import lax

    def fn(rowidx, pos):
        # Padding entries target the sacrificial row ``n_rows_pad``
        # (sliced off below), so duplicate pad masks may ADD-collide
        # there without corrupting any real row.
        mask = jnp.uint32(1) << (pos & 31).astype(jnp.uint32)
        words = jnp.zeros((n_rows_pad + 1, width32), jnp.uint32)
        words = words.at[rowidx, pos >> 5].add(mask)
        words = words[:n_rows_pad]
        counts = jnp.sum(lax.population_count(words).astype(jnp.int32),
                         axis=-1)
        # Run starts: bit p set with bit p-1 clear. Within a word that
        # is x & ~(x << 1); bit 0 of word w consults bit 31 of word
        # w-1 (the carry column).
        carry = jnp.concatenate(
            [jnp.zeros((n_rows_pad, 1), jnp.uint32),
             words[:, :-1] >> 31], axis=1)
        starts = words & ~((words << 1) | carry)
        n_runs = jnp.sum(lax.population_count(starts).astype(jnp.int32),
                         axis=-1)
        return words, counts, n_runs
    return fn


def _pack_classify_kernel(n_rows_pad, width32):
    import jax

    key = ("pack_classify", n_rows_pad, width32)
    fn = _kernel_cache.get(key)
    if fn is None:
        fn = _kernel_cache[key] = jax.jit(
            _pack_classify_impl(n_rows_pad, width32))
    return fn


def pack_classify(rowidx, positions, n_rows, width32):
    """One fused scatter/pack/classify pass over a slice batch.

    ``rowidx`` (int32[nnz]) maps each position to its 0..n_rows-1 row
    group; ``positions`` (int32[nnz]) are window-relative bit
    positions. The (rowidx, position) pairs MUST be deduplicated —
    the scatter uses add-as-or, which only equals OR for distinct
    bits. Returns ``(words, counts, n_runs)``: the packed device
    ``uint32[n_rows, width32]`` matrix and two host int32[n_rows]
    stat vectors (one device->host transfer each — the only bytes
    that ever leave the device from this pass).
    """
    import jax.numpy as jnp

    nnz = len(positions)
    n_rows_pad = _pad_pow2(max(n_rows, 1), _ROWS_FLOOR)
    nnz_pad = _pad_pow2(max(nnz, 1), _NNZ_FLOOR)
    ridx = np.full(nnz_pad, n_rows_pad, dtype=np.int32)
    ridx[:nnz] = rowidx
    pos = np.zeros(nnz_pad, dtype=np.int32)
    pos[:nnz] = positions
    # The packed matrix stays on the device; the two stat vectors come
    # to the host.
    words, counts, n_runs = bitops.launch(
        "ingest.pack_classify", _pack_classify_kernel(n_rows_pad, width32),
        jnp.asarray(ridx), jnp.asarray(pos), cut=bitops.NO_CUT,
        fetch=lambda out: (out[0], *bitops.to_host(out[1:])),
        cell="write", bucket=_kt.shape_bucket(nnz_pad * 4))
    return words[:n_rows], counts[:n_rows], n_runs[:n_rows]


def _classify_stats_impl(n_rows_pad):
    import jax.numpy as jnp

    def fn(rowidx, pos):
        # O(nnz) in the position domain — no words matrix: per-row
        # cardinality is a segment count, and a run starts at any
        # position that is not exactly previous-position-plus-one
        # within the same row (the batch arrives sorted by
        # (row, position) and deduplicated).
        one = jnp.ones((), jnp.int32)
        zero = jnp.zeros((), jnp.int32)
        valid = rowidx < n_rows_pad
        inc = jnp.where(valid, one, zero)
        counts = jnp.zeros(n_rows_pad + 1, jnp.int32).at[rowidx].add(inc)
        same_row = jnp.concatenate(
            [jnp.zeros(1, bool), rowidx[1:] == rowidx[:-1]])
        adj = jnp.concatenate(
            [jnp.zeros(1, bool), pos[1:] == pos[:-1] + 1])
        start = valid & ~(same_row & adj)
        runs = jnp.zeros(n_rows_pad + 1, jnp.int32).at[rowidx].add(
            jnp.where(start, one, zero))
        return counts[:n_rows_pad], runs[:n_rows_pad]
    return fn


def classify_stats_device(rowidx, positions, n_rows):
    """(counts, n_runs) per row via one jitted segment-sum pass over
    the sorted position stream — the accelerator classify cell (the
    stats never touch a dense representation at all)."""
    import jax

    import jax.numpy as jnp

    n_rows_pad = _pad_pow2(max(n_rows, 1), _ROWS_FLOOR)
    nnz = len(positions)
    nnz_pad = _pad_pow2(max(nnz, 1), _NNZ_FLOOR)
    ridx = np.full(nnz_pad, n_rows_pad, dtype=np.int32)
    ridx[:nnz] = rowidx
    pos = np.zeros(nnz_pad, dtype=np.int32)
    pos[:nnz] = positions
    key = ("classify_stats", n_rows_pad)
    fn = _kernel_cache.get(key)
    if fn is None:
        fn = _kernel_cache[key] = jax.jit(
            _classify_stats_impl(n_rows_pad))
    counts, runs = bitops.launch(
        "ingest.classify", fn, jnp.asarray(ridx), jnp.asarray(pos),
        cut=bitops.NO_CUT, cell="write",
        bucket=_kt.shape_bucket(nnz_pad * 4))
    return counts[:n_rows], runs[:n_rows]


def classify_stats_host(rowidx, positions, n_rows):
    """The CPU-backend classify cell: the same stats in one vectorized
    host pass (two bincounts + one adjacency scan — the word-level
    batching discipline of the AVX2 popcount line, arXiv:1611.07612,
    applied in the position domain). Bit-identical to the device cell
    (asserted by test); XLA's CPU scatter-add serializes, so routing
    the segment sums through it would cost ~15x this pass."""
    rowidx = np.asarray(rowidx, dtype=np.int64)
    positions = np.asarray(positions, dtype=np.int64)
    counts = np.bincount(rowidx, minlength=n_rows)
    if len(rowidx):
        start = np.concatenate(
            ([True], ~((rowidx[1:] == rowidx[:-1])
                       & (positions[1:] == positions[:-1] + 1))))
        runs = np.bincount(rowidx[start], minlength=n_rows)
    else:
        runs = np.zeros(n_rows, dtype=np.int64)
    return counts[:n_rows].astype(np.int32), \
        runs[:n_rows].astype(np.int32)


def classify_formats(counts, n_runs):
    """Vectorized roaring-threshold classification over a whole slice
    batch: element-for-element identical to containers.choose_format
    (asserted by test) — run when 2 ints/run undercut both encodings,
    else array at <=4096 set bits, else dense; empty rows are array."""
    counts = np.asarray(counts, dtype=np.int64)
    n_runs = np.asarray(n_runs, dtype=np.int64)
    run_ok = ((n_runs <= containers.RUN_MAX_RUNS)
              & (2 * n_runs < np.minimum(counts,
                                         containers.ARRAY_MAX_BITS + 1)))
    array_ok = counts <= containers.ARRAY_MAX_BITS
    out = np.where(run_ok, bitops.FMT_RUN,
                   np.where(array_ok, bitops.FMT_ARRAY, bitops.FMT_DENSE))
    out = np.where(counts == 0, bitops.FMT_ARRAY, out)
    return out


# ------------------------------------------------------- build cells
# One classified row's sorted (deduplicated) positions -> its
# compressed Container, in slice-global bit coordinates at full
# container width — the exact shape fragment.row_container serves.

def _build_array(positions, width32):
    return containers.Container(
        bitops.FMT_ARRAY, width32, len(positions),
        positions=np.ascontiguousarray(positions, dtype=np.int32))


def _build_run(positions, width32):
    pos = np.ascontiguousarray(positions, dtype=np.int64)
    brk = np.flatnonzero(np.diff(pos) != 1)
    starts = pos[np.concatenate(([0], brk + 1))]
    ends = pos[np.concatenate((brk, [len(pos) - 1]))] + 1
    runs = np.stack([starts, ends], axis=1).astype(np.int32)
    return containers.Container(
        bitops.FMT_RUN, width32, len(pos), runs=runs)


def _build_dense(positions, width32):
    """Dense rows are served from the fragment's existing device
    mirrors (the storage tier's dense path — already paid for, full
    width, governor-charged); returning None tells the pipeline to
    seed the format memo only."""
    return None


def _classify_auto(rowidx, positions, n_rows):
    """First-call backend resolution for the ``classify`` cell (the
    native.scatter_or / exec_reads discipline): segment-sum kernels
    win on an accelerator's vector units; on the CPU backend XLA's
    scatter-add serializes, so the vectorized host pass is the fast,
    bit-identical implementation. Resolved lazily — probing
    jax.default_backend() at import would initialize XLA before
    multi-host startup can (the bitops import-time rule)."""
    import jax

    fn = (classify_stats_host if jax.default_backend() == "cpu"
          else classify_stats_device)
    bitops.register_ingest_kernel("classify", fn)
    return fn(rowidx, positions, n_rows)


def _register():
    bitops.register_ingest_kernel("pack_classify", pack_classify)
    # Both concrete classify cells are registered under their own
    # names too, so tests (and operators probing a backend) pin either
    # explicitly.
    bitops.register_ingest_kernel("classify.device",
                                  classify_stats_device)
    bitops.register_ingest_kernel("classify.host", classify_stats_host)
    bitops.register_ingest_kernel("classify", _classify_auto)
    bitops.register_ingest_kernel("build." + bitops.FMT_ARRAY,
                                  _build_array)
    bitops.register_ingest_kernel("build." + bitops.FMT_RUN, _build_run)
    bitops.register_ingest_kernel("build." + bitops.FMT_DENSE,
                                  _build_dense)


_register()
