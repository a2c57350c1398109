"""TopN device kernels.

The reference's TopN walks a host-side ranked cache with a min-heap and
early-exit thresholds (fragment.go:831-963) because per-row counts are
expensive on CPU. On TPU a full per-row popcount over the fragment's row
matrix is one fused kernel, so the primary path is: popcount all rows
(optionally ∩ a source/filter bitmap) → ``lax.top_k``. The ranked cache
is kept host-side for API parity and warm-start, but correctness does
not depend on it.
"""
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from pilosa_tpu import tracing
from pilosa_tpu.observe import kerneltime
from pilosa_tpu.ops import bitops


@partial(jax.jit, static_argnames=("k",))
def top_k_rows(matrix, k):
    """(counts int32[k], row_indices int32[k]) of the k densest rows.

    ``matrix`` is uint32[R, W]; rows are physical storage rows — the
    caller maps indices back to row IDs.
    """
    counts = jnp.sum(lax.population_count(matrix).astype(jnp.int32), axis=-1)
    return lax.top_k(counts, k)


@partial(jax.jit, static_argnames=("k",))
def top_k_rows_src(matrix, src, k):
    """TopN restricted to a source bitmap (ref: TopOptions.Src,
    fragment.go:886-906): counts are |row ∩ src|."""
    inter = lax.bitwise_and(matrix, src[None, :])
    counts = jnp.sum(lax.population_count(inter).astype(jnp.int32), axis=-1)
    return lax.top_k(counts, k)


def tanimoto_keep(inter, row_n, src_n, threshold):
    """The Tanimoto gate, in integers (ref: fragment.go:850-858 and
    :908-918 keep a row when ceil(100*|A∩B| / (|A|+|B|−|A∩B|)) is
    STRICTLY greater than the threshold): for an integer threshold that
    is ``100*inter > threshold*denom``, and false where the denominator
    is 0. The one place that holds the rule: the per-fragment program,
    the executor's batched program and any host-side caller pass their
    popcounts through here, as jax or NumPy integer arrays alike. int32
    holds it: a fragment's row has at most 2^20 bits, so 100*inter and
    100*denom stay under 2^31. No float stands between the popcounts
    and keep/drop: the v5e's float32 division is not correctly rounded
    and kept rows that lie exactly on a threshold (PR 23)."""
    denom = row_n + src_n - inter
    return (100 * inter > threshold * denom) & (denom > 0)


def _tanimoto_masked_counts(matrix, src, row_n, src_n, threshold):
    """Fused per-fragment Tanimoto path: src-intersection popcounts,
    the integer gate (``tanimoto_keep``) and the mask in ONE device
    program; a single host fetch of the final masked counts.
    ``threshold`` is traced, so one executable serves every
    threshold."""
    inter = bitops.count_and_rows(matrix, src)
    return jnp.where(tanimoto_keep(inter, row_n, src_n, threshold), inter, 0)


# The per-fragment program's name in a device trace (``jit_<name>`` on
# the ``XLA Modules`` line): one probe row against the whole matrix.
TANIMOTO_FRAGMENT_PROGRAM = bitops.program_name("topn_tanimoto_frag", 1)
_tanimoto_masked_counts.__name__ = TANIMOTO_FRAGMENT_PROGRAM
_tanimoto_masked_counts.__qualname__ = TANIMOTO_FRAGMENT_PROGRAM
tanimoto_masked_counts = jax.jit(_tanimoto_masked_counts)


def _tanimoto_masked_counts_at(matrix, phys, row_n, threshold):
    """``_tanimoto_masked_counts`` where the probe is row ``phys`` of
    the matrix it scans: the program takes the src from the matrix
    and ``|src|`` from the row counts it is given anyway (a row's bits
    all lie inside its own fragment's window, so that count is the
    full one). ``phys`` is traced like ``threshold``: one executable
    for every probe and every threshold."""
    return _tanimoto_masked_counts(matrix, bitops.row_at(matrix, phys),
                                   row_n, bitops.row_at(row_n, phys),
                                   threshold)


# Starts with TANIMOTO_FRAGMENT_PROGRAM's tier name, so a reader of the
# trace that matches ``jit_pilosa_topn_tanimoto_frag*`` reads both.
TANIMOTO_FRAGMENT_PROBE_PROGRAM = bitops.program_name(
    "topn_tanimoto_frag_probe", 1)
_tanimoto_masked_counts_at.__name__ = TANIMOTO_FRAGMENT_PROBE_PROGRAM
_tanimoto_masked_counts_at.__qualname__ = TANIMOTO_FRAGMENT_PROBE_PROGRAM
tanimoto_masked_counts_at = jax.jit(_tanimoto_masked_counts_at)


def fetch_counts(fn, matrix, *args, op=None):
    """The per-fragment TopN call as ``Fragment.top`` makes it: enqueue,
    device wait and the copy of the counts to the host in one
    expression; under a trace cut where the time can hide, into
    ``top.kernel`` (the jitted call until it returns, tagged
    ``scanned`` = the rows of the operand it was given and ``program``
    = ``fn``'s name, which the launch carries in a device trace after
    ``jit_``), ``top.wait``
    (``block_until_ready``) and ``top.fetch`` (``np.asarray``). With
    ``op``, a dispatch that grew ``fn``'s executable cache is noted as
    that op's compile in the kernel observatory (``/debug/kernels``)."""
    t0 = time.perf_counter()
    if tracing.active_span() is None:
        counts = np.asarray(fn(matrix, *args))
    else:
        with tracing.span("top.kernel", scanned=matrix.shape[0],
                          program=fn.__name__):
            out = fn(matrix, *args)
        with tracing.span("top.wait"):
            out.block_until_ready()
        with tracing.span("top.fetch"):
            counts = np.asarray(out)
    obs = kerneltime.ACTIVE
    if (op is not None and obs.enabled
            and obs.note_jit_cache(op, fn._cache_size())):
        obs.note(op, bitops.FMT_DENSE,
                 kerneltime.shape_bucket(matrix.nbytes),
                 time.perf_counter() - t0, compiled=True, device=True)
    return counts
