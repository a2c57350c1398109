"""TopN device kernels.

The reference's TopN walks a host-side ranked cache with a min-heap and
early-exit thresholds (fragment.go:831-963) because per-row counts are
expensive on CPU. On TPU a full per-row popcount over the fragment's row
matrix is one fused kernel, so a per-fragment TopN with a src is one
program: the popcounts of ``row ∩ src`` for every row of the mirror,
the Tanimoto gate in integers (``tanimoto_keep``) and, where the
request allows it (``Fragment.top`` says when), the selection as well
(``_select_top``): eligibility, the ``K`` largest masked counts with
their physical rows and how many rows tie at or above the n-th, in one
output of ``2K + 1`` int32 for the host to order by ``(-count, id)``
and cut at ``n``. Exact: no approximate operator and no float anywhere;
where more rows tie at the cut than ``K`` holds the host takes all the
counts instead (``fetch_counts`` of the programs without the tail) and
selects as it does for explicit ids and attribute filters. The ranked
cache is kept host-side for API parity and warm-start; it names the
rows a TopN may return (the ``elig`` operand), the counts are always
the device's.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from pilosa_tpu import tracing
from pilosa_tpu.observe import kerneltime
from pilosa_tpu.ops import bitops


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


def _program(fn, tier, **jit_options):
    """``fn`` jitted under the per-fragment program's name in a device
    trace (``jit_<name>`` on the ``XLA Modules`` line): one probe row
    against the whole matrix. Every tier here starts with
    ``topn_tanimoto_frag``, so a reader of the trace that matches
    ``jit_pilosa_topn_tanimoto_frag*`` reads them all."""
    fn.__name__ = fn.__qualname__ = bitops.program_name(tier, 1)
    return jax.jit(fn, **jit_options)


tanimoto_masked_counts = _program(_tanimoto_masked_counts,
                                  "topn_tanimoto_frag")
TANIMOTO_FRAGMENT_PROGRAM = tanimoto_masked_counts.__name__


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


tanimoto_masked_counts_at = _program(_tanimoto_masked_counts_at,
                                     "topn_tanimoto_frag_probe")
TANIMOTO_FRAGMENT_PROBE_PROGRAM = tanimoto_masked_counts_at.__name__


# ---------------------------------------------------------------------------
# The selection inside the scan's program.
# ---------------------------------------------------------------------------

# The largest bucket the device selects into; a TopN that asks for more
# (n above 512) is selected on the host.
SELECT_MAX_K = 1024
# Rows a chunk of ``_top_k_exact``: a lane's width on the chip.
SELECT_CHUNK = 128


def select_k(n):
    """The static size of the device's selection for a TopN of ``n``:
    the power of two at or above ``2 * n``, at least 64, so that every
    ``n`` of a bucket shares one executable and ties across the cut
    have room (``n`` = 50 gives 128)."""
    return max(64, 1 << (2 * n - 1).bit_length())


def _top_k_exact(cand, k):
    """(values int32[k'], rows int32[k']) of the ``k' = min(k, rows)``
    largest of ``cand``, values descending; which rows of a tie are
    named is the device's choice.

    Over more than ``k`` chunks of ``SELECT_CHUNK`` rows the sort never
    sees the whole array: the maximum of every chunk (a reduction), the
    ``k`` chunks with the largest maxima, and the ``k`` largest of
    their ``k x SELECT_CHUNK`` rows. The values are the true ``k``
    largest: a row left out with its chunk is no larger than that
    chunk's maximum, which is no larger than the maximum of any of the
    ``k`` chunks taken, each of which holds a row at its maximum. A flat
    ``lax.top_k`` over 524,288 rows is a 0.46 ms sort on a v5e; this is
    0.02 ms (PR 36's sizing)."""
    rows = cand.shape[0]
    chunks = rows // SELECT_CHUNK
    if chunks <= k:
        return lax.top_k(cand, min(k, rows))
    by_chunk = cand.reshape(chunks, SELECT_CHUNK)
    _, top_chunks = lax.top_k(jnp.max(by_chunk, axis=1), k)
    vals, at = lax.top_k(by_chunk[top_chunks].reshape(-1), k)
    return vals, top_chunks[at // SELECT_CHUNK] * SELECT_CHUNK \
        + at % SELECT_CHUNK


def _select_top(masked, elig, min_threshold, n, k):
    """The one select tail of the per-fragment programs: int32[2k' + 1]
    holding the ``k'`` largest eligible masked counts (descending),
    their physical rows, and ``n_ge``: how many eligible rows have a
    masked count at or above the n-th largest (and above 0).

    Eligible is ``elig`` (the row is in the ranked cache; false past
    the last physical row) and ``masked >= min_threshold``, never below
    1, so zero counts and padded rows fall out as they do in
    ``Fragment._top_select``; an ineligible row reads 0 here. The host
    may cut at ``n`` by ``(-count, id)`` over these pairs alone exactly
    when ``n_ge <= k'``: every row whose count is at least the n-th
    largest is then among them, whatever order the device broke ties
    in (rows above the k'-th value are in any top k'; if the n-th value
    equals the k'-th, at most ``k'`` rows lie at or above it, so all are
    in; with chunks, ``n_ge <= k`` bounds the chunks that hold such a
    row by ``k`` as well, so none was left out). ``n_ge`` is counted
    over all the rows, not over the pairs."""
    floor = jnp.maximum(min_threshold, 1)
    cand = jnp.where(elig & (masked >= floor), masked, 0)
    vals, rows = _top_k_exact(cand, k)
    nth = lax.dynamic_index_in_dim(vals, jnp.minimum(n, vals.shape[0]) - 1,
                                   keepdims=False)
    n_ge = jnp.sum((cand >= jnp.maximum(nth, 1)).astype(jnp.int32))
    return jnp.concatenate([vals, rows.astype(jnp.int32), n_ge[None]])


def _tanimoto_select(matrix, src, scalars, row_n, elig, k):
    """``_tanimoto_masked_counts`` with the select tail. The request's
    scalars come as ONE host operand, int32[4]: ``|src|``, the Tanimoto
    threshold, ``min_threshold`` and ``n`` (each host operand delays
    the launch on the calling thread). A threshold of 0 is no gate:
    ``tanimoto_keep`` then reads ``inter > 0``, the row counts being
    exact."""
    masked = _tanimoto_masked_counts(matrix, src, row_n, scalars[0],
                                     scalars[1])
    return _select_top(masked, elig, scalars[2], scalars[3], k)


def _tanimoto_select_at(matrix, scalars, row_n, elig, k):
    """``_tanimoto_masked_counts_at`` with the select tail; ``scalars``
    as ``_tanimoto_select`` takes them, with the probe's physical row
    in place of ``|src|``."""
    masked = _tanimoto_masked_counts_at(matrix, scalars[0], row_n,
                                        scalars[1])
    return _select_top(masked, elig, scalars[2], scalars[3], k)


tanimoto_select = _program(_tanimoto_select, "topn_tanimoto_frag_select",
                           static_argnames=("k",))
tanimoto_select_at = _program(_tanimoto_select_at,
                              "topn_tanimoto_frag_probe_select",
                              static_argnames=("k",))


def fetch_counts(fn, matrix, *args, op=None, **static):
    """The per-fragment TopN call as ``Fragment.top`` makes it: enqueue,
    device wait and the copy of the program's one output (a count a
    row of the mirror, or a selection: ``static`` is its ``k``) to the
    host in one expression; under a trace cut where the time can hide,
    into ``top.kernel`` (the jitted call until it returns, tagged
    ``scanned`` = the rows of the operand it was given and ``program``
    = ``fn``'s name, which the launch carries in a device trace after
    ``jit_``), ``top.wait``
    (``block_until_ready``) and ``top.fetch`` (``np.asarray``). With
    ``op``, a dispatch that grew ``fn``'s executable cache is noted as
    that op's compile in the kernel observatory (``/debug/kernels``)."""
    t0 = time.perf_counter()
    if tracing.active_span() is None:
        counts = np.asarray(fn(matrix, *args, **static))
    else:
        with tracing.span("top.kernel", scanned=matrix.shape[0],
                          program=fn.__name__):
            out = fn(matrix, *args, **static)
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
