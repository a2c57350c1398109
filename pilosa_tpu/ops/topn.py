"""TopN device kernels.

The reference's TopN walks a host-side ranked cache with a min-heap and
early-exit thresholds (fragment.go:831-963) because per-row counts are
expensive on CPU. On TPU a full per-row popcount over the fragment's row
matrix is one fused kernel, so the primary path is: popcount all rows
(optionally ∩ a source/filter bitmap) → ``lax.top_k``. The ranked cache
is kept host-side for API parity and warm-start, but correctness does
not depend on it.
"""
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax


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


def tanimoto_score_counts(inter, row_n, src_n):
    """Traceable Tanimoto ×100 from popcount triples (ref:
    fragment.go:850-858): 100·|A∩B| / (|A|+|B|−|A∩B|), 0 when the
    denominator is 0. The single source of the score formula — both the
    per-fragment path and the executor's batched phase-2 kernel trace
    through here, so their float32 arithmetic is identical per backend.
    """
    denom = row_n + src_n - inter
    return jnp.where(
        denom > 0, 100.0 * inter.astype(jnp.float32) / denom.astype(jnp.float32), 0.0
    )


@jax.jit
def tanimoto_masked_counts(matrix, src, row_n, src_n, threshold):
    """Fused per-fragment Tanimoto path: src-intersection popcounts,
    scores, ceil-gate and mask in ONE device program — a single host
    fetch of the final masked counts where the unfused pipeline paid
    ~4 host↔device round trips per query; the score/gate semantics
    are exactly
    tanimoto_score_counts + the ceil(score) > threshold rule of
    fragment.go:908-918, evaluated on device."""
    from pilosa_tpu.ops import bitops

    inter = bitops.count_and_rows(matrix, src)
    scores = tanimoto_score_counts(inter, row_n, src_n)
    keep = jnp.ceil(scores) > threshold
    return jnp.where(keep, inter, 0)


def tanimoto_keep(scores, threshold):
    """Host-side threshold gate (ref: fragment.go:908-918): keep rows
    whose ceil(score) is STRICTLY greater than the threshold."""
    import numpy as np

    return np.ceil(np.asarray(scores)) > threshold


