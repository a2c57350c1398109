"""Hand-blocked Pallas TPU kernels for the count-only hot paths.

The reference's count fast paths (``intersectionCount*`` kernels,
roaring/roaring.go:1811-1923, built on ``popcountAndSlice`` :3242-3283)
never materialize the intermediate bitmap. XLA already fuses
``popcount(a & b) -> sum`` the same way; these Pallas kernels exist to
squeeze the last HBM bandwidth out of the fusion by controlling VMEM
block shapes and accumulating partials in SMEM/VMEM scratch instead of
XLA's generic reduce schedule.

All kernels are count-only reductions over ``uint32`` words:

- :func:`count_and`     — popcount(a & b)           (Count(Intersect))
- :func:`count_rows`    — per-row popcount of a matrix (TopN counts)
- :func:`count_and_rows`— per-row popcount(matrix & filter) (TopN Src /
  BSI plane counts / Tanimoto numerators)

Not measured on current code: the production paths in
:mod:`pilosa_tpu.ops.bitops` stay on XLA's own fusion, nothing routes
through this module, and ``chip_smoke.py`` only proves the kernels
compile under Mosaic and agree with XLA (ROADMAP D10 decides whether
they stay).

Every kernel takes ``interpret``: False (the default) compiles for the
TPU; tests pass True to run the same bodies in the Pallas interpreter
on the CPU mesh. The mode is something the caller asks for, never a
backend sniff.
"""
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# Block geometry. A slice row is 32768 uint32 words; (8, 2048) int32
# blocks are 64 KiB each, 8-sublane aligned, and give a (S/8, W/2048)
# grid with enough steps to double-buffer HBM→VMEM copies. Inputs whose
# word count is not a multiple of 128 lanes are zero-padded by the
# wrappers (popcount of zero words contributes nothing).
_LANE = 128
_SUB = 8


def _block_w(w: int) -> int:
    for cand in (2048, 1024, 512, 256, _LANE):
        if w % cand == 0:
            return cand
    raise AssertionError(f"width {w} not lane-padded")  # _pad_lanes guarantees


def _block_r(r: int) -> int:
    assert r % _SUB == 0, f"rows {r} not sublane-padded"  # _pad_rows guarantees
    return _SUB


def _pad_lanes(x):
    """Zero-pad the trailing word axis to a multiple of 128 lanes."""
    w = x.shape[-1]
    rem = w % _LANE
    if rem == 0:
        return x
    pad = [(0, 0)] * (x.ndim - 1) + [(0, _LANE - rem)]
    return jnp.pad(x, pad)


def _pad_rows(x):
    """Zero-pad the row axis to a multiple of 8 sublanes — Mosaic
    requires block shapes divisible by (8, 128). Zero rows count zero;
    per-row outputs are trimmed back by the wrappers."""
    r = x.shape[0]
    rem = r % _SUB
    if rem == 0:
        return x
    pad = [(0, _SUB - rem)] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, pad)


# ---------------------------------------------------------------------------
# scalar count of a & b over [S, W]
# ---------------------------------------------------------------------------

def _count_and_kernel(a_ref, b_ref, out_ref, acc_ref):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when((i == 0) & (j == 0))
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    words = lax.bitwise_and(a_ref[:], b_ref[:])
    # Vector partial accumulate — keep the reduction on the VPU lanes;
    # collapse to a scalar only once, on the final grid step.
    pc = lax.population_count(words).astype(jnp.int32)
    acc_ref[:] += jnp.sum(pc.reshape(-1, _LANE), axis=0, keepdims=True)

    @pl.when((i == pl.num_programs(0) - 1) & (j == pl.num_programs(1) - 1))
    def _():
        out_ref[0, 0] = jnp.sum(acc_ref[:])


@partial(jax.jit, static_argnames=("interpret",))
def count_and(a, b, interpret=False):
    """popcount(a & b) -> int32 scalar; a, b: uint32[S, W]."""
    if a.ndim == 1:
        a = a[None, :]
        b = b[None, :]
    a, b = _pad_rows(_pad_lanes(a)), _pad_rows(_pad_lanes(b))
    s, w = a.shape
    bs, bw = _block_r(s), _block_w(w)
    grid = (s // bs, w // bw)
    out = pl.pallas_call(
        _count_and_kernel,
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.int32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bs, bw), lambda i, j: (i, j)),
            pl.BlockSpec((bs, bw), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((1, 1), lambda i, j: (0, 0),
                               memory_space=pltpu.SMEM),
        scratch_shapes=[pltpu.VMEM((1, _LANE), jnp.int32)],
        interpret=interpret,
    )(a, b)
    return out[0, 0]


# ---------------------------------------------------------------------------
# per-row counts of matrix [R, W] & filter [W]
# ---------------------------------------------------------------------------

def _count_and_rows_kernel(m_ref, f_ref, out_ref, acc_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    br = acc_ref.shape[0]
    words = lax.bitwise_and(m_ref[:], f_ref[:])
    pc = lax.population_count(words).astype(jnp.int32)
    acc_ref[:] += jnp.sum(pc.reshape(br, -1, _LANE), axis=1)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        out_ref[:] = jnp.sum(acc_ref[:], axis=1, keepdims=True)


@partial(jax.jit, static_argnames=("interpret",))
def count_and_rows(m, filt, interpret=False):
    """Per-row popcount(m & filt): uint32[R, W], uint32[W] -> int32[R]."""
    n_rows = m.shape[0]
    m, filt = _pad_rows(_pad_lanes(m)), _pad_lanes(filt)
    r, w = m.shape
    br, bw = _block_r(r), _block_w(w)
    out = pl.pallas_call(
        _count_and_rows_kernel,
        out_shape=jax.ShapeDtypeStruct((r, 1), jnp.int32),
        grid=(r // br, w // bw),
        in_specs=[
            pl.BlockSpec((br, bw), lambda i, j: (i, j)),
            pl.BlockSpec((1, bw), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((br, 1), lambda i, j: (i, 0)),
        scratch_shapes=[pltpu.VMEM((br, _LANE), jnp.int32)],
        interpret=interpret,
    )(m, filt[None, :])
    return out[:n_rows, 0]


@partial(jax.jit, static_argnames=("interpret",))
def count_rows(m, interpret=False):
    """Per-row popcount: uint32[R, W] -> int32[R].

    Routed through :func:`count_and_rows` with an all-ones filter so
    there is exactly one row-reduction kernel body to tune; the extra
    filter read is W words against R×W read for the matrix.
    """
    return count_and_rows(m, jnp.full((m.shape[-1],), 0xFFFFFFFF,
                                      dtype=jnp.uint32),
                          interpret=interpret)
