"""Sharded query kernels over a ``jax.sharding.Mesh``.

The reference scales by slicing columns into 2^20-wide slices and
map/reducing per-slice results (SURVEY §5.7): the map is embarrassingly
parallel, the reduce is associative. That maps 1:1 onto SPMD over a
device mesh:

- **slice axis** — the data-parallel dimension: per-slice row bitmaps
  shard as ``uint32[S, W]`` with S split over devices; Count/Sum reduce
  with ``psum`` over ICI (the reference's goroutine-per-node scatter +
  streaming reduce, executor.go:1502-1575).
- **row axis** — a tensor-parallel extension the reference never had
  (rows span all slices there): TopN's ``[S, R, W]`` popcount shards
  rows too, so per-row counts psum over the slice axis only.

Every kernel here is jitted once per (mesh, shape) and reads sharded
device-resident inputs, so multi-chip execution is one XLA program with
collectives — no host round-trips between map and reduce.
"""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from pilosa_tpu import WORDS_PER_SLICE
from pilosa_tpu.observe import kerneltime
from pilosa_tpu.ops import bitops


def make_mesh(n_devices=None, axis="slice"):
    """1-D device mesh over the slice axis."""
    devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    return Mesh(np.array(devices), (axis,))


# Sharded-count kernels psum int32 partials: exact while the total set
# bits a single reduce can see stays below 2^31. Callers (the mesh
# data plane) decline slice sets wider than this and fall back to the
# host reduce, which sums per-node partials in Python ints.
INT32_SAFE_SLICES = (2 ** 31 - 1) // (1 << 20)


def eval_plan(plan, args, shape):
    """Left-fold tree evaluation over ``uint32[S_blk, W]`` word blocks
    — the mesh twin of ``Executor._eval_node`` (same plan grammar: the
    batched planner's nested op tuples with leaf/planes/bits arg
    positions), duplicated here so ``parallel/`` never imports the
    executor. "bsi" nodes vmap the per-slice BSI descent kernels over
    the slice axis; "empty" is a statically-known-zero result."""
    from pilosa_tpu.ops import bsi as bsi_ops

    kind = plan[0]
    if kind == "leaf":
        return args[plan[1]]
    if kind == "empty":
        return jnp.zeros(shape, jnp.uint32)
    if kind == "bsi":
        _, ppos, bpos, bkind, op, depth = plan
        planes = args[ppos]
        exists = planes[:, depth, :]
        body = planes[:, :depth, :]
        if bkind == "between":
            return jax.vmap(bsi_ops.bsi_between,
                            in_axes=(0, 0, None, None))(
                body, exists, args[bpos[0]], args[bpos[1]])
        fn = {"==": bsi_ops.bsi_eq, "!=": bsi_ops.bsi_neq,
              "<": bsi_ops.bsi_lt, "<=": bsi_ops.bsi_lte,
              ">": bsi_ops.bsi_gt, ">=": bsi_ops.bsi_gte}[op]
        return jax.vmap(fn, in_axes=(0, 0, None))(
            body, exists, args[bpos[0]])
    out = None
    for kid in plan[1]:
        v = eval_plan(kid, args, shape)
        if out is None:
            out = v
        elif kind == "Intersect":
            out = lax.bitwise_and(out, v)
        elif kind == "Union":
            out = lax.bitwise_or(out, v)
        elif kind == "Difference":
            out = lax.bitwise_and(out, lax.bitwise_not(v))
        else:  # Xor
            out = lax.bitwise_xor(out, v)
    return out


class MeshQueryEngine:
    """Sharded map/reduce kernels bound to one mesh.

    Inputs are "slice-major" stacks: axis 0 indexes slices and is
    sharded over the mesh; padding slices (all-zero) are harmless for
    every op here because the reduces are sums/ors.
    """

    # Compiled collective programs are cached per (plan, shapes); each
    # novel shape costs an XLA compile, so the table is bounded like
    # the executor's batched-fn cache.
    TREE_FN_CACHE_MAX = 64

    def __init__(self, mesh=None):
        self.mesh = mesh or make_mesh()
        self.axis = self.mesh.axis_names[0]
        self.n_devices = self.mesh.devices.size
        self._fns = {}  # (kind, plan str, specs, shapes) -> jitted fn
        self._nv = {}   # n_valid -> committed device scalar (reused
        #                 per call: a fresh jnp.int32 would device_put
        #                 a replicated scalar on EVERY query)

    # ------------------------------------------------------------ layout

    def pad_slices(self, n):
        """Slices must split evenly over devices; round up."""
        d = self.n_devices
        return (n + d - 1) // d * d

    def shard_rows(self, host_rows):
        """np.uint32[S, W] -> device array sharded over the slice axis,
        zero-padded to a multiple of the device count. This is the HBM
        staging step — the analog of fragment open's mmap attach."""
        s = self.pad_slices(host_rows.shape[0])
        if s != host_rows.shape[0]:
            pad = np.zeros((s - host_rows.shape[0],) + host_rows.shape[1:],
                           dtype=host_rows.dtype)
            host_rows = np.concatenate([host_rows, pad])
        sharding = NamedSharding(self.mesh, P(self.axis))
        return jax.device_put(host_rows, sharding)

    # ----------------------------------------------------------- kernels

    @partial(jax.jit, static_argnums=0)
    def count_and(self, a, b):
        """|A ∩ B| over all slices: per-device fused popcount partials,
        one psum over ICI (ref reduce: executor.go:880-889)."""

        def kernel(a_blk, b_blk):
            part = jnp.sum(
                lax.population_count(lax.bitwise_and(a_blk, b_blk))
                .astype(jnp.int32))
            return lax.psum(part, self.axis)

        return shard_map(
            kernel, mesh=self.mesh,
            in_specs=(P(self.axis), P(self.axis)),
            out_specs=P())(a, b)

    @partial(jax.jit, static_argnums=0)
    def count(self, a):
        def kernel(a_blk):
            part = jnp.sum(lax.population_count(a_blk).astype(jnp.int32))
            return lax.psum(part, self.axis)

        return shard_map(kernel, mesh=self.mesh,
                         in_specs=(P(self.axis),), out_specs=P())(a)

    @partial(jax.jit, static_argnums=(0, 2))
    def nary_count(self, rows, op):
        """Count of an n-ary combine: rows uint32[S, K, W], op one of
        'and'/'or'/'xor'/'andnot' folded over K, counted over S×W, psum."""

        def kernel(blk):
            acc = blk[:, 0, :]
            for k in range(1, blk.shape[1]):
                nxt = blk[:, k, :]
                if op == "and":
                    acc = lax.bitwise_and(acc, nxt)
                elif op == "or":
                    acc = lax.bitwise_or(acc, nxt)
                elif op == "xor":
                    acc = lax.bitwise_xor(acc, nxt)
                else:
                    acc = lax.bitwise_and(acc, lax.bitwise_not(nxt))
            part = jnp.sum(lax.population_count(acc).astype(jnp.int32))
            return lax.psum(part, self.axis)

        return shard_map(kernel, mesh=self.mesh,
                         in_specs=(P(self.axis),), out_specs=P())(rows)

    @partial(jax.jit, static_argnums=0)
    def topn_counts(self, matrix):
        """Per-row global counts for TopN: uint32[S, R, W] sharded on S
        -> int32[R] replicated (psum over the slice axis). One fused
        popcount replaces the reference's per-slice cache walks."""

        def kernel(blk):
            part = jnp.sum(
                lax.population_count(blk).astype(jnp.int32), axis=(0, 2))
            return lax.psum(part, self.axis)

        return shard_map(kernel, mesh=self.mesh,
                         in_specs=(P(self.axis),), out_specs=P())(matrix)

    @partial(jax.jit, static_argnums=0)
    def topn_counts_src(self, matrix, src):
        """Per-row counts of row ∩ src: matrix uint32[S, R, W],
        src uint32[S, W] -> int32[R]."""

        def kernel(blk, src_blk):
            inter = lax.bitwise_and(blk, src_blk[:, None, :])
            part = jnp.sum(
                lax.population_count(inter).astype(jnp.int32), axis=(0, 2))
            return lax.psum(part, self.axis)

        return shard_map(kernel, mesh=self.mesh,
                         in_specs=(P(self.axis), P(self.axis)),
                         out_specs=P())(matrix, src)

    @partial(jax.jit, static_argnums=0)
    def bsi_plane_counts(self, planes, filt):
        """BSI Sum map/reduce: planes uint32[S, D, W], filter uint32[S, W]
        -> int32[D] per-plane global counts (host computes Σ 2^i·c_i)."""

        def kernel(planes_blk, filt_blk):
            inter = lax.bitwise_and(planes_blk, filt_blk[:, None, :])
            part = jnp.sum(
                lax.population_count(inter).astype(jnp.int32), axis=(0, 2))
            return lax.psum(part, self.axis)

        return shard_map(kernel, mesh=self.mesh,
                         in_specs=(P(self.axis), P(self.axis)),
                         out_specs=P())(planes, filt)

    @partial(jax.jit, static_argnums=0)
    def union_gather(self, rows):
        """OR-reduce over the slice axis then all_gather — a cross-slice
        row merge materialized on every device (the Bitmap-merge reduce,
        bitmap.go:45-155, as one collective)."""

        def kernel(blk):
            # Unrolled OR fold: XLA:CPU collectives lack OR-reductions,
            # and the per-shard slice count is small and static.
            local = blk[0]
            for i in range(1, blk.shape[0]):
                local = lax.bitwise_or(local, blk[i])
            return lax.all_gather(local, self.axis)

        out = shard_map(kernel, mesh=self.mesh,
                        in_specs=(P(self.axis),), out_specs=P(self.axis))(rows)
        acc = out[0]
        for i in range(1, out.shape[0]):
            acc = bitops.bitmap_or(acc, out[i])
        return acc

    # ------------------------------------------- planned collective cells
    #
    # The mesh data plane (cluster/meshplane.py) compiles a whole query
    # to ONE of these programs: sharded leaf stacks in, a psum'd scalar
    # or small replicated vector out. Padded slices (the device-count
    # round-up) are masked by GLOBAL slice index inside the kernel, so
    # the reduce is bit-exact even when a reused stack's padding lanes
    # hold garbage — zero-fill alone is only safe for sum-of-popcount
    # reduces, and the mask keeps non-sum reduces (thresholded TopN
    # cells, future extrema descents) on the same contract.

    def _slice_mask(self, per_shard, n_valid):
        """bool[per_shard]: True where this shard's global slice index
        is a real (unpadded) slice. Call inside a shard_map kernel."""
        gpos = (lax.axis_index(self.axis).astype(jnp.int32) * per_shard
                + jnp.arange(per_shard, dtype=jnp.int32))
        return gpos < n_valid

    def _tree_fn(self, kind, plan, specs, shapes, build):
        key = (kind, str(plan), tuple(specs), tuple(shapes))
        fn = self._fns.get(key)
        if fn is None:
            while len(self._fns) >= self.TREE_FN_CACHE_MAX:
                self._fns.pop(next(iter(self._fns)))
            fn = self._fns[key] = build()
        return fn

    def _launch(self, kind, fn, n_valid, args):
        """One launch of a collective program (``bitops.launch``), its
        replicated result on the host: noted as ``mesh_<kind>`` in the
        ``collective`` cell, a size class by the real slices."""
        return bitops.launch(
            "mesh_" + kind, fn, self._nv_arg(n_valid), *args,
            cut=bitops.NO_CUT, cell="collective",
            bucket=kerneltime.shape_bucket(n_valid * WORDS_PER_SLICE * 4))

    def _nv_arg(self, n_valid):
        arr = self._nv.get(n_valid)
        if arr is None:
            if len(self._nv) > 4096:
                self._nv.clear()
            arr = self._nv[n_valid] = jnp.int32(n_valid)
        return arr

    def _in_specs(self, specs):
        return tuple(P(self.axis) if s == "slice" else P()
                     for s in specs)

    def tree_count(self, plan, args, specs, n_valid):
        """|tree| over all real slices as ONE collective program:
        eval_plan fold + per-slice popcount, padded lanes masked, one
        ``psum`` over the slice axis (the reference's streaming count
        reduce, executor.go:880-889, as a single collective). int32
        partials — callers bound n_valid by INT32_SAFE_SLICES."""
        shapes = tuple(a.shape for a in args)
        s_idx = specs.index("slice")
        per = shapes[s_idx][0] // self.n_devices
        width = shapes[s_idx][-1]
        mask_fn = self._slice_mask

        def build():
            def kernel(nv, *blks):
                out = eval_plan(plan, blks, (per, width))
                cnt = jnp.sum(
                    lax.population_count(out).astype(jnp.int32), axis=1)
                part = jnp.sum(jnp.where(mask_fn(per, nv), cnt, 0))
                return lax.psum(part, self.axis)

            return jax.jit(shard_map(
                kernel, mesh=self.mesh,
                in_specs=(P(),) + self._in_specs(specs), out_specs=P()))

        fn = self._tree_fn("count", plan, specs, shapes, build)
        return self._launch("count", fn, n_valid, args)

    def topn_tree_counts(self, matrix, src_plan, src_args, specs,
                         n_valid):
        """TopN's exact re-count as one collective: ``matrix``
        uint32[S, R, W] sharded on S, optional src tree folded from
        its own sharded leaf stacks, -> int32[R] replicated global
        counts (psum over the slice axis). The masked padding is what
        makes the per-row counts safe to threshold afterwards: a
        garbage pad lane can neither create nor destroy a candidate."""
        all_args = (matrix,) + tuple(src_args)
        all_specs = ("slice",) + tuple(specs)
        shapes = tuple(a.shape for a in all_args)
        per = matrix.shape[0] // self.n_devices
        width = matrix.shape[-1]
        mask_fn = self._slice_mask

        def build():
            def kernel(nv, blk, *src_blks):
                if src_plan is not None:
                    src = eval_plan(src_plan, src_blks, (per, width))
                    inter = lax.bitwise_and(blk, src[:, None, :])
                else:
                    inter = blk
                cnt = jnp.sum(
                    lax.population_count(inter).astype(jnp.int32),
                    axis=2)                                     # [per, R]
                cnt = jnp.where(mask_fn(per, nv)[:, None], cnt, 0)
                return lax.psum(jnp.sum(cnt, axis=0), self.axis)

            return jax.jit(shard_map(
                kernel, mesh=self.mesh,
                in_specs=(P(),) + self._in_specs(all_specs),
                out_specs=P()))

        fn = self._tree_fn("topn", src_plan, all_specs, shapes, build)
        return self._launch("topn", fn, n_valid, all_args)

    def bsi_sum_counts(self, planes, filt_plan, filt_args, specs,
                       n_valid):
        """BSI Sum as one collective: planes uint32[S, depth+1, W]
        (plane ``depth`` is the exists row) sharded on S, optional
        filter tree -> int32[depth+1] replicated — per-plane global
        counts followed by the filtered-exists count; the host computes
        Σ 2^i·c_i + base·count in arbitrary-precision ints."""
        depth = planes.shape[1] - 1
        all_args = (planes,) + tuple(filt_args)
        all_specs = ("slice",) + tuple(specs)
        shapes = tuple(a.shape for a in all_args)
        per = planes.shape[0] // self.n_devices
        width = planes.shape[-1]
        mask_fn = self._slice_mask

        def build():
            def kernel(nv, blk, *filt_blks):
                exists = blk[:, depth, :]
                if filt_plan is not None:
                    filt = lax.bitwise_and(
                        exists,
                        eval_plan(filt_plan, filt_blks, (per, width)))
                else:
                    filt = exists
                # Masking the FILTER zeroes every downstream count of
                # a padded slice in one place.
                filt = jnp.where(mask_fn(per, nv)[:, None], filt,
                                 jnp.uint32(0))
                inter = lax.bitwise_and(blk[:, :depth, :],
                                        filt[:, None, :])
                counts = jnp.sum(
                    lax.population_count(inter).astype(jnp.int32),
                    axis=(0, 2))                                # [depth]
                fc = jnp.sum(
                    lax.population_count(filt).astype(jnp.int32))
                return lax.psum(
                    jnp.concatenate([counts, fc[None]]), self.axis)

            return jax.jit(shard_map(
                kernel, mesh=self.mesh,
                in_specs=(P(),) + self._in_specs(all_specs),
                out_specs=P()))

        fn = self._tree_fn("bsi_sum", filt_plan, all_specs, shapes,
                           build)
        return self._launch("sum", fn, n_valid, all_args)

    def bsi_range_count(self, planes, op, bits, n_valid, hi_bits=None):
        """|columns matching a BSI condition| as one collective — the
        Range-condition reduction cell: vmapped bit-descent per slice,
        masked padding, one psum. ``op`` is a comparison operator or
        "><" with ``hi_bits`` for BETWEEN; ``bits`` / ``hi_bits`` are
        value_to_bits vectors (replicated args)."""
        depth = planes.shape[1] - 1
        if op == "><":
            plan = ("bsi", 0, (1, 2), "between", "", depth)
            args = (planes, bits, hi_bits)
            specs = ("slice", "rep", "rep")
        else:
            plan = ("bsi", 0, (1,), "cmp", op, depth)
            args = (planes, bits)
            specs = ("slice", "rep")
        return self.tree_count(plan, args, specs, n_valid)


def full_query_step(engine, frag_rows, src_rows, planes, filt):
    """One end-to-end multi-chip "step": the flagship distributed query
    mix — Count(Intersect), TopN counts, and BSI Sum — compiled as one
    jitted program over the mesh. Used by the multi-chip dry run.
    """

    @jax.jit
    def step(frag_rows, src_rows, planes, filt):
        c = engine.count_and(src_rows, filt)
        t = engine.topn_counts(frag_rows)
        b = engine.bsi_plane_counts(planes, filt)
        u = engine.union_gather(src_rows)
        return c, t, b, u

    return step(frag_rows, src_rows, planes, filt)
