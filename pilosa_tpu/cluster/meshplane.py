"""Collective data plane — intra-pod query fan-out as ONE shard_map
program instead of per-node HTTP.

PAPER.md §7 is explicit that the reference's goroutine-per-node HTTP
scatter/gather becomes JAX collectives over ICI/DCN, yet until this
tier every multi-node query serialized protobuf over sockets between
chips wired at hundreds of GB/s — and the PR 10 ``--phases`` capture
shows fan-out/dispatch, not kernels, dominating per-query cost under
concurrency. This module is the two-tier answer (ROADMAP item 3):

- **within a pod** (one JAX process group sharing one device set —
  operationally: nodes registered under the same ``[mesh] group``):
  slice stacks live as sharded device arrays (``NamedSharding`` over
  the slice axis of a ``Mesh``) and Count/Intersect/Union/Difference/
  Xor/TopN/Sum reduce via ``psum`` inside one ``shard_map`` program
  per query (``parallel/mesh.py`` tree cells). The executor's
  ``_map_reduce`` consults this plane BEFORE the HTTP fan-out; a
  served query never opens a socket.
- **across pods** (or whenever the plane declines): the existing
  HTTP + epoch + placement machinery runs untouched. Every decline is
  counted by reason (``pilosa_mesh_fallback_total{reason=}``), so the
  two tiers are observable as one routing decision.

Membership is a process-global **peer-group registry**: each server
whose config enables the plane registers (host → plane) under its
group name. Registration is the liveness signal — a closing node
unregisters before its listener drains, and a query staged against
its holder after that raises and falls back to HTTP. In-process
multi-node clusters (the test/bench topology — and the single-host
many-chips deployment this emulates) share one registry by
construction; separate OS processes never see each other's registry
and therefore never falsely claim mesh residency.

Validity rides the PR 6 plan-cache protocol: the slice→owner cover
memo keys on the cluster topology state (which folds in the placement
generation/version, PR 10) plus the registry version; staged stacks
carry (mutation epoch, topology state, registry version) tokens —
in-process peers share the module-global epoch counters
(storage/fragment.py), so a write on ANY member invalidates the
coordinator's stacks immediately. During a live resize the plane
declines while the placement is in TRANSITION (stream in flight; the
old generation is authoritative but moving) and resumes at COMMITTED
(every moved fragment checksum-verified, reads prefer the new
generation) — queries fall back to HTTP mid-transition and return to
the collective path at commit with zero failed ops.
"""
import logging
import threading
from collections import OrderedDict

import numpy as np

from pilosa_tpu import WORDS_PER_SLICE, lockcheck, querystats, tracing
from pilosa_tpu.cluster.placement import PHASE_TRANSITION
from pilosa_tpu.plancache import slice_key
from pilosa_tpu.storage import fragment as _frag

logger = logging.getLogger(__name__)

# try_collective's "not served here" sentinel: distinct from every real
# reduce result (None is a legitimate empty result for some reduces).
DECLINED = object()

# Fixed decline vocabulary, pre-seeded so the /metrics series exist
# from boot (a zero-valued family is diffable; an absent one is not):
#   unsupported — call shape the plane doesn't compile (bitmap
#                 materialization, Min/Max, TopN discovery, filters)
#   no_group    — the group has no other registered member to cover
#                 remote-owned slices
#   not_resident— some owner host is outside the registered group
#   transition  — placement mid-resize (stream in flight)
#   plan        — the batched planner declined the tree
#   budget      — a stack exceeds the [mesh] stack-bytes budget
#   int32       — slice set wider than the int32 psum contract
#   schema      — frame/field missing (serial path owns the error)
#   error       — unexpected failure; logged, query falls back
FALLBACK_REASONS = ("unsupported", "no_group", "not_resident",
                    "transition", "plan", "budget", "int32", "schema",
                    "error")

KINDS = ("count", "topn", "sum")

DEFAULT_GROUP = "local"
DEFAULT_STACK_BYTES = 1 << 30

# Smallest device-stack window (uint32 words) — matches the batched
# executor's MIN_WIN32 so clustered data compiles the same shapes.
MIN_WIN32 = 128


class MeshDecline(Exception):
    """Internal control flow: this query falls back to HTTP, counted
    under ``reason``."""

    def __init__(self, reason):
        super().__init__(reason)
        self.reason = reason


def _topn_static_gate(ex, call):
    """TopN's static mesh-eligibility gate — explicit-id recount
    only, no tanimoto/threshold/filter semantics (those apply per
    NODE partial over HTTP, which a global psum can't reproduce).
    Returns (sorted unique row_ids, frame_name, view) or raises
    MeshDecline. ONE implementation shared by ``_collective_topn`` and
    ``explain_decision`` so the twin cannot drift."""
    row_ids, has_ids = call.uint_slice_arg("ids")
    if not has_ids or not row_ids:
        raise MeshDecline("unsupported")
    frame_name, view, _n, min_threshold, tanimoto = \
        ex._topn_call_params(call)
    if (tanimoto or min_threshold > 1
            or (call.args.get("field")
                and call.args.get("filters") is not None)):
        raise MeshDecline("unsupported")
    return sorted(set(row_ids)), frame_name, view


def _sum_static_gate(ex, index, call):
    """Sum/Average's static schema gate: (frame_name, field_name,
    field) or MeshDecline("schema"/"unsupported"). Shared by
    ``_collective_sum`` and ``explain_decision``."""
    from pilosa_tpu import errors as perr

    frame_name = call.args.get("frame") or ""
    field_name = call.args.get("field") or ""
    frame = ex.holder.index(index).frame(frame_name)
    if frame is None:
        raise MeshDecline("schema")
    try:
        field = frame.field(field_name)
    except perr.ErrFieldNotFound:
        raise MeshDecline("schema")
    if len(call.children) > 1:
        raise MeshDecline("unsupported")
    return frame_name, field_name, field


# ------------------------------------------------------ peer-group registry

_registry_mu = lockcheck.register("meshplane._registry_mu",
                                  threading.Lock())

# ONE collective program in flight per process: XLA:CPU collectives
# rendezvous all participants of a launch on the shared device set,
# and two concurrent shard_map launches can each hold a subset of the
# per-device execution slots the other needs — a cross-program
# deadlock observed under concurrent serving (meshcheck's resize
# soak). Serializing launch→result is the same funnel the PR 12
# coalescer applies to batched kernels, process-global because every
# plane in this process shares the one device set.
_dispatch_mu = lockcheck.register("meshplane._dispatch_mu",
                                  threading.Lock(),
                                  allow_device_sync=True)
_groups = {}          # group name -> {host: MeshPlane}
_groups_version = 0   # bumps on every (un)registration


def _bump_registry_locked():
    global _groups_version
    _groups_version += 1


def registry_version():
    with _registry_mu:
        return _groups_version


def group_members(group):
    """Snapshot {host: plane} for ``group``."""
    with _registry_mu:
        return dict(_groups.get(group) or ())


class MeshPlane:
    """One node's view of its mesh peer group.

    Thread-safe: ``try_collective`` runs concurrently from handler
    threads; the stack cache is one OrderedDict under a short lock,
    and device staging/dispatch never holds it.
    """

    def __init__(self, holder, cluster, host, group=DEFAULT_GROUP,
                 stack_bytes=DEFAULT_STACK_BYTES, engine=None):
        self.holder = holder
        self.cluster = cluster
        self.local_host = host
        self.group = group or DEFAULT_GROUP
        self.stack_bytes = int(stack_bytes or DEFAULT_STACK_BYTES)
        self._engine = engine
        self._mu = lockcheck.register("meshplane.MeshPlane._mu",
                                      threading.Lock())
        self._stacks = OrderedDict()  # key -> (token, array, nbytes)
        self._bits = {}  # (bits tuple, depth) -> replicated device arg
        self._stack_bytes = 0
        self._stats = {
            "launches": {k: 0 for k in KINDS},
            "fallbacks": {r: 0 for r in FALLBACK_REASONS},
            "stack_hits": 0, "stack_misses": 0, "stack_evictions": 0,
        }

    # ------------------------------------------------------------ members

    @property
    def engine(self):
        """Lazily built MeshQueryEngine over the local device set —
        construction must not force backend init on servers that never
        serve a collective query."""
        eng = self._engine
        if eng is None:
            from pilosa_tpu.parallel.mesh import MeshQueryEngine

            eng = self._engine = MeshQueryEngine()
        return eng

    def register(self):
        with _registry_mu:
            _groups.setdefault(self.group, {})[self.local_host] = self
            _bump_registry_locked()
        return self

    def set_local_host(self, host):
        """A ':0' bind resolved to a real port (server.open): re-key
        the registration so owner-host lookups match."""
        if host == self.local_host:
            return
        with _registry_mu:
            g = _groups.setdefault(self.group, {})
            if g.get(self.local_host) is self:
                del g[self.local_host]
            g[host] = self
            _bump_registry_locked()
        self.local_host = host

    def close(self):
        """Unregister BEFORE the server drains: peers stop routing
        collective reads at our holder the moment we leave."""
        with _registry_mu:
            g = _groups.get(self.group)
            if g and g.get(self.local_host) is self:
                del g[self.local_host]
                _bump_registry_locked()

    # ------------------------------------------------------------- serving

    def try_collective(self, ex, index, call, slices):
        """Serve ``call`` over ``slices`` as one collective program,
        or return DECLINED (counted by reason) so ``_map_reduce``
        proceeds to the HTTP fan-out. The returned value is exactly
        what the fan-out's reduce over the same slices would produce —
        bit-exact by the tree cells' contract."""
        name = call.name
        if name == "Count":
            kind = "count"
        elif name == "TopN":
            kind = "topn"
        elif name in ("Sum", "Average"):
            kind = "sum"
        else:
            return self._decline("unsupported")
        try:
            owners = self._owners(ex, index, slices)
            with tracing.span("mesh.collective", kind=kind,
                              slices=len(slices)):
                run = {"count": self._collective_count,
                       "topn": self._collective_topn,
                       "sum": self._collective_sum}[kind]
                out = run(ex, index, call, slices, owners)
                with self._mu:
                    self._stats["launches"][kind] += 1
                querystats.note_tier("mesh")
                return out
        except MeshDecline as d:
            return self._decline(d.reason)
        except Exception:  # noqa: BLE001 — HTTP fan-out is the backstop
            logger.warning("mesh collective failed; falling back to "
                           "HTTP fan-out", exc_info=True)
            return self._decline("error")

    def _decline(self, reason):
        with self._mu:
            self._stats["fallbacks"][reason] += 1
        # Per-query attribution (the aggregate counter above answers
        # "how often"; this answers "why was THIS query slow"): the
        # decline hop rides the active profile/explain accumulator
        # into ?profile=true, the slow-query ring, and trace spans.
        querystats.note_fallback("mesh", reason)
        return DECLINED

    def explain_decision(self, ex, index, call, slices):
        """Read-only prediction of what ``try_collective`` would do:
        ("served", None) or ("declined", reason). Every static gate
        is the SAME predicate the serving path runs
        (``_coverage_decline``, ``_topn_static_gate``,
        ``_sum_static_gate`` — shared so the twin cannot drift), but
        it never stages a stack, launches a program, or writes a
        cache/memo entry — the explain-only contract."""
        name = call.name
        try:
            if name == "Count":
                if len(call.children) != 1:
                    return "declined", "unsupported"
            elif name == "TopN":
                _topn_static_gate(ex, call)
            elif name in ("Sum", "Average"):
                _sum_static_gate(ex, index, call)
            else:
                return "declined", "unsupported"
        except MeshDecline as d:
            return "declined", d.reason
        except Exception:  # noqa: BLE001 — serial path owns the error
            return "declined", "unsupported"
        reason = self._coverage_decline(slices)
        if reason is not None:
            return "declined", reason
        from pilosa_tpu.observe import explain as explain_mod

        # Count's tree — and TopN's src / Sum's filter child when
        # present — must compile through the batched planner, exactly
        # like _collective_count/_collective_topn/_collective_sum.
        if name == "Count" or call.children:
            plan, _leaves = explain_mod.plan_readonly(
                ex, index, call.children[0])
            if plan is None:
                return "declined", "plan"
        # Residency probe SAMPLED like the explain owner summary — a
        # static prediction over a 9,540-slice universe must not pay
        # a per-slice ownership walk per explain (the serving path's
        # own _owners check is exact and plan-cache-memoized; this
        # read-only twin trades edge-case exactness for O(1)-ish
        # cost).
        members = group_members(self.group)
        for s in explain_mod._sample(slices,
                                     explain_mod.OWNER_SAMPLE_SLICES):
            nodes = self.cluster.fragment_nodes(index, s)
            h = nodes[0].host if nodes else None
            if h is None or h not in members:
                return "declined", "not_resident"
        return "served", None

    # ------------------------------------------------------------ coverage

    def _coverage_decline(self, slices):
        """The static coverage gates (slice width vs the int32 psum
        contract, placement TRANSITION, group membership) as a
        reason-or-None predicate — ONE implementation shared by the
        serving path (``_owners``, which raises) and the explain twin
        (``explain_decision``), so the two can never drift."""
        if not slices:
            return "unsupported"
        from pilosa_tpu.parallel.mesh import INT32_SAFE_SLICES

        if len(slices) > INT32_SAFE_SLICES:
            return "int32"
        cl = self.cluster
        pl = getattr(cl, "placement", None)
        if pl is not None and pl.active \
                and pl.mesh_view()[1] == PHASE_TRANSITION:
            # Stream in flight: the old generation is authoritative
            # but fragments are moving — serve over HTTP until commit
            # verifies the new owners. mesh_view is ONE consistent
            # read of (generation, phase, host order).
            return "transition"
        members = group_members(self.group)
        if len(members) <= 1 and len(cl.nodes) > 1:
            return "no_group"
        return None

    def _owners(self, ex, index, slices):
        """Preferred-owner host per slice, all of them registered group
        members — or a MeshDecline. Memoized in the PR 6 plan cache
        against (topology state ⊇ placement generation/version,
        registry version), so the per-slice fragment_nodes walk runs
        once per topology/registration change, not per query."""
        reason = self._coverage_decline(slices)
        if reason is not None:
            raise MeshDecline(reason)
        cl = self.cluster
        members = group_members(self.group)
        state = (cl.topology_state(), registry_version())
        key = ("meshcover", index, slice_key(slices))
        hit = ex.plans.get(key, state)
        if hit is None:
            owners = []
            ok = True
            for s in slices:
                nodes = cl.fragment_nodes(index, s)
                h = nodes[0].host if nodes else None
                if h is None or h not in members:
                    ok = False
                    break
                owners.append(h)
            hit = ("ok", tuple(owners)) if ok else ("miss",)
            ex.plans.put(key, state, hit)
        if hit[0] != "ok":
            raise MeshDecline("not_resident")
        return hit[1]

    # ----------------------------------------------------------- programs

    def _collective_count(self, ex, index, call, slices, owners):
        if len(call.children) != 1:
            raise MeshDecline("unsupported")
        plan, leaves = ex._plan_memoized(index, call.children[0])
        if plan is None:
            raise MeshDecline("plan")
        win = self._window(ex, index, slices, owners,
                           self._leaf_views(leaves))
        args, specs = self._stage(ex, index, leaves, slices, owners,
                                  win)
        if "slice" not in specs:
            # Statically-empty plan (e.g. an out-of-range BSI Range
            # shortcut): no sharded stack exists and no program need
            # run — the count over every slice is exactly 0.
            return 0
        with _dispatch_mu:
            return int(self.engine.tree_count(plan, args, specs,
                                              len(slices)))

    def _collective_topn(self, ex, index, call, slices, owners):
        """TopN's exact re-count (phase 2 — explicit ids, the device
        half of the two-phase algorithm) as one collective. Candidate
        discovery reads host cache metadata and stays on its existing
        path; a recount with a non-default threshold, a Tanimoto
        score, or attribute filters keeps the HTTP semantics (those
        apply per NODE partial there, which a global psum can't
        reproduce bit-for-bit)."""
        row_ids, frame_name, view = _topn_static_gate(ex, call)
        src_plan, leaves = None, []
        if call.children:
            src_plan, leaves = ex._plan_memoized(index,
                                                 call.children[0])
            if src_plan is None:
                raise MeshDecline("plan")
        win = self._window(
            ex, index, slices, owners,
            self._leaf_views(leaves, extra=((frame_name, view),)))
        matrix = self._matrix_stack(index, frame_name, view,
                                    tuple(row_ids), slices, owners, win)
        src_args, specs = self._stage(
            ex, index, leaves, slices, owners, win,
            extra_bytes=self.engine.pad_slices(len(slices))
            * len(row_ids) * win[1] * 4)
        with _dispatch_mu:
            counts = self.engine.topn_tree_counts(
                matrix, src_plan, src_args, specs, len(slices))
        pairs = [(int(r), int(c)) for r, c in zip(row_ids, counts)
                 if c > 0]
        pairs.sort(key=lambda rc: (-rc[1], rc[0]))
        return pairs

    def _collective_sum(self, ex, index, call, slices, owners):
        from pilosa_tpu.executor import SumCount
        from pilosa_tpu.storage.view import view_field_name

        frame_name, field_name, field = _sum_static_gate(ex, index,
                                                         call)
        depth = field.bit_depth()
        filt_plan, leaves = None, []
        if call.children:
            filt_plan, leaves = ex._plan_memoized(index,
                                                  call.children[0])
            if filt_plan is None:
                raise MeshDecline("plan")
        win = self._window(
            ex, index, slices, owners,
            self._leaf_views(leaves, extra=(
                (frame_name, view_field_name(field_name)),)))
        planes = self._planes_stack(
            index, frame_name, view_field_name(field_name), depth,
            slices, owners, win)
        filt_args, specs = self._stage(
            ex, index, leaves, slices, owners, win,
            extra_bytes=self.engine.pad_slices(len(slices))
            * (depth + 1) * win[1] * 4)
        with _dispatch_mu:
            out = self.engine.bsi_sum_counts(
                planes, filt_plan, filt_args, specs, len(slices))
        count = int(out[depth])
        total = sum((1 << i) * int(c) for i, c in enumerate(out[:depth]))
        return SumCount(total + count * field.min, count)

    # ------------------------------------------------------------- staging

    def _stack_token(self, index):
        """Validity token for staged stacks: any member's write bumps
        the (process-shared) mutation epoch; membership/topology/
        placement changes rotate the other components."""
        return (_frag.mutation_epoch(index),
                self.cluster.topology_state(), registry_version())

    @staticmethod
    def _leaf_views(leaves, extra=()):
        """(frame, view) pairs a plan's leaves actually read — the
        window walk is scoped to THEM, like the executor's leaf-
        scoped _union_window (an unrelated full-width frame must not
        inflate this query's stacks)."""
        from pilosa_tpu.storage.view import view_field_name

        out = set(extra)
        for leaf in leaves:
            if leaf[0] == "row":
                out.add((leaf[1], leaf[3]))
            elif leaf[0] == "planes":
                out.add((leaf[1], view_field_name(leaf[2])))
        return out

    @staticmethod
    def _bucket_window(lo, hi):
        """Power-of-FOUR width bucket with a width-aligned base — the
        batched executor's window economy (executor._union_window):
        device stacks size to the data's span, and the bucketing caps
        how many distinct program shapes a drifting window compiles."""
        width = MIN_WIN32
        while width < WORDS_PER_SLICE:
            base = lo - (lo % width)
            if base + width >= hi:
                return base, width
            width *= 4
        return 0, WORDS_PER_SLICE

    def _window(self, ex, index, slices, owners, views):
        """(base32, width32) covering every fragment the plan's leaf
        ``views`` hold for ``slices`` — ONE window per program, so
        every leaf stack of a query shares a shape and the elementwise
        tree fold needs no alignment. Scoped to the leaves' (frame,
        view) pairs, like the executor's _union_window. Epoch-memoized
        in the plan cache (a write that widens a fragment's span bumps
        the epoch and recomputes). A racing mutation serves the
        consistent pre-write snapshot — the same linearizability class
        as the executor's win32/stack-cache token race."""
        token = self._stack_token(index)
        views = tuple(sorted(views))
        key = ("meshwin", index, views, slice_key(slices))
        hit = ex.plans.get(key, token)
        if hit is not None:
            return hit
        members = group_members(self.group)
        lo = hi = None
        for i, s in enumerate(slices):
            plane = members.get(owners[i])
            if plane is None:
                raise RuntimeError(
                    f"mesh member {owners[i]} left the group "
                    f"mid-staging")
            for frame_name, view in views:
                frag = plane.holder.fragment(index, frame_name, view,
                                             s)
                if frag is None:
                    continue
                win = frag.win32()
                if win is None:
                    continue
                b, w = win
                lo = b if lo is None else min(lo, b)
                hi = b + w if hi is None else max(hi, b + w)
        win = ((0, MIN_WIN32) if lo is None
               else self._bucket_window(lo, hi))
        ex.plans.put(key, token, win)
        return win

    def _stage(self, ex, index, leaves, slices, owners, win,
               extra_bytes=0):
        """Stage every leaf's sharded stack. ``extra_bytes`` carries
        stacks the caller staged directly (TopN's ids matrix, Sum's
        planes) so the budget bounds the QUERY'S aggregate working
        set, not just each stack — the per-query analog of the
        batched executor's BATCH_OVER_BUDGET (LRU eviction cannot
        free arrays an in-flight query still references)."""
        import jax.numpy as jnp

        width = win[1]
        pad = self.engine.pad_slices(len(slices))
        total = extra_bytes
        args, specs = [], []
        for leaf in leaves:
            kind = leaf[0]
            if kind == "row":
                total += pad * width * 4
            elif kind == "planes":
                total += pad * (leaf[3] + 1) * width * 4
            if total > self.stack_bytes:
                raise MeshDecline("budget")
            if kind == "bits":
                # Predicate-bit vectors are immutable by value — cache
                # the replicated device arg (the _nv discipline: a
                # fresh jnp.asarray would device_put on EVERY query).
                arr = self._bits.get(leaf[1:])
                if arr is None:
                    if len(self._bits) > 4096:
                        self._bits.clear()
                    arr = self._bits[leaf[1:]] = jnp.asarray(
                        list(leaf[1]), dtype=jnp.int32)
                args.append(arr)
                specs.append("rep")
            elif kind == "row":
                _, frame_name, row_id, view = leaf
                args.append(self._row_stack(index, frame_name, view,
                                            row_id, slices, owners,
                                            win))
                specs.append("slice")
            elif kind == "planes":
                _, frame_name, field_name, depth = leaf
                from pilosa_tpu.storage.view import view_field_name

                args.append(self._planes_stack(
                    index, frame_name, view_field_name(field_name),
                    depth, slices, owners, win))
                specs.append("slice")
            else:
                raise MeshDecline("plan")
        return tuple(args), tuple(specs)

    @staticmethod
    def _member_fragment(members, index, frame_name, view, s, host):
        """The owning member's fragment for one slice, from a members
        snapshot taken once per stack build — registration IS
        liveness: a member that closed mid-query raises so the query
        falls back loudly instead of counting zeros."""
        plane = members.get(host)
        if plane is None:
            raise RuntimeError(
                f"mesh member {host} left the group mid-staging")
        return plane.holder.fragment(index, frame_name, view, s)

    def _row_stack(self, index, frame_name, view, row_id, slices,
                   owners, win):
        base, width = win
        key = ("row", index, frame_name, view, row_id, win,
               slice_key(slices))
        token = self._stack_token(index)
        pad = self.engine.pad_slices(len(slices))
        nbytes = pad * width * 4

        def build():
            members = group_members(self.group)
            host = np.zeros((pad, width), np.uint32)
            for i, (s, h) in enumerate(zip(slices, owners)):
                frag = self._member_fragment(members, index,
                                             frame_name, view, s, h)
                if frag is not None:
                    host[i] = np.ascontiguousarray(
                        frag.row_words(row_id)).view(
                            np.uint32)[base:base + width]
            return self.engine.shard_rows(host)

        return self._stack(key, token, nbytes, build)

    def _planes_stack(self, index, frame_name, view, depth, slices,
                      owners, win):
        base, width = win
        key = ("planes", index, frame_name, view, depth, win,
               slice_key(slices))
        token = self._stack_token(index)
        pad = self.engine.pad_slices(len(slices))
        nbytes = pad * (depth + 1) * width * 4

        def build():
            members = group_members(self.group)
            host = np.zeros((pad, depth + 1, width), np.uint32)
            for i, (s, h) in enumerate(zip(slices, owners)):
                frag = self._member_fragment(members, index,
                                             frame_name, view, s, h)
                if frag is None:
                    continue
                for p in range(depth + 1):
                    host[i, p] = np.ascontiguousarray(
                        frag.row_words(p)).view(
                            np.uint32)[base:base + width]
            return self.engine.shard_rows(host)

        return self._stack(key, token, nbytes, build)

    def _matrix_stack(self, index, frame_name, view, row_ids, slices,
                      owners, win):
        base, width = win
        key = ("matrix", index, frame_name, view, row_ids, win,
               slice_key(slices))
        token = self._stack_token(index)
        pad = self.engine.pad_slices(len(slices))
        nbytes = pad * len(row_ids) * width * 4

        def build():
            members = group_members(self.group)
            host = np.zeros((pad, len(row_ids), width), np.uint32)
            for i, (s, h) in enumerate(zip(slices, owners)):
                frag = self._member_fragment(members, index,
                                             frame_name, view, s, h)
                if frag is None:
                    continue
                for j, rid in enumerate(row_ids):
                    host[i, j] = np.ascontiguousarray(
                        frag.row_words(rid)).view(
                            np.uint32)[base:base + width]
            return self.engine.shard_rows(host)

        return self._stack(key, token, nbytes, build)

    def _stack(self, key, token, nbytes, build):
        """Epoch-validated byte-budgeted LRU of sharded device stacks.
        ``nbytes`` is the caller-computed size, checked BEFORE the
        host alloc/device_put — the budget must prevent the staging it
        bounds (an oversized client-chosen ids matrix must decline,
        not OOM). The token is read by the CALLER before staging, so a
        write landing mid-build makes the entry stale-on-arrival,
        never wrong (the plan-cache discipline). Device staging runs
        outside the lock."""
        if nbytes > self.stack_bytes:
            raise MeshDecline("budget")
        with self._mu:
            ent = self._stacks.get(key)
            if ent is not None and ent[0] == token:
                self._stacks.move_to_end(key)
                self._stats["stack_hits"] += 1
                return ent[1]
        arr = build()
        with self._mu:
            self._stats["stack_misses"] += 1
            old = self._stacks.pop(key, None)
            if old is not None:
                self._stack_bytes -= old[2]
            while (self._stacks
                   and self._stack_bytes + nbytes > self.stack_bytes):
                _, (_t, _a, nb) = self._stacks.popitem(last=False)
                self._stack_bytes -= nb
                self._stats["stack_evictions"] += 1
            self._stacks[key] = (token, arr, nbytes)
            self._stack_bytes += nbytes
        return arr

    # --------------------------------------------------------------- intro

    def _coords(self):
        """host → mesh coordinate: the pinned placement generation's
        host order when one exists (so device sharding and ownership
        agree across the group), else the static node list."""
        pl = getattr(self.cluster, "placement", None)
        if pl is not None and pl.active:
            return pl.mesh_coords()
        return {n.host: i for i, n in enumerate(self.cluster.nodes)}

    def metrics(self):
        """Flat dict for the /metrics ``pilosa_mesh_*`` group — always
        present while the plane is wired (zeroed on an idle server),
        declines tagged by reason, launches by call kind."""
        members = group_members(self.group)
        with self._mu:
            st = self._stats
            out = {
                "enabled": 1,
                "members": len(members),
                "stack_bytes": self._stack_bytes,
                "stack_capacity_bytes": self.stack_bytes,
                "stack_entries": len(self._stacks),
                "stack_hits_total": st["stack_hits"],
                "stack_misses_total": st["stack_misses"],
                "stack_evictions_total": st["stack_evictions"],
            }
            for k in KINDS:
                out[f"collective_launches_total;kind:{k}"] = \
                    st["launches"][k]
            for r in FALLBACK_REASONS:
                out[f"fallback_total;reason:{r}"] = st["fallbacks"][r]
        return out

    def snapshot(self):
        """GET /debug/mesh payload."""
        members = group_members(self.group)
        coords = self._coords()
        pl = getattr(self.cluster, "placement", None)
        placement = None
        if pl is not None and pl.active:
            w = pl.wire_state()
            placement = {"generation": w["generation"],
                         "phase": w["phase"]}
        with self._mu:
            st = self._stats
            return {
                "enabled": True,
                "group": self.group,
                "localHost": self.local_host,
                "members": {h: {"coord": coords.get(h)}
                            for h in sorted(members)},
                "devices": (self._engine.n_devices
                            if self._engine is not None else None),
                "placement": placement,
                "launches": dict(st["launches"]),
                "fallbacks": dict(st["fallbacks"]),
                "stack": {
                    "bytes": self._stack_bytes,
                    "capacityBytes": self.stack_bytes,
                    "entries": len(self._stacks),
                    "hits": st["stack_hits"],
                    "misses": st["stack_misses"],
                    "evictions": st["stack_evictions"],
                },
            }
