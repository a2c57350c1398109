"""Adaptive cost-based query planner (ROADMAP item 2).

The executor used to evaluate PQL trees in WRITTEN order and pick its
serving tier by a fixed decline chain (mesh → http → coalesce →
batched → serial).  PRs 13/15 built everything a real planner needs —
measured per-(op, format-cell, shape-bucket) kernel costs, per-leaf
format/cardinality probes, a calibrated per-tier cost model, and an
epoch-validated plan cache to memoize decisions in — and this module
closes that loop.  Three passes, each independently switchable
(``[planner]`` config / ``PILOSA_PLANNER_*`` env; everything off =
byte-identical pre-planner behavior):

- **Selectivity reordering** — commutative ``Intersect``/``Union``
  chains re-sort smallest-estimated-cardinality-first (stable sort,
  recursing through nested trees), so later operands intersect
  against an already-tiny intermediate — the gallop-smallest-first
  rule the roaring line measures as the dominant intersection win
  (arXiv:1402.6407, arXiv:1709.07821).  Cardinalities come from the
  same sampled read-only fragment probes the cost model uses
  (``row_count`` on two sample slices, scaled), never a full walk.
- **Short-circuiting** — a statically-empty subtree (the BSI
  out-of-range plan shortcut) kills an Intersect branch at PLAN time
  and drops out of Union chains without a kernel; at RUN time the
  ordered serial path stops an Intersect chain the moment the running
  intermediate goes empty and a Union chain the moment it saturates a
  slice (container cardinalities are host-known, so the checks are
  free on compressed operands — the only shape the pass engages for).
- **Learned tier selection** — instead of the static decline chain,
  the serving tier comes from ``costmodel.estimate_tiers`` over the
  tiers actually ELIGIBLE for the shape.  Overrides are deliberately
  conservative: they honor the executor's test pins (``_force_path``,
  ``_co_route_all``), engage only after ``WARM_USES`` uses of a plan
  (cold queries gain nothing from tier games), demand a margin
  (2× for the deep-compressed serial short-circuit case the static
  chain serves through budgeted densify; 4× otherwise, where the
  model is blind to cross-query fusion), and every overridden serve
  records predicted-vs-measured so the measured-history medians
  correct a misprediction within one memo-refresh bucket — a wrong
  tier cannot be chosen indefinitely.  1-in-``explore_stride`` uses
  serve the static chain anyway, keeping the alternative calibrated.

Plans land in the PR 6 plan cache under ``("planner", index, ast,
slice-key)`` keyed on the existing mutation-epoch tokens (plus the
cost model's bucketed learning version), so a warm query's whole
planning pass is one dict hit.  ``?explain=true`` renders the chosen
order, the tier decision, and the cost rationale per call.
"""
import logging
import os

from pilosa_tpu import SLICE_WIDTH

logger = logging.getLogger(__name__)

# Uses of a memoized plan before tier overrides may engage: the first
# serves always run the static chain — they are exactly the serves
# that calibrate it, and a query too cold to repeat is a query whose
# tier choice cannot matter.
WARM_USES = 8

# Cardinality sentinel for subtrees the probes cannot size (BSI
# predicates): pessimistic, so unknown shapes sort LAST in an
# Intersect chain and never rob a known-small operand of first slot.
UNKNOWN_CARD = float(SLICE_WIDTH)

# Override margins: predicted static-tier cost must exceed the chosen
# tier's by this factor. The deep-compressed case (static chain =
# budgeted densify through the coalescer; chosen = ordered serial
# short-circuit) is the modeled win, so it engages at 2x; every other
# flip demands 4x because the model cannot see cross-query fusion —
# a lane that looks slow single-query may be winning under load.
MARGIN_DEEP = 2.0
MARGIN_DEFAULT = 4.0

# Cold-start densify prior: the static chain stages a DEEP
# all-compressed tree densely (CO_DENSIFY_BYTES budget) before
# fusing; until measured history covers the tier, charge the staging
# bytes at the fallback sweep rate so the estimate reflects it.
DENSIFY_BYTES_PER_SEC = 10e9

# Bound on the planner-private per-plan use counters (the memoized
# plan itself lives in the executor's plan cache; uses must survive
# the memo's learning-version refresh or overrides would disengage
# for WARM_USES after every costmodel bucket tick).
USES_MAX = 512

_COMMUTATIVE = ("Intersect", "Union")
_BOOL_OPS = ("Intersect", "Union", "Difference", "Xor")


def _env_bool(name, default):
    raw = os.environ.get(name)
    if raw is None or raw == "":
        return default
    return raw.lower() not in ("0", "false", "no", "off")


def _env_int(name, default):
    raw = os.environ.get(name)
    if not raw:
        return default
    try:
        return int(raw)
    except ValueError:
        logger.warning("ignoring %s=%r (want an integer)", name, raw)
        return default


class Planner:
    """One executor's planning pass. Config resolves from
    ``PILOSA_PLANNER_*`` env at construction (bare Executors —
    tests, benchmarks); ``set_config`` is the server wiring and wins
    over env (config.py already folded env-over-file precedence).
    Counters are GIL-atomic dict writes (the _co_stats discipline):
    no lock on the serving path, a lost update under extreme
    contention costs one count, never corruption."""

    def __init__(self):
        self.enabled = _env_bool("PILOSA_PLANNER_ENABLED", True)
        self.reorder = _env_bool("PILOSA_PLANNER_REORDER", True)
        self.short_circuit = _env_bool("PILOSA_PLANNER_SHORT_CIRCUIT",
                                       True)
        self.tier_select = _env_bool("PILOSA_PLANNER_TIER_SELECT", True)
        self.explore_stride = max(
            0, _env_int("PILOSA_PLANNER_EXPLORE_STRIDE", 64))
        # Config fingerprint folded into plan-cache tokens: a
        # set_config flip invalidates every memoized plan (an "off"
        # switch must not keep serving "on" decisions).
        self._cfg_version = 0
        self._uses = {}  # plan key -> use count (see USES_MAX)
        self._stats = {
            "plans": 0, "memo_hits": 0, "reorders": 0,
            "static_empty": 0, "explores": 0,
            "shortcircuits": {},   # kind -> count
            "tier_overrides": {},  # (from, to) -> count
        }

    # ------------------------------------------------------ config

    def set_config(self, enabled=None, reorder=None, short_circuit=None,
                   tier_select=None, explore_stride=None):
        """Server wiring for the ``[planner]`` table — explicit values
        override the env/default resolution; None keeps each knob."""
        if enabled is not None:
            self.enabled = bool(enabled)
        if reorder is not None:
            self.reorder = bool(reorder)
        if short_circuit is not None:
            self.short_circuit = bool(short_circuit)
        if tier_select is not None:
            self.tier_select = bool(tier_select)
        if explore_stride is not None:
            self.explore_stride = max(0, int(explore_stride))
        self._cfg_version += 1

    def active(self):
        """One-read gate for the serving path: any pass on?"""
        return self.enabled and (self.reorder or self.short_circuit
                                 or self.tier_select)

    # ----------------------------------------------------- counters

    def _note(self, key, n=1):
        self._stats[key] = self._stats.get(key, 0) + n

    def note_shortcircuit(self, kind):
        """One runtime short-circuit fire (``intersect_empty`` /
        ``union_full``) or a plan-time ``static_empty`` serve."""
        d = self._stats["shortcircuits"]
        d[kind] = d.get(kind, 0) + 1

    # ----------------------------------------------------- planning

    def plan_count(self, ex, index, child, slices, store=True):
        """The full planning pass for ``Count(child)`` over
        ``slices``: a memoized dict with the rewritten child, the
        short-circuit/static-empty verdicts, and the tier decision —
        or None when the tree is unplannable (the executor then runs
        exactly the pre-planner path). ``store=False`` is the
        explain-only mode: every lookup reads through the caches
        without writing and no counter moves (explain-only provably
        mutates nothing)."""
        try:
            return self._plan_count(ex, index, child, slices, store)
        except Exception:  # noqa: BLE001 — planning must never fail a query
            logger.exception("planner pass failed; serving unplanned")
            return None

    def _plan_count(self, ex, index, child, slices, store):
        from pilosa_tpu.observe import costmodel as costmodel_mod
        from pilosa_tpu.plancache import slice_key
        from pilosa_tpu.storage import fragment as _frag

        if not slices:
            return None
        cm = costmodel_mod.ACTIVE
        token = (_frag.mutation_epoch(index),
                 (cm._version >> 4) if cm.enabled else 0,
                 self._cfg_version)
        key = ("planner", index, str(child), slice_key(slices))
        if store:
            planned = ex.plans.get(key, token)
        else:
            planned = ex.plans.peek(key, token)
        if planned is not None:
            if store:
                self._note("memo_hits")
                self._bump_uses(key)
            return planned
        if store:
            plan, leaves = ex._plan_memoized(index, child)
        else:
            from pilosa_tpu.observe.explain import plan_readonly

            plan, leaves = plan_readonly(ex, index, child)
        if plan is None:
            return None
        cards = {}
        child2, est, static_empty, changed = self._annotate(
            ex, index, child, plan, leaves, slices, cards)
        compressed = self._probe_compressed(ex, index, leaves, slices)
        shape = ex._lane_plan_shape(plan)
        # >= 3 operands: a 2-op chain already reduces through the
        # count-only kernel with nothing between the first fetch and
        # the final reduce to skip — routing it through the checked
        # path is pure overhead on already-optimal queries.
        sc = (self.short_circuit and compressed and not static_empty
              and child2.name in _COMMUTATIVE
              and len(child2.children) >= 3)
        tier = self._select_tier(ex, index, child, slices, plan, leaves,
                                 compressed, shape, sc, store)
        planned = {
            "child": child2, "changed": changed,
            "order": [str(c) for c in child2.children]
            if changed else None,
            "cards": cards, "staticEmpty": static_empty, "sc": sc,
            "compressed": compressed,
            "static": tier["static"], "tier": tier["tier"],
            "tiers": tier["tiers"], "rationale": tier["rationale"],
            "key": key,
        }
        if store:
            self._note("plans")
            if changed:
                self._note("reorders")
            self._bump_uses(key)
            ex.plans.put(key, token, planned)
        return planned

    def _bump_uses(self, key):
        u = self._uses
        if len(u) >= USES_MAX and key not in u:
            u.clear()
        u[key] = u.get(key, 0) + 1

    # --------------------------------------- cardinality annotation

    def _annotate(self, ex, index, call, plan, leaves, slices, cards):
        """(rewritten call, estimated cardinality, statically-empty,
        changed) for one (AST, plan) node pair — the plan tree runs
        structurally parallel to the AST for boolean ops (kids align
        1:1), while leaf-expanding nodes (time Ranges, BSI) are
        atomic here and size through their plan subtree."""
        kind = plan[0]
        if (call.name in _BOOL_OPS and kind == call.name
                and call.children):
            kids = [self._annotate(ex, index, c, p, leaves, slices,
                                   cards)
                    for c, p in zip(call.children, plan[1])]
            return self._rewrite_node(call, kids, cards)
        est, empty = self._plan_est(ex, index, plan, leaves, slices)
        return call, est, empty, False

    def _rewrite_node(self, call, kids, cards):
        name = call.name
        changed = any(c for _n, _e, _se, c in kids)
        nodes = [(n, e, se) for n, e, se, _c in kids]
        if name == "Intersect":
            if any(se for _n, _e, se in nodes):
                return call, 0.0, True, changed
            if self.reorder and len(nodes) >= 2:
                order = sorted(range(len(nodes)),
                               key=lambda i: nodes[i][1])
                if order != list(range(len(nodes))):
                    nodes = [nodes[i] for i in order]
                    changed = True
            est = min(e for _n, e, _se in nodes)
        elif name == "Union":
            live = [t for t in nodes if not t[2]]
            if not live:
                return call, 0.0, True, changed
            if len(live) != len(nodes):
                # A statically-empty operand is the Union identity —
                # drop it so its subtree never launches a kernel.
                nodes, changed = live, True
            if self.reorder and len(nodes) >= 2:
                order = sorted(range(len(nodes)),
                               key=lambda i: nodes[i][1])
                if order != list(range(len(nodes))):
                    nodes = [nodes[i] for i in order]
                    changed = True
            est = min(sum(e for _n, e, _se in nodes), UNKNOWN_CARD)
        elif name == "Difference":
            # NON-commutative: operand order is semantics. Children's
            # own subtrees may have been rewritten, but membership
            # and order here never change.
            est = nodes[0][1]
            if nodes[0][2]:
                return call, 0.0, True, changed
        else:  # Xor — commutative but not reordered (no gallop win)
            est = min(sum(e for _n, e, _se in nodes), UNKNOWN_CARD)
        if changed:
            from pilosa_tpu.pql.ast import Call

            call = Call(call.name, dict(call.args),
                        [n for n, _e, _se in nodes])
        for n, e, _se in nodes:
            cards.setdefault(str(n), round(e, 1))
        return call, est, False, changed

    def _plan_est(self, ex, index, plan, leaves, slices):
        """(estimated cardinality, statically-empty) for a plan
        subtree the AST walk treats as atomic."""
        kind = plan[0]
        if kind == "empty":
            return 0.0, True
        if kind == "leaf":
            return self._leaf_card(ex, index, leaves[plan[1]],
                                   slices), False
        if kind == "bsi":
            return UNKNOWN_CARD, False
        kids = [self._plan_est(ex, index, p, leaves, slices)
                for p in plan[1]]
        if kind == "Intersect":
            if any(se for _e, se in kids):
                return 0.0, True
            return min(e for e, _se in kids), False
        if kind == "Difference":
            return kids[0]
        live = [e for e, se in kids if not se]
        if not live:
            return 0.0, True
        return min(sum(live), UNKNOWN_CARD), False

    @staticmethod
    def _leaf_card(ex, index, spec, slices):
        """Estimated total cardinality of one row leaf: mean of two
        sampled fragments' host-known row counts, scaled to the slice
        universe (the _co_tick_route / _leaf_info probe economy —
        read-only, never a fragment walk)."""
        if spec[0] != "row":
            return UNKNOWN_CARD
        _, fname, rid, view = spec
        counts = []
        for s in (slices[0], slices[len(slices) // 2]):
            frag = ex.holder.fragment(index, fname, view, s)
            if frag is not None:
                counts.append(int(frag.row_count(rid)))
        if not counts:
            return 0.0
        return (sum(counts) / len(counts)) * len(slices)

    @staticmethod
    def _probe_compressed(ex, index, leaves, slices):
        """Sampled twin of the executor's _compressed_plan gate: True
        when every row leaf probes compressed (the batched dense path
        would decline; the serial path serves container kernels)."""
        from pilosa_tpu.ops import containers as containers_mod

        if not containers_mod.enabled() or not slices:
            return False
        rows = []
        for sp in leaves:
            if sp[0] == "planes":
                return False
            if sp[0] == "row":
                rows.append(sp)
        # One leaf the executor already found dense settles it, as in
        # its own gate, without touching a fragment.
        if any(ex._known_dense(index, fname, view, rid, slices)
               for _, fname, rid, view in rows):
            return False
        for _, fname, rid, view in rows:
            for s in (slices[0], slices[len(slices) // 2]):
                frag = ex.holder.fragment(index, fname, view, s)
                if frag is not None:
                    if not frag.row_compressed(rid):
                        return False
                    break
        return bool(rows)

    # -------------------------------------------------- tier choice

    def eligible_tiers(self, ex, index, plan, leaves, slices,
                       compressed=None):
        """The engine tiers that could actually serve this shape on
        this node — the candidate set the tier selector (and explain's
        trimmed cost block) estimates over."""
        if compressed is None:
            compressed = self._probe_compressed(ex, index, leaves,
                                                slices)
        shape = ex._lane_plan_shape(plan)
        cands = ["serial"]
        if not compressed:
            cands.append("batched")
        if ex._co_enabled() and ex._co_tick_route(index, leaves,
                                                  slices):
            if compressed and shape is not None and shape[0] != "count":
                cands.append("coalesced_lane")
            else:
                cands.append("coalesced_dense")
        return cands

    def _select_tier(self, ex, index, child, slices, plan, leaves,
                     compressed, shape, sc, store):
        """The static chain's choice, the model's choice, and whether
        the margin justifies overriding — computed once at plan time
        and memoized with the plan."""
        from pilosa_tpu import WORDS_PER_SLICE
        from pilosa_tpu.observe import costmodel as costmodel_mod

        out = {"static": None, "tier": None, "tiers": None,
               "rationale": None}
        cands = self.eligible_tiers(ex, index, plan, leaves, slices,
                                    compressed)
        static = cands[-1] if len(cands) > 1 else "serial"
        # eligible_tiers appends in consultation order, so the LAST
        # candidate is what the static chain would pick (coalesce
        # before batched before serial); a lone "serial" means every
        # other tier declined.
        out["static"] = static
        cm = costmodel_mod.ACTIVE
        if not (self.tier_select and cm.enabled and len(cands) > 1):
            return out
        est = cm.estimate_tiers(ex, index, child, slices, cands,
                                plan=plan, leaves=leaves, store=store)
        if est is None:
            return out
        tiers = dict(est["tiers"])
        deep = compressed and (shape is None or shape[0] == "count")
        if (deep and "coalesced_dense" in tiers
                and "coalesced_dense" not in est.get("measured", ())):
            # Cold-start densify prior: the fused route must first
            # stage every compressed leaf densely (bounded by the
            # densify budget); once measured history covers the tier
            # the real medians replace this arithmetic.
            staged = len(leaves) * len(slices) * WORDS_PER_SLICE * 4
            tiers["coalesced_dense"] += staged / DENSIFY_BYTES_PER_SEC
        out["tiers"] = {t: round(s * 1e6, 3) for t, s in tiers.items()}
        chosen = min(tiers, key=tiers.get)
        if chosen == static or tiers[chosen] <= 0:
            out["rationale"] = f"static {static} already cheapest"
            return out
        margin = tiers[static] / tiers[chosen]
        need = (MARGIN_DEEP if (deep and chosen == "serial" and sc)
                else MARGIN_DEFAULT)
        if margin < need:
            out["rationale"] = (
                f"{chosen} predicted {margin:.1f}x cheaper than "
                f"{static} — below the {need:.0f}x override margin")
            return out
        out["tier"] = chosen
        out["rationale"] = (
            f"override {static} -> {chosen}: predicted "
            f"{margin:.1f}x cheaper (>= {need:.0f}x margin)")
        return out

    def decide_tier(self, ex, planned):
        """The serve-time override decision for one use of a memoized
        plan: honor the executor's test pins, stay on the static
        chain for the first WARM_USES uses, and serve the static
        chain on exploration ticks so the alternative keeps getting
        measured. Returns (tier-or-None, forced-record)."""
        t = planned.get("tier")
        if (t is None or not self.tier_select
                or getattr(ex, "_force_path", None) is not None
                or ex._co_route_all):
            return None, False
        uses = self._uses.get(planned.get("key"), 0)
        if uses <= WARM_USES:
            return None, False
        if self.explore_stride and uses % self.explore_stride == 0:
            # Exploration serve: run the static chain and record it,
            # so a drifting static tier can win the spot back.
            self._note("explores")
            return None, True
        d = self._stats["tier_overrides"]
        k = (planned["static"], t)
        d[k] = d.get(k, 0) + 1
        return t, True

    # -------------------------------------------------------- views

    def snapshot(self):
        """The ``planner`` block in GET /debug/plans."""
        sc = dict(self._stats["shortcircuits"])
        return {
            "enabled": self.enabled,
            "switches": {"reorder": self.reorder,
                         "shortCircuit": self.short_circuit,
                         "tierSelect": self.tier_select,
                         "exploreStride": self.explore_stride},
            "plans": self._stats["plans"],
            "memoHits": self._stats["memo_hits"],
            "reorders": self._stats["reorders"],
            "staticEmpty": self._stats["static_empty"],
            "shortCircuits": sc,
            "explores": self._stats["explores"],
            "tierOverrides": {f"{a}->{b}": n for (a, b), n in
                              sorted(self._stats["tier_overrides"]
                                     .items())},
        }

    def metrics(self):
        """Flat map for the ``pilosa_plan_*`` exposition group —
        untagged totals always present (zeroed from boot, the
        plan_cache discipline); tagged children appear with their
        first event."""
        sc = self._stats["shortcircuits"]
        out = {
            "reorder_total": self._stats["reorders"],
            "shortcircuit_total": sum(sc.values())
            + self._stats["static_empty"],
            "tier_override_total": sum(
                self._stats["tier_overrides"].values()),
        }
        for kind, n in sorted(sc.items()):
            out[f"shortcircuit_total;kind:{kind}"] = n
        if self._stats["static_empty"]:
            out["shortcircuit_total;kind:static_empty"] = (
                self._stats["static_empty"])
        for (a, b), n in sorted(self._stats["tier_overrides"].items()):
            out[f"tier_override_total;from:{a},to:{b}"] = n
        return out

    def note_static_empty(self):
        self._stats["static_empty"] = (
            self._stats.get("static_empty", 0) + 1)
