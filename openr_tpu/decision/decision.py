"""Decision — LSDB consumption, debounced route rebuild, RIB publication.

Reference: openr/decision/Decision.{h,cpp}:
  * consumes KvStore publications (via the Dispatcher, ``adj:`` +
    ``prefix:`` keys) → per-area LinkState + global PrefixState
    (updateKeyInLsdb/deleteKeyFromLsdb, Decision.cpp:711-820)
  * debounced rebuild (AsyncDebounce 10–250 ms, Decision.cpp:114-120)
  * initialization gating: the first build waits for KVSTORE_SYNCED +
    static routes, force-unblocked after unblock_initial_routes_ms
    (Decision.cpp:963-1011); the first publication is FULL_SYNC, then
    incremental deltas
  * static routes from PrefixManager (staticRouteUpdatesQueue)
  * RibPolicy application before publishing + TTL'd persistence
    (Decision.cpp:634-708, 917-950)
  * PerfEvents breadcrumbs carried LSDB → RIB for convergence tracing
  * RIB_COMPUTED initialization event after the first build

The compute itself runs behind a DecisionBackend (scalar oracle or TPU
batched kernels) — the seam BASELINE.json pins at the plugin boundary.
"""

from __future__ import annotations

import os
from typing import Callable, Dict, List, Optional, Set

from openr_tpu import constants as C
from openr_tpu.common.runtime import Actor, Clock, CounterMap
from openr_tpu.common.utils import AsyncDebounce
from openr_tpu.config import DecisionConfig
from openr_tpu.decision.backend import DecisionBackend, ScalarBackend
from openr_tpu.decision.link_state import LinkState
from openr_tpu.decision.prefix_state import PrefixState
from openr_tpu.decision.rib import (
    DecisionRouteDb,
    DecisionRouteUpdate,
    DecisionRouteUpdateType,
)
from openr_tpu.decision.rib_policy import RibPolicy
from openr_tpu.decision.spf_solver import SpfSolver
from openr_tpu.messaging.queue import RQueue, ReplicateQueue
from openr_tpu.ops.csr import CapacityError
from openr_tpu.types import (
    AdjacencyDatabase,
    InitializationEvent,
    PerfEvents,
    PrefixDatabase,
    Publication,
    parse_adj_key,
    parse_prefix_key,
)


def deserialize_adj_db(data: bytes) -> AdjacencyDatabase:
    """Format-sniffing (JSON or the reference's thrift-compact bytes —
    openr_tpu.lsdb_codec), so Decision consumes floods from either
    encoding, including a reference node's."""
    from openr_tpu.lsdb_codec import deserialize_adj_db as _de

    return _de(data)


def deserialize_prefix_db(data: bytes) -> PrefixDatabase:
    from openr_tpu.lsdb_codec import deserialize_prefix_db as _de

    return _de(data)


#: process-wide latch: the long-lived-heap freeze happens once no matter
#: how many Decision actors share the interpreter
_GC_FROZEN = False


class Decision(Actor):
    def __init__(
        self,
        node_name: str,
        clock: Clock,
        config: DecisionConfig,
        route_updates_queue: ReplicateQueue,
        kv_store_updates_reader: Optional[RQueue] = None,
        static_routes_reader: Optional[RQueue] = None,
        backend: Optional[DecisionBackend] = None,
        solver: Optional[SpfSolver] = None,
        initialization_cb: Optional[Callable[[InitializationEvent], None]] = None,
        counters: Optional[CounterMap] = None,
        rib_policy_file: str = "",
        tracer=None,
    ) -> None:
        super().__init__("decision", clock, counters)
        from openr_tpu.tracing import disabled_tracer

        self.tracer = tracer if tracer is not None else disabled_tracer()
        self.node_name = node_name
        self.config = config
        self.route_updates_queue = route_updates_queue
        self.kv_store_updates_reader = kv_store_updates_reader
        self.static_routes_reader = static_routes_reader
        self.solver = solver or SpfSolver(node_name)
        self.backend = backend or ScalarBackend(self.solver)
        self.initialization_cb = initialization_cb
        self.rib_policy_file = rib_policy_file
        self.area_link_states: Dict[str, LinkState] = {}
        self.prefix_state = PrefixState()
        self.route_db = DecisionRouteDb()
        self.rib_policy: Optional[RibPolicy] = None
        self.pending_perf_events: Optional[PerfEvents] = None
        #: trace context of the newest LSDB change awaiting the debounced
        #: rebuild (the debounce coalesces; the span tree reflects the
        #: LAST event, matching pending_perf_events semantics)
        self.pending_trace_ctx = None
        # initialization gating (Decision.cpp:963-1011)
        self._kvstore_synced = False
        self._unblocked = False
        self._first_build_done = False
        #: cold-boot GC pause active (see _on_publication); always
        #: released by _end_boot_gc_window or stop()
        self._boot_gc_paused = False
        self._rebuild_pending = False
        # pending-delta accumulation between debounced rebuilds
        # (DecisionPendingUpdates, Decision.h:40-108): prefix-only deltas
        # drive per-prefix incremental recompute (Decision.cpp:908-952)
        self._pending_prefix_changes: Set[str] = set()
        self._pending_topo_changed = False
        #: a pending topology change is STRUCTURAL (a node, area or
        #: LINK entered/left the LSDB — the membership-churn class a
        #: rolling restart, autoscaling event or adjacency withdrawal
        #: produces) rather than a perturbation (weight/up-down flips
        #: on an unchanged membership, overload/drain flips).
        #: Perturbation ticks warm-start via the O(links) encode patch
        #: (ISSUE 9); structural ticks warm-start via the slot-stable
        #: encode (tombstones + free-list) and the generation-delta
        #: reset frontier (ISSUE 12).
        self._pending_topo_structural = False
        self._pending_force_full = False
        #: fast-reroute protection tier (a ProtectionService, wired by
        #: the daemon when protection_config.enabled; None otherwise)
        self.protection = None
        #: sorted (n1, n2) pairs the un-rebuilt LSDB window reported
        #: DOWN, and whether it carried ANY other topology change —
        #: the protection classifier's inputs, reset with the other
        #: pending-delta state at rebuild time
        self._pending_down_pairs: Set[tuple] = set()
        self._pending_other_change = False
        #: an applied-but-unconfirmed protection patch: what the FIB
        #: currently holds on top of route_db, awaiting the confirming
        #: warm solve ({"generation", "entries", "deletes"})
        self._frr_outstanding: Optional[dict] = None
        self._last_policy_active = False
        #: bumped on every LSDB change AND every RibPolicy set/clear —
        #: keys the fleet-RIB / what-if table caches and the serving
        #: plane's content-addressed result cache.  A policy flip between
        #: two identical-LSDB queries MUST invalidate those caches (the
        #: computed-result generation is (LSDB, policy), not LSDB alone)
        self._change_seq = 0
        #: serving-plane invalidation hooks, called with the new change
        #: seq whenever the computed-result generation moves; entries
        #: are (priority, registration index, fn) and fire in ascending
        #: order — see add_generation_listener
        self._generation_listeners: List[tuple] = []
        self._fleet_engine = None
        self._whatif_engine = None
        self._whatif_multi_engine = None
        self._whatif_native_engine = None
        self._whatif_generic_engine = None
        self._whatif_device_build_engine = None
        self._whatif_rt_ms = None
        self._debounce = AsyncDebounce(
            self,
            config.debounce_min_ms / 1000.0,
            config.debounce_max_ms / 1000.0,
            self._rebuild_routes,
        )

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> None:
        if self.kv_store_updates_reader is not None:
            self.spawn_queue_loop(
                self.kv_store_updates_reader, self._on_publication, "decision.kv"
            )
        if self.static_routes_reader is not None:
            self.spawn_queue_loop(
                self.static_routes_reader, self._on_static_routes, "decision.static"
            )
        self._load_rib_policy()
        # forced unblock of the initial build (unblock_initial_routes_ms)
        self.schedule(
            self.config.unblock_initial_routes_ms / 1000.0, self._force_unblock
        )

    async def stop(self) -> None:
        if self._boot_gc_paused:
            # never leave the process with the collector off (daemon
            # shut down before the first build completed)
            import gc

            self._boot_gc_paused = False
            gc.enable()
        await super().stop()

    def on_initialization_event(self, ev: InitializationEvent) -> None:
        """Wired by the daemon: KVSTORE_SYNCED gates the initial build."""
        if ev == InitializationEvent.KVSTORE_SYNCED:
            self._kvstore_synced = True
            self._maybe_unblock()

    def _maybe_unblock(self) -> None:
        if self._unblocked or not self._kvstore_synced:
            return
        self._unblocked = True
        if self._rebuild_pending or not self._first_build_done:
            self._debounce()

    def _force_unblock(self) -> None:
        if not self._unblocked:
            self.counters.bump("decision.forced_initial_unblock")
            self._unblocked = True
            self._debounce()

    # -- LSDB updates (processPublication, Decision.cpp:822) ---------------

    def _get_link_state(self, area: str) -> LinkState:
        if area not in self.area_link_states:
            self.area_link_states[area] = LinkState(area, self.node_name)
        return self.area_link_states[area]

    #: publications at/above this many prefix keys take the native bulk
    #: decode path (below it, batch setup costs more than it saves)
    BULK_INGEST_MIN = 32

    def _on_publication(self, pub: Publication) -> None:
        if len(pub.key_vals) >= self.BULK_INGEST_MIN:
            # large publication (cold boot / areawide churn): gen-2
            # collections re-scan the ever-growing LSDB heap (measured
            # 2x ingest slowdown at 409,600 prefixes with GC running).
            # During COLD BOOT (before the first build) the pause spans
            # the whole ingest window — re-enabling between publications
            # lets gen-2 scans of the growing, not-yet-frozen LSDB eat
            # the win right back; the window ends (collect + freeze +
            # re-enable) when the first large build completes, and the
            # forced-unblock timer bounds it.  Steady-state large
            # publications pause per-batch only.
            import gc

            if not self._first_build_done:
                if not self._boot_gc_paused and gc.isenabled():
                    gc.disable()
                    self._boot_gc_paused = True
                self._on_publication_inner(pub)
                return
            from openr_tpu.common.utils import gc_paused

            with gc_paused():
                self._on_publication_inner(pub)
            return
        self._on_publication_inner(pub)

    def _on_publication_inner(self, pub: Publication) -> None:
        # the generation this publication transitions FROM — the
        # identity a protection patch must have been minted at
        prev_key = (
            self.generation_key() if self.protection is not None else None
        )
        changed = False
        area = pub.area
        if pub.trace_ctx is not None:
            # flooding-metadata context; an adj payload below may replace
            # it with the origin-rooted one embedded in the LSDB value
            self.pending_trace_ctx = pub.trace_ctx
        bulk_items = None
        if len(pub.key_vals) >= self.BULK_INGEST_MIN:
            from openr_tpu.decision.ingest import get_bulk_decoder

            if get_bulk_decoder() is not None:
                bulk_items = []
        for key, value in pub.key_vals.items():
            if value.value is None:
                continue  # ttl-refresh only
            if bulk_items is not None and key.startswith(C.PREFIX_DB_MARKER):
                bulk_items.append((key, value.value))
                continue
            changed |= self._update_key(area, key, value.value)
        if bulk_items:
            changed |= self._bulk_update_prefix_keys(area, bulk_items)
        for key in pub.expired_keys:
            changed |= self._delete_key(area, key)
        if changed:
            self.counters.bump("decision.lsdb_updates")
            self._bump_generation()
            self._rebuild_pending = True
            if prev_key is not None:
                self._maybe_apply_protection(prev_key)
            if self._unblocked:
                self._debounce()

    def _bump_generation(self) -> None:
        """Advance the computed-result generation and notify the serving
        plane so cached results from the previous generation are never
        served again (the rebuild-path invalidation contract)."""
        self._change_seq += 1
        for _prio, _order, listener in self._generation_listeners:
            listener(self._change_seq)

    def add_generation_listener(
        self, fn: Callable[[int], None], priority: int = 0
    ) -> None:
        """Register a callback fired on every generation bump (LSDB
        change or RibPolicy set/clear).  Listeners fire in ascending
        ``(priority, registration order)`` — the order is STABLE, so
        cache-PURGING listeners (QueryService's result-cache
        invalidation, default priority 0) always run before listeners
        that MINT new state from the fresh generation (the streaming
        tier's publish scheduler registers at priority 10): a snapshot
        computed inside a later listener can never race a purge of its
        own generation's entries."""
        entry = (priority, len(self._generation_listeners), fn)
        self._generation_listeners.append(entry)
        self._generation_listeners.sort(key=lambda e: (e[0], e[1]))

    def pending_delta_hint(self) -> tuple:
        """``(full, changed_prefixes)`` — the delta class of the
        un-rebuilt LSDB window, read by generation listeners (the
        streaming tier) AT BUMP TIME to scope their own diffs.  ``full``
        is True when a topology/policy/static change is pending: such a
        tick can move routes for ANY prefix at ANY vantage.  When False,
        only the returned prefixes' advertisements changed, so no other
        prefix's computed route (at any vantage) can differ — the
        per-prefix delta discipline ``take_last_changed_prefixes``
        applies to the publication diff, extended to the watch plane.
        The returned set is live; callers must copy, not hold."""
        full = (
            self._pending_topo_changed
            or self._pending_force_full
            or not self._first_build_done
        )
        return full, self._pending_prefix_changes

    def rebuild_settled(self) -> bool:
        """True when the computed RIB reflects the current LSDB (first
        build done, no rebuild pending) — the protection tier only
        mints from a settled generation, so a patch's base RIB is
        exactly ``route_db``."""
        return self._first_build_done and not self._rebuild_pending

    # -- fast-reroute protection (apply + confirm authority) ----------------

    def _maybe_apply_protection(self, prev_key: tuple) -> None:
        """Classify the just-ingested publication; on a protected
        single-failure event with a generation-exact protection hit,
        publish the precomputed FIB patch IMMEDIATELY — failure
        convergence becomes a table lookup.  The debounced warm solve
        that follows is the confirming authority (``_confirm_frr``).
        Every refusal is counted ``protection.fallback.<reason>`` and
        degrades to the warm path, never to a wrong answer."""
        svc = self.protection
        pairs = self._pending_down_pairs
        if svc is None or not pairs:
            return
        if not self._first_build_done or not self._unblocked:
            return
        patch_key = svc.classify_pairs(pairs)
        if patch_key is None:
            svc.note_fallback("multi_failure")
            return
        if (
            self._pending_other_change
            or self._pending_force_full
            or self._pending_prefix_changes
            or self._frr_outstanding is not None
        ):
            # the un-rebuilt window carries MORE than this link-down
            # (or a prior patch is still unconfirmed): the patch's base
            # RIB assumption does not hold
            svc.note_fallback("stale")
            return
        status, doc = svc.lookup(prev_key, patch_key)
        if status != "hit":
            svc.note_fallback(status)
            return
        t0 = self.clock.now()
        made = svc.apply_patch(doc, self.prefix_state)
        if made is None:
            svc.note_fallback("miss")
            return
        entries, deletes = made
        from openr_tpu.tracing import pipeline as _pipeline
        from openr_tpu.tracing.pipeline import disabled_probe

        probe = self._backend_probe()
        if probe is None:
            probe = disabled_probe()
        span = self.tracer.start_span(
            "decision.frr_apply", self.pending_trace_ctx, module="decision"
        )
        try:
            with probe.phase(_pipeline.PROTECTION_APPLY):
                update = DecisionRouteUpdate(
                    type=DecisionRouteUpdateType.INCREMENTAL,
                    frr=True,
                    frr_generation=self._change_seq,
                )
                for prefix, entry in entries.items():
                    old = self.route_db.unicast_routes.get(prefix)
                    if old is None or not old.eq_ignoring_cost(entry):
                        update.unicast_routes_to_update[prefix] = entry
                update.unicast_routes_to_delete = [
                    p for p in deletes if p in self.route_db.unicast_routes
                ]
                # record what the FIB holds ON TOP of route_db until
                # the confirming warm solve reconciles it; route_db
                # itself is NOT mutated (warm backends patch from it)
                self._frr_outstanding = {
                    "generation": self._change_seq,
                    "entries": dict(update.unicast_routes_to_update),
                    "deletes": list(update.unicast_routes_to_delete),
                }
                if not update.empty():
                    # pending_trace_ctx is NOT consumed: the confirming
                    # rebuild parents its own span on the same event,
                    # and child_ctx preserves t0 so Fib's convergence
                    # histogram measures event -> patched, not apply
                    update.trace_ctx = self.tracer.child_ctx(
                        span, self.pending_trace_ctx
                    )
                    self.route_updates_queue.push(update)
        finally:
            self.tracer.end_span(span)
        apply_ms = (self.clock.now() - t0) * 1000.0
        self.counters.bump("decision.frr_applied")
        self.counters.observe("decision.frr_apply_ms", apply_ms)
        svc.note_applied(
            patch_key,
            len(self._frr_outstanding["entries"]),
            len(self._frr_outstanding["deletes"]),
            apply_ms,
        )

    def _confirm_frr(
        self, update: DecisionRouteUpdate, new_db: DecisionRouteDb
    ) -> DecisionRouteUpdate:
        """The confirm-authority step: the warm solve's ``new_db`` is
        the truth; the FIB currently holds ``route_db ⊕ patch``.  On a
        generation-exact divergence the patch LIED — purge the table,
        dump the flight recorder, and replace the whole RIB (no
        incremental delta from a lying table is trusted).  Otherwise
        reconcile the diff (computed against route_db alone) so the FIB
        lands exactly on ``new_db``; confirmed patch entries drop out
        of the push instead of being re-programmed."""
        frr, self._frr_outstanding = self._frr_outstanding, None
        svc = self.protection
        exact = frr["generation"] == self._change_seq
        mismatched = []
        for prefix, entry in frr["entries"].items():
            got = new_db.unicast_routes.get(prefix)
            if got is None or not got.eq_ignoring_cost(entry):
                mismatched.append(prefix)
        for prefix in frr["deletes"]:
            if prefix in new_db.unicast_routes:
                mismatched.append(prefix)
        if exact and mismatched:
            self.counters.bump("decision.frr_mismatches")
            if svc is not None:
                svc.on_mismatch(sorted(mismatched))
            return DecisionRouteUpdate(
                type=DecisionRouteUpdateType.FULL_SYNC,
                unicast_routes_to_update=dict(new_db.unicast_routes),
                mpls_routes_to_update=dict(new_db.mpls_routes),
            )
        if svc is not None:
            svc.note_confirm(exact)
        deletes = set(update.unicast_routes_to_delete)
        for prefix, entry in frr["entries"].items():
            truth = new_db.unicast_routes.get(prefix)
            if truth is None:
                deletes.add(prefix)
            elif truth.eq_ignoring_cost(entry):
                update.unicast_routes_to_update.pop(prefix, None)
                deletes.discard(prefix)
            else:
                update.unicast_routes_to_update[prefix] = truth
                deletes.discard(prefix)
        for prefix in frr["deletes"]:
            if prefix in frr["entries"]:
                continue
            truth = new_db.unicast_routes.get(prefix)
            if truth is None:
                # the FIB already dropped it with the patch
                deletes.discard(prefix)
            else:
                update.unicast_routes_to_update[prefix] = truth
                deletes.discard(prefix)
        update.unicast_routes_to_delete = sorted(deletes)
        return update

    def generation_key(self) -> tuple:
        """Content address of the state every computed-result query
        depends on: the change generation (LSDB churn + policy flips)
        plus each area's topology sequence.  Two equal keys guarantee a
        cached answer is still exact; any LSDB or policy change produces
        a fresh key."""
        return (
            self._change_seq,
            tuple(
                (a, self.area_link_states[a].topology_seq)
                for a in sorted(self.area_link_states)
            ),
        )

    def _bulk_update_prefix_keys(self, area: str, items: List[tuple]) -> bool:
        """Native-kernel batch ingest of ``prefix:`` values (the cold-boot
        hot path; reference analogue: generated-C++ thrift decode feeding
        mergeKeyValues, KvStoreUtil.cpp:391).  Semantics are identical to
        per-key `_update_key`: rows the kernel can't express fall back to
        the scalar path, deletes use the key's prefix, updates use the
        payload's (canonical) prefix."""
        from openr_tpu.decision.ingest import ST_DELETE, ST_FAST, get_bulk_decoder

        dec = get_bulk_decoder()
        status, entries = dec.decode([payload for _, payload in items])
        changed_set = self._pending_prefix_changes
        changed = False
        update_changed = self.prefix_state.update_prefix_changed
        for i, (key, payload) in enumerate(items):
            parsed = parse_prefix_key(key)
            if parsed is None:
                continue  # not a prefix key after all (marker collision)
            st = status[i]
            if st == ST_FAST:
                entry = entries[i]
                origin_node = parsed[0]
                if update_changed(origin_node, area, entry):
                    changed_set.add(entry.prefix)
                    changed = True
            elif st == ST_DELETE:
                got = self.prefix_state.delete_prefix(
                    parsed[0], area, parsed[1]
                )
                if got:
                    changed_set |= got
                    changed = True
            else:
                changed |= self._update_key(area, key, payload)
        return changed

    def _update_key(self, area: str, key: str, data: bytes) -> bool:
        node = parse_adj_key(key)
        if node is not None:
            try:
                adj_db = deserialize_adj_db(data)
            except Exception:  # noqa: BLE001
                self.counters.bump("decision.parse_errors")
                return False
            if adj_db.perf_events is not None:
                self.pending_perf_events = adj_db.perf_events
                if adj_db.perf_events.trace_context is not None:
                    # payload-embedded context survives KvStore storage:
                    # prefer it so full-sync-delivered keys still join
                    # the originating event's trace
                    self.pending_trace_ctx = adj_db.perf_events.trace_context
            # structural classification BEFORE the update: a node's
            # first adjacency advertisement (or a fresh area) changes
            # the symbol table, and a link entering/leaving the LSDB
            # (a neighbor withdrawing its side of an adjacency when a
            # peer bounces — the rolling-restart delta class) changes
            # the edge-row membership.  Both route through the
            # slot-stable structural warm path; only pure
            # weight/drain/up-down flips stay perturbation-class.
            new_area = area not in self.area_link_states
            ls = self._get_link_state(area)
            new_node = not ls.has_node(node)
            links_before = ls.num_links()
            change = ls.update_adjacency_database(adj_db)
            if change.topology_changed or change.node_label_changed:
                self._pending_topo_changed = True
                if (
                    new_area
                    or new_node
                    or change.added_links
                    or ls.num_links() != links_before
                ):
                    self._pending_topo_structural = True
                if self.protection is not None:
                    for lk in change.down_links:
                        self._pending_down_pairs.add(
                            tuple(sorted((lk.n1, lk.n2)))
                        )
                    if (
                        change.other_topology_change
                        or change.node_label_changed
                    ):
                        self._pending_other_change = True
                return True
            return False
        parsed = parse_prefix_key(key)
        if parsed is not None:
            origin_node, prefix = parsed
            try:
                prefix_db = deserialize_prefix_db(data)
            except Exception:  # noqa: BLE001
                self.counters.bump("decision.parse_errors")
                return False
            if prefix_db.delete_prefix or not prefix_db.prefix_entries:
                changed_set = self.prefix_state.delete_prefix(
                    origin_node, area, prefix
                )
            else:
                changed_set = set()
                for entry in prefix_db.prefix_entries:
                    changed_set |= self.prefix_state.update_prefix(
                        origin_node, area, entry
                    )
            self._pending_prefix_changes |= changed_set
            return bool(changed_set)
        return False

    def _delete_key(self, area: str, key: str) -> bool:
        node = parse_adj_key(key)
        if node is not None:
            ls = self._get_link_state(area)
            if ls.delete_adjacency_database(node).topology_changed:
                self._pending_topo_changed = True
                # a node left the LSDB: the symbol table shrinks
                self._pending_topo_structural = True
                self._pending_other_change = True
                return True
            return False
        parsed = parse_prefix_key(key)
        if parsed is not None:
            origin_node, prefix = parsed
            changed_set = self.prefix_state.delete_prefix(
                origin_node, area, prefix
            )
            self._pending_prefix_changes |= changed_set
            return bool(changed_set)
        return False

    # -- static routes (PrefixManager originated w/ install_to_fib) --------

    def _on_static_routes(self, update: DecisionRouteUpdate) -> None:
        self.solver.update_static_unicast_routes(
            update.unicast_routes_to_update,
            update.unicast_routes_to_delete,
        )
        self._rebuild_pending = True
        self._pending_force_full = True
        if self._unblocked:
            self._debounce()

    # -- rebuild (rebuildRoutes, Decision.cpp:885) -------------------------

    def _rebuild_routes(self) -> None:
        if not self._unblocked:
            return
        large = self.prefix_state.get_received_routes_count() >= 10_000
        if large:
            # same GC discipline as bulk ingest: a reference-scale full
            # build allocates ~4 container objects per route and gen-2
            # collections re-scan the whole LSDB+RIB heap mid-build
            from openr_tpu.common.utils import gc_paused

            with gc_paused():
                self._rebuild_routes_inner()
            if self._first_build_done:
                self._end_boot_gc_window()
            return
        self._rebuild_routes_inner()
        if self._first_build_done and self._boot_gc_paused:
            self._end_boot_gc_window()

    def _end_boot_gc_window(self) -> None:
        """Boot steady state reached: the LSDB + first RouteDb are
        long-lived by design — collect once (purge any cycles created
        while the boot pause was active; the CPython-documented
        pre-freeze step), then move the surviving heap to the permanent
        generation so later full collections never re-scan it.  The C++
        reference pays zero cycle-collector tax on its LSDB; gc.freeze
        is CPython's mechanism for exactly that.  ONCE per process —
        the latch is module-global, not per-instance, so multi-node
        in-process deployments (EmulatedNetwork) don't repeatedly
        freeze each other's transient heaps."""
        import gc

        global _GC_FROZEN
        if self._boot_gc_paused:
            self._boot_gc_paused = False
            gc.enable()
        if not _GC_FROZEN:
            _GC_FROZEN = True
            gc.collect()
            gc.freeze()
            self.counters.set("decision.gc_freeze_rib", 1)

    def _rebuild_routes_inner(self) -> None:
        self._rebuild_pending = False
        t0 = self.clock.now()
        trace_ctx, self.pending_trace_ctx = self.pending_trace_ctx, None
        rebuild_span = self.tracer.start_span(
            "decision.rebuild", trace_ctx, module="decision"
        )
        try:
            self._rebuild_routes_traced(t0, trace_ctx, rebuild_span)
        finally:
            self.tracer.end_span(rebuild_span)

    def _rebuild_routes_traced(self, t0, trace_ctx, rebuild_span) -> None:
        policy_active = self.rib_policy is not None and self.rib_policy.is_active(
            self.clock
        )
        # incremental recompute gating (Decision.cpp:908-952): a pure
        # prefix-only delta lets the backend patch its previous RouteDb;
        # topology churn, static-route changes, policy application (which
        # mutates the returned db in place) and the first build force full
        force_full = (
            not self._first_build_done
            or self._pending_force_full
            or self._pending_topo_changed
            or policy_active
            or self._last_policy_active
        )
        # warm-rebuild hint (ISSUE 9): every pending topology change is a
        # perturbation (no node/area structural churn) and nothing ELSE
        # forced the full build — the backend may then rebuild its device
        # state incrementally from the previous generation, provided its
        # own caches corroborate (it re-verifies structural compatibility)
        warm_delta = (
            self._first_build_done
            and self._pending_topo_changed
            and not self._pending_topo_structural
            and not self._pending_force_full
            and not policy_active
            and not self._last_policy_active
        )
        # structural warm hint (ISSUE 12): node/area membership churn —
        # the delta class a rolling restart, autoscaling event or LSDB
        # key expiry produces.  The backend routes it through the
        # slot-stable encode patch + the generation-delta reset frontier
        # (tombstoned slots reset to +inf) instead of a cold re-encode;
        # its own caches still re-verify compatibility, and any decline
        # (slot exhaustion, area membership change) rebuilds cold with a
        # counted reason.
        structural_delta = (
            self._first_build_done
            and self._pending_topo_changed
            and self._pending_topo_structural
            and not self._pending_force_full
            and not policy_active
            and not self._last_policy_active
        )
        changed = self._pending_prefix_changes
        self._pending_prefix_changes = set()
        self._pending_topo_changed = False
        self._pending_topo_structural = False
        self._pending_force_full = False
        self._pending_down_pairs = set()
        self._pending_other_change = False
        self._last_policy_active = policy_active
        if not force_full and changed:
            self.counters.bump("decision.incremental_route_builds")
        if warm_delta:
            self.counters.bump("decision.warm_delta_builds")
        if structural_delta:
            self.counters.bump("decision.structural_delta_builds")
        # SPF dispatch span: the backend call (scalar solve or device
        # kernel pipeline); guarded jitted dispatches inside it record
        # `decision.spf_kernel` child spans via the jit_guard trace scope
        spf_span = self.tracer.start_span(
            "decision.spf",
            self.tracer.child_ctx(rebuild_span, trace_ctx),
            module="decision",
            backend=type(self.backend).__name__,
            force_full=force_full,
        )
        from openr_tpu.ops import jit_guard

        try:
            with jit_guard.trace_scope(
                self.tracer, self.tracer.child_ctx(spf_span, trace_ctx)
            ):
                new_db = self.backend.build_route_db(
                    self.area_link_states,
                    self.prefix_state,
                    changed_prefixes=(
                        changed if self._first_build_done else None
                    ),
                    force_full=force_full,
                    cache_result=not policy_active,
                    warm_delta=warm_delta,
                    structural_delta=structural_delta,
                )
        finally:
            self.tracer.end_span(spf_span)
            spf_ms = spf_span.duration_ms()
            if spf_ms is not None:
                self.counters.observe("decision.spf_ms", spf_ms)
        self.counters.bump("decision.route_build_runs")
        if new_db is None:
            return
        if self.rib_policy is not None and self.rib_policy.is_active(self.clock):
            self.rib_policy.apply_policy(new_db, self.clock)
        if self.backend.take_full_replace():
            # quarantine swap: the backend replaced corrupt device output
            # with the scalar oracle's FULL db — diff everything so
            # corrupt entries from unsampled builds are purged from the
            # FIB, not just this tick's changed prefixes
            self.counters.bump("decision.quarantine_full_replaces")
            force_full = True
            if self.protection is not None:
                # purge-on-suspicion: the device path just produced
                # corrupt output — nothing it minted is trusted either
                self.protection.purge_table("full_replace")
        # the RouteDb diff is the pipeline's delta-extract tail: the last
        # host stage between device output and the FIB publication
        probe = self._backend_probe()
        if probe is None:
            from openr_tpu.tracing.pipeline import disabled_probe

            probe = disabled_probe()
        from openr_tpu.tracing import pipeline as _pipeline

        with probe.phase(_pipeline.DELTA_EXTRACT):
            warm_changed = None
            if force_full:
                # a warm-selective backend build PATCHED the previous
                # RouteDb and reports exactly which prefixes could have
                # moved — every other entry is object-identical, so the
                # diff stays O(perturbation) even on a topology tick
                take = getattr(
                    self.backend, "take_last_changed_prefixes", None
                )
                if take is not None:
                    warm_changed = take()
            if force_full and warm_changed is not None:
                self.counters.bump("decision.warm_selective_diffs")
                update = self.route_db.calculate_update_for(
                    new_db, warm_changed
                )
            elif force_full:
                update = self.route_db.calculate_update(new_db)
            else:
                # incremental contract: only the changed prefixes can
                # differ — diff O(changed) instead of O(total) so the
                # publication→FIB latency stays flat in prefix count
                update = self.route_db.calculate_update_for(new_db, changed)
        if self._frr_outstanding is not None:
            update = self._confirm_frr(update, new_db)
        first = not self._first_build_done
        if first:
            update = DecisionRouteUpdate(
                type=DecisionRouteUpdateType.FULL_SYNC,
                unicast_routes_to_update=dict(new_db.unicast_routes),
                mpls_routes_to_update=dict(new_db.mpls_routes),
            )
        self.route_db = new_db
        self.counters.set(
            "decision.route_build_ms", (self.clock.now() - t0) * 1000.0
        )
        self.counters.set(
            "decision.num_routes", len(new_db.unicast_routes)
        )
        if first or not update.empty():
            pe = self.pending_perf_events or PerfEvents()
            pe.add(self.node_name, "DECISION_ROUTE_BUILD", self.clock.now_ms())
            update.perf_events = pe
            self.pending_perf_events = None
            # Fib's programming span parents under this rebuild
            update.trace_ctx = self.tracer.child_ctx(rebuild_span, trace_ctx)
            self.route_updates_queue.push(update)
        if first:
            self._first_build_done = True
            if self.initialization_cb is not None:
                self.initialization_cb(InitializationEvent.RIB_COMPUTED)

    # -- RibPolicy API (setRibPolicy, Decision.cpp:634) --------------------

    def set_rib_policy(self, policy: RibPolicy) -> None:
        self.rib_policy = policy
        self._save_rib_policy()
        # a policy flip changes what every computed-result query would
        # return even on an identical LSDB: the fleet/what-if table
        # caches and the serving result cache key on this generation.
        # force_full is set BEFORE the bump so pending_delta_hint reads
        # "full" inside the listeners this bump fires
        self._pending_force_full = True
        self._bump_generation()
        self._rebuild_pending = True
        if self._unblocked:
            self._debounce()

    def get_rib_policy(self) -> Optional[RibPolicy]:
        return self.rib_policy

    def clear_rib_policy(self) -> None:
        self.rib_policy = None
        if self.rib_policy_file and os.path.exists(self.rib_policy_file):
            os.unlink(self.rib_policy_file)
        self._pending_force_full = True
        self._bump_generation()
        self._rebuild_pending = True
        if self._unblocked:
            self._debounce()

    def _save_rib_policy(self) -> None:
        if not self.rib_policy_file or self.rib_policy is None:
            return
        with open(self.rib_policy_file, "w") as f:
            f.write(self.rib_policy.to_json(self.clock))

    def _load_rib_policy(self) -> None:
        if not self.rib_policy_file or not os.path.exists(self.rib_policy_file):
            return
        try:
            with open(self.rib_policy_file) as f:
                self.rib_policy = RibPolicy.from_json(f.read(), self.clock)
        except (ValueError, KeyError):
            self.counters.bump("decision.rib_policy_load_errors")

    # -- ctrl surface ------------------------------------------------------

    def get_route_db(self) -> DecisionRouteDb:
        return self.route_db

    def get_adj_dbs(self, area: Optional[str] = None) -> List[AdjacencyDatabase]:
        out = []
        for a, ls in self.area_link_states.items():
            if area is not None and a != area:
                continue
            out.extend(ls.get_adjacency_databases().values())
        return out

    def get_received_routes(self) -> Dict[str, dict]:
        return {
            prefix: {f"{n}@{a}": e.to_wire() for (n, a), e in entries.items()}
            for prefix, entries in self.prefix_state.prefixes().items()
        }

    def _backend_pool(self):
        """The backend's DevicePool when multi-chip dispatch is active
        — the fleet/what-if engines then spread their batches over the
        same health-governed chips route builds use (a quarantined
        chip serves no computed-result queries either)."""
        fn = getattr(self.backend, "dispatch_pool", None)
        return fn() if fn is not None else None

    def _backend_probe(self):
        """The backend's PipelineProbe (None for scalar backends) — the
        fleet/what-if engines record their phase samples and per-chip
        busy time on the SAME ledger route builds use, so `pipeline.*`
        histograms and `pipeline.devN.*` gauges cover the whole
        dispatch plane."""
        return getattr(self.backend, "probe", None)

    def _fleet(self):
        if self._fleet_engine is None:
            from openr_tpu.decision.fleet import FleetRibEngine

            self._fleet_engine = FleetRibEngine(
                self.solver,
                pool=self._backend_pool(),
                probe=self._backend_probe(),
            )
        return self._fleet_engine

    def device_available(self) -> bool:
        """Device compute usable for fleet/what-if answers: a device
        backend whose accelerator is not in an (injected or real)
        outage.  While `device_failed` is set — chaos `tpu_fail`, or an
        operator draining a sick accelerator — every computed-result
        query must degrade to the scalar/native paths exactly like the
        daemon's own route builds do."""
        return not isinstance(self.backend, ScalarBackend) and not getattr(
            self.backend, "device_failed", False
        )

    def capacity_sweep_inputs(self) -> dict:
        """Everything the capacity-sweep executor (openr_tpu.sweep)
        reads per context build, as one public surface: the live LSDB +
        prefix state + change generation, the backend's DevicePool /
        PipelineProbe / health governor (the sweep dispatches over the
        same health-governed chips route builds use), and the
        selection-rule flag its multi-area decode needs.  The kwargs of
        :class:`openr_tpu.sweep.executor.SweepInputs`."""
        from openr_tpu.types import RouteComputationRules

        return {
            "area_link_states": self.area_link_states,
            "prefix_state": self.prefix_state,
            "change_seq": self._change_seq,
            "root": self.solver.my_node_name,
            "pool": self._backend_pool(),
            "probe": self._backend_probe(),
            "governor": getattr(self.backend, "governor", None),
            "per_area_distance": (
                self.solver.route_selection_algorithm
                == RouteComputationRules.PER_AREA_SHORTEST_DISTANCE
            ),
        }

    def compute_route_db_for_node(self, node: str) -> Optional[DecisionRouteDb]:
        """What-if: the RouteDb as `node` would compute it
        (getRouteDbComputed ctrl API).  When the device fleet engine is
        eligible, ALL nodes' tables come from one cached batch solve and
        only this node's view is decoded; else a fresh scalar pass."""
        if self.device_available():
            fleet = self._fleet()
            if fleet.eligible(
                self.area_link_states, self.prefix_state, self._change_seq
            ):
                try:
                    db = fleet.compute_for_node(
                        node,
                        self.area_link_states,
                        self.prefix_state,
                        self._change_seq,
                    )
                except CapacityError:  # candidate-bucket overflow → scalar
                    db = None
                if db is not None:
                    return db
        solver = SpfSolver(
            node,
            enable_v4=self.solver.enable_v4,
            enable_node_segment_label=self.solver.enable_node_segment_label,
            enable_best_route_selection=self.solver.enable_best_route_selection,
            v4_over_v6_nexthop=self.solver.v4_over_v6_nexthop,
            route_selection_algorithm=self.solver.route_selection_algorithm,
        )
        return solver.build_route_db(self.area_link_states, self.prefix_state)

    def get_link_criticality(self, max_pairs: int = 0) -> Optional[dict]:
        """Blast-radius report: ONE device sweep failing EVERY link
        ranks links by withdrawn/changed routes; ``max_pairs`` > 0 adds
        an exhaustive double-failure scan (run_sets over on-DAG pairs,
        capped) flagging pairs whose combined failure withdraws routes
        neither single failure does — partition risk.  Net-new vs the
        reference (its tooling answers one failure at a time); the
        batch shape is exactly what the set-repair kernel exists for.
        None = ineligible (device feature: scalar-only deployments and
        multi-area vantages decline; KSP2 declines via fleet gating)."""
        if not self.device_available():
            return None
        if len(self.area_link_states) != 1:
            return None
        if not self._fleet().eligible(
            self.area_link_states, self.prefix_state, self._change_seq
        ):
            return None
        if self._whatif_engine is None:
            from openr_tpu.decision.whatif_api import WhatIfApiEngine

            self._whatif_engine = WhatIfApiEngine(self.solver)
        from openr_tpu.decision.whatif_api import (
            _whatif_engine_criticality,
        )

        try:
            result = _whatif_engine_criticality(
                self._whatif_engine,
                self.area_link_states,
                self.prefix_state,
                self._change_seq,
                max_pairs=max_pairs,
            )
        except CapacityError:
            return None
        self.counters.bump("decision.criticality_reports")
        return result

    def _generic_whatif(self):
        """Lazy algorithm-complete fallback engine (jax-free)."""
        if self._whatif_generic_engine is None:
            from openr_tpu.decision.whatif_api import (
                GenericSolverWhatIfEngine,
            )

            self._whatif_generic_engine = GenericSolverWhatIfEngine(
                self.solver
            )
        return self._whatif_generic_engine

    def get_link_failure_whatif(
        self, link_failures: List, simultaneous: bool = False
    ) -> Optional[dict]:
        """'Which of MY routes change if these links fail?' — one
        warm-start sweep over the candidate failures (the flagship
        what-if machinery, cached per LSDB generation).  With
        ``simultaneous``, ALL listed links fail AT ONCE (maintenance-
        window analysis).  Engine choice: single-area vantages pick
        native-vs-device by measured dispatch RT; multi-area LSDBs run
        the set-capable multi-area kernel (singles, bundles AND
        simultaneous sets); KSP2/exotic-algorithm vantages run
        device-backed full builds (DeviceBuildWhatIfEngine).  Only
        scalar-only deployments beyond the native engine's reach fall
        back to the jax-free GenericSolverWhatIfEngine.  None only when
        there is no LSDB yet or a build overflows the candidate
        buckets."""
        scalar_only = not self.device_available()
        fleet = self._fleet()
        if not self.area_link_states:
            return None
        fleet_ok = fleet.eligible(
            self.area_link_states, self.prefix_state, self._change_seq
        )
        generic_reasons = (
            # KSP2 / unsupported selection algorithm on a SCALAR-ONLY
            # deployment: only the jax-free full solver may serve it
            (not fleet_ok and scalar_only)
            # the multi-area engines are device-only; a scalar
            # deployment must never pull in the device stack
            or (scalar_only and len(self.area_link_states) != 1)
        )
        if generic_reasons:
            # algorithm-complete fallback: rebuild the LSDB minus the
            # links and run the FULL solver (jax-free; slow but exact
            # for every configuration the daemon can run)
            result = self._generic_whatif().run(
                [tuple(f) for f in link_failures],
                self.area_link_states,
                self.prefix_state,
                self._change_seq,
                simultaneous=simultaneous,
            )
            if result is not None:
                self.counters.bump("decision.whatif.engine.generic")
            return result
        if not fleet_ok:
            # KSP2 prefixes / exotic selection with a device backend:
            # full builds minus the links on the DEVICE compute path
            # (tables + device KSP2) — the same engines the daemon's
            # own route builds use for these algorithms
            if self._whatif_device_build_engine is None:
                from openr_tpu.decision.whatif_api import (
                    DeviceBuildWhatIfEngine,
                )

                self._whatif_device_build_engine = DeviceBuildWhatIfEngine(
                    self.solver
                )
            result = self._whatif_device_build_engine.run(
                [tuple(f) for f in link_failures],
                self.area_link_states,
                self.prefix_state,
                self._change_seq,
                simultaneous=simultaneous,
            )
            if result is not None:
                self.counters.bump("decision.whatif.engine.device_build")
            return result
        if len(self.area_link_states) == 1:
            # single-area vantage: pick the warm-start engine by where
            # it runs cheapest — the native C++ sweep solves a handful
            # of failures in microseconds, while the device path pays
            # dispatch round trips it can only amortize over large
            # batches (the same measured-RT calibration the backend's
            # device cutover uses)
            use_native = self._use_native_whatif(
                1 if simultaneous else len(link_failures)
            )
            if scalar_only and not use_native:
                # the device engine would load jax (forbidden on a
                # scalar-only deployment) and the native engine declined
                # (vantage fan-out beyond its lane limit, or a batch the
                # calibration priced for the device): answer through the
                # jax-free generic solver instead of going ineligible
                result = self._generic_whatif().run(
                    [tuple(f) for f in link_failures],
                    self.area_link_states,
                    self.prefix_state,
                    self._change_seq,
                    simultaneous=simultaneous,
                )
                if result is not None:
                    self.counters.bump(
                        "decision.whatif.engine.generic"
                    )
                return result
            if use_native:
                if self._whatif_native_engine is None:
                    from openr_tpu.decision.whatif_api import (
                        NativeWhatIfEngine,
                    )

                    self._whatif_native_engine = NativeWhatIfEngine(
                        self.solver
                    )
                engine = self._whatif_native_engine
                engine_name = "native"
            else:
                if self._whatif_engine is None:
                    from openr_tpu.decision.whatif_api import (
                        WhatIfApiEngine,
                    )

                    self._whatif_engine = WhatIfApiEngine(self.solver)
                engine = self._whatif_engine
                engine_name = "device"
        else:
            # multi-area LSDB: fleet-family kernel (per-snapshot masked
            # area re-solve + global selection + cross-area merge)
            if self._whatif_multi_engine is None:
                from openr_tpu.decision.whatif_api import (
                    MultiAreaWhatIfEngine,
                )

                self._whatif_multi_engine = MultiAreaWhatIfEngine(
                    self.solver,
                    pool=self._backend_pool(),
                    probe=self._backend_probe(),
                )
            engine = self._whatif_multi_engine
            engine_name = "multiarea"
        try:
            kwargs = {"simultaneous": True} if simultaneous else {}
            result = engine.run(
                [tuple(f) for f in link_failures],
                self.area_link_states,
                self.prefix_state,
                self._change_seq,
                **kwargs,
            )
            # counted only once an answer actually came back
            self.counters.bump(f"decision.whatif.engine.{engine_name}")
            return result
        except CapacityError:
            # e.g. an anycast prefix wider than the largest candidate
            # bucket.  Multi-area queries previously ANSWERED such
            # configurations through the generic scalar engine — keep
            # that: a device-table overflow must not downgrade a
            # formerly-answerable query to ineligible (r5 review).
            if engine_name == "multiarea":
                result = self._generic_whatif().run(
                    [tuple(f) for f in link_failures],
                    self.area_link_states,
                    self.prefix_state,
                    self._change_seq,
                    simultaneous=simultaneous,
                )
                if result is not None:
                    self.counters.bump("decision.whatif.engine.generic")
                return result
            return None

    def get_decision_paths(
        self, src: str, dst: str, max_hop: int = 256,
        area: Optional[str] = None,
    ) -> dict:
        """Enumerate loop-free src→dst forwarding paths by walking each
        hop's COMPUTED RouteDb (the reference's `breeze decision path`
        DFS over getRouteDbComputed, decision.py:309-360 of its CLI) —
        here each hop's routes decode from the fleet engine's one batch
        solve instead of a scalar Dijkstra per hop.

        ``dst`` is a prefix or a node name (resolved to that node's
        first advertised prefix, the loopback convention).  ``area``
        restricts hop expansion to nexthops learned in that area (the
        reference CLI's --area)."""
        prefixes = self.prefix_state.prefixes()
        if dst in prefixes:
            dst_prefix = dst
        else:
            advertised = sorted(
                p
                for p, entries in prefixes.items()
                if any(node == dst for (node, _a) in entries)
            )
            if not advertised:
                return {
                    "src": src,
                    "dst": dst,
                    "error": f"{dst!r} is neither a known prefix nor an "
                    "advertising node",
                    "paths": [],
                }
            dst_prefix = advertised[0]
        advertisers = {node for (node, _a) in prefixes[dst_prefix]}

        route_cache: Dict[str, object] = {}

        def route_entry(node):
            if node not in route_cache:
                db = self.compute_route_db_for_node(node)
                route_cache[node] = (
                    None
                    if db is None
                    else db.unicast_routes.get(dst_prefix)
                )
            return route_cache[node]

        paths: List[dict] = []
        truncated = [False]

        def dfs(cur, path, visited):
            if len(paths) >= 1024:
                truncated[0] = True
                return
            if cur in advertisers:
                paths.append(list(path))
                return
            if len(path) - 1 >= max_hop:
                truncated[0] = True
                return
            entry = route_entry(cur)
            if entry is None:
                return  # dead end: cur computes no route for dst
            for nh in sorted(
                {
                    n.neighbor_node_name
                    for n in entry.nexthops
                    if area is None or n.area == area
                }
            ):
                if nh in visited:
                    continue
                visited.add(nh)
                path.append(nh)
                dfs(nh, path, visited)
                path.pop()
                visited.discard(nh)

        src_entry = route_entry(src) if src not in advertisers else None
        dfs(src, [src], {src})
        # metric: the src's computed route cost; 0 when src itself
        # advertises dst; None (not a fake zero) when src has no route
        if src in advertisers:
            metric = 0.0
        elif src_entry is not None:
            metric = float(src_entry.igp_cost)
        else:
            metric = None
        return {
            "src": src,
            "dst": dst,
            "dst_prefix": dst_prefix,
            "metric": metric,
            "truncated": truncated[0],
            "paths": [{"hops": p, "num_hops": len(p) - 1} for p in paths],
        }

    #: per-item cost of a native warm solve + numpy selection (rough;
    #: only needs to pick the right side of a ~100x crossover)
    NATIVE_US_PER_ITEM = 0.2

    def _use_native_whatif(self, num_failures: int) -> bool:
        """Native engine iff its estimated sweep cost undercuts the
        device path's dispatch round trips for this query size.  On a
        scalar-only deployment the native engine is the ONLY eligible
        one (no jax ever loads), so no probe runs."""
        from openr_tpu.decision.backend import (
            TpuBackend,
            estimate_scalar_work_items,
            measure_dispatch_rt_ms,
        )
        from openr_tpu.ops.native_spf import MAX_LANES

        me = self.node_name
        (ls,) = self.area_link_states.values()
        # the native solver packs first-hop lanes into one u64 word; a
        # vantage with more out-links than that stays on the device
        # engine (which handles up to the largest degree bucket)
        if len(ls.links_from_node(me)) > MAX_LANES:
            return False
        if not self.device_available():
            # scalar-only deployment, or the device is out: the native
            # engine is the only warm-start option left (no jax loads)
            return True
        is_tpu = isinstance(self.backend, TpuBackend)
        rt_ms = self.backend.auto_dispatch_rt_ms if is_tpu else None
        if rt_ms is None:
            rt_ms = self._whatif_rt_ms or measure_dispatch_rt_ms()
            self._whatif_rt_ms = rt_ms
            if is_tpu:
                # share the calibration so the backend's own cutover
                # doesn't measure again
                self.backend.auto_dispatch_rt_ms = rt_ms
        items = estimate_scalar_work_items(
            self.area_link_states, self.prefix_state
        )
        native_us = max(num_failures, 1) * items * self.NATIVE_US_PER_ITEM
        device_us = TpuBackend.DEVICE_OVERHEAD_TRIPS * rt_ms * 1000.0
        return native_us < device_us

    def get_fleet_rib_summary(self) -> Optional[Dict[str, dict]]:
        """Per-node route counts for EVERY vantage point from one batched
        device solve; None when the fleet engine isn't eligible (incl.
        scalar-only deployments, which must never touch the device
        stack, and device backends in an injected/real outage)."""
        if not self.device_available():
            return None
        fleet = self._fleet()
        if not fleet.eligible(
            self.area_link_states, self.prefix_state, self._change_seq
        ):
            return None
        try:
            return fleet.fleet_summary(
                self.area_link_states, self.prefix_state, self._change_seq
            )
        except CapacityError:  # candidate-bucket overflow → ineligible
            return None
