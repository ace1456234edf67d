"""PrefixManager — owns everything this node advertises.

Reference: openr/prefix-manager/PrefixManager.{h,cpp}:
  * receives PrefixEvents (plugins/API/config) per origination type and
    keeps the authoritative advertised set
  * advertises per-prefix keys ``prefix:<node>:[<prefix>]`` into KvStore
    via the kvRequestQueue (key format common/LsdbTypes.h:437-458)
  * config-originated prefixes with `minimum_supporting_routes`
    aggregation (OpenrConfig.thrift:345-441): the aggregate is advertised
    only while enough more-specific routes are present in the FIB view,
    and optionally installed locally via the static-routes channel
  * area redistribution (PrefixManager.cpp:1507, 1584): routes the FIB
    confirmed programming whose best entry came from area A are
    re-advertised into every other configured area with `area_stack`
    extended and distance accumulated — with loop prevention (never
    redistribute into an area already on the stack)
  * PREFIX_DB_SYNCED initialization event after the first KvStore sync
"""

from __future__ import annotations

import copy
import ipaddress
from typing import Callable, Dict, List, Optional, Set, Tuple

from openr_tpu import constants as C
from openr_tpu.common.runtime import Actor, Clock, CounterMap
from openr_tpu.config import OriginatedPrefix
from openr_tpu.decision.rib import DecisionRouteUpdate, RibUnicastEntry
from openr_tpu.messaging.queue import RQueue, ReplicateQueue
from openr_tpu.tracing import pipeline
from openr_tpu.types import (
    InitializationEvent,
    KeyValueRequest,
    KvRequestType,
    NextHop,
    PrefixDatabase,
    PrefixEntry,
    PrefixEvent,
    PrefixEventType,
    PrefixMetrics,
    PrefixType,
    prefix_key,
)


def serialize_prefix_db(db: PrefixDatabase, fmt: str = "json") -> bytes:
    from openr_tpu.lsdb_codec import serialize_prefix_db as _ser

    return _ser(db, fmt)


def deserialize_prefix_db(data: bytes) -> PrefixDatabase:
    """Format-sniffing: JSON or the reference's thrift-compact bytes."""
    from openr_tpu.lsdb_codec import deserialize_prefix_db as _de

    return _de(data)


class PrefixManager(Actor):
    def __init__(
        self,
        node_name: str,
        clock: Clock,
        kv_request_queue: ReplicateQueue,
        static_route_updates_queue: Optional[ReplicateQueue] = None,
        prefix_updates_reader: Optional[RQueue] = None,
        fib_route_updates_reader: Optional[RQueue] = None,
        areas: Optional[List[str]] = None,
        originated_prefixes: Optional[List[OriginatedPrefix]] = None,
        initialization_cb: Optional[Callable[[InitializationEvent], None]] = None,
        counters: Optional[CounterMap] = None,
        policy_manager=None,
        area_import_policies: Optional[Dict[str, str]] = None,
        lsdb_wire_format: str = "json",
        probe=None,
    ) -> None:
        super().__init__("prefix_manager", clock, counters)
        self.node_name = node_name
        #: flood-payload encoding ("json" | "thrift-compact") — see
        #: openr_tpu.lsdb_codec
        self.lsdb_wire_format = lsdb_wire_format
        self.kv_request_queue = kv_request_queue
        self.static_route_updates_queue = static_route_updates_queue
        self.prefix_updates_reader = prefix_updates_reader
        self.fib_route_updates_reader = fib_route_updates_reader
        self.areas = areas or [C.DEFAULT_AREA]
        self.originated = {p.prefix: p for p in (originated_prefixes or [])}
        self.initialization_cb = initialization_cb
        #: routing-policy engine (openr/policy/PolicyManager): applied at
        #: origination (per-OriginatedPrefix policy) and at area import
        #: during redistribution (AreaConfig.import_policy)
        self.policy_manager = policy_manager
        self.area_import_policies = area_import_policies or {}
        #: type -> prefix -> (entry, dst_areas)
        self.advertised: Dict[
            PrefixType, Dict[str, Tuple[PrefixEntry, Set[str]]]
        ] = {}
        #: originated prefix -> set of supporting more-specific prefixes
        self._supporting: Dict[str, Set[str]] = {
            p: set() for p in self.originated
        }
        self._originated_advertised: Set[str] = set()
        #: redistribution state: prefix -> (src_area, {dst_area: entry})
        #: (per-area entries: each destination's import policy may rewrite
        #: or reject the entry differently)
        self._redistributed: Dict[str, Tuple[str, Dict[str, PrefixEntry]]] = {}
        #: (area, key) currently present in kvstore
        self._advertised_keys: Set[Tuple[str, str]] = set()
        self._synced_signaled = False
        #: the node's PipelineProbe (the one Decision's backend and Fib
        #: record on)
        self.probe = probe if probe is not None else pipeline.disabled_probe()

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> None:
        if self.prefix_updates_reader is not None:
            self.spawn_queue_loop(
                self.prefix_updates_reader, self._on_prefix_event, "pm.events"
            )
        if self.fib_route_updates_reader is not None:
            self.spawn_queue_loop(
                self.fib_route_updates_reader, self._on_fib_update, "pm.fib"
            )
        # initial sync (possibly empty) then signal
        self.schedule(0.0, self._initial_sync)

    def _initial_sync(self) -> None:
        # aggregates whose support threshold is already met (notably
        # minimum_supporting_routes=0) advertise immediately — they must
        # not wait for a FIB update to touch them
        for agg, op in self.originated.items():
            self._refresh_originated(agg, op)
        self._sync_kv_store()
        if not self._synced_signaled:
            self._synced_signaled = True
            if self.initialization_cb is not None:
                self.initialization_cb(InitializationEvent.PREFIX_DB_SYNCED)

    # -- prefix events (PrefixManager.h:217 advertisePrefixesImpl) ---------

    def _on_prefix_event(self, ev: PrefixEvent) -> None:
        by_type = self.advertised.setdefault(ev.type, {})
        dst = set(ev.dst_areas) if ev.dst_areas else set(self.areas)
        if ev.event_type == PrefixEventType.ADD_PREFIXES:
            for entry in ev.prefixes:
                by_type[entry.prefix] = (entry, dst)
        elif ev.event_type == PrefixEventType.WITHDRAW_PREFIXES:
            for entry in ev.prefixes:
                by_type.pop(entry.prefix, None)
        elif ev.event_type == PrefixEventType.WITHDRAW_PREFIXES_BY_TYPE:
            by_type.clear()
        elif ev.event_type == PrefixEventType.SYNC_PREFIXES_BY_TYPE:
            by_type.clear()
            for entry in ev.prefixes:
                by_type[entry.prefix] = (entry, dst)
        self._sync_kv_store()

    # -- fib feedback: originated support + redistribution -----------------

    def _on_fib_update(self, update: DecisionRouteUpdate) -> None:
        with self.probe.phase(pipeline.PREFIX_REDISTRIBUTE):
            changed = False
            for prefix, entry in update.unicast_routes_to_update.items():
                changed |= self._update_supporting(prefix, present=True)
                changed |= self._maybe_redistribute(prefix, entry)
            for prefix in update.unicast_routes_to_delete:
                changed |= self._update_supporting(prefix, present=False)
                changed |= self._withdraw_redistribution(prefix)
            if changed:
                self._sync_kv_store()

    # -- originated prefix aggregation (PrefixManager.h:325-346) -----------

    def _update_supporting(self, prefix: str, present: bool) -> bool:
        changed = False
        net = ipaddress.ip_network(prefix)
        for agg, op in self.originated.items():
            agg_net = ipaddress.ip_network(agg)
            if net.version != agg_net.version or net == agg_net:
                continue
            if not net.subnet_of(agg_net):
                continue
            before = len(self._supporting[agg])
            if present:
                self._supporting[agg].add(prefix)
            else:
                self._supporting[agg].discard(prefix)
            if len(self._supporting[agg]) != before:
                changed |= self._refresh_originated(agg, op)
        return changed

    def _refresh_originated(self, agg: str, op: OriginatedPrefix) -> bool:
        should = len(self._supporting[agg]) >= op.minimum_supporting_routes
        if should and agg not in self._originated_advertised:
            self._originated_advertised.add(agg)
            if op.install_to_fib and self.static_route_updates_queue is not None:
                self.static_route_updates_queue.push(
                    DecisionRouteUpdate(
                        unicast_routes_to_update={
                            agg: RibUnicastEntry(
                                prefix=agg,
                                nexthops={
                                    NextHop(address=C.LOCAL_ROUTE_NEXTHOP_V6)
                                },
                                do_not_install=False,
                            )
                        }
                    )
                )
            return True
        if not should and agg in self._originated_advertised:
            self._originated_advertised.discard(agg)
            if op.install_to_fib and self.static_route_updates_queue is not None:
                self.static_route_updates_queue.push(
                    DecisionRouteUpdate(unicast_routes_to_delete=[agg])
                )
            return True
        return False

    def _originated_entries(self) -> Dict[str, Tuple[PrefixEntry, Set[str]]]:
        out = {}
        for agg in self._originated_advertised:
            op = self.originated[agg]
            entry = PrefixEntry(
                prefix=agg,
                type=PrefixType.CONFIG,
                forwarding_type=op.forwarding_type,
                forwarding_algorithm=op.forwarding_algorithm,
                metrics=PrefixMetrics(
                    path_preference=op.path_preference,
                    source_preference=op.source_preference,
                ),
                tags=set(op.tags),
                min_nexthop=op.min_nexthop,
            )
            # origination policy (PrefixManager.cpp:480: applyPolicy at
            # origination; rejected => not advertised at all)
            policy = getattr(op, "origination_policy", None)
            if policy and self.policy_manager is not None:
                entry, _hit = self.policy_manager.apply_policy(policy, entry)
                if entry is None:
                    self.counters.bump("prefix_manager.policy.origination_rejects")
                    continue
            out[agg] = (entry, set(self.areas))
        return out

    # -- area redistribution (redistributePrefixesAcrossAreas) -------------

    def _maybe_redistribute(self, prefix: str, entry: RibUnicastEntry) -> bool:
        if len(self.areas) < 2:
            return False
        best = entry.best_prefix_entry
        src_area = entry.best_area
        if not src_area:
            return False
        # never re-advertise something we originate ourselves
        if any(prefix in by_type for by_type in self.advertised.values()):
            return False
        if prefix in self.originated:
            return False
        # loop prevention: target areas not on the path already
        stack = list(best.area_stack) + [src_area]
        dst = {a for a in self.areas if a != src_area and a not in stack}
        if not dst:
            return self._withdraw_redistribution(prefix)
        re_entry = copy.deepcopy(best)
        re_entry.area_stack = stack
        re_entry.metrics = PrefixMetrics(
            version=best.metrics.version,
            drain_metric=best.metrics.drain_metric,
            path_preference=best.metrics.path_preference,
            source_preference=best.metrics.source_preference,
            # accumulate the igp cost to reach the originator
            distance=best.metrics.distance + int(entry.igp_cost),
        )
        # per-destination import policy (AreaConfig.import_policy,
        # OpenrConfig.thrift:456-458; applyPolicy at area import,
        # PrefixManager.cpp:1135): rejected areas are simply not targeted
        per_area: Dict[str, PrefixEntry] = {}
        for area in dst:
            area_entry = re_entry
            policy = self.area_import_policies.get(area)
            if policy and self.policy_manager is not None:
                area_entry, _hit = self.policy_manager.apply_policy(
                    policy, re_entry, igp_cost=int(entry.igp_cost)
                )
                if area_entry is None:
                    self.counters.bump("prefix_manager.policy.import_rejects")
                    continue
            per_area[area] = area_entry
        if not per_area:
            return self._withdraw_redistribution(prefix)
        prior = self._redistributed.get(prefix)
        if prior is not None and prior == (src_area, per_area):
            return False
        self._redistributed[prefix] = (src_area, per_area)
        self.counters.bump("prefix_manager.redistributed")
        for area in per_area:
            self.counters.bump(f"prefix_manager.redistributed.{area}")
        return True

    def _withdraw_redistribution(self, prefix: str) -> bool:
        return self._redistributed.pop(prefix, None) is not None

    # -- KvStore sync (syncKvStore, PrefixManager.cpp:617) -----------------

    def desired_advertisements(self) -> Dict[Tuple[str, str], PrefixEntry]:
        """(area, prefix) → entry: everything this node advertises into
        each area right now — API/plugin advertisements (best-per-prefix
        across types), config-originated aggregates, and cross-area
        redistribution.  Single source of truth for BOTH the KvStore sync
        and the ctrl area-view (so the operator surface can never drift
        from what is actually advertised)."""
        desired: Dict[Tuple[str, str], PrefixEntry] = {}
        # API/plugin advertisements; if the same prefix is advertised under
        # several types, resolve deterministically by best metrics (the
        # reference's per-prefix tie-break), ties by lower type value
        best_per_prefix: Dict[str, Tuple[tuple, PrefixEntry, Set[str]]] = {}
        for ptype in sorted(self.advertised):
            for prefix, (entry, dst_areas) in self.advertised[ptype].items():
                rank = (entry.metrics.sort_key(), int(ptype))
                prior = best_per_prefix.get(prefix)
                if prior is None or rank < prior[0]:
                    best_per_prefix[prefix] = (rank, entry, dst_areas)
        for prefix, (_rank, entry, dst_areas) in best_per_prefix.items():
            for area in dst_areas:
                desired[(area, prefix)] = entry
        # config-originated aggregates
        for prefix, (entry, dst_areas) in self._originated_entries().items():
            for area in dst_areas:
                desired[(area, prefix)] = entry
        # cross-area redistribution
        for prefix, (_src, per_area) in self._redistributed.items():
            for area, entry in per_area.items():
                desired[(area, prefix)] = entry
        return desired

    def _sync_kv_store(self) -> None:
        desired = {
            (area, prefix_key(self.node_name, prefix)): entry
            for (area, prefix), entry in self.desired_advertisements().items()
        }

        for (area, key), entry in desired.items():
            db = PrefixDatabase(
                this_node_name=self.node_name,
                prefix_entries=[entry],
                area=area,
            )
            self.kv_request_queue.push(
                KeyValueRequest(
                    request_type=KvRequestType.PERSIST_KEY,
                    area=area,
                    key=key,
                    value=serialize_prefix_db(db, self.lsdb_wire_format),
                )
            )
        # withdraw keys no longer desired: stop refreshing AND flood an
        # explicit deletePrefix tombstone so withdrawal propagates now
        # instead of at TTL expiry (reference withdraws via PrefixDatabase
        # deletePrefix=true, Types.thrift:436-439)
        for area, key in self._advertised_keys - set(desired):
            self.kv_request_queue.push(
                KeyValueRequest(
                    request_type=KvRequestType.CLEAR_KEY, area=area, key=key
                )
            )
            tombstone = PrefixDatabase(
                this_node_name=self.node_name,
                prefix_entries=[],
                delete_prefix=True,
                area=area,
            )
            self.kv_request_queue.push(
                KeyValueRequest(
                    request_type=KvRequestType.SET_KEY,
                    area=area,
                    key=key,
                    value=serialize_prefix_db(tombstone, self.lsdb_wire_format),
                )
            )
        self._advertised_keys = set(desired)
        self.counters.set(
            "prefix_manager.advertised_keys", len(self._advertised_keys)
        )

    # -- API (ctrl surface) ------------------------------------------------

    def advertise(
        self,
        entries: List[PrefixEntry],
        type: PrefixType = PrefixType.BREEZE,
        dst_areas: Optional[Set[str]] = None,
    ) -> None:
        self._on_prefix_event(
            PrefixEvent(PrefixEventType.ADD_PREFIXES, type, entries, dst_areas)
        )

    def withdraw(
        self, entries: List[PrefixEntry], type: PrefixType = PrefixType.BREEZE
    ) -> None:
        self._on_prefix_event(
            PrefixEvent(PrefixEventType.WITHDRAW_PREFIXES, type, entries)
        )

    def withdraw_by_type(self, type: PrefixType) -> None:
        """Drop every advertisement of one source type
        (withdrawPrefixesByType)."""
        self._on_prefix_event(
            PrefixEvent(PrefixEventType.WITHDRAW_PREFIXES_BY_TYPE, type, [])
        )

    def sync_by_type(
        self,
        type: PrefixType,
        entries: List[PrefixEntry],
        dst_areas: Optional[Set[str]] = None,
    ) -> None:
        """Replace one type's advertised set wholesale
        (syncPrefixesByType)."""
        self._on_prefix_event(
            PrefixEvent(
                PrefixEventType.SYNC_PREFIXES_BY_TYPE, type, entries, dst_areas
            )
        )

    def get_by_type(self, type: PrefixType) -> List[PrefixEntry]:
        """Advertised entries of one source type (getPrefixesByType)."""
        return [e for e, _ in self.advertised.get(type, {}).values()]

    def get_advertised_routes(self) -> List[PrefixEntry]:
        out = []
        for by_type in self.advertised.values():
            out.extend(e for e, _ in by_type.values())
        for prefix, (e, _) in self._originated_entries().items():
            out.append(e)
        return out

    def get_originated_prefixes(self) -> Dict[str, dict]:
        return {
            p: {
                "supporting_count": len(self._supporting[p]),
                "advertised": p in self._originated_advertised,
                "minimum_supporting_routes": op.minimum_supporting_routes,
            }
            for p, op in self.originated.items()
        }
