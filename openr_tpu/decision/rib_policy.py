"""RibPolicy — TTL'd nexthop-weight policy applied to the computed RIB.

Reference: openr/decision/RibPolicy.{h,cpp}: a policy is a list of
statements, each matching routes (by prefix or tag) and applying an
action that sets per-nexthop weights (default / per-area / per-neighbor;
weight 0 drops the nexthop).  The policy carries a TTL and is persisted
by Decision (Decision.cpp:634-708) so it survives restarts until expiry.
"""

from __future__ import annotations

import json
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Set

from openr_tpu.common.runtime import Clock
from openr_tpu.decision.rib import DecisionRouteDb, RibUnicastEntry
from openr_tpu.types import NextHop


@dataclass
class RibRouteActionWeight:
    """if/OpenrCtrl.thrift RibRouteActionWeight."""

    default_weight: int = 1
    area_to_weight: Dict[str, int] = field(default_factory=dict)
    neighbor_to_weight: Dict[str, int] = field(default_factory=dict)


@dataclass
class RibPolicyStatement:
    """Match (prefixes OR tags) → action (RibPolicy.h:24-80)."""

    name: str = ""
    prefixes: List[str] = field(default_factory=list)
    tags: Set[str] = field(default_factory=set)
    action: RibRouteActionWeight = field(default_factory=RibRouteActionWeight)

    def __post_init__(self) -> None:
        # built once: ``matches`` runs once per route, and a list scan
        # there costs routes x prefixes
        self._prefix_set = frozenset(self.prefixes)

    def matches(self, entry: RibUnicastEntry) -> bool:
        if self.prefixes and entry.prefix in self._prefix_set:
            return True
        if self.tags and self.tags & entry.best_prefix_entry.tags:
            return True
        return False

    def apply_action(self, entry: RibUnicastEntry) -> bool:
        """Re-weight nexthops in place; weight 0 drops.  Returns True if
        the entry changed (RibPolicyStatement::applyAction)."""
        new_nexthops = set()
        changed = False
        for nh in entry.nexthops:
            w = self.action.neighbor_to_weight.get(
                nh.neighbor_node_name,
                self.action.area_to_weight.get(
                    nh.area, self.action.default_weight
                ),
            )
            if w == 0:
                changed = True
                continue
            if w != nh.weight:
                changed = True
                nh = NextHop(
                    address=nh.address,
                    if_name=nh.if_name,
                    metric=nh.metric,
                    weight=w,
                    area=nh.area,
                    neighbor_node_name=nh.neighbor_node_name,
                    mpls_action=nh.mpls_action,
                )
            new_nexthops.add(nh)
        if changed:
            entry.nexthops = new_nexthops
        return changed


@dataclass
class RibPolicyStats:
    """What one ``apply_policy`` pass did (Decision's
    ``decision.rib_policy.*`` counters)."""

    #: routes some statement matched
    routes_matched: int = 0
    #: matched routes whose next hops changed (re-weighted or dropped)
    routes_modified: int = 0
    #: next hops a weight of 0 removed
    nexthops_dropped: int = 0
    #: routes left with no next hop, and so removed
    routes_dropped: int = 0


@dataclass
class RibPolicy:
    statements: List[RibPolicyStatement] = field(default_factory=list)
    #: absolute expiry on the shared clock; None = no policy
    valid_until: float = 0.0

    def is_active(self, clock: Clock) -> bool:
        return clock.now() < self.valid_until

    def apply_policy(
        self, route_db: DecisionRouteDb, clock: Clock
    ) -> RibPolicyStats:
        """Apply to every matching route (RibPolicy::applyPolicy, used in
        Decision.cpp:917-950); returns what it matched, changed and
        dropped."""
        stats = RibPolicyStats()
        if not self.is_active(clock):
            return stats
        for entry in route_db.unicast_routes.values():
            for stmt in self.statements:
                if stmt.matches(entry):
                    stats.routes_matched += 1
                    before = len(entry.nexthops)
                    if stmt.apply_action(entry):
                        stats.routes_modified += 1
                        stats.nexthops_dropped += before - len(entry.nexthops)
                    break  # first matching statement wins
        # drop routes whose nexthops were all zero-weighted
        for prefix in [
            p for p, e in route_db.unicast_routes.items() if not e.nexthops
        ]:
            del route_db.unicast_routes[prefix]
            stats.routes_dropped += 1
        return stats

    # -- persistence (FLAGS_rib_policy_file pattern) -----------------------

    def to_json(self, clock: Clock) -> str:
        return json.dumps(
            {
                "ttl_remaining_s": max(0.0, self.valid_until - clock.now()),
                "statements": [
                    {
                        "name": s.name,
                        "prefixes": s.prefixes,
                        "tags": sorted(s.tags),
                        "action": {
                            "default_weight": s.action.default_weight,
                            "area_to_weight": s.action.area_to_weight,
                            "neighbor_to_weight": s.action.neighbor_to_weight,
                        },
                    }
                    for s in self.statements
                ],
            }
        )

    @classmethod
    def from_json(cls, text: str, clock: Clock) -> Optional["RibPolicy"]:
        d = json.loads(text)
        ttl = d.get("ttl_remaining_s", 0.0)
        if ttl <= 0:
            return None
        return cls(
            statements=[
                RibPolicyStatement(
                    name=s.get("name", ""),
                    prefixes=list(s.get("prefixes", [])),
                    tags=set(s.get("tags", [])),
                    action=RibRouteActionWeight(
                        default_weight=s["action"].get("default_weight", 1),
                        area_to_weight=dict(s["action"].get("area_to_weight", {})),
                        neighbor_to_weight=dict(
                            s["action"].get("neighbor_to_weight", {})
                        ),
                    ),
                )
                for s in d.get("statements", [])
            ],
            valid_until=clock.now() + ttl,
        )
