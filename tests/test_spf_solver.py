"""SpfSolver scalar-core tests — semantics ported in spirit from
openr/decision/tests/SpfSolverTest.cpp (drained-node choice, multipath,
MPLS labels, best-route selection, min-nexthop, cross-area merge)."""

import pytest

from openr_tpu.decision.link_state import LinkState
from openr_tpu.decision.prefix_state import PrefixState
from openr_tpu.decision.rib import DecisionRouteDb, RibUnicastEntry
from openr_tpu.decision.spf_solver import SpfSolver
from openr_tpu.emulation.topology import (
    build_adj_dbs,
    grid_edges,
    line_edges,
    ring_edges,
)
from openr_tpu.types import (
    NextHop,
    PrefixEntry,
    PrefixForwardingAlgorithm,
    PrefixForwardingType,
    PrefixMetrics,
)

P1 = "10.1.0.0/16"
P2 = "2001:db8::/64"


def make_area(edges, area="0", **kwargs) -> LinkState:
    ls = LinkState(area)
    for db in build_adj_dbs(edges, area=area, **kwargs).values():
        ls.update_adjacency_database(db)
    return ls


def advertise(ps: PrefixState, node, prefix, area="0", **metrics_kwargs):
    extra = {}
    for k in ("forwarding_type", "forwarding_algorithm", "min_nexthop"):
        if k in metrics_kwargs:
            extra[k] = metrics_kwargs.pop(k)
    entry = PrefixEntry(
        prefix=prefix, metrics=PrefixMetrics(**metrics_kwargs), **extra
    )
    ps.update_prefix(node, area, entry)
    return entry


def test_line_route_via_next_hop():
    ls = make_area(line_edges(3))  # node0-node1-node2
    ps = PrefixState()
    advertise(ps, "node2", P1)
    solver = SpfSolver("node0")
    db = solver.build_route_db({"0": ls}, ps)
    assert db is not None
    route = db.unicast_routes[P1]
    assert route.igp_cost == 2
    nhs = list(route.nexthops)
    assert len(nhs) == 1
    assert nhs[0].neighbor_node_name == "node1"
    assert nhs[0].if_name == "if_node0_node1"


def test_ecmp_two_nexthops():
    edges = [("a", "b", 1), ("a", "c", 1), ("b", "d", 1), ("c", "d", 1)]
    ls = make_area(edges)
    ps = PrefixState()
    advertise(ps, "d", P1)
    solver = SpfSolver("a")
    db = solver.build_route_db({"0": ls}, ps)
    route = db.unicast_routes[P1]
    assert {nh.neighbor_node_name for nh in route.nexthops} == {"b", "c"}
    assert all(nh.metric == 2 for nh in route.nexthops)


def test_skip_route_for_self_advertised_prefix():
    ls = make_area(line_edges(3))
    ps = PrefixState()
    advertise(ps, "node0", P1)  # we advertise it ourselves
    advertise(ps, "node2", P1)
    solver = SpfSolver("node0")
    db = solver.build_route_db({"0": ls}, ps)
    assert P1 not in db.unicast_routes


def test_best_route_selection_path_preference_wins():
    ls = make_area(line_edges(4))
    ps = PrefixState()
    advertise(ps, "node1", P1, path_preference=500)
    advertise(ps, "node3", P1, path_preference=1000)  # farther but preferred
    solver = SpfSolver("node0")
    db = solver.build_route_db({"0": ls}, ps)
    route = db.unicast_routes[P1]
    assert route.igp_cost == 3  # routes to node3 despite node1 being closer
    assert route.best_prefix_entry.metrics.path_preference == 1000


def test_best_route_selection_distance_tiebreak():
    ls = make_area(line_edges(4))
    ps = PrefixState()
    advertise(ps, "node1", P1, distance=2)
    advertise(ps, "node3", P1, distance=1)  # smaller redistribution distance
    solver = SpfSolver("node0")
    db = solver.build_route_db({"0": ls}, ps)
    assert db.unicast_routes[P1].igp_cost == 3


def test_equal_metrics_multiple_winners_union_nexthops():
    # both ends of a ring advertise; equal metrics -> ECMP toward nearest
    ls = make_area(ring_edges(4))
    ps = PrefixState()
    advertise(ps, "node1", P1)
    advertise(ps, "node3", P1)
    solver = SpfSolver("node0")
    db = solver.build_route_db({"0": ls}, ps)
    route = db.unicast_routes[P1]
    # node1 and node3 both at distance 1 -> nexthops to both
    assert {nh.neighbor_node_name for nh in route.nexthops} == {"node1", "node3"}


def test_hard_drained_candidate_filtered():
    ls = make_area(line_edges(4), overloaded=["node1"])
    ps = PrefixState()
    advertise(ps, "node1", P1)
    advertise(ps, "node3", P1)
    solver = SpfSolver("node0")
    db = solver.build_route_db({"0": ls}, ps)
    route = db.unicast_routes[P1]
    # node1 hard-drained -> winner is node3 (3 hops, via node1 as transit?
    # no: node1 overloaded -> no transit -> node3 unreachable... but node1 is
    # the only path; unreachable nodes were already filtered, so the route
    # falls back to node1 per all-drained fallback
    assert route.best_prefix_entry is not None


def test_hard_drain_fallback_when_all_drained():
    ls = make_area(line_edges(2), overloaded=["node1"])
    ps = PrefixState()
    advertise(ps, "node1", P1)
    solver = SpfSolver("node0")
    db = solver.build_route_db({"0": ls}, ps)
    # only candidate is drained: still routed (filterHardDrainedNodes noop)
    route = db.unicast_routes[P1]
    assert route.best_prefix_entry.metrics.drain_metric == 1  # marked drained


def test_soft_drained_node_less_preferred():
    # two advertisers, one soft-drained -> other wins
    edges = [("a", "b", 1), ("a", "c", 1)]
    ls = make_area(edges, soft_drained={"b": 100})
    ps = PrefixState()
    advertise(ps, "b", P1)
    advertise(ps, "c", P1)
    solver = SpfSolver("a")
    db = solver.build_route_db({"0": ls}, ps)
    route = db.unicast_routes[P1]
    assert {nh.neighbor_node_name for nh in route.nexthops} == {"c"}
    assert route.best_prefix_entry.metrics.drain_metric == 0


def test_min_nexthop_gate():
    ls = make_area(line_edges(3))
    ps = PrefixState()
    advertise(ps, "node2", P1, min_nexthop=2)  # need >= 2 nexthops; only 1
    solver = SpfSolver("node0")
    db = solver.build_route_db({"0": ls}, ps)
    assert P1 not in db.unicast_routes


def test_cross_area_min_metric_merge():
    # areas A (a-b-dst) and B (a-c-dst2); dst in A advertises at igp 2,
    # dst2 in B at igp 1 -> only area B nexthops survive
    ls_a = make_area([("a", "b", 1), ("b", "dstA", 1)], area="A")
    ls_b = make_area([("a", "dstB", 1)], area="B")
    ps = PrefixState()
    advertise(ps, "dstA", P1, area="A")
    advertise(ps, "dstB", P1, area="B")
    solver = SpfSolver("a")
    db = solver.build_route_db({"A": ls_a, "B": ls_b}, ps)
    route = db.unicast_routes[P1]
    assert route.igp_cost == 1
    assert {nh.neighbor_node_name for nh in route.nexthops} == {"dstB"}


def test_static_routes_overlay():
    ls = make_area(line_edges(2))
    ps = PrefixState()
    solver = SpfSolver("node0")
    static = RibUnicastEntry(
        prefix=P2, nexthops={NextHop(address="fe80::1", if_name="if_s")}
    )
    solver.update_static_unicast_routes({P2: static}, [])
    db = solver.build_route_db({"0": ls}, ps)
    assert P2 in db.unicast_routes
    # prefixState wins over static for same prefix
    advertise(ps, "node1", P2)
    db2 = solver.build_route_db({"0": ls}, ps)
    assert db2.unicast_routes[P2].best_prefix_entry.prefix == P2
    assert db2.unicast_routes[P2].igp_cost == 1


def test_node_segment_label_routes():
    labels = {"a": 101, "b": 102, "c": 103}
    edges = [("a", "b", 1), ("b", "c", 1)]
    ls = make_area(edges, node_labels=labels)
    solver = SpfSolver("a", enable_node_segment_label=True)
    db = solver.build_route_db({"0": ls}, PrefixState())
    # own label: POP_AND_LOOKUP
    from openr_tpu.types import MplsActionCode

    own = db.mpls_routes[101]
    assert next(iter(own.nexthops)).mpls_action.action == MplsActionCode.POP_AND_LOOKUP
    # directly-connected neighbor: PHP (implicit null)
    php = db.mpls_routes[102]
    nh_b = next(iter(php.nexthops))
    assert nh_b.mpls_action.action == MplsActionCode.PHP
    assert nh_b.mpls_action.swap_label is None
    # two hops away: SWAP with same label
    swap = db.mpls_routes[103]
    nh_c = next(iter(swap.nexthops))
    assert nh_c.mpls_action.action == MplsActionCode.SWAP
    assert nh_c.mpls_action.swap_label == 103


def test_ksp2_two_disjoint_paths():
    # a-b-d cost 2; a-c-d cost 4: KSP2 programs both
    edges = [("a", "b", 1), ("b", "d", 1), ("a", "c", 2), ("c", "d", 2)]
    labels = {"a": 101, "b": 102, "c": 103, "d": 104}
    ls = make_area(edges, node_labels=labels)
    ps = PrefixState()
    advertise(
        ps,
        "d",
        P1,
        forwarding_type=PrefixForwardingType.SR_MPLS,
        forwarding_algorithm=PrefixForwardingAlgorithm.KSP2_ED_ECMP,
    )
    solver = SpfSolver("a")
    db = solver.build_route_db({"0": ls}, ps)
    route = db.unicast_routes[P1]
    by_neighbor = {nh.neighbor_node_name: nh for nh in route.nexthops}
    assert set(by_neighbor) == {"b", "c"}
    assert by_neighbor["b"].metric == 2
    assert by_neighbor["c"].metric == 4
    # label stack pins the path through the downstream node
    assert by_neighbor["b"].mpls_action.push_labels == (104,)
    assert by_neighbor["c"].mpls_action.push_labels == (104,)


def test_route_db_calculate_update():
    old = DecisionRouteDb()
    new = DecisionRouteDb()
    e1 = RibUnicastEntry(prefix=P1, nexthops={NextHop(address="fe80::1")})
    e2 = RibUnicastEntry(prefix=P2, nexthops={NextHop(address="fe80::2")})
    old.add_unicast_route(e1)
    new.add_unicast_route(
        RibUnicastEntry(prefix=P1, nexthops={NextHop(address="fe80::9")})
    )
    new.add_unicast_route(e2)
    delta = old.calculate_update(new)
    assert set(delta.unicast_routes_to_update) == {P1, P2}  # changed + added
    assert delta.unicast_routes_to_delete == []
    delta2 = new.calculate_update(old)
    assert delta2.unicast_routes_to_delete == [P2]
    # no-op diff
    assert new.calculate_update(new).empty()


def test_build_route_db_none_when_node_unknown():
    ls = make_area(line_edges(2))
    solver = SpfSolver("ghost")
    assert solver.build_route_db({"0": ls}, PrefixState()) is None


def test_v4_disabled_skips_v4_prefix():
    ls = make_area(line_edges(2))
    ps = PrefixState()
    advertise(ps, "node1", P1)
    advertise(ps, "node1", P2)
    solver = SpfSolver("node0", enable_v4=False)
    db = solver.build_route_db({"0": ls}, ps)
    assert P1 not in db.unicast_routes
    assert P2 in db.unicast_routes


def test_calculate_update_ignores_igp_cost_only_change():
    # remote metric shift w/ unchanged nexthops must NOT churn the FIB
    nh = {NextHop(address="fe80::1", neighbor_node_name="b")}
    old = DecisionRouteDb()
    new = DecisionRouteDb()
    old.add_unicast_route(RibUnicastEntry(prefix=P1, nexthops=set(nh), igp_cost=2))
    new.add_unicast_route(RibUnicastEntry(prefix=P1, nexthops=set(nh), igp_cost=5))
    assert old.calculate_update(new).empty()


def test_node_label_collision_smaller_name_wins():
    labels = {"a": 101, "bbb": 200, "zzz": 200}  # collision on 200
    edges = [("a", "bbb", 1), ("a", "zzz", 1)]
    ls = make_area(edges, node_labels=labels)
    solver = SpfSolver("a", enable_node_segment_label=True)
    db = solver.build_route_db({"0": ls}, PrefixState())
    nh = next(iter(db.mpls_routes[200].nexthops))
    assert nh.neighbor_node_name == "bbb"  # smaller node name wins


def test_path_a_in_path_b_contiguous_ordered():
    from openr_tpu.decision.link_state import LinkState as LS

    ls = make_area(line_edges(5))
    full = ls.get_kth_paths("node0", "node4", 1)[0]  # 4 links in order
    assert LS.path_a_in_path_b(full[1:3], full)  # contiguous slice
    assert not LS.path_a_in_path_b([full[0], full[2]], full)  # gap
    assert not LS.path_a_in_path_b(list(reversed(full)), full)  # wrong order
    assert LS.path_a_in_path_b([], full)


# ---------------------------------------------------------------------------
# full build's per-build next-hop memo: same answer as the per-prefix path
# ---------------------------------------------------------------------------


def _grid_world():
    ps = PrefixState()
    for i in range(16):
        for p in range(4):
            advertise(ps, f"node{i}", f"10.{i}.{p}.0/24")
    return {"0": make_area(grid_edges(4))}, ps, "node0", {}


def _anycast_world():
    ps = PrefixState()
    for k in range(4):
        # preference decides: node15 loses to the two preferred advertisers
        advertise(ps, "node5", f"10.50.{k}.0/24", path_preference=1000)
        advertise(ps, "node10", f"10.50.{k}.0/24", path_preference=1000)
        advertise(ps, "node15", f"10.50.{k}.0/24", path_preference=500)
        # distance decides
        advertise(ps, "node3", f"10.60.{k}.0/24", distance=2)
        advertise(ps, "node12", f"10.60.{k}.0/24", distance=1)
        # a tie: both advertisers win, next hops toward the nearer
        advertise(ps, "node3", f"10.70.{k}.0/24")
        advertise(ps, "node12", f"10.70.{k}.0/24")
        advertise(ps, "node7", f"10.70.{k}.0/24", distance=3)
    return {"0": make_area(grid_edges(4))}, ps, "node0", {}


def _two_area_world():
    ls_a = make_area(
        [("a", "b", 1), ("b", "x", 1), ("a", "c", 1), ("c", "x", 1)], area="A"
    )
    # x is in both areas: B's SPF scans every winner by name, A's too
    ls_b = make_area([("a", "d", 1), ("d", "y", 1), ("a", "x", 1)], area="B")
    ps = PrefixState()
    for k in range(3):
        # equal igp in both areas: the merge unions A's and B's next hops
        advertise(ps, "x", f"10.1.{k}.0/24", area="A")
        advertise(ps, "y", f"10.1.{k}.0/24", area="B")
        # B is nearer: only B's next hops survive the merge
        advertise(ps, "x", f"10.2.{k}.0/24", area="A")
        advertise(ps, "d", f"10.2.{k}.0/24", area="B")
        advertise(ps, "y", f"10.3.{k}.0/24", area="B")
    return {"A": ls_a, "B": ls_b}, ps, "a", {}


def _v4_v6_world(v4_over_v6_nexthop):
    ps = PrefixState()
    for i in range(9):
        for p in range(3):
            advertise(ps, f"node{i}", f"10.{i}.{p}.0/24")
            advertise(ps, f"node{i}", f"2001:db8:{i}:{p}::/64")
    return (
        {"0": make_area(grid_edges(3))},
        ps,
        "node0",
        {"v4_over_v6_nexthop": v4_over_v6_nexthop},
    )


def _drained_world():
    ls = make_area(grid_edges(4), overloaded=["node5"], soft_drained={"node10": 50})
    ps = PrefixState()
    for k in range(3):
        advertise(ps, "node5", f"10.1.{k}.0/24")  # hard-drained: node6 wins
        advertise(ps, "node6", f"10.1.{k}.0/24")
        advertise(ps, "node10", f"10.2.{k}.0/24")  # soft-drained: node9 wins
        advertise(ps, "node9", f"10.2.{k}.0/24")
        advertise(ps, "node5", f"10.3.{k}.0/24")  # all drained: fallback
        advertise(ps, "node10", f"10.4.{k}.0/24")  # best node drained
    return {"0": ls}, ps, "node0", {}


def _min_nexthop_world():
    ps = PrefixState()
    for k in range(3):
        # node4 is two ECMP paths away from node0
        advertise(ps, "node4", f"10.1.{k}.0/24", min_nexthop=2)  # passes
        advertise(ps, "node4", f"10.2.{k}.0/24", min_nexthop=3)  # gated
        advertise(ps, "node4", f"10.3.{k}.0/24")  # same winners, no gate
        advertise(ps, "node1", f"10.4.{k}.0/24", min_nexthop=2)  # gated
    return {"0": make_area(grid_edges(3))}, ps, "node0", {}


MEMO_WORLDS = {
    "grid": _grid_world,
    "anycast": _anycast_world,
    "two_areas": _two_area_world,
    "v4_v6": lambda: _v4_v6_world(False),
    "v4_over_v6_nexthop": lambda: _v4_v6_world(True),
    "drained": _drained_world,
    "min_nexthop": _min_nexthop_world,
}


def _route_view(entry):
    return (
        set(entry.nexthops),
        entry.igp_cost,
        entry.best_prefix_entry,
        entry.best_area,
        entry.do_not_install,
    )


@pytest.mark.parametrize("world", sorted(MEMO_WORLDS))
def test_full_build_equals_per_prefix_routes(world):
    als, ps, me, kwargs = MEMO_WORLDS[world]()
    solver = SpfSolver(me, **kwargs)
    db = solver.build_route_db(als, ps)
    # prefixes with the same winners really did share the memo
    assert solver.last_build_memo_hits > 0
    alone = SpfSolver(me, **kwargs)
    want = {}
    for prefix in ps.prefixes():
        entry = alone.create_route_for_prefix(prefix, als, ps)
        if entry is not None:
            want[prefix] = _route_view(entry)
    assert alone.last_build_memo_hits == alone.last_build_memo_misses == 0
    got = {p: _route_view(e) for p, e in db.unicast_routes.items()}
    assert got == want


def _metric_world(metric):
    edges = [(a, b, metric if (a, b) == ("node0", "node1") else m)
             for a, b, m in grid_edges(3)]
    ps = PrefixState()
    for p in range(3):
        advertise(ps, "node4", f"10.4.{p}.0/24")
    return {"0": make_area(edges)}, ps


def test_memo_next_hops_follow_a_metric_change():
    solver = SpfSolver("node0")
    als, ps = _metric_world(1)
    before = solver.build_route_db(als, ps)
    assert {
        nh.neighbor_node_name for nh in before.unicast_routes["10.4.0.0/24"].nexthops
    } == {"node1", "node3"}
    als, ps = _metric_world(5)
    after = solver.build_route_db(als, ps)
    for p in range(3):
        route = after.unicast_routes[f"10.4.{p}.0/24"]
        assert {nh.neighbor_node_name for nh in route.nexthops} == {"node3"}
        assert route.igp_cost == 2


def test_memo_does_not_survive_the_build():
    als, ps, me, _ = _grid_world()
    solver = SpfSolver(me)
    solver.build_route_db(als, ps)
    assert solver._next_hop_memo is None
    # a build cut short by an error drops its memo too
    calls = []

    def fail_second(*a, **k):
        calls.append(1)
        if len(calls) == 2:
            raise RuntimeError("cut short")
        return SpfSolver.create_route_for_prefix(solver, *a, **k)

    solver.create_route_for_prefix = fail_second
    with pytest.raises(RuntimeError):
        solver.build_route_db(als, ps)
    assert solver._next_hop_memo is None


def test_no_two_routes_share_a_nexthop_set():
    als, ps, me, _ = _anycast_world()
    db = SpfSolver(me).build_route_db(als, ps)
    sets = [e.nexthops for e in db.unicast_routes.values()]
    assert len({id(s) for s in sets}) == len(sets)
    # and each is a set of its own that a caller may change
    route = db.unicast_routes["10.50.0.0/24"]
    route.nexthops.clear()
    assert db.unicast_routes["10.50.1.0/24"].nexthops


def test_memo_counts_on_a_known_grid():
    # 16 nodes x 4 prefixes; node0's own 4 need no next hops, and each
    # other node's 4 prefixes share that node as their one winner
    als, ps, me, _ = _grid_world()
    solver = SpfSolver(me)
    db = solver.build_route_db(als, ps)
    winner_sets = 15
    assert len(db.unicast_routes) == 15 * 4
    assert solver.last_build_memo_misses == winner_sets
    assert solver.last_build_memo_hits == 15 * 4 - winner_sets
