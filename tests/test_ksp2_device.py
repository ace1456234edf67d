"""Device-backed KSP2_ED_ECMP differential tests.

The device path (decision/ksp2.py: batched masked re-solves + host trace
over device distance fields) must be bit-identical to the scalar chain
(LinkState.get_kth_paths / SpfSolver._select_best_paths_ksp2, mirroring
LinkState.cpp:675-699 + SpfSolver KSP2 semantics).
"""

import pytest

from openr_tpu.decision.backend import ScalarBackend, TpuBackend
from openr_tpu.decision.link_state import LinkState
from openr_tpu.decision.prefix_state import PrefixState
from openr_tpu.decision.spf_solver import SpfSolver
from openr_tpu.emulation.topology import (
    build_adj_dbs,
    fabric_edges,
    grid_edges,
    random_connected_edges,
)
from openr_tpu.types import (
    PrefixEntry,
    PrefixForwardingAlgorithm,
    PrefixForwardingType,
    PrefixMetrics,
)

KSP2 = PrefixForwardingAlgorithm.KSP2_ED_ECMP
SR_MPLS = PrefixForwardingType.SR_MPLS


def make_ls(edges, area="0", me="", **kwargs) -> LinkState:
    ls = LinkState(area, me)
    for db in build_adj_dbs(edges, area=area, **kwargs).values():
        ls.update_adjacency_database(db)
    return ls


def _nh_view(entry):
    return sorted(
        (
            nh.neighbor_node_name,
            nh.if_name,
            nh.metric,
            nh.area,
            None
            if nh.mpls_action is None
            else (nh.mpls_action.action, nh.mpls_action.push_labels),
        )
        for nh in entry.nexthops
    )


def _db_view(db):
    assert db is not None
    return {
        p: (round(e.igp_cost, 1), e.best_area, _nh_view(e))
        for p, e in db.unicast_routes.items()
    }


def assert_backends_match(area_link_states, ps, me="node0", **solver_kwargs):
    scalar = ScalarBackend(SpfSolver(me, **solver_kwargs)).build_route_db(
        area_link_states, ps
    )
    # fresh LinkStates for the device run so scalar memoization cannot leak
    backend = TpuBackend(SpfSolver(me, **solver_kwargs))
    tpu = backend.build_route_db(area_link_states, ps)
    assert _db_view(tpu) == _db_view(scalar)
    return backend


def test_engine_seeded_paths_match_scalar_kth_paths():
    """k=2 paths traced from device distance fields == scalar get_kth_paths."""
    from openr_tpu.decision.ksp2 import Ksp2DeviceEngine
    from openr_tpu.ops.csr import encode_link_state

    edges = fabric_edges(num_pods=2, rsws_per_pod=3, fsws_per_pod=2, num_ssws=4)
    ls_dev = make_ls(edges)
    ls_ref = make_ls(edges)
    root = "rsw0_0"
    dests = [n for n in ls_ref.get_adjacency_databases() if n != root]

    topo = encode_link_state(ls_dev)
    eng = Ksp2DeviceEngine(ls_dev, topo, root)
    eng.seed(dests)
    assert eng.counts["device_batches"] == 1
    assert eng.counts["seeded_dests"] == len(dests)

    for d in dests:
        seeded = ls_dev.get_kth_paths(root, d, 2)
        scalar = ls_ref.get_kth_paths(root, d, 2)
        assert seeded == scalar, d
    # k=1 untouched by seeding: also identical
    for d in dests:
        assert ls_dev.get_kth_paths(root, d, 1) == ls_ref.get_kth_paths(
            root, d, 1
        ), d


def test_engine_seed_is_memoized_until_topology_change():
    from openr_tpu.decision.ksp2 import Ksp2DeviceEngine
    from openr_tpu.ops.csr import encode_link_state

    ls = make_ls(grid_edges(3))
    topo = encode_link_state(ls)
    eng = Ksp2DeviceEngine(ls, topo, "node0")
    eng.seed(["node8", "node4"])
    assert eng.counts["device_batches"] == 1
    eng.seed(["node8", "node4"])  # memo hit: no second device call
    assert eng.counts["device_batches"] == 1
    eng.seed(["node8", "node4", "node7"])  # only the new dest solves
    assert eng.counts["device_batches"] == 2


def test_tpu_backend_ksp2_fabric_matches_scalar():
    edges = fabric_edges(num_pods=3, rsws_per_pod=4, fsws_per_pod=2, num_ssws=4)
    nodes = sorted({n for e in edges for n in e[:2]})
    ps = PrefixState()
    for i, n in enumerate(r for r in nodes if r.startswith("rsw")):
        ps.update_prefix(
            n,
            "0",
            PrefixEntry(f"10.{i}.0.0/24", forwarding_algorithm=KSP2),
        )
    backend = assert_backends_match({"0": make_ls(edges)}, ps, me="rsw0_0")
    assert backend.num_scalar_builds == 0
    assert backend.num_device_builds == 1


def test_tpu_backend_ksp2_sr_mpls_label_stacks():
    # labels pin the non-shortest path: stacks must match scalar exactly
    edges = fabric_edges(num_pods=2, rsws_per_pod=2, fsws_per_pod=2, num_ssws=2)
    nodes = sorted({n for e in edges for n in e[:2]})
    labels = {n: 100 + i for i, n in enumerate(nodes)}

    def mk(me):
        ls = LinkState("0", me)
        for db in build_adj_dbs(edges, node_labels=labels).values():
            ls.update_adjacency_database(db)
        return ls

    ps = PrefixState()
    ps.update_prefix(
        "rsw1_1",
        "0",
        PrefixEntry(
            "2001:db8::/64",
            forwarding_type=SR_MPLS,
            forwarding_algorithm=KSP2,
        ),
    )
    me = "rsw0_0"
    scalar = ScalarBackend(SpfSolver(me)).build_route_db({"0": mk(me)}, ps)
    backend = TpuBackend(SpfSolver(me))
    tpu = backend.build_route_db({"0": mk(me)}, ps)
    assert _db_view(tpu) == _db_view(scalar)
    assert backend.num_scalar_builds == 0
    # the KSP2 second path must actually carry a PUSH stack
    stacks = [
        nh.mpls_action.push_labels
        for nh in tpu.unicast_routes["2001:db8::/64"].nexthops
        if nh.mpls_action is not None
    ]
    assert stacks, "expected SR-MPLS push stacks on non-shortest paths"


def test_tpu_backend_ksp2_anycast_and_min_nexthop():
    edges = grid_edges(4)
    ps = PrefixState()
    # anycast from two corners, KSP2
    for n in ("node15", "node12"):
        ps.update_prefix(
            n, "0", PrefixEntry("10.0.0.0/24", forwarding_algorithm=KSP2)
        )
    # min-nexthop too high -> withheld (gate applies to the k-path union)
    ps.update_prefix(
        "node9",
        "0",
        PrefixEntry(
            "10.1.0.0/24", forwarding_algorithm=KSP2, min_nexthop=64
        ),
    )
    backend = assert_backends_match(
        {"0": make_ls(edges, me="node0")}, ps, me="node0"
    )
    assert backend.num_scalar_builds == 0


def test_tpu_backend_mixed_ksp2_and_spf_prefixes():
    edges = grid_edges(4)
    ps = PrefixState()
    ps.update_prefix(
        "node15", "0", PrefixEntry("10.0.0.0/24", forwarding_algorithm=KSP2)
    )
    ps.update_prefix("node12", "0", PrefixEntry("10.1.0.0/24"))
    ps.update_prefix("node3", "0", PrefixEntry("2001:db8::/64"))
    backend = assert_backends_match(
        {"0": make_ls(edges, me="node0")}, ps, me="node0"
    )
    assert backend.num_scalar_builds == 0


def test_ksp2_algorithm_chosen_by_min_winner_not_any_advertiser():
    """A KSP2 advertisement that LOSES selection must not switch the
    prefix to the KSP2 path (SpfSolver.cpp:247-250)."""
    edges = grid_edges(3)
    ps = PrefixState()
    # node8 wins on path_preference with SP_ECMP; node4's KSP2 entry loses
    ps.update_prefix(
        "node8",
        "0",
        PrefixEntry("10.0.0.0/24", metrics=PrefixMetrics(path_preference=1000)),
    )
    ps.update_prefix(
        "node4",
        "0",
        PrefixEntry(
            "10.0.0.0/24",
            forwarding_algorithm=KSP2,
            metrics=PrefixMetrics(path_preference=100),
        ),
    )
    backend = assert_backends_match(
        {"0": make_ls(edges, me="node0")}, ps, me="node0"
    )
    assert backend.num_scalar_builds == 0


@pytest.mark.parametrize("seed", [1, 2, 3, 4, 5])
def test_tpu_backend_ksp2_random_topologies(seed):
    edges = random_connected_edges(24, 30, seed=seed)
    ps = PrefixState()
    for i, n in enumerate(["node5", "node11", "node17", "node23"]):
        ps.update_prefix(
            n,
            "0",
            PrefixEntry(f"10.{i}.0.0/24", forwarding_algorithm=KSP2),
        )
    backend = assert_backends_match(
        {"0": make_ls(edges, me="node0")}, ps, me="node0"
    )
    assert backend.num_scalar_builds == 0


def test_tpu_backend_ksp2_with_drains():
    edges = grid_edges(4)
    ps = PrefixState()
    for n in ("node15", "node5", "node10"):
        ps.update_prefix(
            n, "0", PrefixEntry("10.0.0.0/24", forwarding_algorithm=KSP2)
        )
    ls_kwargs = dict(overloaded=["node5"], soft_drained={"node10": 60})
    me = "node0"
    scalar = ScalarBackend(SpfSolver(me)).build_route_db(
        {"0": make_ls(edges, me=me, **ls_kwargs)}, ps
    )
    backend = TpuBackend(SpfSolver(me))
    tpu = backend.build_route_db(
        {"0": make_ls(edges, me=me, **ls_kwargs)}, ps
    )
    assert _db_view(tpu) == _db_view(scalar)
    assert backend.num_scalar_builds == 0
