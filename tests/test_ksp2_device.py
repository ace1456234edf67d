"""Device-backed KSP2_ED_ECMP differential tests.

The device path (decision/ksp2.py: batched masked re-solves + host trace
over device distance fields) must be bit-identical to the scalar chain
(LinkState.get_kth_paths / SpfSolver._select_best_paths_ksp2, mirroring
LinkState.cpp:675-699 + SpfSolver KSP2 semantics).
"""

import dataclasses

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
    # k=1 is seeded from the root's base SPF: also identical
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


# ---------------------------------------------------------------------------
# traced k-paths kept across link events (Ksp2Record)
# ---------------------------------------------------------------------------


def _f4_edges():
    from cellbench.topologies import f4fabric

    return f4fabric.edges(
        {"pods": 2, "rsws_per_pod": 3, "planes": 2, "ssws_per_plane": 2}
    )


def _tied_random_edges():
    # metrics 1-2 only: many equal-cost paths, so the trace's tie order
    # decides which links each family takes
    return [
        (a, b, 1 + m % 2) for a, b, m in random_connected_edges(18, 22, seed=7)
    ]


#: name -> (edges, root); the fabrics are seen from a fabric switch, where
#: every rack has a second path family
TOPOLOGIES = {
    "fabric": (
        lambda: fabric_edges(
            num_pods=3, rsws_per_pod=3, fsws_per_pod=2, num_ssws=4
        ),
        "fsw0_0",
    ),
    "f4": (_f4_edges, "fsw0_0"),
    "random_ties": (_tied_random_edges, "node0"),
}


class _Network:
    """A LinkState fed link events as each endpoint's LinkMonitor would
    publish them: a link down withdraws both adjacencies."""

    def __init__(self, edges, root):
        self.root = root
        self.base = {(a, b): m for a, b, m in edges}
        self.metric = dict(self.base)
        self.down = set()
        self.overloaded = []
        self.nodes = sorted({n for e in edges for n in e[:2]})
        self.labels = {n: 100 + i for i, n in enumerate(self.nodes)}
        #: (node, neighbour) -> the v4 next hop node uses over that link
        self.nh_v4 = {}
        self.ls = self.fresh()

    def edges(self):
        return [(a, b, m) for (a, b), m in self.metric.items()
                if (a, b) not in self.down]

    def dbs(self):
        from openr_tpu.types import AdjacencyDatabase

        dbs = build_adj_dbs(self.edges(), node_labels=self.labels,
                            overloaded=self.overloaded)
        for (n, other), address in self.nh_v4.items():
            dbs[n].adjacencies = [
                a if a.other_node_name != other
                else dataclasses.replace(a, next_hop_v4=address)
                for a in dbs[n].adjacencies
            ]
        for n in self.nodes:
            dbs.setdefault(n, AdjacencyDatabase(
                this_node_name=n, adjacencies=[], area="0",
                node_label=self.labels[n],
                is_overloaded=n in self.overloaded))
        return dbs

    def fresh(self) -> LinkState:
        ls = LinkState("0", self.root)
        for db in self.dbs().values():
            ls.update_adjacency_database(db)
        return ls

    def publish(self, *nodes):
        dbs = self.dbs()
        for n in nodes:
            self.ls.update_adjacency_database(dbs[n])

    def apply(self, kind, link, metric=None):
        if kind == "down":
            self.down.add(link)
        elif kind == "up":
            self.down.discard(link)
        elif kind == "metric":
            self.metric[link] = metric
        else:  # restore
            self.metric[link] = self.base[link]
        self.publish(*link)


def _ksp2_prefixes(nodes, root):
    ps = PrefixState()
    for i, n in enumerate(x for x in nodes if x != root):
        ps.update_prefix(n, "0", PrefixEntry(
            f"10.{i // 256}.{i % 256}.0/24", forwarding_type=SR_MPLS,
            forwarding_algorithm=KSP2))
    return ps


def _record_backend(root):
    from openr_tpu.config import ResilienceConfig

    # no governor: a shadow swap would hide a wrong device build behind
    # the oracle's routes
    return TpuBackend(SpfSolver(root),
                      resilience=ResilienceConfig(enabled=False))


def _assert_exact(net, backend, ps):
    """The memo the device path seeded equals a fresh LinkState's host
    k-paths, and the routes equal a scalar build's."""
    tpu = backend.build_route_db({"0": net.ls}, ps)
    fresh = net.fresh()
    reachable = fresh.get_spf_result(net.root)
    for d in net.nodes:
        if d == net.root:
            continue
        # every reachable destination came from the device path's seed
        assert net.ls.has_kth_paths(net.root, d, 2) == (d in reachable), d
        for k in (1, 2):
            assert net.ls.get_kth_paths(net.root, d, k) == (
                fresh.get_kth_paths(net.root, d, k)), (d, k)
    scalar = ScalarBackend(SpfSolver(net.root)).build_route_db(
        {"0": net.fresh()}, ps)
    assert _db_view(tpu) == _db_view(scalar)
    # every field, next-hop addresses and the best entry included
    assert tpu.unicast_routes == scalar.unicast_routes
    return tpu


@pytest.mark.parametrize("seed", [11, 12])
@pytest.mark.parametrize("topology", sorted(TOPOLOGIES))
def test_retained_ksp2_paths_match_fresh_kth_paths_across_link_events(
    topology, seed
):
    import random

    make_edges, root = TOPOLOGIES[topology]
    net = _Network(make_edges(), root)
    ps = _ksp2_prefixes(net.nodes, root)
    backend = _record_backend(root)
    _assert_exact(net, backend, ps)
    rng = random.Random(seed)
    links = sorted(net.base)
    for _cycle in range(8):
        gone, bent = rng.sample(links, 2)
        for kind, link in (("down", gone), ("metric", bent),
                           ("restore", bent), ("up", gone)):
            net.apply(kind, link, rng.randint(2, 4))
            _assert_exact(net, backend, ps)
    counts = backend.ksp2_counts
    assert backend.num_scalar_builds == 0
    assert counts["resolve_rows"] == counts["seeded_dests"]
    assert counts["reused_dests"] + counts["retraced_dests"] == (
        counts["seeded_dests"])
    assert counts["full_retrace"] == counts["full_retrace.cold"] == 1
    assert counts["reused_dests"] > 0


@pytest.mark.parametrize("topology", ["fabric", "f4"])
def test_event_on_a_visited_node_retraces_that_destination(topology):
    make_edges, root = TOPOLOGIES[topology]
    net = _Network(make_edges(), root)
    ps = _ksp2_prefixes(net.nodes, root)
    backend = _record_backend(root)
    _assert_exact(net, backend, ps)
    rec = backend.gen.ksp2_records["0"]
    dest = max(d for d in rec.dests if d.startswith("rsw"))
    fam1, fam2 = rec.dests[dest]
    # a link incident to a visited node, on neither family's paths
    on_paths = {k for fam in (fam1, fam2) for p in fam.paths for k in p}
    link = next(
        (a, b) for (a, b) in sorted(net.base)
        if (a in fam2.visited or b in fam2.visited)
        and not any({a, b} == {k[0], k[2]} for k in on_paths)
    )
    before = dict(rec.dests)
    net.apply("metric", link, 3)
    _assert_exact(net, backend, ps)
    assert rec.dests[dest][1] is not fam2
    # ... while the detector kept both families of some other destination
    kept = [d for d, fams in rec.dests.items()
            if all(a is b for a, b in zip(fams, before[d]))]
    assert kept and dest not in kept
    assert backend.ksp2_counts["reused_dests"] == len(kept)


def test_later_seeds_of_one_generation_keep_as_the_first_does():
    """A decode in several shards seeds each shard's destinations in
    turn: every seed of the generation keeps from the same prior."""
    from openr_tpu.decision.ksp2 import Ksp2DeviceEngine, Ksp2Record
    from openr_tpu.ops.csr import encode_link_state

    make_edges, root = TOPOLOGIES["f4"]
    kept = {}
    for shards in (1, 3):
        net = _Network(make_edges(), root)
        rec = Ksp2Record(net.ls, root)
        dests = [n for n in net.nodes if n != root]

        def seed_all():
            eng = Ksp2DeviceEngine(net.ls, encode_link_state(net.ls), root,
                                   record=rec)
            for i in range(shards):
                eng.seed(dests[i::shards])
            return eng.counts

        seed_all()
        net.apply("metric", sorted(net.base)[0], 3)
        counts = seed_all()
        for d in dests:
            for k in (1, 2):
                assert net.ls.get_kth_paths(root, d, k) == (
                    net.fresh().get_kth_paths(root, d, k)), (d, k)
        kept[shards] = (counts["reused_dests"], set(rec.kept))
    assert kept[3] == kept[1] and kept[1][0] > 0


def _one_build_counts(backend, net, ps):
    before = dict(backend.ksp2_counts)
    _assert_exact(net, backend, ps)
    return {k: v - before.get(k, 0) for k, v in backend.ksp2_counts.items()}


def test_overload_flip_and_node_leaving_retrace_every_destination():
    make_edges, root = TOPOLOGIES["fabric"]
    net = _Network(make_edges(), root)
    ps = _ksp2_prefixes(net.nodes, root)
    backend = _record_backend(root)
    _assert_exact(net, backend, ps)
    rec = backend.gen.ksp2_records["0"]
    dests = len(rec.dests)

    net.overloaded = ["fsw1_1"]
    net.publish("fsw1_1")
    got = _one_build_counts(backend, net, ps)
    assert got["full_retrace.overload"] == 1
    assert got["retraced_dests"] == dests and got.get("reused_dests", 0) == 0

    net.overloaded = []
    net.publish("fsw1_1")
    _assert_exact(net, backend, ps)
    gone = "rsw2_0"
    net.ls.delete_adjacency_database(gone)
    net.nodes.remove(gone)
    net.base = {e: m for e, m in net.base.items() if gone not in e}
    net.metric = {e: m for e, m in net.metric.items() if gone not in e}
    net.publish("fsw2_0", "fsw2_1")
    got = _one_build_counts(backend, net, ps)
    assert got["full_retrace.node_set"] == 1
    assert got.get("reused_dests", 0) == 0
    assert got["retraced_dests"] == len(rec.dests) == dests - 1


def test_suspect_purge_drops_the_record_and_the_next_build_retraces_all():
    make_edges, root = TOPOLOGIES["fabric"]
    net = _Network(make_edges(), root)
    ps = _ksp2_prefixes(net.nodes, root)
    backend = _record_backend(root)
    _assert_exact(net, backend, ps)
    rec = backend.gen.ksp2_records["0"]
    dests = len(rec.dests)
    backend.inject_silent_corruption(True)
    assert rec.dests == {} and rec.reason == "purge"
    backend.inject_silent_corruption(False)
    # no topology change: the purge alone re-traces every destination
    got = _one_build_counts(backend, net, ps)
    assert got["full_retrace.purge"] == 1
    assert got["retraced_dests"] == dests and got.get("reused_dests", 0) == 0
    # a size purge trusts the device: the record stays
    backend.gen.purge(suspect=False)
    assert len(rec.dests) == dests


def test_device_built_masks_equal_link_failure_batch():
    import jax.numpy as jnp
    import numpy as np

    from openr_tpu.ops.csr import encode_link_state, link_failure_batch
    from openr_tpu.ops.spf import link_ignore_masks

    ls = make_ls(_f4_edges())
    topo = encode_link_state(ls)
    L = len(topo.links)
    rows = [[0, 3, L - 1], [], [5], list(range(0, L, 2))]
    width = 16
    assert max(map(len, rows)) <= width
    # padding rows (all -1) ignore nothing, as link_failure_batch's []
    rows += [[]] * 4
    ids = np.full((len(rows), width), -1, np.int32)
    for r, row in enumerate(rows):
        ids[r, : len(row)] = row
    got = np.asarray(link_ignore_masks(
        jnp.asarray(topo.link_edge_pos), jnp.asarray(ids),
        num_edges=topo.padded_edges))
    want = link_failure_batch(topo, rows)
    assert got.dtype == want.dtype and got.shape == want.shape
    assert (got == want).all()
    assert not got[0].all() and got[1].all() and got[-1].all()


# ---------------------------------------------------------------------------
# assembled KSP2 routes kept across builds (Ksp2RouteCache)
# ---------------------------------------------------------------------------


def _assembly_spy(backend):
    """Record the prefixes each build handed to route assembly and the
    ones the scalar KSP2 chain assembled anew."""
    spy = {"assembled": [], "rebuilt": []}
    ksp2_routes = backend._ksp2_routes
    create = backend.solver.create_route_for_prefix

    def routes(ksp2, *args):
        spy["assembled"].extend(ksp2[0])
        return ksp2_routes(ksp2, *args)

    def create_route(prefix, *args):
        spy["rebuilt"].append(prefix)
        return create(prefix, *args)

    backend._ksp2_routes = routes
    backend.solver.create_route_for_prefix = create_route
    return spy


def _build_delta(backend, net, ps, spy):
    """One exact build: its counter deltas, and the prefixes it rebuilt."""
    spy["assembled"].clear()
    spy["rebuilt"].clear()
    got = _one_build_counts(backend, net, ps)
    reused = got.get("reused_routes", 0)
    rebuilt = got.get("rebuilt_routes", 0)
    assert reused + rebuilt == len(spy["assembled"])
    assert rebuilt == len(spy["rebuilt"])
    return reused, set(spy["rebuilt"])


def _prefix_of(ps, node):
    return next(p for p, e in ps.prefixes().items() if (node, "0") in e)


def _route_world(topology="fabric"):
    make_edges, root = TOPOLOGIES[topology]
    net = _Network(make_edges(), root)
    ps = _ksp2_prefixes(net.nodes, root)
    backend = _record_backend(root)
    spy = _assembly_spy(backend)
    first = _assert_exact(net, backend, ps)
    return net, ps, backend, spy, first


@pytest.mark.parametrize("topology", sorted(TOPOLOGIES))
def test_kept_ksp2_routes_match_scalar_across_link_events(topology):
    import random

    net, ps, backend, spy, _ = _route_world(topology)
    rng = random.Random(21)
    links = sorted(net.base)
    reused_total = 0
    for _cycle in range(6):
        gone, bent = rng.sample(links, 2)
        for kind, link in (("down", gone), ("metric", bent),
                           ("restore", bent), ("up", gone)):
            net.apply(kind, link, rng.randint(2, 4))
            before = backend.gen.ksp2_routes.routes.copy()
            reused, rebuilt = _build_delta(backend, net, ps, spy)
            reused_total += reused
            # a reused prefix is handed the very entry it had
            for p in set(spy["assembled"]) - rebuilt:
                assert backend.gen.ksp2_routes.routes[p] is before[p]
    assert reused_total > 0
    counts = backend.ksp2_counts
    assert counts["reused_routes"] + counts["rebuilt_routes"] > reused_total
    assert backend.num_scalar_builds == 0


def test_changed_advertisement_rebuilds_its_route_on_an_unchanged_topology():
    from openr_tpu.types import PrefixEntry

    net, ps, backend, spy, first = _route_world()
    prefix = _prefix_of(ps, "rsw2_1")
    assert first.unicast_routes[prefix].nexthops
    for min_nexthop in (99, None):
        ps.update_prefix("rsw2_1", "0", PrefixEntry(
            prefix, forwarding_type=SR_MPLS, forwarding_algorithm=KSP2,
            min_nexthop=min_nexthop))
        reused, rebuilt = _build_delta(backend, net, ps, spy)
        assert rebuilt == {prefix} and reused > 0
    # the gate withdrew the route, and the re-advertisement restored it
    assert prefix in backend.gen.last_db.unicast_routes


def test_anycast_prefix_rebuilds_when_one_advertiser_was_not_kept():
    from openr_tpu.types import PrefixEntry

    net, ps, backend, spy, _ = _route_world()
    rec = backend.gen.ksp2_records["0"]
    moved = "rsw1_0"
    link = next(e for e in sorted(net.base) if moved in e)
    ends = set(link)
    # an advertiser whose traces never visit the moved link's ends
    steady = next(
        d for d in sorted(rec.dests)
        if d.startswith("rsw") and d != moved
        and not any(ends & fam.visited for fam in rec.dests[d])
    )
    anycast = "10.250.0.0/24"
    for node in (moved, steady):
        ps.update_prefix(node, "0", PrefixEntry(
            anycast, forwarding_type=SR_MPLS, forwarding_algorithm=KSP2))
    _build_delta(backend, net, ps, spy)
    net.apply("metric", link, 3)
    reused, rebuilt = _build_delta(backend, net, ps, spy)
    assert steady in rec.kept and moved not in rec.kept
    assert anycast in rebuilt and _prefix_of(ps, moved) in rebuilt
    assert _prefix_of(ps, steady) not in rebuilt and reused > 0


def test_node_label_change_on_a_kept_path_rebuilds_the_route():
    net, ps, backend, spy, first = _route_world()
    dest = "rsw2_1"
    prefix = _prefix_of(ps, dest)
    # the first node past the first hop of a second-family path: the top
    # of its PUSH stack
    path = net.ls.get_kth_paths(net.root, dest, 2)[0]
    node = path[1].get_other_node_name(
        path[0].get_other_node_name(net.root))
    assert node != dest
    seq = net.ls.topology_seq
    net.labels[node] += 1000
    net.publish(node)
    assert net.ls.topology_seq == seq  # labels move no topology generation
    reused, rebuilt = _build_delta(backend, net, ps, spy)
    assert prefix in rebuilt and reused == 0
    assert (backend.gen.last_db.unicast_routes[prefix]
            != first.unicast_routes[prefix])


def test_root_link_address_change_rebuilds_the_route():
    net, ps, backend, spy, first = _route_world()
    root = net.root
    prefix = _prefix_of(ps, "rsw2_1")
    via = sorted(nh.neighbor_node_name
                 for nh in first.unicast_routes[prefix].nexthops)[0]
    net.nh_v4[(root, via)] = "10.9.9.9"
    seq = net.ls.topology_seq
    net.publish(root)
    assert net.ls.topology_seq == seq
    reused, rebuilt = _build_delta(backend, net, ps, spy)
    assert prefix in rebuilt and reused == 0
    assert "10.9.9.9" in {
        nh.address
        for nh in backend.gen.last_db.unicast_routes[prefix].nexthops
    }


def test_entry_reweighted_in_place_is_rebuilt_and_policy_builds_share_none():
    from openr_tpu.decision.rib_policy import (
        RibPolicyStatement,
        RibRouteActionWeight,
    )

    net, ps, backend, spy, first = _route_world()
    prefix = _prefix_of(ps, "rsw2_1")
    entry = first.unicast_routes[prefix]
    stmt = RibPolicyStatement(
        prefixes=[prefix], action=RibRouteActionWeight(default_weight=3))
    assert stmt.apply_action(entry)
    reused, rebuilt = _build_delta(backend, net, ps, spy)
    assert rebuilt == {prefix} and reused > 0
    assert backend.gen.last_db.unicast_routes[prefix] is not entry
    # a caller that mutates what it gets shares no entry with the last db
    last = backend.gen.last_db.unicast_routes
    before = dict(backend.ksp2_counts)
    db = backend.build_route_db({"0": net.ls}, ps, cache_result=False)
    assert backend.ksp2_counts["reused_routes"] == before["reused_routes"]
    assert all(db.unicast_routes[p] is not e for p, e in last.items())


def test_suspect_purge_rebuilds_every_route():
    net, ps, backend, spy, _ = _route_world()
    reused, rebuilt = _build_delta(backend, net, ps, spy)
    assert not rebuilt and reused == len(spy["assembled"]) > 0
    backend.inject_silent_corruption(True)
    assert backend.gen.ksp2_routes.routes == {}
    backend.inject_silent_corruption(False)
    reused, rebuilt = _build_delta(backend, net, ps, spy)
    assert reused == 0 and rebuilt == set(spy["assembled"])
