"""Operator what-if API — per-failure route deltas vs scalar recompute.

For each candidate link failure, the API's reported changes must match
the difference between the scalar oracle's RouteDb on the intact
topology and on a topology with the link actually removed."""

import pytest

from openr_tpu.common.runtime import SimClock
from openr_tpu.config import DecisionConfig
from openr_tpu.decision.backend import ScalarBackend, TpuBackend
from openr_tpu.decision.decision import Decision
from openr_tpu.decision.link_state import LinkState
from openr_tpu.decision.prefix_state import PrefixState
from openr_tpu.decision.spf_solver import SpfSolver
from openr_tpu.emulation.topology import build_adj_dbs, grid_edges
from openr_tpu.messaging.queue import ReplicateQueue
from openr_tpu.types import PrefixEntry


def build_decision(backend_cls=TpuBackend):
    edges = grid_edges(4)
    dbs = build_adj_dbs(edges)
    ls = LinkState("0")
    for db in dbs.values():
        ls.update_adjacency_database(db)
    ps = PrefixState()
    for i in range(16):
        ps.update_prefix(f"node{i}", "0", PrefixEntry(f"10.{i}.0.0/24"))
    solver = SpfSolver("node0")
    d = Decision(
        "node0",
        SimClock(),
        DecisionConfig(),
        ReplicateQueue("routes"),
        backend=backend_cls(solver),
        solver=solver,
    )
    d.area_link_states = {"0": ls}
    d.prefix_state = ps
    return d, dbs


def scalar_routes_without_link(d, dbs, n1, n2):
    """Oracle: rebuild the LSDB with the link removed, solve scalar."""
    ls = LinkState("0")
    for node, db in dbs.items():
        import dataclasses

        filtered = dataclasses.replace(
            db,
            adjacencies=[
                a
                for a in db.adjacencies
                if {db.this_node_name, a.other_node_name} != {n1, n2}
            ],
        )
        ls.update_adjacency_database(filtered)
    return SpfSolver("node0").build_route_db({"0": ls}, d.prefix_state)


def routes_view(db):
    return {
        p: (round(e.igp_cost, 1), sorted(n.neighbor_node_name for n in e.nexthops))
        for p, e in db.unicast_routes.items()
    }


def test_whatif_matches_scalar_link_removal():
    d, dbs = build_decision()
    base = SpfSolver("node0").build_route_db(d.area_link_states, d.prefix_state)
    base_view = routes_view(base)

    cases = [("node0", "node1"), ("node1", "node2"), ("node14", "node15")]
    resp = d.get_link_failure_whatif([list(c) for c in cases])
    assert resp is not None and resp["eligible"]
    assert resp["vantage"] == "node0"

    for f, (n1, n2) in zip(resp["failures"], cases):
        oracle = scalar_routes_without_link(d, dbs, n1, n2)
        oracle_view = routes_view(oracle)
        expected = {}
        for p in set(base_view) | set(oracle_view):
            was, now = p in base_view, p in oracle_view
            if was and not now:
                expected[p] = ("removed", base_view[p][1], [])
            elif now and not was:
                expected[p] = ("added", [], oracle_view[p][1])
            elif base_view[p] != oracle_view[p]:
                expected[p] = ("rerouted", base_view[p][1], oracle_view[p][1])
        got = {
            ch["prefix"]: (
                ch["change"],
                sorted(ch["old_nexthops"]),
                sorted(ch["new_nexthops"]),
            )
            for ch in f["changes"]
        }
        assert got == expected, (f["link"], got, expected)


def test_whatif_off_dag_link_reports_no_changes():
    """In a unit-metric grid EVERY link is on some shortest path from the
    corner, so force one off-DAG by giving it a heavy metric: the engine
    must classify it off the DAG and report zero route changes (base
    aliasing), matching the scalar recompute."""

    edges = [
        (a, b, 10 if {a, b} == {"node14", "node15"} else m)
        for (a, b, m) in grid_edges(4)
    ]
    dbs = build_adj_dbs(edges)
    ls = LinkState("0")
    for db in dbs.values():
        ls.update_adjacency_database(db)
    ps = PrefixState()
    for i in range(16):
        ps.update_prefix(f"node{i}", "0", PrefixEntry(f"10.{i}.0.0/24"))
    solver = SpfSolver("node0")
    d = Decision(
        "node0",
        SimClock(),
        DecisionConfig(),
        ReplicateQueue("routes"),
        backend=TpuBackend(solver),
        solver=solver,
    )
    d.area_link_states = {"0": ls}
    d.prefix_state = ps
    resp = d.get_link_failure_whatif([["node14", "node15"]])
    f = resp["failures"][0]
    assert f["on_shortest_path_dag"] is False  # heavy link beats no path
    assert f["routes_changed"] == 0


def test_whatif_unknown_link_and_scalar_backend():
    d, _dbs = build_decision()
    resp = d.get_link_failure_whatif([["node0", "node15"]])  # not adjacent
    assert resp["failures"][0]["error"] == "unknown link"

    # scalar-only deployments now serve single-area what-if via the
    # NATIVE engine (no jax loads) — same answers as the device path
    d2, _ = build_decision(backend_cls=ScalarBackend)
    scalar_resp = d2.get_link_failure_whatif([["node0", "node1"]])
    assert scalar_resp is not None and scalar_resp["eligible"]
    assert d2._whatif_native_engine is not None
    assert d2._whatif_engine is None  # device engine never constructed
    tpu_resp = d.get_link_failure_whatif([["node0", "node1"]])
    assert scalar_resp == tpu_resp


def test_whatif_engine_cached_across_calls():
    d, _dbs = build_decision()
    d.get_link_failure_whatif([["node0", "node1"]])
    # the auto choice may pick either warm-start engine; both cache per
    # LSDB generation
    eng = d._whatif_engine or d._whatif_native_engine
    assert eng.num_engine_builds == 1
    d.get_link_failure_whatif([["node1", "node2"]])
    assert eng.num_engine_builds == 1  # cached until LSDB changes
    d.prefix_state.update_prefix("node3", "0", PrefixEntry("10.99.0.0/24"))
    d._change_seq += 1
    d.get_link_failure_whatif([["node1", "node2"]])
    assert eng.num_engine_builds == 2


def test_native_engine_matches_device_engine():
    """NativeWhatIfEngine (C++ warm sweep + numpy selection) must give
    BYTE-identical operator output to the device engine on the same
    world — the two are auto-chosen per deployment, so any drift is an
    operator-visible inconsistency."""
    import numpy as np

    from openr_tpu.decision.whatif_api import (
        NativeWhatIfEngine,
        WhatIfApiEngine,
    )
    from openr_tpu.decision.link_state import LinkState
    from openr_tpu.decision.prefix_state import PrefixState
    from openr_tpu.decision.spf_solver import SpfSolver
    from openr_tpu.emulation.topology import (
        build_adj_dbs,
        random_connected_edges,
    )
    from openr_tpu.types import PrefixEntry, PrefixMetrics

    ls = LinkState("0")
    for db in build_adj_dbs(
        random_connected_edges(48, 70, seed=5),
        soft_drained={"node7": 50},
        overloaded=["node11"],
    ).values():
        ls.update_adjacency_database(db)
    ps = PrefixState()
    for i in range(48):
        ps.update_prefix(f"node{i}", "0", PrefixEntry(f"10.{i}.0.0/24"))
    # anycast with preference spread
    ps.update_prefix("node3", "0", PrefixEntry(
        "10.99.0.0/24", metrics=PrefixMetrics(path_preference=900)))
    ps.update_prefix("node40", "0", PrefixEntry(
        "10.99.0.0/24", metrics=PrefixMetrics(path_preference=900)))
    als = {"0": ls}
    failures = [("node0", "node1"), ("node5", "node9"), ("nope", "x")]
    # every real link too, for breadth
    from openr_tpu.ops.csr import encode_link_state

    topo = encode_link_state(ls)
    failures += [(l.n1, l.n2) for l in topo.links[:40]]

    dev = WhatIfApiEngine(SpfSolver("node0")).run(failures, als, ps, 1)
    nat = NativeWhatIfEngine(SpfSolver("node0")).run(failures, als, ps, 1)
    # engines self-identify; everything else must be byte-identical
    assert nat.pop("engine") == "native" and dev.pop("engine") == "device"
    assert nat == dev


def test_decision_auto_picks_native_for_small_queries():
    from openr_tpu.common.runtime import SimClock
    from openr_tpu.config import DecisionConfig
    from openr_tpu.decision.backend import TpuBackend
    from openr_tpu.decision.decision import Decision
    from openr_tpu.decision.link_state import LinkState
    from openr_tpu.decision.prefix_state import PrefixState
    from openr_tpu.decision.spf_solver import SpfSolver
    from openr_tpu.emulation.topology import build_adj_dbs, grid_edges
    from openr_tpu.messaging.queue import ReplicateQueue
    from openr_tpu.types import PrefixEntry

    ls = LinkState("0")
    for db in build_adj_dbs(grid_edges(4)).values():
        ls.update_adjacency_database(db)
    ps = PrefixState()
    for i in range(16):
        ps.update_prefix(f"node{i}", "0", PrefixEntry(f"10.{i}.0.0/24"))
    backend = TpuBackend(SpfSolver("node0"))
    d = Decision(
        "node0", SimClock(), DecisionConfig(), ReplicateQueue(),
        backend=backend,
    )
    d.area_link_states = {"0": ls}
    d.prefix_state = ps
    d._change_seq = 1
    # slow dispatch: native engine must serve the query
    backend.auto_dispatch_rt_ms = 75.0
    res = d.get_link_failure_whatif([("node0", "node1")])
    assert res is not None and res["eligible"]
    assert d._whatif_native_engine is not None
    assert d._whatif_engine is None
    # collocated device: large batches go to the device engine
    backend.auto_dispatch_rt_ms = 0.01
    res2 = d.get_link_failure_whatif([("node0", "node1")] * 24)
    assert res2 is not None
    assert d._whatif_engine is not None
    # and both engines agreed on the single-failure answer
    assert res["failures"][0] == res2["failures"][0]


@pytest.mark.parametrize("seed", [101, 202, 303])
def test_native_vs_device_engines_random_worlds(seed):
    """Property check: on random weighted topologies with random drains
    and anycast, the auto-selectable engines agree byte for byte."""
    import numpy as np

    from openr_tpu.decision.link_state import LinkState
    from openr_tpu.decision.prefix_state import PrefixState
    from openr_tpu.decision.spf_solver import SpfSolver
    from openr_tpu.decision.whatif_api import (
        NativeWhatIfEngine,
        WhatIfApiEngine,
    )
    from openr_tpu.emulation.topology import (
        build_adj_dbs,
        random_connected_edges,
    )
    from openr_tpu.ops.csr import encode_link_state
    from openr_tpu.types import PrefixEntry, PrefixMetrics

    rng = np.random.default_rng(seed)
    n = int(rng.integers(24, 56))
    edges = random_connected_edges(n, n + int(rng.integers(8, 40)), seed=seed)
    drained = {f"node{int(rng.integers(1, n))}": 40}
    over = [f"node{int(rng.integers(1, n))}"]
    ls = LinkState("0")
    for db in build_adj_dbs(
        edges, soft_drained=drained, overloaded=over
    ).values():
        ls.update_adjacency_database(db)
    ps = PrefixState()
    for i in range(n):
        ps.update_prefix(f"node{i}", "0", PrefixEntry(f"10.{i}.0.0/24"))
    a1, a2 = rng.integers(1, n, size=2)
    ps.update_prefix(f"node{a1}", "0", PrefixEntry(
        "10.200.0.0/24", metrics=PrefixMetrics(source_preference=150)))
    ps.update_prefix(f"node{a2}", "0", PrefixEntry(
        "10.200.0.0/24", metrics=PrefixMetrics(source_preference=150)))
    als = {"0": ls}
    topo = encode_link_state(ls)
    failures = [(l.n1, l.n2) for l in topo.links]
    dev = WhatIfApiEngine(SpfSolver("node0")).run(failures, als, ps, 1)
    nat = NativeWhatIfEngine(SpfSolver("node0")).run(failures, als, ps, 1)
    # engines self-identify; everything else must be byte-identical
    assert nat.pop("engine") == "native" and dev.pop("engine") == "device"
    assert nat == dev


def test_scalar_whatif_never_touches_device_stack():
    """A scalar-only deployment serving an operator what-if must stay
    off the device stack entirely: no openr_tpu device module imported,
    no PJRT backend initialized (backend init claims the chip and costs
    the scalar path a device start-up — this regressed once via a
    module-level jnp constant in ops.spf pulled in through
    ops.route_select)."""
    import subprocess
    import sys

    script = r"""
import sys
from openr_tpu.decision.link_state import LinkState
from openr_tpu.decision.prefix_state import PrefixState
from openr_tpu.decision.spf_solver import SpfSolver
from openr_tpu.decision.decision import Decision
from openr_tpu.decision.backend import ScalarBackend
from openr_tpu.common.runtime import SimClock
from openr_tpu.config import DecisionConfig
from openr_tpu.messaging.queue import ReplicateQueue
from openr_tpu.emulation.topology import grid_edges, build_adj_dbs
from openr_tpu.types import PrefixEntry

ls = LinkState("0")
for db in build_adj_dbs(grid_edges(4)).values():
    ls.update_adjacency_database(db)
ps = PrefixState()
for i in range(16):
    ps.update_prefix(f"node{i}", "0", PrefixEntry(f"10.{i}.0.0/24"))
solver = SpfSolver("node0")
d = Decision("node0", SimClock(), DecisionConfig(), ReplicateQueue("r"),
             backend=ScalarBackend(solver), solver=solver)
d.area_link_states = {"0": ls}
d.prefix_state = ps
resp = d.get_link_failure_whatif([["node0", "node1"]])
assert resp and resp["eligible"], resp
assert resp["failures"][0]["routes_changed"] > 0, resp
for mod in ("openr_tpu.ops.spf", "openr_tpu.ops.route_select",
            "openr_tpu.ops.repair", "openr_tpu.ops.sweep_select"):
    assert mod not in sys.modules, f"device module leaked: {mod}"
if "jax" in sys.modules:  # imported is fine; initialized is not
    from jax._src import xla_bridge
    assert not xla_bridge._backends, (
        "PJRT backend initialized: %s" % list(xla_bridge._backends))
print("CLEAN")
"""
    out = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert out.returncode == 0, out.stderr
    assert "CLEAN" in out.stdout


# ---- simultaneous (set) failures -------------------------------------------


def scalar_routes_without_links(d, dbs, pairs):
    """Oracle: rebuild the LSDB with ALL listed links removed."""
    import dataclasses

    ls = LinkState("0")
    sets = [frozenset(p) for p in pairs]
    for node, db in dbs.items():
        filtered = dataclasses.replace(
            db,
            adjacencies=[
                a
                for a in db.adjacencies
                if frozenset((db.this_node_name, a.other_node_name))
                not in sets
            ],
        )
        ls.update_adjacency_database(filtered)
    return SpfSolver("node0").build_route_db({"0": ls}, d.prefix_state)


def apply_whatif_changes(base_view, failure):
    got = dict(base_view)
    for ch in failure["changes"]:
        if ch["change"] == "withdrawn":
            got.pop(ch["prefix"], None)
        else:
            got[ch["prefix"]] = (
                round(ch["new_metric"], 1),
                sorted(ch["new_nexthops"]),
            )
    return got


@pytest.mark.parametrize("engine", ["device", "native"])
def test_whatif_simultaneous_matches_scalar_multi_removal(engine):
    """--simultaneous: the combined answer must equal the scalar oracle
    with EVERY listed link removed at once, through both the device
    (run_sets) and native (spf_scalar_solve_set) engines."""
    d, dbs = build_decision()
    # force the engine choice via the dispatch-RT calibration override
    # (expensive RT -> native, free RT -> device)
    d._whatif_rt_ms = 1000.0 if engine == "native" else 1e-6

    base = SpfSolver("node0").build_route_db(
        d.area_link_states, d.prefix_state
    )
    base_view = routes_view(base)

    pairs = [("node0", "node1"), ("node5", "node6"), ("node10", "node14")]
    resp = d.get_link_failure_whatif(
        [list(p) for p in pairs], simultaneous=True
    )
    assert resp is not None and resp["eligible"]
    assert resp.get("simultaneous") is True
    (f,) = resp["failures"]
    assert f["links"] == [list(p) for p in pairs]

    oracle = routes_view(scalar_routes_without_links(d, dbs, pairs))
    got = apply_whatif_changes(base_view, f)
    assert got == oracle, engine


def test_whatif_simultaneous_unknown_link_errors():
    d, _dbs = build_decision()
    resp = d.get_link_failure_whatif(
        [["node0", "node1"], ["node0", "nope"]], simultaneous=True
    )
    assert resp["eligible"]
    assert resp["failures"][0]["error"] == "unknown link"


def test_whatif_simultaneous_multiarea_uses_device_kernel():
    """Set-failure analysis on a multi-area vantage runs on the
    multi-area DEVICE kernel since r5 (per-snapshot failure SETS are
    masked on device); parity vs the scalar oracle is asserted."""
    d, dbs = build_decision()
    d.area_link_states["1"] = LinkState("1")
    resp = d.get_link_failure_whatif(
        [["node0", "node1"], ["node5", "node6"]], simultaneous=True
    )
    assert resp is not None and resp["eligible"]
    assert resp["engine"] == "multiarea"
    (f,) = resp["failures"]
    # parity vs the scalar oracle with both links removed
    base_view = routes_view(
        SpfSolver("node0").build_route_db(d.area_link_states, d.prefix_state)
    )
    oracle = routes_view(
        scalar_routes_without_links(
            d, dbs, [("node0", "node1"), ("node5", "node6")]
        )
    )
    assert apply_whatif_changes(base_view, f) == oracle


def test_scalar_only_high_fanout_uses_generic_engine():
    """A scalar-only vantage with more out-links than the native
    engine's 64-lane limit must answer through the jax-free generic
    engine, not return ineligible (code-review r4): previously this
    configuration had NO eligible engine."""
    star = [("node0", f"leaf{i}", 1) for i in range(70)]
    dbs = build_adj_dbs(star)
    ls = LinkState("0")
    for db in dbs.values():
        ls.update_adjacency_database(db)
    ps = PrefixState()
    for i in range(70):
        ps.update_prefix(f"leaf{i}", "0", PrefixEntry(f"10.0.{i}.0/24"))
    solver = SpfSolver("node0")
    d = Decision(
        "node0",
        SimClock(),
        DecisionConfig(),
        ReplicateQueue("routes"),
        backend=ScalarBackend(solver),
        solver=solver,
    )
    d.area_link_states = {"0": ls}
    d.prefix_state = ps
    resp = d.get_link_failure_whatif([["node0", "leaf3"]])
    assert resp is not None and resp["eligible"]
    assert resp["engine"] == "generic-solver"
    assert d._whatif_engine is None  # device engine never constructed
    (f,) = resp["failures"]
    assert f["routes_changed"] == 1
    assert f["changes"][0]["prefix"] == "10.0.3.0/24"
    assert f["changes"][0]["change"] == "removed"


def _parallel_world():
    """a ==2 parallel links== b -- c; prefixes on b and c."""
    from openr_tpu.types import Adjacency, AdjacencyDatabase

    def db(me, adjs):
        return AdjacencyDatabase(
            this_node_name=me,
            adjacencies=[
                Adjacency(
                    other_node_name=o,
                    if_name=i,
                    metric=m,
                    other_if_name=ri,
                )
                for (o, i, m, ri) in adjs
            ],
        )

    ls = LinkState("0")
    ls.update_adjacency_database(
        db("a", [("b", "if_ab1", 1, "if_ba1"), ("b", "if_ab2", 2, "if_ba2")])
    )
    ls.update_adjacency_database(
        db(
            "b",
            [
                ("a", "if_ba1", 1, "if_ab1"),
                ("a", "if_ba2", 2, "if_ab2"),
                ("c", "if_bc", 1, "if_cb"),
            ],
        )
    )
    ls.update_adjacency_database(db("c", [("b", "if_cb", 1, "if_bc")]))
    ps = PrefixState()
    ps.update_prefix("b", "0", PrefixEntry("10.0.1.0/24"))
    ps.update_prefix("c", "0", PrefixEntry("10.0.2.0/24"))
    return ls, ps


@pytest.mark.parametrize("engine", ["device", "native"])
def test_whatif_parallel_bundle_fails_as_set(engine):
    """A (n1, n2) pair with PARALLEL links no longer errors: the engines
    fail the whole bundle as one simultaneous set (failing just one
    would shift traffic to the survivors and mislead)."""
    ls, ps = _parallel_world()
    assert len(ls.all_links()) == 3  # 2 parallel a-b + 1 b-c
    solver = SpfSolver("a")
    d = Decision(
        "a",
        SimClock(),
        DecisionConfig(),
        ReplicateQueue("routes"),
        backend=(TpuBackend if engine == "device" else ScalarBackend)(
            solver
        ),
        solver=solver,
    )
    d.area_link_states = {"0": ls}
    d.prefix_state = ps
    d._whatif_rt_ms = 1000.0 if engine == "native" else 1e-6
    resp = d.get_link_failure_whatif([["a", "b"]])
    assert resp is not None and resp["eligible"]
    (f,) = resp["failures"]
    assert "error" not in f
    assert f["links_failed"] == 2
    # both a-b links down => b and c unreachable: both prefixes removed
    assert f["routes_changed"] == 2
    assert {c["prefix"] for c in f["changes"]} == {
        "10.0.1.0/24",
        "10.0.2.0/24",
    }
    assert all(c["change"] == "removed" for c in f["changes"])


def test_whatif_parallel_bundle_generic_engine_matches():
    """The generic solver engine answers the same bundle identically."""
    from openr_tpu.decision.whatif_api import GenericSolverWhatIfEngine

    ls, ps = _parallel_world()
    eng = GenericSolverWhatIfEngine(SpfSolver("a"))
    resp = eng.run([("a", "b")], {"0": ls}, ps, change_seq=1)
    (f,) = resp["failures"]
    assert f["links_failed"] == 2
    assert {c["prefix"] for c in f["changes"]} == {
        "10.0.1.0/24",
        "10.0.2.0/24",
    }
    assert all(c["change"] == "removed" for c in f["changes"])


def test_link_criticality_matches_per_link_whatif():
    """The criticality report's per-link counts must equal what the
    per-link what-if reports, link by link."""
    d, _dbs = build_decision()
    crit = d.get_link_criticality()
    assert crit is not None
    assert len(crit["links"]) == 24  # 4x4 grid undirected links
    # cross-check three links against the what-if answers
    for e in crit["links"][:3]:
        n1, n2 = e["link"]
        resp = d.get_link_failure_whatif([[n1, n2]])
        (f,) = resp["failures"]
        assert f["routes_changed"] == e["routes_changed"], e
        removed = sum(
            1 for c in f["changes"] if c["change"] == "removed"
        )
        assert removed == e["routes_withdrawn"], e
    # ranking is by withdrawn desc
    w = [e["routes_withdrawn"] for e in crit["links"]]
    assert w == sorted(w, reverse=True)


def test_link_criticality_pair_scan_finds_partitions():
    """Double-failure scan: pairs that withdraw routes beyond their
    single failures must match a brute-force oracle on a small world."""
    from openr_tpu.ops.native_spf import NativeSpf
    from openr_tpu.ops.csr import encode_link_state

    d, _dbs = build_decision()
    crit = d.get_link_criticality(max_pairs=10_000)
    p = crit["pairs"]
    assert p is not None and not p["truncated"]
    # oracle: for every scanned on-DAG pair, removed = prefixes whose
    # advertiser becomes unreachable from node0 (single-advertiser
    # world, all preferences equal)
    ls = d.area_link_states["0"]
    topo = encode_link_state(ls)
    nat = NativeSpf(topo, "node0")
    base_removed = {}
    import itertools

    import numpy as np

    from openr_tpu.ops.whatif import LinkFailureSweep

    eng = LinkFailureSweep(topo, "node0")
    on_dag = eng.on_dag_links()
    # same universe the engine scans: pairs with >= 1 on-DAG member
    # (a pure off-DAG pair provably changes nothing)
    pair_universe = [
        (a, b)
        for a, b in itertools.combinations(range(len(topo.links)), 2)
        if on_dag[a] or on_dag[b]
    ]
    want_risky = 0
    for a, b in pair_universe:
        def removed_for(lids):
            nd, _ = nat.solve_set(list(lids))
            lanes = nat.lanes_dense(eng.D)
            return sum(
                1
                for v in range(16)
                if v != topo.node_id("node0")
                and not (np.isfinite(nd[v]) and lanes[v].any())
            )

        extra = removed_for([a, b]) - removed_for([a]) - removed_for([b])
        if extra > 0:
            want_risky += 1
    assert p["risky_count"] == want_risky


def test_link_criticality_catches_primary_plus_backup_pairs():
    """The canonical partition-risk case pairs an ON-DAG primary with
    an OFF-DAG backup: each single failure merely reroutes (or changes
    nothing), but together they partition.  The pair scan must include
    on x off pairs (code-review r4: an on-DAG-only scan missed
    exactly these)."""
    edges = [
        ("node0", "a", 1), ("a", "v", 1),      # cheap primary
        ("node0", "b", 10), ("b", "v", 10),    # expensive backup
    ]
    dbs = build_adj_dbs(edges)
    ls = LinkState("0")
    for db in dbs.values():
        ls.update_adjacency_database(db)
    ps = PrefixState()
    for n in ("a", "b", "v"):
        ps.update_prefix(n, "0", PrefixEntry(f"10.0.{ord(n[0])}.0/24"))
    solver = SpfSolver("node0")
    d = Decision(
        "node0",
        SimClock(),
        DecisionConfig(),
        ReplicateQueue("routes"),
        backend=TpuBackend(solver),
        solver=solver,
    )
    d.area_link_states = {"0": ls}
    d.prefix_state = ps
    crit = d.get_link_criticality(max_pairs=100)
    # single failures withdraw NOTHING (the ring reroutes everything)
    by_link = {tuple(e["link"]): e for e in crit["links"]}
    assert by_link[("a", "node0")]["routes_withdrawn"] == 0
    assert by_link[("b", "node0")]["routes_withdrawn"] == 0
    # the (node0-a, node0-b) pair isolates node0 -> partition risk found
    risky_pairs = {
        frozenset(tuple(l) for l in e["links"])
        for e in crit["pairs"]["risky"]
    }
    assert frozenset(
        {("a", "node0"), ("b", "node0")}
    ) in risky_pairs, crit["pairs"]
