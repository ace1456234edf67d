"""The KSP2_ED_ECMP + SR-MPLS reference (cellbench.reference_ksp2) and the
program agree, route for route, unicast and MPLS, on small F4 fabrics and
random graphs with seeded random metrics, through both of the program's
backends: TpuBackend (masked re-solves on the device, host trace) and
ScalarBackend (host Dijkstra per k-path)."""

import random

import pytest

from cellbench.reference_ksp2 import Reference, canonical
from cellbench.topologies import f4fabric
from cellbench.topology import Lsdb, adjacency_db, node_prefix

LABEL_BASE = 101


def _f4(pods, racks, planes, spines, rng):
    cfg = {"pods": pods, "rsws_per_pod": racks, "planes": planes,
           "ssws_per_plane": spines}
    return ([(a, b, rng.randint(1, 4)) for a, b, _m in f4fabric.edges(cfg)],
            "fsw0_0", lambda n: n.startswith("rsw"))


def _random(n, extra, rng):
    """A connected graph: a random spanning tree plus ``extra`` chords,
    no two links between one pair, metrics 1..3 (many ties)."""
    names = [f"n{i}" for i in range(n)]
    pairs = set()
    for i in range(1, n):
        pairs.add(tuple(sorted((names[i], names[rng.randrange(i)]))))
    while len(pairs) < n - 1 + extra:
        a, b = rng.sample(names, 2)
        pairs.add(tuple(sorted((a, b))))
    ksp2 = set(rng.sample(names, n // 2))
    return ([(a, b, rng.randint(1, 3)) for a, b in sorted(pairs)], "n0",
            lambda node: node in ksp2)


GRAPHS = {
    "f4_2x4x2x2": lambda rng: _f4(2, 4, 2, 2, rng),
    "f4_3x5x3x3": lambda rng: _f4(3, 5, 3, 3, rng),
    "random_18": lambda rng: _random(18, 24, rng),
    "random_30": lambda rng: _random(30, 45, rng),
}


def _program_db(backend_kind, lsdb, root, is_ksp2, monkeypatch):
    from openr_tpu.decision.backend import ScalarBackend, TpuBackend
    from openr_tpu.decision.link_state import LinkState
    from openr_tpu.decision.prefix_state import PrefixState
    from openr_tpu.decision.spf_solver import SpfSolver
    from openr_tpu.types import (
        PrefixEntry,
        PrefixForwardingAlgorithm,
        PrefixForwardingType,
    )

    ls = LinkState("0", root)
    for i, n in enumerate(lsdb.names):
        db = adjacency_db(lsdb, n, "0")
        db.node_label = LABEL_BASE + i
        ls.update_adjacency_database(db)
    ps = PrefixState()
    for i, n in enumerate(lsdb.names):
        entry = PrefixEntry(node_prefix(i, 0))
        if is_ksp2(n):
            entry = PrefixEntry(
                node_prefix(i, 0),
                forwarding_type=PrefixForwardingType.SR_MPLS,
                forwarding_algorithm=PrefixForwardingAlgorithm.KSP2_ED_ECMP)
        ps.update_prefix(n, "0", entry)
    solver = SpfSolver(root, enable_node_segment_label=True)
    if backend_kind == "scalar":
        return ScalarBackend(solver).build_route_db({"0": ls}, ps)
    monkeypatch.setattr(TpuBackend, "_device_worth_it",
                        lambda self, a, p: True)
    backend = TpuBackend(solver)
    db = backend.build_route_db({"0": ls}, ps)
    assert backend.num_device_builds == 1 and backend.num_scalar_builds == 0
    assert backend.ksp2_counts["device_batches"] >= 1
    return db


@pytest.mark.parametrize("backend_kind", ["tpu", "scalar"])
@pytest.mark.parametrize("seed", range(3))
@pytest.mark.parametrize("graph", sorted(GRAPHS))
def test_reference_equals_the_program(graph, seed, backend_kind,
                                      monkeypatch):
    rng = random.Random(f"ksp2-reference:{graph}:{seed}")
    edges, root, is_ksp2 = GRAPHS[graph](rng)
    lsdb = Lsdb(edges)
    # a down link too: the re-solves must route around it
    lsdb.up[rng.randrange(len(lsdb.links))] = False
    labels = {n: LABEL_BASE + i for i, n in enumerate(lsdb.names)}
    ksp2 = {n for n in lsdb.names if is_ksp2(n)}
    ref = Reference(lsdb, root, "0", labels, ksp2)
    db = _program_db(backend_kind, lsdb, root, is_ksp2, monkeypatch)

    second = [n for n in ksp2 if ref.kth_paths(n)[1]]
    assert second, "no destination has a second path family"
    want_unicast = {node_prefix(i, 0): ref.unicast(n)
                    for i, n in enumerate(lsdb.names)}
    got_unicast = {p: canonical(e.nexthops)
                   for p, e in db.unicast_routes.items()}
    assert got_unicast == {p: h for p, h in want_unicast.items()
                           if h is not None}
    want_mpls = {labels[n]: ref.mpls(n) for n in lsdb.names}
    got_mpls = {lbl: canonical(e.nexthops)
                for lbl, e in db.mpls_routes.items()}
    assert got_mpls == {lbl: h for lbl, h in want_mpls.items()
                        if h is not None}


def test_f4_vantage_has_a_second_family():
    """From a fabric switch every rack has a second family pinned by
    label stacks; from a rack switch the first family takes every
    uplink and KSP2 is plain ECMP."""
    cfg = {"pods": 2, "rsws_per_pod": 4, "planes": 2, "ssws_per_plane": 2}
    lsdb = Lsdb(f4fabric.edges(cfg))
    labels = {n: LABEL_BASE + i for i, n in enumerate(lsdb.names)}
    racks = {n for n in lsdb.names if n.startswith("rsw")}
    fsw = Reference(lsdb, "fsw0_0", "0", labels, racks)
    for r in racks:
        k1, k2 = fsw.kth_paths(r)
        assert k1 and k2, r
        assert any(h[6] and h[6][0] == "PUSH" for h in fsw.unicast(r))
    rsw = Reference(lsdb, "rsw0_0", "0", labels, racks)
    assert all(not rsw.kth_paths(r)[1] for r in racks - {"rsw0_0"})


def test_reference_refuses_parallel_links():
    lsdb = Lsdb([("a", "b", 1), ("a", "b", 2)])
    with pytest.raises(ValueError, match="parallel"):
        Reference(lsdb, "a", "0", {"a": 101, "b": 102}, set())
