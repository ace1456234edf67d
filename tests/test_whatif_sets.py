"""Simultaneous multi-link failure what-if: exactness of the set form.

The repair kernel's warm start generalizes link-by-link: a snapshot's
affected region is the UNION of its failed links' affected bitsets (a
vertex outside the union has a base shortest path avoiding every failed
link — any crossing path would make it a DAG-descendant of a failed
edge's head).  These tests pin that argument against two independent
oracles: the native C++ set solver (spf_scalar_solve_set) and the pure
-Python Dijkstra with links_to_ignore.
"""

import numpy as np
import pytest

from openr_tpu.decision.link_state import LinkState
from openr_tpu.emulation.topology import (
    build_adj_dbs,
    grid_edges,
    random_connected_edges,
)
from openr_tpu.ops.csr import encode_link_state
from openr_tpu.ops.native_spf import NativeSpf
from openr_tpu.ops.sweep_select import SweepCandidates, SweepRouteSelector
from openr_tpu.ops.whatif import LinkFailureSweep


def build_world(seed=4, n_nodes=48, n_links=96):
    edges = random_connected_edges(n_nodes, n_links, seed=seed)
    ls = LinkState("0")
    for db in build_adj_dbs(edges).values():
        ls.update_adjacency_database(db)
    return ls, encode_link_state(ls)


def random_sets(topo, rng, B, kmax):
    return [
        tuple(
            int(x)
            for x in rng.choice(
                len(topo.links), size=int(rng.integers(1, kmax + 1)),
                replace=False,
            )
        )
        for _ in range(B)
    ]


def test_native_solve_set_matches_python_oracle():
    ls, topo = build_world(seed=9, n_nodes=40, n_links=80)
    nat = NativeSpf(topo, "node0")
    rng = np.random.default_rng(1)
    for lids in random_sets(topo, rng, 12, 3):
        dist, _ = nat.solve_set(lids)
        links = frozenset(topo.links[l] for l in lids)
        res = ls.run_spf("node0", links_to_ignore=links)
        for name, nid in topo.node_ids.items():
            want = res[name].metric if name in res else np.inf
            got = dist[nid]
            assert (np.isinf(want) and np.isinf(got)) or want == got, (
                lids,
                name,
            )


def test_run_sets_tables_match_native_set_solver():
    """Engine path: dedup + pure-off-DAG base aliasing + depth sort +
    chunking, table parity (distances AND first-hop lane sets) vs the
    native set solver."""
    _ls, topo = build_world()
    eng = LinkFailureSweep(topo, "node0")
    nat = NativeSpf(topo, "node0")
    rng = np.random.default_rng(2)
    sets = random_sets(topo, rng, 48, 3)
    sets += [sets[0], ()]  # duplicate + empty (base alias)
    res = eng.run_sets(sets)
    V = topo.num_nodes
    for b, lids in enumerate(sets):
        nd, _mask = nat.solve_set(list(lids))
        lanes = nat.lanes_dense(eng.D)
        dist_b = res.dist_of(b)
        nh_b = res.nh_of(b)
        finite = np.isfinite(nd[:V])
        assert np.array_equal(nd[:V][finite], dist_b[:V][finite]), b
        assert np.all(~finite == (dist_b[:V] >= 3.0e38)), b
        assert np.array_equal(lanes[:V][finite], nh_b[:V][finite]), b
    # the empty set aliases the base row, the duplicate solves once
    assert res.snap_row[-1] == 0
    assert res.snap_row[-2] == res.snap_row[0]
    assert res.num_device_solves <= len(set(s for s in sets if s))


def test_run_sets_pure_off_dag_aliases_base():
    """A set with NO on-DAG member provably aliases the base (no base
    shortest path crossed any of its links; removals can't shorten)."""
    _ls, topo = build_world(seed=11)
    eng = LinkFailureSweep(topo, "node0")
    off = np.nonzero(~eng.on_dag_links())[0]
    if len(off) == 0:
        pytest.skip("every link on the DAG for this seed")
    res = eng.run_sets([tuple(int(l) for l in off[:3])])
    assert res.snap_row[0] == 0
    assert np.array_equal(res.dist_of(0), res.base[0])


def test_run_sets_mixed_off_dag_member_still_removed():
    """A link OFF the base DAG can carry the reroute once an on-DAG
    member fails — mixed sets must remove it too (code-review r4
    counterexample: triangle a-b w1 on-DAG, a-c w1, c-b w5 off-DAG;
    failing {a-b, c-b} must leave b UNREACHABLE, not rerouted at 6 via
    the failed c-b link)."""
    from openr_tpu.types import AdjacencyDatabase, Adjacency

    def adj(me, other, metric):
        return Adjacency(
            other_node_name=other,
            if_name=f"if_{me}_{other}",
            metric=metric,
            other_if_name=f"if_{other}_{me}",
        )

    ls = LinkState("0")
    for me, nbrs in {
        "a": [("b", 1), ("c", 1)],
        "b": [("a", 1), ("c", 5)],
        "c": [("a", 1), ("b", 5)],
    }.items():
        ls.update_adjacency_database(
            AdjacencyDatabase(
                this_node_name=me,
                adjacencies=[adj(me, o, m) for o, m in nbrs],
            )
        )
    topo = encode_link_state(ls)
    eng = LinkFailureSweep(topo, "a")
    on_dag = eng.on_dag_links()
    lid_ab = next(
        i for i, l in enumerate(topo.links) if {"a", "b"} == {l.n1, l.n2}
    )
    lid_cb = next(
        i for i, l in enumerate(topo.links) if {"c", "b"} == {l.n1, l.n2}
    )
    assert on_dag[lid_ab] and not on_dag[lid_cb]
    res = eng.run_sets([(lid_ab, lid_cb)])
    b_id = topo.node_id("b")
    assert res.dist_of(0)[b_id] >= 3.0e38, (
        "b must be unreachable when BOTH links fail"
    )
    # native oracle agrees
    nat = NativeSpf(topo, "a")
    nd, _ = nat.solve_set([lid_ab, lid_cb])
    assert not np.isfinite(nd[b_id])
    # sanity: failing only a-b reroutes b via c at metric 6
    single = eng.run_sets([(lid_ab,)])
    assert single.dist_of(0)[b_id] == 6.0


def test_run_sets_through_selector_routes():
    """Full pipeline: set sweep -> on-device selection -> route deltas,
    vs a from-scratch python selection over the native set solve."""
    _ls, topo = build_world(seed=7)
    eng = LinkFailureSweep(topo, "node0")
    V = topo.num_nodes
    cands = SweepCandidates.single_advertiser(np.arange(V))
    sel = SweepRouteSelector(topo, "node0", cands, max_degree=eng.D)
    nat = NativeSpf(topo, "node0")
    rng = np.random.default_rng(3)
    sets = random_sets(topo, rng, 16, 3)
    deltas = sel.run(eng.run_sets(sets, fetch=False))
    root_id = topo.node_id("node0")
    for b, lids in enumerate(sets):
        nd, _ = nat.solve_set(list(lids))
        lanes = nat.lanes_dense(eng.D)
        valid, metric, nh = deltas.routes_of(b)
        for p in range(V):
            reach = np.isfinite(nd[p]) and lanes[p].any()
            want_valid = bool(reach) and p != root_id
            assert valid[p] == want_valid, (b, p)
            if want_valid:
                assert metric[p] == nd[p], (b, p)
                assert np.array_equal(nh[p], lanes[p]), (b, p)


def test_run_sets_sharded_parity():
    """Set sweeps shard over the mesh bit-identically (same shard_map
    path as single-link)."""
    import jax

    if len(jax.devices()) < 2:
        pytest.skip("needs a multi-device mesh")
    from openr_tpu.parallel.mesh import make_mesh

    _ls, topo = build_world(seed=13)
    rng = np.random.default_rng(5)
    sets = None
    eng1 = LinkFailureSweep(topo, "node0")
    sets = random_sets(topo, rng, 40, 3)
    r1 = eng1.run_sets(sets)
    engN = LinkFailureSweep(topo, "node0", mesh=make_mesh())
    rN = engN.run_sets(sets)
    assert np.array_equal(r1.snap_row, rN.snap_row)
    assert np.array_equal(r1.dist, rN.dist)
    assert np.array_equal(r1.nh, rN.nh)
