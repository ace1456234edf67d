"""Sharded flagship engine: mesh-parallel warm-start sweep + selection.

The tests run on the conftest's 8-device virtual CPU mesh and hold the
sharded code paths (shard_map over the batch axis — ops/repair.py,
ops/sweep_select.py, ops/fleet_tables.py) to BIT parity with the
unsharded kernels.  Both relaxation loops reach unique fixed points, so
sharding must not change a single bit of any output (see the
ops/repair.py module docstring for the argument); these tests enforce
that, including non-multiple batch sizes that ride the bucket padding.
"""

import numpy as np
import pytest

pytestmark = pytest.mark.multichip

from openr_tpu.decision.link_state import LinkState
from openr_tpu.decision.prefix_state import PrefixState
from openr_tpu.emulation.topology import build_adj_dbs, grid_edges
from openr_tpu.ops.csr import encode_link_state
from openr_tpu.ops.sweep_select import SweepCandidates, SweepRouteSelector
from openr_tpu.ops.whatif import LinkFailureSweep
from openr_tpu.types import PrefixEntry


@pytest.fixture(scope="module")
def world():
    ls = LinkState("0")
    for db in build_adj_dbs(grid_edges(5)).values():
        ls.update_adjacency_database(db)
    return ls, encode_link_state(ls)


def _mesh(n):
    import jax

    from openr_tpu.parallel.mesh import make_mesh

    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} devices")
    return make_mesh(n)


def test_sharded_sweep_bit_parity(world):
    _ls, topo = world
    L = len(topo.links)
    fails = np.asarray([b % L for b in range(197)], np.int32)  # odd size
    r1 = LinkFailureSweep(topo, "node0").run(fails, fetch=True)
    r8 = LinkFailureSweep(topo, "node0", mesh=_mesh(8)).run(
        fails, fetch=True
    )
    assert np.array_equal(r1.snap_row, r8.snap_row)
    assert np.array_equal(r1.dist, r8.dist)
    assert np.array_equal(r1.nh, r8.nh)


def test_sharded_selector_delta_parity(world):
    _ls, topo = world
    V = 25
    L = len(topo.links)
    fails = np.asarray([b % L for b in range(101)], np.int32)
    cands = SweepCandidates.single_advertiser(np.arange(V))

    def deltas(mesh):
        eng = LinkFailureSweep(topo, "node0", mesh=mesh)
        sel = SweepRouteSelector(
            topo, "node0", cands, max_degree=eng.D, mesh=mesh
        )
        return sel.run(eng.run(fails, fetch=False))

    d1, d8 = deltas(None), deltas(_mesh(8))
    for f in (
        "snap_row",
        "base_valid",
        "base_metric",
        "base_lanes",
        "delta_row",
        "delta_prefix",
        "delta_valid",
        "delta_metric",
        "delta_lanes",
    ):
        assert np.array_equal(getattr(d1, f), getattr(d8, f)), f
    assert d8.num_deltas > 0  # the parity must cover a non-trivial stream


def test_sharded_sweep_odd_mesh_size(world):
    """A 3-device mesh: granularity 96, buckets round up to multiples."""
    _ls, topo = world
    L = len(topo.links)
    fails = np.asarray([b % L for b in range(50)], np.int32)
    eng = LinkFailureSweep(topo, "node0", mesh=_mesh(3))
    assert eng.batch_granularity == 96
    assert all(b % 96 == 0 for b in eng.solve_buckets)
    r3 = eng.run(fails, fetch=True)
    r1 = LinkFailureSweep(topo, "node0").run(fails, fetch=True)
    assert np.array_equal(r1.dist, r3.dist)
    assert np.array_equal(r1.nh, r3.nh)


def test_sharded_fleet_matches_scalar_for_every_root():
    """FleetRibEngine(mesh=...) must equal the scalar per-node solver —
    the same bar the unsharded fleet test holds (Decision.cpp:342)."""
    from openr_tpu.decision.fleet import FleetRibEngine
    from openr_tpu.decision.rib import route_db_summary
    from openr_tpu.decision.spf_solver import SpfSolver

    ls = LinkState("0")
    for db in build_adj_dbs(
        grid_edges(4), soft_drained={"node10": 60}, overloaded=["node5"]
    ).values():
        ls.update_adjacency_database(db)
    ps = PrefixState()
    for i in range(16):
        ps.update_prefix(f"node{i}", "0", PrefixEntry(f"10.{i}.0.0/24"))
    als = {"0": ls}
    eng = FleetRibEngine(SpfSolver("node0"), mesh=_mesh(8))
    assert eng.eligible(als, ps, change_seq=1)
    for i in range(16):
        node = f"node{i}"
        got = eng.compute_for_node(node, als, ps, change_seq=1)
        want = SpfSolver(node).build_route_db(als, ps)
        assert route_db_summary(got) == route_db_summary(want), node
    assert eng.num_batched_solves == 1


def test_sharded_multi_chunk_sweep_parity(world):
    """Chunked dispatch under a mesh: max_chunk forces several chunks
    per sweep; rows must land at the right offsets regardless of
    sharded bucket padding."""
    _ls, topo = world
    L = len(topo.links)
    fails = np.asarray([b % L for b in range(160)], np.int32)
    r1 = LinkFailureSweep(topo, "node0", max_chunk=16).run(
        fails, fetch=True
    )
    r8 = LinkFailureSweep(
        topo, "node0", max_chunk=16, mesh=_mesh(8)
    ).run(fails, fetch=True)
    assert np.array_equal(r1.snap_row, r8.snap_row)
    assert np.array_equal(r1.dist, r8.dist)
    assert np.array_equal(r1.nh, r8.nh)


def test_sharded_multiarea_whatif_engine_parity():
    """MultiAreaWhatIfEngine(mesh=...) must return the IDENTICAL result
    dict as the unsharded engine — singles, parallel bundles, and a
    simultaneous set all ride the failure-batch-sharded kernel
    (ops.fleet_tables.sharded_whatif_tables)."""
    import dataclasses

    from openr_tpu.decision.spf_solver import SpfSolver
    from openr_tpu.decision.whatif_api import MultiAreaWhatIfEngine
    from openr_tpu.emulation.topology import ring_edges

    me = "a0"

    def make_ls(area, edges):
        ls = LinkState(area, me)
        for db in build_adj_dbs(edges).values():
            ls.update_adjacency_database(dataclasses.replace(db, area=area))
        return ls

    als = {
        "1": make_ls("1", ring_edges(5, prefix="a")),
        "2": make_ls("2", [("a0", "b0", 1), ("b0", "b1", 1),
                           ("b1", "b2", 1), ("a0", "b2", 4)]),
    }
    ps = PrefixState()
    for node, area in (("a2", "1"), ("a3", "1"), ("b1", "2"), ("b2", "2")):
        ps.update_prefix(node, area, PrefixEntry(f"10.{ord(node[0])}.{node[1]}.0/24"))
    queries = [
        ([("a0", "a1"), ("b0", "b1"), ("a2", "a3")], False),
        ([("a0", "a1"), ("b1", "b2")], True),  # simultaneous set
    ]
    for failures, sim in queries:
        r1 = MultiAreaWhatIfEngine(SpfSolver(me)).run(
            failures, als, ps, 1, simultaneous=sim
        )
        r8 = MultiAreaWhatIfEngine(SpfSolver(me), mesh=_mesh(8)).run(
            failures, als, ps, 1, simultaneous=sim
        )
        assert r1 == r8, (sim, r1, r8)
