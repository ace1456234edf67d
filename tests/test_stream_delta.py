"""Pipelined host/device rebuild (ISSUE 11 tentpole): dense in-edge
SPF kernel bit-parity, the streamed double-buffered shard dispatcher
(out-of-order completion reassembly, mid-stream chip quarantine
re-pack, in-flight slot ledger, honest per-chip busy accounting), and
the on-device delta-extraction path (full-build delta decode vs the
host full decode it replaces, fleet generation delta)."""

import numpy as np
import pytest

from openr_tpu.common.runtime import CounterMap, WallClock
from openr_tpu.config import ParallelConfig, ResilienceConfig
from openr_tpu.decision.link_state import LinkState
from openr_tpu.decision.prefix_state import PrefixState
from openr_tpu.decision.spf_solver import SpfSolver
from openr_tpu.emulation.topology import build_adj_dbs, grid_edges, ring_edges
from openr_tpu.tracing import pipeline
from openr_tpu.types import PrefixEntry

pytestmark = pytest.mark.multichip


def make_world(side=8, area="0"):
    adj = build_adj_dbs(grid_edges(side), area=area)
    ls = LinkState(area)
    for db in adj.values():
        ls.update_adjacency_database(db)
    ps = PrefixState()
    for i in range(side * side):
        ps.update_prefix(
            f"node{i}", area, PrefixEntry(f"10.{(i >> 8) & 255}.{i & 255}.0/24")
        )
    return adj, {area: ls}, ps


def make_backend(ndev=8, resilience_enabled=False, **kw):
    from openr_tpu.decision.backend import TpuBackend

    return TpuBackend(
        SpfSolver("node0"),
        min_device_prefixes=0,
        clock=WallClock(),
        counters=CounterMap(),
        resilience=ResilienceConfig(enabled=resilience_enabled),
        parallel=ParallelConfig(max_devices=ndev, min_shard_rows=0),
        **kw,
    )


def assert_db_equal(a, b):
    assert a.unicast_routes.keys() == b.unicast_routes.keys()
    for p, e in b.unicast_routes.items():
        d = a.unicast_routes[p]
        assert d.nexthops == e.nexthops, p
        assert d.igp_cost == e.igp_cost, p


# ---------------------------------------------------------------------------
# dense in-edge kernels: bit-parity with the segment-reduction twins
# ---------------------------------------------------------------------------


def _table_pair(enc):
    import jax.numpy as jnp

    from openr_tpu.decision.backend import DEGREE_BUCKETS
    from openr_tpu.ops.csr import bucket_for
    from openr_tpu.ops.route_select import (
        multi_area_spf_tables,
        multi_area_spf_tables_dense,
    )

    D = bucket_for(max(enc.max_out_degree(), 1), DEGREE_BUCKETS)
    seg = multi_area_spf_tables(
        jnp.asarray(enc.src),
        jnp.asarray(enc.dst),
        jnp.asarray(enc.w),
        jnp.asarray(enc.edge_ok),
        jnp.asarray(enc.overloaded),
        jnp.asarray(enc.roots),
        max_degree=D,
    )
    dense = multi_area_spf_tables_dense(
        jnp.asarray(enc.in_src),
        jnp.asarray(enc.in_w),
        jnp.asarray(enc.in_ok),
        jnp.asarray(enc.in_rank),
        jnp.asarray(enc.in_has),
        jnp.asarray(enc.overloaded),
        jnp.asarray(enc.roots),
        max_degree=D,
    )
    return seg, dense


def test_dense_spf_bit_parity_multiarea_with_drains():
    """The dense gather kernels reach the segment kernels' fixed points
    BIT-IDENTICALLY (incl. the int8-min fill on absent-dst lane rows),
    across a multi-area LSDB with asymmetric metrics, a hard-drained
    node and a soft-drained node."""
    from openr_tpu.ops.csr import encode_multi_area

    rng = np.random.default_rng(7)
    adjA = build_adj_dbs(ring_edges(12), area="A")
    lsA = LinkState("A")
    for db in adjA.values():
        for a in db.adjacencies:
            a.metric = int(rng.integers(1, 9))
        lsA.update_adjacency_database(db)
    lsA._update_node_overloaded("node3", True)
    lsA._node_metric_increments["node7"] = 50
    adjB = build_adj_dbs(grid_edges(5), area="B")
    lsB = LinkState("B")
    for db in adjB.values():
        lsB.update_adjacency_database(db)
    als = {"A": lsA, "B": lsB}
    enc = encode_multi_area(als, "node2")
    assert enc.has_dense
    (d1, n1), (d2, n2) = _table_pair(enc)
    assert np.array_equal(np.asarray(d1), np.asarray(d2))
    assert np.array_equal(np.asarray(n1), np.asarray(n2))


def test_dense_parity_survives_encode_patch():
    """The O(links) patch path refreshes the dense weight/validity
    planes through the shared slot layout; parity holds after a metric
    perturbation AND the layout arrays stay identity-shared."""
    from openr_tpu.ops.csr import encode_multi_area, patch_encoded_multi_area

    adj, als, _ps = make_world(6)
    enc = encode_multi_area(als, "node0")
    flip = adj["node8"]
    for a in flip.adjacencies:
        a.metric = 4
    als["0"].update_adjacency_database(flip)
    patched = patch_encoded_multi_area(enc, als, "node0")
    assert patched is not None and patched.has_dense
    assert patched.in_src is enc.in_src
    assert patched.in_rank is enc.in_rank
    assert patched.in_has is enc.in_has
    (d1, n1), (d2, n2) = _table_pair(patched)
    assert np.array_equal(np.asarray(d1), np.asarray(d2))
    assert np.array_equal(np.asarray(n1), np.asarray(n2))


def test_in_edge_matrix_layout_and_ranks():
    """Slot/rank construction against the segment kernels' reference
    semantics: rank == index among same-src edges in edge order, every
    real edge (down links included) owns exactly one slot, pads carry
    in_ok=False."""
    from openr_tpu.ops.csr import build_in_edge_matrix

    # hand-built dst-sorted edge list with a down link and a parallel
    # pair; V=4 padded to 6, E padded to 12
    src = np.array([1, 2, 0, 0, 3, 0, 1, 5, 5, 5, 5, 5], np.int32)
    dst = np.array([0, 0, 1, 1, 1, 2, 3, 5, 5, 5, 5, 5], np.int32)
    w = np.array([1, 2, 1, 3, 9, 4, 2, 0, 0, 0, 0, 0], np.float32)
    ok = np.array(
        [1, 1, 1, 1, 0, 1, 1, 0, 0, 0, 0, 0], bool
    )  # edge 4 is a down link
    link_index = np.array(
        [0, 1, 0, 2, 3, 4, 5, -1, -1, -1, -1, -1], np.int32
    )
    out = build_in_edge_matrix(src, dst, w, ok, link_index, 6)
    assert out is not None
    in_src, in_w, in_ok, in_rank, in_edge_pos, in_has = out
    # node1 has three in-slots (two parallel from node0, one down from 3)
    assert sorted(in_src[1][in_w[1] < np.inf].tolist()) == [0, 0, 3]
    assert sorted(in_src[1][in_ok[1]].tolist()) == [0, 0]
    assert (in_ok[1].sum()) == 2  # the down link's slot is not ok
    # ranks: edges 2,3 are node0's out-edges in order -> ranks 0,1;
    # edge 5 is node0's third out-edge -> rank 2
    flat = in_edge_pos
    assert in_rank.flat[flat[2]] == 0
    assert in_rank.flat[flat[3]] == 1
    assert in_rank.flat[flat[5]] == 2
    # every real edge owns a distinct slot; pads own none
    real = flat[link_index >= 0]
    assert len(set(real.tolist())) == 7 and (flat[link_index < 0] == -1).all()
    # in_has covers every dst present in the padded list (pads point at 5)
    assert in_has[[0, 1, 2, 3, 5]].all() and not in_has[4]


def test_dense_declines_past_in_degree_bucket_and_backend_falls_back():
    """A hub with more in-edges than the largest IN_DEGREE_BUCKET
    declines the dense layout; the backend transparently solves via the
    segment kernels and still matches the scalar oracle."""
    from openr_tpu.ops.csr import IN_DEGREE_BUCKETS, encode_multi_area

    n_leaves = IN_DEGREE_BUCKETS[-1] + 1
    edges = [("hub", f"leaf{i}", 1) for i in range(n_leaves)]
    adj = build_adj_dbs(edges)
    ls = LinkState("0")
    for db in adj.values():
        ls.update_adjacency_database(db)
    als = {"0": ls}
    enc = encode_multi_area(als, "hub")
    assert not enc.has_dense
    ps = PrefixState()
    for i in range(0, 64):
        ps.update_prefix(f"leaf{i}", "0", PrefixEntry(f"10.3.{i}.0/24"))
    from openr_tpu.decision.backend import TpuBackend

    backend = TpuBackend(
        SpfSolver("hub"),
        min_device_prefixes=0,
        resilience=ResilienceConfig(enabled=False),
        parallel=ParallelConfig(max_devices=1),
    )
    db = backend.build_route_db(als, ps, force_full=True)
    sc = SpfSolver("hub").build_route_db(als, ps)
    assert_db_equal(db, sc)


# ---------------------------------------------------------------------------
# the streamed dispatcher
# ---------------------------------------------------------------------------


def test_streamed_full_build_matches_oracle_and_records_stream_phases():
    _adj, als, ps = make_world()
    for ndev in (1, 8):
        b = make_backend(ndev)
        db = b.build_route_db(als, ps, force_full=True)
        assert_db_equal(db, SpfSolver("node0").build_route_db(als, ps))
        assert b.num_stream_builds == 1
        h = b.probe.counters.histogram(
            pipeline.hist_key(pipeline.STREAM_DRAIN)
        )
        assert h is not None and h.count == (1 if ndev == 1 else 8)
        # the in-flight ledger closed the loop: nothing left in flight,
        # and the high watermark proves dispatches actually overlapped
        assert all(n == 0 for n in b.pool.num_inflight)
        assert max(b.pool.max_inflight) >= 1


def test_out_of_order_completion_reassembles_row_order():
    """Shard reassembly must be row-order-correct when chips finish in
    ARBITRARY order: force last-in-first-out and seeded-random drain
    orders through the completion-pick seam and demand bit-parity with
    the scalar oracle either way."""
    _adj, als, ps = make_world()
    oracle = SpfSolver("node0").build_route_db(als, ps)
    rng = np.random.default_rng(11)
    for pick in (
        lambda pending: len(pending) - 1,  # strict LIFO
        lambda pending: int(rng.integers(len(pending))),  # arbitrary
    ):
        b = make_backend(8)
        b._stream_pick = pick
        db = b.build_route_db(als, ps, force_full=True)
        assert_db_equal(db, oracle)
        assert len({d for d, _lo, _hi in b._attr_plan}) > 1


def test_mid_stream_chip_failure_repacks_onto_survivors():
    """A shard failing at drain time quarantines ITS chip, re-packs
    exactly its row range onto the lead survivor and resumes — no rows
    dropped, none duplicated, and the next build's plan excludes the
    quarantined chip."""
    _adj, als, ps = make_world()
    b = make_backend(8, resilience_enabled=True)
    fired = []

    def fault(dev_index):
        if dev_index == 3 and not fired:
            fired.append(dev_index)
            raise RuntimeError("injected mid-stream chip failure")

    b._stream_fault = fault
    db = b.build_route_db(als, ps, force_full=True)
    assert fired == [3]
    assert b.num_stream_repacks == 1
    assert_db_equal(db, SpfSolver("node0").build_route_db(als, ps))
    assert not b.pool.is_healthy(3)
    # re-packed build is unattributable by design (rows moved off plan)
    assert b._attr_table is None
    b._stream_fault = None
    db2 = b.build_route_db(als, ps, force_full=True)
    assert_db_equal(db2, SpfSolver("node0").build_route_db(als, ps))
    assert 3 not in {d for d, _lo, _hi in (b._attr_plan or ())}


def test_mid_stream_failure_without_governor_falls_back_scalar():
    """Legacy resilience-disabled semantics preserved: a drain failure
    with no governor propagates and... the build still answers (scalar
    fallback), it just cannot re-pack."""
    _adj, als, ps = make_world()
    b = make_backend(8, resilience_enabled=False)

    def fault(dev_index):
        if dev_index == 2:
            raise RuntimeError("boom")

    b._stream_fault = fault
    with pytest.raises(RuntimeError):
        b.build_route_db(als, ps, force_full=True)


def test_stream_busy_accounting_charges_completing_chip_only():
    """The honest-utilization satellite: per-chip busy time under the
    streamed dispatcher sums to (at most) the attributed device-side
    phase time — the old barrier charged the whole device_get window to
    EVERY in-flight chip, overcounting by up to the chip count."""
    _adj, als, ps = make_world()
    b = make_backend(8)
    b.build_route_db(als, ps, force_full=True)
    counters = b.probe.counters
    attributed = 0.0
    for phase in pipeline.PHASES:
        h = counters.histogram(pipeline.hist_key(phase))
        if h is not None:
            attributed += h.total
    busy = sum(b.probe.busy_snapshot().values())
    assert busy <= attributed * 1.05 + 1e-6


# ---------------------------------------------------------------------------
# on-device delta extraction (cold/full builds)
# ---------------------------------------------------------------------------


def test_full_build_delta_decode_bit_parity_and_object_identity():
    """The cold-path generation delta: consecutive force_full builds
    with exact (empty) churn patch through unchanged rows
    object-identically, fetch only changed rows, report
    take_last_changed_prefixes, and stay bit-parity with both the host
    full decode they replace and the scalar oracle."""
    adj, als, ps = make_world()
    for ndev in (1, 8):
        b = make_backend(ndev)
        db0 = b.build_route_db(als, ps, changed_prefixes=set(), force_full=True)
        assert b.take_last_changed_prefixes() is None
        flip = adj["node63"]
        for a in flip.adjacencies:
            a.metric = 5
        als["0"].update_adjacency_database(flip)
        db1 = b.build_route_db(als, ps, changed_prefixes=set(), force_full=True)
        assert b.num_delta_builds == 1
        assert b.num_delta_rows_fetched >= 1
        assert b.num_delta_rows_skipped > 0
        changed = b.take_last_changed_prefixes()
        assert changed is not None and changed
        # host full decode it replaces: a fresh backend, full fetch
        fresh = make_backend(ndev)
        ref = fresh.build_route_db(als, ps, force_full=True)
        assert_db_equal(db1, ref)
        assert_db_equal(db1, SpfSolver("node0").build_route_db(als, ps))
        # unchanged prefixes patch through OBJECT-IDENTICALLY
        same = sum(
            1
            for p in db1.unicast_routes
            if db0.unicast_routes.get(p) is db1.unicast_routes[p]
        )
        assert same == len(db1.unicast_routes) - len(
            changed & set(db1.unicast_routes)
        )
        # device_select recorded the compacted gather
        h = b.probe.counters.histogram(
            pipeline.hist_key(pipeline.DEVICE_SELECT)
        )
        assert h is not None and h.count >= 1
        # restore for the next loop iteration
        for a in flip.adjacencies:
            a.metric = 1
        als["0"].update_adjacency_database(flip)


def test_delta_decode_handles_prefix_churn_and_deletion():
    """Churn rows are decoded even when the device reports their
    selection outputs unchanged (entry content the candidate columns
    don't encode), and deletions patch out of the db."""
    _adj, als, ps = make_world()
    b = make_backend(8)
    b.build_route_db(als, ps, changed_prefixes=set(), force_full=True)
    # delete one prefix, add another
    ps.delete_prefix("node5", "0", "10.0.5.0/24")
    ps.update_prefix("node9", "0", PrefixEntry("10.99.0.0/24"))
    changed = {"10.0.5.0/24", "10.99.0.0/24"}
    db = b.build_route_db(als, ps, changed_prefixes=changed, force_full=True)
    assert_db_equal(db, SpfSolver("node0").build_route_db(als, ps))
    assert "10.0.5.0/24" not in db.unicast_routes
    assert "10.99.0.0/24" in db.unicast_routes


def test_delta_declines_after_purge_and_on_static_change():
    """Purge semantics: corruption injection drops the delta base (the
    next full build fetches everything), and a static-route change
    declines the patch path."""
    _adj, als, ps = make_world()
    b = make_backend(8)
    b.build_route_db(als, ps, changed_prefixes=set(), force_full=True)
    assert b._prev_sel is not None
    b.inject_silent_corruption(True)
    assert b._prev_sel is None
    b.inject_silent_corruption(False)
    b.build_route_db(als, ps, changed_prefixes=set(), force_full=True)
    assert b.num_delta_builds == 0
    # static-route change between builds: delta declines
    from openr_tpu.decision.rib import RibUnicastEntry
    from openr_tpu.types import NextHop

    sr = {
        "10.200.0.0/24": RibUnicastEntry(
            prefix="10.200.0.0/24",
            nexthops=frozenset(
                {
                    NextHop(
                        address="fe80::1", if_name="eth0", metric=1
                    )
                }
            ),
            best_prefix_entry=PrefixEntry("10.200.0.0/24"),
            best_area="0",
            igp_cost=1,
        )
    }
    b.solver.update_static_unicast_routes(sr, [])
    db = b.build_route_db(als, ps, changed_prefixes=set(), force_full=True)
    assert b.num_delta_builds == 0
    assert "10.200.0.0/24" in db.unicast_routes


# ---------------------------------------------------------------------------
# fleet generation delta + engine streams
# ---------------------------------------------------------------------------


def test_fleet_generation_delta_parity():
    """The fleet engine's on-device generation delta: a perturbed
    generation re-solves on device but fetches only changed roots'
    rows; summaries and per-node RouteDbs match a fresh engine's full
    fetch."""
    from openr_tpu.decision.fleet import FleetRibEngine
    from openr_tpu.parallel.mesh import DevicePool

    adj, als, ps = make_world(6)
    pool = DevicePool()
    eng = FleetRibEngine(SpfSolver("node0"), pool=pool)
    eng.fleet_summary(als, ps, 1)
    flip = adj["node35"]
    for a in flip.adjacencies:
        a.metric = 7
    als["0"].update_adjacency_database(flip)
    s2 = eng.fleet_summary(als, ps, 2)
    assert eng.num_delta_solves == 1
    assert eng.num_delta_roots_fetched >= 1
    fresh = FleetRibEngine(SpfSolver("node0"), pool=pool)
    assert s2 == fresh.fleet_summary(als, ps, 2)
    db_a = eng.compute_for_node("node17", als, ps, 2)
    db_b = fresh.compute_for_node("node17", als, ps, 2)
    assert_db_equal(db_a, db_b)


def test_fleet_delta_declines_on_membership_change():
    """A node joining the prefix table (row map shifts on full_sync)
    must decline the delta and re-fetch everything."""
    from openr_tpu.decision.fleet import FleetRibEngine
    from openr_tpu.parallel.mesh import DevicePool

    _adj, als, ps = make_world(6)
    eng = FleetRibEngine(SpfSolver("node0"), pool=DevicePool())
    eng.fleet_summary(als, ps, 1)
    ps.update_prefix("node1", "0", PrefixEntry("10.123.0.0/24"))
    s = eng.fleet_summary(als, ps, 2)
    assert eng.num_delta_solves == 0
    fresh = FleetRibEngine(SpfSolver("node0"), pool=DevicePool())
    assert s == fresh.fleet_summary(als, ps, 2)


def test_whatif_pool_stream_matches_single_device():
    """The what-if engine's streamed per-shard drain is bit-identical
    to the single-device path."""
    from openr_tpu.decision.whatif_api import MultiAreaWhatIfEngine
    from openr_tpu.parallel.mesh import DevicePool

    _adj, als, ps = make_world(6)
    failures = [(f"node{i}", f"node{i + 1}") for i in range(0, 10) if (i + 1) % 6]
    pooled = MultiAreaWhatIfEngine(SpfSolver("node0"), pool=DevicePool())
    single = MultiAreaWhatIfEngine(SpfSolver("node0"))
    r1 = pooled.run(failures, als, ps, 1)
    r2 = single.run(failures, als, ps, 1)
    assert r1 == r2
    assert pooled.num_pool_dispatches >= 2


def test_survivor_mesh_collective_repacks_on_quarantine():
    """PR-6 remnant: engines given BOTH a mesh and a pool re-derive the
    collective mesh from DevicePool.survivor_mesh() when a chip
    quarantines mid-run, and results stay bit-identical."""
    from openr_tpu.decision.fleet import FleetRibEngine
    from openr_tpu.parallel.mesh import DevicePool

    _adj, als, ps = make_world(6)
    pool = DevicePool()
    eng = FleetRibEngine(
        SpfSolver("node0"), mesh=pool.survivor_mesh(), pool=pool
    )
    s1 = eng.fleet_summary(als, ps, 1)
    pool.quarantine_device(3)
    try:
        s2 = eng.fleet_summary(als, ps, 2)
        assert eng.mesh is not None
        assert eng.mesh.devices.size == pool.num_healthy
        fresh = FleetRibEngine(SpfSolver("node0"))
        assert s2 == fresh.fleet_summary(als, ps, 2)
        assert s1 == s2  # topology unchanged; only the mesh re-packed
    finally:
        pool.restore_device(3)


def test_active_mesh_rederives_on_health_transitions():
    """The mesh wiring itself: health transitions re-derive, restores
    re-admit, and engines without a pool keep their pinned mesh."""
    from openr_tpu.decision.fleet import FleetRibEngine
    from openr_tpu.parallel.mesh import DevicePool

    pool = DevicePool()
    eng = FleetRibEngine(SpfSolver("node0"), mesh=object(), pool=pool)
    assert eng._active_mesh().devices.size == 8
    pool.quarantine_device(2)
    assert eng._active_mesh().devices.size == 7
    pool.restore_device(2)
    assert eng._active_mesh().devices.size == 8
    # no pool: the constructor's mesh is pinned
    pinned = object()
    eng2 = FleetRibEngine(SpfSolver("node0"), mesh=pinned)
    assert eng2._active_mesh() is pinned
