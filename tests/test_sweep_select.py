"""Sweep → on-device route selection: delta-only pipeline parity.

The SweepRouteSelector must reproduce, for every snapshot, exactly the
route table a from-scratch scalar computation yields: selection chain
over the perturbed SPF (reach, preference tie-breaks, min-distance,
igp-tie ECMP lane union), with deltas fetched only for changed rows."""

import numpy as np

from openr_tpu.decision.link_state import LinkState
from openr_tpu.emulation.topology import (
    build_adj_dbs,
    grid_edges,
    random_connected_edges,
)
from openr_tpu.ops.csr import encode_link_state
from openr_tpu.ops.sweep_select import (
    SweepCandidates,
    SweepRouteDeltas,
    SweepRouteSelector,
)
from openr_tpu.ops.whatif import LinkFailureSweep

BIG = 3.0e38


def build_world(seed=3, n_nodes=48, n_links=96):
    edges = random_connected_edges(n_nodes, n_links, seed=seed)
    ls = LinkState("0")
    for db in build_adj_dbs(edges).values():
        ls.update_adjacency_database(db)
    return encode_link_state(ls)


def scalar_routes(topo, eng, cands, snapshot_fail):
    """Oracle: selection chain in numpy over a from-scratch solve."""
    from openr_tpu.ops.native_spf import NativeSpf

    native = NativeSpf(topo, "node0")
    native.solve(failed_link=int(snapshot_fail))
    dist = native.dist
    lanes = native.lanes_dense(eng.D)  # [V, D]

    P, C = cands.cand_node.shape
    valid = np.zeros(P, bool)
    metric = np.full(P, BIG, np.float32)
    out_lanes = np.zeros((P, eng.D), np.int8)
    for p in range(P):
        cand = [
            (int(cands.cand_node[p, c]))
            for c in range(C)
            if cands.cand_ok[p, c]
        ]
        reach = [n for n in cand if np.isfinite(dist[n])]
        if not reach:
            continue
        # equal preference attributes in these tests: all reachable win
        # selection; igp tie-break picks min-distance advertisers
        best = min(dist[n] for n in reach)
        winners = [n for n in reach if dist[n] == best]
        ln = np.zeros(eng.D, np.int8)
        for n in winners:
            ln |= lanes[n].astype(np.int8)
        if not ln.any():
            continue
        valid[p] = True
        metric[p] = best
        out_lanes[p] = ln
    return valid, metric, out_lanes


def test_sweep_route_deltas_match_scalar_oracle():
    topo = build_world()
    eng = LinkFailureSweep(topo, "node0")
    rng = np.random.default_rng(5)
    fails = rng.integers(-1, len(topo.links), size=96).astype(np.int32)

    # anycast pairs: prefix p advertised by node p AND node (p*7+13)%V
    V = topo.num_nodes
    a = np.arange(V, dtype=np.int32)
    b = (a * 7 + 13) % V
    cands = SweepCandidates(
        cand_node=np.stack([a, b], axis=1),
        cand_ok=np.ones((V, 2), bool),
        drain_metric=np.zeros((V, 2), np.int32),
        path_pref=np.zeros((V, 2), np.int32),
        source_pref=np.zeros((V, 2), np.int32),
        distance=np.zeros((V, 2), np.int32),
        min_nexthop=np.zeros((V, 2), np.int32),
    )
    sel = SweepRouteSelector(topo, "node0", cands, max_degree=eng.D)
    sweep = eng.run(fails, fetch=False)
    deltas = sel.run(sweep)
    assert isinstance(deltas, SweepRouteDeltas)
    assert deltas.fetch_bytes > 0

    for s in [0, 7, 23, 50, 95]:
        valid, metric, lanes = deltas.routes_of(s)
        ev, em, el = scalar_routes(topo, eng, cands, fails[s])
        assert np.array_equal(valid, ev), f"valid mismatch snapshot {s}"
        assert np.array_equal(metric[ev], em[ev]), f"metric snapshot {s}"
        assert np.array_equal(lanes[ev], el[ev]), f"lanes snapshot {s}"


def test_sweep_route_deltas_sparse():
    """Most single-link failures change few routes: the delta payload
    must be a small fraction of B x P, and off-DAG snapshots contribute
    zero deltas."""
    topo = build_world(seed=11)
    eng = LinkFailureSweep(topo, "node0")
    V = topo.num_nodes
    cands = SweepCandidates.single_advertiser(np.arange(V))
    sel = SweepRouteSelector(topo, "node0", cands, max_degree=eng.D)

    fails = np.arange(len(topo.links), dtype=np.int32)
    sweep = eng.run(fails, fetch=False)
    deltas = sel.run(sweep)
    B, P = len(fails), V
    assert 0 < deltas.num_deltas < 0.25 * B * P
    # off-DAG snapshots alias the base row: zero deltas
    off_dag = ~eng.on_dag_links()
    for s in np.nonzero(off_dag)[0][:5]:
        assert deltas.snap_row[s] == 0
        v, m, ln = deltas.routes_of(int(s))
        assert np.array_equal(v, deltas.base_valid)


def test_base_select_eager_workaround_regression():
    """Pin the jax-0.9.0 executable-cache corruption dodge (VERDICT r3
    weak #6): `_base_select` must run EAGER.  Minimal repro of the
    trigger: compile the fleet kernels FIRST, then build two selectors'
    base tables back to back — under a jitted wrapper the second build
    intermittently drew a corrupted cache entry ('Execution supplied 12
    buffers but compiled program expected 15').  This test (a) asserts
    the workaround is still in place (no jit cache on _base_select) and
    (b) drives the exact trigger sequence, asserting correct output
    either way, so removing the workaround while the bug persists fails
    here rather than in production sweeps.
    """
    import jax

    from openr_tpu.decision.fleet import FleetRibEngine
    from openr_tpu.decision.prefix_state import PrefixState
    from openr_tpu.decision.spf_solver import SpfSolver
    from openr_tpu.ops import sweep_select as ss
    from openr_tpu.types import PrefixEntry

    # (a) the workaround: _base_select must not be a jit wrapper
    assert not hasattr(ss._base_select, "lower"), (
        "_base_select is jitted again — only safe once the jax 0.9 "
        "executable-cache corruption (see its docstring) is fixed; "
        "re-verify with this test's trigger sequence before removing"
    )

    # (b) the trigger sequence: fleet kernels compile first...
    ls = LinkState("0")
    for db in build_adj_dbs(grid_edges(4)).values():
        ls.update_adjacency_database(db)
    ps = PrefixState()
    for i in range(16):
        ps.update_prefix(f"node{i}", "0", PrefixEntry(f"10.{i}.0.0/24"))
    als = {"0": ls}
    fleet = FleetRibEngine(SpfSolver("node0"))
    assert fleet.compute_for_node("node1", als, ps, change_seq=1) is not None

    # ...then two selector base-table builds back to back
    topo = encode_link_state(ls)
    for root in ("node0", "node1"):
        eng = LinkFailureSweep(topo, root)
        sel = SweepRouteSelector(
            topo,
            root,
            SweepCandidates.single_advertiser(np.arange(16)),
            max_degree=eng.D,
        )
        base_dist, base_nh = eng.base_solve()
        valid, metric, lanes = sel.base_routes(base_dist, base_nh)
        # correct output either way: metric == base distance for every
        # valid single-advertiser prefix, self-prefix invalid
        rid = topo.node_id(root)
        for p in range(16):
            if p == rid:
                assert not valid[p]
                continue
            assert valid[p], (root, p)
            assert metric[p] == base_dist[p], (root, p)


def test_sweep_fetch_is_one_round_trip_multi_chunk():
    """A multi-chunk sweep must cost ONE blocking device->host fetch
    (a single device_get over all chunk compactions overlaps every
    copy): per-chunk round trips put one blocking wait per chunk on the
    e2e latency.  fetch_groups counts the blocking fetch rounds."""
    topo = build_world(seed=3)
    eng = LinkFailureSweep(topo, "node0", max_chunk=32)
    V = topo.num_nodes
    cands = SweepCandidates.single_advertiser(np.arange(V))
    sel = SweepRouteSelector(topo, "node0", cands, max_degree=eng.D)
    fails = np.arange(len(topo.links), dtype=np.int32)
    sweep = eng.run(fails, fetch=False)
    assert len(sweep.chunks) > 1, "test needs a multi-chunk sweep"
    deltas = sel.run(sweep)
    assert deltas.fetch_groups == 1
    # parity unaffected by the fused fetch
    v, m, ln = deltas.routes_of(0)
    ev, em, el = scalar_routes(topo, eng, cands, fails[0])
    assert np.array_equal(v, ev)


def test_pipelined_start_finish_matches_run():
    """The overlapped fetch path (start() + copy_to_host_async +
    finish()) must be byte-identical to the synchronous run(), including
    with several sweeps in flight — the steady-state what-if service
    keeps a pipeline of pending fetches so the device round trip
    overlaps the next sweeps' SPF + selection."""
    topo = build_world(seed=11)
    eng = LinkFailureSweep(topo, "node0")
    V = topo.num_nodes
    cands = SweepCandidates.single_advertiser(np.arange(V))
    sel = SweepRouteSelector(topo, "node0", cands, max_degree=eng.D)
    rng = np.random.default_rng(5)
    sweeps = [
        rng.integers(0, len(topo.links), size=60).astype(np.int32)
        for _ in range(4)
    ]
    expected = [sel.run(eng.run(f, fetch=False)) for f in sweeps]
    # pipelined: all four in flight before the first finish
    pend = [sel.start(eng.run(f, fetch=False)) for f in sweeps]
    got = [p.finish() for p in pend]
    for e, g in zip(expected, got):
        assert np.array_equal(e.snap_row, g.snap_row)
        assert np.array_equal(e.delta_row, g.delta_row)
        assert np.array_equal(e.delta_prefix, g.delta_prefix)
        assert np.array_equal(e.delta_valid, g.delta_valid)
        assert np.array_equal(e.delta_metric, g.delta_metric)
        assert np.array_equal(e.delta_lanes, g.delta_lanes)
        assert g.fetch_groups == 1


def test_greedy_chunk_decomposition_covers_and_reuses_buckets():
    """_chunk_sizes must exactly cover the unique-solve count with
    bucket-sized chunks, largest first, with padding below the smallest
    bucket — 1125 uniques must NOT pad to a 4096 batch (3.6x wasted
    SPF+selection compute at the headline scale)."""
    topo = build_world(seed=3)
    eng = LinkFailureSweep(topo, "node0")
    assert eng._chunk_sizes(1125) == [1024, 64, 64]
    assert eng._chunk_sizes(64) == [64]
    assert eng._chunk_sizes(1) == [64]
    assert eng._chunk_sizes(0) == []
    assert eng._chunk_sizes(4096) == [4096]
    assert eng._chunk_sizes(10240) == [4096, 4096, 2048]
    for n in (1, 63, 65, 1000, 5000, 12345):
        sizes = eng._chunk_sizes(n)
        assert sum(sizes) >= n
        assert sum(sizes) - n < 64  # waste below the smallest bucket
        assert all(s in eng.solve_buckets for s in sizes)


def test_pending_deltas_pin_their_base_across_engine_rebuilds():
    """A PendingDeltas started against base A must decode against base A
    even if the selector serves a rebuilt engine (base B) before
    finish() — the on-device diff ran against A, so patching B's table
    with A's deltas would corrupt every prefix that differs between the
    generations (review finding on the depth-N pipeline)."""
    edges_a = random_connected_edges(48, 96, seed=21)
    # generation B: same node table, one link metric bumped hard enough
    # to move base routes
    edges_b = [
        (u, v, (w + 900 if i == 0 else w))
        for i, (u, v, w) in enumerate(edges_a)
    ]

    def encode(edges):
        ls = LinkState("0")
        for db in build_adj_dbs(edges).values():
            ls.update_adjacency_database(db)
        return encode_link_state(ls)

    topo_a, topo_b = encode(edges_a), encode(edges_b)
    eng_a = LinkFailureSweep(topo_a, "node0")
    eng_b = LinkFailureSweep(topo_b, "node0")
    V = topo_a.num_nodes
    cands = SweepCandidates.single_advertiser(np.arange(V))
    sel = SweepRouteSelector(topo_a, "node0", cands, max_degree=eng_a.D)
    rng = np.random.default_rng(9)
    fails = rng.integers(0, len(topo_a.links), size=50).astype(np.int32)

    ref_sel = SweepRouteSelector(topo_a, "node0", cands, max_degree=eng_a.D)
    expected = ref_sel.run(eng_a.run(fails, fetch=False))

    pend = sel.start(eng_a.run(fails, fetch=False))
    sel.run(eng_b.run(fails, fetch=False))  # base B replaces sel._base
    got = pend.finish()
    assert np.array_equal(got.base_metric, expected.base_metric)
    assert np.array_equal(got.base_lanes, expected.base_lanes)
    for s in range(0, 50, 7):
        for e, g in zip(expected.routes_of(s), got.routes_of(s)):
            assert np.array_equal(e, g)
    # double-finish must fail loudly, not return "no changes"
    try:
        pend.finish()
    except RuntimeError:
        pass
    else:
        raise AssertionError("second finish() did not raise")
