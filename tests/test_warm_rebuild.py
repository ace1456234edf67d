"""Warm-start generation-delta rebuild (ISSUE 9 tentpole): the host
classifier (plan_generation_delta), the encode patch, the warm device
kernels, the selective-selection patch path, purge semantics, and the
content-hash RepairPlan cache.

The load-bearing property throughout: a warm rebuild's RouteDb is
BIT-IDENTICAL to both the cold device build and the scalar oracle, for
every generation of a seeded churn sweep — the warm start is an
optimization, never an approximation."""

import numpy as np
import pytest

from openr_tpu.common.runtime import SimClock
from openr_tpu.config import ParallelConfig, ResilienceConfig
from openr_tpu.decision.backend import TpuBackend
from openr_tpu.decision.link_state import LinkState
from openr_tpu.decision.prefix_state import PrefixState
from openr_tpu.decision.spf_solver import SpfSolver
from openr_tpu.emulation.topology import build_adj_dbs, grid_edges
from openr_tpu.ops.csr import encode_link_state, patch_encoded_topology
from openr_tpu.ops.repair import (
    build_repair_plan_cached,
    plan_cache_stats,
    plan_generation_delta,
    topology_content_hash,
)
from openr_tpu.types import PrefixEntry


def make_world(side=4, seed_prefix="10.7"):
    edges = grid_edges(side)
    adj = build_adj_dbs(edges)
    ls = LinkState("0", "node0")
    for db in adj.values():
        ls.update_adjacency_database(db)
    ps = PrefixState()
    for i in range(side * side):
        ps.update_prefix(
            f"node{i}", "0", PrefixEntry(f"{seed_prefix}.{i}.0/24")
        )
    return adj, ls, ps


def make_backend(warm=True, parallel=None, **res_kw):
    resilience = (
        ResilienceConfig(**res_kw) if res_kw else ResilienceConfig(enabled=False)
    )
    return TpuBackend(
        SpfSolver("node0"),
        clock=SimClock(),
        resilience=resilience,
        parallel=parallel,
        warm_rebuild=warm,
    )


def norm_db(db):
    return {
        p: (
            sorted((nh.neighbor_node_name, nh.metric) for nh in e.nexthops),
            float(e.igp_cost),
        )
        for p, e in db.unicast_routes.items()
    }


def perturb_metric(adj, ls, rng):
    victim = sorted(adj)[int(rng.integers(len(adj)))]
    db = adj[victim]
    a = db.adjacencies[int(rng.integers(len(db.adjacencies)))]
    a.metric = 1 + (a.metric % 3)
    ls.update_adjacency_database(db)


# ---------------------------------------------------------------------------
# host classifier + encode patch units
# ---------------------------------------------------------------------------


def test_plan_generation_delta_metric_perturbation():
    adj, ls, _ps = make_world()
    old_topo = encode_link_state(ls)
    root = old_topo.node_id("node0")
    from openr_tpu.ops.native_spf import NativeSpf

    native = NativeSpf(old_topo, "node0")
    dist, _ = native.solve(failed_link=-1)
    from openr_tpu.ops.consts import BIG

    dist = np.where(np.isfinite(dist), dist, np.float32(BIG)).astype(
        np.float32
    )
    # weaken one on-DAG link: the head's descendants (and only a
    # bounded set) reset
    db = adj["node0"]
    db.adjacencies[0].metric = 9
    ls.update_adjacency_database(db)
    new_topo = encode_link_state(ls)
    delta = plan_generation_delta(old_topo, root, dist, new_topo)
    assert delta is not None
    assert delta.num_perturbed_edges >= 1
    assert 0 < delta.num_reset < old_topo.padded_nodes
    assert delta.est_depth >= 1
    assert not delta.reset[root]


def test_plan_generation_delta_structural_is_none():
    adj, ls, _ps = make_world()
    old_topo = encode_link_state(ls)
    root = old_topo.node_id("node0")
    dist = np.zeros(old_topo.padded_nodes, np.float32)
    ls.delete_adjacency_database("node15")
    new_topo = encode_link_state(ls)
    assert plan_generation_delta(old_topo, root, dist, new_topo) is None


def test_patch_encoded_topology_matches_full_encode():
    adj, ls, _ps = make_world()
    old = encode_link_state(ls)
    db = adj["node5"]
    for a in db.adjacencies:
        a.metric = 4
    ls.update_adjacency_database(db)
    patched = patch_encoded_topology(old, ls)
    full = encode_link_state(ls)
    assert patched is not None
    # layout arrays are SHARED with the previous encoding
    assert patched.src is old.src and patched.link_index is old.link_index
    for field in ("src", "dst", "w", "edge_ok", "overloaded", "soft"):
        assert np.array_equal(
            getattr(patched, field), getattr(full, field)
        ), field
    # structural churn declines
    ls.delete_adjacency_database("node15")
    assert patch_encoded_topology(old, ls) is None


def test_topology_content_hash_tracks_graph_not_churn():
    _adj, ls, _ps = make_world()
    t1 = encode_link_state(ls)
    t2 = encode_link_state(ls)  # distinct object, same content
    assert topology_content_hash(t1) == topology_content_hash(t2)
    assert topology_content_hash(t1, 0) != topology_content_hash(t1, 1)


def test_repair_plan_cache_content_addressed():
    adj, ls, _ps = make_world()
    # make this test's graph content-unique: the memo is module-global
    # and other tests encode the same canonical 4x4 world
    db0 = adj["node10"]
    db0.adjacencies[0].metric = 1777
    ls.update_adjacency_database(db0)
    topo_a = encode_link_state(ls)
    root = topo_a.node_id("node0")
    from openr_tpu.ops.native_spf import NativeSpf
    from openr_tpu.ops.consts import BIG

    native = NativeSpf(topo_a, "node0")
    dist, _ = native.solve(failed_link=-1)
    dist = np.where(np.isfinite(dist), dist, np.float32(BIG)).astype(
        np.float32
    )
    from openr_tpu.ops.whatif import root_lane_count

    D = root_lane_count(topo_a, root)
    nh = native.lanes_dense(D)
    h0, m0 = plan_cache_stats()
    p1 = build_repair_plan_cached(topo_a, root, dist, nh)
    # a re-encode of the UNCHANGED graph (what every Decision change
    # generation does on prefix churn) must hit, returning the same plan
    topo_b = encode_link_state(ls)
    p2 = build_repair_plan_cached(topo_b, root, dist, nh)
    h1, m1 = plan_cache_stats()
    assert p2 is p1
    assert h1 == h0 + 1 and m1 == m0 + 1
    # a real graph change misses
    db = adj["node1"]
    db.adjacencies[0].metric = 7
    ls.update_adjacency_database(db)
    topo_c = encode_link_state(ls)
    native_c = NativeSpf(topo_c, "node0")
    dist_c, _ = native_c.solve(failed_link=-1)
    dist_c = np.where(
        np.isfinite(dist_c), dist_c, np.float32(BIG)
    ).astype(np.float32)
    p3 = build_repair_plan_cached(
        topo_c, root, dist_c, native_c.lanes_dense(D)
    )
    assert p3 is not p1
    _, m2 = plan_cache_stats()
    assert m2 == m1 + 1


# ---------------------------------------------------------------------------
# warm/cold/scalar parity across a seeded churn sweep
# ---------------------------------------------------------------------------


def test_warm_cold_scalar_parity_across_generations():
    adj, ls, ps = make_world()
    als = {"0": ls}
    warm = make_backend(warm=True)
    cold = make_backend(warm=False)
    warm.build_route_db(als, ps, force_full=True)
    cold.build_route_db(als, ps, force_full=True)
    rng = np.random.default_rng(11)
    prev_db = None
    for gen in range(8):
        kind = gen % 4
        if kind == 3:
            # overload flip rides the same warm classification as a
            # link perturbation (transit-enabled edges leave/enter)
            victim = sorted(adj)[int(rng.integers(len(adj)))]
            db = adj[victim]
            db.is_overloaded = not db.is_overloaded
            ls.update_adjacency_database(db)
        else:
            perturb_metric(adj, ls, rng)
        db_w = warm.build_route_db(
            als, ps, changed_prefixes=set(), force_full=True,
            warm_delta=True,
        )
        db_c = cold.build_route_db(
            als, ps, changed_prefixes=set(), force_full=True
        )
        db_s = SpfSolver("node0").build_route_db(als, ps)
        assert norm_db(db_w) == norm_db(db_c) == norm_db(db_s), f"gen {gen}"
        changed = warm.take_last_changed_prefixes()
        if changed is not None and prev_db is not None:
            # the selective patch path's changed-set guarantee: every
            # prefix OUTSIDE it is object-identical to the previous db
            # (the O(changed) publication diff depends on this)
            for p, e in db_w.unicast_routes.items():
                if p not in changed:
                    assert prev_db.unicast_routes[p] is e, (gen, p)
        prev_db = db_w
    assert warm.num_warm_builds == 8
    assert warm.num_warm_selective_builds == 8
    assert warm.num_warm_cold_fallbacks == 0
    snap = warm.counter_snapshot()
    assert snap["decision.backend.warm_hit_ratio"] == 1.0
    assert snap["decision.backend.warm_context_ready"] == 1.0


def test_warm_parity_with_prefix_churn_on_same_tick():
    adj, ls, ps = make_world()
    als = {"0": ls}
    warm = make_backend(warm=True)
    warm.build_route_db(als, ps, force_full=True)
    rng = np.random.default_rng(3)
    perturb_metric(adj, ls, rng)
    churn = "10.99.7.0/24"
    ps.update_prefix("node9", "0", PrefixEntry(churn))
    db_w = warm.build_route_db(
        als, ps, changed_prefixes={churn}, force_full=True, warm_delta=True
    )
    assert norm_db(db_w) == norm_db(SpfSolver("node0").build_route_db(als, ps))
    changed = warm.take_last_changed_prefixes()
    assert changed is not None and churn in changed
    # a prefix withdrawal coinciding with a perturbation patches too
    perturb_metric(adj, ls, rng)
    ps.delete_prefix("node9", "0", churn)
    db_w = warm.build_route_db(
        als, ps, changed_prefixes={churn}, force_full=True, warm_delta=True
    )
    assert churn not in db_w.unicast_routes
    assert norm_db(db_w) == norm_db(SpfSolver("node0").build_route_db(als, ps))


def test_structural_delta_unhinted_stays_cold_with_parity():
    adj, ls, ps = make_world()
    als = {"0": ls}
    warm = make_backend(warm=True)
    warm.build_route_db(als, ps, force_full=True)
    # node removal WITHOUT a delta hint (a static-route change
    # coinciding with the churn, say): the build stays cold — and the
    # slot-patched encoding it runs on must still match the oracle
    ls.delete_adjacency_database("node15")
    db_w = warm.build_route_db(
        als, ps, changed_prefixes=set(), force_full=True, warm_delta=False
    )
    assert norm_db(db_w) == norm_db(SpfSolver("node0").build_route_db(als, ps))
    assert warm.num_warm_builds == 0
    assert warm.num_warm_cold_fallbacks >= 1
    # ISSUE 12: membership churn with a delta hint (even the legacy
    # warm_delta spelling) now WARMS through the slot-stable encode —
    # the backend's own classifier proves layout identity and seeds
    # the tombstoned region, and the result stays bit-parity
    ls.delete_adjacency_database("node14")
    db_w = warm.build_route_db(
        als, ps, changed_prefixes=set(), force_full=True, warm_delta=True
    )
    assert norm_db(db_w) == norm_db(SpfSolver("node0").build_route_db(als, ps))
    assert warm.num_warm_builds == 1
    assert warm.num_encode_slot_patches >= 1


def test_structural_delta_hint_warms_and_splits_counters():
    adj, ls, ps = make_world()
    als = {"0": ls}
    warm = make_backend(warm=True)
    warm.build_route_db(als, ps, force_full=True)
    # leave: Decision classifies structural → the slot patch tombstones
    # the node in place and the warm solve repairs only its region
    ls.delete_adjacency_database("node15")
    db_w = warm.build_route_db(
        als,
        ps,
        changed_prefixes=set(),
        force_full=True,
        structural_delta=True,
    )
    assert norm_db(db_w) == norm_db(SpfSolver("node0").build_route_db(als, ps))
    assert warm._warm_class_builds["structural"] == 1
    assert warm._warm_class_builds["perturbation"] == 0
    # rejoin: the same node re-advertises identical adjacencies — its
    # slot and rows revive, improvements relax from the over-estimate
    ls.update_adjacency_database(adj["node15"])
    for n in ("node11", "node14"):
        ls.update_adjacency_database(adj[n])
    db_w = warm.build_route_db(
        als,
        ps,
        changed_prefixes=set(),
        force_full=True,
        structural_delta=True,
    )
    assert norm_db(db_w) == norm_db(SpfSolver("node0").build_route_db(als, ps))
    assert warm._warm_class_builds["structural"] == 2
    assert warm.num_warm_cold_fallbacks == 0
    snap = warm.counter_snapshot()
    assert snap["decision.backend.warm_hit_ratio.structural"] == 1.0
    assert snap["decision.backend.warm_encode_slot_patches"] >= 2


# ---------------------------------------------------------------------------
# purge semantics
# ---------------------------------------------------------------------------


def test_corruption_injection_purges_warm_context():
    adj, ls, ps = make_world()
    als = {"0": ls}
    warm = make_backend(warm=True)
    warm.build_route_db(als, ps, force_full=True)
    assert warm.gen.warm_ctx is not None
    warm.inject_silent_corruption(True)
    assert warm.gen.warm_ctx is None
    assert warm.num_warm_purges == 1
    assert warm._warm_purge_reasons.get("tpu_corrupt") == 1
    # the operator's resilience status reads the same state
    status = warm.warm_status()
    assert not status["context_ready"]
    assert status["purges"] == 1
    assert status["purge_reasons"] == {"tpu_corrupt": 1}
    warm.inject_silent_corruption(False)
    # device-scoped injection purges too
    warm.build_route_db(als, ps, force_full=True)
    assert warm.gen.warm_ctx is not None
    warm.inject_silent_corruption(True, device_index=2)
    assert warm.gen.warm_ctx is None
    assert warm.num_warm_purges == 2


def test_purged_context_rebuilds_cold_then_warms_again():
    adj, ls, ps = make_world()
    als = {"0": ls}
    warm = make_backend(warm=True)
    warm.build_route_db(als, ps, force_full=True)
    warm.inject_silent_corruption(True)
    warm.inject_silent_corruption(False)
    rng = np.random.default_rng(2)
    perturb_metric(adj, ls, rng)
    db = warm.build_route_db(
        als, ps, changed_prefixes=set(), force_full=True, warm_delta=True
    )
    assert norm_db(db) == norm_db(SpfSolver("node0").build_route_db(als, ps))
    # the purged context forced this build cold...
    assert warm.num_warm_builds == 0
    assert warm._warm_fallback_reasons.get("no_context") == 1
    # ...and re-established the context: the NEXT perturbation warms
    perturb_metric(adj, ls, rng)
    db = warm.build_route_db(
        als, ps, changed_prefixes=set(), force_full=True, warm_delta=True
    )
    assert norm_db(db) == norm_db(SpfSolver("node0").build_route_db(als, ps))
    assert warm.num_warm_builds == 1


def test_purge_requests_shadow_verification():
    adj, ls, ps = make_world()
    als = {"0": ls}
    backend = TpuBackend(
        SpfSolver("node0"),
        clock=SimClock(),
        resilience=ResilienceConfig(
            shadow_sample_every=1000, jitter_pct=0.0
        ),
        warm_rebuild=True,
    )
    gov = backend.governor
    backend.build_route_db(als, ps, force_full=True)  # first build verified
    checks = gov.num_shadow_checks
    backend.build_route_db(als, ps, force_full=True)
    assert gov.num_shadow_checks == checks  # sampling interval is huge
    backend.inject_silent_corruption(True)
    backend.inject_silent_corruption(False)
    backend.build_route_db(als, ps, force_full=True)
    # the purge made the next device build verification-due
    assert gov.num_shadow_checks == checks + 1


def _swap_on_next_build(b, adj, als, ps):
    # shadow verification serves the oracle's RouteDb in place of the
    # device's: the governor reports the build as not from the device
    b.governor.after_device_build = lambda db, *a, **kw: (db, False)
    b.build_route_db(als, ps, changed_prefixes=set())


def _too_large_on_next_build(b, adj, als, ps):
    b.WARM_MAX_TABLE_BYTES = 0
    perturb_metric(adj, als["0"], np.random.default_rng(5))
    b.build_route_db(
        als, ps, changed_prefixes=set(), force_full=True, warm_delta=True
    )


def _scalar_on_next_build(b, adj, als, ps):
    b.min_device_prefixes = 10**9
    b.build_route_db(als, ps, changed_prefixes=set())


#: trigger -> (members kept, shadow check requested)
_RESETS = {
    "tpu_corrupt": (
        lambda b, *_: b.inject_silent_corruption(True),
        {"last_db", "table_synced"},
        True,
    ),
    "tpu_corrupt:dev0": (
        lambda b, *_: b.inject_silent_corruption(True, device_index=0),
        {"last_db", "table_synced"},
        True,
    ),
    "quarantine": (
        lambda b, *_: b.governor.force_quarantine(reason="test"),
        {"last_db", "table_synced"},
        True,
    ),
    "shadow_swap": (_swap_on_next_build, set(), True),
    "table_too_large": (
        _too_large_on_next_build,
        {"spf_tables", "prev_sel", "last_db", "table_synced", "ksp2"},
        True,
    ),
    "scalar_fallback": (
        _scalar_on_next_build,
        {"spf_tables", "warm_ctx", "ksp2"},
        False,
    ),
}


@pytest.mark.parametrize("trigger", sorted(_RESETS))
def test_generation_reset(trigger):
    """Each reset trigger goes through one of the generation's three
    resets and keeps exactly the members its event leaves trustworthy:
    the SPF tables, the warm context, the full-build delta base, the
    RouteDb base, the candidate table's sync mark, the KSP2 traces."""
    from openr_tpu.types import PrefixForwardingAlgorithm

    adj, ls, ps = make_world()
    ps.update_prefix(
        "node15",
        "0",
        PrefixEntry(
            "10.8.0.0/24",
            forwarding_algorithm=PrefixForwardingAlgorithm.KSP2_ED_ECMP,
        ),
    )
    als = {"0": ls}
    b = make_backend(warm=True, shadow_sample_every=1000, jitter_pct=0.0)
    for _ in range(2):
        b.build_route_db(als, ps, changed_prefixes=set(), force_full=True)
    gen = b.gen
    rec = gen.ksp2_records["0"]
    members = {
        "spf_tables": lambda: gen.spf_tables is not None,
        "warm_ctx": lambda: gen.warm_ctx is not None,
        "prev_sel": lambda: gen.prev_sel is not None,
        "last_db": lambda: gen.last_db is not None,
        "table_synced": lambda: gen.table_synced,
        "ksp2": lambda: bool(rec.dests),
    }
    assert all(alive() for alive in members.values())
    requests = "resilience.backend.shadow_check_requests"
    before = b.governor.counters.get(requests)
    trigger_fn, kept, shadow = _RESETS[trigger]
    trigger_fn(b, adj, als, ps)
    assert {m for m, alive in members.items() if alive()} == kept
    assert (b.governor.counters.get(requests) > before) == shadow
    assert b.take_full_replace() == (trigger == "shadow_swap")


# ---------------------------------------------------------------------------
# multichip: quarantine re-pack purges; warm sweep survives mid-sweep
# quarantine with parity
# ---------------------------------------------------------------------------


@pytest.mark.multichip
def test_warm_sweep_with_midsweep_chip_quarantine_and_repack():
    adj, ls, ps = make_world(side=4)
    als = {"0": ls}
    warm = TpuBackend(
        SpfSolver("node0"),
        clock=SimClock(),
        resilience=ResilienceConfig(jitter_pct=0.0),
        parallel=ParallelConfig(min_shard_rows=0),
        warm_rebuild=True,
    )
    assert warm.pool.size > 1
    warm.build_route_db(als, ps, force_full=True)
    rng = np.random.default_rng(17)
    gov = warm.governor
    for gen in range(6):
        perturb_metric(adj, ls, rng)
        if gen == 3:
            # mid-sweep chip quarantine: the health transition purges
            # the warm context (re-pack makes per-chip residency
            # suspect) and the shard plan re-packs onto survivors
            gov.force_quarantine_device(2, reason="test")
            assert warm.gen.warm_ctx is None
        db_w = warm.build_route_db(
            als, ps, changed_prefixes=set(), force_full=True,
            warm_delta=True,
        )
        db_s = SpfSolver("node0").build_route_db(als, ps)
        assert norm_db(db_w) == norm_db(db_s), f"gen {gen}"
    # warm before the quarantine, cold on the purge tick, warm after
    assert warm.num_warm_builds >= 3
    assert warm._warm_purge_reasons.get("quarantine", 0) >= 1
    assert not warm.pool.is_healthy(2)
    # the replica cache dropped the quarantined chip's residency
    assert 2 not in warm.gen.spf_replicas


# ---------------------------------------------------------------------------
# ksp2 / mpls guards on the selective path
# ---------------------------------------------------------------------------


def test_node_segment_labels_disable_selective_patch_not_warm_tables():
    adj, ls, ps = make_world()
    als = {"0": ls}
    backend = TpuBackend(
        SpfSolver("node0", enable_node_segment_label=True),
        clock=SimClock(),
        resilience=ResilienceConfig(enabled=False),
        warm_rebuild=True,
    )
    backend.build_route_db(als, ps, force_full=True)
    rng = np.random.default_rng(4)
    perturb_metric(adj, ls, rng)
    db_w = backend.build_route_db(
        als, ps, changed_prefixes=set(), force_full=True, warm_delta=True
    )
    # warm SPF tables were used, but the patch path declined (labels
    # must recompute on topology change), so no changed-set guarantee
    assert backend.num_warm_builds == 1
    assert backend.num_warm_selective_builds == 0
    assert backend.take_last_changed_prefixes() is None
    ref = SpfSolver(
        "node0", enable_node_segment_label=True
    ).build_route_db(als, ps)
    assert norm_db(db_w) == norm_db(ref)
    assert {
        k: sorted(str(n) for n in v.nexthops)
        for k, v in db_w.mpls_routes.items()
    } == {
        k: sorted(str(n) for n in v.nexthops)
        for k, v in ref.mpls_routes.items()
    }
