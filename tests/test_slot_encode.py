"""Slot-stable structural encode (ISSUE 12): tombstone/free-list
mechanics, decline reasons, dense-reduction exclusion of tombstoned
rows, the per-platform kernel preference hook, and the load-bearing
property end to end — a structural warm rebuild's RouteDb is
BIT-IDENTICAL to both the cold device build and the scalar oracle over
a seeded join/leave churn sweep."""

import numpy as np
import pytest

from openr_tpu.common.runtime import SimClock
from openr_tpu.config import ResilienceConfig
from openr_tpu.decision.backend import TpuBackend
from openr_tpu.decision.link_state import LinkState
from openr_tpu.decision.prefix_state import PrefixState
from openr_tpu.decision.spf_solver import SpfSolver
from openr_tpu.emulation.topology import build_adj_dbs, grid_edges
from openr_tpu.ops.csr import (
    encode_link_state,
    patch_encoded_multi_area_slots,
    patch_encoded_topology_slots,
)
from openr_tpu.types import PrefixEntry


def make_world(side=4, seed_prefix="10.8"):
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


def make_backend(**kw):
    kw.setdefault("warm_rebuild", True)
    return TpuBackend(
        SpfSolver("node0"),
        clock=SimClock(),
        resilience=ResilienceConfig(enabled=False),
        **kw,
    )


def norm_db(db):
    return {
        p: (
            sorted((nh.neighbor_node_name, nh.metric) for nh in e.nexthops),
            float(e.igp_cost),
        )
        for p, e in db.unicast_routes.items()
    }


def solve_dense(topo, root_name):
    """Cold dense-kernel (dist, lanes) for one encoding."""
    import jax.numpy as jnp

    from openr_tpu.ops.spf import dense_spf_one

    root = topo.node_id(root_name)
    dist, nh = dense_spf_one(
        jnp.asarray(topo.in_src),
        jnp.asarray(topo.in_w),
        jnp.asarray(topo.in_ok),
        jnp.asarray(topo.in_rank),
        jnp.asarray(topo.in_has),
        jnp.asarray(topo.overloaded),
        jnp.int32(root),
        max_degree=8,
    )
    return np.asarray(dist), np.asarray(nh)


# ---------------------------------------------------------------------------
# slot patch mechanics
# ---------------------------------------------------------------------------


def test_leave_tombstones_in_place_layout_shared():
    adj, ls, _ps = make_world()
    old = encode_link_state(ls)
    ls.delete_adjacency_database("node15")
    patched, reason = patch_encoded_topology_slots(old, ls, "node0")
    assert reason is None
    # layout arrays are the SAME OBJECTS — the O(touched links) contract
    assert patched.src is old.src
    assert patched.dst is old.dst
    assert patched.link_index is old.link_index
    assert patched.link_edge_pos is old.link_edge_pos
    assert patched.in_src is old.in_src
    assert patched.in_rank is old.in_rank
    assert patched.in_edge_pos is old.in_edge_pos
    assert patched.node_ids is old.node_ids  # no renames: symbols shared
    assert patched.tombstoned_nodes == frozenset({"node15"})
    # node15 was a corner: both its links' rows are invalidated
    assert len(patched.tombstoned_links) == 2
    nid = old.node_id("node15")
    for li in patched.tombstoned_links:
        for e in old.link_edge_pos[li]:
            assert not patched.edge_ok[e]
            assert patched.w[e] == np.float32(np.inf)
    assert patched.slot_changed[nid]


def test_rejoin_revives_rows_and_matches_original():
    adj, ls, _ps = make_world()
    old = encode_link_state(ls)
    ls.delete_adjacency_database("node15")
    left, _ = patch_encoded_topology_slots(old, ls, "node0")
    # rejoin: identical adjacencies re-advertised
    ls.update_adjacency_database(adj["node15"])
    for n in ("node11", "node14"):
        ls.update_adjacency_database(adj[n])
    back, reason = patch_encoded_topology_slots(left, ls, "node0")
    assert reason is None
    assert back.tombstoned_nodes == frozenset()
    assert back.tombstoned_links == frozenset()
    # revived planes are value-identical to the original encoding
    np.testing.assert_array_equal(back.w, old.w)
    np.testing.assert_array_equal(back.edge_ok, old.edge_ok)
    np.testing.assert_array_equal(back.in_w, old.in_w)
    np.testing.assert_array_equal(back.in_ok, old.in_ok)
    assert back.src is old.src  # still the original layout objects


def test_slot_exhaustion_and_new_link_decline():
    adj, ls, _ps = make_world()
    old = encode_link_state(ls)
    # a brand-new node with no tombstoned slot to reclaim
    from openr_tpu.types import Adjacency, AdjacencyDatabase

    fresh = AdjacencyDatabase(
        "nodeX",
        area="0",
        adjacencies=[
            Adjacency(
                other_node_name="node0",
                if_name="if_x_0",
                other_if_name="if_0_x",
                metric=1,
            )
        ],
    )
    db0 = adj["node0"]
    db0.adjacencies.append(
        Adjacency(
            other_node_name="nodeX",
            if_name="if_0_x",
            other_if_name="if_x_0",
            metric=1,
        )
    )
    ls.update_adjacency_database(fresh)
    ls.update_adjacency_database(db0)
    enc, reason = patch_encoded_topology_slots(old, ls, "node0")
    assert enc is None and reason == "slot_exhaustion"
    # with a free (tombstoned) slot the name is admitted, but its link
    # joins a pair no tombstoned row serves -> new_link decline
    ls2 = LinkState("0", "node0")
    for db in build_adj_dbs(grid_edges(4)).values():
        ls2.update_adjacency_database(db)
    old2 = encode_link_state(ls2)
    ls2.delete_adjacency_database("node15")
    left2, _ = patch_encoded_topology_slots(old2, ls2, "node0")
    fresh2 = AdjacencyDatabase(
        "nodeY",
        area="0",
        adjacencies=[
            Adjacency(
                other_node_name="node0",
                if_name="if_y_0",
                other_if_name="if_0_y",
                metric=1,
            )
        ],
    )
    db0b = build_adj_dbs(grid_edges(4))["node0"]
    db0b.adjacencies.append(
        Adjacency(
            other_node_name="nodeY",
            if_name="if_0_y",
            other_if_name="if_y_0",
            metric=1,
        )
    )
    ls2.update_adjacency_database(fresh2)
    ls2.update_adjacency_database(db0b)
    enc2, reason2 = patch_encoded_topology_slots(left2, ls2, "node0")
    assert enc2 is None and reason2 == "new_link"


def test_replacement_node_reclaims_slot_and_rows():
    """The autoscaling-replacement pattern: node15 dies forever, a NEW
    name joins with the same physical neighbors — it reclaims node15's
    tombstoned slot and its links reclaim the retained rows."""
    adj, ls, _ps = make_world()
    old = encode_link_state(ls)
    slot15 = old.node_id("node15")
    ls.delete_adjacency_database("node15")
    left, _ = patch_encoded_topology_slots(old, ls, "node0")
    from openr_tpu.types import Adjacency, AdjacencyDatabase

    # node15's grid neighbors are node11 and node14: the replacement
    # advertises the same two adjacencies under a new name
    repl = AdjacencyDatabase(
        "node99",
        area="0",
        adjacencies=[
            Adjacency(
                other_node_name=n,
                if_name=f"if_99_{n}",
                other_if_name=f"if_{n}_99",
                metric=1,
            )
            for n in ("node11", "node14")
        ],
    )
    for n in ("node11", "node14"):
        db = adj[n]
        db.adjacencies = [
            a for a in db.adjacencies if a.other_node_name != "node15"
        ] + [
            Adjacency(
                other_node_name="node99",
                if_name=f"if_{n}_99",
                other_if_name=f"if_99_{n}",
                metric=1,
            )
        ]
        ls.update_adjacency_database(db)
    ls.update_adjacency_database(repl)
    enc, reason = patch_encoded_topology_slots(left, ls, "node0")
    assert reason is None
    assert enc.node_id("node99") == slot15
    assert "node15" not in enc.node_ids
    assert enc.tombstoned_nodes == frozenset()
    assert enc.slot_changed[slot15]
    assert enc.src is old.src  # layout survived the rename
    # the reclaimed rows carry the replacement's links
    dist_p, nh_p = solve_dense(enc, "node0")
    fresh = encode_link_state(
        ls,
        node_bucket=enc.padded_nodes,
        edge_bucket=enc.padded_edges,
        extra_nodes=("node0",),
    )
    dist_f, _ = solve_dense(fresh, "node0")
    for name in fresh.node_ids:
        assert (
            dist_p[enc.node_id(name)] == dist_f[fresh.node_id(name)]
        ), name


def test_tombstoned_rows_excluded_from_dense_reductions():
    """A tombstoned node's rows read in_ok=False / in_w=INF: the dense
    kernels must produce, at every surviving slot, exactly the fresh
    re-encode's answer — and BIG (unreachable) at the tombstone."""
    from openr_tpu.ops.consts import BIG

    adj, ls, _ps = make_world()
    old = encode_link_state(ls)
    ls.delete_adjacency_database("node5")  # interior node: 4 links
    patched, reason = patch_encoded_topology_slots(old, ls, "node0")
    assert reason is None
    assert len(patched.tombstoned_links) == 4
    dist_p, _ = solve_dense(patched, "node0")
    fresh = encode_link_state(
        ls,
        node_bucket=old.padded_nodes,
        edge_bucket=old.padded_edges,
        extra_nodes=("node0",),
    )
    dist_f, _ = solve_dense(fresh, "node0")
    for name in fresh.node_ids:
        assert (
            dist_p[patched.node_id(name)] == dist_f[fresh.node_id(name)]
        ), name
    assert dist_p[patched.node_id("node5")] == np.float32(BIG)


def test_multi_area_slot_patch_kinds():
    adj, ls, _ps = make_world()
    from openr_tpu.ops.csr import encode_multi_area

    als = {"0": ls}
    prev = encode_multi_area(als, "node0")
    # pure weight churn -> "patch"
    db = adj["node3"]
    db.adjacencies[0].metric = 5
    ls.update_adjacency_database(db)
    enc, kind, reason = patch_encoded_multi_area_slots(prev, als, "node0")
    assert enc is not None and kind == "patch" and reason is None
    # membership churn -> "slot"
    ls.delete_adjacency_database("node15")
    enc2, kind2, reason2 = patch_encoded_multi_area_slots(
        enc, als, "node0"
    )
    assert enc2 is not None and kind2 == "slot" and reason2 is None
    # area membership change -> cold decline with the counted reason
    ls_b = LinkState("b", "node0")
    enc3, kind3, reason3 = patch_encoded_multi_area_slots(
        enc2, {"0": ls, "b": ls_b}, "node0"
    )
    assert enc3 is None and kind3 == "cold" and reason3 == "area_change"


# ---------------------------------------------------------------------------
# backend end to end: seeded membership churn, warm vs cold vs scalar
# ---------------------------------------------------------------------------


def test_seeded_membership_churn_warm_cold_scalar_parity():
    """The ISSUE-12 acceptance property live: over a seeded sweep of
    leaves, rejoins and weight perturbations, every structural warm
    rebuild is bit-parity with a cold device backend AND the scalar
    oracle — and the warm path actually engaged (slot patches +
    structural warm hits, zero fallbacks)."""
    adj, ls, ps = make_world(side=4)
    als = {"0": ls}
    warm = make_backend()
    cold = make_backend(warm_rebuild=False)
    oracle = SpfSolver("node0")
    warm.build_route_db(als, ps, force_full=True)
    cold.build_route_db(als, ps, force_full=True)

    rng = np.random.default_rng(12)
    bounceable = [f"node{i}" for i in range(1, 16)]
    down = []
    for step in range(14):
        op = int(rng.integers(3))
        structural = False
        if op == 0 and len(down) < 3:
            victim = bounceable[int(rng.integers(len(bounceable)))]
            if victim not in down and ls.has_node(victim):
                ls.delete_adjacency_database(victim)
                down.append(victim)
                structural = True
        elif op == 1 and down:
            back = down.pop(0)
            ls.update_adjacency_database(adj[back])
            for other in adj:
                if other != back and ls.has_node(other):
                    ls.update_adjacency_database(adj[other])
            structural = True
        else:
            alive = sorted(set(adj) - set(down))
            victim = alive[int(rng.integers(len(alive)))]
            db = adj[victim]
            a = db.adjacencies[int(rng.integers(len(db.adjacencies)))]
            a.metric = 1 + (a.metric % 3)
            ls.update_adjacency_database(db)
        db_w = warm.build_route_db(
            als,
            ps,
            changed_prefixes=set(),
            force_full=True,
            warm_delta=not structural,
            structural_delta=structural,
        )
        db_c = cold.build_route_db(als, ps, force_full=True)
        db_s = oracle.build_route_db(als, ps)
        assert norm_db(db_w) == norm_db(db_c) == norm_db(db_s), (
            f"generation {step} diverged"
        )
    assert warm._warm_class_builds["structural"] >= 4
    assert warm.num_warm_cold_fallbacks == 0
    assert warm.num_encode_slot_patches >= 4


def test_structural_selective_patch_object_identity():
    """A structural warm tick far from a prefix's advertiser must patch
    that prefix's RouteDb entry through OBJECT-IDENTICALLY — the
    selective-selection path proves it re-selected only the affected
    region."""
    adj, ls, ps = make_world(side=4)
    als = {"0": ls}
    warm = make_backend()
    db0 = warm.build_route_db(als, ps, force_full=True)
    # node15 (far corner) leaves; node1's prefix routes via node0's
    # immediate neighborhood and cannot be affected
    ls.delete_adjacency_database("node15")
    db1 = warm.build_route_db(
        als,
        ps,
        changed_prefixes=set(),
        force_full=True,
        structural_delta=True,
    )
    assert warm._warm_class_builds["structural"] == 1
    changed = warm.take_last_changed_prefixes()
    assert changed is not None
    assert "10.8.1.0/24" not in changed
    assert (
        db1.unicast_routes["10.8.1.0/24"]
        is db0.unicast_routes["10.8.1.0/24"]
    )
    # the departed node's own prefix is gone
    assert "10.8.15.0/24" not in db1.unicast_routes or (
        db1.unicast_routes.get("10.8.15.0/24") is None
    )


def test_purge_on_suspicion_still_forces_cold_after_structural():
    """PR-5/9 purge semantics survive ISSUE 12: corruption injection
    after structural warm builds purges the context, the next build is
    cold + shadow-verified, and a later structural tick re-warms."""
    adj, ls, ps = make_world()
    als = {"0": ls}
    warm = make_backend()
    warm.build_route_db(als, ps, force_full=True)
    ls.delete_adjacency_database("node15")
    warm.build_route_db(
        als, ps, changed_prefixes=set(), force_full=True,
        structural_delta=True,
    )
    assert warm._warm_class_builds["structural"] == 1
    warm.inject_silent_corruption(True)
    assert warm.gen.warm_ctx is None
    warm.inject_silent_corruption(False)
    ls.update_adjacency_database(adj["node15"])
    for n in ("node11", "node14"):
        ls.update_adjacency_database(adj[n])
    db = warm.build_route_db(
        als, ps, changed_prefixes=set(), force_full=True,
        structural_delta=True,
    )
    # purged context: this structural tick fell back cold (counted)...
    assert warm._warm_class_fallbacks["structural"] == 1
    assert (
        warm._warm_class_fallback_reasons["structural"].get("no_context")
        == 1
    )
    assert norm_db(db) == norm_db(SpfSolver("node0").build_route_db(als, ps))
    # ...and re-established it: the next leave warms again
    ls.delete_adjacency_database("node12")
    db = warm.build_route_db(
        als, ps, changed_prefixes=set(), force_full=True,
        structural_delta=True,
    )
    assert warm._warm_class_builds["structural"] == 2
    assert norm_db(db) == norm_db(SpfSolver("node0").build_route_db(als, ps))


# ---------------------------------------------------------------------------
# per-platform kernel preference hook
# ---------------------------------------------------------------------------


def test_kernel_preference_hook_bit_parity():
    """The ROADMAP policy hook: forcing the segment path on this
    platform must produce the identical RouteDb (both kernel families
    are kept bit-parity); the default preference stays dense."""
    adj, ls, ps = make_world()
    als = {"0": ls}
    dense_be = make_backend()
    assert dense_be._spf_kernel_preference() == "dense"
    db_dense = dense_be.build_route_db(als, ps, force_full=True)
    seg_be = make_backend()
    seg_be.KERNEL_PREFERENCE = {"default": "segment"}
    assert seg_be._spf_kernel_preference() == "segment"
    db_seg = seg_be.build_route_db(als, ps, force_full=True)
    assert norm_db(db_dense) == norm_db(db_seg)
    # and the segment preference keeps full parity across a structural
    # warm tick too (the warm kernels are segment-based either way)
    for be, flag in ((dense_be, "dense"), (seg_be, "segment")):
        pass
    ls.delete_adjacency_database("node15")
    db_d2 = dense_be.build_route_db(
        als, ps, changed_prefixes=set(), force_full=True,
        structural_delta=True,
    )
    db_s2 = seg_be.build_route_db(
        als, ps, changed_prefixes=set(), force_full=True,
        structural_delta=True,
    )
    assert norm_db(db_d2) == norm_db(db_s2)
