"""Batched multi-area fleet tables: every vantage node, every area.

Generalizes the fleet-RIB batch (ops/allroots.py was the single-area
form) to multi-area LSDBs: for each root in a batch, per-area SPF runs
with the root's PER-AREA id (-1 = the root does not participate in that
area: its whole area slice is masked unreachable, exactly the scalar
semantics of a node computing SPF only where it has adjacencies), then
the global multi-area selection chain (ops.route_select
.multi_area_select_from_tables) produces the per-root winner sets,
per-area shortest metrics and ECMP lane sets that the host-side decode
(the same code path the Decision backend uses) turns into RouteDbs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from openr_tpu.ops.spf import BIG


@functools.partial(
    jax.jit, static_argnames=("max_degree", "per_area_distance")
)
def fleet_multi_area_tables(
    src,  # [A, E]
    dst,  # [A, E]
    w,  # [A, E]
    edge_ok,  # [A, E]
    overloaded,  # [A, V]
    soft,  # [A, V]
    roots,  # [B, A] int32 — each root's id in each area, -1 = absent
    cand_area,  # [P, C]
    cand_node,  # [P, C]
    cand_ok,  # [P, C]
    drain_metric,  # [P, C]
    path_pref,  # [P, C]
    source_pref,  # [P, C]
    distance,  # [P, C]
    cand_node_in_area,  # [P, C, A]
    max_degree: int,
    per_area_distance: bool,
):
    """Returns per-root (use [B,P,C], shortest [B,P,A], lanes [B,P,A,D],
    valid [B,P,A])."""
    from openr_tpu.ops.route_select import (
        multi_area_select_from_tables,
        multi_area_spf_tables,
    )

    def one(r):  # r: [A] per-area root ids
        area_ok = r >= 0
        dist, nh = multi_area_spf_tables(
            src,
            dst,
            w,
            edge_ok,
            overloaded,
            jnp.maximum(r, 0),
            max_degree=max_degree,
        )
        # areas the root doesn't participate in contribute nothing
        dist = jnp.where(area_ok[:, None], dist, BIG)
        nh = jnp.where(area_ok[:, None, None], nh, jnp.int8(0))
        return multi_area_select_from_tables(
            dist,
            nh,
            overloaded,
            soft,
            cand_area,
            cand_node,
            cand_ok,
            drain_metric,
            path_pref,
            source_pref,
            distance,
            cand_node_in_area,
            per_area_distance=per_area_distance,
        )

    return jax.vmap(one)(roots)


@functools.partial(
    jax.jit, static_argnames=("max_degree", "per_area_distance")
)
def fleet_multi_area_tables_dense(
    in_src,  # [A, V, K] dense in-edge planes (ops/csr.py)
    in_w,  # [A, V, K]
    in_ok,  # [A, V, K]
    in_rank,  # [A, V, K]
    in_has,  # [A, V]
    overloaded,  # [A, V]
    soft,  # [A, V]
    roots,  # [B, A]
    cand_area,
    cand_node,
    cand_ok,
    drain_metric,
    path_pref,
    source_pref,
    distance,
    cand_node_in_area,
    max_degree: int,
    per_area_distance: bool,
):
    """Dense (gather-formulation) twin of :func:`fleet_multi_area_tables`
    — same outputs, no scatter in the per-root SPF fixpoints.  The
    dense in-edge planes are root-independent, so the whole vantage
    batch shares them."""
    from openr_tpu.ops.route_select import (
        multi_area_select_from_tables,
        multi_area_spf_tables_dense,
    )

    def one(r):  # r: [A] per-area root ids
        area_ok = r >= 0
        dist, nh = multi_area_spf_tables_dense(
            in_src,
            in_w,
            in_ok,
            in_rank,
            in_has,
            overloaded,
            jnp.maximum(r, 0),
            max_degree=max_degree,
        )
        dist = jnp.where(area_ok[:, None], dist, BIG)
        nh = jnp.where(area_ok[:, None, None], nh, jnp.int8(0))
        return multi_area_select_from_tables(
            dist,
            nh,
            overloaded,
            soft,
            cand_area,
            cand_node,
            cand_ok,
            drain_metric,
            path_pref,
            source_pref,
            distance,
            cand_node_in_area,
            per_area_distance=per_area_distance,
        )

    return jax.vmap(one)(roots)


@functools.partial(
    jax.jit, static_argnames=("max_degree", "per_area_distance")
)
def fleet_multi_area_tables_dense_delta(
    in_src,
    in_w,
    in_ok,
    in_rank,
    in_has,
    overloaded,
    soft,
    roots,  # [B, A]
    cand_area,
    cand_node,
    cand_ok,
    drain_metric,
    path_pref,
    source_pref,
    distance,
    cand_node_in_area,
    prev_use,  # [B, P, C] previous generation's chunk outputs
    prev_shortest,  # [B, P, A]
    prev_lanes,  # [B, P, A, D]
    prev_valid,  # [B, P, A]
    max_degree: int,
    per_area_distance: bool,
):
    """Fleet tables + on-device generation delta: solve the vantage
    chunk, diff every ROOT row against the previous generation's
    device-resident outputs, and return ``(use, shortest, lanes, valid,
    changed [B] bool)`` — the host fetches the tiny mask and then only
    the changed roots' rows (compacted), so a small perturbation's
    fleet refresh moves route deltas over the boundary instead of the
    whole [B, P] table."""
    use, shortest, lanes, valid = fleet_multi_area_tables_dense(
        in_src,
        in_w,
        in_ok,
        in_rank,
        in_has,
        overloaded,
        soft,
        roots,
        cand_area,
        cand_node,
        cand_ok,
        drain_metric,
        path_pref,
        source_pref,
        distance,
        cand_node_in_area,
        max_degree=max_degree,
        per_area_distance=per_area_distance,
    )
    changed = (
        jnp.any(use != prev_use, axis=(1, 2))
        | jnp.any(valid != prev_valid, axis=(1, 2))
        | jnp.any(shortest != prev_shortest, axis=(1, 2))
        | jnp.any(lanes != prev_lanes, axis=(1, 2, 3))
    )
    return use, shortest, lanes, valid, changed


@functools.partial(
    jax.jit, static_argnames=("max_degree", "per_area_distance")
)
def whatif_multi_area_tables(
    src,  # [A, E]
    dst,  # [A, E]
    w,  # [A, E]
    edge_ok,  # [A, E]
    link_index,  # [A, E] per-area undirected link ids (-1 pad)
    overloaded,  # [A, V]
    soft,  # [A, V]
    roots,  # [A] my id per area (me is interned into every area)
    fail_area,  # [B, S] int32 area index per failed link (-1 = none)
    fail_link,  # [B, S] int32 link id within that area
    cand_area,  # [P, C]
    cand_node,  # [P, C]
    cand_ok,  # [P, C]
    drain_metric,  # [P, C]
    path_pref,  # [P, C]
    source_pref,  # [P, C]
    distance,  # [P, C]
    cand_node_in_area,  # [P, C, A]
    max_degree: int,
    per_area_distance: bool,
):
    """Multi-area link-failure what-if from ONE vantage (me): the batch
    axis is candidate failures instead of fleet roots — per snapshot the
    failed SET of links (up to S, -1-padded; S=1 covers the single-link
    query, larger S serves simultaneous maintenance-window sets and
    parallel bundles) is masked in each member's own area, every other
    area solves unperturbed, and the GLOBAL selection chain runs per
    snapshot.  This is the multi-area generalization the operator
    what-if API needs (the reference computes any-algorithm/any-area
    what-ifs scalar via getDecisionRouteDb, Decision.cpp:342).

    Returns per-snapshot (use [B,P,C], shortest [B,P,A], lanes
    [B,P,A,D], valid [B,P,A])."""
    from openr_tpu.ops.route_select import (
        multi_area_select_from_tables,
        multi_area_spf_tables,
    )

    A = src.shape[0]

    def one(fa, fl):
        # fa, fl: [S] — OR of the S per-link masks, [A, E]
        masked = (
            (
                jnp.arange(A, dtype=jnp.int32)[None, :, None]
                == fa[:, None, None]
            )
            & (link_index[None] == fl[:, None, None])
            & (fl[:, None, None] >= 0)
        ).any(axis=0)
        dist, nh = multi_area_spf_tables(
            src,
            dst,
            w,
            edge_ok & ~masked,
            overloaded,
            roots,
            max_degree=max_degree,
        )
        return multi_area_select_from_tables(
            dist,
            nh,
            overloaded,
            soft,
            cand_area,
            cand_node,
            cand_ok,
            drain_metric,
            path_pref,
            source_pref,
            distance,
            cand_node_in_area,
            per_area_distance=per_area_distance,
        )

    return jax.vmap(one)(fail_area, fail_link)


_sharded_cache: dict = {}


def sharded_fleet_tables(
    mesh, max_degree: int, per_area_distance: bool, dense: bool = False
):
    """Root-batch-sharded fleet kernel over a device mesh.

    Vantage roots are independent solves, so each device runs the exact
    single-device program on its contiguous root shard (no collectives);
    topology + candidate tables replicate.  Root batches must be
    multiples of the mesh size.  Bit-identical to the unsharded kernel.

    Called as ``fn(roots, *topology, *candidate_tables)``: the topology
    is ``(src, dst, w, edge_ok, overloaded, soft)``, or with ``dense``
    the in-edge planes ``(in_src, in_w, in_ok, in_rank, in_has,
    overloaded, soft)`` of :func:`fleet_multi_area_tables_dense`.
    """
    import functools

    from jax.sharding import PartitionSpec as P

    from openr_tpu.parallel.mesh import BATCH_AXIS

    key = (mesh, max_degree, per_area_distance, dense)
    if key in _sharded_cache:
        return _sharded_cache[key]
    rep = P()
    bat = P(BATCH_AXIS)
    kernel = fleet_multi_area_tables_dense if dense else fleet_multi_area_tables
    body = functools.partial(
        kernel.__wrapped__,
        max_degree=max_degree,
        per_area_distance=per_area_distance,
    )
    n_topo = 7 if dense else 6

    def wrapped(roots, *tables):
        return body(*tables[:n_topo], roots, *tables[n_topo:])

    fn = jax.jit(
        jax.shard_map(
            wrapped,
            mesh=mesh,
            in_specs=(bat, *([rep] * (n_topo + 8))),
            out_specs=(
                P(BATCH_AXIS, None, None),  # use [B, P, C]
                P(BATCH_AXIS, None, None),  # shortest [B, P, A]
                P(BATCH_AXIS, None, None, None),  # lanes [B, P, A, D]
                P(BATCH_AXIS, None, None),  # valid [B, P, A]
            ),
            check_vma=False,
        )
    )
    _sharded_cache[key] = fn
    return fn


_sharded_whatif_cache: dict = {}


def sharded_whatif_tables(mesh, max_degree: int, per_area_distance: bool):
    """Failure-batch-sharded multi-area what-if kernel over a device
    mesh: each failure snapshot (a SET of masked links) is an
    independent solve, so the batch axis shards with no collectives —
    topology, candidate tables and link maps replicate.  The failure
    bucket must be a multiple of the mesh size.  Bit-identical to
    ``whatif_multi_area_tables``."""
    import functools

    from jax.sharding import PartitionSpec as P

    from openr_tpu.parallel.mesh import BATCH_AXIS

    key = (mesh, max_degree, per_area_distance)
    if key in _sharded_whatif_cache:
        return _sharded_whatif_cache[key]
    rep = P()
    bat = P(BATCH_AXIS)
    body = functools.partial(
        whatif_multi_area_tables.__wrapped__,
        max_degree=max_degree,
        per_area_distance=per_area_distance,
    )
    fn = jax.jit(
        jax.shard_map(
            body,
            mesh=mesh,
            # src dst w edge_ok link_index overloaded soft roots |
            # fail_area fail_link | 8 candidate tables
            in_specs=(*([rep] * 8), P(BATCH_AXIS, None), P(BATCH_AXIS, None),
                      *([rep] * 8)),
            out_specs=(
                P(BATCH_AXIS, None, None),  # use [B, P, C]
                P(BATCH_AXIS, None, None),  # shortest [B, P, A]
                P(BATCH_AXIS, None, None, None),  # lanes [B, P, A, D]
                P(BATCH_AXIS, None, None),  # valid [B, P, A]
            ),
            check_vma=False,
        )
    )
    _sharded_whatif_cache[key] = fn
    return fn
