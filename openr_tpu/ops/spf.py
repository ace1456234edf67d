"""Batched SPF kernels — the TPU replacement for LinkState::runSpf.

Heap Dijkstra doesn't vectorize, so shortest paths are computed as a masked
Bellman-Ford fixed point over the directed edge list (jnp.segment_min per
relaxation round), followed by a shortest-path-DAG fixed point that
propagates first-hop ("nexthop lane") sets as boolean matrices — the
device analogue of NodeSpfResult.nextHops (LinkState.h:290-345).

Reference-parity rules implemented on device:
  * node hard-drain: an overloaded node receives traffic but never relaxes
    its out-edges, except when it is the SPF root (LinkState.cpp:739-752)
  * interface hard-drain / down links: excluded via `edge_ok`
  * soft-drain max-directional-metric is already folded into `w` by the
    encoder (LinkState.cpp:789)
  * hop-count mode (useLinkMetric=false): pass `w = 1` weights
  * all-shortest-paths: a nexthop lane r corresponds to the r-th out-edge
    of the root; lane sets propagate along DAG edges with OR (segment_max
    over int8), seeded at the root's direct successors

Everything is shape-static and jit/vmap/shard_map-friendly: batches of
topologies vmap over the leading axis; what-if sweeps reuse one edge list
with a per-snapshot `edge_enabled` mask.

LAYOUT INVARIANT: the edge arrays MUST be sorted by `dst`
(encode_link_state guarantees this).  The segment reductions run with
``indices_are_sorted=True`` — on TPU that compiles to contiguous
reductions instead of general scatter (measured 3.6x end-to-end on the
1024-node what-if sweep) but silently computes garbage on unsorted input.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

# effectively-infinite distance, f32-safe; a plain float (ops.consts)
# so importing it never initializes a device backend
from openr_tpu.ops.consts import BIG


def _can_transit(overloaded: jnp.ndarray, root: jnp.ndarray) -> jnp.ndarray:
    """[V] bool: which nodes may relax their out-edges."""
    v = overloaded.shape[0]
    return (~overloaded) | (jnp.arange(v, dtype=jnp.int32) == root)


def spf_distances(
    src: jnp.ndarray,  # [E] int32
    dst: jnp.ndarray,  # [E] int32
    w: jnp.ndarray,  # [E] float32 (INF/BIG for down/pad edges)
    edge_ok: jnp.ndarray,  # [E] bool
    overloaded: jnp.ndarray,  # [V] bool
    root: jnp.ndarray,  # scalar int32
    max_iters: Optional[int] = None,
) -> jnp.ndarray:
    """Single-source shortest distances, one topology.  Returns [V] f32
    with BIG for unreachable nodes.  vmap over the leading axis for
    batches."""
    V = overloaded.shape[0]
    w = jnp.where(edge_ok, w, BIG).astype(jnp.float32)
    dist0 = jnp.full((V,), BIG, jnp.float32).at[root].set(0.0)
    transit = _can_transit(overloaded, root)
    src_ok = transit[src] & edge_ok
    limit = jnp.int32(max_iters if max_iters is not None else V)

    def cond(state):
        _, changed, i = state
        return changed & (i < limit)

    def body(state):
        d, _, i = state
        cand = jnp.where(src_ok, d[src] + w, BIG)
        best_in = jax.ops.segment_min(
            cand, dst, num_segments=V, indices_are_sorted=True
        )
        nd = jnp.minimum(d, best_in)
        return nd, jnp.any(nd < d), i + 1

    dist, _, _ = jax.lax.while_loop(cond, body, (dist0, jnp.bool_(True), jnp.int32(0)))
    return dist


def shortest_path_dag(
    src: jnp.ndarray,
    dst: jnp.ndarray,
    w: jnp.ndarray,
    edge_ok: jnp.ndarray,
    overloaded: jnp.ndarray,
    root: jnp.ndarray,
    dist: jnp.ndarray,  # [V] from spf_distances
) -> jnp.ndarray:
    """[E] bool: directed edges on some shortest path from root."""
    transit = _can_transit(overloaded, root)
    reached = dist[dst] < BIG
    return (
        edge_ok
        & transit[src]
        & reached
        & (dist[src] + jnp.where(edge_ok, w, BIG) == dist[dst])
    )


def spf_nexthop_lanes(
    src: jnp.ndarray,
    dst: jnp.ndarray,
    w: jnp.ndarray,
    edge_ok: jnp.ndarray,
    overloaded: jnp.ndarray,
    root: jnp.ndarray,
    dist: jnp.ndarray,
    max_degree: int,
    max_iters: Optional[int] = None,
) -> jnp.ndarray:
    """All-shortest-paths first-hop sets as [V, D] int8 (0/1).

    Lane r == the r-th directed out-edge of `root` in edge order (decode
    with EncodedTopology.root_out_edges).  nh[v][r] == 1 iff some shortest
    path root→v leaves root over that edge.
    """
    V = overloaded.shape[0]
    E = src.shape[0]
    D = max_degree
    sp_edge = shortest_path_dag(src, dst, w, edge_ok, overloaded, root, dist)
    is_root_out = src == root
    # stable lane per root-out edge: rank among root-out edges in edge order
    rank = jnp.cumsum(is_root_out.astype(jnp.int32)) - 1  # [E]
    lanes = jnp.arange(D, dtype=jnp.int32)[None, :]  # [1, D]
    seed = (is_root_out[:, None] & (rank[:, None] == lanes)).astype(jnp.int8)
    limit = jnp.int32(max_iters if max_iters is not None else V)

    # root-out contributions never change across iterations: fold them into
    # the initial state once, and propagate only over non-root DAG edges —
    # saves one loop iteration and an [E, D] select per iteration
    seed_mask = (sp_edge & is_root_out)[:, None].astype(jnp.int8)
    nh0 = jax.ops.segment_max(
        seed * seed_mask, dst, num_segments=V, indices_are_sorted=True
    )
    prop_mask = (sp_edge & ~is_root_out)[:, None].astype(jnp.int8)  # [E, 1]

    def cond(state):
        _, changed, i = state
        return changed & (i < limit)

    def body(state):
        nh, _, i = state
        contrib = nh[src] * prop_mask
        new = jax.ops.segment_max(
            contrib, dst, num_segments=V, indices_are_sorted=True
        )
        new = jnp.maximum(new, nh)
        return new, jnp.any(new != nh), i + 1

    nh, _, _ = jax.lax.while_loop(cond, body, (nh0, jnp.bool_(True), jnp.int32(0)))
    return nh


def spf_one(
    src,
    dst,
    w,
    edge_ok,
    overloaded,
    root,
    max_degree: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(dist [V], nexthop lanes [V, D]) for one topology + root."""
    dist = spf_distances(src, dst, w, edge_ok, overloaded, root)
    nh = spf_nexthop_lanes(
        src, dst, w, edge_ok, overloaded, root, dist, max_degree
    )
    return dist, nh


@functools.partial(jax.jit, static_argnames=("max_degree",))
def batched_spf_link_failures(
    src,  # [E]
    dst,  # [E]
    w,  # [E]
    edge_ok,  # [E]
    link_index,  # [E] undirected link id per directed edge (-1 pad)
    failed_link,  # [B] int32 failed undirected link id per snapshot (-1 none)
    overloaded,  # [B, V]
    roots,  # [B]
    max_degree: int,
):
    """Single-link-failure what-if sweep with the perturbation expanded ON
    DEVICE: the host ships one int32 per snapshot instead of a [B, E] mask,
    eliminating the host→device bandwidth bottleneck on big sweeps."""

    def one(fail, ovl, root):
        enabled = link_index != fail
        return spf_one(src, dst, w, edge_ok & enabled, ovl, root, max_degree)

    return jax.vmap(one)(failed_link, overloaded, roots)


@functools.partial(jax.jit, static_argnames=("max_degree",))
def batched_spf(
    src,  # [E] shared edge list
    dst,  # [E]
    w,  # [E]
    edge_ok,  # [E] static validity (padding, permanently-down links)
    edge_enabled,  # [B, E] per-snapshot what-if mask
    overloaded,  # [B, V] per-snapshot hard-drain bits
    roots,  # [B] int32 SPF roots
    max_degree: int,
):
    """The what-if sweep kernel: B topology snapshots (shared edge list,
    per-snapshot edge/overload perturbations + roots) solved in parallel.

    Returns (dist [B, V], nh [B, V, D]).
    """

    def one(edge_en, ovl, root):
        return spf_one(
            src, dst, w, edge_ok & edge_en, ovl, root, max_degree
        )

    return jax.vmap(one)(edge_enabled, overloaded, roots)


@functools.partial(jax.jit, static_argnames=("num_edges",))
def link_ignore_masks(
    link_edge_pos,  # [L, 2] directed-edge positions of each link
    ignore_ids,  # [B, K] ignored undirected link ids, -1 pad
    num_edges: int,
):
    """[B, E] edge-enable masks built on the device: both directed edges
    of every listed link are off — the same mask ``csr.link_failure_batch``
    builds on the host, from a [B, K] id matrix instead of [B, E] bytes
    (the KSP2 re-solve batch's input)."""
    valid = ignore_ids >= 0
    pos = link_edge_pos[jnp.where(valid, ignore_ids, 0)]  # [B, K, 2]
    # pads point past the edge axis, where mode="drop" discards them
    pos = jnp.where(valid[..., None], pos, num_edges)
    rows = jnp.arange(ignore_ids.shape[0])[:, None, None]
    return (
        jnp.ones((ignore_ids.shape[0], num_edges), bool)
        .at[rows, pos]
        .set(False, mode="drop")
    )


@jax.jit
def batched_spf_distances_masked(
    src,  # [E] shared edge list
    dst,  # [E]
    w,  # [E]
    edge_ok,  # [E]
    edge_enabled,  # [B, E] per-snapshot mask
    overloaded,  # [V] shared hard-drain bits
    roots,  # [B]
):
    """Distances-only what-if batch (no nexthop-lane propagation) — the
    KSP2 masked re-solve fan-out (LinkState.cpp:675-699: run SPF ignoring
    links used by paths 1..k-1, one masked solve per destination).  The
    host traces the actual k-th paths from these distance fields."""

    def one(edge_en, root):
        return spf_distances(src, dst, w, edge_ok & edge_en, overloaded, root)

    return jax.vmap(one)(edge_enabled, roots)


@functools.partial(jax.jit, static_argnames=("max_degree",))
def batched_spf_distinct(
    src,  # [B, E] per-snapshot edge lists
    dst,  # [B, E]
    w,  # [B, E]
    edge_ok,  # [B, E]
    overloaded,  # [B, V]
    roots,  # [B]
    max_degree: int,
):
    """Fully distinct topologies per snapshot (different graphs padded to a
    common bucket)."""

    def one(s, d, ww, eo, ovl, root):
        return spf_one(s, d, ww, eo, ovl, root, max_degree)

    return jax.vmap(one)(src, dst, w, edge_ok, overloaded, roots)


def hop_count_weights(w: jnp.ndarray) -> jnp.ndarray:
    """useLinkMetric=false mode: every edge costs 1 (LinkState.cpp:789)."""
    return jnp.ones_like(w)


# ---------------------------------------------------------------------------
# Dense in-edge (gather) kernels
# ---------------------------------------------------------------------------
# The segment-reduction fixpoints above lower to general scatter on host
# platforms, where each relaxation round costs a serial pass over the
# edge list — BENCH_PIPELINE_r01 measured the two loops at ~95% of a
# grid4096 cold rebuild (~505ms hiding inside the device_get barrier).
# The dense formulation consumes the encoder's [V, K] in-edge matrix
# (ops/csr.py build_in_edge_matrix): the relax step is a pure gather
# ``d[in_src] + in_w`` plus a dense min over K, and lane propagation is a
# gather + dense max — no scatter anywhere, vectorizing cleanly on CPU
# and mapping to plain gather/reduce ops on TPU.  Both loops compute the
# same fixed points as their segment twins (bit-parity enforced by
# tests/test_stream_delta.py) and unroll DENSE_UNROLL rounds per
# while_loop iteration to amortize loop-carry overhead — extra rounds
# past the fixed point are exact no-ops.

#: relaxation rounds per while_loop iteration in the dense kernels
DENSE_UNROLL = 8


def dense_spf_distances(
    in_src,  # [V, K] int32 in-edge sources (0 on padding slots)
    in_w,  # [V, K] f32 (INF on padding/down slots)
    in_ok,  # [V, K] bool
    overloaded,  # [V] bool
    root,  # scalar int32
    max_iters: Optional[int] = None,
) -> jnp.ndarray:
    """Single-source shortest distances over the dense in-edge matrix.
    Returns [V] f32 with BIG for unreachable nodes — bit-identical to
    :func:`spf_distances` (same relaxation equations, integral metrics
    keep every f32 path sum exact)."""
    V = in_src.shape[0]
    transit = _can_transit(overloaded, root)
    ok = in_ok & transit[in_src]
    ww = jnp.where(ok, in_w, BIG).astype(jnp.float32)
    dist0 = jnp.full((V,), BIG, jnp.float32).at[root].set(0.0)
    limit = jnp.int32(max_iters if max_iters is not None else V)

    def relax(d):
        cand = jnp.min(d[in_src] + ww, axis=1)
        return jnp.minimum(d, cand)

    def cond(state):
        _, changed, i = state
        return changed & (i < limit)

    def body(state):
        d, _, i = state
        nd = d
        for _ in range(DENSE_UNROLL):
            nd = relax(nd)
        return nd, jnp.any(nd < d), i + DENSE_UNROLL

    dist, _, _ = jax.lax.while_loop(
        cond, body, (dist0, jnp.bool_(True), jnp.int32(0))
    )
    return dist


def dense_spf_nexthop_lanes(
    in_src,  # [V, K]
    in_w,  # [V, K]
    in_ok,  # [V, K]
    in_rank,  # [V, K] int32 out-edge rank of the in-edge (-1 = none)
    in_has,  # [V] bool — v appears in the padded edge list's dst[] at all
    overloaded,  # [V]
    root,
    dist,  # [V] from dense_spf_distances
    max_degree: int,
    max_iters: Optional[int] = None,
) -> jnp.ndarray:
    """All-shortest-paths first-hop sets as [V, D] int8 over the dense
    in-edge matrix — BIT-IDENTICAL to :func:`spf_nexthop_lanes`,
    including the int8-min fill the segment reduction leaves on dsts
    absent from the edge list (``in_has`` masks them), so warm contexts
    seeded from either formulation interchange freely.  ``in_rank`` is
    root-independent (rank among same-src edges in edge order), so the
    seed for any root is just ``in_src == root``."""
    V, _K = in_src.shape
    D = max_degree
    transit = _can_transit(overloaded, root)
    ok = in_ok & transit[in_src]
    ww = jnp.where(ok, in_w, BIG)
    # on-DAG in-edges: reached dst whose distance equals src dist + w
    sp = ok & (dist[in_src] + ww == dist[:, None]) & (dist[:, None] < BIG)
    is_root = in_src == root
    lanes = jnp.arange(D, dtype=jnp.int32)[None, None, :]
    seed = (
        (sp & is_root)[:, :, None] & (in_rank[:, :, None] == lanes)
    ).astype(jnp.int8)
    empty = jnp.full((V, D), jnp.iinfo(jnp.int8).min, jnp.int8)
    nh0 = jnp.where(in_has[:, None], jnp.max(seed, axis=1), empty)
    prop = (sp & ~is_root)[:, :, None].astype(jnp.int8)  # [V, K, 1]
    limit = jnp.int32(max_iters if max_iters is not None else V)

    def relax(nh):
        contrib = jnp.max(nh[in_src] * prop, axis=1)
        return jnp.where(
            in_has[:, None], jnp.maximum(nh, contrib), nh
        )

    def cond(state):
        _, changed, i = state
        return changed & (i < limit)

    def body(state):
        nh, _, i = state
        new = nh
        for _ in range(DENSE_UNROLL):
            new = relax(new)
        return new, jnp.any(new != nh), i + DENSE_UNROLL

    nh, _, _ = jax.lax.while_loop(
        cond, body, (nh0, jnp.bool_(True), jnp.int32(0))
    )
    return nh


def dense_spf_one(
    in_src,
    in_w,
    in_ok,
    in_rank,
    in_has,
    overloaded,
    root,
    max_degree: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(dist [V], nexthop lanes [V, D]) over the dense in-edge matrix."""
    dist = dense_spf_distances(in_src, in_w, in_ok, overloaded, root)
    nh = dense_spf_nexthop_lanes(
        in_src, in_w, in_ok, in_rank, in_has, overloaded, root, dist,
        max_degree,
    )
    return dist, nh


# ---------------------------------------------------------------------------
# Warm-start (generation-delta) kernels
# ---------------------------------------------------------------------------
# The cold kernels above pay O(hop-diameter) relaxation rounds from an
# all-BIG init on every topology generation.  Decision's generation-delta
# rebuild path (decision/backend.py) instead seeds the solve from the
# PREVIOUS generation's fixed point with only the provably-affected
# vertices reset to BIG (ops/repair.py:plan_generation_delta derives the
# reset set on the host):
#
#   * distances: Bellman-Ford converges to the exact fixed point from ANY
#     pointwise over-estimate with d[root] = 0 (ops/repair.py module
#     docstring).  Keeping an unaffected vertex's old distance is such an
#     over-estimate (its old shortest path survives un-weakened); resetting
#     affected vertices restores the invariant for the rest.  Convergence
#     then takes rounds proportional to the perturbed region's DAG depth,
#     not the graph's hop diameter.
#   * lanes: recomputed with RESET semantics — each round REPLACES nh[v]
#     with seed(v) | OR over current DAG in-edges, never OR-accumulating
#     onto the previous round.  On the (new) shortest-path DAG this update
#     has a unique fixed point reached from ANY init (induction in
#     topological order from the pinned root), so warm-starting from the
#     previous generation's lanes is safe: stale bits are overwritten, and
#     unaffected subtrees stop changing immediately.
#
# Both loops run WARM_UNROLL relaxation rounds per while_loop iteration:
# on small per-generation problem sizes the per-iteration dispatch
# overhead of a device while_loop dominates the actual relax math, and
# extra rounds past the fixed point are exact no-ops, so unrolling only
# amortizes overhead without changing the result.  The stop checks stay
# valid under unrolling: the distance loop is monotone (no change over a
# block implies the fixed point), and for the reset-semantics lane loop a
# block-periodic state must equal the unique fixed point (after depth-k
# rounds every depth<=k vertex is final; a period would otherwise persist
# past full convergence).

#: relaxation rounds per while_loop iteration in the warm kernels
WARM_UNROLL = 16


def warm_spf_distances(
    src,
    dst,
    w,
    edge_ok,
    overloaded,
    root,
    d0,  # [V] f32 over-estimate seed (affected vertices = BIG)
    max_iters: Optional[int] = None,
):
    """Warm-started masked Bellman-Ford.  Returns [V] f32 — bit-identical
    to ``spf_distances`` (same fixed-point equations, and integral link
    metrics make every f32 path sum exact)."""
    V = overloaded.shape[0]
    w = jnp.where(edge_ok, w, BIG).astype(jnp.float32)
    dist0 = d0.astype(jnp.float32).at[root].set(0.0)
    transit = _can_transit(overloaded, root)
    src_ok = transit[src] & edge_ok
    limit = jnp.int32(max_iters if max_iters is not None else V)

    def relax(d):
        cand = jnp.where(src_ok, d[src] + w, BIG)
        best = jax.ops.segment_min(
            cand, dst, num_segments=V, indices_are_sorted=True
        )
        return jnp.minimum(d, best)

    def cond(state):
        _, changed, i = state
        return changed & (i < limit)

    def body(state):
        d, _, i = state
        nd = d
        for _ in range(WARM_UNROLL):
            nd = relax(nd)
        return nd, jnp.any(nd < d), i + WARM_UNROLL

    dist, _, rounds = jax.lax.while_loop(
        cond, body, (dist0, jnp.bool_(True), jnp.int32(0))
    )
    return dist, rounds


def spf_nexthop_lanes_reset(
    src,
    dst,
    w,
    edge_ok,
    overloaded,
    root,
    dist,  # [V] converged distances (warm or cold)
    nh0,  # [V, D] int8 warm lane seed (any value is safe; see above)
    max_degree: int,
    max_iters: Optional[int] = None,
):
    """Reset-semantics nexthop-lane fixed point, warm-startable.
    Returns ([V, D] int8, rounds) — the same unique fixed point
    ``spf_nexthop_lanes`` reaches (identical seed construction and DAG)."""
    V = overloaded.shape[0]
    D = max_degree
    sp_edge = shortest_path_dag(src, dst, w, edge_ok, overloaded, root, dist)
    is_root_out = src == root
    rank = jnp.cumsum(is_root_out.astype(jnp.int32)) - 1
    lanes = jnp.arange(D, dtype=jnp.int32)[None, :]
    seed = (is_root_out[:, None] & (rank[:, None] == lanes)).astype(jnp.int8)
    seed_mask = (sp_edge & is_root_out)[:, None].astype(jnp.int8)
    seed_part = jax.ops.segment_max(
        seed * seed_mask, dst, num_segments=V, indices_are_sorted=True
    )
    prop_mask = (sp_edge & ~is_root_out)[:, None].astype(jnp.int8)
    limit = jnp.int32(max_iters if max_iters is not None else V)

    def step(nh):
        contrib = nh[src] * prop_mask
        new = jax.ops.segment_max(
            contrib, dst, num_segments=V, indices_are_sorted=True
        )
        # RESET: seed | in-edge OR, replacing (not accumulating onto)
        # the previous round's value — the warm-start safety property
        return jnp.maximum(new, seed_part)

    def cond(state):
        _, changed, i = state
        return changed & (i < limit)

    def body(state):
        nh, _, i = state
        new = nh
        for _ in range(WARM_UNROLL):
            new = step(new)
        return new, jnp.any(new != nh), i + WARM_UNROLL

    nh, _, rounds = jax.lax.while_loop(
        cond, body, (nh0.astype(jnp.int8), jnp.bool_(True), jnp.int32(0))
    )
    return nh, rounds


def warm_subgraph_repair_one(
    src_sub,  # [Es] int32 — edges whose head is in the reset set
    dst_sub,  # [Es] int32 (ascending: gathered from the dst-sorted list)
    w_sub,  # [Es] float32
    ok_sub,  # [Es] bool — edge_ok & transit[src], host-precomputed
    rank_sub,  # [Es] int32 root-out lane rank (-1 = not a root-out edge)
    prev_dist,  # [V]
    prev_nh,  # [V, D] int8
    reset,  # [V] bool
    max_degree: int,
):
    """Bounded repair for a PURE-WEAKENING generation delta: only the
    reset region re-relaxes, and every per-round reduction runs over the
    compact sub-edge list instead of the full edge set.  Exactness is
    argued in ops/repair.plan_generation_delta — outside the reset set
    neither distances nor lanes can change, so reading boundary values
    straight from the previous generation's vectors is sound.  Returns
    (dist [V], nh [V, D] int8, rounds_d, rounds_l)."""
    V = prev_dist.shape[0]
    D = max_degree
    d0 = jnp.where(reset, BIG, prev_dist.astype(jnp.float32))
    w_sub = jnp.where(ok_sub, w_sub, BIG).astype(jnp.float32)
    limit = jnp.int32(V)

    def relax(d):
        cand = jnp.where(ok_sub, d[src_sub] + w_sub, BIG)
        best = jax.ops.segment_min(
            cand, dst_sub, num_segments=V, indices_are_sorted=True
        )
        return jnp.where(reset, jnp.minimum(d, best), d)

    def dcond(state):
        _, changed, i = state
        return changed & (i < limit)

    def dbody(state):
        d, _, i = state
        nd = d
        for _ in range(WARM_UNROLL):
            nd = relax(nd)
        return nd, jnp.any(nd < d), i + WARM_UNROLL

    dist, _, rounds_d = jax.lax.while_loop(
        dcond, dbody, (d0, jnp.bool_(True), jnp.int32(0))
    )

    # lane repair over the same subgraph: the new shortest-path DAG's
    # in-edges of reset vertices, seeded by root-out lane ranks
    on = ok_sub & (dist[dst_sub] < BIG) & (
        dist[src_sub] + w_sub == dist[dst_sub]
    )
    lanes = jnp.arange(D, dtype=jnp.int32)[None, :]
    seed_mat = (
        (rank_sub[:, None] == lanes) & on[:, None]
    ).astype(jnp.int8)  # [Es, D]
    seed_part = jax.ops.segment_max(
        seed_mat, dst_sub, num_segments=V, indices_are_sorted=True
    )
    prop = (on & (rank_sub < 0))[:, None].astype(jnp.int8)
    nh_start = jnp.where(
        reset[:, None], jnp.int8(0), prev_nh.astype(jnp.int8)
    )

    def lstep(nh):
        contrib = nh[src_sub] * prop
        new = jax.ops.segment_max(
            contrib, dst_sub, num_segments=V, indices_are_sorted=True
        )
        new = jnp.maximum(new, seed_part)
        return jnp.where(reset[:, None], new, nh)

    def lcond(state):
        _, changed, i = state
        return changed & (i < limit)

    def lbody(state):
        nh, _, i = state
        new = nh
        for _ in range(WARM_UNROLL):
            new = lstep(new)
        return new, jnp.any(new != nh), i + WARM_UNROLL

    nh, _, rounds_l = jax.lax.while_loop(
        lcond, lbody, (nh_start, jnp.bool_(True), jnp.int32(0))
    )
    return dist, nh, rounds_d, rounds_l


def warm_spf_one(
    src,
    dst,
    w,
    edge_ok,
    overloaded,
    root,
    prev_dist,  # [V] previous generation's distances
    prev_nh,  # [V, D] previous generation's lanes
    reset,  # [V] bool — vertices whose distance may have increased
    lane_keep,  # scalar bool — root out-edge signature unchanged
    max_degree: int,
):
    """(dist [V], nh [V, D] int8, rounds_d, rounds_l) for one topology,
    warm-started from the previous generation's solution."""
    d0 = jnp.where(reset, BIG, prev_dist)
    dist, rounds_d = warm_spf_distances(
        src, dst, w, edge_ok, overloaded, root, d0
    )
    nh0 = jnp.where(
        lane_keep & ~reset[:, None], prev_nh.astype(jnp.int8), jnp.int8(0)
    )
    nh, rounds_l = spf_nexthop_lanes_reset(
        src, dst, w, edge_ok, overloaded, root, dist, nh0, max_degree
    )
    return dist, nh, rounds_d, rounds_l


# ---------------------------------------------------------------------------
# Transposed (batch-minor) sweep kernels
# ---------------------------------------------------------------------------
# For the big what-if sweeps the batch-LEADING layout above is wrong for
# TPU: every relax round gathers d[b, src] as B scattered rows.  With the
# batch axis LAST (dist [V, B]), d[src] is a contiguous-row gather and the
# segment reductions write full [B]-wide lanes — measured ~3x on the
# 1024-node/10k sweep, and the lane loop's [E, B, D] intermediates stay
# coalesced.  The route-selection path keeps the batch-leading kernels
# (tiny batches, shard_map-friendly); the sweep engine (ops/whatif.py)
# and bench.py use these.


@functools.partial(jax.jit, static_argnames=("max_iters",))
def spf_distances_sweep(
    src,  # [E]
    dst,  # [E]
    w,  # [E]
    edge_enabled,  # [E, B] bool (validity & per-snapshot mask)
    overloaded,  # [V] shared hard-drain bits
    root,  # scalar int32 shared root
    max_iters: Optional[int] = None,
):
    """Masked Bellman-Ford fixed point, batch-minor.  Returns [V, B]."""
    V = overloaded.shape[0]
    B = edge_enabled.shape[1]
    transit = _can_transit(overloaded, root)
    src_ok = transit[src][:, None] & edge_enabled  # [E, B]
    wcol = jnp.where(edge_enabled, w[:, None], BIG).astype(jnp.float32)
    d0 = jnp.full((V, B), BIG, jnp.float32).at[root].set(0.0)
    limit = jnp.int32(max_iters if max_iters is not None else V)

    def cond(state):
        _, changed, i = state
        return changed & (i < limit)

    def body(state):
        d, _, i = state
        cand = jnp.where(src_ok, d[src] + wcol, BIG)  # [E, B] row gather
        best = jax.ops.segment_min(
            cand, dst, num_segments=V, indices_are_sorted=True
        )
        nd = jnp.minimum(d, best)
        return nd, jnp.any(nd < d), i + 1

    d, _, _ = jax.lax.while_loop(
        cond, body, (d0, jnp.bool_(True), jnp.int32(0))
    )
    return d


@functools.partial(jax.jit, static_argnames=("max_degree", "max_iters"))
def spf_lanes_sweep(
    src,
    dst,
    w,
    edge_enabled,  # [E, B]
    overloaded,
    root,
    dist,  # [V, B] from spf_distances_sweep
    max_degree: int,
    max_iters: Optional[int] = None,
):
    """Nexthop-lane fixed point, batch-minor.  Returns [V, B, D] int8."""
    V = overloaded.shape[0]
    D = max_degree
    transit = _can_transit(overloaded, root)
    wcol = jnp.where(edge_enabled, w[:, None], BIG)
    sp_edge = (
        edge_enabled
        & transit[src][:, None]
        & (dist[dst] < BIG)
        & (dist[src] + wcol == dist[dst])
    )  # [E, B]
    is_root_out = src == root
    rank = jnp.cumsum(is_root_out.astype(jnp.int32)) - 1
    lanes = jnp.arange(D, dtype=jnp.int32)[None, :]
    seed = (is_root_out[:, None] & (rank[:, None] == lanes)).astype(jnp.int8)
    # root-out contributions are constant: fold into the initial state
    seed_mask = (sp_edge & is_root_out[:, None]).astype(jnp.int8)  # [E, B]
    nh0 = jax.ops.segment_max(
        seed[:, None, :] * seed_mask[:, :, None],  # [E, B, D]
        dst,
        num_segments=V,
        indices_are_sorted=True,
    )
    prop = (sp_edge & ~is_root_out[:, None]).astype(jnp.int8)  # [E, B]
    limit = jnp.int32(max_iters if max_iters is not None else V)

    def cond(state):
        _, changed, i = state
        return changed & (i < limit)

    def body(state):
        nh, _, i = state
        contrib = nh[src] * prop[:, :, None]  # [E, B, D]
        new = jax.ops.segment_max(
            contrib, dst, num_segments=V, indices_are_sorted=True
        )
        new = jnp.maximum(new, nh)
        return new, jnp.any(new != nh), i + 1

    nh, _, _ = jax.lax.while_loop(
        cond, body, (nh0, jnp.bool_(True), jnp.int32(0))
    )
    return nh


#: packed-lane encoding: 6 lanes per uint32 channel, 5 bits per lane
#: digit.  OR-propagation becomes segment_SUM + per-digit renormalize —
#: TPU stores int8 padded to 32-bit lanes, so the naive [E, B, D] int8
#: lane loop moves ~5.7x more bytes than these packed channels.  The
#: digit holds the count of contributing in-edges, so it must not carry
#: into the next digit: requires max in-degree <= 30 (checked by caller;
#: legacy int8 path otherwise).
LANES_PER_CHANNEL = 6
LANE_BITS = 5
PACKED_MAX_IN_DEGREE = 30


def lane_channels(max_degree: int) -> int:
    return (max_degree + LANES_PER_CHANNEL - 1) // LANES_PER_CHANNEL


def unpack_lanes(packed: jnp.ndarray, max_degree: int) -> jnp.ndarray:
    """[..., C] uint32 -> [..., D] int8 (works on numpy arrays too)."""
    import numpy as np

    xp = np if isinstance(packed, np.ndarray) else jnp
    d = xp.arange(max_degree)
    chan = d // LANES_PER_CHANNEL
    shift = (d % LANES_PER_CHANNEL) * LANE_BITS
    vals = packed[..., chan] >> shift.astype(packed.dtype)
    return ((vals & ((1 << LANE_BITS) - 1)) > 0).astype(xp.int8)


@functools.partial(jax.jit, static_argnames=("max_degree", "max_iters"))
def spf_lanes_sweep_packed(
    src,
    dst,
    w,
    edge_enabled,  # [E, B]
    overloaded,
    root,
    dist,  # [V, B]
    max_degree: int,
    max_iters: Optional[int] = None,
):
    """Packed-channel nexthop-lane fixed point.  Returns [V, B, C] uint32
    with digits renormalized to 0/1 (decode with unpack_lanes)."""
    V = overloaded.shape[0]
    C = lane_channels(max_degree)
    transit = _can_transit(overloaded, root)
    wcol = jnp.where(edge_enabled, w[:, None], BIG)
    sp_edge = (
        edge_enabled
        & transit[src][:, None]
        & (dist[dst] < BIG)
        & (dist[src] + wcol == dist[dst])
    )  # [E, B]
    is_root_out = src == root
    rank = jnp.cumsum(is_root_out.astype(jnp.int32)) - 1
    # per-edge seed word: lane rank's digit in its channel
    chan_ids = jnp.arange(C, dtype=jnp.int32)[None, :]  # [1, C]
    seed_word = jnp.where(
        is_root_out[:, None]
        & (rank[:, None] // LANES_PER_CHANNEL == chan_ids),
        jnp.uint32(1) << ((rank[:, None] % LANES_PER_CHANNEL) * LANE_BITS),
        jnp.uint32(0),
    )  # [E, C]
    seed_mask = (sp_edge & is_root_out[:, None]).astype(jnp.uint32)  # [E, B]
    nh0 = jax.ops.segment_sum(
        seed_word[:, None, :] * seed_mask[:, :, None],  # [E, B, C]
        dst,
        num_segments=V,
        indices_are_sorted=True,
    )
    digit_lsbs = functools.reduce(
        lambda acc, k: acc | (jnp.uint32(1) << (k * LANE_BITS)),
        range(LANES_PER_CHANNEL),
        jnp.uint32(0),
    )
    digit_mask = digit_lsbs * ((1 << LANE_BITS) - 1)  # all digit bits

    def renorm(x):
        # any nonzero digit -> exactly 1 (digits never carry: counts
        # <= in-degree + 1 <= 31)
        present = x | (x >> 1) | (x >> 2) | (x >> 3) | (x >> 4)
        return present & digit_lsbs

    nh0 = renorm(nh0 & digit_mask)
    prop = (sp_edge & ~is_root_out[:, None]).astype(jnp.uint32)  # [E, B]
    limit = jnp.int32(max_iters if max_iters is not None else V)

    def cond(state):
        _, changed, i = state
        return changed & (i < limit)

    def body(state):
        nh, _, i = state
        contrib = nh[src] * prop[:, :, None]  # [E, B, C]
        summed = jax.ops.segment_sum(
            contrib, dst, num_segments=V, indices_are_sorted=True
        )
        new = renorm((summed + nh) & digit_mask)
        return new, jnp.any(new != nh), i + 1

    nh, _, _ = jax.lax.while_loop(
        cond, body, (nh0, jnp.bool_(True), jnp.int32(0))
    )
    return nh


@functools.partial(jax.jit, static_argnames=("max_degree", "packed"))
def sweep_spf_link_failures(
    src,
    dst,
    w,
    edge_ok,  # [E]
    link_index,  # [E]
    failed_link,  # [B] int32 (-1 = none)
    overloaded,  # [V]
    root,  # scalar
    max_degree: int,
    packed: bool = False,
):
    """Fused single-link-failure sweep, batch-minor: ships one int32 per
    snapshot.  Returns (dist [V, B], nh) where nh is [V, B, D] int8, or
    [V, B, C] uint32 packed channels when `packed` (requires max
    in-degree <= PACKED_MAX_IN_DEGREE — caller's responsibility)."""
    en = edge_ok[:, None] & (link_index[:, None] != failed_link[None, :])
    dist = spf_distances_sweep(src, dst, w, en, overloaded, root)
    if packed:
        nh = spf_lanes_sweep_packed(
            src, dst, w, en, overloaded, root, dist, max_degree
        )
    else:
        nh = spf_lanes_sweep(
            src, dst, w, en, overloaded, root, dist, max_degree
        )
    return dist, nh
