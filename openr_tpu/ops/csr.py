"""Topology encoding: LinkState graphs → padded device arrays.

This is the host↔device bridge (SURVEY §7 hard-part 4): node names are
interned to dense int ids, bidirectional links become two directed edges
carrying the soft-drain MAX metric (LinkState.cpp:789 semantics), and
everything is padded to shape buckets so the jit cache stays stable across
LSDB churn.

Layout (single topology; batch adds a leading dim):
  * ``src[E], dst[E]`` int32 directed edge endpoints (padded with 0)
  * ``w[E]`` float32 edge metric; ``INF`` for padding/down links
  * ``edge_ok[E]`` bool validity (up, usable, not padding)
  * ``overloaded[V]`` bool node hard-drain bits
  * ``soft[V]`` int32 node soft-drain increments
  * ``node_ok[V]`` bool validity
  * ``link_index[E]`` int32: undirected link id for each directed edge, so
    per-link what-if failure masks expand to both directions

The decoder side keeps the symbol table and the per-root out-edge ranking
used to map nexthop bitmask lanes back to `Link` objects.
"""

from __future__ import annotations

import ctypes
import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from openr_tpu.decision.link_state import Link, LinkState

INF = np.float32(np.inf)

#: in-degree buckets for the dense in-edge matrix (K axis).  Beyond the
#: largest bucket the dense formulation is declined (fields stay None)
#: and the SPF kernels fall back to the edge-list segment reductions.
IN_DEGREE_BUCKETS = (4, 8, 16, 32, 64, 128, 256, 512, 1024)

#: native fill path (native/csr_bridge.cc) — the per-element expansion in C
#: instead of Python (SURVEY §7 hard-part 4: the bridge must fit in the
#: 10-250ms debounce budget).  None = unavailable; pure-Python fallback.
_native = None


def _get_native():
    global _native
    if _native is None:
        try:
            from openr_tpu.common.native import load_native_lib

            lib = load_native_lib("csr_bridge")
            lib.csr_expand_fill.restype = ctypes.c_int
            lib.csr_failure_masks.restype = ctypes.c_int
            _native = lib
        except Exception:  # noqa: BLE001 - no compiler etc.
            _native = False
    return _native or None


def _np_ptr(arr: np.ndarray, ctype):
    return arr.ctypes.data_as(ctypes.POINTER(ctype))


class CapacityError(ValueError):
    """The data does not fit the device encoding (a bucket, a candidate
    table, a metric the device SPF cannot carry): a data-scale limit the
    caller answers on the scalar path, never a device-health signal.
    A ``ValueError`` so existing callers still catch it; a bare
    ``ValueError`` (jaxlib surfaces XLA errors so, and the native fill
    reports a fault so) is not one."""


def bucket_for(value: int, buckets: Sequence[int]) -> int:
    for b in buckets:
        if value <= b:
            return b
    raise CapacityError(f"{value} exceeds largest bucket {buckets[-1]}")


@dataclasses.dataclass
class EncodedTopology:
    """Device-ready arrays + host-side decode tables for ONE topology."""

    # device arrays (numpy; moved to device by the caller/jit)
    src: np.ndarray  # [E] int32
    dst: np.ndarray  # [E] int32
    w: np.ndarray  # [E] float32
    edge_ok: np.ndarray  # [E] bool
    overloaded: np.ndarray  # [V] bool
    soft: np.ndarray  # [V] int32
    node_ok: np.ndarray  # [V] bool
    link_index: np.ndarray  # [E] int32 (undirected link id, -1 pad)

    # host decode tables
    node_ids: Dict[str, int]
    id_to_node: List[str]
    links: List[Link]  # undirected link objects by link id
    #: [L, 2] positions of each undirected link's two directed edges in
    #: the (dst-sorted) edge arrays — what-if failure masks index this
    link_edge_pos: np.ndarray
    num_nodes: int
    num_edges: int  # valid directed edges

    # dense in-edge matrix (the gather formulation of the SPF fixpoint):
    # slot (v, k) holds the k-th directed edge INTO v in dst-sorted edge
    # order.  The relax step then reads ``d[in_src] + in_w`` and
    # min-reduces over K — pure gathers + a dense reduction, no scatter
    # (the scatter-based segment fixpoint was ~95% of a grid4096 cold
    # rebuild wall on host platforms).  ``in_rank`` carries the src
    # node's out-edge rank of that edge (root-independent: rank among
    # edges sharing the same src, in edge order), which IS the nexthop
    # lane id whenever in_src == root.  ``in_edge_pos`` maps each
    # edge-list position to its flat V*K slot (-1 for padding edges) so
    # the O(links) patch path refreshes in_w/in_ok without re-deriving
    # the layout.  All None when the max in-degree exceeds
    # IN_DEGREE_BUCKETS (segment-kernel fallback).
    in_src: Optional[np.ndarray] = None  # [V, K] int32
    in_w: Optional[np.ndarray] = None  # [V, K] float32 (INF pad/down)
    in_ok: Optional[np.ndarray] = None  # [V, K] bool
    in_rank: Optional[np.ndarray] = None  # [V, K] int32 (-1 = no lane)
    in_edge_pos: Optional[np.ndarray] = None  # [E] int64 flat slot (-1)
    #: [V] bool — v appears in the padded dst[] at all (real OR padding
    #: edge).  The segment kernels leave int8-min (-128) in lane rows of
    #: absent dsts (empty segments); the dense kernels replicate that
    #: exactly so warm contexts seeded from either formulation are
    #: bit-interchangeable.
    in_has: Optional[np.ndarray] = None

    # -- slot-stable structural state (ISSUE 12) ---------------------------
    # The slot patch path (:func:`patch_encoded_topology_slots`) keeps
    # node slots and edge rows STABLE across membership churn: a node
    # that leaves the LSDB keeps its slot (tombstoned) and its links'
    # rows (edge_ok=False, w=INF — exactly a down link, so lane ranks
    # never move); a rejoin revives them in place.  Only ops/csr and the
    # decision backend may produce encodings carrying these fields (the
    # orlint `slot-table` rule enforces it).
    #: names present in the symbol table but absent from the current LSDB
    tombstoned_nodes: frozenset = frozenset()
    #: undirected link ids whose rows are tombstoned (no current link)
    tombstoned_links: frozenset = frozenset()
    #: [V] bool — slots whose MEMBERSHIP changed in the patch that
    #: produced this encoding (newly tombstoned, revived, or renamed);
    #: None on cold encodes and pure perturbation patches.  The warm
    #: rebuild forces these slots into the reset set and the selective
    #: selection path treats them as changed nodes.
    slot_changed: Optional[np.ndarray] = None

    @property
    def has_dense(self) -> bool:
        return self.in_src is not None

    @property
    def padded_nodes(self) -> int:
        return int(self.overloaded.shape[0])

    @property
    def padded_edges(self) -> int:
        return int(self.src.shape[0])

    def node_id(self, name: str) -> int:
        return self.node_ids[name]

    # -- nexthop lane decoding --------------------------------------------

    def root_out_edges(self, root: str) -> List[Tuple[Link, str]]:
        """Lane r of the nexthop bitmask (for SPF rooted at `root`)
        corresponds to the r-th directed edge with src == root, in edge
        order.  Returns [(link, neighbor_node_name)] by lane; a root
        absent from this area's graph has no lanes (the fleet engine
        decodes vantage nodes that participate in only SOME areas — their
        absent-area slices are masked unreachable by the kernel)."""
        rid = self.node_ids.get(root)
        if rid is None:
            return []
        idx = np.nonzero((self.src == rid) & (self.link_index >= 0))[0]
        return [
            (self.links[self.link_index[e]], self.id_to_node[self.dst[e]])
            for e in idx
        ]

    def max_out_degree(self) -> int:
        valid = self.link_index >= 0
        if not valid.any():
            return 0
        counts = np.bincount(self.src[valid], minlength=self.padded_nodes)
        return int(counts.max())


def build_in_edge_matrix(
    src: np.ndarray,
    dst: np.ndarray,
    w: np.ndarray,
    edge_ok: np.ndarray,
    link_index: np.ndarray,
    padded_v: int,
    in_degree_bucket: Optional[int] = None,
):
    """Dense in-edge layout for dst-sorted edge arrays.

    Returns ``(in_src, in_w, in_ok, in_rank, in_edge_pos, in_has)`` or
    None when the max in-degree exceeds the largest bucket (segment
    fallback).
    Every REAL edge (``link_index >= 0``) owns a slot — down links
    included, so a later patch that revives them only flips ``in_ok``;
    padding slots read ``in_ok=False, in_w=INF`` and gather node 0."""
    valid = np.nonzero(link_index >= 0)[0]
    n = len(valid)
    if n:
        counts = np.bincount(dst[valid], minlength=padded_v)
        max_in = int(counts.max())
    else:
        max_in = 0
    try:
        K = in_degree_bucket or bucket_for(max(max_in, 1), IN_DEGREE_BUCKETS)
    except CapacityError:
        return None
    if K < max_in:
        return None
    in_src = np.zeros((padded_v, K), np.int32)
    in_w = np.full((padded_v, K), INF, np.float32)
    in_ok = np.zeros((padded_v, K), bool)
    in_rank = np.full((padded_v, K), -1, np.int32)
    in_edge_pos = np.full(src.shape[0], -1, np.int64)
    if n:
        d = dst[valid]
        # edges are dst-sorted, so each dst's run is contiguous: slot k
        # = position within the run (first-occurrence searchsorted)
        run_start = np.searchsorted(d, d, side="left")
        slot = np.arange(n) - run_start
        flat = d.astype(np.int64) * K + slot
        in_edge_pos[valid] = flat
        s = src[valid]
        # out-edge rank per edge: index among same-src edges in edge
        # order (stable sort by src preserves position order) — the lane
        # id the nexthop kernels seed when src == root
        order = np.argsort(s, kind="stable")
        s_sorted = s[order]
        first = np.searchsorted(s_sorted, s_sorted, side="left")
        rank = np.empty(n, np.int32)
        rank[order] = (np.arange(n) - first).astype(np.int32)
        in_src.flat[flat] = s
        in_w.flat[flat] = w[valid]
        in_ok.flat[flat] = edge_ok[valid]
        in_rank.flat[flat] = rank
    in_has = np.bincount(dst, minlength=padded_v) > 0
    return in_src, in_w, in_ok, in_rank, in_edge_pos, in_has


def encode_link_state(
    link_state: LinkState,
    node_bucket: Optional[int] = None,
    edge_bucket: Optional[int] = None,
    node_buckets: Sequence[int] = (16, 64, 256, 1024, 4096, 16384),
    edge_multiplier: int = 8,
    extra_nodes: Sequence[str] = (),
    in_degree_bucket: Optional[int] = None,
) -> EncodedTopology:
    """Encode one LinkState area graph.

    Only up/usable links are emitted as valid edges (interface hard-drain
    excluded here, exactly as Link::isUp excludes them from SPF).  Node
    hard/soft drain bits ride separately so what-if sweeps can flip them
    per snapshot.  `extra_nodes` forces symbol-table entries for nodes
    known to other modules (e.g. advertisers with no adjacencies yet).
    """
    names = sorted(
        set(link_state.get_adjacency_databases().keys())
        | {n for n in extra_nodes}
    )
    node_ids = {n: i for i, n in enumerate(names)}
    V = len(names)
    padded_v = node_bucket or bucket_for(max(V, 1), node_buckets)

    links = link_state.all_links()
    L = len(links)
    # one pass over the Python Link objects -> flat columns
    col_a = np.empty(max(L, 1), np.int32)
    col_b = np.empty(max(L, 1), np.int32)
    col_m = np.empty(max(L, 1), np.float32)
    col_ok = np.empty(max(L, 1), np.uint8)
    for li, link in enumerate(links):
        col_a[li] = node_ids[link.n1]
        col_b[li] = node_ids[link.n2]
        col_m[li] = link.get_max_metric()
        col_ok[li] = link.is_up()

    E = 2 * L
    padded_e = edge_bucket or bucket_for(
        max(E, 1), [b * edge_multiplier for b in node_buckets]
    )
    if padded_v < V:
        raise CapacityError(f"node bucket {padded_v} < {V} nodes")
    if padded_e < E:
        raise CapacityError(f"edge bucket {padded_e} < {E} directed edges")

    src = np.empty(padded_e, np.int32)
    dst = np.empty(padded_e, np.int32)
    w = np.empty(padded_e, np.float32)
    edge_ok_u8 = np.empty(padded_e, np.uint8)
    link_index = np.empty(padded_e, np.int32)

    # padding endpoints use the highest padded node id so the dst-sort
    # below leaves padding at the tail (lane-rank correctness for root 0)
    pad_node = padded_v - 1
    native = _get_native()
    if native is not None:
        rc = native.csr_expand_fill(
            L,
            _np_ptr(col_a, ctypes.c_int32),
            _np_ptr(col_b, ctypes.c_int32),
            _np_ptr(col_m, ctypes.c_float),
            _np_ptr(col_ok, ctypes.c_uint8),
            padded_e,
            pad_node,
            _np_ptr(src, ctypes.c_int32),
            _np_ptr(dst, ctypes.c_int32),
            _np_ptr(w, ctypes.c_float),
            _np_ptr(edge_ok_u8, ctypes.c_uint8),
            _np_ptr(link_index, ctypes.c_int32),
        )
        if rc == -2:
            # The DAG-equality nexthop propagation assumes strictly positive
            # metrics (a 0-cost edge would union lanes across equidistant
            # nodes where heap Dijkstra keeps them distinct).  The reference
            # never produces metric<=0 adjacencies; reject at the bridge.
            raise CapacityError(
                "non-positive metric on an up link; device SPF requires "
                "metrics >= 1"
            )
        if rc != 0:
            raise ValueError(f"csr_expand_fill failed rc={rc}")
        edge_ok = edge_ok_u8.astype(bool)
    else:
        # vectorized Python fallback (identical semantics)
        if np.any(col_ok[:L].astype(bool) & (col_m[:L] <= 0)):
            raise CapacityError(
                "non-positive metric on an up link; device SPF requires "
                "metrics >= 1"
            )
        src[:E:2], dst[:E:2] = col_a[:L], col_b[:L]
        src[1:E:2], dst[1:E:2] = col_b[:L], col_a[:L]
        m_dir = np.where(col_ok[:L].astype(bool), col_m[:L], INF)
        w[:E:2] = m_dir
        w[1:E:2] = m_dir
        edge_ok_u8[:E:2] = col_ok[:L]
        edge_ok_u8[1:E:2] = col_ok[:L]
        link_index[:E:2] = np.arange(L, dtype=np.int32)
        link_index[1:E:2] = np.arange(L, dtype=np.int32)
        src[E:] = pad_node
        dst[E:] = pad_node
        w[E:] = INF
        edge_ok_u8[E:] = 0
        link_index[E:] = -1
        edge_ok = edge_ok_u8.astype(bool)

    overloaded = np.zeros(padded_v, bool)
    soft = np.zeros(padded_v, np.int32)
    node_ok = np.zeros(padded_v, bool)
    node_ok[:V] = True
    for n, i in node_ids.items():
        overloaded[i] = link_state.is_node_overloaded(n)
        soft[i] = link_state.get_node_metric_increment(n)

    # Canonical device layout: edges sorted by dst.  The SPF kernels'
    # segment reductions then run with indices_are_sorted=True, which on
    # TPU avoids general scatter in the relax step.  Padding edges carry
    # src=dst=pad_node (the HIGHEST padded id, set above) so the stable
    # sort leaves them at the tail — pads labeled 0 would sort to the
    # front and pollute root-out lane ranks for low-id SPF roots.
    order = np.argsort(dst, kind="stable")
    src = src[order]
    dst = dst[order]
    w = w[order]
    edge_ok = edge_ok[order]
    link_index = link_index[order]
    # positions of each link's two directed edges in the sorted layout:
    # stable-argsort link_index groups pads (-1) first, then pairs per li
    by_link = np.argsort(link_index, kind="stable")
    pad_count = int((link_index < 0).sum())
    link_edge_pos = (
        by_link[pad_count:].reshape(L, 2).astype(np.int32)
        if L
        else np.zeros((0, 2), np.int32)
    )

    dense = build_in_edge_matrix(
        src, dst, w, edge_ok, link_index, padded_v, in_degree_bucket
    )
    in_src = in_w = in_ok = in_rank = in_edge_pos = in_has = None
    if dense is not None:
        in_src, in_w, in_ok, in_rank, in_edge_pos, in_has = dense

    return EncodedTopology(
        src=src,
        dst=dst,
        w=w,
        edge_ok=edge_ok,
        overloaded=overloaded,
        soft=soft,
        node_ok=node_ok,
        link_index=link_index,
        node_ids=node_ids,
        id_to_node=names,
        links=links,
        link_edge_pos=link_edge_pos,
        num_nodes=V,
        num_edges=E,
        in_src=in_src,
        in_w=in_w,
        in_ok=in_ok,
        in_rank=in_rank,
        in_edge_pos=in_edge_pos,
        in_has=in_has,
    )


def patch_encoded_topology(
    old: "EncodedTopology", link_state: LinkState, me: Optional[str] = None
) -> Optional["EncodedTopology"]:
    """O(links) re-encode of a PERTURBED topology: when the node symbol
    table and the undirected link identity set are unchanged (link
    weight / up-down / overload / soft-drain churn — the warm-rebuild
    classes), only the weight/validity/drain columns are refreshed and
    every layout array (src/dst/link_index, the dst-sort order,
    link_edge_pos, the symbol tables) is shared with the previous
    encoding.  Returns None on any structural change (node or link
    add/remove, identity drift) — the caller re-encodes cold.  The full
    encoder re-sorts, re-interns and re-expands everything on each
    topology tick; at 4096 nodes that is most of the warm rebuild's
    host budget."""
    names = set(link_state.get_adjacency_databases().keys())
    if me is not None:
        names.add(me)
    if names != set(old.node_ids.keys()):
        return None
    links = link_state.all_links()
    L = len(links)
    if L != len(old.links):
        return None
    for li in range(L):
        if links[li]._key != old.links[li]._key:
            return None

    col_m = np.empty(max(L, 1), np.float32)
    col_ok = np.empty(max(L, 1), np.uint8)
    for li, link in enumerate(links):
        col_m[li] = link.get_max_metric()
        col_ok[li] = link.is_up()
    if np.any(col_ok[:L].astype(bool) & (col_m[:L] <= 0)):
        raise CapacityError(
            "non-positive metric on an up link; device SPF requires "
            "metrics >= 1"
        )
    w = np.full(old.padded_edges, INF, np.float32)
    edge_ok = np.zeros(old.padded_edges, bool)
    if L:
        pos = old.link_edge_pos  # [L, 2] positions in the dst-sorted layout
        m_dir = np.where(col_ok[:L].astype(bool), col_m[:L], INF)
        ok_dir = col_ok[:L].astype(bool)
        for side in (0, 1):
            w[pos[:, side]] = m_dir
            edge_ok[pos[:, side]] = ok_dir

    overloaded = np.zeros(old.padded_nodes, bool)
    soft = np.zeros(old.padded_nodes, np.int32)
    for n, i in old.node_ids.items():
        overloaded[i] = link_state.is_node_overloaded(n)
        soft[i] = link_state.get_node_metric_increment(n)

    # dense in-edge refresh: the layout (in_src/in_rank/in_edge_pos) is
    # identity-shared; only the weight/validity planes re-scatter from
    # the freshly patched edge columns — O(links), like the rest of the
    # patch path
    in_w = in_ok = None
    if old.has_dense:
        pos = old.in_edge_pos
        m = pos >= 0
        in_w = np.full_like(old.in_w, INF)
        in_ok = np.zeros_like(old.in_ok)
        in_w.flat[pos[m]] = w[m]
        in_ok.flat[pos[m]] = edge_ok[m]

    return EncodedTopology(
        src=old.src,
        dst=old.dst,
        w=w,
        edge_ok=edge_ok,
        overloaded=overloaded,
        soft=soft,
        node_ok=old.node_ok,
        link_index=old.link_index,
        node_ids=old.node_ids,
        id_to_node=old.id_to_node,
        links=links,
        link_edge_pos=old.link_edge_pos,
        num_nodes=old.num_nodes,
        num_edges=old.num_edges,
        in_src=old.in_src,
        in_w=in_w,
        in_ok=in_ok,
        in_rank=old.in_rank,
        in_edge_pos=old.in_edge_pos,
        in_has=old.in_has,
    )


def patch_encoded_topology_slots(
    old: "EncodedTopology", link_state: LinkState, me: Optional[str] = None
) -> Tuple[Optional["EncodedTopology"], Optional[str]]:
    """Slot-stable structural patch: membership churn (node join/leave,
    link add/remove — the delta class a rolling restart or autoscaling
    event produces continuously) re-encodes in O(links) with every
    layout array identity-shared, instead of the full re-sort/re-intern/
    re-expand pass.

    Mechanics:

      * a node that LEAVES the LSDB keeps its slot — it is tombstoned,
        and each of its links' edge rows is invalidated in place
        (``edge_ok=False, w=INF``: byte-for-byte a down link, so lane
        ranks, the dst-sort order and the dense in-edge layout never
        move);
      * a node that REJOINS (the rolling-restart case) revives its slot
        and its links reclaim their retained rows by link identity key;
      * a genuinely NEW name takes a slot from the free-list of
        tombstoned slots (deterministic: lowest slot first; the evicted
        tombstone's name is forgotten — a cold re-encode is the GC) and
        its links reclaim tombstoned rows joining the same slot
        endpoints (the replacement-node pattern: new name, same
        physical neighbors).

    Declines — ``(None, reason)`` — fall back to a cold re-encode with
    the reason counted by the backend:

      * ``slot_exhaustion``: a new name with no tombstoned slot free;
      * ``new_link``: a current link with neither an identity-key match
        nor a same-endpoints tombstoned row pair (genuinely new
        topology needs new rows, which would break the dst-sorted
        layout the segment kernels rely on).

    Same contract as :func:`patch_encoded_topology`: weight/validity/
    drain planes are fresh arrays; src/dst/link_index/link_edge_pos,
    the dense in-edge layout and (rename-free) the symbol tables are
    shared with the previous encoding."""
    names = set(link_state.get_adjacency_databases().keys())
    if me is not None:
        names.add(me)
    old_names = set(old.node_ids.keys())
    joins = sorted(names - old_names)
    node_ids = old.node_ids
    id_to_node = old.id_to_node
    renamed_slots: List[int] = []
    if joins:
        # free-list: slots of tombstoned names that are not rejoining
        # this tick, lowest slot first (deterministic across replays)
        free = sorted(
            old.node_ids[n] for n in old.tombstoned_nodes if n not in names
        )
        if len(free) < len(joins):
            return None, "slot_exhaustion"
        node_ids = dict(old.node_ids)
        id_to_node = list(old.id_to_node)
        for name in joins:
            slot = free.pop(0)
            del node_ids[id_to_node[slot]]
            node_ids[name] = slot
            id_to_node[slot] = name
            renamed_slots.append(slot)

    # -- link row assignment: identity key first, then same-endpoints
    # -- reclaim of tombstoned rows for new keys
    links_now = link_state.all_links()
    n_rows = len(old.links)
    assigned: Dict[int, Link] = {}
    key_to_li = {lk._key: li for li, lk in enumerate(old.links)}
    unmatched: List[Link] = []
    for lk in links_now:
        li = key_to_li.get(lk._key)
        if li is not None and li not in assigned:
            assigned[li] = lk
        else:
            unmatched.append(lk)
    if unmatched:
        pos = old.link_edge_pos
        avail: Dict[Tuple[int, int], List[int]] = {}
        for li in range(n_rows):
            if li in assigned:
                continue
            e0 = pos[li, 0]
            pair = (int(old.src[e0]), int(old.dst[e0]))
            avail.setdefault((min(pair), max(pair)), []).append(li)
        for lk in unmatched:
            a = node_ids.get(lk.n1)
            b = node_ids.get(lk.n2)
            if a is None or b is None:
                return None, "new_link"
            cand = avail.get((min(a, b), max(a, b)))
            if not cand:
                return None, "new_link"
            assigned[cand.pop(0)] = lk

    col_m = np.full(max(n_rows, 1), INF, np.float32)
    col_ok = np.zeros(max(n_rows, 1), bool)
    new_links = list(old.links)
    for li, lk in assigned.items():
        new_links[li] = lk
        col_m[li] = lk.get_max_metric()
        col_ok[li] = lk.is_up()
    if np.any(col_ok[:n_rows] & (col_m[:n_rows] <= 0)):
        raise CapacityError(
            "non-positive metric on an up link; device SPF requires "
            "metrics >= 1"
        )
    w = np.full(old.padded_edges, INF, np.float32)
    edge_ok = np.zeros(old.padded_edges, bool)
    if n_rows:
        pos = old.link_edge_pos
        m_dir = np.where(col_ok[:n_rows], col_m[:n_rows], INF)
        for side in (0, 1):
            w[pos[:, side]] = m_dir
            edge_ok[pos[:, side]] = col_ok[:n_rows]

    overloaded = np.zeros(old.padded_nodes, bool)
    soft = np.zeros(old.padded_nodes, np.int32)
    for n, i in node_ids.items():
        # tombstoned names read the LinkState defaults (False / 0)
        overloaded[i] = link_state.is_node_overloaded(n)
        soft[i] = link_state.get_node_metric_increment(n)

    in_w = in_ok = None
    if old.has_dense:
        epos = old.in_edge_pos
        m = epos >= 0
        in_w = np.full_like(old.in_w, INF)
        in_ok = np.zeros_like(old.in_ok)
        in_w.flat[epos[m]] = w[m]
        in_ok.flat[epos[m]] = edge_ok[m]

    tombstoned_nodes = frozenset(set(node_ids) - names)
    tombstoned_links = frozenset(
        li for li in range(n_rows) if li not in assigned
    )
    slot_changed = np.zeros(old.padded_nodes, bool)
    for name in (old.tombstoned_nodes ^ tombstoned_nodes):
        nid = node_ids.get(name)
        if nid is not None:
            slot_changed[nid] = True
    slot_changed[renamed_slots] = True
    # links whose tombstone state flipped mark both endpoint slots —
    # belt and braces for the selective-selection changed-node mask
    # (dist/lane diffs catch them too)
    for li in (old.tombstoned_links ^ tombstoned_links):
        e0 = old.link_edge_pos[li, 0]
        slot_changed[int(old.src[e0])] = True
        slot_changed[int(old.dst[e0])] = True

    return (
        EncodedTopology(
            src=old.src,
            dst=old.dst,
            w=w,
            edge_ok=edge_ok,
            overloaded=overloaded,
            soft=soft,
            node_ok=old.node_ok,
            link_index=old.link_index,
            node_ids=node_ids,
            id_to_node=id_to_node,
            links=new_links,
            link_edge_pos=old.link_edge_pos,
            num_nodes=old.num_nodes,
            num_edges=old.num_edges,
            in_src=old.in_src,
            in_w=in_w,
            in_ok=in_ok,
            in_rank=old.in_rank,
            in_edge_pos=old.in_edge_pos,
            in_has=old.in_has,
            tombstoned_nodes=tombstoned_nodes,
            tombstoned_links=tombstoned_links,
            slot_changed=slot_changed,
        ),
        None,
    )


def patch_encoded_multi_area_slots(
    prev: EncodedMultiArea, area_link_states, me: str
) -> Tuple[Optional[EncodedMultiArea], str, Optional[str]]:
    """Structural-capable multi-area patch: per area, try the pure
    perturbation patch first (weight/drain churn on an unchanged
    membership), then the slot-stable structural patch.  Returns
    ``(enc, kind, reason)`` — kind is ``"patch"`` (every area took the
    perturbation path), ``"slot"`` (at least one area took the slot
    path) or ``"cold"`` (enc None; reason names the decline:
    ``area_change``, ``slot_exhaustion``, ``new_link``)."""
    areas = sorted(area_link_states.keys())
    if areas != prev.areas:
        return None, "cold", "area_change"
    topos = []
    any_slot = False
    for a, old_topo in zip(areas, prev.topos):
        patched = None
        if not old_topo.tombstoned_nodes and not old_topo.tombstoned_links:
            patched = patch_encoded_topology(old_topo, area_link_states[a], me)
        if patched is None:
            patched, reason = patch_encoded_topology_slots(
                old_topo, area_link_states[a], me
            )
            if patched is None:
                return None, "cold", reason
            any_slot = True
        topos.append(patched)
    dense = {}
    if prev.has_dense and all(t.has_dense for t in topos):
        K = prev.in_src.shape[2]

        def widen(a, fill):
            pad = K - a.shape[1]
            if not pad:
                return a
            return np.concatenate(
                [a, np.full((a.shape[0], pad), fill, a.dtype)], axis=1
            )

        dense = dict(
            in_src=prev.in_src,  # layout shared with the previous gen
            in_rank=prev.in_rank,
            in_has=prev.in_has,
            in_w=np.stack([widen(t.in_w, INF) for t in topos]),
            in_ok=np.stack([widen(t.in_ok, False) for t in topos]),
        )
    return (
        EncodedMultiArea(
            areas=areas,
            topos=topos,
            src=prev.src,
            dst=prev.dst,
            w=np.stack([t.w for t in topos]),
            edge_ok=np.stack([t.edge_ok for t in topos]),
            overloaded=np.stack([t.overloaded for t in topos]),
            soft=np.stack([t.soft for t in topos]),
            roots=prev.roots,
            **dense,
        ),
        "slot" if any_slot else "patch",
        None,
    )


@dataclasses.dataclass
class EncodedPrefixCandidates:
    """Per-prefix candidate advertisements → device arrays.

    Shapes [P, C]: for each of P prefixes, up to C candidate (node, metrics)
    advertisements.  Used by the on-device best-route selection.
    """

    cand_node: np.ndarray  # [P, C] int32 node ids
    cand_ok: np.ndarray  # [P, C] bool
    drain_metric: np.ndarray  # [P, C] int32
    path_pref: np.ndarray  # [P, C] int32
    source_pref: np.ndarray  # [P, C] int32
    distance: np.ndarray  # [P, C] int32
    min_nexthop: np.ndarray  # [P, C] int32 (0 = unset)
    prefixes: List[str]

    @property
    def num_prefixes(self) -> int:
        return len(self.prefixes)


def encode_prefix_candidates(
    prefix_state,
    topo: EncodedTopology,
    area: str,
    max_candidates: Optional[int] = None,
    cand_buckets: Sequence[int] = (8, 16, 32, 64),
) -> EncodedPrefixCandidates:
    """Flatten PrefixState (for one area) into padded candidate arrays.

    The candidate axis is padded to the smallest bucket in `cand_buckets`
    that fits the widest prefix (anycast prefixes advertised by many
    nodes), so the jit cache stays warm while wide prefixes still get the
    device path; `max_candidates` pins the width explicitly instead.
    Raises CapacityError past the largest bucket (caller falls back scalar).
    """
    prefixes = sorted(prefix_state.prefixes().keys())
    P = max(len(prefixes), 1)
    if max_candidates is not None:
        C = max_candidates
    else:
        widest = 1
        for prefix in prefixes:
            n = sum(
                1
                for (node, parea) in prefix_state.prefixes()[prefix]
                if parea == area and node in topo.node_ids
            )
            widest = max(widest, n)
        C = bucket_for(widest, cand_buckets)
    cand_node = np.zeros((P, C), np.int32)
    cand_ok = np.zeros((P, C), bool)
    drain = np.zeros((P, C), np.int32)
    pp = np.zeros((P, C), np.int32)
    sp = np.zeros((P, C), np.int32)
    dist = np.zeros((P, C), np.int32)
    minnh = np.zeros((P, C), np.int32)
    for p, prefix in enumerate(prefixes):
        c = 0
        for (node, parea), entry in sorted(prefix_state.prefixes()[prefix].items()):
            if parea != area or node not in topo.node_ids:
                continue
            if c >= C:
                raise CapacityError(
                    f"prefix {prefix}: more than {C} candidates; raise "
                    "max_candidates"
                )
            cand_node[p, c] = topo.node_ids[node]
            cand_ok[p, c] = True
            drain[p, c] = entry.metrics.drain_metric
            pp[p, c] = entry.metrics.path_preference
            sp[p, c] = entry.metrics.source_preference
            dist[p, c] = entry.metrics.distance
            minnh[p, c] = entry.min_nexthop or 0
            c += 1
    return EncodedPrefixCandidates(
        cand_node=cand_node,
        cand_ok=cand_ok,
        drain_metric=drain,
        path_pref=pp,
        source_pref=sp,
        distance=dist,
        min_nexthop=minnh,
        prefixes=prefixes,
    )


@dataclasses.dataclass
class EncodedMultiArea:
    """Per-area EncodedTopologies padded to COMMON buckets + stacked
    device arrays (leading axis = area, in `areas` order)."""

    areas: List[str]
    topos: List[EncodedTopology]
    src: np.ndarray  # [A, E]
    dst: np.ndarray  # [A, E]
    w: np.ndarray  # [A, E]
    edge_ok: np.ndarray  # [A, E]
    overloaded: np.ndarray  # [A, V]
    soft: np.ndarray  # [A, V]
    roots: np.ndarray  # [A] my node id per area
    #: stacked dense in-edge planes (None when any area declined the
    #: dense layout — the SPF dispatch then uses the segment kernels)
    in_src: Optional[np.ndarray] = None  # [A, V, K]
    in_w: Optional[np.ndarray] = None  # [A, V, K]
    in_ok: Optional[np.ndarray] = None  # [A, V, K]
    in_rank: Optional[np.ndarray] = None  # [A, V, K]
    in_has: Optional[np.ndarray] = None  # [A, V]

    @property
    def has_dense(self) -> bool:
        return self.in_src is not None

    @property
    def num_areas(self) -> int:
        return len(self.areas)

    def area_index(self, area: str) -> int:
        return self.areas.index(area)

    def max_out_degree(self) -> int:
        return max((t.max_out_degree() for t in self.topos), default=0)


def encode_multi_area(
    area_link_states,
    me: str,
    node_buckets: Sequence[int] = (16, 64, 256, 1024, 4096, 16384),
    edge_multiplier: int = 8,
) -> EncodedMultiArea:
    """Encode all areas to common node/edge buckets so the kernel's area
    axis is a clean batch dim.  `me` is interned into every area's symbol
    table (even where it has no adjacencies) so per-area SPF roots always
    resolve — an area where I'm isolated yields dist=[0 at me, INF else],
    exactly the scalar get_spf_result(me) semantics there."""
    areas = sorted(area_link_states.keys())
    sizes_v = []
    sizes_e = []
    for a in areas:
        ls = area_link_states[a]
        names = set(ls.get_adjacency_databases().keys()) | {me}
        sizes_v.append(len(names))
        sizes_e.append(2 * len(ls.all_links()))
    edge_buckets = [b * edge_multiplier for b in node_buckets]
    pv = bucket_for(max(max(sizes_v), 1), node_buckets)
    pe = bucket_for(max(max(sizes_e), 1), edge_buckets)
    topos = [
        encode_link_state(
            area_link_states[a],
            node_bucket=pv,
            edge_bucket=pe,
            extra_nodes=(me,),
        )
        for a in areas
    ]
    return EncodedMultiArea(
        areas=areas,
        topos=topos,
        src=np.stack([t.src for t in topos]),
        dst=np.stack([t.dst for t in topos]),
        w=np.stack([t.w for t in topos]),
        edge_ok=np.stack([t.edge_ok for t in topos]),
        overloaded=np.stack([t.overloaded for t in topos]),
        soft=np.stack([t.soft for t in topos]),
        roots=np.asarray([t.node_id(me) for t in topos], np.int32),
        **_stack_dense(topos),
    )


def _stack_dense(topos: List[EncodedTopology]) -> dict:
    """Stack per-area dense in-edge planes to a common K bucket; {} of
    Nones when any area declined the dense layout."""
    if not topos or not all(t.has_dense for t in topos):
        return {}
    K = max(t.in_src.shape[1] for t in topos)

    def widen(a, fill):
        pad = K - a.shape[1]
        if not pad:
            return a
        return np.concatenate(
            [a, np.full((a.shape[0], pad), fill, a.dtype)], axis=1
        )

    return dict(
        in_src=np.stack([widen(t.in_src, 0) for t in topos]),
        in_w=np.stack([widen(t.in_w, INF) for t in topos]),
        in_ok=np.stack([widen(t.in_ok, False) for t in topos]),
        in_rank=np.stack([widen(t.in_rank, -1) for t in topos]),
        in_has=np.stack([t.in_has for t in topos]),
    )


def patch_encoded_multi_area(
    prev: EncodedMultiArea, area_link_states, me: str
) -> Optional[EncodedMultiArea]:
    """Multi-area wrapper over :func:`patch_encoded_topology`: every
    area must patch (same area set, per-area node/link identity
    unchanged) or the whole attempt declines (None) and the caller runs
    ``encode_multi_area`` cold.  The stacked [A, ...] device views are
    restacked from the patched per-area arrays; layout arrays stay
    shared with the previous encoding."""
    areas = sorted(area_link_states.keys())
    if areas != prev.areas:
        return None
    topos = []
    for a, old_topo in zip(areas, prev.topos):
        patched = patch_encoded_topology(old_topo, area_link_states[a], me)
        if patched is None:
            return None
        topos.append(patched)
    dense = {}
    if prev.has_dense and all(t.has_dense for t in topos):
        K = prev.in_src.shape[2]

        def widen(a, fill):
            pad = K - a.shape[1]
            if not pad:
                return a
            return np.concatenate(
                [a, np.full((a.shape[0], pad), fill, a.dtype)], axis=1
            )

        dense = dict(
            in_src=prev.in_src,  # layout shared with the previous gen
            in_rank=prev.in_rank,
            in_has=prev.in_has,
            in_w=np.stack([widen(t.in_w, INF) for t in topos]),
            in_ok=np.stack([widen(t.in_ok, False) for t in topos]),
        )
    return EncodedMultiArea(
        areas=areas,
        topos=topos,
        src=prev.src,
        dst=prev.dst,
        w=np.stack([t.w for t in topos]),
        edge_ok=np.stack([t.edge_ok for t in topos]),
        overloaded=np.stack([t.overloaded for t in topos]),
        soft=np.stack([t.soft for t in topos]),
        roots=prev.roots,
        **dense,
    )


def link_failure_batch(
    topo: EncodedTopology, failed_links_per_snapshot: List[List[int]]
) -> np.ndarray:
    """Build a [B, E] edge-enable mask from per-snapshot failed undirected
    link ids — the 10k what-if perturbation encoding (base topology is
    encoded once; the batch is just this mask)."""
    B = len(failed_links_per_snapshot)
    E = topo.padded_edges
    native = _get_native()
    if native is not None and B:
        F = max((len(f) for f in failed_links_per_snapshot), default=0)
        flat = np.full((B, max(F, 1)), -1, np.int32)
        for b, failed in enumerate(failed_links_per_snapshot):
            if failed:
                flat[b, : len(failed)] = failed
        mask_u8 = np.empty((B, E), np.uint8)
        pos = np.ascontiguousarray(topo.link_edge_pos, np.int32)
        rc = native.csr_failure_masks(
            B,
            flat.shape[1],
            _np_ptr(flat, ctypes.c_int32),
            _np_ptr(pos, ctypes.c_int32),
            E,
            len(topo.links),
            _np_ptr(mask_u8, ctypes.c_uint8),
        )
        if rc == 0:
            return mask_u8.astype(bool)
    mask = np.ones((B, E), bool)
    for b, failed in enumerate(failed_links_per_snapshot):
        if not failed:
            continue
        failed_set = np.isin(topo.link_index, np.asarray(failed, np.int32))
        mask[b, failed_set] = False
    return mask
