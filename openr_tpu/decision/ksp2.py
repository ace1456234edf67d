"""Device-backed KSP2_ED_ECMP: batched masked re-solves + host path trace.

The reference computes the k-th edge-disjoint shortest paths by re-running
full Dijkstra with the links of paths 1..k-1 ignored, once per destination
(LinkState.cpp:675-699) — on a fat-tree where every rack prefix uses
KSP2_ED_ECMP that is O(destinations) host Dijkstras per rebuild, the hot
loop.  Here the re-solves run as ONE batched device call
(``batched_spf_distances_masked``: vmapped masked Bellman-Ford over a
[U, E] ignore-mask batch built on the device from a [U, K] matrix of
ignored link ids), and only the cheap part — greedy path tracing over
the shortest-path DAG (traceOnePath, LinkState.cpp:227-247) — stays on
the host, reconstructed from the distance fields.

Exactness: ``LinkState.run_spf`` iterates sorted adjacency, so its
``path_links`` order is (settle-order of predecessor, link order) — the
reconstruction here sorts by exactly that key, making the greedy trace
bit-identical to the scalar path.  The traced paths are seeded into the
LinkState k-path memo (``seed_kth_paths``), after which the unmodified
scalar KSP2 selection chain (SpfSolver._select_best_paths_ksp2, SR-MPLS
label stacks, cross-area merge, min-nexthop gate) runs without any host
Dijkstra.

Retained traces: a family's trace is a deterministic function of
``path_links(v)`` over the nodes v it visits, and ``path_links(v)`` reads
only v's incident links (up, metric, ignored), the distances of v and of
the neighbours it compares, and the neighbours' overload bits.  A
``Ksp2Record`` keeps, per destination and family, the traced paths (by
link key), the visited nodes and the distances the trace read.  Across
topology generations a family is kept when no link incident to a visited
node changed and every recorded distance is equal in the fresh field
(the unmasked root row for family 1, the re-solved masked row for family
2, which is kept only with its family 1).  The batched re-solve of every
destination is therefore the change detector, and any other change (node
membership, overload, node metric increment, adjacency label, encoding)
drops the whole record.  The destinations whose two families a
generation kept are the record's ``kept`` set, relative to ``prev_seq``.

Kept routes: a KSP2 route reads the prefix's advertisements, each
advertiser's reachability and drain state, the two families of paths to
each winner, the metrics of their links, the node segment labels along
them, the root's first-hop link (address, interface, area, neighbour)
and the solver's settings.  ``Ksp2RouteCache`` hands a prefix's entry
out again when its advertisement objects are the same, every advertiser
was kept in each generation since the entry was assembled (a kept
destination has equal distances on every node its traces read, so its
reachability and path costs are equal, and any node-level change drops
the record), the node labels, the root's link addresses and the settings
are equal (they move without a new topology generation, so the cache
compares them on every build), and the entry still holds the next hops
and best entry it was built with (RibPolicy reassigns them in place).
Anything else is assembled anew by ``SpfSolver.create_route_for_prefix``.
"""

from __future__ import annotations

import operator
from collections import Counter
from typing import (
    Dict,
    FrozenSet,
    List,
    NamedTuple,
    Optional,
    Sequence,
    Set,
    Tuple,
)

import numpy as np

from openr_tpu.decision.link_state import Link, LinkState, Path
from openr_tpu.decision.rib import RibUnicastEntry
from openr_tpu.ops.csr import EncodedTopology
from openr_tpu.tracing import disabled_probe, pipeline
from openr_tpu.types import PrefixForwardingAlgorithm

_BIG = np.float32(3.4e38)
_KSP2_ED_ECMP = PrefixForwardingAlgorithm.KSP2_ED_ECMP

LinkKey = Tuple[str, str, str, str]

#: why a generation dropped the whole retained record and re-traced every
#: destination (``decision.backend.ksp2.full_retrace.<reason>``)
FULL_RETRACE_REASONS = {
    "cold": "no record yet, the area's first KSP2 build",
    "purge": "a suspect device (corruption, quarantine, full replace)",
    "link_state": "another LinkState object or root for the area",
    "node_set": "a node joined or left",
    "overload": "a node's overload bit flipped",
    "node_metric": "a node metric increment changed",
    "adj_label": "an adjacency label changed",
    "encoding": "a re-encode moved the node ids",
}


_LINK_KEY = operator.attrgetter("_key")
#: a link's snapshot: what decides Link.is_up and get_max_metric, then
#: the adjacency labels
_LINK_STATE = operator.attrgetter(
    "overload1", "overload2", "usable", "metric1", "metric2",
    "adj_label1", "adj_label2",
)


class _Family(NamedTuple):
    """One destination's traced path family and what its trace read."""

    paths: Tuple[Tuple[LinkKey, ...], ...]
    #: nodes whose path links the trace consulted
    visited: FrozenSet[str]
    #: node ids of the visited nodes, the neighbours whose distance they
    #: compared and the destination, with those distances
    check_ids: np.ndarray
    check_dist: np.ndarray


class Ksp2Record:
    """One area's traced k-paths, kept across topology generations.

    Owned by the backend per area so it outlives the per-generation
    engines; bound to one ``LinkState`` object and root.  ``seq`` is the
    generation whose link and node snapshots ``dests`` were traced or
    verified against; ``reason`` (set while nothing is retained) is why
    the next generation re-traces every destination.  ``kept`` holds the
    destinations whose two families generation ``seq`` kept from
    generation ``prev_seq``."""

    def __init__(self, link_state: LinkState, root: str, reason: str = "cold"):
        self.link_state = link_state
        self.root = root
        self.seq: Optional[int] = None
        self.prev_seq: Optional[int] = None
        self.kept: Set[str] = set()
        #: what this generation's seeds keep from: the previous
        #: generation's dests and the nodes its link changes touched
        self.prior: Dict[str, Tuple[_Family, _Family]] = {}
        self.touched: Set[str] = set()
        self.links: Optional[Dict[LinkKey, tuple]] = None
        self.nodes: Optional[Dict[str, tuple]] = None
        self.node_ids: Optional[Dict[str, int]] = None
        self.dests: Dict[str, Tuple[_Family, _Family]] = {}
        self.reason: Optional[str] = reason

    def drop(self, reason: str) -> None:
        """Forget every retained trace, and the paths this record seeded
        into the LinkState memo: the next build re-traces them all."""
        if self.dests:
            self.link_state.clear_spf_memoization()
        self.seq = self.prev_seq = None
        self.links = self.nodes = self.node_ids = None
        self.dests, self.prior = {}, {}
        self.kept, self.touched = set(), set()
        self.reason = reason


def _node_change(old: dict, new: dict) -> Optional[str]:
    if old == new:
        return None
    if old.keys() != new.keys():
        return "node_set"
    if any(old[n][0] != v[0] for n, v in new.items()):
        return "overload"
    return "node_metric"


class Ksp2DeviceEngine:
    """Per-(area LinkState, encoded topology) KSP2 seeding engine.

    ``seed(dests)`` guarantees ``link_state.get_kth_paths(root, d, k)`` for
    k in (1, 2) is memoized for every d in dests without running host
    Dijkstra.  Results live in the LinkState memo, so repeat rebuilds on
    an unchanged topology are free; the memo is cleared by LinkState on
    topology change, and the next generation's first ``seed`` re-solves
    every destination on the device but re-traces only the families whose
    inputs moved (``record``, see the module doc).
    """

    def __init__(
        self,
        link_state: LinkState,
        topo: EncodedTopology,
        root: str,
        probe=None,
        counts: Optional[Counter] = None,
        record: Optional[Ksp2Record] = None,
    ) -> None:
        self.link_state = link_state
        self.topo = topo
        self.root = root
        self.probe = probe if probe is not None else disabled_probe()
        #: device_batches, resolve_rows (real destinations),
        #: resolve_padded_rows (after the BATCH_BUCKETS pad),
        #: seeded_dests = reused_dests (both families kept) +
        #: retraced_dests, and full_retrace (+ .<reason>); a backend
        #: passes one Counter to every engine it makes, so the counts
        #: outlive the per-topology engines
        self.counts = counts if counts is not None else Counter()
        self.record = (
            record if record is not None else Ksp2Record(link_state, root)
        )
        self._link_id: Dict[LinkKey, int] = {
            link.key: i for i, link in enumerate(topo.links)
        }

    # -- public entry ------------------------------------------------------

    def seed(self, dests: Sequence[str]) -> None:
        ls = self.link_state
        root = self.root
        todo = [
            d
            for d in dict.fromkeys(dests)  # stable de-dup
            if d != root and not ls.has_kth_paths(root, d, 2)
        ]
        if not todo:
            return
        with self.probe.phase(pipeline.KSP2_TRACE):
            prior, touched = self._advance()
            # family 1 over the root's unmasked distances: the memoized
            # base SPF, which route assembly reads as well
            dist1 = self._root_distances()
            olds: List[Optional[Tuple[_Family, _Family]]] = []
            fam1: List[_Family] = []
            ignore_ids: List[List[int]] = []
            for d in todo:
                old = prior.get(d)
                if old is not None and not self._holds(old[0], touched, dist1):
                    old = None
                f1 = old[0] if old is not None else self._trace(
                    d, dist1, frozenset()
                )
                olds.append(old)
                fam1.append(f1)
                ignore_ids.append(
                    sorted({self._link_id[k] for p in f1.paths for k in p})
                )

        with self.probe.phase(pipeline.KSP2_RESOLVE):
            dist2 = self._device_resolve(ignore_ids)
        with self.probe.phase(pipeline.KSP2_TRACE):
            all_links = ls.all_links()
            link_of = dict(zip(map(_LINK_KEY, all_links), all_links))
            dests_rec = self.record.dests
            for row, d in enumerate(todo):
                f1, old = fam1[row], olds[row]
                row2 = dist2[row]
                if old is not None and self._holds(old[1], touched, row2):
                    f2 = old[1]
                    self.record.kept.add(d)
                    self.counts["reused_dests"] += 1
                else:
                    ignored = {link_of[k] for p in f1.paths for k in p}
                    f2 = self._trace(d, row2, ignored)
                    self.counts["retraced_dests"] += 1
                for k, fam in ((1, f1), (2, f2)):
                    paths = [[link_of[key] for key in p] for p in fam.paths]
                    ls.seed_kth_paths(root, d, k, paths)
                dests_rec[d] = (f1, f2)
                self.counts["seeded_dests"] += 1

    # -- retained record ---------------------------------------------------

    def _advance(self) -> Tuple[Dict[str, tuple], Set[str]]:
        """Bring the record to this topology generation.  Returns the
        destinations whose families may be kept, and the endpoints of
        every link whose (up, metric) changed since they were traced.
        A later seed of the same generation (the next shard of one
        decode) gets the same two while the node ids hold."""
        rec, ls = self.record, self.link_state
        if rec.links is not None and rec.seq == ls.topology_seq:
            ids = self.topo.node_ids
            if ids is rec.node_ids or ids == rec.node_ids:
                return rec.prior, rec.touched
            return {}, set()
        all_links = ls.all_links()
        links = dict(
            zip(map(_LINK_KEY, all_links), map(_LINK_STATE, all_links))
        )
        nodes = {
            n: (ls.is_node_overloaded(n), ls.get_node_metric_increment(n))
            for n in ls.get_adjacency_databases()
        }
        node_ids = self.topo.node_ids
        reason = rec.reason
        touched: Set[str] = set()
        if reason is None:
            reason = _node_change(rec.nodes, nodes)
        if reason is None and node_ids is not rec.node_ids and (
            node_ids != rec.node_ids
        ):
            reason = "encoding"
        if reason is None and links != rec.links:
            old_links = rec.links
            for key in {key for key, _ in links.items() ^ old_links.items()}:
                was, now = old_links.get(key), links.get(key)
                if was is not None and now is not None and was[5:] != now[5:]:
                    reason = "adj_label"
                    break
                touched.update((key[0], key[2]))
        prior = rec.dests
        if reason is not None:
            prior = {}
            self.counts["full_retrace"] += 1
            self.counts[f"full_retrace.{reason}"] += 1
        rec.prev_seq = rec.seq
        rec.seq, rec.links, rec.nodes = ls.topology_seq, links, nodes
        rec.node_ids, rec.dests, rec.reason = node_ids, {}, None
        rec.prior, rec.touched, rec.kept = prior, touched, set()
        return prior, touched

    @staticmethod
    def _holds(fam: _Family, touched: Set[str], dist: np.ndarray) -> bool:
        """True when every input ``fam``'s trace read is unchanged."""
        return fam.visited.isdisjoint(touched) and np.array_equal(
            dist[fam.check_ids], fam.check_dist
        )

    def _root_distances(self) -> np.ndarray:
        """The root's unmasked distances by node id, exact (float64) from
        the memoized base SPF: family 1 traced over them is
        ``get_kth_paths(root, d, 1)`` bit for bit."""
        ids = self.topo.node_ids
        dist = np.full(self.topo.padded_nodes, np.inf)
        for n, res in self.link_state.get_spf_result(self.root).items():
            dist[ids[n]] = res.metric
        return dist

    def _trace(self, dest: str, dist: np.ndarray, ignored) -> _Family:
        paths, visited, reads = self._trace_all(dest, dist, ignored)
        reads |= visited
        reads.add(dest)
        ids = self.topo.node_ids
        check_ids = np.fromiter((ids[n] for n in reads), np.int64, len(reads))
        return _Family(
            tuple(tuple(link.key for link in p) for p in paths),
            frozenset(visited),
            check_ids,
            dist[check_ids],
        )

    # -- device batch ------------------------------------------------------

    #: destination-batch buckets: the jit cache must stay warm across
    #: rebuilds where the number of un-memoized destinations varies
    #: (prefix churn re-arms a few dests at a time) — same discipline as
    #: node_buckets/cand_buckets in the encoder
    BATCH_BUCKETS = (8, 32, 128, 512, 2048, 8192, 32768)
    #: buckets of the ignored-id matrix's width (links of a family 1)
    IGNORE_BUCKETS = (16, 64, 256, 1024, 4096, 16384, 65536)

    def _device_resolve(self, ignore_ids: List[List[int]]) -> np.ndarray:
        import jax
        import jax.numpy as jnp

        from openr_tpu.ops.csr import bucket_for
        from openr_tpu.ops.jit_guard import call_jit_guarded
        from openr_tpu.ops.spf import (
            batched_spf_distances_masked,
            link_ignore_masks,
        )

        topo = self.topo
        n = len(ignore_ids)
        padded = bucket_for(n, self.BATCH_BUCKETS)
        self.counts["resolve_rows"] += n
        self.counts["resolve_padded_rows"] += padded
        width = bucket_for(
            max(1, max(len(ids) for ids in ignore_ids)), self.IGNORE_BUCKETS
        )
        # padding rows (and padding ids) ignore nothing: the unmasked
        # topology, cheap no-op work
        ids = np.full((padded, width), -1, np.int32)
        for row, row_ids in enumerate(ignore_ids):
            ids[row, : len(row_ids)] = row_ids
        masks = call_jit_guarded(
            link_ignore_masks,
            jnp.asarray(topo.link_edge_pos),
            jnp.asarray(ids),
            num_edges=topo.padded_edges,
        )
        roots = np.full(padded, topo.node_id(self.root), np.int32)
        dist = call_jit_guarded(
            batched_spf_distances_masked,
            jnp.asarray(topo.src),
            jnp.asarray(topo.dst),
            jnp.asarray(topo.w),
            jnp.asarray(topo.edge_ok),
            masks,
            jnp.asarray(topo.overloaded),
            jnp.asarray(roots),
        )
        self.counts["device_batches"] += 1
        # one host fetch for the whole batch: one blocking round trip,
        # not one per root
        return np.asarray(jax.device_get(dist))[:n]

    # -- host trace over a distance field ----------------------------------

    def _path_links(
        self,
        node: str,
        dist: np.ndarray,
        ignored: Set[Link],
        reads: Set[str],
    ) -> List[Tuple[Link, str]]:
        """Reconstruct NodeSpfResult.path_links for `node` in run_spf's
        append order: predecessors settle in (metric, name) heap order and
        each relaxes its sorted links (run_spf iterates
        ordered_links_from_node), so the key is (dist[prev], prev, link).
        Adds every neighbour whose distance it compared to ``reads``."""
        ls = self.link_state
        ids = self.topo.node_ids
        num = dist.dtype.type  # float32 device rows, float64 root row
        dv = dist[ids[node]]
        out: List[Tuple[np.floating, str, Link]] = []
        for link in ls.ordered_links_from_node(node):
            prev = link.get_other_node_name(node)
            if not link.is_up() or link in ignored:
                continue
            if ls.is_node_overloaded(prev) and prev != self.root:
                continue
            reads.add(prev)
            du = dist[ids[prev]]
            if du >= _BIG:
                continue
            if num(du + num(link.get_max_metric())) == dv:
                out.append((du, prev, link))
        out.sort(key=lambda t: (t[0], t[1], t[2].key))
        return [(link, prev) for _, prev, link in out]

    def _trace_all(
        self, dest: str, dist: np.ndarray, ignored
    ) -> Tuple[List[Path], Set[str], Set[str]]:
        """Every path of the family (LinkState.get_kth_paths' trace loop),
        the nodes whose path links it consulted, and the neighbours whose
        distance those compared."""
        reads: Set[str] = set()
        if dist[self.topo.node_id(dest)] >= _BIG:
            return [], set(), reads
        visited: Set[Link] = set()
        pl_cache: Dict[str, List[Tuple[Link, str]]] = {}

        def path_links(v: str) -> List[Tuple[Link, str]]:
            cached = pl_cache.get(v)
            if cached is None:
                cached = pl_cache[v] = self._path_links(
                    v, dist, ignored, reads
                )
            return cached

        def trace_one(v: str) -> Optional[Path]:
            # mirrors LinkState._trace_one_path exactly
            if v == self.root:
                return []
            for link, prev in path_links(v):
                if link in visited:
                    continue
                visited.add(link)
                sub = trace_one(prev)
                if sub is not None:
                    sub.append(link)
                    return sub
            return None

        paths: List[Path] = []
        path = trace_one(dest)
        while path:
            paths.append(path)
            path = trace_one(dest)
        return paths, set(pl_cache), reads


# -- assembled routes kept across builds -----------------------------------


class _Route:
    """One prefix's assembled KSP2 route (None: no route) and what its
    assembly read: the advertisement objects, each one's area topology
    generation, and the context the cache held."""

    __slots__ = ("entry", "nexthops", "best", "selection", "adverts",
                 "seqs", "context")


class Ksp2RouteCache:
    """KSP2 route entries kept across builds, keyed by prefix (module
    doc, "Kept routes").  Owned by the device generation, so a suspect
    purge drops it with the records whose traces it rests on."""

    def __init__(self) -> None:
        self.routes: Dict[str, _Route] = {}
        # what every entry reads besides its advertisements and paths:
        # the solver's settings and, per area, the LinkState object, the
        # node labels and the root's link addresses; a new key is a new
        # context object, which no older entry holds
        self._key = None
        self._context = None

    def assemble(
        self,
        prefixes: Sequence[str],
        solver,
        area_link_states: Dict[str, LinkState],
        prefix_state,
        records: Dict[str, Ksp2Record],
        counts: Counter,
        reuse: bool,
    ) -> Dict[str, Optional[RibUnicastEntry]]:
        """Each prefix's route: its kept entry where every input it read
        is unchanged (and ``reuse``: False when the caller mutates the
        entries it gets), else ``solver.create_route_for_prefix``'s.
        Counts ``reused_routes`` and ``rebuilt_routes``."""
        context = self._context_for(solver, area_link_states)
        now = {a: ls.topology_seq for a, ls in area_link_states.items()}
        # areas whose record advanced to this generation: the generation
        # it kept destinations from, and those destinations
        since = {
            a: (rec.prev_seq, rec.kept)
            for a, rec in records.items()
            if rec.prev_seq is not None
            and rec.link_state is area_link_states.get(a)
            and rec.seq == now[a]
        }
        all_entries = prefix_state.prefixes()
        best_cache = solver.best_routes_cache
        routes = self.routes
        out: Dict[str, Optional[RibUnicastEntry]] = {}
        for prefix in prefixes:
            c = routes.get(prefix)
            entries = all_entries.get(prefix)
            if (
                reuse
                and c is not None
                and c.context is context
                and entries is not None
                and self._holds(c, entries, now, since)
            ):
                c.seqs = tuple(now.get(a) for (_, a), _ in c.adverts)
                if c.selection is None:
                    best_cache.pop(prefix, None)
                else:
                    best_cache[prefix] = c.selection
                out[prefix] = c.entry
                counts["reused_routes"] += 1
                continue
            entry = solver.create_route_for_prefix(
                prefix, area_link_states, prefix_state
            )
            counts["rebuilt_routes"] += 1
            out[prefix] = entry
            selection = best_cache.get(prefix)
            if not entries or (
                selection is not None
                and entries[min(selection.all_node_areas)].forwarding_algorithm
                != _KSP2_ED_ECMP
            ):
                # the SP_ECMP chain reads the whole shortest-path DAG,
                # which a kept trace does not cover
                routes.pop(prefix, None)
                continue
            c = _Route()
            if entry is not None:
                # frozen, as the SP_ECMP decode's: a kept entry is shared
                # by successive RouteDbs and must not change in place
                entry.nexthops = frozenset(entry.nexthops)
            c.entry = entry
            c.nexthops = None if entry is None else entry.nexthops
            c.best = None if entry is None else entry.best_prefix_entry
            c.selection = selection
            c.adverts = tuple(entries.items())
            c.seqs = tuple(now.get(a) for (_, a), _ in c.adverts)
            c.context = context
            routes[prefix] = c
        return out

    def retain(self, prefixes) -> None:
        """Forget the entries of every prefix not in ``prefixes``: a full
        build's KSP2 prefixes."""
        routes = self.routes
        for p in [p for p in routes if p not in prefixes]:
            del routes[p]

    def _context_for(self, solver, area_link_states):
        me = solver.my_node_name
        key = (
            me,
            solver.enable_v4,
            solver.v4_over_v6_nexthop,
            solver.enable_best_route_selection,
            solver.route_selection_algorithm,
            [
                (
                    area,
                    ls,
                    {
                        n: db.node_label
                        for n, db in ls.get_adjacency_databases().items()
                    },
                    sorted(
                        (link.key, link.get_nh_v4_from_node(me),
                         link.get_nh_v6_from_node(me))
                        for link in ls.links_from_node(me)
                    ),
                )
                for area, ls in sorted(area_link_states.items())
            ],
        )
        if self._context is None or key != self._key:
            self._key, self._context = key, object()
        return self._context

    @staticmethod
    def _holds(c: _Route, entries, now, since) -> bool:
        """True when ``c`` can be handed out again (module doc)."""
        if len(entries) != len(c.adverts):
            return False
        for ((node, area), entry), seq in zip(c.adverts, c.seqs):
            if entries.get((node, area)) is not entry:
                return False
            if seq != now.get(area):
                kept = since.get(area)
                if kept is None or kept[0] != seq or node not in kept[1]:
                    return False
        route = c.entry
        return route is None or (
            route.nexthops is c.nexthops and route.best_prefix_entry is c.best
        )
