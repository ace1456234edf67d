"""The plain reference for KSP2_ED_ECMP with SR-MPLS label stacks, SP_ECMP
loopbacks and node-segment-label MPLS routes, from one root.

It imports nothing of the program and reads nothing the program made:
its input is the benchmark's own ``Lsdb`` (cellbench.topology), the
node labels the benchmark publishes and which nodes' prefixes use KSP2.
It follows upstream Open/R's description:

- shortest paths from the root (LinkState::runSpf);
- KSP2_ED_ECMP: the first family is traceOnePath's greedy trace over the
  shortest-path DAG, repeated until it finds no path, every link used at
  most once (LinkState.cpp:227-247); the second family is the same trace
  after a re-solve with every link of the first family removed
  (LinkState.cpp:675-699).  A second path that holds a first path as a
  contiguous sub-path is dropped (upstream's anycast guard; with one
  advertiser per prefix it never fires).  Each path gives a next hop: its
  first link, its metric (the sum of its links' metrics) and, for
  SR_MPLS and a path of more than one link, a PUSH of the node labels of
  every node past the first hop;
- SP_ECMP: the shortest metric and every first hop of a shortest path;
- MPLS (SpfSolver.cpp:354-445): one route per reachable node label; the
  root's own POP_AND_LOOKUP, every other node's label over its SP_ECMP
  first hops, PHP where the first hop is that node, else SWAP to it.

Tie orders the trace relies on (upstream leaves them to its heap and
link sets; these are the ones written here):

- settle order is (metric, node name), names compared as strings, so a
  node's predecessors on the DAG are tried in (metric from the root,
  predecessor name) order, the earliest settled first;
- link order within a node: at most one link joins two nodes here (the
  reference refuses parallel links), so no two predecessors tie on the
  name and no order within a node is needed.

A next hop is the tuple (address, interface, metric, weight, area,
neighbour, MPLS action); the action is None, ("PUSH", labels top of
stack first), ("SWAP", label), ("PHP",) or ("POP_AND_LOOKUP",).  v4
prefixes take the adjacency's empty v4 address; MPLS routes the v6
link-local one the root advertises toward the neighbour.  Metrics are
whole numbers and the distance fields exact (float64 sums of small
integers).  Nodes are never overloaded or drained, every node carries a
valid label, and the routes of every prefix of a node are the same.

The distance fields come from ``scipy.sparse.csgraph.dijkstra``; the
trace and everything after it is plain Python.
"""

from __future__ import annotations

import math
import zlib
from typing import Dict, FrozenSet, List, Optional, Set, Tuple

import numpy as np

from cellbench.topology import if_name

NextHops = FrozenSet[tuple]
Path = List[int]  # link ids from the root outward


def v6_address(root: str, nbr: str) -> str:
    """The link-local v6 next hop ``root`` advertises toward ``nbr``
    (cellbench.topology.adjacency_db)."""
    h = zlib.crc32(f"{nbr}|{if_name(nbr, root)}".encode())
    return f"fe80::{(h >> 16) & 0xFFFF:x}:{h & 0xFFFF:x}"


class Reference:
    """Every route one root programs, for one state of the network."""

    def __init__(self, lsdb, root: str, area: str, labels: Dict[str, int],
                 ksp2_nodes: Set[str], sr_mpls: bool = True,
                 second_family: bool = True) -> None:
        self.lsdb = lsdb.copy()  # the caller's goes on changing
        self.root = root
        self.area = area
        self.labels = labels
        self.ksp2_nodes = ksp2_nodes
        self.sr_mpls = sr_mpls
        #: False is the control: KSP2 cut down to its first family
        self.second_family = second_family
        names = lsdb.names
        lsdb = self.lsdb
        self.idx = {n: i for i, n in enumerate(names)}
        seen = set()
        #: node -> [(neighbour, metric, link id)] over up links
        self.adj: Dict[str, List[Tuple[str, int, int]]] = {
            n: [] for n in names}
        src, dst, w, lid = [], [], [], []
        for li, (a, b) in enumerate(lsdb.links):
            if (a, b) in seen:
                raise ValueError(f"parallel links between {a} and {b}")
            seen.add((a, b))
            if not lsdb.up[li]:
                continue
            m = lsdb.metric[li]
            self.adj[a].append((b, m, li))
            self.adj[b].append((a, m, li))
            for u, v in ((a, b), (b, a)):
                src.append(self.idx[u])
                dst.append(self.idx[v])
                w.append(m)
                lid.append(li)
        # directed edges in row order, for CSR without a sort per solve
        order = np.argsort(src, kind="stable")
        self._src = np.asarray(src, np.int64)[order]
        self._dst = np.asarray(dst, np.int64)[order]
        self._w = np.asarray(w, np.float64)[order]
        self._lid = np.asarray(lid, np.int64)[order]
        self.dist0 = self._distances(frozenset())
        self._preds0: Dict[str, list] = {}
        self._first_hops = self._ecmp_first_hops()
        #: node -> expected next hops, filled as asked
        self._unicast: Dict[str, Optional[NextHops]] = {}
        self._mpls: Dict[str, Optional[NextHops]] = {}

    # -- distance fields and the DAG -------------------------------------

    def _distances(self, ignored: FrozenSet[int]) -> List[float]:
        from scipy.sparse import csr_matrix
        from scipy.sparse.csgraph import dijkstra

        n = len(self.lsdb.names)
        keep = ~np.isin(self._lid, np.fromiter(ignored, np.int64,
                                               len(ignored)))
        indptr = np.zeros(n + 1, np.int64)
        np.cumsum(np.bincount(self._src[keep], minlength=n),
                  out=indptr[1:])
        g = csr_matrix((self._w[keep], self._dst[keep], indptr),
                       shape=(n, n))
        return dijkstra(g, directed=True,
                        indices=self.idx[self.root]).tolist()

    def _preds(self, v: str, dist: List[float], ignored, cache: dict):
        """[(predecessor, link id)] of ``v`` on the shortest-path DAG of
        ``dist``, earliest settled first."""
        got = cache.get(v)
        if got is None:
            dv = dist[self.idx[v]]
            got = sorted(
                ((dist[self.idx[u]], u, li) for u, m, li in self.adj[v]
                 if li not in ignored and dist[self.idx[u]] + m == dv),
                key=lambda t: (t[0], t[1]))
            got = cache[v] = [(u, li) for _d, u, li in got]
        return got

    def _trace_all(self, dest: str, dist: List[float], ignored,
                   cache: dict) -> List[Path]:
        """traceOnePath until it finds nothing: greedy depth-first over
        the DAG from ``dest`` back to the root, each link used once."""
        if math.isinf(dist[self.idx[dest]]):
            return []
        visited: Set[int] = set()

        def one(v: str) -> Optional[Path]:
            if v == self.root:
                return []
            for u, li in self._preds(v, dist, ignored, cache):
                if li in visited:
                    continue
                visited.add(li)
                sub = one(u)
                if sub is not None:
                    sub.append(li)
                    return sub
            return None

        paths = []
        while (p := one(dest)):
            paths.append(p)
        return paths

    def _ecmp_first_hops(self) -> Dict[str, FrozenSet[str]]:
        """{node: the root's neighbours that begin a shortest path to
        it}, for every reachable node but the root."""
        dist = self.dist0
        order = sorted((d, n) for n, d in zip(self.lsdb.names, dist)
                       if not math.isinf(d) and n != self.root)
        out: Dict[str, FrozenSet[str]] = {}
        for _d, v in order:
            hops = set()
            for u, _li in self._preds(v, dist, (), self._preds0):
                hops |= {v} if u == self.root else out[u]
            out[v] = frozenset(hops)
        return out

    # -- routes ------------------------------------------------------------

    def kth_paths(self, dest: str) -> Tuple[List[Path], List[Path]]:
        """(first family, second family) of edge-disjoint shortest
        paths to ``dest``."""
        k1 = self._trace_all(dest, self.dist0, (), self._preds0)
        if not k1 or not self.second_family:
            return k1, []
        ignored = frozenset(li for p in k1 for li in p)
        dist = self._distances(ignored)
        k2 = self._trace_all(dest, dist, ignored, {})
        return k1, [p for p in k2 if not any(_contains(p, q) for q in k1)]

    def _path_hop(self, path: Path, use_mpls: bool) -> tuple:
        cur, nodes = self.root, []
        for li in path:
            cur = self.lsdb.other(li, cur)
            nodes.append(cur)
        metric = sum(self.lsdb.metric[li] for li in path)
        action = None
        if use_mpls and len(nodes) > 1:
            action = ("PUSH", tuple(self.labels[n] for n in nodes[1:]))
        return ("", if_name(self.root, nodes[0]), metric, 0, self.area,
                nodes[0], action)

    def unicast(self, node: str) -> Optional[NextHops]:
        """The next hops of every prefix ``node`` advertises, None when
        nothing is programmed (unreachable, or the root's own)."""
        if node not in self._unicast:
            self._unicast[node] = self._unicast_route(node)
        return self._unicast[node]

    def mpls(self, node: str) -> Optional[NextHops]:
        """The next hops of ``node``'s label route, None when there is
        none (the node is unreachable)."""
        if node not in self._mpls:
            self._mpls[node] = self._mpls_route(node)
        return self._mpls[node]

    def _unicast_route(self, node: str) -> Optional[NextHops]:
        if node == self.root or node not in self._first_hops:
            return None
        if node in self.ksp2_nodes:
            k1, k2 = self.kth_paths(node)
            hops = frozenset(self._path_hop(p, self.sr_mpls)
                             for p in k1 + k2)
            return hops or None
        metric = int(self.dist0[self.idx[node]])
        return frozenset(("", if_name(self.root, n), metric, 0, self.area,
                          n, None) for n in self._first_hops[node])

    def _mpls_route(self, node: str) -> Optional[NextHops]:
        if node == self.root:
            return frozenset({("::", "", 0, 0, self.area, "",
                               ("POP_AND_LOOKUP",))})
        hops = self._first_hops.get(node)
        if not hops:
            return None
        metric = int(self.dist0[self.idx[node]])
        label = self.labels[node]
        return frozenset(
            (v6_address(self.root, n), if_name(self.root, n), metric, 0,
             self.area, n, ("PHP",) if n == node else ("SWAP", label))
            for n in hops)


def _contains(path: Path, sub: Path) -> bool:
    """``sub`` appears in ``path`` as a contiguous run of links."""
    k = len(sub)
    return any(path[i:i + k] == sub for i in range(len(path) - k + 1))


def canonical(next_hops) -> Optional[NextHops]:
    """Programmed next hops (the program's ``NextHop`` wire type) in the
    reference's tuple form.  The program's ``MplsAction.push_labels``
    lists the top of the stack first (decision/spf_solver.py builds it
    from the second hop outward), which is the form used here."""
    if next_hops is None:
        return None
    out = set()
    for nh in next_hops:
        ma = nh.mpls_action
        if ma is None:
            action = None
        elif ma.action.name == "PUSH":
            action = ("PUSH", tuple(ma.push_labels or ()))
        elif ma.action.name == "SWAP":
            action = ("SWAP", ma.swap_label)
        else:
            action = (ma.action.name,)
        out.add((nh.address, nh.if_name, nh.metric, nh.weight, nh.area,
                 nh.neighbor_node_name, action))
    return frozenset(out)
