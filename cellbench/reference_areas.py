"""The plain reference for an F4 fabric split into Open/R areas, one per
pod and one per spine plane, whose fabric switches redistribute their
best routes across areas, seen from one of them under a RibPolicy.

It imports nothing of the program and reads nothing the program made:
its input is the benchmark's own ``Lsdb`` (cellbench.topology), the
configuration's prefix counts and tag, and the policy as the
configuration states it.  It follows upstream Open/R's description.

The split (``Split``): a link of rack switch ``rsw{k}_{r}`` is in area
``pod{k}``, a link of spine switch ``ssw{p}_{s}`` in ``plane{p}``; a node
is in the areas of its links, so every fabric switch ``fsw{k}_{p}`` is
an ABR of ``pod{k}`` and ``plane{p}``.  Every node originates its
prefixes into each of its areas with distance 0 and an empty area stack.

Selection, as one node ``X`` makes it for one prefix
(SpfSolver::createRouteForPrefix, LsdbUtil selectRoutes):

1. candidates are the entries advertised into X's areas by nodes that X
   reaches in that area (X's own entries included);
2. best PrefixMetrics: drain, then the highest path_preference, then the
   highest source_preference, then the lowest distance; the winners are
   every candidate that ties on all four.  Here one origin's entries all
   carry the same drain, path and source preference (the configuration
   sets none), so the distance alone decides;
3. X among the winners: no route (the prefix is X's own);
4. per area holding winners, the lowest IGP metric from X to a winner
   of that area, and the ECMP next hops: every neighbour ``n`` of X over
   an up link of the area with ``metric(X, n) + dist(n, w)`` equal to it
   for a winner ``w`` at that metric.  Across areas the lowest metric
   wins and equal metrics take the union of both areas' next hops.  A
   next hop is (address, interface, metric, weight, area, neighbour,
   MPLS action): v4 prefixes take the adjacency's empty v4 address, the
   metric is the route's IGP metric, the weight 0 and the action None;
5. the route's best entry is the winner with the lowest (node name,
   area name), compared as strings (selectBestNodeArea).

Redistribution (PrefixManager::redistributePrefixesAcrossAreas): an ABR
with a route re-advertises the best entry into each of its areas that
is neither the best entry's area nor on its area stack, with the best
area appended to the stack and its IGP metric added to the distance.
An entry that went from area a into area b carries both on its stack,
so nothing derived from it reaches either again: an ABR's inputs never
depend on its own output, and the fixed point is unique.  ``solve``
reaches it by synchronous rounds, each ABR recomputed from the previous
round's advertisements, until a round changes nothing.

RibPolicy (RibPolicy::applyPolicy), at the vantage only and after
selection: the first statement whose tags meet the route's best entry's
tags sets each next hop's weight: the neighbour's weight if it names
the neighbour, else the area's if it names the area, else its default;
weight 0 removes the next hop, and a route left with none is removed.
A route the policy removes is not in the FIB, so the vantage does not
redistribute it.

Distances and metrics are whole numbers (int64); the per-area distance
fields come from ``scipy.sparse.csgraph.dijkstra`` and the rest is
numpy over every destination at once.
"""

from __future__ import annotations

import re
from typing import Dict, FrozenSet, List, Optional, Tuple

import numpy as np

INF = np.int64(1) << 40
_NAME = re.compile(r"([a-z]+)(\d+)_(\d+)$")


class Split:
    """The areas of an F4 fabric and who advertises what into them.

    ``lsdb`` gives the nodes and links; a node's destination group is its
    prefixes (``counts[role]`` of them, carrying ``tags[role]``), all of
    which one node originates alike, so the fixed point runs per group.
    """

    def __init__(self, lsdb, counts: Dict[str, int],
                 tags: Dict[str, FrozenSet[str]]) -> None:
        self.names: List[str] = list(lsdb.names)
        self.index = {n: i for i, n in enumerate(self.names)}
        self.role = [_NAME.match(n).group(1) for n in self.names]
        self.link_area: List[str] = []
        for a, b in lsdb.links:
            r = next((n for n in (a, b) if n.startswith(("rsw", "ssw"))),
                     None)
            if r is None:
                raise ValueError(f"link {a}-{b} joins no rsw or ssw")
            m = _NAME.match(r)
            kind = "pod" if m.group(1) == "rsw" else "plane"
            self.link_area.append(f"{kind}{m.group(2)}")
        self.areas: List[str] = sorted(set(self.link_area))
        self.area_index = {a: i for i, a in enumerate(self.areas)}
        node_areas: Dict[str, set] = {n: set() for n in self.names}
        for (a, b), area in zip(lsdb.links, self.link_area):
            node_areas[a].add(area)
            node_areas[b].add(area)
        self.node_areas = {n: tuple(sorted(s)) for n, s in node_areas.items()}
        #: per area, its nodes (in ``names`` order) and its links
        self.members: Dict[str, List[int]] = {a: [] for a in self.areas}
        for i, n in enumerate(self.names):
            for a in self.node_areas[n]:
                self.members[a].append(i)
        self.local = {a: {g: k for k, g in enumerate(m)}
                      for a, m in self.members.items()}
        self.links: Dict[str, List[int]] = {a: [] for a in self.areas}
        for li, area in enumerate(self.link_area):
            self.links[area].append(li)
        self.abrs = [i for i, n in enumerate(self.names)
                     if len(self.node_areas[n]) == 2]
        self.counts = [counts[r] for r in self.role]
        self.tags = [frozenset(tags.get(r, ())) for r in self.role]
        # (node name, area name) order of selectBestNodeArea
        pairs = sorted((self.names[i], a) for a in self.areas
                       for i in self.members[a])
        rank = {p: k for k, p in enumerate(pairs)}
        self.rank = {a: np.array([rank[(self.names[i], a)]
                                  for i in self.members[a]], np.int64)
                     for a in self.areas}


def area_costs(split: Split, lsdb) -> Dict[str, np.ndarray]:
    """Per area, the all-pairs shortest metrics among its nodes over its
    up links (INF where unreachable)."""
    from scipy.sparse import csr_matrix
    from scipy.sparse.csgraph import dijkstra

    out = {}
    for a in split.areas:
        loc = split.local[a]
        n = len(loc)
        rows, cols, w = [], [], []
        for li in split.links[a]:
            if not lsdb.up[li]:
                continue
            x, y = lsdb.links[li]
            i, j = loc[split.index[x]], loc[split.index[y]]
            rows += [i, j]
            cols += [j, i]
            w += [lsdb.metric[li]] * 2
        d = dijkstra(csr_matrix((np.array(w, float), (rows, cols)),
                                shape=(n, n)), directed=True)
        out[a] = np.where(np.isinf(d), INF, d).astype(np.int64)
    return out


class Stacks:
    """Area stacks, interned: id -> tuple of area indices, and a bit mask
    of the areas on each."""

    def __init__(self) -> None:
        self.stacks: List[Tuple[int, ...]] = [()]
        self.masks: List[int] = [0]
        self._ids = {(): 0}

    def push(self, ids: np.ndarray, areas: np.ndarray) -> np.ndarray:
        """The stack ids of ``stacks[ids[k]] + (areas[k],)``."""
        pairs, inv = np.unique(np.stack([ids, areas]), axis=1,
                               return_inverse=True)
        got = np.empty(pairs.shape[1], np.int64)
        for k, (s, a) in enumerate(pairs.T.tolist()):
            t = self.stacks[s] + (a,)
            sid = self._ids.get(t)
            if sid is None:
                sid = self._ids[t] = len(self.stacks)
                self.stacks.append(t)
                self.masks.append(self.masks[s] | (1 << a))
            got[k] = sid
        return got[inv.reshape(-1)]

    def mask(self, ids: np.ndarray) -> np.ndarray:
        return np.array(self.masks, np.int64)[ids]


class State:
    """One fixed point: the network it was solved on, per area the
    costs, and every member's advertisement of every group (distance,
    stack id; INF = none)."""

    def __init__(self, lsdb, cost, dist, stack) -> None:
        self.lsdb = lsdb
        self.cost = cost
        self.dist = dist
        self.stack = stack


class Reference:
    """The fixed point and the vantage's routes for states of one
    network.  ``policy`` is the configuration's ``rib_policy`` (None:
    no policy), applied at ``vantage`` only."""

    def __init__(self, split: Split, vantage: str,
                 policy: Optional[dict]) -> None:
        self.split = split
        self.vantage = split.index[vantage]
        self.policy = policy
        self.stacks = Stacks()
        s = split
        G = len(s.names)
        self.group = np.arange(G)
        # originations: every node into each of its areas, distance 0
        self.orig = {}
        for a in s.areas:
            o = np.full((len(s.members[a]), G), INF, np.int64)
            o[np.arange(len(s.members[a])), s.members[a]] = 0
            self.orig[a] = o
        self.tagged = {}
        if policy is not None:
            for st in policy["statements"]:
                if st.get("prefixes"):
                    raise ValueError("the reference matches tags only")
            for g in range(G):
                self.tagged[g] = next(
                    (k for k, st in enumerate(policy["statements"])
                     if s.tags[g] & set(st["tags"])), None)

    # -- one ABR ---------------------------------------------------------

    def _select(self, x: int, st: State, cols: np.ndarray):
        """Node x's selection for the groups ``cols``: (route, best
        distance, IGP metric, best area index, best stack id, and per
        area (area, x's index there, winners, the area's IGP metric))."""
        s = self.split
        a1, a2 = s.node_areas[s.names[x]]
        at = np.arange(len(cols))
        sides = []
        for a in (a1, a2):
            i = s.local[a][x]
            reach = st.cost[a][i] < INF
            c = np.where(reach[:, None], st.dist[a][:, cols], INF)
            sides.append((a, i, c))
        best = np.minimum(sides[0][2].min(0), sides[1][2].min(0))
        has = best < INF
        out, ranks, args = [], [], []
        self_win = np.zeros_like(has)
        for a, i, c in sides:
            win = (c == best[None, :]) & has[None, :]
            self_win |= win[i]
            igp = np.where(win, st.cost[a][i][:, None], INF).min(0)
            r = np.where(win, s.rank[a][:, None], INF)
            arg = r.argmin(0)
            out.append((a, i, win, igp))
            ranks.append(r[arg, at])
            args.append(arg)
        route = has & ~self_win
        igp = np.minimum(out[0][3], out[1][3])
        first = ranks[0] < ranks[1]
        barea = np.where(first, s.area_index[a1], s.area_index[a2])
        bstack = np.where(first, st.stack[a1][args[0], cols],
                          st.stack[a2][args[1], cols])
        return route, best, igp, barea, bstack, out

    def _redistribute(self, x: int, st: State, cols: np.ndarray,
                      new_dist, new_stack) -> None:
        """Write node x's re-advertisements of ``cols`` into the next
        round."""
        s = self.split
        route, best, igp, barea, bstack, _ = self._select(x, st, cols)
        if x == self.vantage and self.policy is not None:
            route &= self._kept(st, cols)
        a1, a2 = s.node_areas[s.names[x]]
        i1, i2 = s.area_index[a1], s.area_index[a2]
        target = np.where(barea == i1, i2, i1)
        ok = route & ((self.stacks.mask(bstack) >> target) & 1 == 0)
        d = np.where(ok, best + igp, INF)
        stk = np.zeros(len(ok), np.int64)
        idx = np.flatnonzero(ok)
        stk[idx] = self.stacks.push(bstack[idx], barea[idx])
        for a, ai in ((a1, i1), (a2, i2)):
            here = ok & (target == ai)
            k = s.local[a][x]
            new_dist[a][k, cols] = np.where(here, d, self.orig[a][k, cols])
            new_stack[a][k, cols] = np.where(here, stk, 0)

    # -- the fixed point ---------------------------------------------------

    def solve(self, lsdb, prev: Optional[State] = None,
              max_rounds: int = 64) -> State:
        """The fixed point on ``lsdb``, from ``prev``'s when given: a
        round recomputes, at each ABR, the groups whose advertisements
        or costs changed in one of its areas in the round before."""
        s = self.split
        G = len(s.names)
        lsdb = lsdb.copy()  # the caller's goes on changing
        cost = area_costs(s, lsdb)
        if prev is None:
            dist = {a: self.orig[a].copy() for a in s.areas}
            stack = {a: np.zeros_like(dist[a]) for a in s.areas}
            dirty = {a: np.ones(G, bool) for a in s.areas}
        else:
            dist, stack = prev.dist, prev.stack
            dirty = {a: np.full(G, not np.array_equal(cost[a], prev.cost[a]))
                     for a in s.areas}
        st = State(lsdb, cost, dist, stack)
        for _ in range(max_rounds):
            if not any(d.any() for d in dirty.values()):
                return st
            new_dist = {a: v.copy() for a, v in st.dist.items()}
            new_stack = {a: v.copy() for a, v in st.stack.items()}
            for x in s.abrs:
                a1, a2 = s.node_areas[s.names[x]]
                cols = np.flatnonzero(dirty[a1] | dirty[a2])
                if cols.size:
                    self._redistribute(x, st, cols, new_dist, new_stack)
            dirty = {a: ((new_dist[a] != st.dist[a])
                         | (new_stack[a] != st.stack[a])).any(0)
                     for a in s.areas}
            st = State(lsdb, cost, new_dist, new_stack)
        raise RuntimeError("redistribution found no fixed point")

    # -- the vantage's routes ----------------------------------------------

    def _next_hops(self, st: State, cols: np.ndarray) -> list:
        """Per group of ``cols``, the vantage's route before the policy:
        None, or (metric, [(area, neighbour)])."""
        s = self.split
        lsdb = st.lsdb
        x = self.vantage
        route, _best, igp, _ba, _bs, sides = self._select(x, st, cols)
        me = s.names[x]
        hops = [[] for _ in cols]
        for a, i, win, igp_a in sides:
            at = win & (st.cost[a][i][:, None] == igp_a[None, :])
            use = route & (igp_a == igp) & (igp_a < INF)
            for li in s.links[a]:
                if not lsdb.up[li] or me not in lsdb.links[li]:
                    continue
                n = lsdb.other(li, me)
                k = s.local[a][s.index[n]]
                via = st.cost[a][k][:, None] + lsdb.metric[li]
                on = ((via == igp_a[None, :]) & at).any(0) & use
                for j in np.flatnonzero(on).tolist():
                    hops[j].append((a, n))
        return [(int(igp[j]), hops[j]) if route[j] and hops[j] else None
                for j in range(len(cols))]

    def _weight(self, g: int, area: str, nbr: str) -> Optional[int]:
        """The policy's weight of one next hop (None: no statement)."""
        k = self.tagged.get(g) if self.policy is not None else None
        if k is None:
            return None
        act = self.policy["statements"][k]["action"]
        return act.get("neighbor_to_weight", {}).get(
            nbr, act.get("area_to_weight", {}).get(
                area, act.get("default_weight", 1)))

    def _kept(self, st: State, cols: np.ndarray) -> np.ndarray:
        """Per group of ``cols``, whether the policy leaves the vantage's
        route a next hop (the route itself may be absent)."""
        keep = np.ones(len(cols), bool)
        for j, r in enumerate(self._next_hops(st, cols)):
            g = int(cols[j])
            if r is not None and self.tagged.get(g) is not None:
                keep[j] = any(self._weight(g, a, n) != 0 for a, n in r[1])
        return keep

    def routes(self, st: State, policy: bool = True) -> list:
        """Per group, the vantage's FIB entry: None or a frozenset of
        next-hop tuples; ``policy=False`` leaves the policy out (the
        control)."""
        me = self.split.names[self.vantage]
        out = []
        for g, r in enumerate(self._next_hops(st, self.group)):
            if r is None:
                out.append(None)
                continue
            metric, hops = r
            nhs = set()
            for a, n in hops:
                w = self._weight(g, a, n) if policy else None
                if w == 0:
                    continue
                nhs.add(("", f"if_{me}_{n}", metric, w or 0, a, n, None))
            out.append(frozenset(nhs) if nhs else None)
        return out

    # -- advertisements ----------------------------------------------------

    def advertisements(self, st: State, node: str, areas=None) -> dict:
        """{(area, group): (distance, area stack names)} of everything
        ``node`` re-advertises (its originations left out)."""
        s = self.split
        x = s.index[node]
        out = {}
        for a in s.node_areas[node]:
            if areas is not None and a not in areas:
                continue
            k = s.local[a][x]
            red = (st.dist[a][k] < INF) & (self.orig[a][k] == INF)
            for g in np.flatnonzero(red).tolist():
                out[(a, g)] = (int(st.dist[a][k, g]), tuple(
                    s.areas[i]
                    for i in self.stacks.stacks[int(st.stack[a][k, g])]))
        return out
