"""Device-backed KSP2_ED_ECMP: batched masked re-solves + host path trace.

The reference computes the k-th edge-disjoint shortest paths by re-running
full Dijkstra with the links of paths 1..k-1 ignored, once per destination
(LinkState.cpp:675-699) — on a fat-tree where every rack prefix uses
KSP2_ED_ECMP that is O(destinations) host Dijkstras per rebuild, the hot
loop.  Here the re-solves run as ONE batched device call
(``batched_spf_distances_masked``: vmapped masked Bellman-Ford over a
[U, E] ignore-mask batch), and only the cheap part — greedy path tracing
over the shortest-path DAG (traceOnePath, LinkState.cpp:227-247) — stays
on the host, reconstructed from the device distance fields.

Exactness: ``LinkState.run_spf`` iterates sorted adjacency, so its
``path_links`` order is (settle-order of predecessor, link order) — the
reconstruction here sorts by exactly that key, making the greedy trace
bit-identical to the scalar path.  The traced paths are seeded into the
LinkState k-path memo (``seed_kth_paths``), after which the unmodified
scalar KSP2 selection chain (SpfSolver._select_best_paths_ksp2, SR-MPLS
label stacks, cross-area merge, min-nexthop gate) runs without any host
Dijkstra.
"""

from __future__ import annotations

from collections import Counter
from typing import Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

from openr_tpu.decision.link_state import Link, LinkState, Path
from openr_tpu.ops.csr import EncodedTopology, link_failure_batch
from openr_tpu.tracing import disabled_probe, pipeline

_BIG = np.float32(3.4e38)


class Ksp2DeviceEngine:
    """Per-(area LinkState, encoded topology) KSP2 seeding engine.

    ``seed(dests)`` guarantees ``link_state.get_kth_paths(root, d, k)`` for
    k in (1, 2) is memoized for every d in dests without running host
    Dijkstra for the k=2 re-solves.  Results live in the LinkState memo, so
    repeat rebuilds on an unchanged topology are free; the memo is cleared
    by LinkState on topology change, which re-arms this engine.
    """

    def __init__(
        self,
        link_state: LinkState,
        topo: EncodedTopology,
        root: str,
        probe=None,
        counts: Optional[Counter] = None,
    ) -> None:
        self.link_state = link_state
        self.topo = topo
        self.root = root
        self.probe = probe if probe is not None else disabled_probe()
        #: device_batches, resolve_rows (real destinations),
        #: resolve_padded_rows (after the BATCH_BUCKETS pad) and
        #: seeded_dests; a backend passes one Counter to every engine
        #: it makes, so the counts outlive the per-topology engines
        self.counts = counts if counts is not None else Counter()
        self._link_id: Dict[Tuple[str, str, str, str], int] = {
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
        # k=1: trace over the (memoized) base SPF — cheap, scalar-exact
        with self.probe.phase(pipeline.KSP2_TRACE):
            ignore_ids: List[List[int]] = []
            for d in todo:
                ignored: Set[Link] = set()
                for path in ls.get_kth_paths(root, d, 1):
                    ignored.update(path)
                ignore_ids.append(
                    sorted(self._link_id[l.key] for l in ignored)
                )

        with self.probe.phase(pipeline.KSP2_RESOLVE):
            dist2 = self._device_resolve(ignore_ids)
        with self.probe.phase(pipeline.KSP2_TRACE):
            for row, d in enumerate(todo):
                ignored_links = {
                    self.topo.links[i] for i in ignore_ids[row]
                }
                paths = self._trace_all(d, dist2[row], ignored_links)
                ls.seed_kth_paths(root, d, 2, paths)
                self.counts["seeded_dests"] += 1

    # -- device batch ------------------------------------------------------

    #: destination-batch buckets: the jit cache must stay warm across
    #: rebuilds where the number of un-memoized destinations varies
    #: (prefix churn re-arms a few dests at a time) — same discipline as
    #: node_buckets/cand_buckets in the encoder
    BATCH_BUCKETS = (8, 32, 128, 512, 2048, 8192, 32768)

    def _device_resolve(self, ignore_ids: List[List[int]]) -> np.ndarray:
        import jax
        import jax.numpy as jnp

        from openr_tpu.ops.csr import bucket_for
        from openr_tpu.ops.jit_guard import call_jit_guarded
        from openr_tpu.ops.spf import batched_spf_distances_masked

        topo = self.topo
        n = len(ignore_ids)
        padded = bucket_for(n, self.BATCH_BUCKETS)
        self.counts["resolve_rows"] += n
        self.counts["resolve_padded_rows"] += padded
        # padding rows solve the unmasked topology (cheap no-op work)
        ignore_ids = ignore_ids + [[]] * (padded - n)
        masks = link_failure_batch(topo, ignore_ids)
        roots = np.full(padded, topo.node_id(self.root), np.int32)
        dist = call_jit_guarded(
            batched_spf_distances_masked,
            jnp.asarray(topo.src),
            jnp.asarray(topo.dst),
            jnp.asarray(topo.w),
            jnp.asarray(topo.edge_ok),
            jnp.asarray(masks),
            jnp.asarray(topo.overloaded),
            jnp.asarray(roots),
        )
        self.counts["device_batches"] += 1
        # one host fetch for the whole batch: one blocking round trip,
        # not one per root
        return np.asarray(jax.device_get(dist))[:n]

    # -- host trace over the device distance field -------------------------

    def _path_links(
        self,
        node: str,
        dist: np.ndarray,
        ignored: Set[Link],
    ) -> List[Tuple[Link, str]]:
        """Reconstruct NodeSpfResult.path_links for `node` in run_spf's
        append order: predecessors settle in (metric, name) heap order and
        each relaxes its sorted links (run_spf iterates
        ordered_links_from_node), so the key is (dist[prev], prev, link)."""
        ls = self.link_state
        ids = self.topo.node_ids
        dv = dist[ids[node]]
        out: List[Tuple[np.float32, str, Link]] = []
        for link in ls.ordered_links_from_node(node):
            prev = link.get_other_node_name(node)
            if not link.is_up() or link in ignored:
                continue
            if ls.is_node_overloaded(prev) and prev != self.root:
                continue
            du = dist[ids[prev]]
            if du >= _BIG:
                continue
            if np.float32(du + np.float32(link.get_max_metric())) == dv:
                out.append((du, prev, link))
        out.sort(key=lambda t: (t[0], t[1], t[2].key))
        return [(link, prev) for _, prev, link in out]

    def _trace_all(
        self, dest: str, dist: np.ndarray, ignored: Set[Link]
    ) -> List[Path]:
        if dist[self.topo.node_id(dest)] >= _BIG:
            return []
        visited: Set[Link] = set()
        pl_cache: Dict[str, List[Tuple[Link, str]]] = {}

        def path_links(v: str) -> List[Tuple[Link, str]]:
            cached = pl_cache.get(v)
            if cached is None:
                cached = pl_cache[v] = self._path_links(v, dist, ignored)
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
        return paths
