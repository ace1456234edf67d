"""Operator-facing what-if: 'which of MY routes change if link X fails?'

Wires the flagship sweep engine (ops/whatif.py + ops/sweep_select.py)
into the daemon: the ctrl call takes a list of candidate link failures,
runs them as one device batch against the CURRENT LSDB from this node's
vantage, and returns per-failure route deltas (removed / rerouted /
metric-changed) decoded to neighbor names.  The engine (base solve +
repair plan + selection tables) is cached per LSDB change generation,
so an operator sweeping many links pays the setup once.

Three device engines cover the accelerated configurations:

  * ``WhatIfApiEngine`` — single-area vantage over the warm-start
    repair sweep + on-device selection (the fastest path).
  * ``MultiAreaWhatIfEngine`` — multi-area LSDBs over the fleet-family
    kernel (ops.fleet_tables.whatif_multi_area_tables): per snapshot
    the failed SET of links (singles, parallel bundles, simultaneous
    maintenance windows) is masked in each member's area, selection is
    global, and the cross-area min-metric merge happens in the host
    decode — the same semantics the reference reaches scalar via
    getDecisionRouteDb (Decision.cpp:342).
  * ``DeviceBuildWhatIfEngine`` — KSP2_ED_ECMP vantages / exotic
    selection rules: full DEVICE builds (tables + the device KSP2
    engine) minus the links, diffed.

Only scalar-only deployments outside the native engine's reach answer
through ``GenericSolverWhatIfEngine``: a full scalar-solver build with
the links actually removed — slow but jax-free and algorithm-complete,
so every configuration the daemon can run gets a what-if answer.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from openr_tpu.decision.spf_solver import SpfSolver
from openr_tpu.types import prefix_is_v4

#: failure-batch buckets for the multi-area kernel (jit shapes stay
#: cache-stable across operator query sizes; chosen strictly GREATER
#: than the failure count so at least one -1 pad row exists — that row
#: doubles as the unperturbed base snapshot)
FAILURE_BUCKETS = (4, 16, 64, 256)


def resolve_pair_failures(pair_links: Dict, link_failures,
                          allow_parallel: bool = False):
    """Resolve (n1, n2) pairs against a pair→links map.  Returns
    (values, errors), one entry per failure; errors[i] is None or a
    ready-to-emit error row.  Without ``allow_parallel`` values[i] is
    the unique link value or None (pairs with multiple links error —
    engines without set solves would mislead by failing just one).
    With ``allow_parallel`` values[i] is ALWAYS a tuple of every link
    between the pair (1-tuple for a unique link): the engine fails the
    whole bundle as one simultaneous set.  Shared by every what-if
    engine so their operator-facing semantics cannot drift."""
    values, errors = [], []
    for n1, n2 in link_failures:
        hits = pair_links.get(frozenset((n1, n2)), [])
        if not hits:
            values.append(None)
            errors.append({"link": [n1, n2], "error": "unknown link"})
        elif allow_parallel:
            values.append(tuple(hits))
            errors.append(None)
        elif len(hits) == 1:
            values.append(hits[0])
            errors.append(None)
        else:
            # engines without set solves reject parallel pairs
            values.append(None)
            errors.append(
                {
                    "link": [n1, n2],
                    "error": (
                        f"{len(hits)} parallel links between pair; "
                        "single-link what-if would shift traffic to "
                        "the survivors — not supported by this engine"
                    ),
                }
            )
    return values, errors


def build_pair_links(links, area_index=None) -> Dict:
    """(n1, n2) → list of link values: plain link ids, or
    (area_index, link_id) pairs when ``area_index`` is given.  One
    builder for every what-if engine so link-identity handling cannot
    drift between them."""
    out: Dict[frozenset, list] = {}
    for i, link in enumerate(links):
        val = i if area_index is None else (area_index, i)
        out.setdefault(frozenset((link.n1, link.n2)), []).append(val)
    return out


def lane_names_for(topo, root: str) -> List[str]:
    """Lane rank → neighbor name for decoding first-hop lane rows."""
    return [nbr for (_link, nbr) in topo.root_out_edges(root)]


def decode_lane_names(lane_names: List[str], row) -> List[str]:
    return [
        lane_names[i]
        for i in np.nonzero(row)[0]
        if i < len(lane_names)
    ]


def change_kind(was: bool, now: bool) -> str:
    if was and not now:
        return "removed"
    if now and not was:
        return "added"
    return "rerouted"


class WhatIfApiEngine:
    """Cached sweep→routes pipeline for one node's vantage."""

    def __init__(self, solver: SpfSolver) -> None:
        self.solver = solver
        self._cache_key = None
        self._sweep = None
        self._selector = None
        self._topo = None
        self._prefixes: List[str] = []
        self.num_engine_builds = 0
        self.num_sweeps = 0

    def _engine_for(self, area_link_states, prefix_state, change_seq):
        from openr_tpu.ops.csr import encode_link_state, encode_prefix_candidates
        from openr_tpu.ops.sweep_select import SweepRouteSelector
        from openr_tpu.ops.whatif import LinkFailureSweep

        (area, ls), = area_link_states.items()
        key = (area, ls.topology_seq, change_seq)
        if self._cache_key == key:
            return
        topo = encode_link_state(ls)
        me = self.solver.my_node_name
        # EncodedPrefixCandidates exposes the exact candidate-array schema
        # the selector reads — no copy
        cands = encode_prefix_candidates(prefix_state, topo, area)
        sweep = LinkFailureSweep(topo, me)
        # the first what-if after an LSDB change used to pay a full cold
        # base solve; seed it from the previous generation instead (only
        # removal-affected vertices re-converge — exact, VERDICT r3
        # weak #7)
        sweep.seed_base_from(self._sweep)
        self._sweep = sweep
        self._selector = SweepRouteSelector(topo, me, cands, max_degree=sweep.D)
        self._topo = topo
        self._prefixes = cands.prefixes
        #: node-pair -> undirected link ids (PARALLEL links are distinct:
        #: link identity includes interfaces, link_state.py)
        self._pair_links = build_pair_links(topo.links)
        self._cache_key = key
        self.num_engine_builds += 1

    def run(
        self,
        link_failures: List[Tuple[str, str]],
        area_link_states,
        prefix_state,
        change_seq: int,
        simultaneous: bool = False,
    ) -> Dict:
        """One device sweep over the given candidate failures; returns
        per-failure route deltas from this node's vantage.  With
        ``simultaneous`` ALL listed links fail at once (one combined
        failure entry — maintenance-window analysis over
        LinkFailureSweep.run_sets)."""
        self._engine_for(area_link_states, prefix_state, change_seq)
        me = self.solver.my_node_name
        lane_names = lane_names_for(self._topo, me)
        v4_ok = self.solver.enable_v4 or self.solver.v4_over_v6_nexthop

        # allow_parallel returns every resolved failure as a tuple of
        # link ids (a bundle fails as one simultaneous set via run_sets)
        lid_sets, errors = resolve_pair_failures(
            self._pair_links, link_failures, allow_parallel=True
        )

        def lanes_to_names(lane_row) -> List[str]:
            return decode_lane_names(lane_names, lane_row)

        def changes_from_row(deltas, row: int) -> List[dict]:
            changes = []
            if row == 0:
                return changes
            base_valid = deltas.base_valid
            p_idx, valid, metric, lanes = deltas.deltas_of_row(row)
            for k in range(len(p_idx)):
                p = int(p_idx[k])
                prefix = self._prefixes[p]
                if prefix_is_v4(prefix) and not v4_ok:
                    continue
                was, now = bool(base_valid[p]), bool(valid[k])
                changes.append(
                    {
                        "prefix": prefix,
                        "change": change_kind(was, now),
                        "old_nexthops": (
                            lanes_to_names(deltas.base_lanes[p])
                            if was
                            else []
                        ),
                        "new_nexthops": (
                            lanes_to_names(lanes[k]) if now else []
                        ),
                        "old_metric": (
                            float(deltas.base_metric[p]) if was else None
                        ),
                        "new_metric": float(metric[k]) if now else None,
                    }
                )
            return changes

        if simultaneous:
            bad = [e for e in errors if e is not None]
            if bad:
                return {
                    "eligible": True,
                    "vantage": me,
                    "engine": "device",
                    "simultaneous": True,
                    "failures": bad,
                }
            fail_set = tuple(
                int(l) for tup in lid_sets for l in tup  # type: ignore[union-attr]
            )
            deltas = self._selector.run(
                self._sweep.run_sets([fail_set], fetch=False)
            )
            self.num_sweeps += 1
            changes = changes_from_row(deltas, int(deltas.snap_row[0]))
            on_dag = self._sweep.on_dag_links()
            return {
                "eligible": True,
                "vantage": me,
                "engine": "device",
                "simultaneous": True,
                "failures": [
                    {
                        "links": [list(f) for f in link_failures],
                        "on_shortest_path_dag": bool(
                            any(on_dag[l] for l in fail_set)
                        ),
                        "routes_changed": len(changes),
                        "changes": changes,
                    }
                ],
            }

        # per-failure snapshots: a parallel bundle is one snapshot that
        # fails its whole link set; error rows become empty sets (base)
        deltas = self._selector.run(
            self._sweep.run_sets(
                [s if s is not None else () for s in lid_sets],
                fetch=False,
            )
        )
        self.num_sweeps += 1

        on_dag = self._sweep.on_dag_links()
        out = []
        for s, ((n1, n2), tup) in enumerate(zip(link_failures, lid_sets)):
            if tup is None:
                out.append(errors[s])
                continue
            changes = changes_from_row(deltas, int(deltas.snap_row[s]))
            entry = {
                "link": [n1, n2],
                "on_shortest_path_dag": bool(
                    any(on_dag[l] for l in tup)
                ),
                "routes_changed": len(changes),
                "changes": changes,
            }
            if len(tup) > 1:
                # the pair is a bundle (parallel links): ALL failed
                entry["links_failed"] = len(tup)
            out.append(entry)
        return {"eligible": True, "vantage": me, "engine": "device", "failures": out}


def _whatif_engine_criticality(
    engine: "WhatIfApiEngine",
    area_link_states,
    prefix_state,
    change_seq: int,
    max_pairs: int = 0,
) -> Dict:
    """Criticality report over the engine's cached sweep context."""
    engine._engine_for(area_link_states, prefix_state, change_seq)
    v4_ok = engine.solver.enable_v4 or engine.solver.v4_over_v6_nexthop
    return _criticality_from_engine(
        engine._sweep,
        engine._selector,
        engine._topo,
        engine._prefixes,
        max_pairs,
        v4_ok,
    )


def _criticality_from_engine(
    sweep, selector, topo, prefixes, max_pairs: int, v4_ok: bool
) -> Dict:
    """Shared criticality computation over a (sweep, selector) pair:
    one single-failure sweep across EVERY link ranks blast radius; an
    optional double-failure run_sets scan (capped at ``max_pairs``)
    finds pairs whose combined failure withdraws routes that neither
    single failure withdraws (partition risk).  Pairs with at least
    one on-DAG member are scanned — an off-DAG link can carry the
    reroute once its on-DAG partner fails (the canonical
    primary+backup partition case), but a pair of two off-DAG links
    provably changes nothing.  Counts skip v4 prefixes the node would
    never install (same filter the what-if answers apply)."""
    import itertools

    L = len(topo.links)
    fails = np.arange(L, dtype=np.int32)
    deltas = selector.run(sweep.run(fails, fetch=False))
    on_dag = sweep.on_dag_links()
    #: prefix rows excluded from counts (v4 on a v6-only node)
    skip_p = (
        np.asarray([prefix_is_v4(p) for p in prefixes], bool)
        if not v4_ok
        else np.zeros(len(prefixes), bool)
    )

    def removed_of_row(dl, row: int):
        if row == 0:
            return 0, 0
        p_idx, valid, _m, _l = dl.deltas_of_row(row)
        keep = ~skip_p[p_idx]
        removed = int((~valid[keep]).sum())
        return int(keep.sum()), removed

    links = []
    single_removed = {}
    for li in range(L):
        changed, removed = removed_of_row(deltas, int(deltas.snap_row[li]))
        link = topo.links[li]
        single_removed[li] = removed
        links.append(
            {
                "link": sorted((link.n1, link.n2)),
                "on_shortest_path_dag": bool(on_dag[li]),
                "routes_changed": changed,
                "routes_withdrawn": removed,
            }
        )
    links.sort(
        key=lambda e: (-e["routes_withdrawn"], -e["routes_changed"],
                       e["link"])
    )

    pairs_out = None
    if max_pairs > 0:
        n_off = int((~on_dag[:L]).sum())
        # pairs with >= 1 on-DAG member, capped WITHOUT materializing
        # the full O(L^2) product
        def gen_pairs():
            for a, b in itertools.combinations(range(L), 2):
                if on_dag[a] or on_dag[b]:
                    yield (a, b)

        capped = list(itertools.islice(gen_pairs(), max_pairs))
        total = L * (L - 1) // 2 - n_off * (n_off - 1) // 2
        pair_deltas = selector.run(
            sweep.run_sets(capped, fetch=False)
        )
        risky = []
        for s, (a, b) in enumerate(capped):
            _c, removed = removed_of_row(
                pair_deltas, int(pair_deltas.snap_row[s])
            )
            extra = removed - single_removed[a] - single_removed[b]
            if extra > 0:
                la, lb = topo.links[a], topo.links[b]
                risky.append(
                    {
                        "links": [
                            sorted((la.n1, la.n2)),
                            sorted((lb.n1, lb.n2)),
                        ],
                        "routes_withdrawn": removed,
                        "beyond_single_failures": extra,
                    }
                )
        risky.sort(key=lambda e: -e["beyond_single_failures"])
        pairs_out = {
            "checked": len(capped),
            "total": total,
            "truncated": len(capped) < total,
            "risky": risky[:64],
            "risky_count": len(risky),
            "risky_truncated": len(risky) > 64,
        }
    return {"links": links, "pairs": pairs_out}


class MultiAreaWhatIfEngine:
    """Multi-area link-failure what-if from this node's vantage.

    Tables (topology encode, candidate table, base snapshot) are cached
    per LSDB change generation; each ``run`` solves the candidate
    failures plus one base snapshot as a single device batch and decodes
    only the prefixes whose merged route view changed."""

    def __init__(
        self, solver: SpfSolver, mesh=None, pool=None, probe=None
    ) -> None:
        """``mesh``: optional ``jax.sharding.Mesh`` with a ``batch``
        axis — failure snapshots then shard across the mesh
        (ops.fleet_tables.sharded_whatif_tables), bit-identical to the
        unsharded kernel.  ``pool``: optional
        :class:`~openr_tpu.parallel.mesh.DevicePool` — the failure
        batch then splits contiguously over the pool's HEALTHY chips as
        committed per-device dispatches (no shard_map requirement; a
        quarantined chip's share re-packs onto the survivors).
        ``probe``: optional
        :class:`~openr_tpu.tracing.pipeline.PipelineProbe` sharing the
        backend's phase/busy ledger."""
        from openr_tpu.tracing.pipeline import disabled_probe

        self.solver = solver
        self.mesh = mesh
        self.pool = pool
        self.probe = probe if probe is not None else disabled_probe()
        self._cache_key = None
        self._state = None
        #: PR-6 remnant: with BOTH a mesh and a pool, the collective
        #: mesh re-derives from DevicePool.survivor_mesh() on every
        #: health transition, so the shard_map path re-packs on chip
        #: quarantine exactly like the committed-dispatch path
        self._mesh_health_seq = None
        self._mesh_requested = mesh is not None
        self.num_engine_builds = 0
        self.num_sweeps = 0
        self.num_pool_dispatches = 0

    def _active_mesh(self):
        if not self._mesh_requested:
            return None
        if self.pool is None:
            return self.mesh
        if self._mesh_health_seq != self.pool.health_seq:
            self.mesh = self.pool.survivor_mesh()
            self._mesh_health_seq = self.pool.health_seq
        return self.mesh

    def _context(self, area_link_states, prefix_state, change_seq):
        import numpy as np

        from openr_tpu.decision.backend import DEGREE_BUCKETS
        from openr_tpu.decision.cand_table import CandidateTable
        from openr_tpu.ops.csr import bucket_for, encode_multi_area

        key = (
            tuple(
                (a, area_link_states[a].topology_seq)
                for a in sorted(area_link_states)
            ),
            change_seq,
        )
        if self._cache_key == key and self._state is not None:
            return self._state
        from openr_tpu.tracing import pipeline

        me = self.solver.my_node_name
        with self.probe.phase(pipeline.ENCODE):
            enc = encode_multi_area(area_link_states, me)
        with self.probe.phase(pipeline.HOST_FETCH):
            table = CandidateTable()
            table.full_sync(prefix_state)
            dv = table.derived(enc)
            link_index = np.stack([t.link_index for t in enc.topos])
            # (n1, n2) -> [(area_index, link_id)]; parallel links (within
            # or across areas) are rejected like the single-area engine
            pair_links: Dict[frozenset, list] = {}
            for ai, t in enumerate(enc.topos):
                for pair, vals in build_pair_links(
                    t.links, area_index=ai
                ).items():
                    pair_links.setdefault(pair, []).extend(vals)
            out_edges_by_area = [t.root_out_edges(me) for t in enc.topos]
        D = bucket_for(max(enc.max_out_degree(), 1), DEGREE_BUCKETS)
        self._state = dict(
            enc=enc,
            table=table,
            dv=dv,
            link_index=link_index,
            pair_links=pair_links,
            out_edges_by_area=out_edges_by_area,
            D=D,
            base_dist=None,  # filled on first run (on-DAG flags)
        )
        self._cache_key = key
        self.num_engine_builds += 1
        return self._state

    def run(
        self,
        link_failures: List[Tuple[str, str]],
        area_link_states,
        prefix_state,
        change_seq: int,
        simultaneous: bool = False,
    ) -> Dict:
        import jax
        import jax.numpy as jnp
        import numpy as np

        from openr_tpu.ops.fleet_tables import whatif_multi_area_tables
        from openr_tpu.ops.route_select import multi_area_spf_tables
        from openr_tpu.types import RouteComputationRules

        st = self._context(area_link_states, prefix_state, change_seq)
        enc, dv, table = st["enc"], st["dv"], st["table"]
        me = self.solver.my_node_name
        A = enc.num_areas
        per_area = (
            self.solver.route_selection_algorithm
            == RouteComputationRules.PER_AREA_SHORTEST_DISTANCE
        )

        # resolve candidate failures (shared semantics with the
        # single-area engine); every value is a TUPLE of (area, link)
        # hits — parallel bundles and simultaneous sets fail together
        # (the kernel masks up to S links per snapshot)
        pairs, errors = resolve_pair_failures(
            st["pair_links"], link_failures, allow_parallel=True
        )
        if simultaneous:
            bad = [e for e in errors if e is not None]
            if bad:
                return {
                    "eligible": True,
                    "vantage": me,
                    "engine": "multiarea",
                    "simultaneous": True,
                    "failures": bad,
                }
            # ONE snapshot failing the union of every listed link
            union = tuple(
                hit for tup in pairs if tup is not None for hit in tup
            )
            fail_sets: List[Optional[tuple]] = [union]
        else:
            fail_sets = pairs
        B = len(fail_sets)
        from openr_tpu.ops.csr import bucket_for

        # pad the batch to a bucket STRICTLY larger than B so jit shapes
        # stay cache-stable across query sizes AND at least one -1 pad
        # row exists — that row solves the unperturbed topology and
        # doubles as the base snapshot (an explicit base row would cost
        # the same as the padding the bucket already requires).  The set
        # width S is bucketed too (most queries are single links: S=1).
        bucket = bucket_for(
            B + 1, FAILURE_BUCKETS + (max(B + 1, FAILURE_BUCKETS[-1]),)
        )
        mesh = self._active_mesh()
        if mesh is not None:
            # sharded dispatch splits the failure batch across devices
            gran = mesh.devices.size
            bucket = ((bucket + gran - 1) // gran) * gran
        from openr_tpu.tracing import pipeline

        smax = max(
            [len(tup) for tup in fail_sets if tup is not None] or [1]
        )
        with self.probe.phase(pipeline.PAD_PACK):
            S = bucket_for(smax, (1, 2, 4, 8, 16, 32, max(smax, 32)))
            fa = np.full((bucket, S), -1, np.int32)
            fl = np.full((bucket, S), -1, np.int32)
            for i, tup in enumerate(fail_sets):
                if tup is not None:
                    for s, (ai, li) in enumerate(tup):
                        fa[i, s], fl[i, s] = ai, li

        from openr_tpu.ops.jit_guard import call_jit_guarded

        with self.probe.phase(pipeline.TRANSFER):
            kernel_args = dict(
                src=jnp.asarray(enc.src),
                dst=jnp.asarray(enc.dst),
                w=jnp.asarray(enc.w),
                edge_ok=jnp.asarray(enc.edge_ok),
                link_index=jnp.asarray(st["link_index"]),
                overloaded=jnp.asarray(enc.overloaded),
                soft=jnp.asarray(enc.soft),
                roots=jnp.asarray(enc.roots),
            )
            cand_args = dict(
                cand_area=jnp.asarray(dv.cand_area),
                cand_node=jnp.asarray(dv.cand_node),
                cand_ok=jnp.asarray(dv.cand_ok),
                drain_metric=jnp.asarray(dv.drain_metric),
                path_pref=jnp.asarray(dv.path_pref),
                source_pref=jnp.asarray(dv.source_pref),
                distance=jnp.asarray(dv.distance),
                cand_node_in_area=jnp.asarray(dv.cand_node_in_area),
            )
        if mesh is not None:
            from openr_tpu.ops.fleet_tables import sharded_whatif_tables
            from openr_tpu.parallel.mesh import batch_sharding, replicated

            rep = replicated(mesh)
            bat = batch_sharding(mesh)
            fn = sharded_whatif_tables(mesh, st["D"], per_area)
            use, shortest, lanes, valid = jax.device_get(
                call_jit_guarded(
                    fn,
                    *(
                        jax.device_put(v, rep)
                        for v in kernel_args.values()
                    ),
                    jax.device_put(jnp.asarray(fa), bat),
                    jax.device_put(jnp.asarray(fl), bat),
                    *(
                        jax.device_put(v, rep)
                        for v in cand_args.values()
                    ),
                )
            )
        else:
            pool_devs = None
            if self.pool is not None and B >= 2:
                healthy = self.pool.healthy_indices()
                if len(healthy) > 1:
                    pool_devs = healthy
            if pool_devs is not None:
                # data-parallel over the pool: contiguous failure-row
                # shards, one committed dispatch per healthy chip, each
                # with its own -1 pad row (the pad row solves the
                # unperturbed topology, so every shard carries a base —
                # the first shard's is the one the decode diffs against).
                # Shards drain as STREAMED completions (is_ready poll +
                # per-shard stream_drain charged only to the completing
                # chip) instead of one all-chip device_get barrier.
                from openr_tpu.ops import jit_guard

                shards = self.pool.shard_ranges(B, pool_devs)
                dispatched = []
                for idx, lo, hi in shards:
                    n_i = hi - lo
                    with self.probe.phase(pipeline.PAD_PACK, device=idx):
                        bucket_i = bucket_for(
                            n_i + 1,
                            FAILURE_BUCKETS
                            + (max(n_i + 1, FAILURE_BUCKETS[-1]),),
                        )
                        fa_i = np.full((bucket_i, S), -1, np.int32)
                        fl_i = np.full((bucket_i, S), -1, np.int32)
                        fa_i[:n_i] = fa[lo:hi]
                        fl_i[:n_i] = fl[lo:hi]
                    d = self.pool.device(idx)
                    with self.probe.phase(pipeline.TRANSFER, device=idx):
                        shard_kwargs = dict(
                            fail_area=jax.device_put(jnp.asarray(fa_i), d),
                            fail_link=jax.device_put(jnp.asarray(fl_i), d),
                            **{
                                k: jax.device_put(v, d)
                                for k, v in kernel_args.items()
                            },
                            **{
                                k: jax.device_put(v, d)
                                for k, v in cand_args.items()
                            },
                        )
                    with self.probe.phase(
                        pipeline.DEVICE_COMPUTE, device=idx
                    ), jit_guard.dispatch_device(idx):
                        out = call_jit_guarded(
                            whatif_multi_area_tables,
                            max_degree=st["D"],
                            per_area_distance=per_area,
                            **shard_kwargs,
                        )
                    self.pool.note_inflight(idx)
                    for o in out:
                        o.copy_to_host_async()
                    dispatched.append((idx, n_i, out))
                    self.num_pool_dispatches += 1
                fetched_by_pos: Dict[int, tuple] = {}
                pending_shards = list(enumerate(dispatched))
                while pending_shards:
                    sel = 0
                    for j, (_p, r) in enumerate(pending_shards):
                        if all(o.is_ready() for o in r[2]):
                            sel = j
                            break
                    pos, rec = pending_shards.pop(sel)
                    idx, _n_i, out = rec
                    with self.probe.phase(
                        pipeline.STREAM_DRAIN, device=idx
                    ):
                        for o in out:
                            o.block_until_ready()
                    self.pool.note_complete(idx)
                    with self.probe.phase(
                        pipeline.DEVICE_GET, device=idx
                    ):
                        fetched_by_pos[pos] = jax.device_get(out)
                fetched = [
                    fetched_by_pos[i] for i in range(len(dispatched))
                ]
                parts = []
                for k in range(4):
                    rows = [
                        outs[k][:n]
                        for (_i, n, _), outs in zip(dispatched, fetched)
                    ]
                    # base snapshot: the FIRST shard's pad row, placed
                    # at index B exactly where the unsharded layout
                    # puts it (all shards' pad rows are bit-identical —
                    # same kernel, same unperturbed inputs)
                    n0 = dispatched[0][1]
                    rows.append(fetched[0][k][n0 : n0 + 1])
                    parts.append(np.concatenate(rows, axis=0))
                use, shortest, lanes, valid = parts
            else:
                with self.probe.phase(pipeline.DEVICE_COMPUTE, device=0):
                    pending = call_jit_guarded(
                        whatif_multi_area_tables,
                        fail_area=jnp.asarray(fa),
                        fail_link=jnp.asarray(fl),
                        max_degree=st["D"],
                        per_area_distance=per_area,
                        **kernel_args,
                        **cand_args,
                    )
                with self.probe.phase(pipeline.DEVICE_GET, device=0):
                    use, shortest, lanes, valid = jax.device_get(pending)
        if st["base_dist"] is None:
            with self.probe.phase(pipeline.DEVICE_COMPUTE):
                dist, _nh = call_jit_guarded(
                    multi_area_spf_tables,
                    kernel_args["src"],
                    kernel_args["dst"],
                    kernel_args["w"],
                    kernel_args["edge_ok"],
                    kernel_args["overloaded"],
                    kernel_args["roots"],
                    max_degree=st["D"],
                )
            with self.probe.phase(pipeline.DEVICE_GET):
                st["base_dist"] = np.asarray(jax.device_get(dist))
        self.num_sweeps += 1

        # ---- merged route view per snapshot (SpfSolver.cpp:276-302) ----
        with self.probe.phase(pipeline.DECODE):
            B1, P, _A = valid.shape
            m = np.where(valid, shortest, np.inf)  # [B1, P, A]
            m_star = m.min(axis=2)  # [B1, P]
            at_min = valid & (m == m_star[:, :, None])
            eff_lanes = lanes & at_min[:, :, :, None]  # [B1, P, A, D]
            merged = eff_lanes.sum(axis=(2, 3))  # nexthop count
            req = np.max(
                np.where(use, dv.min_nexthop[None, :, :], 0), axis=2
            )  # [B1, P]
            my_gid = table._node_gid.get(me)
            if my_gid is None:
                self_win = np.zeros((B1, P), bool)
            else:
                self_win = (
                    use & (table.adv_gid[None, :, :] == my_gid)
                ).any(axis=2)
            v4_ok = self.solver.enable_v4 or self.solver.v4_over_v6_nexthop
            include = np.asarray(
                [
                    p is not None and (v4_ok or not prefix_is_v4(p))
                    for p in table.row_prefix
                ],
                bool,
            )
            route_ok = (
                include[None, :]
                & valid.any(axis=2)
                & ~self_win
                & (merged > 0)
                & (merged >= req)
            )

        base = B  # the first pad row: the unperturbed snapshot
        out_edges_by_area = st["out_edges_by_area"]

        def nh_names(b, p):
            names = []
            for ai, lane in zip(*np.nonzero(eff_lanes[b, p])):
                oe = out_edges_by_area[ai]
                if lane < len(oe):
                    names.append(oe[lane][1])
            return sorted(set(names))

        # on-DAG flag per (area, link): some directed edge of the link
        # lies on a shortest path from me in its area
        bd = st["base_dist"]

        def on_dag(ai, li):
            t = enc.topos[ai]
            es = np.nonzero(t.link_index == li)[0]
            d = bd[ai]
            transit = (~t.overloaded) | (
                np.arange(t.padded_nodes) == int(enc.roots[ai])
            )
            for e in es:
                u, v = int(t.src[e]), int(t.dst[e])
                if (
                    t.edge_ok[e]
                    and transit[u]
                    and d[u] < 3.0e38
                    and d[v] < 3.0e38
                    and d[u] + t.w[e] == d[v]
                ):
                    return True
            return False

        def changes_for(s) -> List[dict]:
            # changed prefixes: validity flipped, metric moved, or the
            # merged ECMP lane set moved
            diff = (route_ok[s] != route_ok[base]) | (
                route_ok[s]
                & route_ok[base]
                & (
                    (m_star[s] != m_star[base])
                    | (eff_lanes[s] != eff_lanes[base]).any(axis=(1, 2))
                )
            )
            changes = []
            for p in np.nonzero(diff)[0]:
                was, now = bool(route_ok[base, p]), bool(route_ok[s, p])
                changes.append(
                    {
                        "prefix": table.row_prefix[p],
                        "change": change_kind(was, now),
                        "old_nexthops": nh_names(base, p) if was else [],
                        "new_nexthops": nh_names(s, p) if now else [],
                        "old_metric": (
                            float(m_star[base, p]) if was else None
                        ),
                        "new_metric": float(m_star[s, p]) if now else None,
                    }
                )
            return changes

        if simultaneous:
            with self.probe.phase(pipeline.DECODE):
                changes = changes_for(0)
                any_on_dag = bool(
                    any(on_dag(ai, li) for ai, li in (fail_sets[0] or ()))
                )
            return {
                "eligible": True,
                "vantage": me,
                "engine": "multiarea",
                "simultaneous": True,
                "failures": [
                    {
                        "links": [list(f) for f in link_failures],
                        "on_shortest_path_dag": any_on_dag,
                        "routes_changed": len(changes),
                        "changes": changes,
                    }
                ],
            }

        out = []
        with self.probe.phase(pipeline.DECODE):
            for s, ((n1, n2), tup) in enumerate(zip(link_failures, pairs)):
                if tup is None:
                    out.append(errors[s])
                    continue
                changes = changes_for(s)
                entry = {
                    "link": [n1, n2],
                    "area": enc.areas[tup[0][0]],
                    "on_shortest_path_dag": bool(
                        any(on_dag(ai, li) for ai, li in tup)
                    ),
                    "routes_changed": len(changes),
                    "changes": changes,
                }
                if len(tup) > 1:
                    # parallel bundle (within or across areas): every
                    # member failed at once as one set
                    entry["links_failed"] = len(tup)
                    entry["areas"] = sorted(
                        {enc.areas[ai] for ai, _ in tup}
                    )
                out.append(entry)
        return {
            "eligible": True,
            "vantage": me,
            "engine": "multiarea",
            "failures": out,
        }


class NativeWhatIfEngine:
    """Single-area what-if over the NATIVE warm-start sweep.

    The C++ incremental-repair solver (native/spf_scalar.cc
    spf_warm_sweep — the same off-DAG-skip + affected-region trick the
    device kernel uses) solves a single-link failure in tens of
    microseconds at 1024-node scale, while the what-if device path pays
    1-2 dispatch round trips before any compute.  For small operator
    queries where those trips cost more than the native solve, the
    native engine is the right backend, and Decision auto-picks it from
    the measured
    dispatch round trip (the same calibration the Decision backend's
    device cutover uses).  Output schema and selection semantics are
    identical to WhatIfApiEngine — selection runs the numpy mirror of
    the device chain (ops.np_select.select_routes_numpy, jax-free so
    scalar-only deployments never load the device stack), so the two
    engines are interchangeable and parity-tested.
    """

    def __init__(self, solver: SpfSolver) -> None:
        self.solver = solver
        self._cache_key = None
        self._ctx = None
        self.num_engine_builds = 0
        self.num_sweeps = 0

    def _engine_for(self, area_link_states, prefix_state, change_seq):
        from openr_tpu.ops.csr import (
            encode_link_state,
            encode_prefix_candidates,
        )
        from openr_tpu.ops.native_spf import NativeSpf
        from openr_tpu.ops.np_select import select_routes_numpy

        (area, ls), = area_link_states.items()
        key = (area, ls.topology_seq, change_seq)
        if self._cache_key == key:
            return self._ctx
        topo = encode_link_state(ls)
        me = self.solver.my_node_name
        cands = encode_prefix_candidates(prefix_state, topo, area)
        native = NativeSpf(topo, me)
        native.warm_prepare()
        # shared lane-count formula (ops.whatif.root_lane_count) — a
        # third independent implementation here could silently diverge
        # from the device engine and the bench on padded topologies
        from openr_tpu.ops.whatif import root_lane_count

        D = root_lane_count(topo, topo.node_id(me))
        soft = np.zeros(topo.padded_nodes, np.int32)
        sel_args = (
            cands.cand_node,
            cands.cand_ok,
            cands.drain_metric,
            cands.path_pref,
            cands.source_pref,
            cands.distance,
            cands.min_nexthop,
        )
        base_dist, base_nh_mask = native.warm_base
        base_lanes = native.lanes_dense(D, mask=base_nh_mask)
        bvalid, bmetric, bnh, _n, _u = select_routes_numpy(
            *sel_args,
            base_dist,
            base_lanes,
            topo.overloaded,
            soft,
            topo.node_id(me),
        )
        pair_links = build_pair_links(topo.links)
        self._ctx = dict(
            topo=topo,
            native=native,
            cands=cands,
            D=D,
            soft=soft,
            sel_args=sel_args,
            base=(bvalid, bmetric, bnh),
            pair_links=pair_links,
            lane_names=lane_names_for(topo, me),
            root_id=topo.node_id(me),
        )
        self._cache_key = key
        self.num_engine_builds += 1
        return self._ctx

    def run(
        self,
        link_failures: List[Tuple[str, str]],
        area_link_states,
        prefix_state,
        change_seq: int,
        simultaneous: bool = False,
    ) -> Dict:
        from openr_tpu.ops.np_select import select_routes_numpy

        ctx = self._engine_for(area_link_states, prefix_state, change_seq)
        me = self.solver.my_node_name
        topo, native, D = ctx["topo"], ctx["native"], ctx["D"]
        bvalid, bmetric, bnh = ctx["base"]
        v4_ok = self.solver.enable_v4 or self.solver.v4_over_v6_nexthop
        prefixes = ctx["cands"].prefixes
        lane_names = ctx["lane_names"]

        def lanes_to_names(row) -> List[str]:
            return decode_lane_names(lane_names, row)

        lid_sets, errors = resolve_pair_failures(
            ctx["pair_links"], link_failures, allow_parallel=True
        )
        self.num_sweeps += 1

        def select_current():
            lanes = native.lanes_dense(D)
            return select_routes_numpy(
                *ctx["sel_args"],
                native.dist,
                lanes,
                topo.overloaded,
                ctx["soft"],
                ctx["root_id"],
            )

        def diff_changes(valid, metric, nh_out) -> List[dict]:
            diff = (valid != bvalid) | (
                valid
                & bvalid
                & ((metric != bmetric) | (nh_out != bnh).any(axis=1))
            )
            changes = []
            for p in np.nonzero(diff)[0]:
                prefix = prefixes[p]
                if prefix_is_v4(prefix) and not v4_ok:
                    continue
                was, now = bool(bvalid[p]), bool(valid[p])
                changes.append(
                    {
                        "prefix": prefix,
                        "change": change_kind(was, now),
                        "old_nexthops": (
                            lanes_to_names(bnh[p]) if was else []
                        ),
                        "new_nexthops": (
                            lanes_to_names(nh_out[p]) if now else []
                        ),
                        "old_metric": float(bmetric[p]) if was else None,
                        "new_metric": float(metric[p]) if now else None,
                    }
                )
            return changes

        if simultaneous:
            bad = [e for e in errors if e is not None]
            if bad:
                return {
                    "eligible": True,
                    "vantage": me,
                    "engine": "native",
                    "simultaneous": True,
                    "failures": bad,
                }
            all_lids = [l for tup in lid_sets for l in tup]  # type: ignore[union-attr]
            any_on_dag = any(native.link_on_dag[l] for l in all_lids)
            if any_on_dag:
                # native multi-link cold solve with the FULL set — an
                # off-DAG member can carry the reroute once on-DAG
                # members fail, so it must be removed too.  Only a set
                # with NO on-DAG member provably changes nothing.
                native.solve_set(all_lids)
                valid, metric, nh_out, _n, _u = select_current()
                changes = diff_changes(valid, metric, nh_out)
            else:
                changes = []
            return {
                "eligible": True,
                "vantage": me,
                "engine": "native",
                "simultaneous": True,
                "failures": [
                    {
                        "links": [list(f) for f in link_failures],
                        "on_shortest_path_dag": any_on_dag,
                        "routes_changed": len(changes),
                        "changes": changes,
                    }
                ],
            }

        out = []
        for s, ((n1, n2), tup) in enumerate(zip(link_failures, lid_sets)):
            if tup is None:
                out.append(errors[s])
                continue
            on_dag = bool(any(native.link_on_dag[l] for l in tup))
            changes = []
            if on_dag:
                if len(tup) == 1:
                    # single link: the warm incremental sweep
                    native.warm_sweep(
                        np.asarray([tup[0]], np.int32), keep_last=True
                    )
                else:
                    # parallel bundle: fail every member at once (cold
                    # set solve — same removal the device engine does)
                    native.solve_set(list(tup))
                valid, metric, nh_out, _n, _u = select_current()
                changes = diff_changes(valid, metric, nh_out)
            entry = {
                "link": [n1, n2],
                "on_shortest_path_dag": on_dag,
                "routes_changed": len(changes),
                "changes": changes,
            }
            if len(tup) > 1:
                entry["links_failed"] = len(tup)
            out.append(entry)
        return {"eligible": True, "vantage": me, "engine": "native", "failures": out}


class GenericSolverWhatIfEngine:
    """Algorithm-complete what-if fallback: rebuild the LSDB with the
    candidate links actually removed and run the FULL SpfSolver (the
    same selection code every installed route went through), then diff
    the route databases.

    This is the slow path — one full scalar build per failure (or one
    for a simultaneous set) — but it supports everything
    ``build_route_db`` supports: KSP2_ED_ECMP, any
    route_selection_algorithm, multi-area LSDBs, cross-area
    redistribution, simultaneous sets.  jax-free, so scalar-only
    deployments use it without loading the device stack.  It serves the
    queries the fast engines decline (reference
    Decision.cpp:342 getDecisionRouteDb computes any configured
    algorithm; our fast engines cover the SHORTEST_DISTANCE family).
    """

    engine_label = "generic-solver"

    def __init__(self, solver) -> None:
        self.solver = solver
        self.num_builds = 0
        self._cache_key = None
        self._base_view = None
        self._pair_links: Dict = {}

    def _build(self, states, prefix_state):
        """One full route build; subclasses swap the compute engine."""
        return self.solver.build_route_db(states, prefix_state)

    @staticmethod
    def _pairs_map(area_link_states) -> Dict:
        """pair -> occurrences across every area, through the SHARED
        build_pair_links so link-identity semantics live in one place
        (only uniqueness of the pair is consumed)."""
        m: Dict = {}
        for _area, ls in sorted(area_link_states.items()):
            for pair, vals in build_pair_links(ls.all_links()).items():
                m.setdefault(pair, []).extend(vals)
        return m

    @staticmethod
    def _states_without(area_link_states, drop_pairs) -> Dict:
        import dataclasses

        from openr_tpu.decision.link_state import LinkState

        out: Dict = {}
        for area, ls in area_link_states.items():
            nls = LinkState(area, ls.my_node_name)
            for _node, db in sorted(ls.get_adjacency_databases().items()):
                filtered = dataclasses.replace(
                    db,
                    adjacencies=[
                        a
                        for a in db.adjacencies
                        if frozenset(
                            (db.this_node_name, a.other_node_name)
                        )
                        not in drop_pairs
                    ],
                )
                nls.update_adjacency_database(filtered)
            out[area] = nls
        return out

    def run(
        self,
        link_failures: List[Tuple[str, str]],
        area_link_states,
        prefix_state,
        change_seq: int,
        simultaneous: bool = False,
    ) -> Optional[Dict]:
        me = self.solver.my_node_name

        def view(db):
            if db is None:  # vantage absent from the (modified) LSDB
                return {}
            return {
                p: (
                    float(e.igp_cost),
                    sorted({n.neighbor_node_name for n in e.nexthops}),
                )
                for p, e in db.unicast_routes.items()
            }

        # base view + pair map cached per LSDB generation, like every
        # other what-if engine
        key = (
            change_seq,
            tuple(
                (a, area_link_states[a].topology_seq)
                for a in sorted(area_link_states)
            ),
        )
        if self._cache_key != key:
            base = self._build(area_link_states, prefix_state)
            self.num_builds += 1
            if base is None:
                return None  # no vantage in the LSDB yet -> ineligible
            self._base_view = view(base)
            self._pair_links = self._pairs_map(area_link_states)
            self._cache_key = key
        base_view = self._base_view
        # parallel bundles are fine here: removal is by node PAIR, which
        # drops every parallel adjacency at once
        resolved, errors = resolve_pair_failures(
            self._pair_links, link_failures, allow_parallel=True
        )
        v4_ok = self.solver.enable_v4 or self.solver.v4_over_v6_nexthop

        def diff_against(mod_db) -> List[dict]:
            mod_view = view(mod_db)
            changes = []
            for p in sorted(set(base_view) | set(mod_view)):
                if prefix_is_v4(p) and not v4_ok:
                    continue
                old, new = base_view.get(p), mod_view.get(p)
                if old == new:
                    continue
                changes.append(
                    {
                        "prefix": p,
                        "change": change_kind(
                            old is not None, new is not None
                        ),
                        "old_nexthops": old[1] if old else [],
                        "new_nexthops": new[1] if new else [],
                        "old_metric": old[0] if old else None,
                        "new_metric": new[0] if new else None,
                    }
                )
            return changes

        def solve_without(drop_pairs) -> List[dict]:
            mod = self._states_without(area_link_states, drop_pairs)
            self.num_builds += 1
            return diff_against(self._build(mod, prefix_state))

        if simultaneous:
            bad = [e for e in errors if e is not None]
            if bad:
                return {
                    "eligible": True,
                    "vantage": me,
                    "engine": self.engine_label,
                    "simultaneous": True,
                    "failures": bad,
                }
            changes = solve_without(
                {frozenset(p) for p in link_failures}
            )
            return {
                "eligible": True,
                "vantage": me,
                "engine": self.engine_label,
                "simultaneous": True,
                "failures": [
                    {
                        "links": [list(f) for f in link_failures],
                        "on_shortest_path_dag": bool(changes),
                        "routes_changed": len(changes),
                        "changes": changes,
                    }
                ],
            }

        out = []
        for (n1, n2), hit, err in zip(link_failures, resolved, errors):
            if hit is None:
                out.append(err)
                continue
            changes = solve_without({frozenset((n1, n2))})
            entry = {
                "link": [n1, n2],
                "on_shortest_path_dag": bool(changes),
                "routes_changed": len(changes),
                "changes": changes,
            }
            if len(hit) > 1:
                # bundle: parallel links in one area, or the pair's
                # links across several areas — all removed at once
                entry["links_failed"] = len(hit)
            out.append(entry)
        return {
            "eligible": True,
            "vantage": me,
            "engine": self.engine_label,
            "failures": out,
        }


class DeviceBuildWhatIfEngine(GenericSolverWhatIfEngine):
    """What-if for configurations OUTSIDE the sweep kernels' algebra —
    KSP2_ED_ECMP prefixes in the LSDB, exotic selection rules — served
    by DEVICE full builds instead of the scalar solver.

    Same structure as the generic fallback (rebuild the LSDB minus the
    candidate links, diff), but each build runs through a dedicated
    TpuBackend: SPF + selection tables on device and KSP2 prefixes on
    the device KSP2 engine (decision/ksp2.py) — the identical compute
    path the daemon's own route builds use for these algorithms, so
    parity with installed routes is by construction.  O(failures)
    device builds rather than the O(1) sweep, but every build after the
    first reuses warm jit shapes; at reference scale that is orders of
    magnitude faster than the per-failure scalar build (the reference
    solves any-algorithm what-ifs scalar via getDecisionRouteDb,
    Decision.cpp:342 — this is that surface, accelerated).

    Builds that the backend itself declines (unsupported selection
    algorithm) transparently run scalar inside TpuBackend — answers
    never differ from GenericSolverWhatIfEngine, only their speed.
    """

    engine_label = "device-build"

    def __init__(self, solver) -> None:
        super().__init__(solver)
        from openr_tpu.decision.backend import TpuBackend

        #: dedicated backend: what-if builds on modified topologies must
        #: never pollute the daemon backend's encoding/table caches.
        #: min_device_prefixes=0 pins always-device (deterministic)
        #: explicitly rather than relying on the constructor default.
        self._backend = TpuBackend(solver, min_device_prefixes=0)

    def _build(self, states, prefix_state):
        return self._backend.build_route_db(
            states, prefix_state, force_full=True, cache_result=False
        )
