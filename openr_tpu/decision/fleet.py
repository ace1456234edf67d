"""Fleet RIB engine: every node's what-if RouteDb from one device batch.

The ctrl API's getRouteDbComputed answers "what routes would node X
compute?" — the reference runs a fresh scalar SpfSolver pass per call
(Decision.cpp:342), so a fleet-wide sweep costs |V| sequential
Dijkstras.  Here all vantage points are one batched device solve
(ops/fleet_tables.py: root = a batch dim over the multi-area SPF +
selection kernels, with per-area absence masked exactly like the scalar
semantics); tables are cached until the LSDB changes, and each ctrl
request decodes ONLY its root — through the SAME decode path the
Decision backend uses (backend._decode_rows), so fleet results can
never drift from the live RouteDb semantics.

Eligibility (else the scalar path runs, exactness preserved):
SHORTEST_DISTANCE or PER_AREA_SHORTEST_DISTANCE with best-route
selection, and no KSP2_ED_ECMP advertisements (the k-path trace is
per-root host work the batch can't amortize yet).  Multi-area LSDBs are
first-class: cross-area min-metric merge happens in decode, per-area
participation comes from each root's per-area symbol-table presence.
"""

from __future__ import annotations

from typing import Dict, Optional

import numpy as np

from openr_tpu.decision.rib import DecisionRouteDb
from openr_tpu.decision.spf_solver import SpfSolver
from openr_tpu.types import (
    PrefixForwardingAlgorithm,
    RouteComputationRules,
    prefix_is_v4,
)

ROOT_CHUNK = 1024


class FleetRibEngine:
    """Caches all-roots selection tables per LSDB change generation."""

    def __init__(
        self, solver: SpfSolver, mesh=None, pool=None, probe=None
    ) -> None:
        """``mesh``: optional ``jax.sharding.Mesh`` with a ``batch``
        axis — the vantage-root batch then shards across the mesh
        (ops.fleet_tables.sharded_fleet_tables), bit-identical to the
        single-device kernel.  ``pool``: optional
        :class:`~openr_tpu.parallel.mesh.DevicePool` — root chunks then
        spread as committed per-device dispatches over the pool's
        HEALTHY chips (the health-governed data-parallel path: a
        quarantined chip's share re-packs onto the survivors on the
        next solve, with no shard_map requirement).  ``probe``: optional
        :class:`~openr_tpu.tracing.pipeline.PipelineProbe` — fleet
        solves then record the same phase histograms / per-chip busy
        gauges route builds do (Decision shares the backend's probe so
        the whole dispatch plane lands on one ledger)."""
        from openr_tpu.tracing.pipeline import disabled_probe

        self.solver = solver  # settings template (v4 flags, labels, algo)
        self.mesh = mesh
        self.pool = pool
        self.probe = probe if probe is not None else disabled_probe()
        self._cache_key = None
        self._state = None  # dict of cached tables + decode context
        self._ksp2_scan = None  # (change_seq, result)
        #: pool health generation the collective mesh was derived under
        #: (PR-6 remnant: engines given BOTH a mesh and a pool re-derive
        #: the mesh from DevicePool.survivor_mesh() whenever the healthy
        #: set changes, so the shard_map-collective path re-packs on
        #: chip quarantine exactly like the committed-dispatch path)
        self._mesh_health_seq = None
        self._mesh_requested = mesh is not None
        #: previous generation's delta base (device-resident chunk
        #: outputs + host tables + kernel-input pins)
        self._prev_gen = None
        self.num_batched_solves = 0
        self.num_decodes = 0
        self.num_pool_dispatches = 0
        #: ids of the devices the mesh path's outputs landed on (read
        #: from their shardings) — proves a collective solve used every
        #: chip of the mesh, not only the first
        self.mesh_device_ids: set = set()
        self.num_delta_solves = 0
        self.num_delta_roots_fetched = 0
        self.num_delta_roots_skipped = 0

    def _active_mesh(self):
        """The collective mesh for this solve.  With no pool, the
        constructor's mesh is pinned.  With a pool, the mesh re-derives
        from ``DevicePool.survivor_mesh()`` on every health transition:
        a chip quarantine re-packs the collective onto the survivors
        (or, when fewer than two chips survive / shard_map is
        unavailable, drops to the committed-dispatch pool path), and a
        restore re-admits the chip."""
        if not self._mesh_requested:
            return None
        if self.pool is None:
            return self.mesh
        if self._mesh_health_seq != self.pool.health_seq:
            self.mesh = self.pool.survivor_mesh()
            self._mesh_health_seq = self.pool.health_seq
        return self.mesh

    # -- eligibility -------------------------------------------------------

    def eligible(self, area_link_states, prefix_state, change_seq) -> bool:
        if not area_link_states:
            return False
        s = self.solver
        if not s.enable_best_route_selection or s.route_selection_algorithm not in (
            RouteComputationRules.SHORTEST_DISTANCE,
            RouteComputationRules.PER_AREA_SHORTEST_DISTANCE,
        ):
            return False
        # the O(P*C) KSP2 scan is cached on the same change generation
        # as the tables — ctrl requests between LSDB changes skip it
        if self._ksp2_scan is not None and self._ksp2_scan[0] == change_seq:
            return self._ksp2_scan[1]
        ok = not any(
            entry.forwarding_algorithm
            == PrefixForwardingAlgorithm.KSP2_ED_ECMP
            for entries in prefix_state.prefixes().values()
            for entry in entries.values()
        )
        self._ksp2_scan = (change_seq, ok)
        return ok

    # -- table computation (cached) ---------------------------------------

    def _tables_for(self, area_link_states, prefix_state, change_seq):
        import jax
        import jax.numpy as jnp

        from openr_tpu.decision.backend import DEGREE_BUCKETS
        from openr_tpu.decision.cand_table import CandidateTable
        from openr_tpu.ops.csr import bucket_for, encode_multi_area
        from openr_tpu.ops.fleet_tables import fleet_multi_area_tables
        from openr_tpu.ops.jit_guard import call_jit_guarded

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
            # every node participating in ANY area gets a vantage row
            names = sorted(
                set().union(*[set(t.node_ids) for t in enc.topos])
            )
            roots_mat = np.asarray(
                [[t.node_ids.get(n, -1) for t in enc.topos] for n in names],
                np.int32,
            )
        D = bucket_for(max(enc.max_out_degree(), 1), DEGREE_BUCKETS)
        per_area = (
            self.solver.route_selection_algorithm
            == RouteComputationRules.PER_AREA_SHORTEST_DISTANCE
        )
        with self.probe.phase(pipeline.TRANSFER):
            dev = dict(
                src=jnp.asarray(enc.src),
                dst=jnp.asarray(enc.dst),
                w=jnp.asarray(enc.w),
                edge_ok=jnp.asarray(enc.edge_ok),
                overloaded=jnp.asarray(enc.overloaded),
                soft=jnp.asarray(enc.soft),
                cand_area=jnp.asarray(dv.cand_area),
                cand_node=jnp.asarray(dv.cand_node),
                cand_ok=jnp.asarray(dv.cand_ok),
                drain_metric=jnp.asarray(dv.drain_metric),
                path_pref=jnp.asarray(dv.path_pref),
                source_pref=jnp.asarray(dv.source_pref),
                distance=jnp.asarray(dv.distance),
                cand_node_in_area=jnp.asarray(dv.cand_node_in_area),
            )
        B = len(names)
        P, C = dv.cand_ok.shape
        A = enc.num_areas
        mesh = self._active_mesh()
        mesh_n = mesh.devices.size if mesh is not None else 1
        # pool path (no shard_map needed): root chunks spread round-robin
        # over the pool's HEALTHY chips as committed per-device
        # dispatches — a quarantined chip's share re-packs onto the
        # survivors on the next solve
        pool_devs = None
        chunk_rows = ROOT_CHUNK
        per_dev_args: dict = {}
        if mesh is None and self.pool is not None:
            healthy = self.pool.healthy_indices()
            if len(healthy) > 1:
                pool_devs = healthy
                chunk_rows = min(
                    ROOT_CHUNK, max(32, -(-B // len(healthy)))
                )
        # dense kernel args when the encoding carries the in-edge
        # planes (the scatter-free SPF formulation); also the
        # precondition for the on-device generation delta
        dense_keys = None
        if enc.has_dense:
            with self.probe.phase(pipeline.TRANSFER):
                dev = dict(
                    dev,
                    in_src=jnp.asarray(enc.in_src),
                    in_w=jnp.asarray(enc.in_w),
                    in_ok=jnp.asarray(enc.in_ok),
                    in_rank=jnp.asarray(enc.in_rank),
                    in_has=jnp.asarray(enc.in_has),
                )
                for k in ("src", "dst", "w", "edge_ok"):
                    dev.pop(k)
            dense_keys = True
        if mesh is not None:
            from openr_tpu.ops.fleet_tables import sharded_fleet_tables
            from openr_tpu.parallel.mesh import batch_sharding, replicated

            rep = replicated(mesh)
            dev = {k: jax.device_put(v, rep) for k, v in dev.items()}
            roots_sh = batch_sharding(mesh)
            fleet_fn = sharded_fleet_tables(
                mesh, D, per_area, dense=bool(dense_keys)
            )
            topo_keys = (
                ("in_src", "in_w", "in_ok", "in_rank", "in_has")
                if dense_keys
                else ("src", "dst", "w", "edge_ok")
            ) + ("overloaded", "soft")

        def args_on(idx):
            if idx not in per_dev_args:
                d = self.pool.device(idx)
                with self.probe.phase(pipeline.TRANSFER, device=idx):
                    per_dev_args[idx] = {
                        k: jax.device_put(v, d) for k, v in dev.items()
                    }
            return per_dev_args[idx]

        from openr_tpu.decision.backend import STREAM_SLOTS
        from openr_tpu.ops import jit_guard
        from openr_tpu.ops.fleet_tables import (
            fleet_multi_area_tables_dense,
            fleet_multi_area_tables_dense_delta,
        )
        from openr_tpu.ops.route_select import gather_selection_rows

        # on-device generation delta: when the previous generation's
        # chunk outputs are device-resident and every decode input is
        # provably equivalent, each chunk solves with the fused
        # solve+diff kernel and only CHANGED roots' rows cross the host
        # boundary — the unchanged rows patch through from the previous
        # generation's host tables
        delta = self._fleet_delta_ctx(
            enc, dv, table, names, roots_mat, chunk_rows, pool_devs,
            mesh, D,
        )
        if delta is not None:
            use = delta["use"].copy()
            shortest = delta["shortest"].copy()
            lanes = delta["lanes"].copy()
            valid = delta["valid"].copy()
            self.num_delta_solves += 1
        else:
            use = np.empty((B, P, C), bool)
            shortest = np.empty((B, P, A), np.float32)
            lanes = np.empty((B, P, A, D), bool)
            valid = np.empty((B, P, A), bool)

        def dispatch_chunk(off):
            chunk = roots_mat[off : off + chunk_rows]
            with self.probe.phase(pipeline.PAD_PACK):
                b = 1 << max(5, (len(chunk) - 1).bit_length())  # pow2
                b = ((b + mesh_n - 1) // mesh_n) * mesh_n  # whole shards
                padded = np.full((b, A), -1, np.int32)
                padded[: len(chunk)] = chunk
            # a fully -1 pad row would make SPF roots all-absent: fine
            idx = None
            ch = None
            if mesh is not None:
                with self.probe.phase(pipeline.DEVICE_COMPUTE):
                    out = fleet_fn(
                        jax.device_put(padded, roots_sh),
                        *(dev[k] for k in topo_keys),
                        dev["cand_area"],
                        dev["cand_node"],
                        dev["cand_ok"],
                        dev["drain_metric"],
                        dev["path_pref"],
                        dev["source_pref"],
                        dev["distance"],
                        dev["cand_node_in_area"],
                    )
                self.mesh_device_ids.update(
                    d.id for d in out[0].sharding.device_set
                )
            else:
                if pool_devs is not None:
                    idx = pool_devs[(off // chunk_rows) % len(pool_devs)]
                    args = args_on(idx)
                    with self.probe.phase(pipeline.TRANSFER, device=idx):
                        roots_dev = jax.device_put(
                            jnp.asarray(padded), self.pool.device(idx)
                        )
                else:
                    idx = 0
                    args = dev
                    roots_dev = jnp.asarray(padded)
                extra = {}
                if delta is not None:
                    kernel = fleet_multi_area_tables_dense_delta
                    pu, ps, pl, pv = delta["chunks"][off]
                    extra = dict(
                        prev_use=pu,
                        prev_shortest=ps,
                        prev_lanes=pl,
                        prev_valid=pv,
                    )
                elif dense_keys:
                    kernel = fleet_multi_area_tables_dense
                else:
                    kernel = fleet_multi_area_tables
                with self.probe.phase(
                    pipeline.DEVICE_COMPUTE, device=idx
                ), jit_guard.dispatch_device(
                    idx if pool_devs is not None else None
                ):
                    out = call_jit_guarded(
                        kernel,
                        roots=roots_dev,
                        max_degree=D,
                        per_area_distance=per_area,
                        **args,
                        **extra,
                    )
                if delta is not None:
                    out, ch = out[:4], out[4]
                if self.pool is not None and pool_devs is not None:
                    self.pool.note_inflight(idx)
                    self.num_pool_dispatches += 1
                for o in (ch,) if ch is not None else out:
                    o.copy_to_host_async()
            return {
                "off": off,
                "n": len(chunk),
                "idx": idx,
                "out": out,
                "ch": ch,
            }

        def drain_chunk(rec):
            off, n, idx = rec["off"], rec["n"], rec["idx"]
            if idx is not None:
                # streamed completion: the wait charges ONLY this chip
                with self.probe.phase(pipeline.STREAM_DRAIN, device=idx):
                    for o in (
                        (rec["ch"],) if rec["ch"] is not None else rec["out"]
                    ):
                        o.block_until_ready()
                if self.pool is not None and pool_devs is not None:
                    self.pool.note_complete(idx)
            if rec["ch"] is not None:
                with self.probe.phase(pipeline.DEVICE_GET, device=idx):
                    ch = np.asarray(jax.device_get(rec["ch"]))[:n]
                rows = np.nonzero(ch)[0]
                self.num_delta_roots_fetched += len(rows)
                self.num_delta_roots_skipped += n - len(rows)
                if not len(rows):
                    return
                from openr_tpu.decision.backend import ROWSEL_BUCKETS
                from openr_tpu.ops.csr import bucket_for

                k = bucket_for(len(rows), ROWSEL_BUCKETS)
                idx_arr = np.zeros(k, np.int64)
                idx_arr[: len(rows)] = rows
                with self.probe.phase(
                    pipeline.DEVICE_SELECT, device=idx
                ), jit_guard.dispatch_device(
                    idx if pool_devs is not None else None
                ):
                    g = call_jit_guarded(
                        gather_selection_rows,
                        *rec["out"],
                        jnp.asarray(idx_arr),
                    )
                with self.probe.phase(pipeline.DEVICE_GET, device=idx):
                    gu, gs, gl, gv = jax.device_get(g)
                m = len(rows)
                use[off + rows] = gu[:m]
                shortest[off + rows] = gs[:m]
                lanes[off + rows] = gl[:m]
                valid[off + rows] = gv[:m]
                return
            with self.probe.phase(pipeline.DEVICE_GET, device=idx):
                u, s_, l, v = jax.device_get(rec["out"])
            use[off : off + n] = u[:n]
            shortest[off : off + n] = s_[:n]
            lanes[off : off + n] = l[:n]
            valid[off : off + n] = v[:n]

        # streamed dispatch: chunk N+1's pad/transfer overlaps chunk
        # N's solve; the in-flight slot gate keeps any one chip's
        # undrained backlog bounded, and chunks drain in COMPLETION
        # order so host-side assembly overlaps the solves still in
        # flight
        pending: list = []
        chunk_outs: dict = {}
        for off in range(0, B, chunk_rows):
            if pool_devs is not None:
                idx = pool_devs[(off // chunk_rows) % len(pool_devs)]
                while self.pool.inflight(idx) >= STREAM_SLOTS:
                    sel = next(
                        j
                        for j, r in enumerate(pending)
                        if r["idx"] == idx
                    )
                    early = pending.pop(sel)
                    chunk_outs[early["off"]] = early["out"]
                    drain_chunk(early)
            pending.append(dispatch_chunk(off))
        while pending:
            sel = 0
            for j, r in enumerate(pending):
                if r["idx"] is not None and all(
                    o.is_ready()
                    for o in (
                        (r["ch"],) if r["ch"] is not None else r["out"]
                    )
                ):
                    sel = j
                    break
            rec = pending.pop(sel)
            chunk_outs[rec["off"]] = rec["out"]
            drain_chunk(rec)
        self._state = dict(
            enc=enc,
            dv=dv,
            table=table,
            names=names,
            index={n: i for i, n in enumerate(names)},
            use=use,
            shortest=shortest,
            lanes=lanes,
            valid=valid,
        )
        self._retain_fleet_delta(
            enc, dv, table, names, roots_mat, chunk_rows, pool_devs,
            mesh, D, chunk_outs, use, shortest, lanes, valid,
        )
        self._cache_key = key
        self.num_batched_solves += 1
        return self._state

    #: device-resident fleet outputs beyond this size are not retained
    #: as a delta base (mirrors TpuBackend.WARM_MAX_TABLE_BYTES)
    DELTA_MAX_TABLE_BYTES = 64 << 20

    def _fleet_delta_ctx(
        self, enc, dv, table, names, roots_mat, chunk_rows, pool_devs,
        mesh, D,
    ):
        """Eligibility for the fleet generation delta: the previous
        generation's device-resident chunk outputs may vouch for
        'root unchanged' only when every KERNEL INPUT mapping is
        equivalent — same vantage list and per-area root ids, same
        symbol tables (value equality: the fleet engine re-encodes per
        generation), same candidate row->prefix mapping and shapes,
        same chunk decomposition and chip assignment.  Decode inputs
        read fresh state per request (prefix entries, drain lookups,
        min_nexthop), so they impose no additional pinning."""
        prev = self._prev_gen
        if prev is None or mesh is not None or not enc.has_dense:
            return None
        if (
            prev["degree"] != D
            or prev["chunk_rows"] != chunk_rows
            or prev["pool_devs"] != pool_devs
            or prev["names"] != names
            or not np.array_equal(prev["roots_mat"], roots_mat)
            or prev["shape"] != dv.cand_ok.shape
            or prev["row_prefix"] != table.row_prefix
            or prev["id_to_node"]
            != [t.id_to_node for t in enc.topos]
        ):
            return None
        return prev

    def _retain_fleet_delta(
        self, enc, dv, table, names, roots_mat, chunk_rows, pool_devs,
        mesh, D, chunk_outs, use, shortest, lanes, valid,
    ) -> None:
        if mesh is not None or not enc.has_dense or not chunk_outs:
            self._prev_gen = None
            return
        table_bytes = use.nbytes + shortest.nbytes + lanes.nbytes + valid.nbytes
        if table_bytes > self.DELTA_MAX_TABLE_BYTES:
            self._prev_gen = None
            return
        self._prev_gen = dict(
            degree=D,
            chunk_rows=chunk_rows,
            pool_devs=list(pool_devs) if pool_devs is not None else None,
            names=list(names),
            roots_mat=roots_mat,
            shape=dv.cand_ok.shape,
            row_prefix=list(table.row_prefix),
            id_to_node=[t.id_to_node for t in enc.topos],
            chunks=chunk_outs,
            use=use,
            shortest=shortest,
            lanes=lanes,
            valid=valid,
        )

    # -- per-root decode (the backend's own decode path) -------------------

    def compute_for_node(
        self, node: str, area_link_states, prefix_state, change_seq
    ) -> Optional[DecisionRouteDb]:
        """The RouteDb `node` would compute, decoded from the cached
        batch tables; None when node is unknown (caller falls back)."""
        from openr_tpu.decision.backend import TpuBackend

        from openr_tpu.tracing import pipeline

        st = self._tables_for(area_link_states, prefix_state, change_seq)
        ri = st["index"].get(node)
        if ri is None:
            return None
        self.num_decodes += 1
        tb = TpuBackend(self._vantage_solver(node))
        table = st["table"]
        with self.probe.phase(pipeline.DECODE):
            row_items = [
                (int(r), table.row_prefix[r])
                for r in np.nonzero(st["use"][ri].any(axis=1))[0]
                if table.row_prefix[r] is not None
            ]
            ksp2 = ([], {})
            results = tb._decode_rows(
                row_items,
                st["use"][ri],
                st["shortest"][ri],
                st["lanes"][ri],
                st["valid"][ri],
                st["dv"],
                None,
                st["enc"],
                area_link_states,
                prefix_state,
                ksp2,
            )
            # tb's probe is off: its KSP2 phases stay inside this decode
            results.update(
                tb._ksp2_routes(
                    ksp2, st["enc"], area_link_states, prefix_state
                )
            )
            db = DecisionRouteDb()
            for _prefix, entry in sorted(results.items()):
                if entry is not None:
                    db.add_unicast_route(entry)
            if self.solver.enable_node_segment_label:
                tb.solver._build_node_label_routes(area_link_states, db)
        return db

    def _vantage_solver(self, node: str) -> SpfSolver:
        s = self.solver
        return SpfSolver(
            node,
            enable_v4=s.enable_v4,
            enable_node_segment_label=s.enable_node_segment_label,
            enable_best_route_selection=s.enable_best_route_selection,
            v4_over_v6_nexthop=s.v4_over_v6_nexthop,
            route_selection_algorithm=s.route_selection_algorithm,
        )

    # -- fleet summary -----------------------------------------------------

    def fleet_summary(
        self, area_link_states, prefix_state, change_seq
    ) -> Dict[str, dict]:
        """Per-node unicast route counts + total nexthops from ONE batch
        solve — the 'what does every router see' operator view.  Applies
        the same host-side gates the decode applies (v4 family,
        skip-if-self, min-nexthop over the cross-area merge) so counts
        always match compute_for_node."""
        st = self._tables_for(area_link_states, prefix_state, change_seq)
        dv, table = st["dv"], st["table"]
        use, shortest, lanes, valid = (
            st["use"],
            st["shortest"],
            st["lanes"],
            st["valid"],
        )
        B, P, A = valid.shape

        include = np.asarray(
            [
                p is not None
                and (
                    self.solver.enable_v4
                    or self.solver.v4_over_v6_nexthop
                    or not prefix_is_v4(p)
                )
                for p in table.row_prefix
            ],
            bool,
        )  # [P]
        # cross-area min-metric merge, vectorized (SpfSolver.cpp:276-302)
        m = np.where(valid, shortest, np.inf)  # [B, P, A]
        m_star = m.min(axis=2)  # [B, P]
        at_min = valid & (m == m_star[:, :, None])
        num_nh_area = lanes.sum(axis=3)  # [B, P, A]
        merged = (num_nh_area * at_min).sum(axis=2)  # [B, P]
        # per-root gates, matching the backend decode exactly:
        #   min-nexthop req = max over THIS root's selection winners
        #   (not all candidates — a losing advertiser's requirement must
        #   not gate the winner's route)
        #   skip-if-self by GLOBAL candidate identity (adv_gid interned
        #   per advertiser name; a never-advertising root has no gid and
        #   can never self-win)
        adv_gid = table.adv_gid  # [P, C] (-1 = empty slot)
        gid_of = table._node_gid
        self_win = np.zeros((B, P), bool)
        req = np.zeros((B, P), np.int32)
        for i, name in enumerate(st["names"]):
            req[i] = np.max(np.where(use[i], dv.min_nexthop, 0), axis=1)
            g = gid_of.get(name)
            if g is not None:
                self_win[i] = (use[i] & (adv_gid == g)).any(axis=1)
        route_ok = (
            include[None, :]
            & valid.any(axis=2)
            & ~self_win
            & (merged > 0)
            & (merged >= req)
        )
        out = {}
        for i, name in enumerate(st["names"]):
            out[name] = {
                "num_routes": int(route_ok[i].sum()),
                "total_nexthops": int(merged[i][route_ok[i]].sum()),
            }
        return out
