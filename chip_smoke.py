"""Chip smoke: drive the Decision/SPF device path and the what-if engine
once on the chip, through the daemon's own entry points, and check every
answer against the scalar reference.

    python chip_smoke.py            # one chip: Phases A and B
    python chip_smoke.py --chips 4  # four chips: Phase C only

Phase A  a 64x64 grid (4,096 nodes, 8,064 links, 100 prefixes per node)
         fed as KvStore publications to one OpenrNode on TpuBackend with
         the daemon's default compute config: initial build, link-metric
         changes (warm path), a node leave (structural path), then
         get_route_db and get_link_failure_whatif through the ctrl
         handler.  FIB and answers vs the scalar SpfSolver.
Phase B  the what-if engine (LinkFailureSweep + SweepRouteSelector) on
         the 1,024-node headline WAN, 10,240 link failures, route deltas
         vs NativeSpf (or the scalar Dijkstra where no native library
         builds) on a seeded sample.
Phase C  the Phase B engine on a 4-device mesh, bit for bit against the
         1-device engine, plus the sharded fleet-RIB engine over the
         Phase A grid against the scalar solver.

One JSON line per phase; the LAST line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Exits non-zero, with the reason on stderr and no such line, when JAX
finds no TPU, a phase raises, parity fails, or the device path fell
back.  The phases are plain functions a test can run at a tiny size on
the CPU; only ``main()`` insists on the chip.
"""

from __future__ import annotations

import argparse
import json
import sys
import time


class SmokeFailure(AssertionError):
    """A check of the device path failed."""


def _check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def _emit(record: dict) -> None:
    print(json.dumps(record, sort_keys=True), flush=True)


class CompileMeter:
    """Seconds JAX spent tracing, lowering and compiling (persistent
    cache retrievals included), and persistent-cache hits/misses, from
    JAX's own monitoring events."""

    _EVENTS = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )
    _installed = None

    def __init__(self) -> None:
        self.secs = 0.0
        self.cache_hits = 0
        self.cache_misses = 0

    @classmethod
    def shared(cls) -> "CompileMeter":
        if cls._installed is None:
            from jax import monitoring

            m = cls()

            def on_duration(event, secs, **_kw):
                if event in cls._EVENTS:
                    m.secs += secs

            def on_event(event, **_kw):
                if event == "/jax/compilation_cache/cache_hits":
                    m.cache_hits += 1
                elif event == "/jax/compilation_cache/cache_misses":
                    m.cache_misses += 1

            monitoring.register_event_duration_secs_listener(on_duration)
            monitoring.register_event_listener(on_event)
            cls._installed = m
        return cls._installed

    def snapshot(self) -> tuple:
        return (self.secs, self.cache_hits, self.cache_misses)

    def since(self, snap: tuple) -> dict:
        return {
            "compile_s": self.secs - snap[0],
            "cache_hits": self.cache_hits - snap[1],
            "cache_misses": self.cache_misses - snap[2],
        }


def _peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# ---------------------------------------------------------------------------
# Phase A — the daemon's route computation
# ---------------------------------------------------------------------------


def _grid_prefix(i: int, p: int) -> str:
    return f"10.{(i >> 8) & 255}.{i & 255}.{p}/32"


def _sub_prefix_state(prefix_state, prefixes):
    """The advertisements of ``prefixes`` only: route selection is per
    prefix, so the scalar solver over this subset computes exactly the
    routes it would compute for them over the whole state."""
    from openr_tpu.decision.prefix_state import PrefixState

    sub = PrefixState()
    table = prefix_state.prefixes()
    for p in prefixes:
        for (node, area), entry in table.get(p, {}).items():
            sub.update_prefix(node, area, entry)
    return sub


def phase_a(side: int = 64, ppn: int = 100, n_metric_changes: int = 3,
            n_whatif: int = 64, n_sample: int = 1000, seed: int = 0) -> dict:
    """One OpenrNode (node0) on TpuBackend learns a side x side grid with
    ``ppn`` prefixes per node through its KvStore; returns the phase
    record, raising SmokeFailure on any mismatch or fallback."""
    import asyncio
    import random

    from openr_tpu import constants as Const
    from openr_tpu.common.runtime import SimClock
    from openr_tpu.ctrl.handler import OpenrCtrlHandler
    from openr_tpu.decision.backend import TpuBackend
    from openr_tpu.decision.spf_solver import SpfSolver
    from openr_tpu.decision.whatif_api import GenericSolverWhatIfEngine
    from openr_tpu.emulation.network import EmulatedNetwork
    from openr_tpu.emulation.topology import build_adj_dbs, grid_edges
    from openr_tpu.ops import jit_guard
    from openr_tpu.types import (
        AdjacencyDatabase,
        PrefixDatabase,
        PrefixEntry,
        Value,
        prefix_key,
    )

    meter = CompileMeter.shared()
    snap = meter.snapshot()
    t_phase = time.perf_counter()
    rng = random.Random(seed)
    me = "node0"
    area = "0"
    edges = [list(e) for e in grid_edges(side)]
    names = sorted({n for a, b, _m in edges for n in (a, b)})
    node_of = {}
    rep_of = {}  # node -> its first prefix (all of a node's are alike)
    index_of = {n: i for i, n in enumerate(names)}
    for i, node in enumerate(names):
        for p in range(ppn):
            node_of[_grid_prefix(i, p)] = node
        rep_of[node] = _grid_prefix(i, 0)
    version: dict = {}

    def value(key_owner, obj):
        version[key_owner] = version.get(key_owner, 0) + 1
        return Value(
            version=version[key_owner],
            originator_id=key_owner.split(":")[0],
            value=json.dumps(obj.to_wire()).encode(),
            ttl=Const.TTL_INFINITY,
        )

    def adj_vals(nodes, dbs):
        out = {}
        for n in nodes:
            db = dbs.get(n) or AdjacencyDatabase(this_node_name=n, area=area)
            out[f"adj:{n}"] = value(f"{n}:adj", db)
        return out

    record: dict = {"phase": "A", "grid": side, "nodes": len(names),
                    "links": len(edges), "prefixes": len(node_of)}
    loop = asyncio.new_event_loop()
    clock = SimClock()
    net = EmulatedNetwork(clock, use_tpu_backend=None)

    async def drive():
        node = net.add_node(me)
        agent = net.agents[me]
        decision = node.decision
        backend = decision.backend
        _check(isinstance(backend, TpuBackend), "node is not on TpuBackend")
        _check(backend.min_device_prefixes is None,
               "daemon default must auto-calibrate the cutover")
        gov = backend.governor
        handler = OpenrCtrlHandler(node)
        node.start()
        kv = node.kv_store

        def builds():
            return (backend.num_device_builds, backend.num_scalar_builds,
                    backend.num_small_scalar_builds)

        async def settle(want_builds: int, what: str) -> float:
            """Run virtual time until Decision has taken ``want_builds``
            builds and the FIB agent holds its routes; wall seconds."""
            t0 = time.perf_counter()
            for _ in range(2000):
                await clock.run_for(0.1)
                done = sum(builds())
                if done >= want_builds and not decision._rebuild_pending:
                    await clock.run_for(1.0)  # let Fib program the delta
                    return time.perf_counter() - t0
            raise SmokeFailure(f"{what}: no build after 200 s virtual")

        def oracle_routes(prefixes):
            ps = _sub_prefix_state(decision.prefix_state, prefixes)
            db = SpfSolver(me).build_route_db(decision.area_link_states, ps)
            return {} if db is None else db.unicast_routes

        def compare_fib(prev_fib, prev_rep, what):
            """FIB vs scalar oracle over every prefix that changed in the
            FIB or (per the oracle) should have, plus a seeded sample."""
            fib = dict(agent.unicast)
            rep = oracle_routes(rep_of.values())
            moved_nodes = {
                node_of[p] for p in set(rep) | set(prev_rep)
                if (p in rep) != (p in prev_rep)
                or (p in rep and rep[p].to_unicast_route()
                    != prev_rep[p].to_unicast_route())
            }
            fib_changed = {
                p for p in set(fib) | set(prev_fib)
                if fib.get(p) != prev_fib.get(p)
            }
            oracle_changed = {
                _grid_prefix(index_of[n], k)
                for n in moved_nodes for k in range(ppn)
            }
            changed = fib_changed | oracle_changed
            others = sorted(set(node_of) - changed)
            sample = rng.sample(others, min(n_sample, len(others)))
            want = oracle_routes(sorted(changed) + sample)
            bad = [
                p for p in sorted(changed) + sample
                if fib.get(p) != (
                    want[p].to_unicast_route() if p in want else None
                )
            ]
            _check(not bad, f"{what}: FIB != scalar oracle for "
                   f"{len(bad)} prefixes, e.g. {bad[:3]}")
            return fib, rep, {
                "changed_prefixes": len(changed),
                "sampled_prefixes": len(sample),
                "compared": len(changed) + len(sample),
                "fib_routes": len(fib),
            }

        # -- initial build: adjacency + prefix publications --------------
        dbs = build_adj_dbs([tuple(e) for e in edges], area=area)
        t0 = time.perf_counter()
        kv.set_key_vals(area, adj_vals(names, dbs))
        for i, n in enumerate(names):
            kv.set_key_vals(area, {
                prefix_key(n, _grid_prefix(i, p)): value(
                    f"{n}:{p}",
                    PrefixDatabase(this_node_name=n, prefix_entries=[
                        PrefixEntry(_grid_prefix(i, p))]),
                )
                for p in range(ppn)
            })
        record["publish_wall_s"] = time.perf_counter() - t0
        comp0 = meter.secs
        record["initial_build_wall_s"] = await settle(1, "initial build")
        record["initial_build_compile_s"] = meter.secs - comp0
        want_routes = (len(names) - 1) * ppn
        _check(len(agent.unicast) == want_routes,
               f"initial FIB holds {len(agent.unicast)} routes, "
               f"want {want_routes}")
        first = builds()
        fib, rep, cmp0 = compare_fib({}, {}, "initial build")
        record["initial_parity"] = cmp0
        record["dispatch_rt_ms"] = backend.auto_dispatch_rt_ms

        # -- warm path: link-metric changes --------------------------------
        warm = []
        # the first change is on row 0, whose far nodes have one shortest
        # path from node0, so it moves routes; the others are anywhere
        row0 = [e for e in edges
                if max(int(e[0][4:]), int(e[1][4:])) < side]
        for k in range(n_metric_changes):
            e = rng.choice(row0 if k == 0 else edges)
            e[2] = e[2] + 1 + rng.randrange(9)
            dbs = build_adj_dbs([tuple(x) for x in edges], area=area)
            before = sum(builds())
            t0 = time.perf_counter()
            kv.set_key_vals(area, adj_vals(e[:2], dbs))
            wall = await settle(before + 1, f"metric change {k}")
            fib, rep, cmp = compare_fib(fib, rep, f"metric change {k}")
            warm.append({"link": e[:2], "metric": e[2], "wall_s": wall,
                         "encode": backend._last_encode_kind, **cmp})
        record["warm"] = warm

        # -- structural path: one node leaves ------------------------------
        gone = rng.choice([n for n in names if n != me])
        nbrs = sorted({b if a == gone else a for a, b, _m in edges
                       if gone in (a, b)})
        edges[:] = [e for e in edges if gone not in e[:2]]
        dbs = build_adj_dbs([tuple(x) for x in edges], area=area)
        before = sum(builds())
        kv.set_key_vals(area, adj_vals([gone] + nbrs, dbs))
        wall = await settle(before + 1, "node leave")
        fib, rep, cmp = compare_fib(fib, rep, "node leave")
        record["structural"] = {"node": gone, "neighbors": len(nbrs),
                                "wall_s": wall,
                                "encode": backend._last_encode_kind, **cmp}

        # -- ctrl handler: get_route_db ------------------------------------
        t0 = time.perf_counter()
        wire = handler.get_route_db()
        routes = {r["dest"]: r for r in wire["unicast_routes"]}
        _check(len(routes) == len(fib),
               f"get_route_db has {len(routes)} routes, FIB {len(fib)}")
        sample = rng.sample(sorted(node_of), min(n_sample, len(node_of)))
        want = oracle_routes(sample)
        bad = [p for p in sample if routes.get(p) != (
            want[p].to_unicast_route().to_wire() if p in want else None)]
        _check(not bad, f"get_route_db != scalar oracle for {len(bad)} "
               f"prefixes, e.g. {bad[:3]}")
        record["get_route_db"] = {"routes": len(routes),
                                  "compared": len(sample),
                                  "wall_s": time.perf_counter() - t0}

        # -- ctrl handler: get_link_failure_whatif -------------------------
        failures = [list(e[:2]) for e in rng.sample(edges, n_whatif)]
        t0 = time.perf_counter()
        got = handler.get_link_failure_whatif(failures)
        whatif_wall = time.perf_counter() - t0
        _check(got.get("eligible"), f"what-if not eligible: {got}")
        engine = got.get("engine")
        ref = GenericSolverWhatIfEngine(SpfSolver(me)).run(
            [tuple(f) for f in failures], decision.area_link_states,
            _sub_prefix_state(decision.prefix_state, rep_of.values()),
            decision._change_seq,
        )

        def key(c):
            return (c["change"], c["old_metric"], c["new_metric"],
                    tuple(c["old_nexthops"]), tuple(c["new_nexthops"]))

        n_changes = 0
        for f, g, r in zip(failures, got["failures"], ref["failures"]):
            want_by_node = {node_of[c["prefix"]]: key(c)
                            for c in r["changes"]}
            got_by_prefix = {c["prefix"]: key(c) for c in g["changes"]}
            _check(len(got_by_prefix) == ppn * len(want_by_node) and all(
                want_by_node.get(node_of[p]) == k
                for p, k in got_by_prefix.items()
            ), f"what-if {f}: {len(got_by_prefix)} changes != scalar "
               f"{ppn * len(want_by_node)}")
            n_changes += len(got_by_prefix)
        record["whatif"] = {"failures": len(failures), "engine": engine,
                            "route_changes": n_changes,
                            "wall_s": whatif_wall}

        # -- the device did the work, and nothing fell back ---------------
        after = builds()
        record["builds"] = {
            "device": backend.num_device_builds,
            "scalar": backend.num_scalar_builds,
            "small_scalar": backend.num_small_scalar_builds,
            "incremental": backend.num_incremental_builds,
            "fallback_injected": backend.num_fallback_injected,
            "dispatch_errors": backend.num_dispatch_errors,
            "fallback_cand_overflow": backend.num_fallback_cand_overflow,
        }
        _check(backend.num_device_builds > 0, "no device build")
        _check(after[1:] == first[1:],
               f"scalar build after the first build: {first} -> {after}")
        for k in ("fallback_injected", "dispatch_errors",
                  "fallback_cand_overflow"):
            _check(record["builds"][k] == 0, f"backend {k} != 0")
        g = gov.counter_snapshot() if gov is not None else {}
        record["governor"] = {k.rsplit(".", 1)[-1]: v for k, v in g.items()
                              if k.startswith("resilience.backend.")
                              and k.count(".") == 2}
        _check(gov is not None, "governor is off")
        _check(not gov.quarantined, "governor quarantined the device")
        _check(gov.num_shadow_mismatches == 0, "shadow verification failed")
        _check(gov.num_dispatch_failures == 0, "governor saw dispatch errors")
        record["jit_guard_cache_clear"] = jit_guard.counter_snapshot()[
            "jit_guard.cache_clear"]
        await node.stop()

    try:
        loop.run_until_complete(drive())
    finally:
        loop.close()
    record.update(meter.since(snap))
    record["wall_s"] = time.perf_counter() - t_phase
    record["peak_bytes_in_use"] = _peak_bytes()
    record["ok"] = True
    return record


# ---------------------------------------------------------------------------
# Phase B — the what-if engine at the headline size
# ---------------------------------------------------------------------------


def _scalar_routes(ls, topo, root, link):
    """Pure-Python oracle: (valid [V], metric [V], first-hop lanes [V, D])
    with ``link`` removed — Dijkstra from the root and from each of its
    neighbours, a neighbour being a first hop iff w + d_n == metric."""
    import numpy as np

    V = topo.num_nodes
    out_edges = topo.root_out_edges(root)
    ignore = frozenset([link])
    res = ls.run_spf(root, links_to_ignore=ignore)
    subs = [(lk, nbr, ls.run_spf(nbr, links_to_ignore=ignore))
            for lk, nbr in out_edges]
    name_of = {i: n for n, i in topo.node_ids.items()}
    valid = np.zeros(V, bool)
    metric = np.zeros(V, np.float32)
    lanes = np.zeros((V, len(out_edges)), np.int8)
    for p in range(V):
        n = name_of[p]
        if n == root or n not in res:
            continue
        m = res[n].metric
        for r, (lk, nbr, sub) in enumerate(subs):
            if lk in ignore:
                continue
            d_n = 0 if nbr == n else (sub[n].metric if n in sub else None)
            if d_n is not None and lk.get_max_metric() + d_n == m:
                lanes[p, r] = 1
        valid[p] = lanes[p].any()
        metric[p] = m
    return valid, metric, lanes


def _route_oracle(ls, topo, root, D):
    """(name, fn(link index) -> (valid, metric, lanes)): NativeSpf where
    the native library builds, else the pure-Python Dijkstra."""
    import numpy as np

    V = topo.num_nodes
    root_id = topo.node_id(root)
    try:
        from openr_tpu.ops.native_spf import NativeSpf

        native = NativeSpf(topo, root)
    except (ImportError, OSError) as e:
        print(f"chip_smoke: native SPF unavailable ({e}); "
              "Phase B uses the scalar Dijkstra", file=sys.stderr)
        return "scalar", lambda li: _scalar_routes(
            ls, topo, root, topo.links[li])

    def solve(li):
        native.solve(failed_link=li)
        nd = native.dist[:V]
        nl = native.lanes_dense(D)[:V]
        valid = np.isfinite(nd) & nl.any(axis=1) & (np.arange(V) != root_id)
        return valid, nd, nl

    return "native", solve


def _sweep(topo, cands, fails, mesh):
    from openr_tpu.ops.sweep_select import SweepRouteSelector
    from openr_tpu.ops.whatif import LinkFailureSweep

    eng = LinkFailureSweep(topo, "node0", mesh=mesh)
    sel = SweepRouteSelector(topo, "node0", cands, max_degree=eng.D,
                             mesh=mesh)
    t0 = time.perf_counter()
    sel.run(eng.run(fails, fetch=False))
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    sweep = eng.run(fails, fetch=False)
    deltas = sel.run(sweep)
    warm = time.perf_counter() - t0
    devices = {d.id for c in sweep.chunks or [] for d in
               c[2].sharding.device_set}
    return eng, sweep, deltas, {"cold_wall_s": cold, "warm_wall_s": warm,
                                "devices": sorted(devices)}


_DELTA_FIELDS = ("snap_row", "base_valid", "base_metric", "base_lanes",
                 "delta_row", "delta_prefix", "delta_valid", "delta_metric",
                 "delta_lanes")


def phase_b(n_nodes: int = 1024, batch: int = 10_240, n_sample: int = 256,
            seed: int = 0) -> dict:
    """LinkFailureSweep + SweepRouteSelector over ``batch`` seeded link
    failures of the headline WAN, on one device."""
    import numpy as np

    from bench import build_headline_world

    meter = CompileMeter.shared()
    snap = meter.snapshot()
    t_phase = time.perf_counter()
    ls, topo, cands = build_headline_world(n_nodes)
    L = len(topo.links)
    rng = np.random.default_rng(seed)
    fails = rng.integers(0, L, size=batch).astype(np.int32)
    eng, sweep, deltas, times = _sweep(topo, cands, fails, None)
    oracle, solve = _route_oracle(ls, topo, "node0", eng.D)
    if oracle == "scalar":
        n_sample = min(n_sample, 32)
    sample = rng.choice(batch, size=min(n_sample, batch), replace=False)
    checked = 0
    for s in sample:
        valid, metric, lanes = deltas.routes_of(int(s))
        ev, em, el = solve(int(fails[s]))
        _check(np.array_equal(valid, ev), f"route valid parity, snap {s}")
        _check(np.array_equal(metric[ev], em[ev]),
               f"route metric parity, snap {s}")
        _check(np.array_equal(lanes[ev], el[ev]),
               f"route lane parity, snap {s}")
        checked += int(ev.sum())
    _check(sweep.num_device_solves > 0, "no device solve")
    return {
        "phase": "B", "nodes": n_nodes, "links": L, "batch": batch,
        "unique_device_solves": int(sweep.num_device_solves),
        "route_deltas": int(deltas.num_deltas), "oracle": oracle,
        "sampled_snapshots": len(sample), "routes_compared": checked,
        **times, **meter.since(snap),
        "wall_s": time.perf_counter() - t_phase,
        "peak_bytes_in_use": _peak_bytes(), "ok": True,
    }


# ---------------------------------------------------------------------------
# Phase C — the mesh path (four chips)
# ---------------------------------------------------------------------------


def phase_c(n_devices: int = 4, n_nodes: int = 1024, batch: int = 10_240,
            side: int = 64, n_roots: int = 16, seed: int = 0) -> dict:
    """The Phase B engine on an ``n_devices`` mesh vs the 1-device
    engine, bit for bit; the sharded fleet-RIB engine over the Phase A
    grid (one loopback per node) vs the scalar solver for a seeded
    sample of vantage roots."""
    import random

    import numpy as np

    from bench import build_headline_world
    from openr_tpu.decision.fleet import FleetRibEngine
    from openr_tpu.decision.link_state import LinkState
    from openr_tpu.decision.prefix_state import PrefixState
    from openr_tpu.decision.rib import route_db_summary
    from openr_tpu.decision.spf_solver import SpfSolver
    from openr_tpu.emulation.topology import build_adj_dbs, grid_edges
    from openr_tpu.parallel.mesh import make_mesh
    from openr_tpu.types import PrefixEntry

    meter = CompileMeter.shared()
    snap = meter.snapshot()
    t_phase = time.perf_counter()
    mesh = make_mesh(n_devices)
    mesh_ids = sorted(d.id for d in mesh.devices.flat)
    _ls, topo, cands = build_headline_world(n_nodes)
    fails = np.random.default_rng(seed).integers(
        0, len(topo.links), size=batch).astype(np.int32)
    _e, sw_n, d_n, t_n = _sweep(topo, cands, fails, mesh)
    _e, sw_1, d_1, t_1 = _sweep(topo, cands, fails, None)
    _check(t_n["devices"] == mesh_ids,
           f"sweep outputs on devices {t_n['devices']}, mesh {mesh_ids}")
    sw_n.materialize()
    sw_1.materialize()
    for f in ("snap_row", "dist", "nh"):
        _check(np.array_equal(getattr(sw_n, f), getattr(sw_1, f)),
               f"sharded sweep table {f} != 1-device")
    for f in _DELTA_FIELDS:
        _check(np.array_equal(getattr(d_n, f), getattr(d_1, f)),
               f"sharded route deltas {f} != 1-device")

    ls = LinkState("0")
    for db in build_adj_dbs(grid_edges(side)).values():
        ls.update_adjacency_database(db)
    ps = PrefixState()
    for i in range(side * side):
        ps.update_prefix(f"node{i}", "0", PrefixEntry(_grid_prefix(i, 0)))
    als = {"0": ls}
    fleet = FleetRibEngine(SpfSolver("node0"), mesh=mesh)
    roots = random.Random(seed).sample(range(side * side), n_roots)
    t0 = time.perf_counter()
    for r in roots:
        node = f"node{r}"
        got = fleet.compute_for_node(node, als, ps, change_seq=1)
        want = SpfSolver(node).build_route_db(als, ps)
        _check(route_db_summary(got) == route_db_summary(want),
               f"sharded fleet RIB != scalar at {node}")
    fleet_wall = time.perf_counter() - t0
    _check(sorted(fleet.mesh_device_ids) == mesh_ids,
           f"fleet outputs on {sorted(fleet.mesh_device_ids)}, "
           f"mesh {mesh_ids}")
    return {
        "phase": "C", "devices": mesh_ids, "nodes": n_nodes, "batch": batch,
        "unique_device_solves": int(sw_n.num_device_solves),
        "route_deltas": int(d_n.num_deltas),
        "sharded_vs_1device": "bit-equal",
        "sweep_mesh": t_n, "sweep_1device": t_1,
        "fleet": {"grid": side, "roots": n_roots, "wall_s": fleet_wall,
                  "devices": sorted(fleet.mesh_device_ids)},
        **meter.since(snap), "wall_s": time.perf_counter() - t_phase,
        "peak_bytes_in_use": _peak_bytes(), "ok": True,
    }


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the mesh path (Phase C)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    try:
        from openr_tpu.ops.platform_env import (
            enable_persistent_compile_cache,
        )

        enable_persistent_compile_cache()  # before the first compile
        import jax

        devices = jax.devices()
    except Exception as e:  # noqa: BLE001 — no repo, no jax backend
        print(f"chip_smoke: cannot start: {e!r}", file=sys.stderr)
        return 2
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} devices", file=sys.stderr)
        return 2
    meter = CompileMeter.shared()
    _emit({"device_kind": dev.device_kind, "devices": len(devices),
           "jax": jax.__version__,
           "compile_cache_dir": jax.config.jax_compilation_cache_dir})
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            _emit(phase_c(n_devices=4, seed=args.seed))
        else:
            _emit(phase_a(seed=args.seed))
            _emit(phase_b(seed=args.seed))
    except Exception as e:  # noqa: BLE001 — any failure fails the smoke
        import traceback

        traceback.print_exc()
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    _emit({"total_wall_s": time.perf_counter() - t0,
           "total_compile_s": meter.secs,
           "cache_hits": meter.cache_hits,
           "cache_misses": meter.cache_misses})
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
