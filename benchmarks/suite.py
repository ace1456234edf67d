"""Benchmark suite — ports of the reference's folly::Benchmark harnesses.

Reference parity (SURVEY §6 / BASELINE.md):
  * DecisionBenchmark (openr/decision/tests/DecisionBenchmark.cpp:20-80):
    grid initial route build, adjacency-update reconvergence, prefix
    updates — topology generators from RoutingBenchmarkUtils.cpp
    (grid :251, 3-tier fabric :422) live in openr_tpu.emulation.topology
  * KvStoreBenchmarkTest.cpp:676: key persist/update at 100/1k/10k keys
  * KvStoreConvergenceBenchmark.cpp:146: multi-store flood convergence
  * FibBenchmark.cpp: route-programming throughput
  * PrefixManagerBenchmarkTest.cpp: advertise throughput
  * MessagingBenchmark.cpp: queue throughput

Run:  python -m benchmarks.suite [--full] [--json PATH]
Each result prints as one JSON line {"metric", "value", "unit", ...};
the aggregate is written to --json (default BENCH_SUITE.json).

The decision benches run BOTH backends (scalar oracle and the TPU/JAX
batched kernel) so the speedup is measured, not assumed.
"""

from __future__ import annotations

import argparse
import json
import time
from typing import Callable, Dict, List


def _best_of(fn: Callable[[], None], repeats: int = 3) -> float:
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _result(metric: str, value: float, unit: str, **detail) -> Dict:
    out = {"metric": metric, "value": round(value, 3), "unit": unit}
    if detail:
        out["detail"] = detail
    print(json.dumps(out), flush=True)
    return out


# ---------------------------------------------------------------------------
# Decision (DecisionBenchmark.cpp)
# ---------------------------------------------------------------------------

def _build_decision_problem(edges, prefixes_per_node: int, area: str = "0"):
    from openr_tpu.decision.link_state import LinkState
    from openr_tpu.decision.prefix_state import PrefixState
    from openr_tpu.emulation.topology import build_adj_dbs
    from openr_tpu.types import PrefixEntry

    ls = LinkState(area)
    dbs = build_adj_dbs(edges)
    for db in dbs.values():
        ls.update_adjacency_database(db)
    ps = PrefixState()
    for i, node in enumerate(sorted(dbs)):
        for p in range(prefixes_per_node):
            # globally-unique /32 per (node, p) across a 24-bit space
            idx = i * prefixes_per_node + p
            ps.update_prefix(
                node,
                area,
                PrefixEntry(
                    prefix=f"10.{(idx >> 16) & 255}.{(idx >> 8) & 255}"
                    f".{idx & 255}/32"
                ),
            )
    return ls, ps, sorted(dbs)


def _make_backends(root: str):
    from openr_tpu.decision.backend import ScalarBackend, TpuBackend
    from openr_tpu.decision.spf_solver import SpfSolver

    return {
        "scalar": ScalarBackend(SpfSolver(root)),
        "tpu": TpuBackend(SpfSolver(root)),
    }


def bench_decision_initial(results: List[Dict], full: bool) -> None:
    """BM_DecisionGridInitialUpdate: cold full route build on grids and
    3-tier fabrics at reference scales (DecisionBenchmark.cpp:20-35 runs
    grids of 10/100/1000/10000 nodes; RoutingBenchmarkUtils.cpp:251,422).
    Every config measures BOTH backends (repeats shrink as scale grows:
    the 10,000-node scalar pass runs once); absent rows mean 'not
    measured', never 'assumed'."""
    from openr_tpu.emulation.topology import fabric_edges, grid_edges

    # (kind, edges, prefixes/node, backends, repeats)
    cases = [
        ("grid", grid_edges(4), 10, ("scalar", "tpu"), 3),
        ("grid", grid_edges(8), 10, ("scalar", "tpu"), 3),
        (
            "fabric",
            fabric_edges(num_pods=4, rsws_per_pod=8, fsws_per_pod=4,
                         num_ssws=8),
            10,
            ("scalar", "tpu"),
            3,
        ),
    ]
    if full:
        cases += [
            ("grid", grid_edges(16), 10, ("scalar", "tpu"), 3),
            # 1024-node grid — reference's 1000-node row
            ("grid", grid_edges(32), 10, ("scalar", "tpu"), 2),
            # 256 nodes x 100 prefixes/node
            ("grid", grid_edges(16), 100, ("scalar", "tpu"), 2),
            # 100 nodes x 1000 prefixes/node (BM prefix-density row)
            ("grid", grid_edges(10), 1000, ("scalar", "tpu"), 1),
            # ~1000-node 3-tier fabric
            (
                "fabric",
                fabric_edges(num_pods=12, rsws_per_pod=64, fsws_per_pod=8,
                             num_ssws=96),
                10,
                ("scalar", "tpu"),
                1,
            ),
            # 10,000-node grid — the reference's largest config; scalar
            # runs once (a single from-scratch pass is ~half a minute)
            ("grid", grid_edges(100), 10, ("scalar", "tpu"), 1),
        ]
    for kind, edges, ppn, backends, repeats in cases:
        ls, ps, nodes = _build_decision_problem(edges, ppn)
        n = len(nodes)
        timings = {}
        for name, backend in _make_backends(nodes[0]).items():
            if name not in backends:
                continue
            if name != "scalar":
                backend.build_route_db({"0": ls}, ps)  # warm (jit compile)

            def cold_build(b=backend):
                # cold = no memoized SPF and no cached topology encoding:
                # that's what "initial update" measures in the reference
                ls.clear_spf_memoization()
                if hasattr(b, "gen"):
                    b.gen.enc_cache = {}
                b.build_route_db({"0": ls}, ps)

            timings[name] = _best_of(cold_build, repeats=repeats)
            results.append(
                _result(
                    f"decision_initial_{kind}{n}_ppn{ppn}_{name}",
                    timings[name] * 1000,
                    "ms",
                    nodes=n,
                    prefixes=n * ppn,
                )
            )
        if timings.get("scalar") and timings.get("tpu"):
            results.append(
                _result(
                    f"decision_initial_{kind}{n}_ppn{ppn}_speedup",
                    timings["scalar"] / timings["tpu"],
                    "x",
                )
            )
        # what the DAEMON default (auto cutover) would pick at this
        # scale: the backend probes the dispatch round trip and chooses
        # scalar when the device can't amortize it (the rows above force
        # each path to keep measuring both)
        from openr_tpu.decision.backend import TpuBackend
        from openr_tpu.decision.spf_solver import SpfSolver

        auto = TpuBackend(SpfSolver(nodes[0]), min_device_prefixes=None)
        choice = (
            "device" if auto._device_worth_it({"0": ls}, ps) else "scalar"
        )
        results.append(
            _result(
                f"decision_initial_{kind}{n}_ppn{ppn}_auto_choice",
                1.0 if choice == "device" else 0.0,
                choice,
                nodes=n,
                prefixes=n * ppn,
                dispatch_rt_ms=round(auto.auto_dispatch_rt_ms, 2),
            )
        )


def bench_decision_adj_update(results: List[Dict], full: bool) -> None:
    """BM_DecisionGridAdjUpdates: reconvergence after one metric change."""
    from openr_tpu.emulation.topology import build_adj_dbs, grid_edges

    side = 16 if full else 8
    edges = grid_edges(side)
    ls, ps, nodes = _build_decision_problem(edges, 10)
    dbs = build_adj_dbs(edges)
    flip_node = nodes[1]
    for name, backend in _make_backends(nodes[0]).items():
        backend.build_route_db({"0": ls}, ps)  # steady state
        toggle = [0]

        def one_update(b=backend):
            toggle[0] ^= 1
            db = dbs[flip_node]
            for adj in db.adjacencies:
                adj.metric = 10 if toggle[0] else 1
            ls.update_adjacency_database(db)
            # exactly what Decision passes on a topology-only delta:
            # force_full (SPF changed) with an empty prefix-churn set, so
            # backends keep their candidate tables instead of re-reading
            # the whole PrefixState
            b.build_route_db(
                {"0": ls}, ps, changed_prefixes=set(), force_full=True
            )

        dt = _best_of(one_update, repeats=5)
        results.append(
            _result(
                f"decision_adj_update_grid{side * side}_{name}",
                dt * 1000,
                "ms",
                nodes=side * side,
            )
        )


def bench_decision_prefix_update(results: List[Dict], full: bool) -> None:
    """BM_DecisionGridPrefixUpdates: prefix churn on a fixed topology —
    measured BOTH as a full rebuild (the reference's only mode) and as a
    per-prefix incremental rebuild (Decision.cpp:908-952 parity path).
    The incremental row must stay ~flat as TOTAL prefixes grow; that is
    the sub-linearity VERDICT r2 item 4 demands."""
    from openr_tpu.emulation.topology import grid_edges
    from openr_tpu.types import PrefixEntry, PrefixMetrics

    batch = 1000 if full else 100
    ppn_cases = [10, 1000] if full else [10]
    for ppn in ppn_cases:
        # fresh, identical problem per backend (churn must not accumulate
        # across backends/repeats), with names from the backend registry
        first = _build_decision_problem(grid_edges(10), ppn)
        names = list(_make_backends(first[2][0]))
        problems = {names[0]: first}
        for name in names[1:]:
            problems[name] = _build_decision_problem(grid_edges(10), ppn)
        for name, (ls, ps, nodes) in problems.items():
            backend = _make_backends(nodes[0])[name]
            backend.build_route_db({"0": ls}, ps)
            toggle = [0]

            def churn_prefixes(ps=ps, nodes=nodes):
                # overwrite the SAME prefix set with alternating payloads:
                # steady-state update churn, constant workload per repeat
                toggle[0] ^= 1
                changed = set()
                for i in range(batch):
                    changed |= ps.update_prefix(
                        nodes[i % len(nodes)],
                        "0",
                        PrefixEntry(
                            prefix=f"172.16.{i >> 8}.{i & 255}/32",
                            metrics=PrefixMetrics(path_preference=toggle[0]),
                        ),
                    )
                return changed

            def full_rebuild(b=backend, ls=ls, ps=ps):
                churn_prefixes()
                b.build_route_db({"0": ls}, ps, force_full=True)

            def incremental(b=backend, ls=ls, ps=ps):
                changed = churn_prefixes()
                b.build_route_db({"0": ls}, ps, changed_prefixes=changed)

            total = len(ps.prefixes()) + batch
            churn_prefixes()  # populate the churn set once before timing
            backend.build_route_db({"0": ls}, ps, force_full=True)
            dt = _best_of(full_rebuild, repeats=3 if ppn <= 10 else 1)
            results.append(
                _result(
                    f"decision_prefix_update_full_{batch}of{total}_{name}",
                    dt * 1000,
                    "ms",
                    nodes=100,
                    prefixes_churned=batch,
                    prefixes_total=total,
                )
            )
            dt = _best_of(incremental, repeats=3)
            results.append(
                _result(
                    f"decision_prefix_update_inc_{batch}of{total}_{name}",
                    dt * 1000,
                    "ms",
                    nodes=100,
                    prefixes_churned=batch,
                    prefixes_total=total,
                )
            )


def bench_parity_device_coverage(results: List[Dict], full: bool) -> None:
    """BASELINE parity configs: every one must run the device path with
    ZERO scalar fallbacks (num_scalar_builds == 0), and match the scalar
    oracle.  The 5th config (10k what-if sweep) is bench.py's headline."""
    from openr_tpu.decision.backend import ScalarBackend, TpuBackend
    from openr_tpu.decision.link_state import LinkState
    from openr_tpu.decision.prefix_state import PrefixState
    from openr_tpu.common.runtime import SimClock
    from openr_tpu.decision.rib_policy import (
        RibPolicy,
        RibPolicyStatement,
        RibRouteActionWeight,
    )
    from openr_tpu.decision.spf_solver import SpfSolver
    from openr_tpu.emulation.topology import (
        build_adj_dbs,
        fabric_edges,
        grid_edges,
        ring_edges,
    )
    from openr_tpu.types import (
        PrefixEntry,
        PrefixForwardingAlgorithm,
        PrefixForwardingType,
    )

    def mk_ls(edges, area="0", **kw):
        ls = LinkState(area)
        for db in build_adj_dbs(edges, area=area, **kw).values():
            ls.update_adjacency_database(db)
        return ls

    def cfg_grid16():
        als = {"0": mk_ls(grid_edges(4))}
        ps = PrefixState()
        for i in range(16):
            ps.update_prefix(f"node{i}", "0", PrefixEntry(f"10.0.{i}.0/24"))
        return "grid16_shortest_distance", als, ps, "node0", {}

    def cfg_ksp2_fabric():
        edges = fabric_edges(num_pods=3, rsws_per_pod=4, fsws_per_pod=2,
                             num_ssws=4)
        als = {"0": mk_ls(edges)}
        ps = PrefixState()
        rsws = sorted(n for e in edges for n in e[:2] if n.startswith("rsw"))
        for i, n in enumerate(dict.fromkeys(rsws)):
            ps.update_prefix(n, "0", PrefixEntry(
                f"10.{i}.0.0/24",
                forwarding_algorithm=PrefixForwardingAlgorithm.KSP2_ED_ECMP))
        return "ksp2_fabric", als, ps, "rsw0_0", {}

    def cfg_multiarea_ribpolicy():
        als = {
            "1": mk_ls(grid_edges(3), "1"),
            "2": mk_ls(ring_edges(6, prefix="b") + [("b0", "node0", 1)], "2"),
        }
        ps = PrefixState()
        ps.update_prefix("node8", "1", PrefixEntry("10.0.0.0/24"))
        ps.update_prefix("b3", "2", PrefixEntry("10.0.0.0/24"))
        ps.update_prefix("b4", "2", PrefixEntry("10.1.0.0/24"))
        policy = RibPolicy(
            statements=[RibPolicyStatement(
                name="prefer-area1",
                prefixes=["10.0.0.0/24"],
                action=RibRouteActionWeight(
                    default_weight=1, area_to_weight={"1": 2}),
            )],
            valid_until=300.0,
        )
        return "multiarea_ribpolicy", als, ps, "node0", {"policy": policy}

    def cfg_sr_mpls():
        edges = fabric_edges(num_pods=2, rsws_per_pod=3, fsws_per_pod=2,
                             num_ssws=2)
        nodes = sorted({n for e in edges for n in e[:2]})
        labels = {n: 100 + i for i, n in enumerate(nodes)}
        als = {"0": mk_ls(edges, node_labels=labels)}
        ps = PrefixState()
        ps.update_prefix("rsw1_2", "0", PrefixEntry(
            "2001:db8::/64",
            forwarding_type=PrefixForwardingType.SR_MPLS,
            forwarding_algorithm=PrefixForwardingAlgorithm.KSP2_ED_ECMP))
        return (
            "sr_mpls_labels", als, ps, "rsw0_0",
            {"solver_kwargs": {"enable_node_segment_label": True}},
        )

    all_on_device = True
    for cfg in (cfg_grid16, cfg_ksp2_fabric, cfg_multiarea_ribpolicy,
                cfg_sr_mpls):
        name, als, ps, me, extra = cfg()
        skw = extra.get("solver_kwargs", {})
        backend = TpuBackend(SpfSolver(me, **skw))
        db = backend.build_route_db(als, ps)
        ref = ScalarBackend(SpfSolver(me, **skw)).build_route_db(als, ps)
        policy = extra.get("policy")
        if policy is not None:
            clock = SimClock()
            for d in (db, ref):
                assert policy.apply_policy(d, clock).routes_modified > 0
        from openr_tpu.decision.rib import route_db_summary

        match = route_db_summary(db) == route_db_summary(ref)
        on_device = backend.num_scalar_builds == 0 and match
        all_on_device &= on_device
        results.append(_result(
            f"parity_{name}_on_device", 1.0 if on_device else 0.0, "bool",
            scalar_builds=backend.num_scalar_builds,
            device_builds=backend.num_device_builds,
            matches_oracle=match,
        ))
    results.append(_result(
        "parity_configs_device_coverage", 1.0 if all_on_device else 0.0,
        "fraction"))


def bench_fleet_rib(results: List[Dict], full: bool) -> None:
    """Network-wide RIB: every node's route table from one batched device
    solve (ops/fleet_tables.py) vs sequential scalar per-vantage passes (the
    reference's only mode, Decision.cpp:342 per getRouteDbComputed call).
    The scalar side measures a sample of roots and reports the measured
    per-root cost; 'scalar_projected_s' = per_root x V is labeled as a
    projection, not a measurement."""
    from openr_tpu.decision.fleet import FleetRibEngine
    from openr_tpu.decision.link_state import LinkState
    from openr_tpu.decision.prefix_state import PrefixState
    from openr_tpu.decision.spf_solver import SpfSolver
    from openr_tpu.emulation.topology import (
        build_adj_dbs,
        grid_edges,
        random_connected_edges,
    )
    from openr_tpu.types import PrefixEntry

    edges = (
        random_connected_edges(1024, 2048, seed=7) if full else grid_edges(16)
    )
    ls = LinkState("0")
    dbs = build_adj_dbs(edges)
    for db in dbs.values():
        ls.update_adjacency_database(db)
    nodes = sorted(dbs)
    V = len(nodes)
    ps = PrefixState()
    for i, node in enumerate(nodes):
        ps.update_prefix(
            node, "0", PrefixEntry(f"10.{(i >> 8) & 255}.{i & 255}.0/24")
        )
    als = {"0": ls}

    eng = FleetRibEngine(SpfSolver(nodes[0]))
    assert eng.eligible(als, ps, change_seq=0)
    eng.compute_for_node(nodes[0], als, ps, change_seq=0)  # warm/compile
    t0 = time.perf_counter()
    # change_seq bump = cache miss: measures a full re-solve
    eng.compute_for_node(nodes[0], als, ps, change_seq=1)
    batch_s = time.perf_counter() - t0
    # decoding EVERY root's RouteDb from the cached tables (decode is
    # per-request in production; this measures the full-fleet cost the
    # batch number doesn't include)
    t0 = time.perf_counter()
    for node in nodes:
        eng.compute_for_node(node, als, ps, change_seq=1)
    decode_all_s = time.perf_counter() - t0

    # scalar: at --full, ONE measured full fleet (the honest denominator
    # for the headline speedup); quick mode keeps the 8-root sample and
    # labels the result a projection
    if full:
        t0 = time.perf_counter()
        for node in nodes:
            SpfSolver(node).build_route_db(als, ps)
        scalar_full_s = time.perf_counter() - t0
        per_root_s = scalar_full_s / V
    else:
        sample = nodes[:: max(1, V // 8)][:8]
        t0 = time.perf_counter()
        for node in sample:
            SpfSolver(node).build_route_db(als, ps)
        per_root_s = (time.perf_counter() - t0) / len(sample)
        scalar_full_s = None

    detail = dict(
        batch_s=round(batch_s, 3),
        decode_all_ms=round(decode_all_s * 1000, 1),
        scalar_per_root_ms=round(per_root_s * 1000, 2),
        nodes=V,
    )
    if scalar_full_s is not None:
        detail["scalar_measured_s"] = round(scalar_full_s, 1)
        detail["measured_speedup"] = round(scalar_full_s / batch_s, 1)
        # end-to-end: batch solve + decoding every root, vs the measured
        # full scalar fleet (which also materializes every RouteDb)
        detail["measured_speedup_incl_decode_all"] = round(
            scalar_full_s / (batch_s + decode_all_s), 1
        )
    else:
        detail["scalar_projected_s"] = round(per_root_s * V, 1)
        detail["projected_speedup"] = round(per_root_s * V / batch_s, 1)
        detail["scalar_sample_roots"] = 8
    results.append(
        _result(
            f"fleet_rib_all_roots_{V}",
            V / batch_s,
            "vantage_ribs/s",
            **detail,
        )
    )


def bench_p50_convergence(results: List[Dict], full: bool) -> None:
    """North-star metric 2 (BASELINE.md): p50 publication→FIB-programmed
    convergence on the device path.  Drives the REAL Decision + Fib actors
    (debounce, queues, route-delta diff, FIB programming) on a SimClock:
    virtual time costs nothing, so the measured wall-clock IS the compute
    latency the 10-250ms debounce budget (OpenrConfig.thrift:105-108) must
    absorb.  Steady state is a 4096-node grid (--full; 256 quick) with one
    loopback per node plus prefix density; each sample advertises a batch
    of 10 prefixes in one publication and waits until the mock FIB agent
    holds them."""
    import asyncio
    import json as _json
    import statistics

    from openr_tpu.common.runtime import SimClock
    from openr_tpu.config import DecisionConfig, FibConfig
    from openr_tpu.decision.backend import TpuBackend
    from openr_tpu.decision.decision import Decision
    from openr_tpu.decision.spf_solver import SpfSolver
    from openr_tpu.emulation.topology import build_adj_dbs, grid_edges
    from openr_tpu.fib.fib import Fib, MockFibAgent
    from openr_tpu.messaging.queue import ReplicateQueue
    from openr_tpu.types import (
        InitializationEvent,
        PrefixDatabase,
        PrefixEntry,
        PrefixMetrics,
        Publication,
        Value,
        prefix_key,
    )

    side = 64 if full else 16
    ppn = 100 if full else 10  # density beyond the per-node loopback
    samples = 20 if full else 8
    batch = 10

    async def run():
        clock = SimClock()
        solver = SpfSolver("node0")
        backend = TpuBackend(solver)
        routes_q = ReplicateQueue("routes")
        kv_q = ReplicateQueue("kv")
        agent = MockFibAgent(clock)
        decision = Decision(
            "node0",
            clock,
            DecisionConfig(debounce_min_ms=10, debounce_max_ms=250),
            routes_q,
            kv_store_updates_reader=kv_q.get_reader(),
            backend=backend,
            solver=solver,
        )
        fib = Fib(
            node_name="node0",
            clock=clock,
            config=FibConfig(),
            agent=agent,
            route_updates_reader=routes_q.get_reader(),
        )
        decision.start()
        fib.start()
        decision.on_initialization_event(InitializationEvent.KVSTORE_SYNCED)

        edges = grid_edges(side)
        dbs = build_adj_dbs(edges)
        n = len(dbs)

        def val(node, obj):
            return Value(
                version=1,
                originator_id=node,
                value=_json.dumps(obj.to_wire()).encode(),
            )

        kv_q.push(
            Publication(
                key_vals={f"adj:{node}": val(node, db) for node, db in dbs.items()}
            )
        )
        # one loopback + (ppn-1) density prefixes per node, pushed in
        # node-sized publications (not timed; builds the steady state)
        for i, node in enumerate(sorted(dbs)):
            kvs = {}
            for p in range(ppn):
                pfx = f"10.{(i >> 8) & 255}.{i & 255}.{p}/32"
                pdb = PrefixDatabase(
                    this_node_name=node, prefix_entries=[PrefixEntry(pfx)]
                )
                kvs[prefix_key(node, pfx)] = val(node, pdb)
            kv_q.push(Publication(key_vals=kvs))

        t0 = time.perf_counter()
        while not decision._first_build_done or len(agent.unicast) < (n - 1) * ppn:
            await clock.run_for(0.05)
            if time.perf_counter() - t0 > 1800:
                raise RuntimeError(
                    f"initial build stalled: {len(agent.unicast)} routes"
                )
        initial_ms = (time.perf_counter() - t0) * 1000

        lat_ms = []
        all_nodes = sorted(dbs)
        for s in range(-1, samples):  # s == -1: untimed jit-compile warmup
            # never the local node: its own advertisements are skip-if-self
            # and would produce no FIB route to wait for
            node = all_nodes[1 + (s * 37) % (n - 1)]
            kvs = {}
            want = []
            for b in range(batch):
                pfx = f"172.20.{s & 255}.{b}/32"
                pdb = PrefixDatabase(
                    this_node_name=node,
                    prefix_entries=[
                        PrefixEntry(
                            pfx, metrics=PrefixMetrics(path_preference=1000)
                        )
                    ],
                )
                kvs[prefix_key(node, pfx)] = val(node, pdb)
                want.append(pfx)
            t0 = time.perf_counter()
            kv_q.push(Publication(key_vals=kvs))
            while not all(p in agent.unicast for p in want):
                await clock.run_for(0.02)
                if time.perf_counter() - t0 > 300:
                    raise RuntimeError("churn sample stalled")
            if s >= 0:
                lat_ms.append((time.perf_counter() - t0) * 1000)
        await decision.stop()
        await fib.stop()
        return initial_ms, lat_ms, backend

    initial_ms, lat_ms, backend = asyncio.run(run())
    lat_sorted = sorted(lat_ms)
    p50 = statistics.median(lat_sorted)
    p95 = lat_sorted[max(0, int(round(0.95 * len(lat_sorted))) - 1)]
    results.append(
        _result(
            f"p50_publication_to_fib_ms_grid{side * side}",
            p50,
            "ms",
            p95_ms=round(p95, 1),
            samples=len(lat_ms),
            batch_per_sample=batch,
            nodes=side * side,
            total_prefixes=side * side * ppn,
            initial_full_build_ms=round(initial_ms, 1),
            incremental_builds=backend.num_incremental_builds,
            within_debounce_budget=bool(p50 <= 250.0),
        )
    )


# ---------------------------------------------------------------------------
# KvStore (KvStoreBenchmarkTest.cpp, KvStoreConvergenceBenchmark.cpp)
# ---------------------------------------------------------------------------

def bench_kvstore_persist(results: List[Dict], full: bool) -> None:
    import asyncio

    from openr_tpu.common.runtime import SimClock
    from openr_tpu.config import KvStoreConfig
    from openr_tpu.kvstore.kv_store import KvStore
    from openr_tpu.kvstore.transport import InProcessTransport
    from openr_tpu.messaging.queue import ReplicateQueue

    sizes = [100, 1000, 10_000] if full else [100, 1000]
    for n in sizes:
        async def run(n=n):
            clock = SimClock()
            store = KvStore(
                node_name="b0",
                clock=clock,
                config=KvStoreConfig(),
                areas=["0"],
                transport=InProcessTransport(clock),
                publications_queue=ReplicateQueue("pubs"),
            )
            db = store.areas["0"]
            payload = b"x" * 128
            t0 = time.perf_counter()
            for i in range(n):
                db.persist_self_originated_key(f"prefix:b0:k{i}", payload)
            dt = time.perf_counter() - t0
            # update pass: same keys, new values (version bump path)
            t0 = time.perf_counter()
            for i in range(n):
                db.persist_self_originated_key(f"prefix:b0:k{i}", payload + b"y")
            dt_update = time.perf_counter() - t0
            await store.stop()
            return dt, dt_update

        dt, dt_update = asyncio.run(run())
        results.append(
            _result(f"kvstore_persist_{n}", n / dt, "keys/s")
        )
        results.append(
            _result(f"kvstore_update_{n}", n / dt_update, "keys/s")
        )


def bench_kvstore_flood_convergence(results: List[Dict], full: bool) -> None:
    """N stores in a line; one key injected at the head; time until every
    store holds it (virtual time = protocol latency, wall time = compute)."""
    import asyncio

    from openr_tpu.common.runtime import SimClock
    from openr_tpu.config import KvStoreConfig
    from openr_tpu.kvstore.kv_store import KvStore
    from openr_tpu.kvstore.transport import InProcessTransport
    from openr_tpu.messaging.queue import ReplicateQueue
    from openr_tpu.types import PeerSpec

    n = 64 if full else 16

    async def run():
        clock = SimClock()
        transport = InProcessTransport(clock, latency_s=0.001)
        stores = []
        for i in range(n):
            store = KvStore(
                node_name=f"s{i}",
                clock=clock,
                config=KvStoreConfig(),
                areas=["0"],
                transport=transport,
                publications_queue=ReplicateQueue(f"pubs{i}"),
            )
            transport.register(f"s{i}", store)
            stores.append(store)
            store.start()
        for i, store in enumerate(stores):
            peers = {}
            if i > 0:
                peers[f"s{i - 1}"] = PeerSpec()
            if i < n - 1:
                peers[f"s{i + 1}"] = PeerSpec()
            store.areas["0"].add_peers(peers)
        await clock.run_for(5.0)

        t_wall = time.perf_counter()
        t_virtual = clock.now()
        stores[0].areas["0"].persist_self_originated_key("prefix:s0:x", b"v")
        while not all("prefix:s0:x" in s.areas["0"].key_vals for s in stores):
            await clock.run_for(0.05)
            if clock.now() - t_virtual > 60:
                raise RuntimeError("flood did not converge")
        wall = time.perf_counter() - t_wall
        virtual = clock.now() - t_virtual
        for store in stores:
            await store.stop()
        return wall, virtual

    wall, virtual = asyncio.run(run())
    results.append(
        _result(
            f"kvstore_flood_convergence_{n}",
            virtual * 1000,
            "virtual_ms",
            wall_ms=round(wall * 1000, 1),
            stores=n,
        )
    )


# ---------------------------------------------------------------------------
# Fib (FibBenchmark.cpp)
# ---------------------------------------------------------------------------

def bench_fib_programming(results: List[Dict], full: bool) -> None:
    import asyncio

    from openr_tpu.common.runtime import SimClock
    from openr_tpu.config import FibConfig
    from openr_tpu.decision.rib import (
        DecisionRouteUpdate,
        DecisionRouteUpdateType,
        RibUnicastEntry,
    )
    from openr_tpu.fib.fib import Fib, MockFibAgent
    from openr_tpu.messaging.queue import ReplicateQueue
    from openr_tpu.types import NextHop

    n = 10_000 if full else 2_000

    async def run():
        clock = SimClock()
        agent = MockFibAgent(clock)
        q = ReplicateQueue("routes")
        fib = Fib(
            node_name="b0",
            clock=clock,
            config=FibConfig(),
            agent=agent,
            route_updates_reader=q.get_reader(),
        )
        fib.start()
        routes = {
            f"10.{(i >> 8) & 255}.{i & 255}.0/24": RibUnicastEntry(
                prefix=f"10.{(i >> 8) & 255}.{i & 255}.0/24",
                nexthops=[NextHop(address="fe80::1", if_name="eth0")],
            )
            for i in range(n)
        }
        t0 = time.perf_counter()
        q.push(
            DecisionRouteUpdate(
                type=DecisionRouteUpdateType.FULL_SYNC,
                unicast_routes_to_update=routes,
            )
        )
        while len(agent.unicast) < n:
            await clock.run_for(0.05)
        dt = time.perf_counter() - t0
        await fib.stop()
        return dt

    dt = asyncio.run(run())
    results.append(_result(f"fib_program_{n}", n / dt, "routes/s"))


# ---------------------------------------------------------------------------
# PrefixManager (PrefixManagerBenchmarkTest.cpp)
# ---------------------------------------------------------------------------

def bench_prefix_manager_advertise(results: List[Dict], full: bool) -> None:
    import asyncio

    from openr_tpu.common.runtime import SimClock
    from openr_tpu.messaging.queue import ReplicateQueue
    from openr_tpu.prefix_manager.prefix_manager import PrefixManager
    from openr_tpu.types import (
        PrefixEntry,
        PrefixEvent,
        PrefixEventType,
    )

    n = 10_000 if full else 2_000

    async def run():
        clock = SimClock()
        kv_q = ReplicateQueue("kvreq")
        kv_r = kv_q.get_reader()
        prefix_q = ReplicateQueue("prefixEvents")
        pm = PrefixManager(
            node_name="b0",
            clock=clock,
            kv_request_queue=kv_q,
            prefix_updates_reader=prefix_q.get_reader(),
        )
        pm.start()
        await clock.run_for(0.1)
        while kv_r.try_get() is not None:
            pass
        entries = [
            PrefixEntry(prefix=f"10.{(i >> 8) & 255}.{i & 255}.0/24")
            for i in range(n)
        ]
        t0 = time.perf_counter()
        prefix_q.push(
            PrefixEvent(
                event_type=PrefixEventType.ADD_PREFIXES, prefixes=entries
            )
        )
        seen = 0
        while seen < n:
            await clock.run_for(0.05)
            while kv_r.try_get() is not None:
                seen += 1
        dt = time.perf_counter() - t0
        await pm.stop()
        return dt

    dt = asyncio.run(run())
    results.append(_result(f"prefix_manager_advertise_{n}", n / dt, "prefixes/s"))


# ---------------------------------------------------------------------------
# Messaging (MessagingBenchmark.cpp)
# ---------------------------------------------------------------------------

def bench_messaging(results: List[Dict], full: bool) -> None:
    import asyncio

    from openr_tpu.messaging.queue import ReplicateQueue

    n = 200_000 if full else 50_000
    readers = 4

    async def run():
        q = ReplicateQueue("bench")
        rs = [q.get_reader() for _ in range(readers)]
        t0 = time.perf_counter()

        async def drain(r):
            for _ in range(n):
                await r.get()

        tasks = [asyncio.ensure_future(drain(r)) for r in rs]
        for i in range(n):
            q.push(i)
            if i % 4096 == 0:
                await asyncio.sleep(0)  # let readers drain; bounds memory
        await asyncio.gather(*tasks)
        return time.perf_counter() - t0

    dt = asyncio.run(run())
    results.append(
        _result(
            "messaging_replicate_throughput",
            n * readers / dt,
            "deliveries/s",
            items=n,
            readers=readers,
        )
    )


def bench_whatif_double_failures(results: List[Dict], full: bool) -> None:
    """Exhaustive DOUBLE-failure analysis: every unordered pair of
    links failed simultaneously (the maintenance-window question "is
    there any second failure that partitions us?").  Pairs scale as
    L^2/2 — the batch shape the set-repair kernel exists for; the
    native baseline is the same exhaustive loop over
    spf_scalar_solve_set (sampled, then extrapolated, when full=False).
    """
    import itertools

    import numpy as np

    from openr_tpu.decision.link_state import LinkState
    from openr_tpu.emulation.topology import (
        build_adj_dbs,
        random_connected_edges,
    )
    from openr_tpu.ops.csr import encode_link_state
    from openr_tpu.ops.native_spf import NativeSpf
    from openr_tpu.ops.whatif import LinkFailureSweep

    # pairs scale as L^2/2, with L = (nodes-1) tree edges + extra
    # chords: 128 nodes + 128 chords -> L=255 -> ~32k solves (CPU
    # smoke); --full 256+256 -> L=511 -> ~130k (a device-scale batch)
    n_nodes, extra = (128, 128) if not full else (256, 256)
    edges = random_connected_edges(n_nodes, extra, seed=21)
    ls = LinkState("0")
    for db in build_adj_dbs(edges).values():
        ls.update_adjacency_database(db)
    topo = encode_link_state(ls)
    L = len(topo.links)
    pairs = list(itertools.combinations(range(L), 2))

    eng = LinkFailureSweep(topo, "node0")
    eng.base_solve()
    sets_mat = np.asarray(pairs, np.int32)
    res = eng.run_sets(sets_mat, fetch=False)  # warm-up compile
    res.block()
    t0 = time.perf_counter()
    res = eng.run_sets(sets_mat, fetch=False)
    res.block()
    device_s = time.perf_counter() - t0
    # partition scan: pairs whose failure disconnects some node — one
    # bool per UNIQUE solve row, then mapped through snap_row.  Only the
    # dist chunks are fetched (one overlapped device_get); materialize()
    # would also pull + bit-unpack the nh tables this scan never reads.
    import jax

    from openr_tpu.ops.consts import BIG

    U = 1 + res.num_device_solves
    row_partitions = np.zeros(U, bool)  # base row: connected graph
    dists_h = jax.device_get([c[2] for c in res.chunks or []])
    for (off, n, _dd, _nd), dist_h in zip(res.chunks or [], dists_h):
        row_partitions[1 + off : 1 + off + n] = (
            dist_h[: topo.num_nodes, :n] >= BIG
        ).any(axis=0)
    n_partitioning = int(row_partitions[res.snap_row].sum())

    nat = NativeSpf(topo, "node0")
    sample = pairs if full else pairs[:: max(1, len(pairs) // 2000)]
    t0 = time.perf_counter()
    for pr in sample:
        nat.solve_set(list(pr))
    native_s_sample = time.perf_counter() - t0
    native_s = native_s_sample * (len(pairs) / len(sample))

    results.append(
        _result(
            f"whatif_double_failures_L{L}",
            len(pairs) / device_s,
            "pairs/s",
            pairs=len(pairs),
            device_s=round(device_s, 3),
            native_set_solver_s=round(native_s, 3),
            native_sampled=not full,
            speedup=round(native_s / device_s, 1),
            partitioning_pairs=n_partitioning,
            nodes=n_nodes,
        )
    )


ALL_BENCHES = [
    bench_decision_initial,
    bench_decision_adj_update,
    bench_decision_prefix_update,
    bench_parity_device_coverage,
    bench_fleet_rib,
    bench_p50_convergence,
    bench_whatif_double_failures,
    bench_kvstore_persist,
    bench_kvstore_flood_convergence,
    bench_fib_programming,
    bench_prefix_manager_advertise,
    bench_messaging,
]


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--full", action="store_true",
                   help="reference-scale sizes (slower)")
    p.add_argument("--json", default="BENCH_SUITE.json")
    p.add_argument("--only", default="",
                   help="substring filter on bench function names")
    args = p.parse_args()
    from openr_tpu.ops.platform_env import enable_persistent_compile_cache

    enable_persistent_compile_cache()
    results: List[Dict] = []
    t0 = time.time()
    for bench in ALL_BENCHES:
        if args.only and args.only not in bench.__name__:
            continue
        bench(results, args.full)
    import jax

    from bench import env_stamp

    with open(args.json, "w") as f:
        json.dump(
            {
                # the platform stamp keeps CPU smoke runs from being
                # mistaken for device measurements
                "devices": [str(d) for d in jax.devices()],
                "env": env_stamp(),
                "full": args.full,
                "results": results,
                "wall_s": round(time.time() - t0, 1),
            },
            f,
            indent=2,
        )
    print(f"# {len(results)} results -> {args.json}", flush=True)


if __name__ == "__main__":
    main()
