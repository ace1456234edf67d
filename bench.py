#!/usr/bin/env python
"""Headline benchmark: the 10k x 1024-node what-if sweep, END TO END.

Task (BASELINE.md north star): route tables for 10,240 single-link-
failure perturbations of a 1024-node WAN LSDB, one vantage root, 1024
advertised prefixes.

The HEADLINE is the full operator-visible pipeline — sweep in, route
deltas out: warm-start repair SPF (ops/repair.py) + on-device route
selection diffed against the base table (ops/sweep_select.py) with
delta-only host fetch, chunk selection dispatched behind the next
chunk's SPF.  SPF-tables-only throughput (what rounds 2-3 headlined) is
reported as a detail line (VERDICT r3 weak #2).

The engine runs through the SAME mesh-sharded code path the multichip
dryrun validates (shard_map over the batch axis; on the single bench
chip the mesh has one device).

Baselines (single-threaded C++, native/spf_scalar.cc):
  * **naive** — from-scratch heap Dijkstra per snapshot, the reference's
    true behavior (its SPF memo is invalidated per topology change,
    LinkState.h:346-390).  Median of NATIVE_REPS sweeps with spread
    (VERDICT r3 weak #1: a single timing swung -33% between rounds).
  * **dedup** — Dijkstra once per unique failed link (the courtesy the
    reference's memo would give within one unchanged topology).
  * **warm-start** — the SAME incremental-repair trick the device kernel
    uses, in C++ (spf_warm_sweep: off-DAG skip + affected-region
    Dijkstra seeded from the base solve).  The demanding apples-to-
    apples line: it separates "TPU is fast" from "incremental beats
    from-scratch" (VERDICT r3 missing #2).  SPF tables only.
  * **native engine end-to-end** — C++ warm sweep + numpy selection +
    base diff per unique on-DAG failure: the actual off-device engine
    the Decision what-if API runs, producing ROUTES OUT like the
    headline (and asserted to find the identical delta count).
  * **python** — the pure-Python oracle (round-1's flattering
    denominator, kept for transparency).

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}
value = end-to-end snapshots->route-deltas throughput;
vs_baseline = that / native naive median.
"""

import json
import statistics
import sys
import time
from typing import Optional

import numpy as np

NATIVE_REPS = 5
DEVICE_REPS = 3


def env_stamp() -> dict:
    """Host/chip environment recorded into every bench artifact: the
    native denominator swings ~2x across machine-days (r4 review weak
    #3), so cross-round ratios are only comparable with the environment
    pinned alongside them."""
    import os
    import platform

    cpu_model = ""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith("model name"):
                    cpu_model = line.split(":", 1)[1].strip()
                    break
    except OSError:
        pass
    governor = ""
    try:
        with open(
            "/sys/devices/system/cpu/cpu0/cpufreq/scaling_governor"
        ) as f:
            governor = f.read().strip()
    except OSError:
        pass
    try:
        load1, load5, _ = os.getloadavg()
    except OSError:
        load1 = load5 = -1.0
    import jax

    return {
        "cpu_model": cpu_model,
        "cpu_count": os.cpu_count(),
        "cpu_governor": governor,
        "loadavg_1m": round(load1, 2),
        "loadavg_5m": round(load5, 2),
        "python": platform.python_version(),
        "jax": jax.__version__,
        # the accelerator identity triple every BENCH_* artifact must
        # carry so perf points are comparable across environments
        # (ISSUE 6 satellite): chip kind, jax version, visible devices
        "platform": jax.default_backend(),
        "device_count": len(jax.devices()),
    }


def build_headline_world(n_nodes: int = 1024):
    """The benchmark's canonical world: 1024-node WAN, 3071 undirected
    links (spanning tree + 2048 chords), seed 7, one loopback prefix
    per node.  Shared with
    benchmarks/soak.py so the soak can never silently measure a
    different workload than the headline it pins (r5 review).
    Returns (link_state, topo, cands)."""
    from openr_tpu.decision.link_state import LinkState
    from openr_tpu.emulation.topology import (
        build_adj_dbs,
        random_connected_edges,
    )
    from openr_tpu.ops.csr import encode_link_state
    from openr_tpu.ops.sweep_select import SweepCandidates

    edges = random_connected_edges(n_nodes, 2 * n_nodes, seed=7)
    ls = LinkState("0")
    for db in build_adj_dbs(edges).values():
        ls.update_adjacency_database(db)
    topo = encode_link_state(ls)
    cands = SweepCandidates.single_advertiser(np.arange(n_nodes))
    return ls, topo, cands


def validate_convergence_bench(doc: dict) -> None:
    """Schema contract for BENCH_CONVERGENCE_r*.json — shared by the
    bench emitter and the tier-1 artifact gate.  Virtual-time
    percentiles of the 9-node flap sweep; deterministic across hosts,
    so the benchtrack ratchet holds this headline tightly."""
    assert doc["metric"] == "convergence_event_to_fib_ms_9node_grid"
    assert doc["unit"] == "ms_p50_virtual"
    d = doc["detail"]
    assert d["samples"] > 0
    assert 0 < d["p50_ms"] <= d["p95_ms"] <= d["p99_ms"] <= d["max_ms"]
    assert doc["value"] == d["p50_ms"]
    assert d["nodes"] == 9
    assert d["virtual_time"] is True
    assert d["dropped_spans"] == 0
    for key in ("platform", "jax", "device_count"):
        assert key in d["env"], f"env.{key}"


def convergence_main(seed: Optional[int] = None) -> None:
    """Trace-derived convergence percentiles: p50/p95/p99 of
    `convergence.event_to_fib_ms` over every single-link flap (fail +
    restore) of the 9-node emulated grid, measured by the tracing layer
    end to end (Spark/LinkMonitor origin → KvStore flood → Decision
    rebuild → Fib ack) in deterministic virtual time.  This is the
    protocol-plane convergence trajectory point (the device headline
    above measures the compute plane); emitted as one JSON line for the
    BENCH_* artifact series.  ``seed`` shuffles the flap order (None =
    the canonical edge order the checked-in rounds use)."""
    import asyncio
    import random as _random

    from openr_tpu.common.runtime import SimClock
    from openr_tpu.emulation.network import EmulatedNetwork
    from openr_tpu.emulation.topology import grid_edges

    edges = grid_edges(3)
    if seed is not None:
        _random.Random(seed).shuffle(edges)

    async def run():
        clock = SimClock()
        net = EmulatedNetwork(clock)
        net.build(edges)
        net.start()
        await clock.run_for(20.0)
        ok, why = net.converged_full_mesh()
        assert ok, why
        # drain cold-boot samples: only flap-driven convergence is scored
        for node in net.nodes.values():
            node.counters.clear()
        for a, b, _m in edges:
            net.fail_link(a, b)
            await clock.run_for(4.0)
            net.restore_link(a, b)
            await clock.run_for(4.0)
        ok, why = net.converged_full_mesh()
        assert ok, why
        conv = net.merged_histogram("convergence.event_to_fib_ms")
        spf = net.merged_histogram("decision.spf_ms")
        spans = len(net.all_spans())
        dropped = sum(
            n.tracer.num_dropped for n in net.nodes.values()
        )
        await net.stop()
        return conv, spf, spans, dropped

    conv, spf, spans, dropped = asyncio.new_event_loop().run_until_complete(
        run()
    )
    assert conv is not None and conv.count > 0, "no convergence samples"
    pct = conv.percentiles()
    doc = {
        "metric": "convergence_event_to_fib_ms_9node_grid",
        "value": round(pct["p50"], 2),
        "unit": "ms_p50_virtual",
        "detail": {
            "p50_ms": round(pct["p50"], 2),
            "p95_ms": round(pct["p95"], 2),
            "p99_ms": round(pct["p99"], 2),
            "max_ms": round(conv.vmax, 2),
            "samples": conv.count,
            "spf_p50_ms": (
                round(spf.percentile(50), 4) if spf else None
            ),
            "spans_recorded": spans,
            "dropped_spans": dropped,
            "link_flaps": len(edges) * 2,
            "nodes": 9,
            "topology": "grid3x3",
            "virtual_time": True,
            "seed": seed,
            "note": "SimClock: latencies are modeled protocol "
            "time (spark timers, debounce, flood hops), "
            "deterministic across hosts",
            "env": env_stamp(),
        },
    }
    validate_convergence_bench(doc)
    print(json.dumps(doc))


RESILIENCE_SAMPLE_EVERY = 8
RESILIENCE_BUILDS_PER_SIDE = 64


def validate_resilience_bench(doc: dict) -> None:
    """Schema contract for BENCH_RESILIENCE_r*.json — shared by the
    bench emitter and the tier-1 smoke test so the artifact can never
    drift from what the test validates.  The headline value is the
    shadow-verification overhead on the rebuild p50, and the acceptance
    bound (ISSUE 5) is <= 5%."""
    assert doc["metric"] == "resilience_shadow_overhead_pct_rebuild_p50"
    assert doc["unit"] == "pct"
    assert isinstance(doc["value"], (int, float))
    assert doc["value"] <= 5.0, "shadow overhead must stay <= 5% on p50"
    d = doc["detail"]
    assert d["rebuild_p50_ms_shadow_off"] > 0
    assert d["rebuild_p50_ms_shadow_on"] > 0
    assert d["rebuild_p95_ms_shadow_on"] >= d["rebuild_p50_ms_shadow_on"]
    assert d["builds_per_side"] >= 32
    assert d["shadow_sample_every"] >= 2
    assert d["shadow_checks_during_run"] >= 1
    sc = d["sdc_scenario"]
    assert sc["detected"] is True
    assert sc["recovered"] is True
    assert 1 <= sc["rebuilds_to_detect"] <= d["shadow_sample_every"]
    assert sc["shadow_mismatches"] >= 1
    assert sc["probes"] >= 1
    assert sc["deterministic_replay"] is True
    for key in ("world", "env", "mode"):
        assert key in d, key
    for key in ("platform", "jax", "device_count"):
        assert key in d["env"], f"env.{key}"
    assert d["env"]["device_count"] >= 1


def _resilience_sdc_scenario(seed: int = 7):
    """Seeded 9-node emulation with a ``tpu_corrupt`` fault: corruption
    detected within one shadow-sample interval, device quarantined,
    routes served from the scalar engine (InvariantChecker green
    throughout), device restored by a half-open probe after heal.  Run
    twice from one seed; byte-identical counter dumps prove the replay
    contract.  Returns the scenario detail dict."""
    import asyncio

    from openr_tpu.chaos import ChaosController, FaultPlan, InvariantChecker
    from openr_tpu.common.runtime import SimClock
    from openr_tpu.config import ResilienceConfig
    from openr_tpu.emulation.network import EmulatedNetwork
    from openr_tpu.emulation.topology import grid_edges
    from openr_tpu.types import PrefixEntry

    sample_every = 2
    victim = "node4"

    def overrides(cfg):
        cfg.watchdog_config.interval_s = 1.0
        cfg.tpu_compute_config.min_device_prefixes = 0  # always device
        cfg.resilience_config = ResilienceConfig(
            shadow_sample_every=sample_every,
            failure_threshold=2,
            probe_backoff_initial_s=0.5,
            probe_backoff_max_s=4.0,
            jitter_pct=0.1,
            seed=seed,
        )

    async def one_run():
        clock = SimClock()
        net = EmulatedNetwork(
            clock, use_tpu_backend=True, config_overrides=overrides
        )
        net.build(grid_edges(3))
        net.start()
        checker = InvariantChecker(net)
        plan = FaultPlan().tpu_corrupt(victim, at=2.0, duration=10.0)
        controller = ChaosController(net, plan, seed=seed)
        await clock.run_for(18.0)
        ok, why = net.converged_full_mesh()
        assert ok, why
        gov = net.nodes[victim].decision.backend.governor
        controller.start()
        await clock.run_for(3.0)  # corruption live at t=+2
        rebuilds_to_detect = 0
        for i in range(sample_every):
            net.nodes["node0"].advertise_prefixes(
                [PrefixEntry(f"10.99.{i}.0/24")]
            )
            await clock.run_for(1.5)
            checker.sample()
            if not gov.quarantined:
                continue
            rebuilds_to_detect = i + 1
            break
        detected = gov.quarantined
        checker.check_no_blackholes()  # scalar engine serving, no holes
        await clock.run_for(8.0)  # heal fires at t=+12
        net.nodes["node0"].advertise_prefixes([PrefixEntry("10.99.8.0/24")])
        await clock.run_for(4.0)
        recovered = not gov.quarantined
        await clock.run_for(8.0)
        checker.check_all()
        detail = {
            "detected": detected,
            "rebuilds_to_detect": rebuilds_to_detect,
            "recovered": recovered,
            "shadow_mismatches": gov.num_shadow_mismatches,
            "probes": gov.breaker.num_probes,
            "restores": gov.num_restores,
        }
        dumps = (
            controller.counter_dump(),
            net.nodes[victim].counters.dump("resilience."),
        )
        await controller.stop()
        await net.stop()
        return detail, dumps

    def run(coro):
        loop = asyncio.new_event_loop()
        try:
            return loop.run_until_complete(coro)
        finally:
            loop.close()

    detail_a, dumps_a = run(one_run())
    _detail_b, dumps_b = run(one_run())
    detail_a["deterministic_replay"] = dumps_a == dumps_b
    detail_a["seed"] = seed
    detail_a["shadow_sample_every"] = sample_every
    return detail_a


def resilience_main(seed: Optional[int] = None) -> None:
    """Resilience benchmark (the BENCH_RESILIENCE_r* artifact).

    Part A — shadow-verification overhead on the rebuild p50: one
    256-node LSDB, prefix-churn rebuild ticks through the SAME TpuBackend
    incremental path the daemon runs, measured with the governor's
    sampling off vs every-8th-build.  Sampled builds pay a full scalar
    solve, but they are 1-in-8 tail events, so the p50 (the acceptance
    metric: <= 5%) is expected ~flat — the artifact records the honest
    p50 AND p95 so the tail cost is visible, not hidden.

    Part B — the seeded tpu_corrupt emulation scenario (detection within
    one sample interval, scalar serving with invariants green, probed
    recovery, deterministic replay).  Emits one JSON line."""
    from openr_tpu.ops.platform_env import enable_persistent_compile_cache

    enable_persistent_compile_cache()

    from openr_tpu.common.runtime import SimClock
    from openr_tpu.config import ResilienceConfig
    from openr_tpu.decision.backend import TpuBackend
    from openr_tpu.decision.link_state import LinkState
    from openr_tpu.decision.prefix_state import PrefixState
    from openr_tpu.decision.spf_solver import SpfSolver
    from openr_tpu.emulation.topology import (
        build_adj_dbs,
        random_connected_edges,
    )
    from openr_tpu.types import PrefixEntry

    # historical defaults (world 11, SDC scenario 7) keep the checked-in
    # rounds reproducible when --seed is omitted
    sdc_seed = 7 if seed is None else seed
    n_nodes, n_links, seed = 256, 512, (11 if seed is None else seed)
    edges = random_connected_edges(n_nodes, n_links, seed=seed)
    ls = LinkState("0", "node0")
    for db in build_adj_dbs(edges).values():
        ls.update_adjacency_database(db)
    ps = PrefixState()
    for i in range(n_nodes):
        ps.update_prefix(
            f"node{i}", "0", PrefixEntry(f"10.{i // 256}.{i % 256}.0/24")
        )
    als = {"0": ls}
    churn_prefix = "10.200.0.0/24"

    def measure(sample_every: int):
        backend = TpuBackend(
            SpfSolver("node0"),
            clock=SimClock(),
            resilience=ResilienceConfig(
                shadow_sample_every=sample_every, jitter_pct=0.0
            ),
        )
        backend.build_route_db(als, ps)  # warm-up: compile + first build
        for i in range(2):  # warm the incremental row-selection bucket too
            if i % 2 == 0:
                ps.update_prefix("node3", "0", PrefixEntry(churn_prefix))
            else:
                ps.delete_prefix("node3", "0", churn_prefix)
            backend.build_route_db(als, ps, changed_prefixes={churn_prefix})
        lat = []
        for i in range(RESILIENCE_BUILDS_PER_SIDE):
            # alternate advertise/withdraw of one prefix: a realistic
            # prefix-churn rebuild tick (incremental device path)
            if i % 2 == 0:
                ps.update_prefix("node3", "0", PrefixEntry(churn_prefix))
            else:
                ps.delete_prefix("node3", "0", churn_prefix)
            t0 = time.perf_counter()
            backend.build_route_db(
                als, ps, changed_prefixes={churn_prefix}
            )
            lat.append((time.perf_counter() - t0) * 1000.0)
        # leave the churn prefix withdrawn for the next side
        ps.delete_prefix("node3", "0", churn_prefix)
        lat.sort()
        return lat, backend.governor.num_shadow_checks

    lat_off, _ = measure(0)
    lat_on, shadow_checks = measure(RESILIENCE_SAMPLE_EVERY)

    def pct(lat, q):
        return lat[min(len(lat) - 1, int(len(lat) * q))]

    p50_off, p50_on = pct(lat_off, 0.50), pct(lat_on, 0.50)
    overhead_pct = (p50_on - p50_off) / p50_off * 100.0

    sdc = _resilience_sdc_scenario(seed=sdc_seed)

    doc = {
        "metric": "resilience_shadow_overhead_pct_rebuild_p50",
        "value": round(overhead_pct, 2),
        "unit": "pct",
        "detail": {
            "rebuild_p50_ms_shadow_off": round(p50_off, 3),
            "rebuild_p50_ms_shadow_on": round(p50_on, 3),
            "rebuild_p95_ms_shadow_off": round(pct(lat_off, 0.95), 3),
            "rebuild_p95_ms_shadow_on": round(pct(lat_on, 0.95), 3),
            "rebuild_max_ms_shadow_on": round(lat_on[-1], 3),
            "builds_per_side": RESILIENCE_BUILDS_PER_SIDE,
            "shadow_sample_every": RESILIENCE_SAMPLE_EVERY,
            "shadow_checks_during_run": shadow_checks,
            "sdc_scenario": sdc,
            "world": {
                "nodes": n_nodes,
                "links": n_links,
                "prefixes": n_nodes,
                "topology": "random_connected",
                "seed": seed,
            },
            "mode": (
                "part A: direct TpuBackend incremental rebuild ticks "
                "(wall clock); part B: 9-node grid SimClock emulation "
                "with chaos tpu_corrupt"
            ),
            "env": env_stamp(),
        },
    }
    validate_resilience_bench(doc)
    print(json.dumps(doc))


PIPELINE_DEVICES = (1, 8)
PIPELINE_REBUILDS = 3
PIPELINE_GAP_BOUND_PCT = 10.0


def validate_pipeline_bench(doc: dict) -> None:
    """Schema contract for BENCH_PIPELINE_r*.json — shared by the bench
    emitter and the tier-1 schema gate (tests/test_bench_artifacts).

    The headline value is the UNATTRIBUTED GAP on the grid4096 full
    rebuild: the fraction of measured end-to-end wall time NOT covered
    by a `pipeline.{phase}.ms` sample.  The ISSUE-7 acceptance bound is
    <= 10% — below that, the per-phase table is trustworthy enough to
    baseline the pipelining work against.

    Two artifact eras validate here.  r01 predates the streamed
    pipeline: its dispatch loop ended in ONE blocking device_get
    barrier (no stream_drain/pad_pack at 1 device, busy fractions
    overlap-counted up to 1.5).  From r02 on (detected by a
    ``stream_drain`` sample), the ISSUE-11 contract binds: every shard
    drains as a streamed completion (stream_drain + pad_pack required
    at EVERY device count), ``device_get`` — now just the host copy of
    ready bytes — must no longer be the dominant phase, per-chip busy
    fractions are honest (<= 1, each wait window charged to exactly
    one chip), and a ``delta_round`` must prove the on-device
    delta-extraction path fetches only changed rows."""
    from openr_tpu.tracing.pipeline import (
        DELTA_PHASES,
        DEVICE_GET,
        DEVICE_SELECT,
        EVENT_PHASES,
        KSP2_PHASES,
        PAD_PACK,
        PHASES,
        PROTECTION_PHASES,
        STREAM_DRAIN,
        SWEEP_PHASES,
        WARM_PHASES,
    )

    assert doc["metric"] == "pipeline_attribution_gap_pct_grid4096_rebuild"
    assert doc["unit"] == "pct_of_rebuild_wall"
    assert isinstance(doc["value"], (int, float))
    assert abs(doc["value"]) <= PIPELINE_GAP_BOUND_PCT
    d = doc["detail"]
    rounds = d["rebuild_rounds"]
    assert [r["devices"] for r in rounds] == list(PIPELINE_DEVICES)
    streamed = any(
        STREAM_DRAIN in r["phases_ms"] for r in rounds
    )
    for r in rounds:
        assert r["rebuilds"] >= 2
        assert r["wall_ms"] > 0
        assert abs(r["gap_pct"]) <= PIPELINE_GAP_BOUND_PCT
        assert r["attributed_ms"] > 0
        phases = r["phases_ms"]
        assert set(phases) <= set(PHASES)
        # a full rebuild exercises the whole lifecycle: every phase
        # must have recorded real time (delta_extract rides the diff).
        # warm_plan/warm_repair fire only on warm-start rebuilds
        # (BENCH_WARMSTART), device_select only on delta builds, the
        # sweep phases only in the capacity-sweep orchestrator, the
        # protection phases only with a live protection tier, the KSP2
        # and label phases only with KSP2 or SR-MPLS routes, and the
        # event phases only on a convergence event — never on the cold
        # lifecycle these rounds measure.
        required = (
            set(PHASES)
            - set(WARM_PHASES)
            - set(DELTA_PHASES)
            - set(SWEEP_PHASES)
            - set(PROTECTION_PHASES)
            - set(KSP2_PHASES)
            - set(EVENT_PHASES)
        )
        if not streamed:
            required.discard(STREAM_DRAIN)
            if r["devices"] == 1:
                required.discard(PAD_PACK)
        for phase in sorted(required):
            assert phases.get(phase, 0.0) > 0.0, f"phase {phase} empty"
        if streamed:
            # the dispatch-sync wall is dead: the blocking fetch
            # barrier may no longer dominate the phase table
            assert phases[DEVICE_GET] < max(phases.values()), (
                "device_get is still the dominant phase"
            )
        assert 0.0 <= r["host_share_pct"] <= 100.0
        assert abs(
            r["host_share_pct"] + r["device_share_pct"] - 100.0
        ) < 0.5
        busy = r["per_chip_busy"]
        assert len(busy) == r["devices"]
        busy_bound = 1.05 if streamed else 1.5  # honest vs overlap-counted
        for row in busy.values():
            assert row["busy_ms"] >= 0.0
            assert 0.0 <= row["busy_fraction"] <= busy_bound
    if streamed:
        dr = d["delta_round"]
        assert dr["rebuilds"] >= 2 and dr["wall_ms"] > 0
        assert dr["delta_builds"] == dr["rebuilds"]
        assert dr["rows_fetched"] >= 1
        # the DeltaPath claim: a small perturbation's rebuild moves
        # only changed rows over the host boundary
        assert dr["rows_skipped"] > dr["rows_fetched"]
        assert dr["phases_ms"].get(DEVICE_SELECT, 0.0) > 0.0
        assert (
            dr["wall_ms"] / dr["rebuilds"]
            < rounds[0]["wall_ms"] / rounds[0]["rebuilds"]
        )
    for key in ("fleet_round", "whatif_round"):
        eng = d[key]
        assert eng["devices"] == PIPELINE_DEVICES[-1]
        assert eng["wall_ms"] > 0
        assert eng["phases_ms"]
        assert set(eng["phases_ms"]) <= set(PHASES)
        assert eng["pool_dispatches"] >= eng["devices"]
    for key in ("world", "env", "mode"):
        assert key in d, key
    for key in ("platform", "jax", "device_count"):
        assert key in d["env"], f"env.{key}"
    assert d["env"]["device_count"] >= 8


def pipeline_main(seed: Optional[int] = None) -> None:
    """Pipeline-attribution benchmark (BENCH_PIPELINE_r*): phase-level
    accounting of the grid4096 full rebuild at 1 and 8 forced host
    devices, plus fleet and what-if rounds over the 8-chip pool.

    Methodology.  Each rebuild round drives PIPELINE_REBUILDS full
    device builds (a link-metric flip between builds bumps the
    topology seq, so every build re-encodes, re-solves the SPF tables
    and re-runs selection — the true cold-rebuild lifecycle, not a
    cache replay) and diffs each result against the previous RouteDb
    (the delta_extract tail).  Wall time is measured around exactly
    that window; attribution is the delta of every
    `pipeline.{phase}.ms` histogram over the same window.  The
    headline is the worst-round unattributed gap — the ISSUE-7
    acceptance demands the phase table explain >= 90% of the wall.
    Per-chip busy fractions come from the probe's busy ledger
    (committed per-shard dispatch time + the blocking drain window
    each chip had work outstanding in; on forced HOST devices chips
    share physical cores, so fractions measure dispatch-plane
    structure, not silicon occupancy).  The governor is disabled for
    the measured rounds: shadow verification is a resilience cost,
    priced separately in BENCH_RESILIENCE."""
    import os

    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    from openr_tpu.ops.platform_env import enable_persistent_compile_cache

    enable_persistent_compile_cache()

    from openr_tpu.common.runtime import CounterMap, WallClock
    from openr_tpu.config import ParallelConfig, ResilienceConfig
    from openr_tpu.decision.backend import TpuBackend
    from openr_tpu.decision.fleet import FleetRibEngine
    from openr_tpu.decision.link_state import LinkState
    from openr_tpu.decision.prefix_state import PrefixState
    from openr_tpu.decision.spf_solver import SpfSolver
    from openr_tpu.decision.whatif_api import MultiAreaWhatIfEngine
    from openr_tpu.emulation.topology import build_adj_dbs, grid_edges
    from openr_tpu.tracing import pipeline
    from openr_tpu.types import PrefixEntry

    side = 64  # grid4096: the ROADMAP's canonical scale point
    edges = grid_edges(side)
    adj_dbs = build_adj_dbs(edges)
    ls = LinkState("0")
    for db in adj_dbs.values():
        ls.update_adjacency_database(db)
    n_nodes = side * side
    ps = PrefixState()
    for i in range(n_nodes):
        ps.update_prefix(
            f"node{i}",
            "0",
            PrefixEntry(f"10.{(i >> 8) & 0xFF}.{i & 0xFF}.0/24"),
        )
    als = {"0": ls}
    # the measured lifecycle is seed-invariant (full rebuilds); the
    # seed only picks WHICH adjacency flips between builds
    victim = (
        "node0"
        if seed is None
        else f"node{np.random.default_rng(seed).integers(n_nodes)}"
    )
    flip_db = adj_dbs[victim]

    def flip_topology(step: int) -> None:
        # alternate one adjacency metric: a real topology change, so
        # the encode cache and the device SPF tables must rebuild
        for adj in flip_db.adjacencies:
            adj.metric = 1 + (step % 2)
        ls.update_adjacency_database(flip_db)

    def fresh_backend(num_devices: int) -> TpuBackend:
        return TpuBackend(
            SpfSolver("node0"),
            min_device_prefixes=0,  # always device
            clock=WallClock(),
            counters=CounterMap(),
            resilience=ResilienceConfig(enabled=False),
            parallel=ParallelConfig(
                max_devices=num_devices, min_shard_rows=0
            ),
        )

    def phase_totals(counters: CounterMap) -> dict:
        out = {}
        for phase in pipeline.PHASES:
            h = counters.histogram(pipeline.hist_key(phase))
            if h is not None:
                out[phase] = h.total
        return out

    def rebuild_round(num_devices: int) -> dict:
        backend = fresh_backend(num_devices)
        probe = backend.probe
        counters = probe.counters
        flip_topology(0)
        prev = backend.build_route_db(als, ps, force_full=True)  # warm
        t0_phase = phase_totals(counters)
        t0_busy = probe.busy_snapshot()
        walls = []
        t_round = time.perf_counter()
        for step in range(1, PIPELINE_REBUILDS + 1):
            flip_topology(step)
            t0 = time.perf_counter()
            db = backend.build_route_db(als, ps, force_full=True)
            with probe.phase(pipeline.DELTA_EXTRACT):
                update = prev.calculate_update(db)
            walls.append((time.perf_counter() - t0) * 1000.0)
            assert not update.empty()  # the metric flip moved routes
            prev = db
        wall_ms = (time.perf_counter() - t_round) * 1000.0
        t1_phase = phase_totals(counters)
        t1_busy = probe.busy_snapshot()
        phases_ms = {
            k: round(t1_phase.get(k, 0.0) - t0_phase.get(k, 0.0), 3)
            for k in pipeline.PHASES
            if t1_phase.get(k, 0.0) - t0_phase.get(k, 0.0) > 0.0
        }
        attributed = sum(phases_ms.values())
        host_ms = sum(
            phases_ms.get(p, 0.0) for p in pipeline.HOST_PHASES
        )
        device_ms = sum(
            phases_ms.get(p, 0.0) for p in pipeline.DEVICE_PHASES
        )
        per_chip = {}
        for dev in range(num_devices):
            busy = t1_busy.get(dev, 0.0) - t0_busy.get(dev, 0.0)
            per_chip[f"dev{dev}"] = {
                "busy_ms": round(busy, 3),
                "busy_fraction": round(busy / wall_ms, 4),
            }
        return {
            "devices": num_devices,
            "rebuilds": PIPELINE_REBUILDS,
            "wall_ms": round(wall_ms, 3),
            "rebuild_ms_each": [round(w, 3) for w in walls],
            "attributed_ms": round(attributed, 3),
            "gap_pct": round((wall_ms - attributed) / wall_ms * 100.0, 3),
            "phases_ms": phases_ms,
            "host_ms": round(host_ms, 3),
            "device_ms": round(device_ms, 3),
            "host_share_pct": round(host_ms / attributed * 100.0, 2),
            "device_share_pct": round(device_ms / attributed * 100.0, 2),
            "per_chip_busy": per_chip,
            "routes": len(prev.unicast_routes),
        }

    def engine_round(kind: str) -> dict:
        # fleet/what-if attribution rides a 256-node world: the point
        # is phase coverage of the pooled dispatch paths, and a
        # 4096-root fleet batch (4096 SPF solves) would turn the bench
        # into a soak on host devices
        eside = 16
        e_edges = grid_edges(eside)
        e_ls = LinkState("0")
        for db in build_adj_dbs(e_edges).values():
            e_ls.update_adjacency_database(db)
        e_ps = PrefixState()
        for i in range(eside * eside):
            e_ps.update_prefix(
                f"node{i}", "0", PrefixEntry(f"10.77.{i % 256}.0/24")
            )
        e_als = {"0": e_ls}
        backend = fresh_backend(PIPELINE_DEVICES[-1])
        probe = backend.probe
        pool = backend.dispatch_pool()
        assert pool is not None and pool.size == PIPELINE_DEVICES[-1]
        solver = SpfSolver("node0")
        if kind == "fleet":
            eng = FleetRibEngine(solver, pool=pool, probe=probe)

            def run_once(seq):
                return eng.fleet_summary(e_als, e_ps, seq)
        else:
            eng = MultiAreaWhatIfEngine(solver, pool=pool, probe=probe)
            failures = [
                (f"node{i}", f"node{i + 1}") for i in range(0, 48)
                if (i + 1) % eside  # same-row neighbors only
            ]

            def run_once(seq):
                return eng.run(failures, e_als, e_ps, seq)

        run_once(1)  # warm compile (cold kernels)
        run_once(2)  # warm compile (generation-delta kernels)
        t0_phase = phase_totals(probe.counters)
        t0 = time.perf_counter()
        run_once(3)  # fresh generation: tables rebuilt, real dispatches
        wall_ms = (time.perf_counter() - t0) * 1000.0
        t1_phase = phase_totals(probe.counters)
        phases_ms = {
            k: round(t1_phase.get(k, 0.0) - t0_phase.get(k, 0.0), 3)
            for k in pipeline.PHASES
            if t1_phase.get(k, 0.0) - t0_phase.get(k, 0.0) > 0.0
        }
        return {
            "devices": PIPELINE_DEVICES[-1],
            "world_nodes": eside * eside,
            "wall_ms": round(wall_ms, 3),
            "attributed_ms": round(sum(phases_ms.values()), 3),
            "phases_ms": phases_ms,
            "pool_dispatches": int(sum(pool.num_dispatches)),
        }

    def delta_round() -> dict:
        """The on-device delta-extraction path (ISSUE 11): a FAR-corner
        victim perturbs routes to a handful of prefixes; consecutive
        full rebuilds with an exact (empty) prefix-churn delta then run
        the fused select+diff kernel and move only the changed rows
        over the host boundary (device_select gather), patching the
        rest through object-identically."""
        backend = fresh_backend(1)
        counters = backend.probe.counters
        far = f"node{n_nodes - 1}"
        far_db = adj_dbs[far]

        def flip_far(step: int) -> None:
            for a in far_db.adjacencies:
                a.metric = 1 + (step % 2)
            ls.update_adjacency_database(far_db)

        flip_far(0)
        prev = backend.build_route_db(
            als, ps, changed_prefixes=set(), force_full=True
        )
        # one unmeasured delta build compiles the fused select+diff and
        # gather kernels (the rebuild rounds warm the non-delta shapes
        # the same way via their own warm-up build)
        flip_far(1)
        prev = backend.build_route_db(
            als, ps, changed_prefixes=set(), force_full=True
        )
        assert backend.num_delta_builds == 1
        backend.take_last_changed_prefixes()
        backend.num_delta_builds = 0
        backend.num_delta_rows_fetched = 0
        backend.num_delta_rows_skipped = 0
        t0_phase = phase_totals(counters)
        walls = []
        t_round = time.perf_counter()
        for step in range(2, PIPELINE_REBUILDS + 2):
            flip_far(step)
            t0 = time.perf_counter()
            db = backend.build_route_db(
                als, ps, changed_prefixes=set(), force_full=True
            )
            changed = backend.take_last_changed_prefixes()
            with backend.probe.phase(pipeline.DELTA_EXTRACT):
                update = prev.calculate_update(db)
            walls.append((time.perf_counter() - t0) * 1000.0)
            assert not update.empty() and changed
            prev = db
        wall_ms = (time.perf_counter() - t_round) * 1000.0
        t1_phase = phase_totals(counters)
        phases_ms = {
            k: round(t1_phase.get(k, 0.0) - t0_phase.get(k, 0.0), 3)
            for k in pipeline.PHASES
            if t1_phase.get(k, 0.0) - t0_phase.get(k, 0.0) > 0.0
        }
        return {
            "devices": 1,
            "rebuilds": PIPELINE_REBUILDS,
            "victim": far,
            "wall_ms": round(wall_ms, 3),
            "rebuild_ms_each": [round(w, 3) for w in walls],
            "delta_builds": backend.num_delta_builds,
            "rows_fetched": backend.num_delta_rows_fetched,
            "rows_skipped": backend.num_delta_rows_skipped,
            "phases_ms": phases_ms,
        }

    rounds = [rebuild_round(n) for n in PIPELINE_DEVICES]
    for r in rounds:
        print(
            f"# {r['devices']} device(s): wall {r['wall_ms']}ms, "
            f"attributed {r['attributed_ms']}ms "
            f"(gap {r['gap_pct']}%), host {r['host_share_pct']}%",
            file=sys.stderr,
        )
    dround = delta_round()
    print(
        f"# delta round: wall {dround['wall_ms']}ms, rows fetched "
        f"{dround['rows_fetched']} vs skipped {dround['rows_skipped']}",
        file=sys.stderr,
    )
    fleet_round = engine_round("fleet")
    whatif_round = engine_round("whatif")
    worst_gap = max((abs(r["gap_pct"]) for r in rounds), key=abs)
    doc = {
        "metric": "pipeline_attribution_gap_pct_grid4096_rebuild",
        "value": worst_gap,
        "unit": "pct_of_rebuild_wall",
        "detail": {
            "rebuild_rounds": rounds,
            "delta_round": dround,
            "fleet_round": fleet_round,
            "whatif_round": whatif_round,
            "world": {
                "nodes": n_nodes,
                "topology": f"grid{side}x{side}",
                "prefixes": n_nodes,
                "engine_world_nodes": 256,
            },
            "mode": (
                "emulate (in-process LSDB, WallClock probe, 8 forced "
                "virtual host devices sharing physical cores — per-chip "
                "busy fractions measure dispatch-plane structure, not "
                "silicon occupancy; streamed drains charge each wait "
                "window to the completing chip only, so fractions are "
                "honest under overlap)"
            ),
            "gap_definition": (
                "wall_ms measured around build_route_db(force_full) + "
                "RouteDb diff; attributed_ms = delta of every "
                "pipeline.{phase}.ms histogram total over the same "
                "window; gap = (wall - attributed) / wall"
            ),
            "env": env_stamp(),
        },
    }
    validate_pipeline_bench(doc)
    print(json.dumps(doc))


SERVING_CONCURRENCY = (1, 8, 64, 512)


def validate_serving_bench(doc: dict) -> None:
    """Schema contract for BENCH_SERVING_r*.json — shared by the bench
    emitter and the tier-1 smoke test so the artifact can never drift
    from what the test validates."""
    assert doc["metric"] == "serving_route_db_queries_per_sec_64_clients"
    assert doc["unit"] == "queries/s"
    assert doc["value"] > 0
    assert doc["vs_baseline"] > 0
    detail = doc["detail"]
    rounds = detail["rounds"]
    assert [r["clients"] for r in rounds] == list(SERVING_CONCURRENCY)
    for r in rounds:
        assert r["waves"] >= 2 and r["distinct_queries"] >= 1
        for side in ("steady", "cold", "unbatched"):
            res = r[side]
            assert res["qps"] > 0
            assert 0 <= res["p50_ms"] <= res["p99_ms"]
            assert res["queries"] >= r["clients"]
        assert r["speedup_steady"] > 0 and r["speedup_cold"] > 0
        assert 0 <= r["steady"]["cache_hit_ratio"] <= 1
        assert r["steady"]["batches"] >= 1
    wf = detail["whatif_coalescing_64"]
    assert wf["batched_ms"] > 0 and wf["unbatched_device_ms"] > 0
    for key in ("world", "serving_config", "env", "mode"):
        assert key in detail, key
    for key in ("platform", "jax", "device_count"):
        assert key in detail["env"], f"env.{key}"
    assert detail["env"]["device_count"] >= 1


def serving_main(seed: Optional[int] = None) -> None:
    """Serving-plane benchmark (the BENCH_SERVING_r* artifact): the
    micro-batched/cached serving path vs the unbatched path — one fresh
    scalar SpfSolver pass per call, the reference's getRouteDbComputed
    behavior (Decision.cpp:342) — at 1/8/64/512 concurrent clients
    against one in-process emulated LSDB.  Emits one JSON line.

    Methodology.  Each concurrency round runs W waves of K concurrent
    route_db clients re-sweeping a closed query set (client i queries
    vantage i mod min(K, |V|)) against ONE serving Decision at a fixed
    LSDB generation — the steady state between routing changes (query
    rate >> LSDB churn in the millions-of-users regime).  Two batched
    measurements per round keep the claim honest:

    * ``steady`` — the serving plane as deployed: result cache ON.
      Wave 1 pays the fleet batch solve + decodes; later waves hit the
      content-addressed cache.  This is the headline (value /
      vs_baseline at 64 clients).
    * ``cold`` — cache CLEARED between waves: isolates micro-batching +
      the engines' per-generation table reuse with the result cache
      handicapped off.

    The unbatched side pays one fresh scalar build per request,
    strictly sequential, no reuse of any kind — exactly what the
    reference does per ctrl call (it has no result cache).  jit compile
    happens in an excluded warm-up; latencies are per-request
    (submit→answer).  A what-if coalescing measurement (64 distinct
    single-link queries: one coalesced engine sweep vs 64 per-query
    dispatches, device and native engines) rides in the detail."""
    import asyncio

    from openr_tpu.ops.platform_env import enable_persistent_compile_cache

    enable_persistent_compile_cache()

    from openr_tpu.common.runtime import WallClock
    from openr_tpu.config import DecisionConfig, ServingConfig
    from openr_tpu.decision.backend import TpuBackend
    from openr_tpu.decision.decision import Decision
    from openr_tpu.decision.link_state import LinkState
    from openr_tpu.decision.prefix_state import PrefixState
    from openr_tpu.decision.spf_solver import SpfSolver
    from openr_tpu.emulation.topology import (
        build_adj_dbs,
        random_connected_edges,
    )
    from openr_tpu.messaging.queue import ReplicateQueue
    from openr_tpu.serving.service import QueryService
    from openr_tpu.types import PrefixEntry

    n_nodes, n_links, seed = 256, 512, (11 if seed is None else seed)
    min_queries = 640  # per round, so the one-time solve amortizes
    edges = random_connected_edges(n_nodes, n_links, seed=seed)
    ls = LinkState("0")
    for db in build_adj_dbs(edges).values():
        ls.update_adjacency_database(db)
    ps = PrefixState()
    for i in range(n_nodes):
        ps.update_prefix(
            f"node{i}", "0", PrefixEntry(f"10.{i // 256}.{i % 256}.0/24")
        )
    als = {"0": ls}
    serving_cfg = ServingConfig(max_batch=64, max_wait_ms=2)

    def fresh_decision() -> Decision:
        solver = SpfSolver("node0")
        d = Decision(
            "node0",
            WallClock(),
            DecisionConfig(),
            ReplicateQueue("routes"),
            backend=TpuBackend(solver),
            solver=solver,
        )
        d.area_link_states = als
        d.prefix_state = ps
        d._change_seq = 1
        return d

    def unbatched_round(k: int, waves: int, distinct: int):
        """The reference path: one fresh scalar vantage solve + wire
        serialization per call, strictly sequential, no reuse."""
        lat = []
        t0 = time.perf_counter()
        for _w in range(waves):
            for i in range(k):
                node = f"node{i % distinct}"
                t1 = time.perf_counter()
                SpfSolver(node).build_route_db(als, ps).to_route_database(
                    node
                ).to_wire()
                lat.append((time.perf_counter() - t1) * 1000.0)
        wall = time.perf_counter() - t0
        return wall, lat

    async def batched_round(k: int, waves: int, distinct: int, cold: bool):
        clock = WallClock()
        d = fresh_decision()
        sv = QueryService(
            "node0", clock, serving_cfg, d, counters=d.counters
        )
        sv.start()
        lat = []

        async def client(i: int):
            t1 = time.perf_counter()
            await sv.submit(
                "route_db",
                {"node": f"node{i % distinct}"},
                client_id=f"client{i}",
            )
            lat.append((time.perf_counter() - t1) * 1000.0)

        t0 = time.perf_counter()
        for _w in range(waves):
            await asyncio.gather(*[client(i) for i in range(k)])
            if cold:
                sv.cache.clear()
        wall = time.perf_counter() - t0
        total = k * waves
        stats = dict(
            batches=sv.num_batches,
            batch_solves=sv.num_batch_solves,
            dedup_hits=sv.num_dedup_hits,
            cache_hit_ratio=round(
                d.counters.get("serving.cache.hits") / total, 3
            ),
        )
        await sv.stop()
        return wall, lat, stats

    def pcts(lat):
        srt = sorted(lat)
        return (
            srt[len(srt) // 2],
            srt[min(len(srt) - 1, int(len(srt) * 0.99))],
        )

    def whatif_coalescing_detail():
        """64 distinct single-link what-ifs: one coalesced sweep (what
        the serving batcher dispatches) vs 64 per-query dispatches on
        the device engine, with the native engine's per-query cost
        reported for transparency (the repo's auto engine choice at
        small scale)."""
        pairs = [(a, b) for a, b, _m in edges][:64]
        d = fresh_decision()
        d.backend.auto_dispatch_rt_ms = 0.0  # pin the device engine
        d.get_link_failure_whatif([list(pairs[0])])  # warm compile
        d.get_link_failure_whatif([list(p) for p in pairs])
        t0 = time.perf_counter()
        for p in pairs:
            d.get_link_failure_whatif([list(p)])
        un_ms = (time.perf_counter() - t0) * 1000.0
        t0 = time.perf_counter()
        d.get_link_failure_whatif([list(p) for p in pairs])
        b_ms = (time.perf_counter() - t0) * 1000.0
        dn = fresh_decision()
        dn.backend.auto_dispatch_rt_ms = 1000.0  # pin the native engine
        dn.get_link_failure_whatif([list(pairs[0])])
        t0 = time.perf_counter()
        for p in pairs:
            dn.get_link_failure_whatif([list(p)])
        nat_ms = (time.perf_counter() - t0) * 1000.0
        return {
            "queries": 64,
            "batched_ms": round(b_ms, 1),
            "unbatched_device_ms": round(un_ms, 1),
            "unbatched_native_ms": round(nat_ms, 1),
            "speedup_vs_device": round(un_ms / b_ms, 2),
            "speedup_vs_native": round(nat_ms / b_ms, 2),
        }

    def side(wall, lat, total, extra=None):
        p50, p99 = pcts(lat)
        out = {
            "qps": round(total / wall, 1),
            "p50_ms": round(p50, 2),
            "p99_ms": round(p99, 2),
            "wall_s": round(wall, 4),
            "queries": total,
        }
        if extra:
            out.update(extra)
        return out

    async def run_all():
        await batched_round(8, 2, 8, cold=True)  # compile warm-up
        unbatched_round(2, 1, 2)
        rounds = []
        for k in SERVING_CONCURRENCY:
            waves = max(2, -(-min_queries // k))  # ceil, >= 2 waves
            distinct = min(k, n_nodes)
            total = k * waves
            uw, ulat = unbatched_round(k, waves, distinct)
            sw, slat, sstats = await batched_round(
                k, waves, distinct, cold=False
            )
            cw, clat, cstats = await batched_round(
                k, waves, distinct, cold=True
            )
            rounds.append(
                {
                    "clients": k,
                    "waves": waves,
                    "distinct_queries": distinct,
                    "steady": side(sw, slat, total, sstats),
                    "cold": side(cw, clat, total, cstats),
                    "unbatched": side(uw, ulat, total),
                    "speedup_steady": round(uw / sw, 2),
                    "speedup_cold": round(uw / cw, 2),
                }
            )
        return rounds

    rounds = asyncio.new_event_loop().run_until_complete(run_all())
    whatif_detail = whatif_coalescing_detail()
    r64 = next(r for r in rounds if r["clients"] == 64)
    doc = {
        "metric": "serving_route_db_queries_per_sec_64_clients",
        "value": r64["steady"]["qps"],
        "unit": "queries/s",
        "vs_baseline": r64["speedup_steady"],
        "detail": {
            "rounds": rounds,
            "whatif_coalescing_64": whatif_detail,
            "world": {
                "nodes": n_nodes,
                "links": n_links,
                "prefixes": n_nodes,
                "topology": "random_connected",
                "seed": seed,
            },
            "serving_config": {
                "max_batch": serving_cfg.max_batch,
                "max_wait_ms": serving_cfg.max_wait_ms,
            },
            "mode": "emulate (in-process LSDB, WallClock serving actor)",
            "steady_definition": (
                "serving plane as deployed (result cache ON), W waves "
                "of K clients re-sweeping a closed query set at one "
                "LSDB generation"
            ),
            "cold_definition": (
                "result cache cleared between waves: micro-batching + "
                "engine table reuse only"
            ),
            "unbatched_definition": (
                "one fresh scalar SpfSolver vantage build per request, "
                "sequential (the reference getRouteDbComputed path, "
                "Decision.cpp:342; no cache of any kind)"
            ),
            "env": env_stamp(),
        },
    }
    validate_serving_bench(doc)
    print(json.dumps(doc))


SERVING_MULTICHIP_DEVICES = (1, 2, 4, 8)


def validate_multichip_serving_bench(doc: dict) -> None:
    """Schema contract for BENCH_MULTICHIP_SERVING_r*.json — shared by
    the bench emitter and the tier-1 smoke test.  The headline value is
    serving throughput with the full 8-chip pool; the degraded round
    proves a 7-of-8 pool (one chip quarantined) KEEPS serving through
    the device engines (`serving_stayed_available`)."""
    assert doc["metric"] == "multichip_serving_route_db_qps_8dev"
    assert doc["unit"] == "queries/s"
    assert doc["value"] > 0
    assert doc["vs_baseline"] > 0
    d = doc["detail"]
    rounds = d["rounds"]
    assert [r["devices"] for r in rounds] == list(SERVING_MULTICHIP_DEVICES)
    for r in rounds:
        assert r["qps"] > 0
        assert 0 <= r["p50_ms"] <= r["p99_ms"]
        assert r["queries"] >= 64
        assert r["healthy_devices"] == r["devices"]
        # multi-chip rounds must actually dispatch over the pool
        assert r["pool_dispatches"] >= (1 if r["devices"] > 1 else 0)
    deg = d["degraded_7of8"]
    assert deg["healthy_devices"] == 7
    assert 0 <= deg["quarantined_device"] < 8
    assert deg["qps"] > 0
    assert deg["serving_stayed_available"] is True
    assert deg["device_failed"] is False
    for key in ("world", "env", "mode"):
        assert key in d, key
    for key in ("platform", "jax", "device_count"):
        assert key in d["env"], f"env.{key}"
    assert d["env"]["device_count"] >= 8


def multichip_serving_main(seed: Optional[int] = None) -> None:
    """Multi-chip serving benchmark (BENCH_MULTICHIP_SERVING_r*): fleet
    route_db serving throughput through QueryService at a 1/2/4/8-chip
    DevicePool, plus a 7-of-8 degraded round with one chip quarantined
    by the health governor — proving the serving plane keeps answering
    on the survivors with `Decision.device_available()` still true.

    Methodology: one in-process LSDB (random connected graph), a fresh
    Decision + QueryService per round, W waves of K=64 concurrent
    route_db clients over distinct vantages with the RESULT CACHE
    CLEARED between waves — each wave pays real engine work (one pooled
    fleet batch solve on the first wave, per-vantage decodes after), so
    the number measures the compute path, not cache hits.  On forced
    virtual host devices (this artifact's environment) all chips share
    the physical cores, so scaling is STRUCTURAL (shard routing,
    re-packing, health governance) rather than physical — the round
    shape is what transfers to a real mesh."""
    import os

    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    import asyncio

    from openr_tpu.ops.platform_env import enable_persistent_compile_cache

    enable_persistent_compile_cache()

    from openr_tpu.common.runtime import WallClock
    from openr_tpu.config import (
        DecisionConfig,
        ParallelConfig,
        ServingConfig,
    )
    from openr_tpu.decision.backend import TpuBackend
    from openr_tpu.decision.decision import Decision
    from openr_tpu.decision.link_state import LinkState
    from openr_tpu.decision.prefix_state import PrefixState
    from openr_tpu.decision.spf_solver import SpfSolver
    from openr_tpu.emulation.topology import (
        build_adj_dbs,
        random_connected_edges,
    )
    from openr_tpu.messaging.queue import ReplicateQueue
    from openr_tpu.serving.service import QueryService
    from openr_tpu.types import PrefixEntry

    n_nodes, n_links, seed = 128, 256, (11 if seed is None else seed)
    clients, waves = 64, 3
    edges = random_connected_edges(n_nodes, n_links, seed=seed)
    ls = LinkState("0")
    for db in build_adj_dbs(edges).values():
        ls.update_adjacency_database(db)
    ps = PrefixState()
    for i in range(n_nodes):
        ps.update_prefix(
            f"node{i}", "0", PrefixEntry(f"10.{i // 256}.{i % 256}.0/24")
        )
    als = {"0": ls}
    serving_cfg = ServingConfig(max_batch=64, max_wait_ms=2)

    def fresh_decision(num_devices: int) -> Decision:
        solver = SpfSolver("node0")
        d = Decision(
            "node0",
            WallClock(),
            DecisionConfig(),
            ReplicateQueue("routes"),
            backend=TpuBackend(
                solver,
                parallel=ParallelConfig(
                    max_devices=num_devices, min_shard_rows=0
                ),
            ),
            solver=solver,
        )
        d.area_link_states = als
        d.prefix_state = ps
        d._change_seq = 1
        return d

    async def serve_round(d: Decision):
        clock = WallClock()
        sv = QueryService(
            "node0", clock, serving_cfg, d, counters=d.counters
        )
        sv.start()
        lat = []

        async def client(i: int):
            t1 = time.perf_counter()
            await sv.submit(
                "route_db",
                {"node": f"node{i % n_nodes}"},
                client_id=f"client{i}",
            )
            lat.append((time.perf_counter() - t1) * 1000.0)

        t0 = time.perf_counter()
        for _w in range(waves):
            await asyncio.gather(*[client(i) for i in range(clients)])
            # advance the computed-result generation so the NEXT wave
            # pays a fresh pooled fleet batch solve — the number must
            # measure the compute path (pool-sharded solve + decodes),
            # not the result cache or the engine's per-generation
            # table cache
            d._change_seq += 1
            sv.cache.clear()
        wall = time.perf_counter() - t0
        await sv.stop()
        total = clients * waves
        srt = sorted(lat)
        return {
            "qps": round(total / wall, 1),
            "p50_ms": round(srt[len(srt) // 2], 2),
            "p99_ms": round(srt[min(len(srt) - 1, int(len(srt) * 0.99))], 2),
            "wall_s": round(wall, 4),
            "queries": total,
        }

    loop = asyncio.new_event_loop()

    def run_round(num_devices: int, quarantine=None):
        d = fresh_decision(num_devices)
        gov = d.backend.governor
        if quarantine is not None:
            gov.force_quarantine_device(quarantine, reason="bench")
        # warm compile OUTSIDE the measured window
        loop.run_until_complete(serve_round(d))
        fleet = d._fleet_engine
        dispatch_before = fleet.num_pool_dispatches if fleet else 0
        res = loop.run_until_complete(serve_round(d))
        fleet = d._fleet_engine
        pool = d.backend.pool
        res.update(
            {
                "healthy_devices": pool.num_healthy,
                "pool_dispatches": (
                    (fleet.num_pool_dispatches - dispatch_before)
                    if fleet
                    else 0
                ),
                "device_available": d.device_available(),
            }
        )
        return res

    rounds = []
    for n in SERVING_MULTICHIP_DEVICES:
        r = run_round(n)
        r["devices"] = n
        rounds.append(r)
        print(
            f"# {n} device(s): {r['qps']} q/s p50={r['p50_ms']}ms",
            file=sys.stderr,
        )
    bad_chip = 3
    deg = run_round(8, quarantine=bad_chip)
    deg.update(
        {
            "quarantined_device": bad_chip,
            "serving_stayed_available": deg.pop("device_available"),
            "device_failed": False,
        }
    )
    print(
        f"# 7-of-8 degraded: {deg['qps']} q/s (chip {bad_chip} "
        "quarantined)",
        file=sys.stderr,
    )

    r8 = rounds[-1]
    doc = {
        "metric": "multichip_serving_route_db_qps_8dev",
        "value": r8["qps"],
        "unit": "queries/s",
        "vs_baseline": round(r8["qps"] / rounds[0]["qps"], 2),
        "detail": {
            "rounds": rounds,
            "degraded_7of8": deg,
            "clients": clients,
            "waves": waves,
            "world": {
                "nodes": n_nodes,
                "links": n_links,
                "prefixes": n_nodes,
                "topology": "random_connected",
                "seed": seed,
            },
            "mode": (
                "emulate (in-process LSDB, WallClock serving actor, 8 "
                "forced virtual host devices sharing physical cores — "
                "scaling is structural, not physical)"
            ),
            "degraded_definition": (
                "chip 3 hard-quarantined via the health governor "
                "before the round: fleet chunks re-pack onto the 7 "
                "survivors, Decision.device_available() stays true, "
                "serving keeps answering through the device engines"
            ),
            "env": env_stamp(),
        },
    }
    validate_multichip_serving_bench(doc)
    print(json.dumps(doc))


HEALTH_OVERHEAD_BOUND_PCT = 2.0
HEALTH_FLEET_NODES = 9
HEALTH_SEEDS = (7, 11, 13)
HEALTH_FAULT_FAMILIES = ("partition", "tpu_corrupt", "fib_burst", "actor_kill")


def validate_health_bench(doc: dict) -> None:
    """Schema contract for BENCH_HEALTH_r*.json — shared by the bench
    emitter and the tier-1 smoke test (tests/test_health_bench_schema).
    The headline is the fleet-health aggregator's sweep overhead on the
    serving p50 (acceptance bound <= 2%); the detail records the
    fault-injection -> alert detection-latency distribution per fault
    family over a seeded 9-node sweep."""
    assert doc["metric"] == "health_sweep_overhead_pct_serving_p50"
    assert doc["unit"] == "pct"
    assert isinstance(doc["value"], (int, float))
    assert doc["value"] <= HEALTH_OVERHEAD_BOUND_PCT, (
        "aggregator sweep overhead must stay <= 2% on serving p50"
    )
    d = doc["detail"]
    assert d["serving_p50_ms_health_off"] > 0
    assert d["serving_p50_ms_health_on"] > 0
    assert d["serving_p99_ms_health_on"] >= d["serving_p50_ms_health_on"]
    assert d["sweeps_during_run"] >= 10
    assert d["fleet_nodes"] == HEALTH_FLEET_NODES
    assert d["queries_per_sweep"] <= 64, (
        "the measured cadence must be far more aggressive than prod"
    )
    det = d["detection"]
    assert set(det) == set(HEALTH_FAULT_FAMILIES)
    for family, row in det.items():
        assert row["samples"] >= len(HEALTH_SEEDS), family
        assert row["detected"] == row["samples"], (
            f"{family}: every seeded injection must be detected"
        )
        assert 0.0 <= row["p50_ms"] <= row["max_ms"], family
        assert row["alert"], family
        assert row["max_sweeps"] >= 1, family
    assert d["deterministic_replay"] is True
    for key in ("env", "mode"):
        assert key in d, key
    for key in ("platform", "jax", "device_count"):
        assert key in d["env"], f"env.{key}"


def _health_detection_sweep(seeds=HEALTH_SEEDS) -> dict:
    """Part B: for each fault family, a seeded 9-node SimClock emulation
    measuring fault-injection -> first-alert latency (virtual ms) at a
    500ms sweep cadence, across HEALTH_SEEDS.  The partition family is
    additionally replayed to assert byte-identical alert logs."""
    import asyncio
    import json as _json

    from openr_tpu.chaos import ChaosController, FaultPlan, Supervisor
    from openr_tpu.common.runtime import SimClock
    from openr_tpu.config import ParallelConfig, ResilienceConfig
    from openr_tpu.emulation.network import EmulatedNetwork
    from openr_tpu.emulation.topology import grid_edges
    from openr_tpu.types import PrefixEntry

    SWEEP_S = 0.5
    FAULT_AT = 2.0

    def overrides(cfg, tpu=False):
        hc = cfg.health_config
        hc.sweep_interval_s = SWEEP_S
        hc.skew_min_generations = 2
        hc.skew_hold_s = 2.0
        cfg.watchdog_config.interval_s = 1.0
        if tpu:
            cfg.tpu_compute_config.min_device_prefixes = 0
            cfg.parallel_config = ParallelConfig(min_shard_rows=0)
            cfg.resilience_config = ResilienceConfig(
                shadow_sample_every=1,
                failure_threshold=2,
                probe_backoff_initial_s=0.5,
                probe_backoff_max_s=4.0,
                jitter_pct=0.1,
                seed=7,
            )

    async def one_family(family: str, seed: int):
        clock = SimClock()
        tpu = family == "tpu_corrupt"
        net = EmulatedNetwork(
            clock,
            use_tpu_backend=tpu,
            config_overrides=lambda cfg: overrides(cfg, tpu=tpu),
        )
        net.build(grid_edges(3))
        net.start()
        supervisor = None
        if family == "actor_kill":
            supervisor = Supervisor(
                clock, initial_backoff_s=0.25, max_backoff_s=5.0
            )
            supervisor.start()
            for name, node in net.nodes.items():
                supervisor.supervise(name, node, net.restart_node)
        await clock.run_for(18.0)
        ok, why = net.converged_full_mesh()
        assert ok, why
        if tpu:
            net.nodes["node0"].advertise_prefixes(
                [PrefixEntry(f"10.99.{i}.0/24") for i in range(9)]
            )
            await clock.run_for(3.0)
        plan = FaultPlan()
        expected = {
            "partition": "generation_skew",
            "tpu_corrupt": "chip_quarantine",
            "fib_burst": "breaker_open",
            "actor_kill": "node_crash",
        }[family]
        if family == "partition":
            plan.partition(
                [f"node{i}" for i in range(8)], ["node8"],
                at=FAULT_AT, duration=30.0,
            )
        elif family == "tpu_corrupt":
            plan.tpu_corrupt(
                "node4", at=FAULT_AT, duration=30.0, device_index=3
            )
        elif family == "fib_burst":
            plan.fib_burst("node4", at=FAULT_AT, duration=20.0)
        else:
            plan.actor_kill("node4", "decision", at=FAULT_AT)
        controller = ChaosController(net, plan, seed=seed)
        t_fault_ms = (clock.now() + FAULT_AT) * 1000.0
        controller.start()
        h = net.nodes["node0"].health
        sweeps_at_fault = h.num_sweeps
        detect_ms = None
        for i in range(60):  # bounded: 30s of virtual time
            fired = [
                _json.loads(line)
                for line in h.alert_log()
                if _json.loads(line)["event"] == "fired"
            ]
            hit = [e for e in fired if e["name"] == expected]
            if hit:
                detect_ms = hit[0]["ts_ms"] - t_fault_ms
                break
            # drive the churn the family needs to surface
            if family in ("partition", "fib_burst"):
                net.nodes["node0"].advertise_prefixes(
                    [PrefixEntry(f"10.9{i % 10}.{i}.0/24")]
                )
            elif family == "tpu_corrupt" and i % 2 == 0:
                pair = [("node0", "node1"), ("node1", "node2")][
                    (i // 2) % 2
                ]
                net.fail_link(*pair)
            await clock.run_for(SWEEP_S)
        sweeps_to_detect = h.num_sweeps - sweeps_at_fault
        log = h.sink.log_bytes()
        if supervisor is not None:
            await supervisor.stop()
        await controller.stop()
        await net.stop()
        return detect_ms, sweeps_to_detect, log

    def run(coro):
        loop = asyncio.new_event_loop()
        try:
            return loop.run_until_complete(coro)
        finally:
            loop.close()

    detection = {}
    replay_identical = True
    for family in HEALTH_FAULT_FAMILIES:
        lats, sweeps, detected = [], [], 0
        for seed in seeds:
            detect_ms, n_sweeps, log = run(one_family(family, seed))
            if detect_ms is not None:
                detected += 1
                lats.append(detect_ms)
                sweeps.append(n_sweeps)
            if family == "partition" and seed == seeds[0]:
                _ms2, _n2, log2 = run(one_family(family, seed))
                replay_identical = replay_identical and log == log2
        lats.sort()
        detection[family] = {
            "alert": {
                "partition": "generation_skew",
                "tpu_corrupt": "chip_quarantine",
                "fib_burst": "breaker_open",
                "actor_kill": "node_crash",
            }[family],
            "samples": len(seeds),
            "detected": detected,
            "p50_ms": round(lats[len(lats) // 2], 1) if lats else -1.0,
            "max_ms": round(lats[-1], 1) if lats else -1.0,
            "max_sweeps": max(sweeps) if sweeps else 0,
        }
    return {
        "families": detection,
        "replay_identical": replay_identical,
        "sweep_interval_ms": SWEEP_S * 1000.0,
    }


def health_main(seed: Optional[int] = None) -> None:
    """Fleet-health benchmark (the BENCH_HEALTH_r* artifact).

    Part A — aggregator sweep overhead on the serving p50: one serving
    Decision answers W waves of K concurrent route_db queries (cache
    cleared per wave, so every wave pays a real millisecond-scale
    batch solve) while a FleetHealthAggregator
    sweeps a 9-node snapshot fleet ON THE SAME event loop, one full
    sweep (9 captures + cross-node merge + signal evaluation) per
    64-query wave — orders of magnitude more often than the production
    15s cadence, so the measured contention is an upper bound.
    Acceptance: p50 inflation <= 2%.

    Part B — chaos detection latency: per fault family, seeded 9-node
    SimClock emulations measure fault-injection -> first-alert latency
    in virtual ms at a 500ms sweep cadence (plus a replay determinism
    check on the alert JSONL).  Emits one JSON line."""
    import asyncio
    import os

    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    from openr_tpu.ops.platform_env import enable_persistent_compile_cache

    enable_persistent_compile_cache()

    from openr_tpu.common.runtime import WallClock
    from openr_tpu.config import DecisionConfig, ServingConfig
    from openr_tpu.decision.backend import TpuBackend
    from openr_tpu.decision.decision import Decision
    from openr_tpu.decision.link_state import LinkState
    from openr_tpu.decision.prefix_state import PrefixState
    from openr_tpu.decision.spf_solver import SpfSolver
    from openr_tpu.emulation.topology import (
        build_adj_dbs,
        random_connected_edges,
    )
    from openr_tpu.health import AlertSink, FleetHealthAggregator
    from openr_tpu.messaging.queue import ReplicateQueue
    from openr_tpu.monitor.metrics import MetricsSnapshot
    from openr_tpu.serving.service import QueryService
    from openr_tpu.types import PrefixEntry

    detection_seeds = (
        HEALTH_SEEDS if seed is None else (seed, seed + 4, seed + 6)
    )
    n_nodes, n_links, seed = 256, 512, (11 if seed is None else seed)
    waves, clients = 20, 64
    edges = random_connected_edges(n_nodes, n_links, seed=seed)
    ls = LinkState("0")
    for db in build_adj_dbs(edges).values():
        ls.update_adjacency_database(db)
    ps = PrefixState()
    for i in range(n_nodes):
        ps.update_prefix(
            f"node{i}", "0", PrefixEntry(f"10.{i // 256}.{i % 256}.0/24")
        )
    als = {"0": ls}

    def fresh_decision() -> Decision:
        solver = SpfSolver("node0")
        d = Decision(
            "node0",
            WallClock(),
            DecisionConfig(),
            ReplicateQueue("routes"),
            backend=TpuBackend(solver),
            solver=solver,
        )
        d.area_link_states = als
        d.prefix_state = ps
        d._change_seq = 1
        return d

    async def serving_round(with_health: bool):
        clock = WallClock()
        d = fresh_decision()
        sv = QueryService(
            "node0",
            clock,
            ServingConfig(max_batch=64, max_wait_ms=2),
            d,
            counters=d.counters,
        )
        sv.start()
        agg = None
        if with_health:
            # a 9-snapshot fleet sharing the serving node's live counter
            # surface: every sweep pays 9 captures + the full merge
            def fleet_source():
                return [
                    MetricsSnapshot.capture(
                        counters=d.counters,
                        node_name=f"node{i}",
                        clock=clock,
                    )
                    for i in range(HEALTH_FLEET_NODES)
                ]

            agg = FleetHealthAggregator(
                node_name="bench",
                clock=clock,
                source=fleet_source,
                sink=AlertSink("bench", clock, d.counters),
                counters=d.counters,
            )
        lat = []

        async def sweep_once():
            # rides the SAME event loop as the in-flight clients, so
            # the full capture+merge cost contends with serving exactly
            # like the HealthMonitor fiber does in production
            agg.sweep()

        async def client(i: int):
            t1 = time.perf_counter()
            await sv.submit(
                "route_db",
                {"node": f"node{i % clients}"},
                client_id=f"client{i}",
            )
            lat.append((time.perf_counter() - t1) * 1000.0)

        # warm-up wave (compile + first batch solve) excluded
        await asyncio.gather(*[client(i) for i in range(clients)])
        lat.clear()
        for _w in range(waves):
            # cold wave: every wave re-pays the batch solve, so the
            # p50 is a real millisecond-scale serving latency and the
            # sweep's contention is measured against it, not against
            # sub-microsecond cache hits
            sv.cache.clear()
            tasks = [client(i) for i in range(clients)]
            if agg is not None:
                tasks.append(sweep_once())  # one sweep per 64 queries
            await asyncio.gather(*tasks)
        sweeps = agg.num_sweeps if agg is not None else 0
        await sv.stop()
        lat.sort()
        return lat, sweeps

    def pct(lat, q):
        return lat[min(len(lat) - 1, int(len(lat) * q))]

    loop = asyncio.new_event_loop()
    try:
        lat_off, _ = loop.run_until_complete(serving_round(False))
        lat_on, sweeps = loop.run_until_complete(serving_round(True))
    finally:
        loop.close()
    p50_off, p50_on = pct(lat_off, 0.50), pct(lat_on, 0.50)
    overhead_pct = (p50_on - p50_off) / p50_off * 100.0

    det = _health_detection_sweep(seeds=detection_seeds)

    doc = {
        "metric": "health_sweep_overhead_pct_serving_p50",
        "value": round(overhead_pct, 2),
        "unit": "pct",
        "detail": {
            "serving_p50_ms_health_off": round(p50_off, 4),
            "serving_p50_ms_health_on": round(p50_on, 4),
            "serving_p99_ms_health_off": round(pct(lat_off, 0.99), 4),
            "serving_p99_ms_health_on": round(pct(lat_on, 0.99), 4),
            "sweeps_during_run": sweeps,
            "queries_per_sweep": clients,
            "fleet_nodes": HEALTH_FLEET_NODES,
            "waves": waves,
            "clients": clients,
            "detection": det["families"],
            "detection_sweep_interval_ms": det["sweep_interval_ms"],
            "deterministic_replay": det["replay_identical"],
            "world": {
                "nodes": n_nodes,
                "links": n_links,
                "prefixes": n_nodes,
                "topology": "random_connected",
                "seed": seed,
            },
            "mode": (
                "part A: wall-clock serving rounds with one full fleet "
                "sweep (9 captures + merge + evaluation) per 64-query "
                "wave on the shared event loop (far above the prod 15s "
                "cadence); part B: seeded 9-node grid SimClock "
                "emulations per fault family, detection in virtual ms"
            ),
            "env": env_stamp(),
        },
    }
    validate_health_bench(doc)
    print(json.dumps(doc))


WARMSTART_GENERATIONS = 24
WARMSTART_PARITY_EVERY = 8
WARMSTART_SWEEP_WARM = 2048
WARMSTART_SWEEP_COLD = 256
#: BENCH_SUITE_p50_r05.json grid4096 p50 publication→FIB — the round-5
#: cold-path baseline the warm rebuild must beat
WARMSTART_COLD_P50_REFERENCE_MS = 127.172


def validate_warmstart_bench(doc: dict) -> None:
    """Schema contract for BENCH_WARMSTART_r*.json — shared by the bench
    emitter and the tier-1 smoke test (tests/test_warmstart_bench_schema).

    The headline value is the warm generation-delta rebuild p50
    (publication→FIB equivalent: build + RouteDb diff) on grid4096,
    which must beat BOTH the in-run cold rebuild p50 and the round-5
    127ms reference.  The sweep block pins device warm-vs-cold
    incrementality (warm must win) and records the native C++ warm
    baseline; the device-beats-native gate applies whenever a real
    accelerator is attached (on a cpu-platform run the 'device' kernel
    IS host XLA, so that comparison measures compilers, not the
    architecture — the artifact records it honestly instead of gating)."""
    assert doc["metric"] == (
        "warmstart_rebuild_p50_publication_to_fib_ms_grid4096"
    )
    assert doc["unit"] == "ms"
    assert 0 < doc["value"] < WARMSTART_COLD_P50_REFERENCE_MS
    d = doc["detail"]
    rb = d["rebuild"]
    assert rb["warm_p50_ms"] == doc["value"]
    assert rb["warm_p50_ms"] < rb["cold_p50_ms"]
    assert rb["warm_p95_ms"] >= rb["warm_p50_ms"]
    assert rb["cold_p50_ms"] > 0
    assert rb["generations"] >= 16
    # every generation in the sweep is a pure perturbation: the warm
    # path must take ALL of them (hit ratio 1.0), with the selective
    # patch engaged and the counters recorded for the operator surface
    assert rb["warm_hits"] == rb["generations"]
    assert rb["warm_selective_builds"] == rb["generations"]
    assert rb["cold_fallbacks"] == 0
    assert rb["warm_purges"] == 0
    assert rb["encode_patches"] >= 1
    assert rb["parity_checks"] >= 2
    assert rb["parity_ok"] is True
    assert rb["reference_cold_p50_ms_r05"] == WARMSTART_COLD_P50_REFERENCE_MS
    assert rb["speedup_vs_cold"] > 1.0
    sw = d["sweep"]
    assert sw["device_warm_solves_per_sec"] > 0
    assert sw["device_cold_solves_per_sec"] > 0
    assert sw["native_warm_solves_per_sec"] > 0
    assert (
        sw["device_warm_solves_per_sec"] > sw["device_cold_solves_per_sec"]
    ), "warm-start must beat the cold kernel on the same sweep"
    assert sw["warm_solves"] >= 1024 and sw["cold_solves"] >= 128
    if d["env"]["platform"] != "cpu":
        assert (
            sw["device_warm_solves_per_sec"]
            > sw["native_warm_solves_per_sec"]
        ), "an attached accelerator must beat the native warm sweep"
    for key in ("world", "env", "mode"):
        assert key in d, key
    for key in ("platform", "jax", "device_count"):
        assert key in d["env"], f"env.{key}"
    assert d["env"]["device_count"] >= 1


def warmstart_main(seed: Optional[int] = None) -> None:
    """Warm-start benchmark (BENCH_WARMSTART_r*): the ISSUE-9
    generation-delta rebuild path on grid4096.

    Part A — rebuild p50: one TpuBackend with the warm context enabled
    and one with it disabled replay the SAME seeded link-metric
    perturbation sweep (one random link flips its metric per
    generation).  Each generation is measured publication→FIB
    equivalent: ``build_route_db(force_full=True, warm_delta=True)``
    plus the RouteDb diff Decision would publish (O(changed) for the
    warm-selective path, full for cold).  Every WARMSTART_PARITY_EVERY
    generations the warm RIB is asserted equal to the cold device build
    AND the scalar oracle.

    Part B — sweep solves/s: the single-link-failure repair sweep
    (ops/repair.RepairSweep, depth-sorted chunks) vs the cold
    batch-minor kernel on the same grid4096 world, plus the native C++
    warm-start sweep (spf_warm_sweep) as the cross-engine baseline."""
    from openr_tpu.ops.platform_env import enable_persistent_compile_cache

    enable_persistent_compile_cache()

    import jax
    import jax.numpy as jnp

    from openr_tpu.common.runtime import CounterMap, WallClock
    from openr_tpu.config import ParallelConfig, ResilienceConfig
    from openr_tpu.decision.backend import TpuBackend
    from openr_tpu.decision.link_state import LinkState
    from openr_tpu.decision.prefix_state import PrefixState
    from openr_tpu.decision.spf_solver import SpfSolver
    from openr_tpu.emulation.topology import build_adj_dbs, grid_edges
    from openr_tpu.types import PrefixEntry

    seed = 7 if seed is None else seed
    side = 64  # grid4096: the ROADMAP's canonical scale point
    edges = grid_edges(side)
    adj_dbs = build_adj_dbs(edges)
    ls = LinkState("0", "node0")
    for db in adj_dbs.values():
        ls.update_adjacency_database(db)
    n_nodes = side * side
    ps = PrefixState()
    for i in range(n_nodes):
        ps.update_prefix(
            f"node{i}",
            "0",
            PrefixEntry(f"10.{(i >> 8) & 0xFF}.{i & 0xFF}.0/24"),
        )
    als = {"0": ls}
    rng = np.random.default_rng(seed)

    def make_backend(warm: bool) -> TpuBackend:
        return TpuBackend(
            SpfSolver("node0"),
            min_device_prefixes=0,
            clock=WallClock(),
            counters=CounterMap(),
            resilience=ResilienceConfig(enabled=False),
            parallel=ParallelConfig(max_devices=1, min_shard_rows=0),
            warm_rebuild=warm,
        )

    def norm_db(db):
        return {
            p: (
                sorted(
                    (nh.neighbor_node_name, nh.metric) for nh in e.nexthops
                ),
                float(e.igp_cost),
            )
            for p, e in db.unicast_routes.items()
        }

    # ---- part A: the generation sweep --------------------------------
    warm_be = make_backend(True)
    cold_be = make_backend(False)
    prev_warm = warm_be.build_route_db(als, ps, force_full=True)
    prev_cold = cold_be.build_route_db(als, ps, force_full=True)
    # one unmeasured perturbation warms every jit shape both sides use
    node_names = sorted(adj_dbs)

    def perturb(step: int) -> None:
        victim = node_names[int(rng.integers(len(node_names)))]
        db = adj_dbs[victim]
        a = db.adjacencies[int(rng.integers(len(db.adjacencies)))]
        a.metric = 1 + (a.metric % 3)  # cycles 1→2→3→1: always a change
        ls.update_adjacency_database(db)

    # unmeasured warm-up perturbations: compile the warm kernels' shape
    # buckets (sub-edge + gathered-selection) before the timed window
    for step in range(-4, 0):
        perturb(step)
        warm_be.build_route_db(
            als, ps, changed_prefixes=set(), force_full=True,
            warm_delta=True,
        )
        warm_be.take_last_changed_prefixes()
        cold_be.build_route_db(
            als, ps, changed_prefixes=set(), force_full=True
        )
    w0, s0 = warm_be.num_warm_builds, warm_be.num_warm_selective_builds
    f0, p0 = warm_be.num_warm_cold_fallbacks, warm_be.num_warm_purges
    e0 = warm_be.num_encode_patches
    warm_lat, cold_lat = [], []
    parity_checks = 0
    parity_ok = True
    depths, rounds = [], []
    for gen in range(WARMSTART_GENERATIONS):
        perturb(gen)
        t0 = time.perf_counter()
        db_w = warm_be.build_route_db(
            als,
            ps,
            changed_prefixes=set(),
            force_full=True,
            warm_delta=True,
        )
        changed = warm_be.take_last_changed_prefixes()
        if changed is not None:
            update = prev_warm.calculate_update_for(db_w, changed)
        else:
            update = prev_warm.calculate_update(db_w)
        warm_lat.append((time.perf_counter() - t0) * 1000.0)
        prev_warm = db_w
        depths.append(warm_be.warm_last_est_depth)
        rounds.append(warm_be.warm_last_rounds)
        t0 = time.perf_counter()
        db_c = cold_be.build_route_db(
            als, ps, changed_prefixes=set(), force_full=True
        )
        cold_update = prev_cold.calculate_update(db_c)
        cold_lat.append((time.perf_counter() - t0) * 1000.0)
        prev_cold = db_c
        # the two engines must agree on WHAT changed, not just the state
        assert set(update.unicast_routes_to_update) <= set(
            db_w.unicast_routes
        )
        if gen % WARMSTART_PARITY_EVERY == 0:
            parity_checks += 1
            scalar = SpfSolver("node0").build_route_db(als, ps)
            parity_ok = parity_ok and (
                norm_db(db_w) == norm_db(db_c) == norm_db(scalar)
            )
        print(
            f"# gen {gen}: warm {warm_lat[-1]:.1f}ms "
            f"(depth {depths[-1]}, rounds {rounds[-1]}, "
            f"changed {len(changed) if changed is not None else 'all'}) "
            f"cold {cold_lat[-1]:.1f}ms",
            file=sys.stderr,
        )

    def pct(lat, q):
        srt = sorted(lat)
        return srt[min(len(srt) - 1, int(len(srt) * q))]

    warm_p50, cold_p50 = pct(warm_lat, 0.5), pct(cold_lat, 0.5)

    # ---- part B: the repair-sweep comparison -------------------------
    from openr_tpu.ops.csr import encode_link_state
    from openr_tpu.ops.repair import sort_by_depth
    from openr_tpu.ops.spf import sweep_spf_link_failures
    from openr_tpu.ops.whatif import LinkFailureSweep

    topo = encode_link_state(ls)
    eng = LinkFailureSweep(topo, "node0")
    eng.base_solve()
    plan = eng.plan()
    rs = eng.repair_sweep()
    g = rs.batch_granularity
    fails = rng.integers(
        0, len(topo.links), size=WARMSTART_SWEEP_WARM
    ).astype(np.int32)
    sfails, _ = sort_by_depth(plan, fails)
    chunk = 1024

    def warm_sweep_once():
        outs = []
        for off in range(0, len(sfails), chunk):
            c = sfails[off : off + chunk]
            if len(c) % g:
                c = np.concatenate(
                    [c, np.full(g - len(c) % g, -1, np.int32)]
                )
            outs.append(rs.solve(c))
        return outs

    jax.block_until_ready(warm_sweep_once())  # compile warm-up
    t0 = time.perf_counter()
    jax.block_until_ready(warm_sweep_once())
    device_warm_sps = WARMSTART_SWEEP_WARM / (time.perf_counter() - t0)

    cold_args = (
        jnp.asarray(topo.src),
        jnp.asarray(topo.dst),
        jnp.asarray(topo.w),
        jnp.asarray(topo.edge_ok),
        jnp.asarray(topo.link_index),
    )
    ovl = jnp.asarray(topo.overloaded)
    root = jnp.int32(topo.node_id("node0"))
    cold_fails = fails[:WARMSTART_SWEEP_COLD]

    def cold_sweep_once():
        return sweep_spf_link_failures(
            *cold_args,
            jnp.asarray(cold_fails),
            ovl,
            root,
            max_degree=topo.max_out_degree(),
            packed=False,
        )

    jax.block_until_ready(cold_sweep_once())
    t0 = time.perf_counter()
    jax.block_until_ready(cold_sweep_once())
    device_cold_sps = WARMSTART_SWEEP_COLD / (time.perf_counter() - t0)

    from openr_tpu.ops.native_spf import NativeSpf

    native = NativeSpf(topo, "node0")
    native.warm_prepare()
    native.warm_sweep(fails[:32])
    t0 = time.perf_counter()
    native.warm_sweep(fails)
    native_warm_sps = WARMSTART_SWEEP_WARM / (time.perf_counter() - t0)

    env = env_stamp()
    doc = {
        "metric": "warmstart_rebuild_p50_publication_to_fib_ms_grid4096",
        "value": round(warm_p50, 3),
        "unit": "ms",
        "vs_baseline": round(cold_p50 / warm_p50, 2),
        "detail": {
            "rebuild": {
                "warm_p50_ms": round(warm_p50, 3),
                "warm_p95_ms": round(pct(warm_lat, 0.95), 3),
                "warm_max_ms": round(max(warm_lat), 3),
                "cold_p50_ms": round(cold_p50, 3),
                "cold_p95_ms": round(pct(cold_lat, 0.95), 3),
                "speedup_vs_cold": round(cold_p50 / warm_p50, 2),
                "generations": WARMSTART_GENERATIONS,
                "warm_hits": warm_be.num_warm_builds - w0,
                "warm_selective_builds": (
                    warm_be.num_warm_selective_builds - s0
                ),
                "cold_fallbacks": warm_be.num_warm_cold_fallbacks - f0,
                "warm_purges": warm_be.num_warm_purges - p0,
                "encode_patches": warm_be.num_encode_patches - e0,
                "est_depth_max": max(depths),
                "warm_rounds_max": max(r for pair in rounds for r in pair),
                "parity_checks": parity_checks,
                "parity_ok": parity_ok,
                "reference_cold_p50_ms_r05": (
                    WARMSTART_COLD_P50_REFERENCE_MS
                ),
                "reference_note": (
                    "BENCH_SUITE_p50_r05.json grid4096 "
                    "p50_publication_to_fib_ms (TPU v5e capture, "
                    "2026-07-30); the in-run cold_p50_ms is the "
                    "same-host apples-to-apples denominator"
                ),
            },
            "sweep": {
                "device_warm_solves_per_sec": round(device_warm_sps, 1),
                "device_cold_solves_per_sec": round(device_cold_sps, 1),
                "native_warm_solves_per_sec": round(native_warm_sps, 1),
                "warm_vs_cold": round(
                    device_warm_sps / device_cold_sps, 2
                ),
                "warm_vs_native": round(
                    device_warm_sps / native_warm_sps, 3
                ),
                "warm_solves": WARMSTART_SWEEP_WARM,
                "cold_solves": WARMSTART_SWEEP_COLD,
                "native_reference_note": (
                    "this sweep re-measures BOTH engines on grid4096 in THIS environment.  On "
                    "platform=cpu the device kernel is host XLA sharing "
                    "the native baseline's silicon, so beating native "
                    "is only gated when a real accelerator is attached "
                    "(see validate_warmstart_bench)."
                ),
            },
            "world": {
                "nodes": n_nodes,
                "links": len(topo.links),
                "prefixes": n_nodes,
                "topology": f"grid{side}x{side}",
                "seed": seed,
            },
            "mode": (
                "emulate (in-process LSDB, WallClock backends; part A "
                "measures build_route_db(force_full, warm_delta) + the "
                "RouteDb diff Decision publishes, one random link-metric "
                "perturbation per generation; part B sweeps single-link "
                "failures through the repair kernel vs the cold kernel "
                "vs native C++ warm-start)"
            ),
            "env": env,
        },
    }
    validate_warmstart_bench(doc)
    print(json.dumps(doc))


#: topology classes the full --suite mode sweeps (the multi-area WAN
#: variant is exercised through per-area LSDB unit tests, not the
#: single-area protocol emulation)
SUITE_CLASSES = ("grid", "fattree_multipod", "wan_hierarchy")
SUITE_FULL_SCALE = 1024
SUITE_MIN_FULL_NODES = 1000
SUITE_SMOKE_SCALE = 256
SUITE_FLAPS = 6
SUITE_DRAINS = 2
SUITE_ANCHORS = 8
SUITE_SEED = 7


def validate_trajectory_bench(doc: dict) -> None:
    """Schema contract for BENCH_TRAJECTORY_r*.json — shared by the
    suite emitter, the tier-1 artifact gate, and the benchtrack
    manifest.  The headline value is the WORST per-class p50
    publication→FIB over the required topology classes at full scale;
    each class block must carry the 1k+-node floor, ordered
    percentiles, the warm-hit ratio, the per-class SLO verdict, full
    pipeline-phase shares, and the zero-unexpected-alerts assertion;
    the smoke block pins the tier-1 replay-determinism contract."""
    from openr_tpu.emulation.topology import TOPOLOGY_CLASSES

    assert doc["metric"] == "suite_worst_class_p50_publication_to_fib_ms"
    assert doc["unit"] == "ms_p50_virtual"
    d = doc["detail"]
    classes = d["classes"]
    assert set(SUITE_CLASSES) <= set(classes), (
        "the required topology classes must all be present"
    )
    for name, row in classes.items():
        assert name in TOPOLOGY_CLASSES, name
        assert row["nodes"] >= SUITE_MIN_FULL_NODES, (
            f"{name}: full-scale classes must be >= 1k nodes"
        )
        assert row["links"] > row["nodes"] * 0.9, name
        conv = row["convergence"]
        assert conv["samples"] > 0, name
        assert (
            0
            < conv["p50_ms"]
            <= conv["p95_ms"]
            <= conv["p99_ms"]
            <= conv["max_ms"]
        ), name
        w = row["warm"]
        assert w["hits"] >= 1, f"{name}: the flap sweep must warm-start"
        assert 0.0 <= w["hit_ratio"] <= 1.0, name
        slo = row["slo"]
        assert slo["convergence_slo_ms"] > 0, name
        assert slo["p99_within_slo"] is (
            conv["p99_ms"] <= slo["convergence_slo_ms"]
        ), name
        assert slo["p99_within_slo"], (
            f"{name}: p99 {conv['p99_ms']}ms blew the per-class SLO "
            f"{slo['convergence_slo_ms']}ms"
        )
        shares = row["pipeline_phase_share_pct"]
        assert shares, f"{name}: observer pipeline shares missing"
        assert abs(sum(shares.values()) - 100.0) < 1.0, name
        alerts = row["alerts"]
        assert alerts["unexpected"] == 0, (
            f"{name}: unexpected health alerts fired: {alerts}"
        )
        assert row["flaps"] >= 4 and row["drains"] >= 1, name
        assert row["observer"], name
    worst = max(
        classes[c]["convergence"]["p50_ms"] for c in SUITE_CLASSES
    )
    assert doc["value"] == worst
    smoke = d["smoke"]
    assert smoke["nodes"] <= SUITE_SMOKE_SCALE
    assert smoke["convergence"]["samples"] > 0
    assert d["deterministic_replay"] is True
    for key in ("seed", "mode", "env"):
        assert key in d, key
    for key in ("platform", "jax", "device_count"):
        assert key in d["env"], f"env.{key}"


def _class_phase_shares(edges, root: str, prefixes: int = 64) -> dict:
    """Wall-clock pipeline-phase shares for one topology class: one
    cold full device rebuild plus one warm perturbation tick of the
    class LSDB through a WallClock-probed TpuBackend.

    The emulation observer's probe rides the SimClock, where a
    synchronous build spans ZERO virtual ms — phase *time shares* are a
    wall-clock concept, so they come from this shadow build over the
    identical topology (compile excluded; shares recorded, absolute ms
    deliberately not: they are environment-bound)."""
    from openr_tpu.common.runtime import CounterMap, WallClock
    from openr_tpu.config import ParallelConfig, ResilienceConfig
    from openr_tpu.decision.backend import TpuBackend
    from openr_tpu.decision.link_state import LinkState
    from openr_tpu.decision.prefix_state import PrefixState
    from openr_tpu.decision.spf_solver import SpfSolver
    from openr_tpu.emulation.topology import build_adj_dbs, topology_nodes
    from openr_tpu.tracing import pipeline
    from openr_tpu.types import PrefixEntry

    adj_dbs = build_adj_dbs(edges)
    ls = LinkState("0", root)
    for db in adj_dbs.values():
        ls.update_adjacency_database(db)
    names = topology_nodes(edges)
    ps = PrefixState()
    step = max(1, len(names) // prefixes)
    for i, n in enumerate(names[::step][:prefixes]):
        ps.update_prefix(
            n, "0", PrefixEntry(f"10.{220 + i // 256}.{i % 256}.0/24")
        )
    als = {"0": ls}
    counters = CounterMap()
    backend = TpuBackend(
        SpfSolver(root),
        min_device_prefixes=0,
        clock=WallClock(),
        counters=counters,
        resilience=ResilienceConfig(enabled=False),
        parallel=ParallelConfig(max_devices=1, min_shard_rows=0),
        warm_rebuild=True,
    )
    backend.build_route_db(als, ps, force_full=True)  # compile, unmeasured

    def totals():
        out = {}
        for phase in pipeline.PHASES:
            h = counters.histogram(pipeline.hist_key(phase))
            if h is not None:
                out[phase] = h.total
        return out

    t0 = totals()
    flip = adj_dbs[root].adjacencies[0]
    flip.metric += 1
    ls.update_adjacency_database(adj_dbs[root])
    backend.build_route_db(
        als, ps, changed_prefixes=set(), force_full=True
    )  # the cold lifecycle
    flip.metric += 1
    ls.update_adjacency_database(adj_dbs[root])
    backend.build_route_db(
        als, ps, changed_prefixes=set(), force_full=True, warm_delta=True
    )  # the warm generation-delta tick
    t1 = totals()
    deltas = {
        k: t1.get(k, 0.0) - t0.get(k, 0.0)
        for k in t1
        if t1.get(k, 0.0) - t0.get(k, 0.0) > 0.0
    }
    attributed = sum(deltas.values())
    if not attributed:
        return {}
    return {
        k: round(v / attributed * 100.0, 2)
        for k, v in sorted(deltas.items())
    }


def suite_sweep_class(
    cls_name: str,
    scale: int,
    seed: int,
    flaps: int = SUITE_FLAPS,
    drains: int = SUITE_DRAINS,
    phase_shares: bool = True,
):
    """One topology class's seeded chaos flap/drain sweep through the
    protocol emulation under SimClock.

    Shape: the whole class-scale fleet runs complete OpenrNodes on the
    scalar decision path; ONE observer node (the sorted-first name)
    runs the device backend with warm rebuild and the fleet-health
    aggregator with the class's per-topology SLO catalog — a thousand
    jitted backends in one process would measure the harness, not the
    system, while one observer yields the warm-hit / pipeline-phase /
    alert surfaces the trajectory records.  ``SUITE_ANCHORS`` anchor
    prefixes (not full-mesh loopbacks) keep the route plane
    proportional to the control-plane story being measured.

    Returns ``(detail, fingerprint)``: the per-class artifact block and
    the replay-comparable bytes (alert JSONL + chaos counter dump +
    convergence histogram buckets) — two runs from one seed must match
    byte for byte."""
    import asyncio
    import random as _random
    import zlib

    from openr_tpu.chaos import ChaosController, FaultPlan
    from openr_tpu.common.runtime import SimClock
    from openr_tpu.config import SloSpecConfig
    from openr_tpu.emulation.network import EmulatedNetwork
    from openr_tpu.emulation.topology import (
        TOPOLOGY_CLASSES,
        topology_nodes,
    )
    from openr_tpu.health.slo import slos_for_topology_class
    from openr_tpu.types import PrefixEntry

    row = TOPOLOGY_CLASSES[cls_name]
    edges = row.build(scale, seed)
    names = topology_nodes(edges)
    observer = names[0]
    rng = _random.Random(zlib.crc32(cls_name.encode()) ^ (seed * 2654435761))
    anchors = sorted(rng.sample(names, min(SUITE_ANCHORS, len(names))))
    anchor_prefix = {
        a: f"10.210.{i}.0/24" for i, a in enumerate(anchors)
    }
    slo_specs = slos_for_topology_class(cls_name)

    def overrides(cfg):
        is_obs = cfg.node_name == observer
        cfg.tpu_compute_config.enable_tpu_spf = is_obs
        if is_obs:
            cfg.tpu_compute_config.min_device_prefixes = 0
        hc = cfg.health_config
        hc.enabled = is_obs
        hc.sweep_interval_s = 5.0
        hc.slos = [
            SloSpecConfig(
                name=s.name,
                metric=s.metric,
                kind=s.kind,
                percentile=s.percentile,
                threshold=s.threshold,
                objective=s.objective,
                fast_window_s=s.fast_window_s,
                slow_window_s=s.slow_window_s,
                burn_threshold=s.burn_threshold,
            )
            for s in slo_specs
        ]
        cfg.tracing_config.flight_recorder = is_obs

    async def run():
        clock = SimClock()
        net = EmulatedNetwork(
            clock, use_tpu_backend=None, config_overrides=overrides
        )
        net.build(edges)
        net.start(advertise_loopbacks=False)
        for a in anchors:
            net.nodes[a].advertise_prefixes([PrefixEntry(anchor_prefix[a])])
        all_prefixes = set(anchor_prefix.values())

        def anchors_routed():
            for name, node in net.nodes.items():
                want = all_prefixes - {anchor_prefix.get(name)}
                if want - set(net.fib_routes(name)):
                    return False
            return True

        converged = False
        for _ in range(30):
            await clock.run_for(4.0)
            if anchors_routed():
                converged = True
                break
        assert converged, f"{cls_name}@{scale}: anchors never converged"

        # baseline reset: only chaos-driven convergence is scored.  The
        # incarnation stamp survives the wipe (a reset start_ms would
        # read as a crash to the health plane's latch).
        for node in net.nodes.values():
            start_ms = node.counters.get("node.start_ms")
            node.counters.clear()
            node.counters.set("node.start_ms", start_ms)
        obs = net.nodes[observer]
        be = obs.decision.backend
        w0 = be.num_warm_builds
        s0 = be.num_warm_selective_builds
        f0 = be.num_warm_cold_fallbacks
        p0 = be.num_warm_purges
        t_mark_ms = clock.now_ms()

        links = sorted({tuple(sorted((a, b))) for a, b, _m in edges})
        flap_links = rng.sample(links, min(flaps, len(links)))
        plan = FaultPlan()
        t = 2.0
        for a, b in flap_links:
            plan.link_down(a, b, at=t, duration=4.0)
            t += 8.0
        controller = ChaosController(net, plan, seed=seed)
        controller.start()
        drain_pool = [
            n for n in names if n != observer and n not in anchors
        ]
        drain_nodes = rng.sample(drain_pool, min(drains, len(drain_pool)))
        step_s = 2.0
        steps = int((plan.horizon_s() + 4.0) / step_s) + 1
        # soft-drain flips ride the flap window: drain i raises its
        # node metric at step 2+3i and clears it three steps later —
        # both edges are pure perturbation ticks for the warm path
        drain_sched = {}
        for i, dn in enumerate(drain_nodes):
            on = 2 + 3 * i
            drain_sched.setdefault(on, []).append((dn, 100))
            drain_sched.setdefault(on + 3, []).append((dn, 0))
        for step in range(steps):
            for dn, inc in drain_sched.get(step, ()):
                net.nodes[dn].link_monitor.set_node_metric_increment(inc)
            await clock.run_for(step_s)
        for dn in drain_nodes:
            net.nodes[dn].link_monitor.set_node_metric_increment(0)
        await clock.run_for(12.0)
        assert anchors_routed(), (
            f"{cls_name}@{scale}: anchors lost after the sweep healed"
        )

        conv = net.merged_histogram("convergence.event_to_fib_ms")
        assert conv is not None and conv.count > 0, (
            f"{cls_name}@{scale}: no convergence samples in the window"
        )
        pct = conv.percentiles()

        warm_hits = be.num_warm_builds - w0
        fallbacks = be.num_warm_cold_fallbacks - f0

        health = obs.health
        fired_after_mark = []
        if health is not None:
            for line in health.alert_log():
                e = json.loads(line)
                if e["event"] == "fired" and e["ts_ms"] >= t_mark_ms:
                    fired_after_mark.append(e["name"])
        # a flap/drain sweep on a path-redundant class must fire NO
        # alerts: no partitions, no corruption, no crashes, and the
        # per-class convergence SLO holds
        unexpected = sorted(fired_after_mark)

        detail = {
            "topology_class": cls_name,
            "scale": scale,
            "nodes": len(names),
            "links": len(links),
            "seed": seed,
            "observer": observer,
            "anchors": len(anchors),
            "flaps": len(flap_links),
            "drains": len(drain_nodes),
            "virtual_s": round(clock.now(), 1),
            "convergence": {
                "p50_ms": round(pct["p50"], 2),
                "p95_ms": round(pct["p95"], 2),
                "p99_ms": round(pct["p99"], 2),
                "max_ms": round(conv.vmax, 2),
                "samples": conv.count,
            },
            "warm": {
                "hits": warm_hits,
                "selective_builds": be.num_warm_selective_builds - s0,
                "cold_fallbacks": fallbacks,
                "purges": be.num_warm_purges - p0,
                "hit_ratio": round(
                    warm_hits / max(1, warm_hits + fallbacks), 3
                ),
            },
            "alerts": {
                "fired": len(fired_after_mark),
                "unexpected": len(unexpected),
                "unexpected_names": unexpected,
                "health_sweeps": (
                    health.num_sweeps if health is not None else 0
                ),
            },
            "slo": {
                "convergence_slo_ms": row.convergence_slo_ms,
                "p99_within_slo": (
                    round(pct["p99"], 2) <= row.convergence_slo_ms
                ),
            },
        }
        fingerprint = b"\n".join(
            [
                health.sink.log_bytes() if health is not None else b"",
                json.dumps(
                    controller.counter_dump(), sort_keys=True
                ).encode(),
                json.dumps(
                    sorted(conv.bucket_items()), sort_keys=True
                ).encode(),
            ]
        )
        await controller.stop()
        await net.stop()
        return detail, fingerprint

    loop = asyncio.new_event_loop()
    try:
        detail, fingerprint = loop.run_until_complete(run())
    finally:
        loop.close()
    # wall-clock phase shares ride OUTSIDE the deterministic emulation
    # (and outside the fingerprint): shares are a wall-time concept
    detail["pipeline_phase_share_pct"] = (
        _class_phase_shares(edges, observer) if phase_shares else {}
    )
    return detail, fingerprint


def suite_main(seed: Optional[int] = None) -> None:
    """Trajectory suite benchmark (BENCH_TRAJECTORY_r*): per topology
    class at full scale (1k+ nodes), a seeded chaos flap/drain sweep
    through the SimClock protocol emulation, harvesting the
    publication→FIB percentile trajectory, observer warm-hit ratio,
    pipeline phase shares, and the zero-unexpected-alerts assertion;
    plus the 256-node smoke replayed twice to pin byte-identical
    determinism (the same contract tier-1 re-proves live).  Emits one
    JSON line; `python -m openr_tpu.benchtrack` reads the result into
    the cross-round trajectory."""
    seed = SUITE_SEED if seed is None else seed
    classes = {}
    for cls in SUITE_CLASSES:
        t0 = time.time()
        detail, _fp = suite_sweep_class(cls, SUITE_FULL_SCALE, seed)
        detail["wall_s"] = round(time.time() - t0, 1)
        classes[cls] = detail
        print(
            f"# {cls}@{detail['nodes']}: p50 "
            f"{detail['convergence']['p50_ms']}ms p99 "
            f"{detail['convergence']['p99_ms']}ms warm-hit "
            f"{detail['warm']['hit_ratio']} "
            f"({detail['wall_s']}s wall)",
            file=sys.stderr,
        )
    d1, fp1 = suite_sweep_class(
        "grid", SUITE_SMOKE_SCALE, seed, phase_shares=False
    )
    _d2, fp2 = suite_sweep_class(
        "grid", SUITE_SMOKE_SCALE, seed, phase_shares=False
    )
    deterministic = fp1 == fp2
    worst = max(
        classes[c]["convergence"]["p50_ms"] for c in SUITE_CLASSES
    )
    doc = {
        "metric": "suite_worst_class_p50_publication_to_fib_ms",
        "value": worst,
        "unit": "ms_p50_virtual",
        "detail": {
            "classes": classes,
            "smoke": {
                "topology_class": "grid",
                "scale": SUITE_SMOKE_SCALE,
                "nodes": d1["nodes"],
                "convergence": d1["convergence"],
            },
            "deterministic_replay": deterministic,
            "seed": seed,
            "mode": (
                "emulate (SimClock, full OpenrNodes; scalar fleet + one "
                "device-backend observer with warm rebuild and the "
                "per-class SLO catalog; anchor prefixes, seeded "
                "link-flap + soft-drain chaos; virtual-ms percentiles, "
                "deterministic across hosts)"
            ),
            "env": env_stamp(),
        },
    }
    validate_trajectory_bench(doc)
    print(json.dumps(doc))


# ---------------------------------------------------------------------------
# rolling-restart survival (ISSUE 12): BENCH_ROLLING_r*
# ---------------------------------------------------------------------------

ROLLING_CLASS = "grid"
ROLLING_SCALE = 64
ROLLING_SMOKE_SCALE = 36
ROLLING_SEED = 11
ROLLING_DOWN_S = 5.0
ROLLING_SETTLE_S = 6.0


def validate_rolling_bench(doc: dict) -> None:
    """Schema contract for BENCH_ROLLING_r*.json — shared by the bench
    emitter, the tier-1 artifact gate and the benchtrack manifest.  The
    headline is the STRUCTURAL warm-hit ratio over a rolling-restart
    sweep (every non-observer node bounced exactly once through the
    supervisor's storm-guarded queue): before the slot-stable encode it
    was 0 by construction.  The publication→FIB percentiles must hold
    the per-class SLO for the whole upgrade, the health plane must stay
    silent, and the seeded smoke must replay byte-identically."""
    assert doc["metric"] == "rolling_restart_structural_warm_hit_ratio"
    assert doc["unit"] == "ratio"
    d = doc["detail"]
    assert d["topology_class"] == ROLLING_CLASS
    sweep = d["sweep"]
    # every node except the measurement observer bounces exactly once,
    # and the restart-storm guard keeps the fleet from going down at
    # once (default cap: 1 in-flight restart)
    assert sweep["nodes_bounced"] == d["nodes"] - 1
    assert sweep["restarts"] == sweep["nodes_bounced"]
    assert sweep["max_concurrent_observed"] == 1
    assert sweep["crashes"] == 0, "deliberate restarts must not latch"
    w = d["warm"]
    assert 0.0 <= w["structural_hit_ratio"] <= 1.0
    assert doc["value"] == w["structural_hit_ratio"]
    # each bounce produces at least one structural tick at the observer
    # (leave + rejoin, possibly debounce-coalesced)
    assert w["structural_hits"] >= sweep["nodes_bounced"]
    assert w["slot_patches"] >= w["structural_hits"]
    conv = d["convergence"]
    assert conv["samples"] > 0
    assert (
        0
        < conv["p50_ms"]
        <= conv["p95_ms"]
        <= conv["p99_ms"]
        <= conv["max_ms"]
    )
    slo = d["slo"]
    assert slo["convergence_slo_ms"] > 0
    assert slo["p99_within_slo"] is (
        conv["p99_ms"] <= slo["convergence_slo_ms"]
    )
    assert slo["p99_within_slo"], (
        f"p99 {conv['p99_ms']}ms blew the per-class SLO "
        f"{slo['convergence_slo_ms']}ms mid-upgrade"
    )
    alerts = d["alerts"]
    assert alerts["unexpected"] == 0, (
        f"unexpected health alerts fired during the upgrade: {alerts}"
    )
    assert d["serving"]["queries"] > 0, "the sweep must run under load"
    assert d["smoke"]["nodes"] <= ROLLING_SMOKE_SCALE
    assert d["deterministic_replay"] is True
    for key in ("seed", "mode", "env"):
        assert key in d, key
    for key in ("platform", "jax", "device_count"):
        assert key in d["env"], f"env.{key}"


def rolling_sweep_world(
    scale: int,
    seed: int,
    down_s: float = ROLLING_DOWN_S,
    settle_s: float = ROLLING_SETTLE_S,
):
    """One rolling-restart survival round through the SimClock protocol
    emulation: boot a grid-class fleet (scalar decision path + ONE
    device-backend observer carrying warm rebuild, the health plane and
    the per-class SLO catalog — the suite's shape), converge, then
    bounce every non-observer node exactly once via the supervisor's
    storm-guarded deliberate-restart queue, with a down window past the
    Spark hold timer (neighbors must really observe the leave) and a
    serving-query load riding the observer throughout.

    Returns ``(detail, fingerprint)`` — fingerprint covers the bounce
    log, the supervisor restart log, the health alert JSONL and the
    convergence histogram buckets: two runs from one seed must match
    byte for byte."""
    import asyncio
    import random as _random
    import zlib

    from openr_tpu.chaos import RollingRestartSweep, Supervisor
    from openr_tpu.common.runtime import SimClock
    from openr_tpu.config import SloSpecConfig
    from openr_tpu.emulation.network import EmulatedNetwork
    from openr_tpu.emulation.topology import (
        TOPOLOGY_CLASSES,
        topology_nodes,
    )
    from openr_tpu.health.slo import slos_for_topology_class
    from openr_tpu.types import PrefixEntry

    row = TOPOLOGY_CLASSES[ROLLING_CLASS]
    edges = row.build(scale, seed)
    names = topology_nodes(edges)
    observer = names[0]
    rng = _random.Random(
        zlib.crc32(b"rolling") ^ (seed * 2654435761)
    )
    anchors = sorted(rng.sample(names, min(SUITE_ANCHORS, len(names))))
    anchor_prefix = {a: f"10.212.{i}.0/24" for i, a in enumerate(anchors)}
    slo_specs = slos_for_topology_class(ROLLING_CLASS)

    def overrides(cfg):
        is_obs = cfg.node_name == observer
        cfg.tpu_compute_config.enable_tpu_spf = is_obs
        if is_obs:
            cfg.tpu_compute_config.min_device_prefixes = 0
        hc = cfg.health_config
        hc.enabled = is_obs
        hc.sweep_interval_s = 5.0
        hc.slos = [
            SloSpecConfig(
                name=s.name,
                metric=s.metric,
                kind=s.kind,
                percentile=s.percentile,
                threshold=s.threshold,
                objective=s.objective,
                fast_window_s=s.fast_window_s,
                slow_window_s=s.slow_window_s,
                burn_threshold=s.burn_threshold,
            )
            for s in slo_specs
        ]

    async def run():
        clock = SimClock()
        net = EmulatedNetwork(
            clock, use_tpu_backend=None, config_overrides=overrides
        )
        net.build(edges)
        net.start(advertise_loopbacks=False)
        for a in anchors:
            net.nodes[a].advertise_prefixes([PrefixEntry(anchor_prefix[a])])
        all_prefixes = set(anchor_prefix.values())

        def anchors_routed():
            for name, node in net.nodes.items():
                want = all_prefixes - {anchor_prefix.get(name)}
                if want - set(net.fib_routes(name)):
                    return False
            return True

        converged = False
        for _ in range(30):
            await clock.run_for(4.0)
            if anchors_routed():
                converged = True
                break
        assert converged, f"rolling@{scale}: anchors never converged"

        # baseline reset: only sweep-driven convergence is scored; the
        # incarnation stamp survives (a reset start_ms would read as a
        # crash to the health plane's latch)
        for node in net.nodes.values():
            start_ms = node.counters.get("node.start_ms")
            node.counters.clear()
            node.counters.set("node.start_ms", start_ms)
        obs = net.nodes[observer]
        be = obs.decision.backend
        w0 = be.warm_status()["by_class"]
        slot0 = be.num_encode_slot_patches
        purge0 = be.num_warm_purges
        t_mark_ms = clock.now_ms()

        supervisor = Supervisor(clock)

        async def restart_and_readvertise(name):
            # a production daemon re-reads its configured prefixes at
            # boot; the anchor advertisements are harness-owned config,
            # so the harness restores them on the replacement node
            node = await net.restart_node(name)
            if name in anchor_prefix:
                node.advertise_prefixes(
                    [PrefixEntry(anchor_prefix[name])]
                )
            return node

        sweep = RollingRestartSweep(
            net,
            supervisor,
            seed=seed,
            down_s=down_s,
            settle_s=settle_s,
            skip=(observer,),
            restart_fn=restart_and_readvertise,
        )
        serving_stats = {"queries": 0, "errors": 0}
        serving_alive = [True]

        async def serving_load():
            # "under serving load": a route_db query per tick against
            # the observer's serving plane, vantage rotating over the
            # anchors — rides the device fleet engine while the sweep
            # churns under it
            i = 0
            while serving_alive[0]:
                target = anchors[i % len(anchors)]
                try:
                    await obs.serving.submit(
                        "route_db", {"node": target}, client_id="bench"
                    )
                    serving_stats["queries"] += 1
                except Exception:  # noqa: BLE001 - shed/quota under churn
                    serving_stats["errors"] += 1
                i += 1
                await clock.sleep(3.0)

        load_task = asyncio.ensure_future(serving_load())
        sweep_task = asyncio.ensure_future(sweep.run())
        while not sweep_task.done():
            await clock.run_for(2.0)
        sweep_task.result()
        settled = False
        for _ in range(20):
            await clock.run_for(4.0)
            if anchors_routed():
                settled = True
                break
        serving_alive[0] = False
        await clock.run_for(4.0)
        load_task.cancel()
        assert settled, (
            f"rolling@{scale}: anchors lost after the upgrade completed"
        )

        # publication→FIB at the STABLE vantage (the observer): a
        # freshly reborn node's full sync re-delivers keys whose
        # embedded trace contexts join their ORIGINAL origin events
        # (PR-3 semantics), so its convergence samples measure key age,
        # not propagation — the upgrade's latency story is what the
        # surviving vantage experienced while the fleet churned under
        # it
        conv = obs.counters.histogram("convergence.event_to_fib_ms")
        assert conv is not None and conv.count > 0
        pct = conv.percentiles()

        w1 = be.warm_status()
        s_hits = (
            w1["by_class"]["structural"]["hits"] - w0["structural"]["hits"]
        )
        s_fb = (
            w1["by_class"]["structural"]["fallbacks"]
            - w0["structural"]["fallbacks"]
        )
        p_hits = (
            w1["by_class"]["perturbation"]["hits"]
            - w0["perturbation"]["hits"]
        )

        health = obs.health
        fired_after_mark = []
        if health is not None:
            for line in health.alert_log():
                e = json.loads(line)
                if e["event"] == "fired" and e["ts_ms"] >= t_mark_ms:
                    fired_after_mark.append(e["name"])
        unexpected = sorted(fired_after_mark)

        detail = {
            "topology_class": ROLLING_CLASS,
            "scale": scale,
            "nodes": len(names),
            "links": len({tuple(sorted((a, b))) for a, b, _m in edges}),
            "seed": seed,
            "observer": observer,
            "anchors": len(anchors),
            "virtual_s": round(clock.now(), 1),
            "sweep": {
                "nodes_bounced": sweep.num_bounced,
                "down_s": down_s,
                "settle_s": settle_s,
                "restarts": supervisor.num_restarts,
                "requested": supervisor.num_requested_restarts,
                "crashes": supervisor.num_crashes,
                "max_concurrent_observed": (
                    supervisor.max_observed_concurrency
                ),
            },
            "warm": {
                "structural_hits": s_hits,
                "structural_fallbacks": s_fb,
                "structural_hit_ratio": round(
                    s_hits / max(1, s_hits + s_fb), 3
                ),
                "perturbation_hits": p_hits,
                "slot_patches": be.num_encode_slot_patches - slot0,
                "slot_declines": w1["slot_declines"],
                "purges": be.num_warm_purges - purge0,
            },
            "convergence": {
                "vantage": observer,
                "p50_ms": round(pct["p50"], 2),
                "p95_ms": round(pct["p95"], 2),
                "p99_ms": round(pct["p99"], 2),
                "max_ms": round(conv.vmax, 2),
                "samples": conv.count,
            },
            "slo": {
                "convergence_slo_ms": row.convergence_slo_ms,
                "p99_within_slo": (
                    round(pct["p99"], 2) <= row.convergence_slo_ms
                ),
            },
            "alerts": {
                "fired": len(fired_after_mark),
                "unexpected": len(unexpected),
                "unexpected_names": unexpected,
                "health_sweeps": (
                    health.num_sweeps if health is not None else 0
                ),
            },
            "serving": dict(serving_stats),
        }
        fingerprint = b"\n".join(
            [
                sweep.fingerprint(),
                health.sink.log_bytes() if health is not None else b"",
                json.dumps(
                    sorted(conv.bucket_items()), sort_keys=True
                ).encode(),
            ]
        )
        await net.stop()
        return detail, fingerprint

    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(run())
    finally:
        loop.close()


def rolling_main(seed: Optional[int] = None) -> None:
    """Rolling-restart survival benchmark (BENCH_ROLLING_r*): bounce
    every non-observer node of a grid-class fleet exactly once through
    the supervisor's storm-guarded queue, under serving load, and prove
    the system never goes cold — structural warm-hit ratio as the
    headline (0 before the slot-stable encode), publication→FIB p99
    held within the per-class SLO for the entire upgrade, zero health
    alerts, and the seeded smoke replayed twice for byte-identical
    determinism.  Emits one JSON line."""
    seed = ROLLING_SEED if seed is None else seed
    t0 = time.time()
    detail, _fp = rolling_sweep_world(ROLLING_SCALE, seed)
    detail["wall_s"] = round(time.time() - t0, 1)
    print(
        f"# rolling grid@{detail['nodes']}: bounced "
        f"{detail['sweep']['nodes_bounced']} structural warm-hit "
        f"{detail['warm']['structural_hit_ratio']} p99 "
        f"{detail['convergence']['p99_ms']}ms ({detail['wall_s']}s wall)",
        file=sys.stderr,
    )
    d1, fp1 = rolling_sweep_world(ROLLING_SMOKE_SCALE, seed)
    _d2, fp2 = rolling_sweep_world(ROLLING_SMOKE_SCALE, seed)
    doc = {
        "metric": "rolling_restart_structural_warm_hit_ratio",
        "value": detail["warm"]["structural_hit_ratio"],
        "unit": "ratio",
        "detail": {
            **detail,
            "smoke": {
                "scale": ROLLING_SMOKE_SCALE,
                "nodes": d1["nodes"],
                "nodes_bounced": d1["sweep"]["nodes_bounced"],
                "structural_hit_ratio": (
                    d1["warm"]["structural_hit_ratio"]
                ),
                "convergence": d1["convergence"],
            },
            "deterministic_replay": fp1 == fp2,
            "mode": (
                "emulate (SimClock, full OpenrNodes; scalar fleet + one "
                "device-backend observer with warm rebuild, health plane "
                "and per-class SLOs; every non-observer node bounced "
                "once via the supervisor's storm-guarded queue, down "
                "window past the Spark hold timer, serving load riding "
                "the observer; virtual-ms percentiles.  Class params "
                "derive from --scale: the 1k-node rerun of this sweep "
                "is owed on faster iron — wall cost scales ~N^2 in the "
                "in-process emulation)"
            ),
            "env": env_stamp(),
        },
    }
    validate_rolling_bench(doc)
    print(json.dumps(doc))


# ===========================================================================
# --streaming: snapshot+delta fan-out at 10k+ subscribers (ISSUE 13)
# ===========================================================================

STREAMING_SEED = 11
STREAMING_SUBS = 10_000
STREAMING_CHURN_PER_TICK = 64
STREAMING_TICKS = 24
STREAMING_SMOKE_SUBS = 64
STREAMING_SMOKE_TICKS = 12
#: pull-mode cohort left undrained until the end: their 16-deep queues
#: overflow over the tick run, proving shed_oldest-to-resync escalation
STREAMING_OVERFLOW_COHORT = 32


def validate_streaming_bench(doc: dict) -> None:
    """Schema contract for BENCH_STREAMING_r*.json — shared by the
    bench emitter, the tier-1 artifact gate and the benchtrack
    manifest.  The headline is wall-clock fan-out throughput (delivered
    emissions/s) over a 10k+ subscriber churn sweep under seeded chaos
    (partition/heal mid-sweep); generation correctness is gated hard:
    zero monotone-invariant violations, the stalled subscriber's single
    merged delta reproducing the live db, no pre-partition generation
    ever emitted, zero unexpected alerts, byte-identical seeded
    replays."""
    assert doc["metric"] == "streaming_fanout_emissions_per_sec"
    assert doc["unit"] == "emissions/s"
    d = doc["detail"]
    subs = d["subscribers"]
    assert subs["peak"] >= 10_000, "the sweep must reach 10k+ subscribers"
    assert subs["churned"] > 0
    fan = d["fanout"]
    assert fan["emissions"] > 0 and fan["wall_s"] > 0
    assert doc["value"] == fan["emissions_per_sec"] > 0
    # the emissions/s regression guard (ISSUE-14 satellite): the
    # shared-wire-encode fan-out loop must never regress to an
    # order-of-magnitude-slower per-subscriber rebuild path.  An
    # absolute floor (r01 measured ~69k/s on this class of host; the
    # benchtrack ratchet holds the fine-grained line)
    assert fan["emissions_per_sec"] >= 5_000, (
        "fan-out throughput collapsed an order of magnitude"
    )
    if "shared_encode" in fan:
        # emitted from the shared-wire-encode era on: the delta body
        # must be rendered once per feed entry, shared across the
        # subscriber fan-out
        se = fan["shared_encode"]
        assert se["shared_payloads"] > se["rendered_payloads"] > 0
    assert fan["deltas"] > 0 and fan["snapshots"] > 0
    st = d["staleness_ms"]
    assert st["samples"] > 0
    assert 0 <= st["p50"] <= st["p95"] <= st["p99"] <= st["max"]
    rs = d["resyncs"]
    assert rs["count"] >= 1, "the overflow cohort must have resynced"
    assert rs["overflow_cohort_resynced"] >= 1
    assert 0.0 <= rs["rate"] <= 1.0
    assert rs["shed_deltas"] >= 1
    md = d["merged_delta"]
    assert md["skipped_generations"] >= 3
    assert md["emissions"] == 1, "one merged delta, never a replay of N"
    assert md["kind_ok"] is True, "the merged window must be ONE delta"
    assert md["parity"] is True
    part = d["partition"]
    assert part["post_heal_emissions"] > 0
    assert part["pre_partition_generation_emissions"] == 0
    assert d["invariant_violations"] == 0
    assert d["alerts"]["unexpected"] == 0, d["alerts"]
    assert d["smoke"]["subscribers"] == STREAMING_SMOKE_SUBS
    assert d["deterministic_replay"] is True
    for key in ("seed", "mode", "env"):
        assert key in d, key
    for key in ("platform", "jax", "device_count"):
        assert key in d["env"], f"env.{key}"


def streaming_fanout_world(n_subs: int, seed: int, ticks: int):
    """One watch-plane fan-out round through the SimClock protocol
    emulation: a 9-node grid converges, node0's StreamingService takes
    ``n_subs`` push subscribers (vantages rotating over the other 8
    nodes, a quarter of them prefix-filtered) plus a pull-mode overflow
    cohort and one deliberately stalled probe, then a seeded churn
    sweep drives ``ticks`` generations (prefix churn + a mid-sweep
    partition/heal of node8) while subscribers attach/detach each tick.

    Returns ``(detail, fingerprint)`` — the fingerprint covers the
    probe subscribers' full emission logs and every node's alert JSONL:
    two runs from one seed must match byte for byte."""
    import asyncio
    import random as _random
    import zlib

    from openr_tpu.common.runtime import SimClock
    from openr_tpu.emulation.network import EmulatedNetwork
    from openr_tpu.emulation.topology import grid_edges
    from openr_tpu.serving import apply_emission
    from openr_tpu.types import PrefixEntry

    rng = _random.Random(zlib.crc32(b"streaming") ^ (seed * 2654435761))

    def overrides(cfg):
        s = cfg.serving_config
        s.stream_publish_min_ms = 5
        s.stream_publish_max_ms = 20
        # shallow queues so the never-drained overflow cohort provably
        # escalates to resync within the tick budget
        s.stream_queue_depth = 8
        s.quota_tokens = 50
        s.quota_refill_per_s = 100.0
        # pull-mode cohorts are drained at the END of the sweep; the
        # stall detacher must not reap them mid-measurement
        s.stream_stall_detach_s = 300.0

    def canon_rows(rows) -> str:
        return json.dumps(
            {"|".join(map(str, k)): v for k, v in rows.items()},
            sort_keys=True,
            default=str,
        )

    async def run():
        clock = SimClock()
        net = EmulatedNetwork(clock, config_overrides=overrides)
        net.build(grid_edges(3))
        net.start()
        for _ in range(10):
            await clock.run_for(4.0)
            if net.converged_full_mesh()[0]:
                break
        ok, why = net.converged_full_mesh()
        assert ok, why

        n0 = net.nodes["node0"]
        st = n0.streaming
        vantages = [f"node{i}" for i in range(1, 9)]

        delivered = [0]
        monotone_regressions = [0]
        pre_partition_emissions = [0]
        post_heal_emissions = [0]
        partition_seq = [None]
        healed_at_emission = [None]

        def make_deliver(record: Optional[list] = None):
            state = {"last": -1}

            def deliver(e):
                delivered[0] += 1
                if e["seq"] < state["last"]:
                    monotone_regressions[0] += 1
                state["last"] = e["seq"]
                if (
                    partition_seq[0] is not None
                    and e["seq"] <= partition_seq[0]
                ):
                    pre_partition_emissions[0] += 1
                if healed_at_emission[0] is not None:
                    post_heal_emissions[0] += 1
                if record is not None:
                    record.append(e)

            return deliver

        live: list = []  # (sub_id, client) attach order, churn pool
        attached_total = 0

        def attach_one(i: int, record: Optional[list] = None):
            nonlocal attached_total
            filters = ("10.220.",) if i % 4 == 0 else ()
            sid = st.subscribe(
                "route_db",
                {"node": vantages[i % len(vantages)]},
                client_id=f"w{i}",
                prefix_filters=filters,
                deliver=make_deliver(record),
            )
            live.append((sid, f"w{i}"))
            attached_total += 1
            return sid

        # probe subscribers: full emission logs (the determinism
        # fingerprint) + applied-state parity at the end
        probe_logs = [[] for _ in range(4)]
        probe_ids = [
            attach_one(i, record=probe_logs[i]) for i in range(4)
        ]
        for i in range(4, n_subs):
            attach_one(i)
        # pull-mode cohorts: the overflow cohort never polls until the
        # end; the stalled probe polls exactly once after skipping >= 3
        # generations
        overflow_ids = [
            st.subscribe(
                "route_db",
                {"node": vantages[i % len(vantages)]},
                client_id=f"ov{i}",
            )
            for i in range(STREAMING_OVERFLOW_COHORT)
        ]
        stalled_id = st.subscribe(
            "route_db", {"node": "node3"}, client_id="stalled"
        )

        async def poll1(sid, hold=0.1):
            # SimClock discipline: the poll must park on a task while
            # run_for advances virtual time
            t = asyncio.ensure_future(st.next_emission(sid, hold_s=hold))
            await clock.run_for(max(hold * 4, 0.5))
            return t.result()

        stalled_snap = await poll1(stalled_id)
        assert stalled_snap["type"] == "snapshot"
        stalled_state = apply_emission({}, stalled_snap)
        stalled_cursor = stalled_snap["seq"]
        # prime the overflow cohort's cursors (first contact = the
        # subscribe snapshot); they never drain again until the end
        for sid in overflow_ids:
            e = await poll1(sid)
            assert e["type"] == "snapshot"
        merged_stats = {}

        peak = len(st._subs)
        churned = 0
        side_a = [f"node{i}" for i in range(8)]
        t0 = time.time()
        for tick in range(ticks):
            n0.advertise_prefixes([PrefixEntry(f"10.220.{tick}.0/24")])
            await clock.run_for(1.0)
            if tick == ticks // 3:
                # mid-sweep partition: node8's hold-timer leave is a
                # structural (full-window) generation at node0
                partition_seq[0] = n0.decision.generation_key()[0]
                net.partition(side_a, ["node8"])
                await clock.run_for(4.0)
            if tick == (2 * ticks) // 3:
                net.heal_partition(side_a, ["node8"])
                await clock.run_for(8.0)
                healed_at_emission[0] = delivered[0]
            if tick == 5:
                # the stalled probe drains once mid-sweep, BEFORE its
                # queue overflows: >= 3 skipped generations must fold
                # into exactly ONE merged delta reproducing live
                skipped = (
                    n0.decision.generation_key()[0] - stalled_cursor
                )
                merged = await poll1(stalled_id)
                emitted = 0
                if merged is not None:
                    emitted = 1
                    stalled_state = apply_emission(stalled_state, merged)
                more = await poll1(stalled_id)
                _g, live_db = n0.serving.snapshot_for(
                    "route_db", {"node": "node3"}
                )
                want = {
                    ("u", r["dest"]): r
                    for r in live_db["unicast_routes"]
                }
                want.update(
                    {
                        ("m", r["top_label"]): r
                        for r in live_db["mpls_routes"]
                    }
                )
                merged_stats = {
                    "skipped_generations": skipped,
                    "emissions": emitted,
                    "kind_ok": (
                        merged is not None
                        and merged["type"] == "delta"
                        and merged["merged_generations"] >= 3
                        and more is None
                    ),
                    "parity": (
                        canon_rows(stalled_state) == canon_rows(want)
                    ),
                }
            # subscriber churn: seeded detach + fresh attach
            for _ in range(min(STREAMING_CHURN_PER_TICK, len(live) - 8)):
                idx = rng.randrange(4, len(live))  # never the probes
                sid, _client = live.pop(idx)
                st.unsubscribe(sid)
                churned += 1
            for j in range(STREAMING_CHURN_PER_TICK):
                attach_one(attached_total)
            peak = max(peak, len(st._subs))
        await clock.run_for(4.0)
        wall_s = time.time() - t0

        # the overflow cohort: shallow queues over `ticks` generations
        # must have escalated to snapshot resync
        overflow_resyncs = 0
        for sid in overflow_ids:
            e = await poll1(sid)
            if e is not None and e["type"] == "snapshot" and e[
                "reason"
            ].startswith("resync"):
                overflow_resyncs += 1

        # probe parity: every probe's applied state matches live
        probe_parity = True
        for i, log in enumerate(probe_logs):
            state: dict = {}
            for e in log:
                state = apply_emission(state, e)
            _g, db = n0.serving.snapshot_for(
                "route_db", {"node": vantages[i % len(vantages)]}
            )
            wrows = {("u", r["dest"]): r for r in db["unicast_routes"]}
            wrows.update(
                {("m", r["top_label"]): r for r in db["mpls_routes"]}
            )
            if probe_ids[i] in st._subs and st._subs[
                probe_ids[i]
            ].prefix_filters:
                wrows = {
                    k: v
                    for k, v in wrows.items()
                    if k[0] != "u" or k[1].startswith("10.220.")
                }
            if canon_rows(state) != canon_rows(wrows):
                probe_parity = False

        c = n0.counters
        stale_h = c.histogram("streaming.staleness_ms")
        pct = stale_h.percentiles() if stale_h is not None else {}
        emissions = int(c.get("streaming.emissions"))
        resyncs = int(c.get("streaming.resyncs"))
        fired = []
        for _name, node in sorted(net.nodes.items()):
            if node.health is not None:
                for line in node.health.alert_log():
                    e = json.loads(line)
                    if e["event"] == "fired":
                        fired.append(e["name"])

        detail = {
            "nodes": 9,
            "seed": seed,
            "ticks": ticks,
            "virtual_s": round(clock.now(), 1),
            "subscribers": {
                "peak": peak,
                "attached_total": attached_total
                + STREAMING_OVERFLOW_COHORT
                + 1,
                "churned": churned,
                "final": len(st._subs),
                "quota_clients_final": len(n0.serving._quotas),
            },
            "feeds": len(st._feeds),
            "fanout": {
                "emissions": emissions,
                "delivered": delivered[0],
                "wall_s": round(wall_s, 3),
                "emissions_per_sec": round(delivered[0] / wall_s, 1),
                "deltas": int(c.get("streaming.deltas")),
                "snapshots": int(c.get("streaming.snapshots")),
                "coalesced": int(
                    c.get("streaming.coalesced_emissions")
                ),
                # shared-wire-encode evidence (ISSUE-14 satellite):
                # delta bodies rendered once per feed entry, shared by
                # reference across the unfiltered subscriber fan-out
                "shared_encode": {
                    "rendered_payloads": int(
                        c.get("streaming.rendered_payloads")
                    ),
                    "shared_payloads": int(
                        c.get("streaming.shared_payloads")
                    ),
                },
            },
            "staleness_ms": {
                "p50": round(pct.get("p50", 0.0), 3),
                "p95": round(pct.get("p95", 0.0), 3),
                "p99": round(pct.get("p99", 0.0), 3),
                "max": round(stale_h.vmax if stale_h else 0.0, 3),
                "samples": stale_h.count if stale_h else 0,
            },
            "resyncs": {
                "count": resyncs,
                "rate": round(resyncs / max(1, emissions), 5),
                "shed_deltas": int(c.get("streaming.shed_deltas")),
                "overflow_cohort_resynced": overflow_resyncs,
            },
            "merged_delta": {
                **merged_stats,
                "parity": merged_stats.get("parity", False)
                and probe_parity,
            },
            "partition": {
                "partition_seq": partition_seq[0],
                "pre_partition_generation_emissions": (
                    pre_partition_emissions[0]
                ),
                "post_heal_emissions": (
                    delivered[0] - (healed_at_emission[0] or 0)
                ),
                "monotone_regressions": monotone_regressions[0],
            },
            "invariant_violations": int(
                c.get("streaming.invariant_violations")
            ),
            "alerts": {
                "fired": len(fired),
                "unexpected": len(fired),
                "unexpected_names": sorted(fired),
            },
        }
        fingerprint = b"\n".join(
            [
                json.dumps(
                    [
                        [
                            json.dumps(e, sort_keys=True, default=str)
                            for e in log
                        ]
                        for log in probe_logs
                    ]
                ).encode(),
                *(
                    log
                    for _n, log in sorted(
                        net.health_alert_logs().items()
                    )
                ),
            ]
        )
        await net.stop()
        return detail, fingerprint

    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(run())
    finally:
        loop.close()


def streaming_main(seed: Optional[int] = None) -> None:
    """Watch-plane fan-out benchmark (BENCH_STREAMING_r*): 10k+ push
    subscribers with per-tick churn on one node's StreamingService,
    under a seeded chaos sweep (mid-sweep partition/heal of node8), with
    generation correctness gated hard — see validate_streaming_bench.
    Emits one JSON line."""
    seed = STREAMING_SEED if seed is None else seed
    t0 = time.time()
    detail, _fp = streaming_fanout_world(
        STREAMING_SUBS, seed, STREAMING_TICKS
    )
    detail["wall_s"] = round(time.time() - t0, 1)
    print(
        f"# streaming fan-out: {detail['subscribers']['peak']} subs peak "
        f"{detail['fanout']['emissions_per_sec']} emissions/s "
        f"p99 staleness {detail['staleness_ms']['p99']}ms virtual "
        f"resync rate {detail['resyncs']['rate']} "
        f"({detail['wall_s']}s wall)",
        file=sys.stderr,
    )
    d1, fp1 = streaming_fanout_world(
        STREAMING_SMOKE_SUBS, seed, STREAMING_SMOKE_TICKS
    )
    _d2, fp2 = streaming_fanout_world(
        STREAMING_SMOKE_SUBS, seed, STREAMING_SMOKE_TICKS
    )
    doc = {
        "metric": "streaming_fanout_emissions_per_sec",
        "value": detail["fanout"]["emissions_per_sec"],
        "unit": "emissions/s",
        "detail": {
            **detail,
            "smoke": {
                "subscribers": STREAMING_SMOKE_SUBS,
                "ticks": STREAMING_SMOKE_TICKS,
                "emissions": d1["fanout"]["emissions"],
                "resyncs": d1["resyncs"]["count"],
            },
            "deterministic_replay": fp1 == fp2,
            "mode": (
                "emulate (SimClock, 9-node grid, full OpenrNodes; "
                "scalar decision path; 10k+ push subscribers with "
                "seeded per-tick churn on node0's StreamingService, "
                "pull-mode overflow cohort + one stalled probe; "
                "mid-sweep partition/heal of node8; staleness in "
                "virtual ms, fan-out throughput in wall seconds)"
            ),
            "env": env_stamp(),
        },
    }
    validate_streaming_bench(doc)
    print(json.dumps(doc))


SWEEP_SEED = 7
SWEEP_GRID_SIDE = 64  # 4096 nodes, 8064 links: the grid4096 class
SWEEP_SHARD = 1024
SWEEP_COMBOS_PER_WORLD = 512
SWEEP_RESUME_KILL_AFTER = 3


def validate_sweep_bench(doc: dict) -> None:
    """Schema contract for BENCH_SWEEP_r*.json — shared by the bench
    emitter, the tier-1 artifact gate and the benchtrack manifest.

    The ISSUE-14 acceptance: 100k+ scenarios on a grid4096-class
    topology end to end in ONE round, per-phase pipeline attribution
    proving the sweep is DEVICE-bound (not decode- or spill-bound),
    spill-file row count + peak host-resident rows recorded
    in-artifact, and a kill-after-shard-K resume reproducing the
    uninterrupted ranked summary byte for byte."""
    from openr_tpu.tracing.pipeline import (
        DECODE,
        DEVICE_PHASES,
        HOST_PHASES,
        STREAM_DRAIN,
        SWEEP_REDUCE,
        SWEEP_SHARD_SOLVE,
    )

    assert doc["metric"] == "sweep_scenarios_per_sec_grid4096"
    assert doc["unit"] == "scenarios/s"
    d = doc["detail"]
    assert d["world"]["nodes"] == SWEEP_GRID_SIDE * SWEEP_GRID_SIDE
    sc = d["scenarios"]
    assert sc["total"] >= 100_000, "the acceptance floor is 100k+"
    assert sc["singles"] > 0 and sc["worlds"] >= 2
    assert sc["device_solves"] > 0
    sh = d["shards"]
    assert sh["completed"] == sh["total"] >= 2
    assert sh["scenarios_per_shard"] >= 1
    th = d["throughput"]
    assert doc["value"] == th["scenarios_per_sec"] > 0
    assert th["wall_s"] > 0
    sp = d["spill"]
    assert sp["rows"] == sc["total"], "every scenario spills exactly once"
    assert sp["segments_sealed"] >= 1 and sp["bytes"] > 0
    # the never-host-resident claim: peak rows in host memory bounded
    # by ONE shard, never the sweep
    assert 0 < sp["peak_host_rows"] <= sh["scenarios_per_shard"]
    att = d["attribution"]
    phases = att["phases_ms"]
    assert phases.get(SWEEP_SHARD_SOLVE, 0.0) > 0.0
    assert phases.get(STREAM_DRAIN, 0.0) > 0.0
    assert phases.get(SWEEP_REDUCE, 0.0) > 0.0
    assert phases.get(DECODE, 0.0) > 0.0
    host = sum(phases.get(p, 0.0) for p in HOST_PHASES)
    device = sum(phases.get(p, 0.0) for p in DEVICE_PHASES)
    assert att["device_share_pct"] == round(
        device / max(host + device, 1e-9) * 100.0, 2
    )
    assert att["device_bound"] is True
    assert att["device_share_pct"] > 50.0, (
        "the sweep must be device-bound"
    )
    for p, bound in ((DECODE, 25.0), (SWEEP_REDUCE, 25.0)):
        share = phases.get(p, 0.0) / max(host + device, 1e-9) * 100.0
        assert share < bound, f"{p} share {share:.1f}% — not device-bound"
    assert 0.0 <= att["gap_pct"] <= 30.0, (
        "un-attributed wall beyond the loop-overhead allowance"
    )
    pc = d["plan_cache"]
    assert pc["hits"] >= 1, (
        "world engine replicas must HIT the content-hash plan cache"
    )
    assert pc["size"] <= pc["cap"]
    rs = d["resume"]
    assert rs["proof_scenarios"] >= 8_000
    assert rs["killed_after_shards"] >= 1
    assert rs["resumed_shards"] == rs["killed_after_shards"]
    assert rs["checkpoint_verified"] is True
    assert rs["summary_byte_identical"] is True
    rk = d["ranked"]
    assert rk["criticality_rows"] >= 1
    assert rk["worst_case"] is not None
    for key in ("seed", "mode", "env"):
        assert key in d, key
    for key in ("platform", "jax", "device_count"):
        assert key in d["env"], f"env.{key}"
    assert d["env"]["device_count"] >= 8


def _sweep_bench_world(n_side: int):
    from openr_tpu.decision.link_state import LinkState
    from openr_tpu.decision.prefix_state import PrefixState
    from openr_tpu.emulation.topology import build_adj_dbs, grid_edges
    from openr_tpu.types import PrefixEntry

    ls = LinkState("0")
    for db in build_adj_dbs(grid_edges(n_side)).values():
        ls.update_adjacency_database(db)
    ps = PrefixState()
    for i in range(n_side * n_side):
        ps.update_prefix(
            f"node{i}", "0",
            PrefixEntry(f"10.{i // 256}.{i % 256}.0/24"),
        )
    return {"0": ls}, ps


def sweep_main(seed: Optional[int] = None) -> None:
    """Capacity-planning sweep benchmark (BENCH_SWEEP_r*): 100k+
    scenarios (single-link failures x drain states x metric
    perturbations + bounded 2-node-domain combos) on the grid4096
    class, sharded as committed per-device dispatches over an 8-chip
    DevicePool, spilled + checkpointed + rank-reduced end to end; plus
    the kill-after-shard-K resume proof on a single-world sub-sweep.
    Emits one JSON line."""
    import os
    import shutil
    import tempfile

    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    seed = SWEEP_SEED if seed is None else seed

    from openr_tpu.common.runtime import CounterMap, WallClock
    from openr_tpu.ops import repair
    from openr_tpu.parallel.mesh import DevicePool
    from openr_tpu.sweep import ScenarioSpec, SweepExecutor, SweepInputs
    from openr_tpu.sweep.spill import CheckpointManifest, SpillReader
    from openr_tpu.tracing import pipeline
    from openr_tpu.tracing.pipeline import PipelineProbe

    als, ps = _sweep_bench_world(SWEEP_GRID_SIDE)
    clock = WallClock()
    counters = CounterMap()
    probe = PipelineProbe(clock, counters)
    pool = DevicePool()

    def inputs():
        return SweepInputs(
            area_link_states=als,
            prefix_state=ps,
            change_seq=1,
            root="node0",
            pool=pool,
            probe=probe,
        )

    def phase_totals() -> dict:
        out = {}
        for phase in pipeline.PHASES:
            h = counters.histogram(pipeline.hist_key(phase))
            if h is not None:
                out[phase] = h.total
        return out

    def make_ex(spill_dir):
        return SweepExecutor(
            inputs,
            spill_dir,
            clock=clock,
            counters=counters,
            shard_scenarios=SWEEP_SHARD,
            inflight=2,
        )

    # the headline grammar: 12 worlds x 8064 single-link failures +
    # 512 seeded 2-node-domain combos per world = 102,912 scenarios
    spec = ScenarioSpec(
        drain_node_sets=(
            (),
            ("node2080",),            # center drain
            ("node1032",),            # off-center drain
            ("node1032", "node2080"),  # double maintenance window
        ),
        metric_perturbations=(
            (r"node1[0-9]{3}", 2.0),  # mid-band cost-up
            (r"node2[0-9]{3}", 8.0),  # deep cost-out
        ),
        combo_k=2,
        max_combo_scenarios=SWEEP_COMBOS_PER_WORLD,
        combo_seed=seed,
    )
    tmp = tempfile.mkdtemp(prefix="openr_sweep_bench.")
    try:
        ex = make_ex(os.path.join(tmp, "headline"))
        t0 = time.time()
        rep = ex.prepare(spec)
        prepare_s = time.time() - t0
        print(
            f"# sweep: {rep['scenarios']} scenarios in {rep['shards']} "
            f"shards over {pool.num_healthy} devices "
            f"(enumerate {prepare_s:.1f}s)",
            file=sys.stderr,
        )
        p0 = phase_totals()
        t0 = time.time()
        ex.run()
        wall_s = time.time() - t0
        p1 = phase_totals()
        phases_ms = {
            k: round(p1.get(k, 0.0) - p0.get(k, 0.0), 3)
            for k in pipeline.PHASES
            if p1.get(k, 0.0) - p0.get(k, 0.0) > 0.0
        }
        host = sum(
            phases_ms.get(p, 0.0) for p in pipeline.HOST_PHASES
        )
        device = sum(
            phases_ms.get(p, 0.0) for p in pipeline.DEVICE_PHASES
        )
        attributed = host + device
        status = ex.status()
        summary = ex.summary()
        plan_gauges = repair.plan_cache_gauges()
        per_device = [int(n) for n in pool.num_dispatches]
        print(
            f"# sweep: {status['scenarios_completed']} scenarios in "
            f"{wall_s:.1f}s ({status['scenarios_completed'] / wall_s:.0f}"
            f"/s), {status['device_solves']} device solves, "
            f"device share "
            f"{device / max(attributed, 1e-9) * 100.0:.1f}%",
            file=sys.stderr,
        )

        # ---- the resume proof: kill after shard K, resume, compare --
        proof_spec = ScenarioSpec()  # identity world, 8064 singles
        exf = make_ex(os.path.join(tmp, "proof_full"))
        exf.prepare(proof_spec)
        exf.run()
        exk = make_ex(os.path.join(tmp, "proof_kill"))
        exk.prepare(proof_spec)
        exk.run(stop_after_shards=SWEEP_RESUME_KILL_AFTER)
        killed = len(exk.completed)
        exr = make_ex(os.path.join(tmp, "proof_kill"))
        rrep = exr.prepare(proof_spec)
        # checkpoint verification: the manifest's committed shards are
        # exactly what the kill left, and the spill holds their rows
        cp = CheckpointManifest(os.path.join(tmp, "proof_kill"))
        committed = cp.completed_shards()
        replayed = sum(
            1
            for _ in SpillReader(os.path.join(tmp, "proof_kill")).rows(
                shard_filter=set(committed)
            )
        )
        checkpoint_verified = (
            sorted(committed) == sorted(range(killed))
            and replayed == sum(m["rows"] for m in committed.values())
        )
        exr.run()
        resume = {
            "proof_scenarios": len(exf.scenarios),
            "killed_after_shards": killed,
            "resumed_shards": rrep["resumed_shards"],
            "checkpoint_verified": checkpoint_verified,
            "summary_byte_identical": (
                exr.summary()["summary_digest"]
                == exf.summary()["summary_digest"]
            ),
        }
        print(
            f"# sweep resume proof: killed after {killed} shards, "
            f"resumed {rrep['resumed_shards']}, byte-identical "
            f"{resume['summary_byte_identical']}",
            file=sys.stderr,
        )
        ranked = summary["summary"]
        doc = {
            "metric": "sweep_scenarios_per_sec_grid4096",
            "value": round(status["scenarios_completed"] / wall_s, 1),
            "unit": "scenarios/s",
            "detail": {
                "world": {
                    "topology": f"grid{SWEEP_GRID_SIDE}x{SWEEP_GRID_SIDE}",
                    "nodes": SWEEP_GRID_SIDE * SWEEP_GRID_SIDE,
                    "links": 2
                    * SWEEP_GRID_SIDE
                    * (SWEEP_GRID_SIDE - 1),
                    "prefixes": SWEEP_GRID_SIDE * SWEEP_GRID_SIDE,
                    "vantage": "node0",
                },
                "scenarios": {
                    "total": status["scenarios_completed"],
                    "singles": 12 * 2 * SWEEP_GRID_SIDE
                    * (SWEEP_GRID_SIDE - 1),
                    "combos": status["scenarios_completed"]
                    - 12 * 2 * SWEEP_GRID_SIDE * (SWEEP_GRID_SIDE - 1),
                    "worlds": 12,
                    "device_solves": status["device_solves"],
                    "alias_rows": ranked["alias_rows"],
                    "zero_delta": ranked["zero_delta"],
                },
                "shards": {
                    "total": status["shards_total"],
                    "completed": status["shards_completed"],
                    "scenarios_per_shard": SWEEP_SHARD,
                    "repacked": status["repacked_shards"],
                    "per_device_dispatches": per_device,
                },
                "throughput": {
                    "scenarios_per_sec": round(
                        status["scenarios_completed"] / wall_s, 1
                    ),
                    "device_solves_per_sec": round(
                        status["device_solves"] / wall_s, 1
                    ),
                    "wall_s": round(wall_s, 1),
                    "prepare_s": round(prepare_s, 1),
                },
                "spill": status["spill"],
                "attribution": {
                    "phases_ms": phases_ms,
                    "attributed_ms": round(attributed, 1),
                    "host_ms": round(host, 1),
                    "device_ms": round(device, 1),
                    "device_share_pct": round(
                        device / max(attributed, 1e-9) * 100.0, 2
                    ),
                    "device_bound": device
                    / max(attributed, 1e-9)
                    > 0.5,
                    "gap_pct": round(
                        max(
                            (wall_s * 1000.0 - attributed)
                            / (wall_s * 1000.0)
                            * 100.0,
                            0.0,
                        ),
                        2,
                    ),
                },
                "plan_cache": {
                    "hits": int(plan_gauges["plan_cache.hits"]),
                    "misses": int(plan_gauges["plan_cache.misses"]),
                    "evictions": int(
                        plan_gauges["plan_cache.evictions"]
                    ),
                    "size": int(plan_gauges["plan_cache.size"]),
                    "cap": int(plan_gauges["plan_cache.cap"]),
                },
                "resume": resume,
                "ranked": {
                    "criticality_rows": len(ranked["criticality"]),
                    "top_links": ranked["criticality"][:5],
                    "worst_case": ranked["worst_case"],
                    "spof_count": len(ranked["spof_links"]),
                    "summary_digest": summary["summary_digest"],
                },
                "seed": seed,
                "mode": (
                    "standalone executor (WallClock) over a synthetic "
                    "grid4096 LSDB; 8 forced host devices (virtual "
                    "chips share physical cores — per-device scaling "
                    "is structural, the throughput is the one-host "
                    "number); warm-repair solve + on-device selection "
                    "per shard, streamed FIFO drains"
                ),
                "env": env_stamp(),
            },
        }
        validate_sweep_bench(doc)
        print(json.dumps(doc))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


FRR_GRID_SIDE = 64
FRR_MAX_LINKS = 128
FRR_FLAPS = 24
#: the checked-in BENCH_WARMSTART_r01 warm generation-delta rebuild p50
#: (publication→FIB equivalent) on the same grid4096 world — the
#: protection tier's 10x acceptance floor is judged against this
#: warm-path reference (rebuilding is the thing the table replaces)
FRR_WARM_REFERENCE_P50_MS = 79.314
FRR_SPEEDUP_FLOOR = 10.0


def validate_frr_bench(doc: dict) -> None:
    """Schema contract for BENCH_FRR_r*.json — shared by the bench
    emitter, the tier-1 artifact gate and the benchtrack manifest.

    The ISSUE-16 acceptance: on grid4096 with a 128-link minted
    protection table, the publication→FIB p99 of a PROTECTED
    single-link flap (kv ingest → classify → generation-exact lookup →
    materialize → publish → FIB program, real Decision + Fib actors on
    the wall clock) must sit >= 10x below the 79.3ms warm-rebuild p50
    reference; every applied patch carries scalar-oracle RIB parity
    after its confirming warm solve (zero mismatches); stale-table and
    unminted-link fallbacks are exercised and counted in-artifact; a
    mint killed after shard K resumes to the byte-identical table
    hash."""
    assert doc["metric"] == (
        "frr_protected_flap_publication_to_fib_p99_ms_grid4096"
    )
    assert doc["unit"] == "ms"
    d = doc["detail"]
    ap = d["apply"]
    assert doc["value"] == ap["p99_ms"]
    assert 0 < ap["p50_ms"] <= ap["p95_ms"] <= ap["p99_ms"] <= ap["max_ms"]
    assert ap["flaps"] >= 16
    assert len(ap["samples_ms"]) == ap["flaps"]
    # every measured flap applied from the table, was confirmed by the
    # warm authority, and reached the FIB as an frr-stamped patch
    assert ap["applied"] == ap["flaps"]
    assert ap["fib_patches_applied"] == ap["flaps"]
    assert ap["confirms"] == ap["flaps"]
    assert ap["mismatches"] == 0
    assert ap["scalar_parity"] is True
    assert ap["parity_checks"] == ap["flaps"]
    wm = d["warm"]
    assert wm["samples"] >= 16
    assert 0 < wm["p50_ms"] <= wm["p99_ms"]
    assert wm["reference_p50_ms_r01"] == FRR_WARM_REFERENCE_P50_MS
    sp = d["speedup"]
    assert sp["floor"] == FRR_SPEEDUP_FLOOR
    assert sp["vs_reference_warm_p50"] == round(
        FRR_WARM_REFERENCE_P50_MS / ap["p99_ms"], 2
    )
    assert sp["vs_reference_warm_p50"] >= FRR_SPEEDUP_FLOOR, (
        "protected convergence must be a lookup: p99 >= 10x under the "
        "warm-rebuild reference"
    )
    fb = d["fallbacks"]
    assert fb["stale"] >= 1, "stale-table fallback must be exercised"
    assert fb["miss"] >= 1, "unminted-link fallback must be exercised"
    assert fb["total"] >= fb["stale"] + fb["miss"]
    mi = d["mint"]
    assert mi["patches"] == mi["max_links"] == FRR_MAX_LINKS
    assert mi["eligible"] >= 1
    assert mi["mints"] >= ap["flaps"]
    assert mi["cold_wall_ms"] > 0 and mi["warm_wall_p50_ms"] > 0
    assert 0 < mi["coverage_pct"] < 100.0
    rs = d["resume"]
    assert rs["killed_after_shards"] >= 1
    assert rs["resumed"] is True
    assert rs["table_hash_byte_identical"] is True
    assert d["world"]["nodes"] == FRR_GRID_SIDE * FRR_GRID_SIDE
    for key in ("seed", "mode", "env"):
        assert key in d, key
    for key in ("platform", "jax", "device_count"):
        assert key in d["env"], f"env.{key}"
    assert d["env"]["device_count"] >= 1


def frr_main(seed: Optional[int] = None) -> None:
    """Fast-reroute protection-tier benchmark (BENCH_FRR_r*): failure
    convergence as a lookup, on grid4096 with REAL actors.

    One Decision (TPU backend) and one Fib (instrumented in-memory
    agent) run on the wall clock, fed delta kv publications exactly the
    way a flood would deliver them.  A 128-link protection table is
    minted from the live generation before every measured flap; the
    headline sample is t(kv publication push) → t(the frr patch's
    routes hit the FibAgent), covering ingest, down-classification, the
    generation-exact table lookup, patch materialization, the
    INCREMENTAL publish and the Fib actor's program step.  The same
    flap set replays with the tier detached for the in-run warm-path
    comparison (debounce + generation-delta rebuild + publish).  Every
    applied patch is confirmed by the warm solve and checked against
    the scalar oracle; stale-table and unminted-link refusals are
    driven on purpose so the fallback ledger is populated; a mint
    killed after one shard proves byte-identical resume."""
    import asyncio
    import copy
    import gc
    import os
    import random as _random
    import shutil
    import tempfile

    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    from openr_tpu.ops.platform_env import enable_persistent_compile_cache

    enable_persistent_compile_cache()

    from openr_tpu.common.runtime import CounterMap, WallClock
    from openr_tpu.config import DecisionConfig, FibConfig, ProtectionConfig
    from openr_tpu.decision.backend import ScalarBackend, TpuBackend
    from openr_tpu.decision.decision import Decision
    from openr_tpu.decision.rib import route_db_summary
    from openr_tpu.decision.spf_solver import SpfSolver
    from openr_tpu.emulation.topology import build_adj_dbs, grid_edges
    from openr_tpu.fib.fib import Fib, MockFibAgent
    from openr_tpu.messaging.queue import ReplicateQueue
    from openr_tpu.protection import ProtectionBuilder, ProtectionService, ProtectionStore
    from openr_tpu.sweep import SweepInputs
    from openr_tpu.types import (
        InitializationEvent,
        PrefixDatabase,
        PrefixEntry,
        PrefixMetrics,
        Publication,
        Value,
        prefix_key,
    )

    seed = 7 if seed is None else seed
    side = FRR_GRID_SIDE
    n_nodes = side * side
    # seeded heterogeneous link costs: a unit-metric grid is pathological
    # ECMP — from a corner vantage every destination keeps the same two
    # nexthops across ANY interior-link failure, so most patches would be
    # empty.  Random WAN-style costs make shortest paths (mostly) unique,
    # so a protected flap actually reroutes a subtree.
    _mrng = _random.Random(seed * 7919 + 1)
    edges = [
        (a, b, 1 + _mrng.randrange(15)) for a, b, _m in grid_edges(side)
    ]
    base_dbs = build_adj_dbs(edges)
    versions = {node: 1 for node in base_dbs}

    def adj_value(node, without=None):
        db = copy.deepcopy(base_dbs[node])
        if without is not None:
            db.adjacencies = [
                a for a in db.adjacencies if a.other_node_name != without
            ]
        return Value(
            version=versions[node],
            originator_id=node,
            value=json.dumps(db.to_wire()).encode(),
        )

    def link_pub(a, b, down):
        """The delta publication a flood delivers for one link event:
        just the two endpoints' re-encoded adjacency DBs."""
        versions[a] += 1
        versions[b] += 1
        return Publication(
            key_vals={
                f"adj:{a}": adj_value(a, without=b if down else None),
                f"adj:{b}": adj_value(b, without=a if down else None),
            }
        )

    class TimingAgent(MockFibAgent):
        """MockFibAgent that timestamps the first route programming
        after arm() — the measurement endpoint of every flap sample."""

        def __init__(self, c) -> None:
            super().__init__(c)
            self.armed = False
            self.t_program = 0.0
            self.programmed = asyncio.Event()

        def arm(self) -> None:
            self.armed = True
            self.programmed.clear()

        async def add_unicast_routes(self, routes):
            if self.armed:
                self.t_program = time.perf_counter()
                self.armed = False
                self.programmed.set()
            await super().add_unicast_routes(routes)

    prot_dir = tempfile.mkdtemp(prefix="openr_frr_bench.")

    async def bench():
        clock = WallClock()
        solver = SpfSolver("node0")
        out_q = ReplicateQueue("routes")
        kv_q = ReplicateQueue("kv")
        d = Decision(
            "node0",
            clock,
            DecisionConfig(debounce_min_ms=10, debounce_max_ms=250),
            out_q,
            kv_store_updates_reader=kv_q.get_reader(),
            backend=TpuBackend(solver),
            solver=solver,
        )
        d.backend.auto_dispatch_rt_ms = 0.0
        agent = TimingAgent(clock)
        fib = Fib(
            "node0",
            clock,
            FibConfig(route_delete_delay_ms=50),
            agent,
            out_q.get_reader(),
            counters=d.counters,
        )
        d.start()
        fib.start()
        d.on_initialization_event(InitializationEvent.KVSTORE_SYNCED)
        kv_q.push(
            Publication(
                key_vals={f"adj:{n}": adj_value(n) for n in base_dbs}
            )
        )
        prefix_kvs = {}
        for i in range(1, n_nodes):
            node = f"node{i}"
            prefix = f"10.{(i >> 8) & 0xFF}.{i & 0xFF}.0/24"
            pdb = PrefixDatabase(
                this_node_name=node,
                prefix_entries=[
                    PrefixEntry(
                        prefix,
                        metrics=PrefixMetrics(path_preference=1000),
                    )
                ],
            )
            prefix_kvs[prefix_key(node, prefix)] = Value(
                version=1,
                originator_id=node,
                value=json.dumps(pdb.to_wire()).encode(),
            )
        kv_q.push(Publication(key_vals=prefix_kvs))

        async def wait_for(pred, what, timeout_s=120.0):
            deadline = time.perf_counter() + timeout_s
            while not pred():
                if time.perf_counter() > deadline:
                    raise AssertionError(f"timed out waiting for {what}")
                await asyncio.sleep(0.002)

        await wait_for(
            lambda: d._first_build_done and agent.num_sync >= 1,
            "first build + FULL_SYNC",
        )

        async def push_and_settle(pubs, what):
            s = d._change_seq
            for p in pubs:
                kv_q.push(p)
            await wait_for(
                lambda: d._change_seq >= s + len(pubs)
                and d.rebuild_settled(),
                what,
            )

        svc = ProtectionService(
            "node0",
            clock,
            ProtectionConfig(
                enabled=True,
                store_dir=os.path.join(prot_dir, "store"),
                shard_scenarios=64,
                max_links=FRR_MAX_LINKS,
            ),
            d,
            counters=d.counters,
        )
        d.protection = svc
        d.add_generation_listener(svc._on_generation, priority=20)

        # -- mint the table (cold: includes sweep-kernel compile) -----------
        t0 = time.perf_counter()
        rep = svc.mint_now()
        cold_mint_ms = (time.perf_counter() - t0) * 1000.0
        assert rep["patches"] == FRR_MAX_LINKS, rep
        mint_walls = []

        def mint_warm():
            t0 = time.perf_counter()
            svc.mint_now()
            mint_walls.append((time.perf_counter() - t0) * 1000.0)

        minted = [
            tuple(k.split("|"))
            for k in svc.table.store.keys()
            if k.count("|") == 1
        ]
        # measured flaps must carry a real route delta (a flap off the
        # vantage's SPF tree legitimately mints an empty patch — nothing
        # to program, nothing to time), and the vantage keeps its own
        # adjacencies up
        protected = []
        for a, b in minted:
            if "node0" in (a, b):
                continue
            doc = svc.table.store.lookup(f"{a}|{b}")
            if doc and doc.get("eligible") and doc.get("sets"):
                protected.append((a, b))
        assert len(protected) >= FRR_FLAPS + 2, (
            f"only {len(protected)} non-trivial protected links minted"
        )
        rng = _random.Random(seed)
        flap_pairs = rng.sample(protected, FRR_FLAPS)
        spare = [p for p in protected if p not in flap_pairs]

        # -- warm-path comparison: same flaps, tier detached ----------------
        d.protection = None
        warm_ms = []
        for i, (a, b) in enumerate([flap_pairs[0]] + flap_pairs):
            print(f"warm flap {i}: {a}|{b}", file=sys.stderr, flush=True)
            s = d._change_seq
            agent.arm()
            t0 = time.perf_counter()
            kv_q.push(link_pub(a, b, down=True))
            await asyncio.wait_for(agent.programmed.wait(), timeout=60.0)
            if i > 0:  # flap 0 replays unmeasured to absorb compiles
                warm_ms.append((agent.t_program - t0) * 1000.0)
            await wait_for(
                lambda: d._change_seq >= s + 1 and d.rebuild_settled(),
                "warm flap settle",
            )
            await push_and_settle(
                [link_pub(a, b, down=False)], "warm restore"
            )
        d.protection = svc

        # -- fallback ledger: an unminted link misses ----------------------
        mint_warm()
        pairs_all = {tuple(sorted((a, b))) for a, b, _m in edges}
        miss_pair = next(
            p
            for p in sorted(pairs_all - set(minted))
            if "node0" not in p
        )
        await push_and_settle(
            [link_pub(*miss_pair, down=True)], "miss flap"
        )
        await push_and_settle(
            [link_pub(*miss_pair, down=False)], "miss restore"
        )
        assert d.counters.get("protection.fallback.miss") >= 1

        # -- fallback ledger: a second flap hits the now-stale table -------
        mint_warm()
        first, second = spare[0], spare[1]
        # the first flap applies from the table and moves the generation;
        # the second (NO re-mint) finds its previous generation no longer
        # matching the mint — refuse stale, converge warm
        await push_and_settle(
            [link_pub(*first, down=True)], "stale first flap"
        )
        await push_and_settle(
            [link_pub(*second, down=True)], "stale second flap"
        )
        await push_and_settle(
            [
                link_pub(*first, down=False),
                link_pub(*second, down=False),
            ],
            "stale restore",
        )
        assert d.counters.get("protection.fallback.stale") >= 1

        # -- the measured pass ----------------------------------------------
        counter_keys = (
            "decision.frr_applied",
            "decision.frr_mismatches",
            "protection.confirms",
            "fib.frr_patches_applied",
        )
        base = {k: d.counters.get(k) for k in counter_keys}
        frr_ms = []
        parity_checks = 0
        parity_ok = True
        for a, b in flap_pairs:
            mint_warm()  # fresh-generation table for THIS flap
            gc.collect()
            confirms0 = d.counters.get("protection.confirms")
            s = d._change_seq
            agent.arm()
            # a 24-sample p99 is the max sample: keep the collector out
            # of the timed window (it is re-enabled before the confirm)
            gc.disable()
            try:
                t0 = time.perf_counter()
                kv_q.push(link_pub(a, b, down=True))
                await asyncio.wait_for(agent.programmed.wait(), timeout=60.0)
                frr_ms.append((agent.t_program - t0) * 1000.0)
            finally:
                gc.enable()
            print(
                f"frr flap {a}|{b}: {frr_ms[-1]:.3f} ms",
                file=sys.stderr,
                flush=True,
            )
            # the confirming warm solve is the authority — wait for it,
            # then hold the patched RIB against the scalar oracle
            await wait_for(
                lambda: d.counters.get("protection.confirms") > confirms0,
                "confirm",
            )
            await wait_for(
                lambda: d._change_seq >= s + 1 and d.rebuild_settled(),
                "flap settle",
            )
            oracle = ScalarBackend(SpfSolver("node0")).build_route_db(
                d.area_link_states, d.prefix_state
            )
            parity_checks += 1
            parity_ok = parity_ok and (
                route_db_summary(d.route_db) == route_db_summary(oracle)
            )
            await push_and_settle(
                [link_pub(a, b, down=False)], "restore"
            )
        deltas = {k: d.counters.get(k) - base[k] for k in counter_keys}

        # -- kill-after-shard-K resume: byte-identical table hash -----------
        def inputs_fn():
            return SweepInputs(**d.capacity_sweep_inputs())

        def run_builder(sub, kill_after=None, resume=False):
            b = ProtectionBuilder(
                inputs_fn,
                ProtectionStore(os.path.join(prot_dir, sub, "store")),
                d.solver,
                os.path.join(prot_dir, sub, "sweep"),
                counters=CounterMap(),
                shard_scenarios=32,
                max_links=FRR_MAX_LINKS,
            )
            rep = b.prepare(resume=resume)
            steps = 0
            while not b.finished():
                b.step(1)
                steps += 1
                if kill_after is not None and steps >= kill_after:
                    return rep, None
            return rep, b.finalize()

        _, clean = run_builder("clean")
        run_builder("killed", kill_after=1)
        rep_res, fin_res = run_builder("killed", resume=True)
        resume_detail = {
            "killed_after_shards": 1,
            "resumed": bool(rep_res.get("resumed")),
            "resumed_shards": int(rep_res.get("resumed_shards", 0)),
            "table_hash_byte_identical": (
                fin_res["table_hash"] == clean["table_hash"]
            ),
        }

        fallbacks = {
            "total": d.counters.get("protection.fallbacks"),
            "stale": d.counters.get("protection.fallback.stale"),
            "miss": d.counters.get("protection.fallback.miss"),
            "minting": d.counters.get("protection.fallback.minting"),
            "multi_failure": d.counters.get(
                "protection.fallback.multi_failure"
            ),
        }
        table_stats = {
            "patches": svc.table.patches,
            "eligible": svc.table.eligible,
            "mints": svc.table.num_mints,
        }
        await d.stop()
        await fib.stop()
        return (
            frr_ms,
            warm_ms,
            deltas,
            parity_checks,
            parity_ok,
            cold_mint_ms,
            mint_walls,
            fallbacks,
            table_stats,
            resume_detail,
        )

    loop = asyncio.new_event_loop()
    try:
        (
            frr_ms,
            warm_ms,
            deltas,
            parity_checks,
            parity_ok,
            cold_mint_ms,
            mint_walls,
            fallbacks,
            table_stats,
            resume_detail,
        ) = loop.run_until_complete(bench())
    finally:
        pending = asyncio.all_tasks(loop)
        for t in pending:
            t.cancel()
        if pending:
            loop.run_until_complete(
                asyncio.gather(*pending, return_exceptions=True)
            )
        loop.close()
        shutil.rmtree(prot_dir, ignore_errors=True)

    def pct(xs, q):
        ys = sorted(xs)
        return ys[min(len(ys) - 1, int(round(q / 100.0 * (len(ys) - 1))))]

    p99 = round(pct(frr_ms, 99), 3)
    doc = {
        "metric": "frr_protected_flap_publication_to_fib_p99_ms_grid4096",
        "value": p99,
        "unit": "ms",
        "detail": {
            "world": {
                "nodes": n_nodes,
                "links": len(edges),
                "prefixes": n_nodes - 1,
                "topology": f"grid{side}x{side}",
            },
            "apply": {
                "flaps": len(frr_ms),
                "p50_ms": round(pct(frr_ms, 50), 3),
                "p95_ms": round(pct(frr_ms, 95), 3),
                "p99_ms": p99,
                "max_ms": round(max(frr_ms), 3),
                "samples_ms": [round(x, 3) for x in frr_ms],
                "applied": deltas["decision.frr_applied"],
                "fib_patches_applied": deltas["fib.frr_patches_applied"],
                "confirms": deltas["protection.confirms"],
                "mismatches": deltas["decision.frr_mismatches"],
                "scalar_parity": parity_ok,
                "parity_checks": parity_checks,
            },
            "warm": {
                "samples": len(warm_ms),
                "p50_ms": round(pct(warm_ms, 50), 3),
                "p99_ms": round(pct(warm_ms, 99), 3),
                "reference_p50_ms_r01": FRR_WARM_REFERENCE_P50_MS,
                "note": "same flap set with the protection tier "
                "detached: debounce + generation-delta warm rebuild + "
                "publish + FIB program; reference = BENCH_WARMSTART_r01 "
                "warm_p50_ms on the same grid4096 world",
            },
            "speedup": {
                "floor": FRR_SPEEDUP_FLOOR,
                "vs_reference_warm_p50": round(
                    FRR_WARM_REFERENCE_P50_MS / p99, 2
                ),
                "vs_inrun_warm_p50": round(pct(warm_ms, 50) / p99, 2),
            },
            "fallbacks": fallbacks,
            "mint": {
                "max_links": FRR_MAX_LINKS,
                "patches": table_stats["patches"],
                "eligible": table_stats["eligible"],
                "mints": table_stats["mints"],
                "cold_wall_ms": round(cold_mint_ms, 1),
                "warm_wall_p50_ms": round(pct(mint_walls, 50), 1),
                "coverage_pct": round(
                    FRR_MAX_LINKS / len(edges) * 100.0, 2
                ),
            },
            "resume": resume_detail,
            "seed": seed,
            "mode": (
                "real Decision (TPU backend) + Fib actors on the wall "
                "clock, delta kv publications; seeded heterogeneous "
                "link costs (unit-metric grids are pathological ECMP "
                "— interior flaps would mint empty patches); per-flap "
                "re-mint so every lookup is generation-exact; 8 "
                "forced host devices"
            ),
            "env": env_stamp(),
        },
    }
    try:
        validate_frr_bench(doc)
    except AssertionError:
        # the doc never reaches stdout on a failed gate — surface it on
        # stderr so the failing run is diagnosable from its log alone
        print(json.dumps(doc), file=sys.stderr, flush=True)
        raise
    print(json.dumps(doc))


# ---------------------------------------------------------------------------
# fleet compute fabric bench (--fleet-sweep / --fleet-streaming)
# ---------------------------------------------------------------------------

FLEET_BENCH_NODES = ("fab0", "fab1", "fab2")
FLEET_BENCH_SIDE = 4


def validate_fleet_bench(doc: dict) -> None:
    """Schema contract for BENCH_FLEET_r*.json — shared by the bench
    emitter, the tier-1 artifact gate and the benchtrack manifest.

    The ISSUE-19 acceptance, in-artifact: the 3-node fleet sweep's
    merged summary digest is byte-equal to the single-node run of the
    same scenario set; a mid-sweep node kill re-packs ONLY the victim's
    worlds onto survivors and still converges to the byte-identical
    digest AND fleet manifest; a mid-stream node kill migrates exactly
    the victim's watchers to their hash successors with zero
    monotone-generation invariant violations and no pre-migration
    generation re-emitted; a maintenance drain hands off cleanly (zero
    residual subscribers on the drained daemon); the whole chaos
    schedule replays byte-identically on the virtual clock.

    The ISSUE-20 liveness tier rides the same artifact: an UNANNOUNCED
    kill is concluded from heartbeat silence alone within the TTL
    bound (p50/max over phase-shifted samples), and the sweep still
    merges to the byte-identical digest with zero stream violations;
    an asymmetric partition's stale-epoch pushes are fenced, never
    double-delivered; stale-epoch sweep dispatches are fenced and
    re-packed; a straggling member's worlds re-pack first-committed-
    wins with the digest unchanged; a heartbeating-but-raising member
    is gray-demoted without crashing the coordinator; a flapping
    member is damped with ownership churn bounded to <=2 moves per
    flap cycle.  Every liveness chaos schedule replays
    byte-identically."""
    assert doc["metric"] == "fleet_sweep_merged_scenarios_per_s_3node"
    assert doc["unit"] == "scenarios/s"
    assert doc["value"] > 0
    d = doc["detail"]
    sw = d["sweep"]
    assert sw["nodes"] == len(FLEET_BENCH_NODES)
    assert sw["worlds"] >= 8
    assert sw["scenarios"] >= sw["worlds"]
    assert doc["value"] == sw["merged_scenarios_per_s"]
    assert sw["single_node_digest"]
    assert sw["fleet_digest"] == sw["single_node_digest"]
    assert sw["summary_digest_equal"] is True
    k = sw["kill"]
    assert k["victim"] in FLEET_BENCH_NODES
    assert k["repacked_worlds"] >= 1
    assert k["rounds"] >= 2
    assert k["digest_equal"] is True
    assert k["manifest_byte_identical"] is True
    st = d["streaming"]
    assert st["watchers"] >= 8
    assert st["migrated_watchers"] >= 1
    assert st["invariant_violations"] == 0
    assert st["pre_migration_generation_emissions"] == 0
    assert st["deterministic_replay"] is True
    dr = st["drain"]
    assert dr["migrated_watchers"] >= 1
    assert dr["invariant_violations"] == 0
    assert dr["residual_subscribers"] == 0
    # -- the ISSUE-20 liveness tier: self-hosted membership ------------
    lv = d["liveness"]
    hb = lv["heartbeat"]
    assert 0 < hb["interval_s"] < hb["suspect_after_s"] < hb["ttl_s"]
    det = lv["detection"]
    assert det["samples"] >= 3
    assert 0 < det["p50_s"] <= det["max_s"] <= det["bound_s"]
    uk = lv["unannounced_kill"]
    assert uk["victim"] in FLEET_BENCH_NODES
    assert uk["detection_s"] > 0
    assert uk["suspects_seen"] >= 1
    assert uk["repacked_worlds"] >= 1
    assert uk["digest_equal"] is True
    assert uk["manifest_byte_identical"] is True
    assert uk["invariant_violations"] == 0
    assert uk["pre_migration_generation_emissions"] == 0
    assert uk["deterministic_replay"] is True
    sb = lv["split_brain"]
    assert sb["victim"] in FLEET_BENCH_NODES
    assert sb["fenced_stream_deliveries"] >= 1
    assert sb["invariant_violations"] == 0
    assert sb["double_pushes"] == 0
    assert sb["healed_stale_subscriptions"] == 0
    assert sb["deterministic_replay"] is True
    fe = lv["epoch_fence"]
    assert fe["fenced_worlds"] >= 1
    assert fe["digest_equal"] is True
    assert fe["manifest_byte_identical"] is True
    sg = lv["straggler"]
    assert sg["straggler_repacks"] >= 1
    assert sg["duplicate_completions"] >= 1
    assert sg["digest_equal"] is True
    assert sg["manifest_byte_identical"] is True
    gr = lv["gray_failure"]
    assert gr["victim"] in FLEET_BENCH_NODES
    assert gr["demotions"] >= 1
    assert gr["coordinator_crashes"] == 0
    assert gr["ticket_firing"] is True
    assert gr["digest_equal"] is True
    fl = lv["flap"]
    assert fl["flap_damped"] >= 1
    assert fl["flap_cycles"] >= 2
    assert fl["max_watcher_migrations"] <= 2 * fl["flap_cycles"]
    assert fl["invariant_violations"] == 0
    for key in ("seed", "mode", "env"):
        assert key in d, key
    for key in ("platform", "jax", "device_count"):
        assert key in d["env"], f"env.{key}"
    assert d["env"]["device_count"] >= 1


def _fleet_bench_doc(seed: Optional[int]) -> dict:
    """Measure both fleet halves over one FleetFabric world and build
    the combined BENCH_FLEET document.  Everything runs on the SimClock
    (chaos schedules are replayable); only the headline merge rate is
    wall-clock."""
    import asyncio
    import shutil
    import tempfile

    from openr_tpu.common.runtime import SimClock
    from openr_tpu.emulation.fabric import FleetFabric
    from openr_tpu.sweep import SweepExecutor
    from openr_tpu.sweep.scenario import ScenarioSpec

    seed = 7 if seed is None else int(seed)
    params = {
        "drain_node_sets": [
            [], ["node5"], ["node7"], ["node3"], ["node11"], ["node13"],
        ],
        "metric_perturbations": [{"pattern": "node.*", "factor": 2.0}],
        "combo_k": 2,
        "max_combo_scenarios": 8,
        "combo_seed": seed,
    }
    root = tempfile.mkdtemp(prefix="bench_fleet_")

    def make_fabric(sub: str, **kw) -> "tuple":
        clock = SimClock()
        fab = FleetFabric(
            clock,
            spill_root=f"{root}/{sub}",
            node_names=FLEET_BENCH_NODES,
            n_side=FLEET_BENCH_SIDE,
            sweep_overrides={
                "shard_scenarios": 8, "inter_shard_pause_s": 0.05,
            },
            **kw,
        )
        return clock, fab

    async def drive_sweep(fab, clock, kill=False):
        """Pump one fleet sweep to completion; with ``kill``, crash the
        first member seen with a running sub-sweep (rendezvous decides
        who holds worlds under this grammar, so the victim is picked by
        observation, not by name)."""
        fab.coordinator.prepare(params)
        fab.coordinator.start()
        victim = None
        for _ in range(20000):
            await clock.run_for(0.05)
            st = fab.coordinator.status()
            if kill and victim is None:
                running = [
                    t["node"] for t in st["assignments"]
                    if t["state"] == "running"
                ]
                if running:
                    victim = running[0]
                    await fab.kill_node(victim)
            if fab.coordinator.state != "running":
                break
        assert fab.coordinator.state == "done", fab.coordinator.state
        if kill:
            assert victim is not None, "kill window never opened"
        s = fab.coordinator.summary()
        return (
            s["summary_digest"],
            fab.coordinator.manifest_bytes(),
            fab.coordinator.status(),
            victim,
        )

    async def sweep_half():
        # single-node reference: the same grammar through one executor
        clock, fab = make_fabric("single")
        fab.start()
        await clock.run_for(2.0)
        svc = fab.nodes["fab0"].sweep
        spec = ScenarioSpec.from_params(svc.config, params)
        ex = SweepExecutor(
            svc._inputs, f"{root}/single/ref", clock=clock,
            shard_scenarios=64,
        )
        ex.prepare(spec, resume=False)
        ex.run()
        single_digest = ex.reducer.summary_digest()
        await fab.stop()

        # the clean 3-node fleet run (wall-clocked for the headline)
        clock, fab = make_fabric("clean")
        fab.start()
        await clock.run_for(2.0)
        t0 = time.perf_counter()
        digest, manifest, st, _ = await drive_sweep(fab, clock)
        wall_s = time.perf_counter() - t0
        await fab.stop()

        # the chaos run: kill one member while its sub-sweep runs
        clock, fab = make_fabric("killed")
        fab.start()
        await clock.run_for(2.0)
        kdigest, kmanifest, kst, victim = await drive_sweep(
            fab, clock, kill=True
        )
        await fab.stop()
        return digest, manifest, {
            "nodes": len(FLEET_BENCH_NODES),
            "worlds": st["worlds_total"],
            "scenarios": st["scenarios_total"],
            "merge_wall_ms": round(wall_s * 1000.0, 1),
            "merged_scenarios_per_s": round(
                st["scenarios_total"] / wall_s, 1
            ),
            "single_node_digest": single_digest,
            "fleet_digest": digest,
            "summary_digest_equal": digest == single_digest,
            "kill": {
                "victim": victim,
                "repacked_worlds": kst["repacked_worlds"],
                "rounds": kst["rounds"],
                "digest_equal": kdigest == digest,
                "manifest_byte_identical": kmanifest == manifest,
            },
        }

    async def stream_scenario(sub: str, drain_instead: bool = False):
        clock, fab = make_fabric(sub)
        fab.start()
        await clock.run_for(2.0)
        n_watch = 12
        watchers = [
            fab.router.watch("route_db", {"node": f"node{i}"})
            for i in range(n_watch)
        ]
        await clock.run_for(1.0)
        fab.announce_prefix("node2", "10.99.0.0/24")
        await clock.run_for(2.0)
        placement = {}
        for w in watchers:
            placement.setdefault(w.serving_node, []).append(w)
        victim = max(placement, key=lambda n: len(placement[n]))
        if drain_instead:
            fab.drain_node(victim)
        else:
            await fab.kill_node(victim)
        await clock.run_for(1.0)
        fab.announce_prefix("node0", "10.98.0.0/24")
        await clock.run_for(2.0)
        out = {
            "watchers": n_watch,
            "victim": victim,
            "migrated_watchers": len(placement[victim]),
            "invariant_violations": fab.router.invariant_violations(),
            "pre_migration_generation_emissions": (
                fab.router.pre_migration_re_emissions()
            ),
            "log": b"\x00".join(w.log_bytes() for w in watchers),
        }
        if drain_instead:
            stats = fab.nodes[victim].streaming.stats()
            out["residual_subscribers"] = sum(
                f["subscribers"] for f in stats["feeds"]
            )
        await fab.stop()
        return out

    async def streaming_half():
        a = await stream_scenario("skill_a")
        b = await stream_scenario("skill_b")
        dr = await stream_scenario("sdrain", drain_instead=True)
        return {
            "watchers": a["watchers"],
            "victim": a["victim"],
            "migrated_watchers": a["migrated_watchers"],
            "invariant_violations": a["invariant_violations"],
            "pre_migration_generation_emissions": (
                a["pre_migration_generation_emissions"]
            ),
            "deterministic_replay": (
                a["victim"] == b["victim"] and a["log"] == b["log"]
            ),
            "drain": {
                "victim": dr["victim"],
                "migrated_watchers": dr["migrated_watchers"],
                "invariant_violations": dr["invariant_violations"],
                "residual_subscribers": dr["residual_subscribers"],
            },
        }

    # -- the ISSUE-20 liveness tier: compressed heartbeat timers so the
    #    suspicion machine runs its whole arc inside seconds of virtual
    #    time (the production defaults only stretch the same schedule)
    fast_liveness = {
        "heartbeat_interval_s": 0.1,
        "suspect_after_s": 0.25,
        "heartbeat_ttl_s": 0.5,
        "tick_s": 0.05,
    }

    async def detect_once(sub: str, k: int) -> float:
        """Kill one member UNANNOUNCED at a phase offset off the
        heartbeat grid and time how long heartbeat silence alone takes
        to conclude the death (suspect -> TTL expiry -> down)."""
        clock, fab = make_fabric(
            sub, liveness_overrides=dict(fast_liveness)
        )
        fab.start()
        await clock.run_for(2.0 + 0.013 + 0.037 * k)
        victim = FLEET_BENCH_NODES[k % len(FLEET_BENCH_NODES)]
        await fab.kill_node_unannounced(victim)
        t_kill = clock.now()
        t_detect = None
        for _ in range(400):
            await clock.run_for(0.01)
            if not fab.membership.is_live(victim):
                t_detect = clock.now()
                break
        assert t_detect is not None, "liveness never concluded the kill"
        await fab.stop()
        return round(t_detect - t_kill, 6)

    async def unannounced_scenario(sub: str) -> dict:
        """The detection-tier acceptance: a mid-sweep member killed
        with membership told NOTHING — heartbeat silence re-packs its
        worlds and migrates its watchers, digest/manifest byte-equal."""
        clock, fab = make_fabric(
            sub, liveness_overrides=dict(fast_liveness)
        )
        fab.start()
        await clock.run_for(2.0)
        watchers = [
            fab.router.watch("route_db", {"node": f"node{i}"})
            for i in range(8)
        ]
        await clock.run_for(1.0)
        fab.coordinator.prepare(params)
        fab.coordinator.start()
        victim = t_kill = t_detect = None
        for _ in range(20000):
            await clock.run_for(0.05)
            st = fab.coordinator.status()
            if victim is None:
                running = sorted(
                    t["node"] for t in st["assignments"]
                    if t["state"] == "running"
                )
                if running:
                    victim = running[0]
                    await fab.kill_node_unannounced(victim)
                    t_kill = clock.now()
            elif t_detect is None and not fab.membership.is_live(victim):
                t_detect = clock.now()
                # churn after detection: the migrated watchers must
                # keep applying deltas with the invariants intact
                fab.announce_prefix("node0", "10.95.0.0/24")
            if fab.coordinator.state != "running":
                break
        assert fab.coordinator.state == "done", fab.coordinator.state
        assert victim is not None and t_detect is not None
        await clock.run_for(1.0)
        st = fab.coordinator.status()
        out = {
            "victim": victim,
            "detection_s": round(t_detect - t_kill, 6),
            "suspects_seen": fab.counters.get("fleet.membership.suspect"),
            "repacked_worlds": st["repacked_worlds"],
            "digest": fab.coordinator.summary()["summary_digest"],
            "manifest": fab.coordinator.manifest_bytes(),
            "violations": fab.router.invariant_violations(),
            "re_emissions": fab.router.pre_migration_re_emissions(),
            "log": b"\x00".join(w.log_bytes() for w in watchers),
        }
        await fab.stop()
        return out

    async def split_brain_scenario(sub: str) -> dict:
        """Asymmetric partition: the victim's heartbeats stop REACHING
        the tracker while its services keep pushing — every stale-epoch
        delivery must be fenced, never applied, never doubled."""
        clock, fab = make_fabric(
            sub, liveness_overrides=dict(fast_liveness)
        )
        fab.start()
        await clock.run_for(2.0)
        watchers = [
            fab.router.watch("route_db", {"node": f"node{i}"})
            for i in range(12)
        ]
        await clock.run_for(1.0)
        placement = {}
        for w in watchers:
            placement.setdefault(w.serving_node, []).append(w)
        victim = max(sorted(placement), key=lambda n: len(placement[n]))
        fab.partition_asymmetric(victim)
        await clock.run_for(1.0)
        assert not fab.membership.is_live(victim)
        assert fab.nodes[victim].running  # daemon alive: asymmetric
        # churn: EVERY service pushes, including the stale owner
        fab.announce_prefix("node1", "10.94.0.0/24")
        await clock.run_for(1.0)
        out = {
            "victim": victim,
            "fenced_stream": fab.router.fenced_deliveries(),
            "violations": fab.router.invariant_violations(),
            "re_emissions": fab.router.pre_migration_re_emissions(),
        }
        fab.heal_partition(victim)
        await clock.run_for(1.0)
        out["healed_live"] = fab.membership.is_live(victim)
        out["stale_after_heal"] = (
            fab.router.status()["stale_subscriptions"]
        )
        fab.announce_prefix("node2", "10.93.0.0/24")
        await clock.run_for(1.0)
        out["violations"] = fab.router.invariant_violations()
        out["log"] = b"\x00".join(w.log_bytes() for w in watchers)
        await fab.stop()
        return out

    async def epoch_fence_scenario(sub: str) -> dict:
        """Dispatches stamped under a pre-kill epoch are refused by the
        receivers (counted, returned, never raised) and re-packed at
        the current epoch — the digest contract survives the fence."""
        clock, fab = make_fabric(sub)
        fab.start()
        await clock.run_for(2.0)
        fab.coordinator.prepare(params)
        holder = sorted({t.node for t in fab.coordinator.tasks})[0]
        await fab.kill_node(holder)
        fab.coordinator.start()
        for _ in range(20000):
            await clock.run_for(0.05)
            if fab.coordinator.state != "running":
                break
        assert fab.coordinator.state == "done", fab.coordinator.state
        st = fab.coordinator.status()
        out = {
            "fenced_worlds": st["fenced_worlds"],
            "sweep_fence_rejections": sum(
                f.counters.get("fleet.fenced.sweep_rejected") or 0
                for f in fab.nodes.values()
            ),
            "digest": fab.coordinator.summary()["summary_digest"],
            "manifest": fab.coordinator.manifest_bytes(),
        }
        await fab.stop()
        return out

    async def straggler_scenario(sub: str) -> dict:
        """The busiest member turns slow mid-round; its unfinished
        worlds re-pack past ``straggler_deadline_s`` WITHOUT declaring
        it dead, and merge reconciles first-committed-wins."""
        clock, fab = make_fabric(
            sub,
            # above the busiest member's natural round (~1.2s virtual:
            # half the 384-scenario grammar at 8/shard x 0.05s), below
            # the wedged member's never-finishing round
            coordinator_overrides={"straggler_deadline_s": 2.0},
        )
        fab.start()
        await clock.run_for(2.0)
        fab.coordinator.prepare(params)
        counts = {}
        for t in fab.coordinator.tasks:
            counts[t.node] = counts.get(t.node, 0) + len(t.worlds)
        slow = max(sorted(counts), key=lambda n: counts[n])
        fab.nodes[slow].sweep.config.inter_shard_pause_s = 60.0
        fab.coordinator.start()
        for _ in range(20000):
            await clock.run_for(0.05)
            if fab.coordinator.state != "running":
                break
        assert fab.coordinator.state == "done", fab.coordinator.state
        st = fab.coordinator.status()
        out = {
            "straggler": slow,
            "straggler_repacks": st["straggler_repacks"],
            "repacked_worlds": st["straggler_repacked_worlds"],
            "duplicate_completions": st["duplicate_completions"],
            "digest": fab.coordinator.summary()["summary_digest"],
            "manifest": fab.coordinator.manifest_bytes(),
        }
        await fab.stop()
        return out

    async def gray_scenario(sub: str) -> dict:
        """Gray failure: heartbeats keep flowing while the victim's
        sweep ctrl surface raises on every touch — the breaker + strike
        policy demotes it to drained, the survivors finish."""
        clock, fab = make_fabric(sub)
        fab.start()
        await clock.run_for(2.0)
        fab.coordinator.prepare(params)
        fab.coordinator.start()
        victim = None
        for _ in range(20000):
            await clock.run_for(0.05)
            st = fab.coordinator.status()
            if victim is None:
                running = sorted(
                    t["node"] for t in st["assignments"]
                    if t["state"] == "running"
                )
                if running:
                    victim = running[0]
                    fab.gray_sweep_failure(victim)
            if fab.coordinator.state != "running":
                break
        assert fab.coordinator.state == "done", fab.coordinator.state
        assert victim is not None
        firing = fab.membership.health_firing()
        out = {
            "victim": victim,
            "demotions": fab.counters.get("fleet.gray.demotions"),
            "ctrl_errors": fab.counters.get("fleet.ctrl.errors"),
            "crashes": fab.counters.get("fleet.crash") or 0,
            "drained_still_up": (
                fab.membership.is_up(victim)
                and not fab.membership.is_live(victim)
            ),
            "ticket_firing": "fleet_gray_failure" in firing,
            "digest": fab.coordinator.summary()["summary_digest"],
            "manifest": fab.coordinator.manifest_bytes(),
        }
        await fab.stop()
        return out

    async def flap_scenario(sub: str) -> dict:
        """A member bouncing inside the flap window is DAMPED with an
        exponential hold, bounding ownership churn to <=2 moves per
        flap cycle (one out, one back)."""
        cycles = 2
        clock, fab = make_fabric(
            sub,
            liveness_overrides={
                **fast_liveness,
                "flap_hold_base_s": 1.0,
                "flap_hold_max_s": 4.0,
                "flap_window_s": 30.0,
            },
        )
        fab.start()
        await clock.run_for(2.0)
        watchers = [
            fab.router.watch("route_db", {"node": f"node{i}"})
            for i in range(12)
        ]
        await clock.run_for(1.0)
        placement = {}
        for w in watchers:
            placement.setdefault(w.serving_node, []).append(w)
        victim = max(sorted(placement), key=lambda n: len(placement[n]))
        epoch0 = fab.membership.epoch
        for _ in range(cycles):
            fab.heartbeat_stall(victim)
            await clock.run_for(0.8)  # past the TTL: down
            fab.heal_heartbeat(victim)
            await clock.run_for(0.3)
        # ride out the exponential hold of the damped rejoin, plus the
        # tick that readmits once the hold expires with beats flowing
        await clock.run_for(3.0)
        assert fab.membership.is_live(victim)
        out = {
            "victim": victim,
            "flap_cycles": cycles,
            "flap_damped": fab.counters.get("fleet.flap_damped"),
            "epoch_bumps": fab.membership.epoch - epoch0,
            "max_watcher_migrations": max(
                w.migrations for w in watchers
            ),
            "violations": fab.router.invariant_violations(),
        }
        await fab.stop()
        return out

    async def liveness_half(clean_digest, clean_manifest) -> dict:
        det = [
            await detect_once(f"live_det{k}", k) for k in range(5)
        ]
        det_sorted = sorted(det)
        uk_a = await unannounced_scenario("live_uk_a")
        uk_b = await unannounced_scenario("live_uk_b")
        sb_a = await split_brain_scenario("live_sb_a")
        sb_b = await split_brain_scenario("live_sb_b")
        fe = await epoch_fence_scenario("live_fence")
        sg = await straggler_scenario("live_strag")
        gr = await gray_scenario("live_gray")
        fl = await flap_scenario("live_flap")
        return {
            "heartbeat": {
                "interval_s": fast_liveness["heartbeat_interval_s"],
                "suspect_after_s": fast_liveness["suspect_after_s"],
                "ttl_s": fast_liveness["heartbeat_ttl_s"],
                "tick_s": fast_liveness["tick_s"],
            },
            "detection": {
                "samples": len(det),
                "p50_s": det_sorted[len(det_sorted) // 2],
                "max_s": det_sorted[-1],
                # TTL from the last pre-kill beat + one tracker tick +
                # the harness sampling step
                "bound_s": round(
                    fast_liveness["heartbeat_ttl_s"]
                    + fast_liveness["tick_s"]
                    + 0.02,
                    6,
                ),
            },
            "unannounced_kill": {
                "victim": uk_a["victim"],
                "detection_s": uk_a["detection_s"],
                "suspects_seen": uk_a["suspects_seen"],
                "repacked_worlds": uk_a["repacked_worlds"],
                "digest_equal": uk_a["digest"] == clean_digest,
                "manifest_byte_identical": (
                    uk_a["manifest"] == clean_manifest
                ),
                "invariant_violations": uk_a["violations"],
                "pre_migration_generation_emissions": (
                    uk_a["re_emissions"]
                ),
                "deterministic_replay": (
                    uk_a["victim"] == uk_b["victim"]
                    and uk_a["detection_s"] == uk_b["detection_s"]
                    and uk_a["digest"] == uk_b["digest"]
                    and uk_a["manifest"] == uk_b["manifest"]
                    and uk_a["log"] == uk_b["log"]
                ),
            },
            "split_brain": {
                "victim": sb_a["victim"],
                "fenced_stream_deliveries": sb_a["fenced_stream"],
                "invariant_violations": sb_a["violations"],
                "double_pushes": sb_a["re_emissions"],
                "healed_rejoined": sb_a["healed_live"],
                "healed_stale_subscriptions": sb_a["stale_after_heal"],
                "deterministic_replay": (
                    sb_a["victim"] == sb_b["victim"]
                    and sb_a["log"] == sb_b["log"]
                ),
            },
            "epoch_fence": {
                "fenced_worlds": fe["fenced_worlds"],
                "sweep_fence_rejections": fe["sweep_fence_rejections"],
                "digest_equal": fe["digest"] == clean_digest,
                "manifest_byte_identical": (
                    fe["manifest"] == clean_manifest
                ),
            },
            "straggler": {
                "straggler": sg["straggler"],
                "straggler_repacks": sg["straggler_repacks"],
                "repacked_worlds": sg["repacked_worlds"],
                "duplicate_completions": sg["duplicate_completions"],
                "digest_equal": sg["digest"] == clean_digest,
                "manifest_byte_identical": (
                    sg["manifest"] == clean_manifest
                ),
            },
            "gray_failure": {
                "victim": gr["victim"],
                "demotions": gr["demotions"],
                "ctrl_errors": gr["ctrl_errors"],
                "coordinator_crashes": gr["crashes"],
                "drained_still_up": gr["drained_still_up"],
                "ticket_firing": gr["ticket_firing"],
                "digest_equal": gr["digest"] == clean_digest,
            },
            "flap": {
                "victim": fl["victim"],
                "flap_cycles": fl["flap_cycles"],
                "flap_damped": fl["flap_damped"],
                "epoch_bumps": fl["epoch_bumps"],
                "max_watcher_migrations": fl["max_watcher_migrations"],
                "invariant_violations": fl["violations"],
            },
        }

    try:
        clean_digest, clean_manifest, sweep_detail = asyncio.run(
            sweep_half()
        )
        streaming_detail = asyncio.run(streaming_half())
        liveness_detail = asyncio.run(
            liveness_half(clean_digest, clean_manifest)
        )
    finally:
        shutil.rmtree(root, ignore_errors=True)
    return {
        "metric": "fleet_sweep_merged_scenarios_per_s_3node",
        "value": sweep_detail["merged_scenarios_per_s"],
        "unit": "scenarios/s",
        "detail": {
            "sweep": sweep_detail,
            "streaming": streaming_detail,
            "liveness": liveness_detail,
            "seed": seed,
            "mode": (
                "3 fleet members (serving+streaming+sweep) over one "
                "shared scalar decision on a grid16 LSDB, SimClock; "
                "content-derived world assignment (rendezvous over the "
                "scenario-set hash), sub-sweeps merged through the "
                "feed-order-independent reducer; chaos = mid-sweep "
                "member kill + mid-stream kill/drain via the fleet "
                "membership plane, plus the ISSUE-20 liveness tier "
                "(compressed heartbeat timers): unannounced kill, "
                "asymmetric partition, stale-epoch fencing, straggler "
                "re-pack, gray-failure demotion, flap damping"
            ),
            "env": env_stamp(),
        },
    }


def fleet_sweep_main(seed: Optional[int] = None) -> None:
    """Fleet compute-fabric benchmark (BENCH_FLEET_r*), sweep-first
    entry point.  The fabric's two halves share the membership/
    directory core, so either entry point measures BOTH and emits the
    one combined artifact — benching a half alone would skip exactly
    the coupling the acceptance gates (a membership transition must
    re-pack worlds AND migrate watchers off the same event)."""
    doc = _fleet_bench_doc(seed)
    try:
        validate_fleet_bench(doc)
    except AssertionError:
        print(json.dumps(doc), file=sys.stderr, flush=True)
        raise
    print(json.dumps(doc))


def fleet_streaming_main(seed: Optional[int] = None) -> None:
    """Fleet compute-fabric benchmark (BENCH_FLEET_r*), streaming-first
    entry point — same combined measurement as --fleet-sweep (see
    fleet_sweep_main for why the halves are never benched apart)."""
    fleet_sweep_main(seed)


def fleet_liveness_main(seed: Optional[int] = None) -> None:
    """Fleet compute-fabric benchmark (BENCH_FLEET_r*), liveness-first
    entry point — same combined measurement as --fleet-sweep: the
    liveness tier's kill-detection/fencing/straggler/gray/flap
    scenarios share the membership plane the other halves gate, so the
    one artifact carries all three sections."""
    fleet_sweep_main(seed)


def main() -> None:
    t_start = time.time()
    from openr_tpu.ops.platform_env import enable_persistent_compile_cache

    enable_persistent_compile_cache()
    from openr_tpu.ops.native_spf import NativeSpf
    from openr_tpu.ops.whatif import LinkFailureSweep

    import jax

    # ---- the 1024-node WAN + 10,240 perturbations ------------------------
    n_nodes = 1024
    total = 10_240
    ls, topo, cands = build_headline_world(n_nodes)
    rng = np.random.default_rng(0)
    fails = rng.integers(0, len(topo.links), size=total).astype(np.int32)

    # ---- native C++ single-threaded baselines (median of N + spread) -----
    native = NativeSpf(topo, "node0")
    native.sweep(fails[:32])  # warm caches
    naive_times = []
    for _ in range(NATIVE_REPS):
        t0 = time.perf_counter()
        native.sweep(fails)
        naive_times.append(time.perf_counter() - t0)
    native_naive_s = statistics.median(naive_times)
    native_sps = total / native_naive_s
    uniq = np.unique(fails)
    dedup_times = []
    for _ in range(NATIVE_REPS):
        t0 = time.perf_counter()
        native.sweep(uniq)
        dedup_times.append(time.perf_counter() - t0)
    native_dedup_sps = total / statistics.median(dedup_times)
    # native warm-start: same incremental-repair trick as the device
    native.warm_prepare()
    native.warm_sweep(fails[:32])
    warm_times = []
    for _ in range(NATIVE_REPS):
        t0 = time.perf_counter()
        native.warm_sweep(fails)
        warm_times.append(time.perf_counter() - t0)
    native_warm_sps = total / statistics.median(warm_times)

    # ---- native ENGINE end to end: the operator alternative --------------
    # C++ warm-start sweep per unique on-DAG failure + numpy selection +
    # diff vs the base route table — exactly what the Decision what-if
    # API runs when it picks the native engine, with the same dedup and
    # off-DAG-alias courtesies the device pipeline gets (an off-DAG
    # failure provably changes no routes).  This is the most demanding
    # apples-to-apples denominator: same algorithm, same output.
    from openr_tpu.ops.np_select import select_routes_numpy
    from openr_tpu.ops.whatif import root_lane_count

    sel_args_np = (
        cands.cand_node,
        cands.cand_ok,
        cands.drain_metric,
        cands.path_pref,
        cands.source_pref,
        cands.distance,
        cands.min_nexthop,
    )
    soft_np = np.zeros(topo.padded_nodes, np.int32)
    root_np = topo.node_id("node0")
    D_eng = root_lane_count(topo, root_np)  # == LinkFailureSweep.D
    uniq_on = uniq[native.link_on_dag[uniq].astype(bool)]
    bdist_n, bmask_n = native.warm_base
    blanes_n = native.lanes_dense(D_eng, mask=bmask_n)
    bvalid, bmetric, bnh, _, _ = select_routes_numpy(
        *sel_args_np, bdist_n, blanes_n, topo.overloaded, soft_np, root_np
    )
    native_e2e_times = []
    native_route_deltas = 0
    for _ in range(NATIVE_REPS):
        t0 = time.perf_counter()
        native_route_deltas = 0
        for link in uniq_on:
            native.warm_sweep(
                np.asarray([link], np.int32), keep_last=True
            )
            lanes = native.lanes_dense(D_eng)
            v, m, nh, _n, _u = select_routes_numpy(
                *sel_args_np, native.dist, lanes,
                topo.overloaded, soft_np, root_np,
            )
            changed = (v != bvalid) | (
                v & bvalid & (
                    (m != bmetric) | (nh != bnh).any(axis=1)
                )
            )
            native_route_deltas += int(changed.sum())
        native_e2e_times.append(time.perf_counter() - t0)
    native_e2e_sps = total / statistics.median(native_e2e_times)

    # ---- pure-Python oracle (round-1's flattering denominator) -----------
    ls.run_spf("node0", links_to_ignore=frozenset([topo.links[0]]))
    best = float("inf")
    for rep in range(3):
        t0 = time.perf_counter()
        for i in range(8):
            link = topo.links[int(fails[rep * 8 + i])]
            ls.run_spf("node0", links_to_ignore=frozenset([link]))
        best = min(best, (time.perf_counter() - t0) / 8)
    python_sps = 1.0 / best

    # ---- device: engine setup (base solve + repair plan) -----------------
    import jax.numpy as jnp

    from openr_tpu.parallel.mesh import make_mesh

    mesh = make_mesh()  # all local devices (1 on the bench chip) —
    # the SAME shard_map path dryrun_multichip runs on 8
    eng = LinkFailureSweep(topo, "node0", mesh=mesh)
    t0 = time.perf_counter()
    eng.base_solve()
    base_solve_ms = (time.perf_counter() - t0) * 1000
    t0 = time.perf_counter()
    eng.plan()
    plan_build_ms = (time.perf_counter() - t0) * 1000
    rs = eng.repair_sweep()

    # measure the dispatch sync cost once, for the detail split
    (jnp.zeros(8) + 1).block_until_ready()
    t0 = time.perf_counter()
    (jnp.zeros(8) + 1).block_until_ready()
    sync_ms = (time.perf_counter() - t0) * 1000

    # ---- device raw: every snapshot solved via the repair kernel ---------
    from openr_tpu.ops.repair import sort_by_depth

    chunk = 4096
    g = eng.batch_granularity
    sfails, _ = sort_by_depth(eng.plan(), fails)

    def raw_sweep(fl):
        outs = []
        for off in range(0, total, chunk):
            c = fl[off : off + chunk]
            if len(c) % g:
                c = np.concatenate(
                    [c, np.full(g - len(c) % g, -1, np.int32)]
                )
            outs.append(rs.solve(c))
        return outs

    outs = raw_sweep(sfails)
    # jit warm-up, excluded from the timer — including the per-rep
    # reduction kernels the barrier below uses (their first-call
    # compiles would otherwise land inside the timed region)
    jax.block_until_ready(
        [jax.tree.map(lambda a: a.sum(), o) for o in outs]
    )
    t0 = time.perf_counter()
    rep_sums = []
    for _ in range(DEVICE_REPS):
        outs = raw_sweep(sfails)
        # per-rep scalar reductions: their readiness implies every chunk
        # of the rep completed (a last-buffer-only barrier can report a
        # nonsense rate if a runtime signals a later buffer early),
        # without keeping all reps' full-size outputs live on device
        # inside the timed region
        rep_sums.append(
            [jax.tree.map(lambda a: a.sum(), o) for o in outs]
        )
    jax.block_until_ready(rep_sums)
    device_raw_sps = DEVICE_REPS * total / (time.perf_counter() - t0)
    raw_rounds = [
        (int(np.max(o[2])), int(np.max(o[3]))) for o in outs
    ]  # per-device maxima under the sharded kernel

    # ---- device cold kernel (round-2's raw path, for transparency) -------
    from openr_tpu.ops.spf import sweep_spf_link_failures

    D_cold = topo.max_out_degree()
    cold_args = (
        jnp.asarray(topo.src),
        jnp.asarray(topo.dst),
        jnp.asarray(topo.w),
        jnp.asarray(topo.edge_ok),
        jnp.asarray(topo.link_index),
    )
    ovl = jnp.asarray(topo.overloaded)
    root = jnp.int32(topo.node_id("node0"))

    def cold_sweep():
        last = None
        for off in range(0, total, 2048):
            f = jnp.asarray(fails[off : off + 2048])
            d, nh = sweep_spf_link_failures(
                *cold_args, f, ovl, root, max_degree=D_cold, packed=True
            )
            last = d
        return last

    cold_sweep().block_until_ready()
    t0 = time.perf_counter()
    last = None
    for _ in range(DEVICE_REPS):
        last = cold_sweep()
    last.block_until_ready()
    device_cold_sps = DEVICE_REPS * total / (time.perf_counter() - t0)

    # ---- device: SPF-tables-only engine throughput (detail line) ---------
    res = eng.run(fails, fetch=False)
    res.block()  # warm-up (compiles the bucket shapes)
    t0 = time.perf_counter()
    results = [eng.run(fails, fetch=False) for _ in range(DEVICE_REPS)]
    results[-1].block()
    engine_sps = DEVICE_REPS * total / (time.perf_counter() - t0)
    # single-shot latency (what one cold rebuild tick would see)
    t0 = time.perf_counter()
    single = eng.run(fails, fetch=False)
    single.block()
    engine_latency_ms = (time.perf_counter() - t0) * 1000

    # ---- THE HEADLINE: sweep -> route deltas, end to end -----------------
    # (ops/sweep_select.py): 1024 loopback prefixes selected against every
    # snapshot ON DEVICE, diffed vs the base route table on device, only
    # changed route rows reach the host; every chunk's selection kernel
    # is dispatched before the first blocking fetch so selection of chunk
    # k overlaps SPF of chunk k+1
    from openr_tpu.ops.sweep_select import SweepRouteSelector

    sel = SweepRouteSelector(
        topo,
        "node0",
        cands,
        max_degree=eng.D,
        mesh=mesh,
    )
    deltas = sel.run(single)  # warm-up (compiles chunk + compact shapes)
    # single-shot latency: what ONE operator sweep experiences
    t0 = time.perf_counter()
    deltas = sel.run(eng.run(fails, fetch=False))
    routes_pipeline_ms = (time.perf_counter() - t0) * 1000
    # steady-state throughput: PIPELINE_DEPTH sweeps in flight via
    # sel.start()/finish() — selection+compaction fetches ride
    # copy_to_host_async, so the device round trip overlaps the
    # following sweeps' SPF+selection instead of serializing after them
    # (the continuous-what-if-service shape)
    # the two end-to-end pipelines must find the IDENTICAL delta count —
    # computed independently (C++ sweep + numpy select vs device repair
    # kernel + on-device select + fused compaction); asserted on the
    # same failure set the native engine ran
    assert int(deltas.num_deltas) == native_route_deltas, (
        deltas.num_deltas,
        native_route_deltas,
    )
    # steady-state reps use FRESH random failure sets each (r4 review
    # weak #5: one reused set flatters caching; the 3-minute soak's
    # honest fresh-sets number now IS the committed headline's shape)
    PIPELINE_DEPTH = 4
    e2e_reps = 12
    rng_reps = np.random.default_rng(20260730)
    # rep 0 re-runs the native engine's failure set so the ASYNC
    # (copy_to_host_async) pipeline path stays correctness-validated
    # against the native delta count, not just the synchronous run
    rep_fails = [fails] + [
        rng_reps.integers(0, len(topo.links), size=total).astype(np.int32)
        for _ in range(e2e_reps - 1)
    ]
    t0 = time.perf_counter()
    pend = []
    finished = []
    for r in range(e2e_reps):
        sw = eng.run(rep_fails[r], fetch=False)
        pend.append(sel.start(sw))
        if len(pend) >= PIPELINE_DEPTH:
            finished.append(pend.pop(0).finish())
    while pend:
        finished.append(pend.pop(0).finish())
    e2e_sps = e2e_reps * total / (time.perf_counter() - t0)
    assert int(finished[0].num_deltas) == native_route_deltas, (
        finished[0].num_deltas,
        native_route_deltas,
    )
    # sanity on every fresh-set rep: a 10k random sweep of this world
    # always changes SOME routes, and can never exceed the full table
    assert all(
        0 < int(d.num_deltas) <= total * n_nodes for d in finished
    ), [int(d.num_deltas) for d in finished]

    # route parity vs native for sample snapshots (base + changed rows)
    for s in (3, 1007, 9000):
        native.solve(failed_link=int(fails[s]))
        valid, metric, lanes = deltas.routes_of(s)
        nd = native.dist[:n_nodes]
        nl = native.lanes_dense(eng.D)[:n_nodes]
        # valid = advertiser reachable with a first-hop set, and not the
        # root's own prefix (skip-if-self)
        exp_valid = (
            np.isfinite(nd)
            & nl.any(axis=1)
            & (np.arange(n_nodes) != topo.node_id("node0"))
        )
        assert np.array_equal(valid, exp_valid), f"route valid parity {s}"
        assert np.array_equal(metric[exp_valid], nd[exp_valid]), (
            f"route metric parity {s}"
        )
        assert np.array_equal(lanes[exp_valid], nl[exp_valid]), (
            f"route lane parity {s}"
        )

    # host fetch of the unique tables (transfer-bound; reported, not part
    # of the throughput number — the routes pipeline above is what
    # downstream consumes; this line kept for the before/after contrast)
    t0 = time.perf_counter()
    single.materialize()
    fetch_ms = (time.perf_counter() - t0) * 1000

    # ---- parity: device results == native results ------------------------
    for s in (3, 1007, 9000):
        native.solve(failed_link=int(fails[s]))
        finite = np.isfinite(native.dist)
        assert np.array_equal(
            native.dist[finite], single.dist_of(s)[finite]
        ), f"distance parity failure at snapshot {s}"
        assert np.array_equal(
            native.lanes_dense(eng.D)[finite], single.nh_of(s)[finite]
        ), f"lane parity failure at snapshot {s}"

    def spread(ts):
        return {
            "median_s": round(statistics.median(ts), 4),
            "min_s": round(min(ts), 4),
            "max_s": round(max(ts), 4),
            "reps": len(ts),
        }

    print(
        json.dumps(
            {
                "metric": "whatif_routes_end_to_end_per_sec_10k_x_1024node",
                "value": round(e2e_sps, 1),
                "unit": "snapshots/s",
                "vs_baseline": round(e2e_sps / native_sps, 2),
                "detail": {
                    "native_cxx_solves_per_sec": round(native_sps, 1),
                    "native_naive_spread": spread(naive_times),
                    "native_cxx_dedup_effective_per_sec": round(
                        native_dedup_sps, 1
                    ),
                    "native_warmstart_solves_per_sec": round(
                        native_warm_sps, 1
                    ),
                    "native_warm_spread": spread(warm_times),
                    "native_engine_routes_per_sec": round(
                        native_e2e_sps, 1
                    ),
                    "native_engine_spread": spread(native_e2e_times),
                    "native_engine_route_deltas": int(native_route_deltas),
                    "vs_native_engine_e2e": round(
                        e2e_sps / native_e2e_sps, 2
                    ),
                    "python_solves_per_sec": round(python_sps, 1),
                    "device_spf_tables_per_sec": round(engine_sps, 1),
                    "device_raw_solves_per_sec": round(device_raw_sps, 1),
                    "device_cold_solves_per_sec": round(device_cold_sps, 1),
                    "vs_native_spf_tables_only": round(
                        engine_sps / native_sps, 2
                    ),
                    "vs_native_raw_kernel_only": round(
                        device_raw_sps / native_sps, 2
                    ),
                    "vs_native_cold_kernel": round(
                        device_cold_sps / native_sps, 2
                    ),
                    "vs_native_dedup": round(e2e_sps / native_dedup_sps, 2),
                    "vs_native_warmstart": round(
                        e2e_sps / native_warm_sps, 2
                    ),
                    "vs_python": round(e2e_sps / python_sps, 2),
                    "engine_latency_ms": round(engine_latency_ms, 1),
                    "base_solve_ms": round(base_solve_ms, 1),
                    "repair_plan_build_ms": round(plan_build_ms, 1),
                    "routes_pipeline_ms": round(routes_pipeline_ms, 1),
                    "pipeline_depth": PIPELINE_DEPTH,
                    "route_deltas": int(deltas.num_deltas),
                    "route_delta_fetch_bytes": int(deltas.fetch_bytes),
                    "host_fetch_unique_tables_ms": round(fetch_ms, 1),
                    "dispatch_sync_ms": round(sync_ms, 1),
                    "unique_device_solves": int(single.num_device_solves),
                    "on_dag_link_fraction": round(
                        float(eng.on_dag_links().mean()), 3
                    ),
                    "raw_chunk_rounds_dist_lanes": raw_rounds,
                    "batch_total": total,
                    "nodes": n_nodes,
                    "directed_edges": topo.num_edges,
                    "lanes": eng.D,
                    "mesh_devices": int(mesh.devices.size),
                    "devices": [str(d) for d in jax.devices()],
                    "env": env_stamp(),
                    "fresh_failure_sets_per_rep": True,
                    "wall_s": round(time.time() - t_start, 1),
                },
            }
        )
    )


class _Tee:
    """stdout tee for --out: bench modes print exactly one JSON artifact
    line to stdout (progress goes to stderr), so mirroring stdout into
    the artifact file gives every mode shared output-path handling."""

    def __init__(self, *streams) -> None:
        self._streams = streams

    def write(self, data: str) -> int:
        n = 0
        for s in self._streams:
            n = s.write(data)
        return n

    def flush(self) -> None:
        for s in self._streams:
            s.flush()


#: one dispatch table for every bench mode — a new mode registers here
#: (and nowhere else) and inherits the shared env_stamp/--seed/--out
#: handling.  Values: (runner, default_seed_note, help text).  EVERY
#: runner accepts ``seed=None``; None reproduces the mode's historical
#: defaults (noted here), so checked-in artifacts regenerate unchanged
#: when --seed is omitted.
BENCH_MODES = {
    "convergence": (convergence_main, "canonical flap order", "9-node flap convergence percentiles (virtual time)"),
    "serving": (serving_main, "world 11", "micro-batched serving plane vs unbatched scalar"),
    "multichip-serving": (multichip_serving_main, "world 11", "fleet serving over a 1/2/4/8-chip DevicePool"),
    "pipeline": (pipeline_main, "flip victim node0", "phase-level attribution of the grid4096 rebuild"),
    "resilience": (resilience_main, "world 11, SDC scenario 7", "shadow-verification overhead + seeded SDC scenario"),
    "health": (health_main, "world 11, detection (7,11,13)", "fleet health sweep overhead + detection latency"),
    "warm-start": (warmstart_main, "perturbations 7", "generation-delta warm rebuild vs cold + native warm sweep"),
    "suite": (suite_main, "sweeps 7", "topology-class trajectory: seeded chaos sweeps at 1k+ nodes per class"),
    "rolling": (rolling_main, "sweep 11", "rolling-restart survival: every node bounced once, structural warm-hit + SLO hold"),
    "streaming": (streaming_main, "sweep 11", "watch-plane fan-out: 10k+ subscriber churn under chaos, snapshot+delta generation correctness"),
    "sweep": (sweep_main, "grammar 7", "capacity-planning sweep: 100k+ scenarios on grid4096, sharded/spilled/resumable, ranked risk summary"),
    "frr": (frr_main, "flap sample 7", "fast-reroute protection tier: protected-flap publication→FIB percentiles vs the warm path on grid4096"),
    "fleet-sweep": (fleet_sweep_main, "grammar 7", "fleet fabric: 3-node sharded sweep digest parity + mid-sweep kill repack (emits the combined fleet artifact)"),
    "fleet-streaming": (fleet_streaming_main, "grammar 7", "fleet fabric: consistent-hash watcher migration under kill/drain (emits the combined fleet artifact)"),
    "fleet-liveness": (fleet_liveness_main, "grammar 7", "fleet liveness: heartbeat kill-detection latency, epoch fencing, straggler/gray digest parity, flap damping (emits the combined fleet artifact)"),
}


def _cli(argv) -> int:
    import argparse

    parser = argparse.ArgumentParser(
        prog="bench.py",
        description=(
            "openr-tpu benchmark suite.  With no mode flag, runs the "
            "headline 10k x 1024-node what-if sweep."
        ),
    )
    group = parser.add_mutually_exclusive_group()
    for name, (_fn, _seed_note, help_text) in BENCH_MODES.items():
        group.add_argument(
            f"--{name}",
            dest=name.replace("-", "_"),
            action="store_true",
            help=help_text,
        )
    group.add_argument(
        "--list-modes",
        action="store_true",
        help="list every bench mode with its default-seed behavior",
    )
    parser.add_argument(
        "--out",
        metavar="PATH",
        default=None,
        help="also write the emitted JSON line(s) to PATH",
    )
    parser.add_argument(
        "--seed",
        type=int,
        default=None,
        help=(
            "world/perturbation seed (every mode takes one; omitted = "
            "the mode's historical default, so checked-in artifacts "
            "regenerate unchanged)"
        ),
    )
    args = parser.parse_args(argv)
    if args.list_modes:
        width = max(len(n) for n in BENCH_MODES)
        for name, (_fn, seed_note, help_text) in BENCH_MODES.items():
            print(
                f"--{name:<{width}}  {help_text}  "
                f"[default seed: {seed_note}]"
            )
        return 0
    runner = main
    for name, (fn, _seed_note, _help) in BENCH_MODES.items():
        if getattr(args, name.replace("-", "_")):
            runner = lambda fn=fn, s=args.seed: fn(seed=s)  # noqa: E731
            break
    if args.out:
        with open(args.out, "w") as f:
            real = sys.stdout
            sys.stdout = _Tee(real, f)
            try:
                return runner() or 0
            finally:
                sys.stdout = real
    return runner() or 0


if __name__ == "__main__":
    sys.exit(_cli(sys.argv[1:]))
