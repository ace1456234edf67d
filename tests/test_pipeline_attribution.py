"""Pipeline attribution (ISSUE 7 tentpole): the phase registry, the
PipelineProbe, the instrumented dispatch lifecycle in the Decision
backend and the fleet/what-if engines, per-chip busy gauges, and the
per-device Chrome-trace lanes."""

import pytest

from openr_tpu.common.runtime import CounterMap, SimClock
from openr_tpu.config import ParallelConfig, ResilienceConfig
from openr_tpu.decision.link_state import LinkState
from openr_tpu.decision.prefix_state import PrefixState
from openr_tpu.decision.spf_solver import SpfSolver
from openr_tpu.emulation.topology import build_adj_dbs, ring_edges
from openr_tpu.tracing import PipelineProbe, Tracer, disabled_probe, pipeline
from openr_tpu.types import PrefixEntry

pytestmark = pytest.mark.multichip


def make_world(n=12):
    ls = LinkState("0")
    for db in build_adj_dbs(ring_edges(n)).values():
        ls.update_adjacency_database(db)
    ps = PrefixState()
    for i in range(n):
        ps.update_prefix(f"node{i}", "0", PrefixEntry(f"10.7.{i}.0/24"))
    return {"0": ls}, ps


def make_backend(clock=None, counters=None, tracer=None):
    from openr_tpu.decision.backend import TpuBackend

    return TpuBackend(
        SpfSolver("node0"),
        clock=clock,
        counters=counters,
        tracer=tracer,
        resilience=ResilienceConfig(enabled=False),
        parallel=ParallelConfig(min_shard_rows=0),
    )


# ---------------------------------------------------------------------------
# the registry
# ---------------------------------------------------------------------------


def test_registry_names_and_validation():
    assert pipeline.span_name(pipeline.DECODE) == "pipeline.decode"
    assert pipeline.hist_key(pipeline.HOST_FETCH) == "pipeline.host_fetch.ms"
    with pytest.raises(ValueError):
        pipeline.span_name("decod")
    with pytest.raises(ValueError):
        pipeline.hist_key("not_a_phase")
    # host/device split covers the registry exactly, with no overlap
    assert set(pipeline.HOST_PHASES) | set(pipeline.DEVICE_PHASES) == set(
        pipeline.PHASES
    )
    assert not set(pipeline.HOST_PHASES) & set(pipeline.DEVICE_PHASES)


def test_warm_phases_registered():
    """ISSUE-9 satellite: the warm-start rebuild phases are first-class
    registry members (hist/span spellings come from the registry, the
    host/device split covers them, and the cold-lifecycle bench treats
    them as optional coverage via WARM_PHASES)."""
    assert pipeline.WARM_PLAN in pipeline.PHASES
    assert pipeline.WARM_REPAIR in pipeline.PHASES
    assert set(pipeline.WARM_PHASES) == {
        pipeline.WARM_PLAN, pipeline.WARM_REPAIR
    }
    assert pipeline.span_name(pipeline.WARM_REPAIR) == "pipeline.warm_repair"
    assert pipeline.hist_key(pipeline.WARM_PLAN) == "pipeline.warm_plan.ms"
    assert pipeline.WARM_PLAN in pipeline.HOST_PHASES
    assert pipeline.WARM_REPAIR in pipeline.DEVICE_PHASES


def test_warm_rebuild_records_warm_phases():
    """A warm generation-delta rebuild lands samples under BOTH warm
    phases (plus the shared lifecycle phases), so BENCH_PIPELINE-style
    attribution stays fully explained on warm ticks."""
    from openr_tpu.decision.link_state import LinkState
    from openr_tpu.decision.prefix_state import PrefixState
    from openr_tpu.emulation.topology import build_adj_dbs

    clock = SimClock()
    counters = CounterMap()
    from openr_tpu.decision.backend import TpuBackend
    from openr_tpu.decision.spf_solver import SpfSolver

    backend = TpuBackend(
        SpfSolver("node0"),
        clock=clock,
        counters=counters,
        resilience=ResilienceConfig(enabled=False),
        parallel=ParallelConfig(max_devices=1),
    )
    adj = build_adj_dbs(ring_edges(8))
    ls = LinkState("0", "node0")
    for db in adj.values():
        ls.update_adjacency_database(db)
    ps = PrefixState()
    for i in range(8):
        ps.update_prefix(f"node{i}", "0", PrefixEntry(f"10.9.{i}.0/24"))
    als = {"0": ls}
    backend.build_route_db(als, ps, force_full=True)
    for phase in pipeline.WARM_PHASES:
        h = counters.histogram(pipeline.hist_key(phase))
        assert h is None or h.count == 0  # cold build: no warm samples
    adj["node2"].adjacencies[0].metric = 5
    ls.update_adjacency_database(adj["node2"])
    backend.build_route_db(
        als, ps, changed_prefixes=set(), force_full=True, warm_delta=True
    )
    assert backend.num_warm_builds == 1
    for phase in pipeline.WARM_PHASES:
        h = counters.histogram(pipeline.hist_key(phase))
        assert h is not None and h.count >= 1, phase


def test_stream_phases_registered():
    """ISSUE-11 satellite: the streaming phases are first-class registry
    members — stream_drain is device time (the host blocked on ONE
    chip's in-flight shard), device_select is the on-device
    delta-extraction dispatch, and the bench treats DELTA_PHASES as
    optional coverage exactly like WARM_PHASES."""
    assert pipeline.STREAM_DRAIN in pipeline.PHASES
    assert pipeline.DEVICE_SELECT in pipeline.PHASES
    assert pipeline.DELTA_PHASES == (pipeline.DEVICE_SELECT,)
    assert pipeline.span_name(pipeline.STREAM_DRAIN) == "pipeline.stream_drain"
    assert pipeline.hist_key(pipeline.DEVICE_SELECT) == (
        "pipeline.device_select.ms"
    )
    assert pipeline.STREAM_DRAIN in pipeline.DEVICE_PHASES
    assert pipeline.DEVICE_SELECT in pipeline.DEVICE_PHASES


def test_streamed_build_attributes_drain_to_completing_chip():
    """Each stream_drain span carries exactly ONE device attr (the
    completing chip) — never the whole in-flight set the old device_get
    barrier charged."""
    als, ps = make_world()
    clock = SimClock()
    counters = CounterMap()
    tracer = Tracer("node0", clock=clock, counters=counters)
    backend = make_backend(clock, counters, tracer)
    backend.build_route_db(als, ps)
    drains = [
        s for s in tracer.get_spans() if s.name == "pipeline.stream_drain"
    ]
    assert drains, "streamed build recorded no stream_drain spans"
    plan_devs = {d for d, _lo, _hi in backend.gen.attr_plan}
    assert {s.attrs["device"] for s in drains} == plan_devs
    # one drain window per shard; busy ledger covers every planned chip
    assert len(drains) == len(plan_devs)


def test_device_gauge_keys():
    assert pipeline.device_busy_key(3) == "pipeline.dev3.busy_ms"
    assert pipeline.device_utilization_key(0) == "pipeline.dev0.utilization"


# ---------------------------------------------------------------------------
# the probe
# ---------------------------------------------------------------------------


def test_disabled_probe_is_a_noop():
    probe = disabled_probe()
    assert not probe.enabled
    with probe.phase(pipeline.ENCODE) as scope:
        assert scope is None
    assert probe.gauges() == {}
    # clock-less explicit construction is also disabled
    assert not PipelineProbe(counters=CounterMap()).enabled


def test_probe_records_histograms_spans_and_busy():
    clock = SimClock()
    counters = CounterMap()
    tracer = Tracer("node0", clock=clock, counters=counters)
    probe = PipelineProbe(clock, counters, tracer)
    with probe.phase(pipeline.ENCODE):
        pass
    with probe.phase(pipeline.DEVICE_COMPUTE, device=2):
        clock._now += 0.005  # 5 virtual ms inside the phase
    with probe.phase(pipeline.DEVICE_GET, device=5):
        clock._now += 0.001
    h = counters.histogram(pipeline.hist_key(pipeline.ENCODE))
    assert h is not None and h.count == 1
    h2 = counters.histogram(pipeline.hist_key(pipeline.DEVICE_COMPUTE))
    assert h2 is not None and h2.total == pytest.approx(5.0)
    # spans: named pipeline.{phase}, chip-attributed where applicable
    names = [s.name for s in tracer.get_spans()]
    assert "pipeline.encode" in names and "pipeline.device_compute" in names
    dc = [s for s in tracer.get_spans() if s.name == "pipeline.device_compute"]
    assert dc[0].attrs["device"] == 2
    # busy ledger: each scope charged only the chip it names
    busy = probe.busy_snapshot()
    assert busy[2] == pytest.approx(5.0)
    assert busy[5] == pytest.approx(1.0)
    gauges = probe.gauges()
    assert pipeline.device_busy_key(2) in gauges
    assert 0.0 <= gauges[pipeline.device_utilization_key(2)] <= 1.0


def test_probe_phase_records_error_attr():
    clock = SimClock()
    tracer = Tracer("node0", clock=clock)
    probe = PipelineProbe(clock, CounterMap(), tracer)
    with pytest.raises(RuntimeError):
        with probe.phase(pipeline.DECODE):
            raise RuntimeError("boom")
    sp = tracer.get_spans()[-1]
    assert sp.name == "pipeline.decode" and sp.attrs["error"] == "RuntimeError"


def test_probe_without_tracer_still_observes():
    clock = SimClock()
    counters = CounterMap()
    probe = PipelineProbe(clock, counters)
    with probe.phase(pipeline.TRANSFER):
        pass
    assert counters.histogram(pipeline.hist_key(pipeline.TRANSFER)).count == 1


# ---------------------------------------------------------------------------
# the instrumented backend
# ---------------------------------------------------------------------------


def test_sharded_full_build_attributes_every_phase_and_chip():
    als, ps = make_world()
    clock = SimClock()
    counters = CounterMap()
    tracer = Tracer("node0", clock=clock, counters=counters)
    backend = make_backend(clock, counters, tracer)
    assert backend.probe.enabled
    db = backend.build_route_db(als, ps)
    assert db is not None and db.unicast_routes
    # every lifecycle phase of a sharded full build recorded samples
    for phase in (
        pipeline.HOST_FETCH,
        pipeline.ENCODE,
        pipeline.PAD_PACK,
        pipeline.TRANSFER,
        pipeline.DEVICE_COMPUTE,
        pipeline.DEVICE_GET,
        pipeline.DECODE,
    ):
        h = counters.histogram(pipeline.hist_key(phase))
        assert h is not None and h.count >= 1, phase
    # device_compute samples are chip-attributed spans; the shard plan's
    # chips and the span-attributed chips agree
    plan_devs = {d for d, _lo, _hi in backend.gen.attr_plan}
    span_devs = {
        s.attrs["device"]
        for s in tracer.get_spans()
        if s.name == "pipeline.device_compute" and "device" in s.attrs
    }
    assert span_devs == plan_devs and len(plan_devs) > 1
    # the pool counted one committed dispatch per planned shard
    for d in plan_devs:
        assert backend.pool.num_dispatches[d] == 1
    # per-chip busy gauges exist for every dispatched chip
    gauges = backend.probe.gauges()
    for d in plan_devs:
        assert pipeline.device_busy_key(d) in gauges
    # pool counter_snapshot exports the per-chip dispatch tallies
    snap = backend.counter_snapshot()
    assert snap["decision.backend.pool.dev0.dispatches"] >= 1.0


def test_kernel_spans_carry_the_dispatch_device():
    """`decision.spf_kernel` spans inside a traced build inherit the
    pool chip from the per-shard dispatch loop (jit_guard.dispatch_device)
    — the Chrome-trace chip lanes depend on it."""
    from openr_tpu.ops import jit_guard

    als, ps = make_world()
    clock = SimClock()
    counters = CounterMap()
    tracer = Tracer("node0", clock=clock, counters=counters)
    backend = make_backend(clock, counters, tracer)
    with jit_guard.trace_scope(tracer, None):
        backend.build_route_db(als, ps)
    kernel_devs = {
        s.attrs.get("device")
        for s in tracer.get_spans()
        if s.name == "decision.spf_kernel"
    }
    # the SPF-tables build is unattributed (replicated input), but every
    # selection shard dispatch carries its chip
    assert len(kernel_devs - {None}) > 1


def test_incremental_gather_attributes_the_lead_chip():
    als, ps = make_world()
    clock = SimClock()
    counters = CounterMap()
    backend = make_backend(clock, counters)
    backend.build_route_db(als, ps)
    before = list(backend.pool.num_dispatches)
    ps.update_prefix("node3", "0", PrefixEntry("10.99.3.0/24"))
    db = backend.build_route_db(
        als, ps, changed_prefixes={"10.99.3.0/24"}
    )
    assert db is not None
    after = backend.pool.num_dispatches
    assert sum(after) == sum(before) + 1  # ONE chip rode the gather
    h = counters.histogram(pipeline.hist_key(pipeline.ROUTE_PATCH))
    assert h is not None and h.count >= 1  # the patch path is the tail


# ---------------------------------------------------------------------------
# the engines share the ledger
# ---------------------------------------------------------------------------


def test_fleet_engine_records_phases_on_the_shared_probe():
    from openr_tpu.decision.fleet import FleetRibEngine

    als, ps = make_world()
    clock = SimClock()
    counters = CounterMap()
    backend = make_backend(clock, counters)
    pool = backend.dispatch_pool()
    assert pool is not None
    eng = FleetRibEngine(SpfSolver("node0"), pool=pool, probe=backend.probe)
    summary = eng.fleet_summary(als, ps, change_seq=1)
    assert len(summary) == 12
    for phase in (
        pipeline.ENCODE,
        pipeline.HOST_FETCH,
        pipeline.PAD_PACK,
        pipeline.DEVICE_COMPUTE,
        pipeline.DEVICE_GET,
    ):
        h = counters.histogram(pipeline.hist_key(phase))
        assert h is not None and h.count >= 1, phase
    # root chunks spread over the pool and were tallied there
    assert sum(pool.num_dispatches) == eng.num_pool_dispatches > 0
    db = eng.compute_for_node("node5", als, ps, change_seq=1)
    assert db is not None
    assert counters.histogram(pipeline.hist_key(pipeline.DECODE)).count >= 1


def test_whatif_engine_records_phases_on_the_shared_probe():
    from openr_tpu.decision.whatif_api import MultiAreaWhatIfEngine

    als, ps = make_world()
    clock = SimClock()
    counters = CounterMap()
    backend = make_backend(clock, counters)
    pool = backend.dispatch_pool()
    eng = MultiAreaWhatIfEngine(
        SpfSolver("node0"), pool=pool, probe=backend.probe
    )
    failures = [(f"node{i}", f"node{i + 1}") for i in range(8)]
    result = eng.run(failures, als, ps, change_seq=1)
    assert result["eligible"] and len(result["failures"]) == 8
    for phase in (
        pipeline.PAD_PACK,
        pipeline.TRANSFER,
        pipeline.DEVICE_COMPUTE,
        pipeline.DEVICE_GET,
        pipeline.DECODE,
    ):
        h = counters.histogram(pipeline.hist_key(phase))
        assert h is not None and h.count >= 1, phase
    assert sum(pool.num_dispatches) == eng.num_pool_dispatches > 0


def test_decision_hands_engines_the_backend_probe():
    from openr_tpu.common.runtime import SimClock as SC
    from openr_tpu.config import DecisionConfig
    from openr_tpu.decision.decision import Decision
    from openr_tpu.messaging.queue import ReplicateQueue

    als, ps = make_world()
    clock = SC()
    solver = SpfSolver("node0")
    backend = make_backend(clock, CounterMap())
    d = Decision(
        "node0",
        clock,
        DecisionConfig(),
        ReplicateQueue("routes"),
        backend=backend,
        solver=solver,
    )
    d.area_link_states = als
    d.prefix_state = ps
    d._change_seq = 1
    assert d._backend_probe() is backend.probe
    assert d._fleet().probe is backend.probe


# ---------------------------------------------------------------------------
# the event path's phases: governor, backend patch, Decision ingest, Fib
# ---------------------------------------------------------------------------


def _run(coro):
    import asyncio

    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        loop.close()


def _shadow_checked_builds(counters):
    """Two device builds under a governor that shadow-checks every one."""
    from openr_tpu.decision.backend import TpuBackend

    als, ps = make_world()
    backend = TpuBackend(
        SpfSolver("node0"),
        clock=SimClock(),
        counters=counters,
        resilience=ResilienceConfig(shadow_sample_every=1),
        parallel=ParallelConfig(min_shard_rows=0),
    )
    backend.build_route_db(als, ps)
    ps.update_prefix("node3", "0", PrefixEntry("10.99.3.0/24"))
    backend.build_route_db(als, ps, changed_prefixes={"10.99.3.0/24"})
    gov = backend.governor
    assert gov.num_shadow_checks == 2 and not gov.num_shadow_mismatches
    for phase in (pipeline.SHADOW_REBUILD, pipeline.SHADOW_COMPARE):
        assert counters.histogram(pipeline.hist_key(phase)).count >= 2


def _incremental_build(counters):
    """A patched build: the patch is route_patch, and nothing inside
    build_route_db is Decision's diff any more."""
    als, ps = make_world()
    backend = make_backend(SimClock(), counters)
    backend.build_route_db(als, ps)
    ps.update_prefix("node3", "0", PrefixEntry("10.99.3.0/24"))
    backend.build_route_db(als, ps, changed_prefixes={"10.99.3.0/24"})
    assert backend.num_incremental_builds == 1
    assert counters.histogram(pipeline.hist_key(pipeline.DELTA_EXTRACT)) is None


def _decision_and_fib(counters):
    """Decision ingests three publications (one phase each), builds on
    the device, diffs, and Fib programs the update with Decision's
    probe."""
    import json

    from openr_tpu.config import DecisionConfig, FibConfig
    from openr_tpu.decision.decision import Decision
    from openr_tpu.fib.fib import Fib, MockFibAgent
    from openr_tpu.messaging.queue import ReplicateQueue
    from openr_tpu.types import (
        InitializationEvent,
        PrefixDatabase,
        Publication,
        Value,
        adj_key,
        prefix_key,
    )

    def value(node, payload):
        return Value(version=1, originator_id=node, ttl=300000,
                     value=json.dumps(payload).encode())

    async def main():
        clock = SimClock()
        routes_q = ReplicateQueue("routeUpdates")
        kv_q = ReplicateQueue("kvpubs")
        decision = Decision(
            "node0",
            clock,
            DecisionConfig(unblock_initial_routes_ms=120000),
            routes_q,
            kv_store_updates_reader=kv_q.get_reader(),
            backend=make_backend(clock, counters),
        )
        agent = MockFibAgent(clock)
        fib = Fib("node0", clock, FibConfig(), agent, routes_q.get_reader(),
                  probe=decision._backend_probe())
        decision.start()
        fib.start()
        decision.on_initialization_event(InitializationEvent.KVSTORE_SYNCED)
        adj = build_adj_dbs(ring_edges(6))
        kv_q.push(Publication(key_vals={
            adj_key(n): value(n, db.to_wire()) for n, db in adj.items()}))
        for i in (3, 4):
            pfx = f"10.7.{i}.0/24"
            db = PrefixDatabase(this_node_name=f"node{i}",
                                prefix_entries=[PrefixEntry(pfx)], area="0")
            kv_q.push(Publication(key_vals={
                prefix_key(f"node{i}", pfx): value(f"node{i}", db.to_wire())}))
        await clock.run_for(2.0)
        assert "10.7.3.0/24" in agent.unicast
        await fib.stop()
        await decision.stop()

    _run(main())
    ingest = counters.histogram(pipeline.hist_key(pipeline.DECISION_INGEST))
    assert ingest.count == 3  # one phase per publication
    assert counters.histogram(pipeline.hist_key(pipeline.DELTA_EXTRACT))


def _decision_with_policy(counters):
    """Decision with a RibPolicy set builds twice: once because the
    policy was set, once for a prefix-only change that only the policy
    keeps cold; each applies the policy in its phase and counts what it
    did."""
    import json

    from openr_tpu.config import DecisionConfig
    from openr_tpu.decision.decision import Decision
    from openr_tpu.decision.rib_policy import (
        RibPolicy,
        RibPolicyStatement,
        RibRouteActionWeight,
    )
    from openr_tpu.messaging.queue import ReplicateQueue
    from openr_tpu.types import (
        InitializationEvent,
        PrefixDatabase,
        Publication,
        Value,
        adj_key,
        prefix_key,
    )

    def value(node, payload):
        return Value(version=1, originator_id=node, ttl=300000,
                     value=json.dumps(payload).encode())

    def advertise(kv_q, i, tags):
        pfx = f"10.7.{i}.0/24"
        db = PrefixDatabase(this_node_name=f"node{i}", area="0",
                            prefix_entries=[PrefixEntry(pfx, tags=tags)])
        kv_q.push(Publication(key_vals={
            prefix_key(f"node{i}", pfx): value(f"node{i}", db.to_wire())}))

    async def main():
        clock = SimClock()
        kv_q = ReplicateQueue("kvpubs")
        decision = Decision(
            "node0", clock, DecisionConfig(unblock_initial_routes_ms=120000),
            ReplicateQueue("routeUpdates"),
            kv_store_updates_reader=kv_q.get_reader(),
            backend=make_backend(clock, counters), counters=counters)
        decision.start()
        decision.on_initialization_event(InitializationEvent.KVSTORE_SYNCED)
        adj = build_adj_dbs(ring_edges(6))
        kv_q.push(Publication(key_vals={
            adj_key(n): value(n, db.to_wire()) for n, db in adj.items()}))
        advertise(kv_q, 3, {"RACK"})
        await clock.run_for(1.0)
        # node0 reaches node3 over node1 and node5; node1, weighed 0,
        # is the only next hop toward node1's own prefix, which goes
        decision.set_rib_policy(RibPolicy(
            statements=[RibPolicyStatement(
                tags={"RACK"}, action=RibRouteActionWeight(
                    default_weight=3, neighbor_to_weight={"node1": 0}))],
            valid_until=clock.now() + 3600))
        await clock.run_for(1.0)
        advertise(kv_q, 1, {"RACK"})
        await clock.run_for(1.0)
        route = decision.get_route_db().unicast_routes["10.7.3.0/24"]
        assert {nh.weight for nh in route.nexthops} == {3}
        assert "10.7.1.0/24" not in decision.get_route_db().unicast_routes
        await decision.stop()

    _run(main())
    assert counters.get("decision.policy_forced_full_builds") == 1
    assert counters.get("decision.rib_policy.routes_matched") == 3
    assert counters.get("decision.rib_policy.routes_modified") == 3
    assert counters.get("decision.rib_policy.routes_dropped") == 1
    assert counters.get("decision.rib_policy.nexthops_dropped") == 3


def _prefix_manager_redistributes(counters):
    """An ABR's PrefixManager re-advertises a FIB route of area a into
    area b, with a's IGP cost added and a on the stack, in its phase."""
    from openr_tpu.decision.rib import DecisionRouteUpdate, RibUnicastEntry
    from openr_tpu.messaging.queue import ReplicateQueue
    from openr_tpu.prefix_manager.prefix_manager import (
        PrefixManager,
        deserialize_prefix_db,
    )
    from openr_tpu.types import NextHop, PrefixMetrics

    clock = SimClock()
    kv_q = ReplicateQueue("kvRequests")
    requests = kv_q.get_reader()
    fib_q = ReplicateQueue("fibRouteUpdates")
    pm = PrefixManager("node0", clock, kv_q,
                       fib_route_updates_reader=fib_q.get_reader(),
                       areas=["a", "b"], counters=counters,
                       probe=PipelineProbe(clock, counters))
    entry = RibUnicastEntry(
        prefix="10.7.3.0/24", nexthops={NextHop(address="fe80::1",
                                                 area="a")},
        best_prefix_entry=PrefixEntry(
            "10.7.3.0/24", metrics=PrefixMetrics(distance=1)),
        best_area="a", igp_cost=2)

    async def main():
        pm.start()
        fib_q.push(DecisionRouteUpdate(
            unicast_routes_to_update={entry.prefix: entry}))
        await clock.run_for(1.0)
        await pm.stop()

    _run(main())
    # the initial sync repeats the persist; KvStore takes it as a no-op
    sent = set()
    while (r := requests.try_get()) is not None:
        sent.add((r.area, r.key, r.value))
    ((area, _key, value),) = sent
    assert area == "b"
    (got,) = deserialize_prefix_db(value).prefix_entries
    assert got.metrics.distance == 3 and got.area_stack == ["a"]
    assert counters.get("prefix_manager.redistributed.b") == 1
    assert counters.get("prefix_manager.redistributed.a") == 0


EVENT_PATHS = {
    pipeline.SHADOW_REBUILD: _shadow_checked_builds,
    pipeline.SHADOW_COMPARE: _shadow_checked_builds,
    pipeline.ROUTE_PATCH: _incremental_build,
    pipeline.DECISION_INGEST: _decision_and_fib,
    pipeline.FIB_PROGRAM: _decision_and_fib,
    pipeline.RIB_POLICY: _decision_with_policy,
    pipeline.PREFIX_REDISTRIBUTE: _prefix_manager_redistributes,
}


@pytest.fixture
def nested_phases(monkeypatch):
    """``[(outer, inner)]`` of every phase scope opened inside another
    (recorded, not raised: an actor's queue loop would swallow it)."""
    from openr_tpu.tracing.pipeline import _PhaseScope

    open_scopes, nested = [], []
    enter, exit_ = _PhaseScope.__enter__, _PhaseScope.__exit__

    def recording_enter(self):
        if open_scopes:
            nested.append((open_scopes[-1], self._phase))
        open_scopes.append(self._phase)
        return enter(self)

    def recording_exit(self, *exc):
        open_scopes.pop()
        return exit_(self, *exc)

    monkeypatch.setattr(_PhaseScope, "__enter__", recording_enter)
    monkeypatch.setattr(_PhaseScope, "__exit__", recording_exit)
    return nested


@pytest.mark.parametrize("phase", pipeline.EVENT_PHASES)
def test_event_path_records_its_phase_and_phases_tile(phase, nested_phases):
    assert phase in pipeline.HOST_PHASES
    counters = CounterMap()
    EVENT_PATHS[phase](counters)
    h = counters.histogram(pipeline.hist_key(phase))
    assert h is not None and h.count >= 1
    assert nested_phases == []


# ---------------------------------------------------------------------------
# phases on the profiler's clock
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def profiled_phase_names(tmp_path_factory):
    """Host-plane event names of one profiler session in which a probe
    with no tracer opened one scope of every registry phase."""
    import glob
    import os

    import jax

    from openr_tpu.common.runtime import WallClock

    probe = PipelineProbe(WallClock(), CounterMap())
    log_dir = str(tmp_path_factory.mktemp("profile"))
    with jax.profiler.trace(log_dir):
        for phase in pipeline.PHASES:
            with probe.phase(phase):
                pass
    (path,) = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    return {
        ev.name
        for plane in data.planes if plane.name.startswith("/host:")
        for line in plane.lines for ev in line.events
    }


@pytest.mark.parametrize("phase", pipeline.PHASES)
def test_phase_is_a_span_on_the_profilers_host_plane(phase,
                                                     profiled_phase_names):
    assert pipeline.span_name(phase) in profiled_phase_names


def test_no_annotation_outside_a_profiler_session():
    import jax  # noqa: F401 - loaded, yet no session is active

    assert pipeline._active_annotation() is None


def test_pipeline_module_never_imports_jax():
    """The protocol plane runs without jax: the registry only looks for
    a jax that something else already loaded."""
    import ast
    import inspect

    tree = ast.parse(inspect.getsource(pipeline))
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported.update(a.name.split(".")[0] for a in node.names)
        elif isinstance(node, ast.ImportFrom) and node.module:
            imported.add(node.module.split(".")[0])
    assert "jax" not in imported and "jaxlib" not in imported


# ---------------------------------------------------------------------------
# per-device Chrome-trace lanes (satellite)
# ---------------------------------------------------------------------------


def test_chrome_trace_emits_per_device_lanes():
    from openr_tpu.tracing import chrome_trace_events

    clock = SimClock()
    tracer = Tracer("node0", clock=clock)
    s0 = tracer.start_span("decision.spf_kernel", module="decision", device=0)
    tracer.end_span(s0)
    s1 = tracer.start_span("decision.spf_kernel", module="decision", device=3)
    tracer.end_span(s1)
    s2 = tracer.start_span("resilience.probe", module="resilience", device=3)
    tracer.end_span(s2)
    s3 = tracer.start_span("decision.rebuild", module="decision")
    tracer.end_span(s3)
    events = chrome_trace_events(tracer.get_spans())
    threads = {
        e["args"]["name"]: (e["pid"], e["tid"])
        for e in events
        if e.get("name") == "thread_name" and e.get("ph") == "M"
    }
    # chip-attributed spans get one lane per (module, chip); the plain
    # rebuild span stays on the module lane
    assert "decision.dev0" in threads and "decision.dev3" in threads
    assert "resilience.dev3" in threads and "decision" in threads
    assert threads["decision.dev0"][1] != threads["decision.dev3"][1]
    lane_of = {}
    for e in events:
        if e.get("ph") == "X":
            lane_of.setdefault((e["pid"], e["tid"]), []).append(e["name"])
    # the two kernel spans on different chips landed on different lanes
    kernel_lanes = [
        lane for lane, names in lane_of.items()
        if "decision.spf_kernel" in names
    ]
    assert len(kernel_lanes) == 2


# ---------------------------------------------------------------------------
# KSP2_ED_ECMP and SR-MPLS label routes: their own phases, outside decode
# ---------------------------------------------------------------------------


def _fabric_world(ksp2: bool):
    """A 2-pod fabric seen from a fabric switch (every rack has a second
    path family), node labels on, one prefix per rack switch."""
    from openr_tpu.emulation.topology import fabric_edges
    from openr_tpu.types import PrefixForwardingAlgorithm, PrefixForwardingType

    edges = fabric_edges(num_pods=2, rsws_per_pod=4, fsws_per_pod=2,
                         num_ssws=4)
    nodes = sorted({n for e in edges for n in e[:2]})
    labels = {n: 101 + i for i, n in enumerate(nodes)}
    ls = LinkState("0", "fsw0_0")
    for db in build_adj_dbs(edges, node_labels=labels).values():
        ls.update_adjacency_database(db)
    ps = PrefixState()
    racks = [n for n in nodes if n.startswith("rsw")]
    for i, n in enumerate(racks):
        entry = (
            PrefixEntry(
                f"10.8.{i}.0/24",
                forwarding_type=PrefixForwardingType.SR_MPLS,
                forwarding_algorithm=PrefixForwardingAlgorithm.KSP2_ED_ECMP,
            )
            if ksp2
            else PrefixEntry(f"10.8.{i}.0/24")
        )
        ps.update_prefix(n, "0", entry)
    return {"0": ls}, ps, racks


def _ksp2_backend(counters, sr_mpls=True):
    from openr_tpu.decision.backend import TpuBackend

    return TpuBackend(
        SpfSolver("fsw0_0", enable_node_segment_label=sr_mpls),
        clock=SimClock(),
        counters=counters,
        resilience=ResilienceConfig(enabled=False),
        parallel=ParallelConfig(max_devices=1),
    )


@pytest.fixture
def phase_of_call(monkeypatch):
    """{what: [innermost open phase at each call]} for the KSP2 engine's
    re-solve and trace, the KSP2 route assembly and the label routes."""
    from openr_tpu.decision.ksp2 import Ksp2DeviceEngine
    from openr_tpu.tracing.pipeline import _PhaseScope

    open_scopes, seen = [], {}
    enter, exit_ = _PhaseScope.__enter__, _PhaseScope.__exit__

    def recording_enter(self):
        open_scopes.append(self._phase)
        return enter(self)

    def recording_exit(self, *exc):
        open_scopes.pop()
        return exit_(self, *exc)

    monkeypatch.setattr(_PhaseScope, "__enter__", recording_enter)
    monkeypatch.setattr(_PhaseScope, "__exit__", recording_exit)

    def watch(cls, name, what):
        orig = getattr(cls, name)

        def wrapped(*a, **k):
            seen.setdefault(what, []).append(
                open_scopes[-1] if open_scopes else None)
            return orig(*a, **k)

        monkeypatch.setattr(cls, name, wrapped)

    watch(Ksp2DeviceEngine, "_device_resolve", "resolve")
    watch(Ksp2DeviceEngine, "_trace_all", "trace")
    watch(SpfSolver, "create_route_for_prefix", "route")
    watch(SpfSolver, "_build_node_label_routes", "labels")
    return seen


def test_ksp2_sr_build_records_its_phases_outside_decode(nested_phases,
                                                         phase_of_call):
    counters = CounterMap()
    als, ps, racks = _fabric_world(ksp2=True)
    backend = _ksp2_backend(counters)
    db = backend.build_route_db(als, ps)
    assert backend.num_device_builds == 1 and db.mpls_routes
    for phase in pipeline.KSP2_PHASES:
        h = counters.histogram(pipeline.hist_key(phase))
        assert h is not None and h.count >= 1, phase
    assert nested_phases == []
    # the work runs in its own phase, never inside decode
    assert set(phase_of_call["resolve"]) == {pipeline.KSP2_RESOLVE}
    assert set(phase_of_call["trace"]) == {pipeline.KSP2_TRACE}
    assert set(phase_of_call["route"]) == {pipeline.KSP2_ROUTES}
    assert set(phase_of_call["labels"]) == {pipeline.LABEL_ROUTES}
    assert pipeline.KSP2_RESOLVE in pipeline.DEVICE_PHASES
    assert {pipeline.KSP2_TRACE, pipeline.KSP2_ROUTES,
            pipeline.LABEL_ROUTES} <= set(pipeline.HOST_PHASES)


def test_ksp2_counters_count_unmemoised_destinations_and_their_bucket():
    from openr_tpu.decision.ksp2 import Ksp2DeviceEngine
    from openr_tpu.ops.csr import bucket_for

    counters = CounterMap()
    als, ps, racks = _fabric_world(ksp2=True)
    backend = _ksp2_backend(counters)
    backend.build_route_db(als, ps)
    snap = backend.counter_snapshot()
    rows = len(racks)
    assert snap["decision.backend.ksp2.device_batches"] == 1
    assert snap["decision.backend.ksp2.resolve_rows"] == rows
    assert snap["decision.backend.ksp2.seeded_dests"] == rows
    assert snap["decision.backend.ksp2.resolve_padded_rows"] == bucket_for(
        rows, Ksp2DeviceEngine.BATCH_BUCKETS)
    # memoised: a rebuild on the same topology re-solves nothing
    backend.build_route_db(als, ps, force_full=True)
    assert backend.counter_snapshot()[
        "decision.backend.ksp2.resolve_rows"] == rows
    from openr_tpu.monitor.metrics import metric_description

    for k in snap:
        if k.startswith("decision.backend.ksp2."):
            assert metric_description(k), k


def test_sp_ecmp_build_records_no_ksp2_phase():
    counters = CounterMap()
    als, ps, _racks = _fabric_world(ksp2=False)
    backend = _ksp2_backend(counters, sr_mpls=False)
    backend.build_route_db(als, ps)
    assert backend.num_device_builds == 1
    for phase in pipeline.KSP2_PHASES:
        h = counters.histogram(pipeline.hist_key(phase))
        assert h is None or h.count == 0, phase
    assert backend.counter_snapshot()[
        "decision.backend.ksp2.device_batches"] == 0
